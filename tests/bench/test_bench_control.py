"""The comparison that decides ``correct`` fails what it must fail.

First the control: the plain reference put in the program's place one
step below the configuration's precision or guarantee (bfloat16 in the
model, documents cut short in hashing) reads above each cell's limits.
Then the run with the timed path broken underneath: a step that returns
its state unchanged, half of each batch left out, the gradient exchange
between chips left out, a hashed byte or a served score altered where it
is produced; each makes ``correct`` come out false.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from bench_small import SMALL, run_small, small_context
from bench import controls

SEED = 2 ** 31 + 101


@pytest.mark.parametrize("name", sorted(SMALL))
def test_control_reads_above_the_limits(name, tmp_path):
    ctx = small_context(name, SEED, str(tmp_path))
    got = controls.readings(ctx)
    limits = dict(ctx.traffic.get("limits", {}), rows_differing=0)
    over = {k: v for k, v in got.items() if k in limits and v > limits[k]}
    assert over, (got, limits)


def _broken_step(monkeypatch, wrap):
    import repro.train.streaming as st
    orig = st.build_averaged_train_step

    def build(loss_fn, opt, **kw):
        return wrap(orig(loss_fn, opt, donate=False, **kw))
    monkeypatch.setattr(st, "build_averaged_train_step", build)
    monkeypatch.setattr(st, "_STEP_CACHE", {})


def test_state_left_unchanged_is_caught(monkeypatch, tmp_path):
    def wrap(step):
        def run(astate, active, *batch):
            return astate, step(astate, active, *batch)[1]
        return run
    _broken_step(monkeypatch, wrap)
    _, checks, correct = run_small("oph-train", SEED, str(tmp_path))
    assert not correct
    assert {c.name for c in checks if not c.ok} >= {"param_change_gap"}


def test_half_the_batch_is_caught(monkeypatch, tmp_path):
    def wrap(step):
        def run(astate, active, batch, labels):
            h = batch.shape[0] // 2
            return step(astate, active, batch[:h], labels[:h])
        return run
    _broken_step(monkeypatch, wrap)
    _, checks, correct = run_small("oph-train", SEED, str(tmp_path))
    assert not correct, checks


def test_exchange_between_chips_left_out_is_caught(tmp_path):
    code = f"""
    import sys
    sys.path[:0] = [{os.path.dirname(os.path.abspath(__file__))!r}]
    import bench_small
    import jax
    assert len(jax.devices()) == 4
    import repro.train.data_parallel as dp
    dp.psum_mean = lambda tree, axis: tree     # each chip keeps its own
    _, checks, correct = bench_small.run_small(
        "oph-train-dp4", {SEED}, {str(tmp_path)!r})
    print("CORRECT", correct, [c for c in checks if not c.ok])
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "CORRECT False" in proc.stdout, proc.stdout[-2000:]


def test_altered_hash_byte_is_caught(monkeypatch, tmp_path):
    from repro.data import hashed_dataset as hd
    orig = hd.HashedShardWriter.append

    def append(self, row_ids, packed, labels, empty=None):
        packed = np.array(packed)
        packed[:, 0] ^= 1
        return orig(self, row_ids, packed, labels, empty)
    monkeypatch.setattr(hd.HashedShardWriter, "append", append)
    _, checks, correct = run_small("minwise-hash", SEED, str(tmp_path))
    assert not correct
    assert {c.name for c in checks if not c.ok} == {"rows_differing"}


def test_altered_score_is_caught(monkeypatch, tmp_path):
    import repro.serving.engine as eng
    orig = eng.bbit_scores_packed

    def scores(params, packed, cfg, empty_packed=None):
        return orig(params, packed, cfg, empty_packed=empty_packed) + 1e-2
    monkeypatch.setattr(eng, "bbit_scores_packed", scores)
    _, checks, correct = run_small("oph-serve", SEED, str(tmp_path))
    assert not correct
    assert {c.name for c in checks if not c.ok} == {"score_max_abs_gap"}


@pytest.mark.parametrize("name", ["oph-train", "oph-train-dp4"])
def test_training_faults_read_above_the_limits(name, tmp_path):
    """Each fault planted in the reference in the program's place reads
    above one of the cell's limits."""
    ctx = small_context(name, SEED, str(tmp_path))
    limits = ctx.traffic["limits"]
    got = controls.train_faults(ctx)
    assert set(got) == ({"half_batch", "no_exchange"}
                        if name == "oph-train-dp4" else {"half_batch"})
    for fault, reading in got.items():
        assert any(reading[k] > v for k, v in limits.items()), \
            (fault, reading, limits)
