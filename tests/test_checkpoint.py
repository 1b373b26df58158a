"""Fault tolerance: atomic checkpoints, bitwise restart, elastic reshard,
failure injection, straggler watchdog."""
import os
import tracemalloc
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.ckpt import checkpoint as ckpt
from repro.ft.watchdog import StepWatchdog, FailureInjector
from repro.models.linear import BBitLinearConfig, init_bbit_linear, bbit_logits
from repro.optim.optimizers import make_optimizer
from repro.train.losses import mean_loss_fn
from repro.train.steps import init_state, build_train_step
from repro.data.loader import HashedCodesLoader


def _training_setup(seed=0):
    lcfg = BBitLinearConfig(k=16, b=4)
    opt = make_optimizer("adamw", 1e-2)
    loss_fn = mean_loss_fn(lambda p, c: bbit_logits(p, c, lcfg),
                           "logistic", l2=1e-6)
    step_fn = build_train_step(loss_fn, opt, donate=False)
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, 16, size=(256, 16)).astype(np.uint16)
    labels = (codes.sum(axis=1) % 2).astype(np.int32)
    loader = HashedCodesLoader(codes, labels, batch_size=32, seed=seed)
    state = init_state(init_bbit_linear(lcfg, jax.random.key(seed)), opt)
    return step_fn, loader, state


def _run(step_fn, loader, state, start, stop, ckpt_dir=None, every=5,
         fail_at=None):
    injector = FailureInjector(fail_at)
    for step, bc, by in loader.batches(start_step=start):
        if step >= stop:
            break
        injector.maybe_fail(step)
        state, _ = step_fn(state, jnp.asarray(bc.astype(np.int32)),
                           jnp.asarray(by))
        if ckpt_dir and (step + 1) % every == 0:
            ckpt.save(ckpt_dir, step + 1, state)
    return state


def test_save_restore_roundtrip(tmp_path):
    step_fn, loader, state = _training_setup()
    state = _run(step_fn, loader, state, 0, 7)
    d = str(tmp_path / "ck")
    ckpt.save(d, 7, state)
    template = jax.tree.map(jnp.zeros_like, state)
    restored, step = ckpt.restore(d, template)
    assert step == 7
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def _copy_crc(x):
    """The CRC checkpoint format 4 records: over a C-order byte copy."""
    return zlib.crc32(np.ascontiguousarray(x).tobytes())


_F32 = np.random.default_rng(7).standard_normal((500, 64, 1)) \
    .astype(np.float32)
LEAVES = {
    "int64_scalar": np.int64(-1234567890123),
    "int64_0d": np.array(987654321, np.int64),
    "f32": _F32,
    "f32_transposed": _F32[:, :, 0].T,
    "bfloat16": _F32.astype(jnp.bfloat16),
    "bool": _F32 > 0,
    "empty": np.zeros((0, 3), np.float32),
}


@pytest.mark.parametrize("name", sorted(LEAVES))
def test_leaf_crc32_equals_the_copy_crc(name):
    x = LEAVES[name]
    assert ckpt.leaf_crc32(x) == _copy_crc(x)
    if name == "f32_transposed":
        assert not x.flags.c_contiguous


def test_saved_crcs_equal_the_copy_crc(tmp_path):
    """A save records the format-4 CRCs, and restore checks them; a
    non-contiguous leaf comes back from the npz in its own order.
    (bfloat16 is left out: the npz stores it as raw void.)"""
    tree = {k: v for k, v in LEAVES.items() if k != "bfloat16"}
    d = str(tmp_path / "ck")
    ckpt.save(d, 3, tree)
    leaves = jax.tree.leaves(tree)
    assert ckpt.load_meta(d, 3)["crc32"] == {
        f"leaf_{i:05d}": _copy_crc(x) for i, x in enumerate(leaves)}
    restored, step = ckpt.restore(d, tree)
    assert step == 3
    for a, b in zip(leaves, jax.tree.leaves(restored)):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        assert np.array_equal(a, b)


def test_leaf_crc32_makes_no_copy():
    x = np.ones(8 << 20, np.float32)                # 32 MiB, contiguous
    tracemalloc.start()
    try:
        crc = ckpt.leaf_crc32(x)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < 1 << 20
    assert crc == _copy_crc(x)


def test_restart_is_bitwise_identical(tmp_path):
    """kill at step 12 → resume from ckpt → same params as straight run."""
    d = str(tmp_path / "ck")
    # straight run to 20
    step_fn, loader, state0 = _training_setup()
    straight = _run(step_fn, loader, state0, 0, 20)
    # interrupted run: crash at 12, checkpoints every 5
    step_fn2, loader2, state1 = _training_setup()
    with pytest.raises(RuntimeError):
        _run(step_fn2, loader2, state1, 0, 20, ckpt_dir=d, every=5,
             fail_at=12)
    # restart: restore latest (step 10) and replay
    step_fn3, loader3, state2 = _training_setup()
    restored, start = ckpt.restore(d, state2)
    assert start == 10
    resumed = _run(step_fn3, loader3, restored, start, 20)
    for a, b in zip(jax.tree.leaves(straight.params),
                    jax.tree.leaves(resumed.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_keep_last_pruning(tmp_path):
    step_fn, loader, state = _training_setup()
    d = str(tmp_path / "ck")
    for s in (5, 10, 15, 20):
        ckpt.save(d, s, state, keep_last=2)
    assert ckpt.latest_step(d) == 20
    steps = sorted(int(p.split("_")[1]) for p in os.listdir(d)
                   if p.startswith("step_"))
    assert steps == [15, 20]


def test_atomicity_no_partial_dirs(tmp_path):
    step_fn, loader, state = _training_setup()
    d = str(tmp_path / "ck")
    ckpt.save(d, 3, state)
    assert not any(p.startswith(".tmp") for p in os.listdir(d))


def test_elastic_reshard_roundtrip(tmp_path):
    """Checkpoint written on one topology restores onto another."""
    from repro.ckpt.elastic import mesh_from_available_devices, reshard
    step_fn, loader, state = _training_setup()
    state = _run(step_fn, loader, state, 0, 3)
    d = str(tmp_path / "ck")
    ckpt.save(d, 3, state)
    mesh = mesh_from_available_devices(model_parallel=1, max_devices=1)
    restored, _ = ckpt.restore(d, state)
    from jax.sharding import NamedSharding, PartitionSpec as P
    placed = reshard(restored, NamedSharding(mesh, P()))
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(placed)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_watchdog_flags_and_escalates():
    wd = StepWatchdog(threshold=2.0, window=16, escalate_after=2)
    for s in range(10):
        wd.end_step(s, duration=0.1)
    assert not wd.flagged_steps
    wd.end_step(10, duration=0.5)        # 5× median
    wd.end_step(11, duration=0.5)
    assert wd.flagged_steps == [10, 11]
    assert wd.escalations == [11]        # escalated after 2 consecutive


def test_failure_injector_fires_once():
    inj = FailureInjector(fail_at=3)
    inj.maybe_fail(2)
    with pytest.raises(RuntimeError):
        inj.maybe_fail(3)
    inj.maybe_fail(3)  # second pass: no re-fire (restart semantics)
