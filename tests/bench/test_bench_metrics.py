"""The per-layer metric readers on a trace summary and counters worked by
hand: each reads its number, and returns nothing where there is nothing
to read (never 0 for a share of a roofline or of a peak)."""
import glob
import os

import pytest

import bench_small  # noqa: F401  (puts the repository on sys.path)
from bench import counts, harness
from bench.trace import Summary

KIND = "TPU v5 lite"


def summary(op_s, collective=None):
    return Summary(window_s=10.0, busy_s=8.0, op_s=op_s,
                   collective_exposed_s=collective, idle_gaps=[], devices=1)


def read(name, trace, counters, chips=1):
    return harness.metric_reader(name)(
        {"trace": trace, "counters": counters, "config": {}, "traffic": {},
         "device_kind": KIND, "chips": chips})


TRAIN = {"rows": 4096, "steps": 4, "k": 256, "b": 8, "classes_out": 1,
         "window_s": 10.0}
LOGITS = {"jvp_jit_bbit_linear_packed_fwd_pallas__.2": 0.5,
          "transpose_jvp_jit_bbit_linear_packed_bwd_dw_pallas___.2": 1.5,
          "fusion.1": 7.0}


def test_every_reader_is_covered():
    names = {os.path.basename(p)[:-3] for p in glob.glob(
        os.path.join(harness.BENCH_DIR, "metrics", "*.py"))}
    assert names == {"hash.device_idle", "hash.encode_roofline",
                     "train.device_idle", "train.logits_roofline",
                     "train.step_mfu", "train.allreduce_exposed",
                     "serve.device_idle", "serve.batch_rows"}


@pytest.mark.parametrize("name", ["hash.device_idle", "train.device_idle",
                                  "serve.device_idle"])
def test_device_idle(name):
    assert read(name, summary({}), {}) == pytest.approx(20.0)


def test_logits_roofline_by_hand():
    ops, nbytes = counts.logits_train(4096, 4, 256, 8, 1)
    floor = max(ops / 197e12, nbytes / 819e9)
    got = read("train.logits_roofline", summary(LOGITS), TRAIN)
    assert got == pytest.approx(100.0 * floor / 2.0)
    # on four chips each reads a quarter of the rows in the same steps
    ops4, nbytes4 = counts.logits_train(1024, 4, 256, 8, 1)
    floor4 = max(ops4 / 197e12, nbytes4 / 819e9)
    assert read("train.logits_roofline", summary(LOGITS), TRAIN,
                chips=4) == pytest.approx(100.0 * floor4 / 2.0)
    assert read("train.logits_roofline", summary({"fusion.1": 1.0}),
                TRAIN) is None


def test_step_mfu_by_hand():
    flops = 4096 * 4 * 256 * 1
    assert read("train.step_mfu", summary({}), TRAIN) == pytest.approx(
        100.0 * flops / (10.0 * 197e12))
    assert read("train.step_mfu", summary({}), dict(TRAIN, rows=0)) is None


def test_encode_roofline_by_hand():
    c = {"nnz": 10_000_000, "docs": 1000, "k": 500, "b": 8}
    nbytes = 4 * 10_000_000 + 1000 * 500
    got = read("hash.encode_roofline",
               summary({"minhash_pack_pallas.1": 0.25}), c)
    assert got == pytest.approx(100.0 * nbytes / 819e9 / 0.25)
    assert read("hash.encode_roofline", summary({"copy.1": 1.0}), c) is None


def test_allreduce_exposed_by_hand():
    assert read("train.allreduce_exposed", summary({}, collective=0.5),
                TRAIN, chips=4) == pytest.approx(5.0)
    assert read("train.allreduce_exposed", summary({}), TRAIN) is None


def test_batch_rows_by_hand():
    assert read("serve.batch_rows", None,
                {"served": 300, "batches": 200}) == pytest.approx(1.5)
    assert read("serve.batch_rows", None,
                {"served": 0, "batches": 0}) is None
