"""The ``bbit16-train`` cell (the paper's k=500, b=16 model) at a small
size on the CPU: set-up, window and check as a chip run drives them, and
the stream_train limits still fail the bfloat16 control at b=16."""
import os

from bench_small import ROOT
from bench import controls, harness

# the configuration at a size a CPU test run holds; its widths (b=16,
# one class) are kept.  Kernel arms are not required: off a TPU the
# program dispatches its XLA arms.
SMALL = {"k": 16, "train_rows": 3000, "stream_batch": 128,
         "preprocess_shards": 4, "ckpt_every_shards": 4}
SEED = 2 ** 31 + 1601


def _context(workdir: str) -> harness.Context:
    bench = harness.benchmark()
    cell = harness.find_cell("bbit16-train", bench)
    config, traffic = harness.cell_files(cell, bench)
    assert config["b"] == 16 and config["ckpt_every_shards"] == \
        config["preprocess_shards"]
    config.update(SMALL)
    traffic["kernel_arms"] = {}
    os.makedirs(workdir, exist_ok=True)
    return harness.Context(cell, config, traffic, SEED, 1, workdir, 1.0)


def test_config_is_the_papers_model():
    conf = harness.load_json(os.path.join(
        ROOT, "bench", "configs", "rcv1-bbit16.json"))
    assert (conf["scheme"], conf["hash_family"]) == ("minwise",
                                                     "multiply_shift")
    assert (conf["k"], conf["b"], conf["n_classes"]) == (500, 16, 2)
    assert conf["reduced"] == []


def test_cell_runs_and_is_correct(tmp_path):
    ctx = _context(str(tmp_path))
    drv = harness.runner(ctx.traffic)
    state = drv.setup(ctx)
    out = drv.window(ctx, state, ctx.seconds)
    drv.release(ctx, state)
    checks = drv.check(ctx, state, out)
    assert all(c.ok for c in checks), [c for c in checks if not c.ok]
    assert out.attempted >= 1 and out.failed == 0
    assert out.counters["b"] == 16
    assert out.metrics["train_rows_per_s"] > 0
    # one save a pass, at its end: the whole state (table, AdamW
    # moments, Polyak average) and the published table
    counters = drv._job(ctx, state).result.counters
    drv.release(ctx, state)
    table = ctx.config["k"] * (1 << 16) * 4
    assert counters["ckpt_saves"] == 1
    assert 5 * table <= counters["ckpt_bytes"] < 5 * table + 1024


def test_bf16_control_reads_above_the_limits(tmp_path):
    ctx = _context(str(tmp_path))
    got = controls.readings(ctx)
    limits = ctx.traffic["limits"]
    over = {k: v for k, v in got.items() if k in limits and v > limits[k]}
    assert over, (got, limits)
