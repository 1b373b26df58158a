"""Shared helpers of the benchmark's tests: every cell at a size a CPU
test run can hold, driven through the same set-up, window and check as
a chip run (the look for a chip is the only part left out)."""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness  # noqa: E402

# per cell: (configuration overrides, traffic overrides).  Kernel arms
# are not required: off a TPU the program dispatches its XLA arms.
SMALL = {
    "oph-train": ({"k": 16, "train_rows": 3000, "stream_batch": 128,
                   "preprocess_shards": 4}, {"kernel_arms": {}}),
    "oph-train-dp4": ({"k": 16, "train_rows": 3000, "stream_batch": 128,
                       "preprocess_shards": 8}, {"kernel_arms": {}}),
    "minwise-hash": ({"k": 32, "block_docs": 200, "nnz_max": 9000,
                      "preprocess_chunk": 64}, {"kernel_arms": {}}),
    "oph-serve": ({"nnz_max": 40000, "serve_nnz_buckets": [128, 512, 2048],
                   "serve_max_batch": 8},
                  {"kernel_arms": {}, "rate": 15, "check_requests": 10}),
}


# cells whose traffic and metric files are kept for a later benchmark
# change but which BENCHMARK.json does not list yet
PENDING = {
    "oph-serve": {"name": "oph-serve", "config": "rcv1-oph",
                  "traffic": "http_open_loop", "chips": 1},
    "minwise-hash": {"name": "minwise-hash", "config": "rcv1-minwise",
                     "traffic": "hash_block", "chips": 1},
    "oph-train-dp4": {"name": "oph-train-dp4", "config": "rcv1-oph",
                      "traffic": "stream_train_dp4", "chips": 4},
}


def small_context(name: str, seed: int, workdir: str,
                  seconds: float = 1.0) -> harness.Context:
    bench = harness.benchmark()
    cell = PENDING.get(name) or harness.find_cell(name, bench)
    if all(c["name"] != cell["config"] for c in bench["configs"]):
        # a pending cell's configuration: its file, found by its name
        bench["configs"].append({"name": cell["config"], "file": os.path.join(
            "bench", "configs", cell["config"] + ".json")})
    config, traffic = harness.cell_files(cell, bench)
    conf, traf = SMALL[name]
    config.update(conf)
    traffic.update(traf)
    os.makedirs(workdir, exist_ok=True)
    return harness.Context(cell, config, traffic, seed, 1, workdir,
                           seconds)


def run_small(name: str, seed: int, workdir: str, seconds: float = 1.0):
    """Set-up, window, release and check of one cell at its small size;
    returns (window result, checks, correct)."""
    ctx = small_context(name, seed, workdir, seconds)
    drv = harness.runner(ctx.traffic)
    state = drv.setup(ctx)
    out = drv.window(ctx, state, seconds)
    drv.release(ctx, state)
    checks = drv.check(ctx, state, out)
    return out, checks, all(c.ok for c in checks)
