"""Readings of each cell's control, at the cell's own size.

The control is the plain reference put in the program's place, one step
below the precision or the guarantee the configuration states (see
``bench/reference.py``).  It must read above the cell's limits, so that
a program that took that step would come out not correct.  The
benchmark's own runs never run this; it is run by hand on the chip when
limits are set, and at a small size by the tests:

    python3 bench/controls.py --workload oph-train --seeds 1 2 3
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

import numpy as np  # noqa: E402

from bench import corpus, harness, reference  # noqa: E402


def train_readings(ctx: harness.Context, fault: str = None) -> dict:
    """The control's job (or, with ``fault``, the reference broken as
    ``reference.run_job`` describes) in place of the program's, through
    the cell's own check."""
    from bench.runners import train
    cfg, tr = ctx.config, ctx.traffic
    codes = corpus.random_codes(ctx.seed, cfg["train_rows"], cfg["k"],
                                cfg["b"])
    labels = corpus.planted_labels(ctx.seed, codes, cfg["b"],
                                   tr["label_noise"])
    run = dict(k=cfg["k"], b=cfg["b"], shards=cfg["preprocess_shards"],
               batch=cfg["stream_batch"], seed=ctx.seed32,
               lr=cfg["stream_lr"], l2=cfg["l2"],
               avg_start_frac=cfg["avg_start_frac"],
               world=tr["data_parallel"] or 1)
    low = reference.run_job(codes, labels, control=fault is None,
                            fault=fault, **run)
    state = {"codes": codes, "labels": labels, "arms": {},
             "first": {"params": low["params"],
                       "avg_params": low["avg_params"], "hits": low["hits"],
                       "seen": low["seen"], "steps": low["steps"],
                       "completed": True}}
    return {c.name: c.value for c in train.check(ctx, state, None)
            if c.name != "ops_off_kernel_arm"}


def train_faults(ctx: harness.Context) -> dict:
    """Readings of the faults a training cell can have, planted in the
    reference put in the program's place: half of each batch left out,
    and on more than one slot the exchange between chips left out.  (A
    state left unchanged reads 1 by the change gaps and needs no run.)"""
    faults = ["half_batch"]
    if (ctx.traffic["data_parallel"] or 1) > 1:
        faults.append("no_exchange")
    return {f: train_readings(ctx, fault=f) for f in faults}


def hash_readings(ctx: harness.Context) -> dict:
    from bench.runners import hash as drv
    cfg = ctx.config
    lengths = drv.block_lengths(ctx)
    ids = drv.sample_rows(ctx, lengths)
    # only the sampled documents are needed: make the block and keep them
    docs = drv.make_block(ctx.seed32, lengths)
    picked = [np.array(docs[i]) for i in ids]
    del docs
    encode = {"minwise": reference.minwise_packed,
              "oph": reference.oph_packed}[cfg["scheme"]]
    want = encode(picked, cfg["k"], cfg["b"], ctx.seed32)
    low = encode(picked, cfg["k"], cfg["b"], ctx.seed32, control=True)
    return {"rows_differing": int(np.sum(np.any(low != want, axis=1)))}


def serve_readings(ctx: harness.Context) -> dict:
    """bfloat16 scores of the requests the check would sample (every
    request answered) against the float32 reference."""
    from bench.runners import serve
    from bench.loadgen import plan
    p = serve.load_params(ctx)
    _, lengths = plan(p)
    recs = [{"status": 200} for _ in lengths]
    ids = serve.sample(ctx, recs, lengths)
    params = serve.make_params(ctx)
    state = {"load": p, "lengths": lengths,
             "params": {k: np.asarray(v) for k, v in params.items()}}
    want = serve.reference_scores(ctx, state, ids)
    low = serve.reference_scores(ctx, state, ids, control=True)
    return {"score_max_abs_gap": float(np.max(np.abs(
        low.astype(np.float64) - want.astype(np.float64))))}


READINGS = {"train": train_readings, "hash": hash_readings,
            "serve": serve_readings}


def readings(ctx: harness.Context) -> dict:
    return READINGS[ctx.traffic["runner"]](ctx)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    bench = harness.benchmark()
    cell = harness.find_cell(args.workload, bench)
    config, traffic = harness.cell_files(cell, bench)
    seconds = args.seconds or bench["run_seconds"]
    for seed in args.seeds:
        ctx = harness.Context(cell, config, traffic, seed, cell["chips"],
                              os.path.join(harness.WORK_DIR, cell["name"]),
                              seconds)
        t0 = time.perf_counter()
        rec = {"workload": cell["name"], "seed": seed,
               "control": readings(ctx)}
        if traffic["runner"] == "train":
            rec["faults"] = train_faults(ctx)
        harness.log(dict(rec, seconds=time.perf_counter() - t0))
    return 0


if __name__ == "__main__":
    sys.exit(main())
