"""Streaming training: one-pass jobs over a packed shard archive.

Set-up makes the archive from the seed (uniform b-bit codes, labels of a
planted linear model), writes it with the program's shard writer, and
runs one whole job, which compiles every step shape the window uses.
The window runs the same job back to back, as the production launcher
runs it: ``run_supervised`` → ``fit_streaming`` with checkpoints.  The
check replays the first job of the window with the plain float32
reference on the same batches in the same order.
"""
from __future__ import annotations

import gc
import os
import shutil
import time

import numpy as np

from bench import corpus, harness, reference

PACK_ROWS = 1 << 14


def _pack(codes: np.ndarray, b: int) -> np.ndarray:
    if b == 8:
        return codes.astype(np.uint8)
    out = [reference.pack_codes(codes[i:i + PACK_ROWS], b)
           for i in range(0, len(codes), PACK_ROWS)]
    return np.concatenate(out)


def fit_kwargs(cfg: dict, traffic: dict) -> dict:
    """``fit_streaming`` settings of the configuration."""
    return dict(loss=cfg["loss"], optimizer=cfg["optimizer"],
                lr=cfg["stream_lr"], l2=cfg["l2"],
                epochs=cfg["stream_epochs"], batch_size=cfg["stream_batch"],
                avg_start_frac=cfg["avg_start_frac"],
                ckpt_every_shards=cfg["ckpt_every_shards"],
                prefetch=cfg["stream_prefetch"],
                data_parallel=traffic["data_parallel"],
                elastic=cfg["ft_elastic"],
                ckpt_keep_last=cfg["ft_ckpt_keep_last"])


def setup(ctx: harness.Context) -> dict:
    from repro.data.hashed_dataset import HashedShardWriter
    cfg, tr = ctx.config, ctx.traffic
    k, b, n = cfg["k"], cfg["b"], cfg["train_rows"]
    codes = corpus.random_codes(ctx.seed, n, k, b)
    labels = corpus.planted_labels(ctx.seed, codes, b, tr["label_noise"])
    root = ctx.fresh_dir("archive")
    writer = HashedShardWriter(root, k, b, n_total=n, scheme=cfg["scheme"],
                               seed=ctx.seed32,
                               n_shards=cfg["preprocess_shards"])
    writer.append(np.arange(n), _pack(codes, b), labels)
    writer.close()
    state = {"codes": codes, "labels": labels, "root": root,
             "kwargs": fit_kwargs(cfg, tr), "jobs": 0}
    t0 = time.perf_counter()
    _job(ctx, state)                         # compiles every step shape
    harness.log({"phase": "warmup", "job_s": time.perf_counter() - t0,
                 "arms": harness.arms()})
    return state


def _job(ctx: harness.Context, state: dict):
    import jax
    from repro.models.linear import BBitLinearConfig
    from repro.train import RestartPolicy, run_supervised
    cfg = ctx.config
    ckpt = os.path.join(ctx.workdir, "ckpt", f"job{state['jobs']}")
    shutil.rmtree(ckpt, ignore_errors=True)
    state["jobs"] += 1
    lcfg = BBitLinearConfig(k=cfg["k"], b=cfg["b"],
                            n_classes=cfg["n_classes"])
    sup = run_supervised(state["root"], lcfg,
                         policy=RestartPolicy(max_restarts=0),
                         ckpt_dir=ckpt, seed=ctx.seed32, **state["kwargs"])
    jax.block_until_ready((sup.result.params, sup.result.avg_params))
    return sup


def window(ctx: harness.Context, state: dict, seconds: float):
    rows = steps = jobs = restarts = 0
    first = None
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with ctx.span("bench.train_job"):
            sup = _job(ctx, state)
        res = sup.result
        rows += res.examples_seen
        steps += res.n_steps
        jobs += 1
        restarts += sup.restarts
        if first is None:
            first = {"params": _host(res.params),
                     "avg_params": _host(res.avg_params),
                     "hits": int(round(res.progressive_acc
                                       * res.examples_seen)),
                     "seen": res.examples_seen, "steps": res.n_steps,
                     "completed": res.completed}
    elapsed = time.perf_counter() - t0
    state["first"] = first
    state["arms"] = harness.arms()
    cfg = ctx.config
    counters = {"rows": rows, "steps": steps, "jobs": jobs,
                "window_s": elapsed, "k": cfg["k"], "b": cfg["b"],
                "classes_out": 1 if cfg["n_classes"] == 2
                else cfg["n_classes"],
                "batch": cfg["stream_batch"],
                "slots": ctx.traffic["data_parallel"] or 1}
    return harness.WindowResult(
        metrics={"train_rows_per_s": rows / elapsed},
        attempted=jobs, failed=restarts, counters=counters,
        notes={"arms": state["arms"]})


def _host(tree):
    import jax
    return None if tree is None else {
        k: np.asarray(v) for k, v in jax.device_get(tree).items()}


def release(ctx: harness.Context, state: dict) -> None:
    shutil.rmtree(os.path.join(ctx.workdir, "ckpt"), ignore_errors=True)
    gc.collect()


def check(ctx: harness.Context, state: dict, out) -> list:
    import jax
    cfg, tr = ctx.config, ctx.traffic
    got = state["first"]
    run = dict(k=cfg["k"], b=cfg["b"], shards=cfg["preprocess_shards"],
               batch=cfg["stream_batch"], seed=ctx.seed32,
               lr=cfg["stream_lr"], l2=cfg["l2"],
               avg_start_frac=cfg["avg_start_frac"],
               world=tr["data_parallel"] or 1)
    want = reference.run_job(state["codes"], state["labels"], **run)
    init = {"table": np.asarray(0.01 * jax.random.normal(
        jax.random.key(ctx.seed32),
        (cfg["k"], 1 << cfg["b"], 1))), "bias": np.zeros(1, np.float32)}
    limits = tr["limits"]

    def gap(name):
        if got[name] is None:              # no average kept: nothing moved
            return 1.0
        return max(reference.change_gaps(got[name], want[name], init,
                                         want["first_grad"]).values())

    checks = [
        harness.Check("param_change_gap", gap("params"),
                      limits["param_change_gap"]),
        harness.Check("avg_change_gap", gap("avg_params"),
                      limits["avg_change_gap"]),
        harness.Check("hits_gap", abs(got["hits"] - want["hits"])
                      / want["seen"], limits["hits_gap"]),
        harness.Check("steps_rows_mismatch",
                      abs(got["steps"] - want["steps"])
                      + abs(got["seen"] - want["seen"])
                      + (0 if got["completed"] else 1), 0),
    ]
    checks += harness.arm_checks(state["arms"], tr.get("kernel_arms", {}))
    return checks

