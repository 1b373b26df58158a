"""Scoring service: open-loop HTTP traffic against ``ScoreServer``.

Set-up makes the weights on the device from the seed, builds the engine
and the HTTP server with the configuration's settings, warms every
(row, nnz) lane the traffic reaches (the engine precompiles its static
lanes; documents past the largest bucket grow into power-of-two lanes,
warmed here), and starts the load generator, a child process that never
imports JAX, which builds its request bodies meanwhile.  The window
sends one document per ``POST /score`` at a fixed Poisson-like rate and
ends when every request is answered.  Latency runs from when a request
was due to its full response.  The check rescores a seeded sample of the
answered requests, with the longest among them, with the plain reference
(densified OPH encode and the gather form of the model).
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import time

import numpy as np

from bench import corpus, harness, reference

LOADGEN = os.path.join(harness.BENCH_DIR, "loadgen.py")


def load_params(ctx: harness.Context) -> dict:
    cfg, tr = ctx.config, ctx.traffic
    return {"host": cfg["serve_host"], "port": 0, "seed": ctx.seed,
            "rate": tr["rate"], "seconds": ctx.seconds,
            "grace_s": tr["grace_s"], "nnz_median": cfg["nnz_median"],
            "nnz_mean": cfg["nnz_mean"], "nnz_max": cfg["nnz_max"]}


def make_params(ctx: harness.Context):
    """Weights in the type they are served in, made in one jitted call."""
    import jax
    import jax.numpy as jnp
    cfg = ctx.config
    shape = (cfg["k"], 1 << cfg["b"], 1)

    @jax.jit
    def init(key):
        kt, kb = jax.random.split(key)
        return {"table": cfg["weight_scale"] * jax.random.normal(
                    kt, shape, jnp.float32),
                "bias": cfg["weight_scale"] * jax.random.normal(
                    kb, (1,), jnp.float32)}
    return init(jax.random.key(ctx.seed32))


def grown_lanes(lengths, buckets) -> list:
    """Lane widths past the largest bucket that the lengths reach."""
    top = max(buckets)
    return sorted({1 << (int(n) - 1).bit_length() for n in lengths
                   if n > top})


def setup(ctx: harness.Context) -> dict:
    from repro.models.linear import BBitLinearConfig
    from repro.serving import HashedClassifierEngine, ScoreServer
    from bench.loadgen import plan
    cfg = ctx.config
    p = load_params(ctx)
    child = subprocess.Popen(
        [sys.executable, LOADGEN, json.dumps(p)], stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, text=True, cwd=harness.ROOT)
    try:
        params = make_params(ctx)
        lcfg = BBitLinearConfig(k=cfg["k"], b=cfg["b"],
                                n_classes=cfg["n_classes"])
        engine = HashedClassifierEngine(
            params, lcfg, seed=ctx.seed32, scheme=cfg["scheme"],
            max_batch=cfg["serve_max_batch"],
            max_wait_ms=cfg["serve_max_wait_ms"],
            replicas=cfg["serve_replicas"],
            nnz_buckets=tuple(cfg["serve_nnz_buckets"]),
            pipeline_depth=cfg["serve_pipeline_depth"],
            stats_window=cfg["serve_stats_window"],
            adapt_every=cfg["serve_adapt_every"],
            dedup_cache=cfg["dedup_cache"],
            dedup_entries=cfg["dedup_entries"],
            dedup_rows_per_band=cfg["dedup_rows_per_band"],
            dedup_probe_bands=cfg["dedup_probe_bands"])
        _, lengths = plan(p)
        for m in grown_lanes(lengths, cfg["serve_nnz_buckets"]):
            doc = np.arange(m, dtype=np.int64)
            for r in engine.row_buckets:
                engine.score_docs([doc] * r)
        server = ScoreServer(engine, host=cfg["serve_host"], port=0,
                             drain_timeout_s=cfg["serve_drain_timeout_s"])
        server.start_in_thread()
        ready = child.stdout.readline().strip()
        if ready != "READY":
            raise RuntimeError(f"load generator failed to start: {ready!r}")
    except BaseException:
        child.kill()
        child.wait()
        raise
    harness.log({"phase": "warmup", "arms": harness.arms(),
                 "requests": len(lengths),
                 "grown_lanes": grown_lanes(lengths,
                                            cfg["serve_nnz_buckets"])})
    return {"params": params, "engine": engine, "server": server,
            "child": child, "load": p, "lengths": lengths,
            "before": engine.stats()}


def window(ctx: harness.Context, state: dict, seconds: float):
    child, engine = state["child"], state["engine"]
    t0 = time.perf_counter()
    with ctx.span("bench.http_load"):
        out, _ = child.communicate(f"{state['server'].port}\n")
    elapsed = time.perf_counter() - t0
    if child.returncode != 0:
        raise RuntimeError(f"load generator exited {child.returncode}")
    res = json.loads(out.strip().splitlines()[-1])
    recs = res["records"]
    state["records"] = recs
    after, before = engine.stats(), state["before"]
    miss = 1e3 * (recs[-1]["due"] + state["load"]["grace_s"])
    lat = [1e3 * (r["done"] - r["due"]) if r["status"] == 200 else miss
           for r in recs]
    late = [1e3 * (r["sent"] - r["due"]) for r in recs]
    last_due = recs[-1]["due"]
    backlog = sum(1 for r in recs if r["done"] is None
                  or r["done"] > last_due)
    half = len(recs) // 2
    batches = after["batches_run"] - before["batches_run"]
    served = after["requests_served"] - before["requests_served"]
    counters = {"requests": len(recs), "batches": batches,
                "served": served, "window_s": elapsed,
                "compile_misses": after["compile_misses"]
                - before["compile_misses"]}
    notes = {"p50_ms": harness.percentile(lat, 50),
             "p95_first_half_ms": harness.percentile(lat[:half], 95),
             "p95_second_half_ms": harness.percentile(lat[half:], 95),
             "backlog_at_last_due": backlog,
             "lateness_p50_ms": harness.percentile(late, 50),
             "lateness_max_ms": max(late),
             "connections": res["connections"],
             "dedup": after.get("dedup")}
    failed = sum(1 for r in recs if r["status"] != 200)
    return harness.WindowResult(
        metrics={"serve_p95_ms": harness.percentile(lat, 95)},
        attempted=len(recs), failed=failed, counters=counters,
        notes=notes)


def release(ctx: harness.Context, state: dict) -> None:
    state["arms"] = harness.arms()
    server, child = state["server"], state["child"]
    server.request_drain()
    server.wait_finished(state["load"]["grace_s"])
    if child.poll() is None:
        child.kill()
    child.wait()
    state["params"] = {k: np.asarray(v) for k, v in state["params"].items()}
    state.pop("engine", None)


def sample(ctx: harness.Context, recs: list, lengths) -> np.ndarray:
    """A seeded sample of the answered requests, with the longest."""
    ok = [i for i, r in enumerate(recs) if r["status"] == 200]
    if not ok:
        return np.zeros(0, dtype=np.int64)
    rng = np.random.default_rng(np.random.SeedSequence((ctx.seed, 19)))
    n = min(ctx.traffic["check_requests"], len(ok))
    pick = set(rng.choice(ok, size=n, replace=False).tolist())
    pick.add(max(ok, key=lambda i: lengths[i]))
    return np.asarray(sorted(pick))


def reference_scores(ctx: harness.Context, state: dict, ids,
                     control: bool = False) -> np.ndarray:
    cfg, p = ctx.config, state["load"]
    docs = [corpus.serve_doc(p["seed"], int(i), int(state["lengths"][i]))
            for i in ids]
    packed = reference.oph_packed(docs, cfg["k"], cfg["b"], ctx.seed32)
    codes = reference.unpack_codes(packed, cfg["k"], cfg["b"])
    return reference.scores(state["params"]["table"],
                            state["params"]["bias"], codes, control=control)


def check(ctx: harness.Context, state: dict, out) -> list:
    recs = state["records"]
    ids = sample(ctx, recs, state["lengths"])
    got = np.asarray([recs[i]["score"] for i in ids], dtype=np.float64)
    want = reference_scores(ctx, state, ids).astype(np.float64)
    gap = float(np.max(np.abs(got - want))) if len(ids) else math.inf
    unanswered = sum(1 for r in recs if r["status"] != 200)
    return [harness.Check("score_max_abs_gap", gap,
                          ctx.traffic["limits"]["score_max_abs_gap"]),
            harness.Check("requests_unanswered", unanswered, 0)] + \
        harness.arm_checks(state["arms"], ctx.traffic.get("kernel_arms", {}))
