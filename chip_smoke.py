"""Bring-up smoke test: hash → stream-train → serve on one TPU chip.

Drives the production configuration (``configs/rcv1_oph.py``: densified
OPH, k=256, b=8, stream batch 1024, prefetch 2) once through the
library entry points a user calls, all in this one process:

  hash   a synthetic expanded-rcv1 corpus (``data/synth_rcv1`` defaults,
         made from ``--seed``) → ``preprocess_and_save`` (fused device
         encode) → 4 packed shards; a slice is also encoded with the
         minwise scheme.  Packed bytes must equal the numpy encoders
         bit for bit.
  train  ``fit_streaming`` over the shards under ``train.run_supervised``
         (no restarts allowed); the loss must be finite and the
         progressive accuracy must clear ``PROGRESSIVE_ACC_FLOOR``.
  serve  ``HashedClassifierEngine`` scores held-out short and long
         documents; they must match numpy encode + ``kernels/ref.py``
         logits within ``SCORE_RTOL``/``SCORE_ATOL``.

Each phase prints one JSON line: the device, the dispatch arms
``perf`` chose, and how many Pallas kernels (``tpu_custom_call``) the
compiled encode / train-step / serving HLO holds.  On a TPU a phase
fails if a hot op fell back off its kernel arm.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any backend but a TPU fails before work starts.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # dp=4 training vs the one-chip
                                        # fold; 4 serving replicas vs 1
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")
WORKDIR = os.path.join(HERE, ".chip_smoke")
HAVE_SRC = os.path.isdir(os.path.join(SRC, "repro"))
if HAVE_SRC and SRC not in sys.path:
    sys.path.insert(0, SRC)

# one pass over ~16k documents: chance is 0.5, and a CPU rehearsal at
# 2048 documents and batch 128 reached 0.94
PROGRESSIVE_ACC_FLOOR = 0.8
SCORE_RTOL = 1e-5
SCORE_ATOL = 1e-4
# dp=4 on four chips vs the same logical schedule folded onto one: the
# gradient all-reduce may add the four slot sums in another order
FOLD_PARAM_ATOL = 1e-4
REPLICA_SCORE_ATOL = 1e-6

CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Corpus and batch sizes; the defaults are the chip run's."""
    n_docs: int = 16384
    held_out: int = 256        # kept out of the archive, served later
    check_docs: int = 512      # per scheme, compared bit for bit
    serve_docs: int = 64       # half the shortest, half the longest
    k: int = 256
    b: int = 8
    batch: int = 1024
    n_shards: int = 4


def _device() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _arms() -> dict:
    """op → the impls ``perf`` chose since the last ``perf.reset``."""
    from repro import perf
    out: dict = {}
    for key, impl in perf.dispatch_report()["choices"].items():
        out.setdefault(key.split("|")[0], set()).add(impl)
    return {op: sorted(impls) for op, impls in sorted(out.items())}


def _custom_calls(jitted, *args) -> int:
    return jitted.lower(*args).compile().as_text().count(CUSTOM_CALL)


def _require(rec: dict, arms: dict, calls: tuple) -> None:
    """On a TPU: every listed op ran only its kernel arm, and every
    listed compiled program holds at least one Pallas kernel."""
    bad = {op: rec["arms"].get(op) for op, want in arms.items()
           if rec["arms"].get(op) != [want]}
    bad.update({name: rec["tpu_custom_calls"][name] for name in calls
                if rec["tpu_custom_calls"][name] < 1})
    if bad:
        raise AssertionError(f"{rec['phase']}: fell back off the Pallas "
                             f"path on {rec['device']['platform']}: {bad}")


def _padded(docs):
    from repro.data import pad_rows
    return pad_rows(list(docs), pad_to_multiple=1)


def _numpy_packed(scheme, docs, b: int):
    """Host reference bytes, one document at a time (no padding to the
    longest document of the set)."""
    import numpy as np
    out = [scheme.encode_packed_numpy(*_padded([d]), b)[0] for d in docs]
    return np.concatenate(out)


def _encode_fn(scheme, b: int):
    import jax
    return jax.jit(lambda idx, nnz: scheme.encode_packed_jit(idx, nnz, b)[0])


def build_archive(workdir: str, sizes: Sizes, seed: int):
    """Corpus from ``seed`` → packed OPH shards of every document but
    the held-out tail.  Returns (rows, labels, archive root, stats)."""
    from repro.data import (SynthRcv1Config, generate_arrays,
                            preprocess_and_save)
    rows, labels = generate_arrays(sizes.n_docs, SynthRcv1Config(seed=seed))
    n_train = sizes.n_docs - sizes.held_out
    root = os.path.join(workdir, "shards")
    stats = preprocess_and_save(root, rows[:n_train], labels[:n_train],
                                k=sizes.k, b=sizes.b, scheme="oph",
                                seed=seed, n_shards=sizes.n_shards)
    return rows, labels, root, stats


def phase_hash(workdir: str, sizes: Sizes, seed: int,
               require_kernels: bool) -> tuple:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import perf
    from repro.core.schemes import make_scheme
    from repro.data import load_packed_shard, preprocess_rows_packed

    perf.reset()
    rows, labels, root, stats = build_archive(workdir, sizes, seed)
    n_train = sizes.n_docs - sizes.held_out

    # OPH: archive bytes of a seeded sample (plus the longest document)
    # against the numpy encoder
    ids, packed = [], []
    for s in range(sizes.n_shards):
        pk, _lab, rid, _em = load_packed_shard(root, s)
        ids.append(np.asarray(rid))
        packed.append(np.asarray(pk))
    ids, packed = np.concatenate(ids), np.concatenate(packed)
    if sorted(ids.tolist()) != list(range(n_train)):
        raise AssertionError("archive does not hold every document once")
    by_id = np.empty_like(packed)
    by_id[ids] = packed
    rng = np.random.default_rng(seed)
    sel = rng.choice(n_train, size=min(sizes.check_docs, n_train),
                     replace=False)
    longest = int(np.argmax([len(r) for r in rows[:n_train]]))
    sel = np.union1d(sel, [longest])
    oph = make_scheme("oph", sizes.k, seed)
    want = _numpy_packed(oph, [rows[i] for i in sel], sizes.b)
    if not np.array_equal(by_id[sel], want):
        bad = int(np.sum(np.any(by_id[sel] != want, axis=1)))
        raise AssertionError(f"oph: {bad}/{len(sel)} archive rows differ "
                             "from encode_packed_numpy")

    # minwise: the fused device encode of a slice against numpy
    mw_docs = rows[:sizes.check_docs]
    mw_dev, _ = preprocess_rows_packed(mw_docs, sizes.k, sizes.b,
                                       scheme="minwise", seed=seed)
    minwise = make_scheme("minwise", sizes.k, seed)
    mw_want = _numpy_packed(minwise, mw_docs, sizes.b)
    if not np.array_equal(mw_dev, mw_want):
        bad = int(np.sum(np.any(mw_dev != mw_want, axis=1)))
        raise AssertionError(f"minwise: {bad}/{len(mw_docs)} rows differ "
                             "from encode_packed_numpy")

    idx, nnz = _padded(rows[:8])
    idx, nnz = jnp.asarray(idx), jnp.asarray(nnz)
    calls = {name: _custom_calls(_encode_fn(sch, sizes.b), idx, nnz)
             for name, sch in (("encode_oph", oph),
                               ("encode_minwise", minwise))}
    rec = {"phase": "hash", "ok": True, "device": _device(),
           "docs": sizes.n_docs, "docs_in_archive": n_train,
           "shards": sizes.n_shards, "total_nnz": stats["total_nnz"],
           "bitwise_checked": {"oph": int(len(sel)),
                               "minwise": len(mw_docs)},
           "arms": _arms(), "tpu_custom_calls": calls}
    if require_kernels:
        _require(rec, {"encode_packed": "pallas", "pallas_mode": "compiled"},
                 tuple(calls))
    return rec, (rows, labels, root)


def _lcfg(sizes: Sizes):
    from repro.configs.rcv1_oph import CONFIG
    from repro.models.linear import BBitLinearConfig
    return BBitLinearConfig(k=sizes.k, b=sizes.b, n_classes=CONFIG.n_classes)


def _fit(root: str, ckpt_dir: str, sizes: Sizes, seed: int, **overrides):
    from repro.configs.rcv1_oph import CONFIG
    from repro.train import RestartPolicy, run_supervised
    return run_supervised(
        root, _lcfg(sizes), policy=RestartPolicy(max_restarts=0),
        ckpt_dir=ckpt_dir, seed=seed, loss=CONFIG.loss,
        **CONFIG.stream_kwargs(batch_size=sizes.batch, **overrides))


def phase_train(workdir: str, sizes: Sizes, seed: int, root: str,
                require_kernels: bool,
                acc_floor: float = PROGRESSIVE_ACC_FLOOR) -> tuple:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import perf
    from repro.configs.rcv1_oph import CONFIG
    from repro.data import load_packed_shard
    from repro.models.linear import bbit_logits_packed
    from repro.train.losses import mean_loss_fn

    perf.reset()
    sup = _fit(root, os.path.join(workdir, "ckpt"), sizes, seed)
    res = sup.result
    if sup.restarts or sup.crashes:
        raise AssertionError(f"training crashed: {sup.crashes}")
    if res.n_steps < 8 or not res.completed:
        raise AssertionError(f"{res.n_steps} steps, completed="
                             f"{res.completed}; want a whole pass of >= 8")
    params = res.eval_params
    if not all(bool(jnp.all(jnp.isfinite(x)))
               for x in jax.tree.leaves(params)):
        raise AssertionError("non-finite parameters")

    lcfg = _lcfg(sizes)
    pk, lab, _ids, _em = load_packed_shard(root, 0)
    pk = jnp.asarray(np.asarray(pk[:sizes.batch]))
    lab = jnp.asarray(np.asarray(lab[:sizes.batch]))
    loss_fn = mean_loss_fn(lambda p, x: bbit_logits_packed(p, x, lcfg),
                           CONFIG.loss)
    loss = float(jax.jit(loss_fn)(params, pk, lab))
    if not np.isfinite(loss):
        raise AssertionError(f"non-finite training loss {loss}")
    if res.progressive_acc < acc_floor:
        raise AssertionError(f"progressive accuracy {res.progressive_acc}"
                             f" < floor {acc_floor}")
    calls = {"train_grad": _custom_calls(
        jax.jit(jax.value_and_grad(loss_fn)), params, pk, lab)}
    rec = {"phase": "train", "ok": True, "device": _device(),
           "steps": res.n_steps, "examples_seen": res.examples_seen,
           "batch": sizes.batch, "loss": loss,
           "progressive_acc": res.progressive_acc,
           "acc_floor": acc_floor, "restarts": sup.restarts,
           "dispatch": res.dispatch, "arms": _arms(),
           "tpu_custom_calls": calls}
    if require_kernels:
        _require(rec, {"logits_packed": "kernel",
                       "logits_packed_bwd": "kernel",
                       "pallas_mode": "compiled"}, tuple(calls))
    return rec, params


def _serve_docs(rows, sizes: Sizes) -> list:
    """The held-out tail, half its shortest documents and half its
    longest."""
    held = rows[sizes.n_docs - sizes.held_out:]
    order = sorted(range(len(held)), key=lambda i: len(held[i]))
    half = sizes.serve_docs // 2
    pick = order[:half] + order[-(sizes.serve_docs - half):]
    return [held[i] for i in pick]


def _engine_scores(params, sizes: Sizes, seed: int, docs, replicas: int):
    """Scores ``docs`` submitted in four waves, each resolved before the
    next, so that a four-replica engine serves a batch on every chip."""
    import numpy as np
    from repro.configs.rcv1_oph import CONFIG
    from repro.serving import HashedClassifierEngine
    engine = HashedClassifierEngine(
        params, _lcfg(sizes), seed=seed,
        **CONFIG.serve_kwargs(replicas=replicas))
    try:
        scores = []
        for wave in np.array_split(np.arange(len(docs)), 4):
            futs = [engine.submit(docs[i]) for i in wave]
            engine.flush()
            scores += [float(f.result(timeout=600)) for f in futs]
        return engine, np.asarray(scores), engine.stats()
    finally:
        engine.close()


def phase_serve(sizes: Sizes, seed: int, rows, params,
                require_kernels: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import perf
    from repro.core.schemes import make_scheme
    from repro.kernels import ref

    perf.reset()
    docs = _serve_docs(rows, sizes)
    engine, got, stats = _engine_scores(params, sizes, seed, docs, 1)

    packed = _numpy_packed(make_scheme("oph", sizes.k, seed), docs, sizes.b)
    logits = ref.bbit_linear_packed_fwd(jnp.asarray(packed),
                                        params["table"], sizes.k, sizes.b)
    want = np.asarray(logits + params["bias"].astype(jnp.float32))[:, 0]
    np.testing.assert_allclose(got, want, rtol=SCORE_RTOL, atol=SCORE_ATOL)

    idx = jnp.zeros((engine.row_buckets[-1], engine.nnz_buckets[0]),
                    jnp.int32)
    nnz = jnp.ones((idx.shape[0],), jnp.int32)
    calls = {"serve_score": _custom_calls(engine._score_fn, idx, nnz,
                                          params)}
    lens = [len(d) for d in docs]
    rec = {"phase": "serve", "ok": True, "device": _device(),
           "docs": len(docs), "nnz_min": min(lens), "nnz_max": max(lens),
           "max_abs_err": float(np.max(np.abs(got - want))),
           "rtol": SCORE_RTOL, "atol": SCORE_ATOL,
           "compile_misses": stats["compile_misses"],
           "arms": _arms(), "tpu_custom_calls": calls}
    if require_kernels:
        _require(rec, {"encode_packed": "pallas",
                       "logits_packed": "kernel",
                       "pallas_mode": "compiled"}, tuple(calls))
    return rec


def _max_diff(a, b) -> tuple:
    import jax
    import numpy as np
    la = [np.asarray(x) for x in jax.tree.leaves(a)]
    lb = [np.asarray(x) for x in jax.tree.leaves(b)]
    diff = max(float(np.max(np.abs(x - y))) for x, y in zip(la, lb))
    return diff, all(np.array_equal(x, y) for x, y in zip(la, lb))


def phase_four_chips(workdir: str, sizes: Sizes, seed: int) -> list:
    """dp=4 streaming training on four chips vs the same logical
    schedule folded onto one; four serving replicas vs one."""
    import jax
    import numpy as np

    n = len(jax.devices())
    if n < 4:
        raise AssertionError(f"--four-chips needs 4 devices, found {n}")
    rows, _labels, root, _stats = build_archive(workdir, sizes, seed)
    runs = {}
    for name, cap in (("dp4", None), ("fold1", 1)):
        runs[name] = _fit(root, os.path.join(workdir, f"ckpt_{name}"),
                          sizes, seed, data_parallel=4, elastic=True,
                          max_devices=cap).result
    dp4, fold1 = runs["dp4"], runs["fold1"]
    physical = [[e["physical"] for e in r.topology_lineage]
                for r in (dp4, fold1)]
    if physical != [[4], [1]]:
        raise AssertionError(f"realizations {physical}, want [[4], [1]]")
    if (dp4.n_steps, dp4.examples_seen) != (fold1.n_steps,
                                            fold1.examples_seen):
        raise AssertionError("dp4 and the fold took different schedules")
    diff, bitwise = _max_diff(dp4.params, fold1.params)
    if not diff <= FOLD_PARAM_ATOL:
        raise AssertionError(f"dp4 vs fold params differ by {diff}")
    train = {"phase": "dp4_vs_fold1", "ok": True, "device": _device(),
             "steps": dp4.n_steps, "max_abs_param_diff": diff,
             "bitwise": bitwise, "atol": FOLD_PARAM_ATOL,
             "progressive_acc": [dp4.progressive_acc,
                                 fold1.progressive_acc]}

    docs = _serve_docs(rows, sizes)
    params = dp4.eval_params
    _, one, _ = _engine_scores(params, sizes, seed, docs, 1)
    engine4, four, _ = _engine_scores(params, sizes, seed, docs, 4)
    np.testing.assert_allclose(four, one, rtol=0,
                               atol=REPLICA_SCORE_ATOL)
    if min(engine4.device_batches) < 1:
        raise AssertionError(f"a replica served nothing: batches per "
                             f"chip {engine4.device_batches}")
    serve = {"phase": "replicas4_vs_1", "ok": True, "device": _device(),
             "docs": len(docs),
             "max_abs_diff": float(np.max(np.abs(four - one))),
             "bitwise": bool(np.array_equal(four, one)),
             "atol": REPLICA_SCORE_ATOL,
             "device_batches": engine4.device_batches}
    return [train, serve]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--docs", type=int, default=Sizes.n_docs,
                    help="corpus size (documents)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only dp=4 vs the one-chip fold and 4 "
                         "serving replicas vs 1")
    args = ap.parse_args(argv)

    if not HAVE_SRC:
        print(f"chip_smoke: no repro package under {SRC}; run from a "
              "checkout of the repository", file=sys.stderr)
        return 2

    import jax
    device = _device()
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device}",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    print(json.dumps({"phase": "setup", "device": device,
                      "jax": jax.__version__, "compile_cache": cache}),
          flush=True)

    sizes = Sizes(n_docs=args.docs)
    shutil.rmtree(WORKDIR, ignore_errors=True)
    os.makedirs(WORKDIR)
    try:
        if args.four_chips:
            for rec in phase_four_chips(WORKDIR, sizes, args.seed):
                print(json.dumps(rec), flush=True)
        else:
            rec, (rows, _labels, root) = phase_hash(
                WORKDIR, sizes, args.seed, require_kernels=True)
            print(json.dumps(rec), flush=True)
            rec, params = phase_train(WORKDIR, sizes, args.seed, root,
                                      require_kernels=True)
            print(json.dumps(rec), flush=True)
            rec = phase_serve(sizes, args.seed, rows, params,
                              require_kernels=True)
            print(json.dumps(rec), flush=True)
    except Exception as e:  # noqa: BLE001 — report, then fail
        traceback.print_exc()
        print(json.dumps({"phase": "failed", "ok": False,
                          "error": f"{type(e).__name__}: {e}"[:2000]}),
              flush=True)
        return 1
    finally:
        shutil.rmtree(WORKDIR, ignore_errors=True)
    print(json.dumps({"ok": True, "device": _device()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
