"""Preprocessing: a resident block of documents hashed pass after pass.

Set-up makes the block from the seed (a fixed set of lengths in a seeded
order, ids uniform over the id space and distinct within a document)
and runs one whole pass, which compiles every chunk shape.  The window
re-hashes the block into the same archive directory with
``preprocess_and_save``, as a user hashes a corpus larger than memory
one block at a time.  The check compares the packed bytes of the last
pass, on rows from every chunk and the longest document, with the plain
reference encoder.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from bench import corpus, harness, reference

GROUP_DOCS = 4096           # documents made per device call, at most
GROUP_IDS = 1 << 24         # ids made per device call


def block_lengths(ctx: harness.Context) -> np.ndarray:
    cfg = ctx.config
    lengths = corpus.length_set(cfg["block_docs"], cfg["nnz_median"],
                                cfg["nnz_mean"], cfg["nnz_max"])
    return corpus.shuffled(lengths, ctx.seed, 1)


def make_block(seed: int, lengths: np.ndarray) -> list:
    """The block's documents as views of one int32 host buffer, made on
    the device a group of documents at a time (at most ``GROUP_DOCS``
    documents and ``GROUP_IDS`` ids a call): id j of a document of
    length L is j·(D//L) plus a uniform offset below D//L, so ids are
    distinct within a document and uniform over the id space."""
    import jax
    import jax.numpy as jnp

    lengths = np.asarray(lengths, dtype=np.int64)
    if lengths.max() > GROUP_IDS:
        raise ValueError(f"documents longer than {GROUP_IDS} ids")
    starts = np.zeros(len(lengths) + 1, dtype=np.int64)
    np.cumsum(lengths, out=starts[1:])

    @jax.jit
    def ids(key, lens):
        owner = jnp.repeat(jnp.arange(GROUP_DOCS, dtype=jnp.int32), lens,
                           total_repeat_length=GROUP_IDS)
        first = jnp.cumsum(lens) - lens
        stride = jnp.int32(corpus.ID_SPACE) // jnp.maximum(lens[owner], 1)
        local = jnp.arange(GROUP_IDS, dtype=jnp.int32) - first[owner]
        r = jax.random.randint(key, (GROUP_IDS,), 0, 1 << 30,
                               dtype=jnp.int32) % stride
        return local * stride + r

    buf = np.empty(int(starts[-1]), dtype=np.int32)
    key = jax.random.key(seed)
    lo = 0
    while lo < len(lengths):
        hi = max(lo + 1, min(lo + GROUP_DOCS, len(lengths),
                             int(np.searchsorted(starts, starts[lo] + GROUP_IDS,
                                                 side="right")) - 1))
        lens = np.zeros(GROUP_DOCS, dtype=np.int32)
        lens[:hi - lo] = lengths[lo:hi]
        n = int(starts[hi] - starts[lo])
        out = ids(jax.random.fold_in(key, lo), jnp.asarray(lens))
        # slice on the host: a device slice of each length n would be a
        # program of its own
        buf[starts[lo]:starts[hi]] = np.asarray(out)[:n]
        lo = hi
    return [buf[starts[i]:starts[i + 1]] for i in range(len(lengths))]


def padded_slots(lengths: np.ndarray, chunk: int) -> int:
    """Id slots the encode pads to: length-sorted chunks of ``chunk``
    rows, widths rounded up to 128 and then to a power of two, rows of a
    short chunk to a power of two (at least 8)."""
    order = np.sort(np.asarray(lengths))
    slots = 0
    for lo in range(0, len(order), chunk):
        sel = order[lo:lo + chunk]
        width = max(128, -(-int(sel.max()) // 128) * 128)
        width = 1 << (width - 1).bit_length()
        rows = min(chunk, 1 << max(3, (len(sel) - 1).bit_length()))
        slots += rows * width
    return slots


def setup(ctx: harness.Context) -> dict:
    lengths = block_lengths(ctx)
    t0 = time.perf_counter()
    docs = make_block(ctx.seed32, lengths)
    make_s = time.perf_counter() - t0
    labels = np.zeros(len(docs), dtype=np.int32)
    state = {"docs": docs, "labels": labels, "lengths": lengths,
             "root": ctx.fresh_dir("archive"),
             "nnz": int(lengths.sum())}
    t0 = time.perf_counter()
    _pass(ctx, state)                        # compiles every chunk shape
    harness.log({"phase": "warmup", "block_s": make_s,
                 "pass_s": time.perf_counter() - t0,
                 "nnz": state["nnz"], "arms": harness.arms()})
    return state


def _pass(ctx: harness.Context, state: dict) -> dict:
    from repro.data.hashed_dataset import preprocess_and_save
    cfg = ctx.config
    return preprocess_and_save(
        state["root"], state["docs"], state["labels"], cfg["k"], cfg["b"],
        scheme=cfg["scheme"], family=cfg["hash_family"], seed=ctx.seed32,
        n_shards=cfg["preprocess_shards"], chunk=cfg["preprocess_chunk"])


def window(ctx: harness.Context, state: dict, seconds: float):
    nnz = passes = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with ctx.span("bench.hash_pass"):
            stats = _pass(ctx, state)
        nnz += stats["total_nnz"]
        passes += 1
    elapsed = time.perf_counter() - t0
    state["arms"] = harness.arms()
    cfg = ctx.config
    counters = {"nnz": nnz, "passes": passes, "window_s": elapsed,
                "docs": passes * len(state["docs"]), "k": cfg["k"],
                "b": cfg["b"],
                "padded_slots": passes * padded_slots(
                    state["lengths"], cfg["preprocess_chunk"])}
    return harness.WindowResult(
        metrics={"hash_mnnz_per_s": nnz / elapsed / 1e6},
        attempted=passes, failed=0, counters=counters,
        notes={"arms": state["arms"],
               "padded_slots_per_nnz": counters["padded_slots"] / nnz})


def release(ctx: harness.Context, state: dict) -> None:
    pass


def stored_rows(root: str, ids: np.ndarray, width: int):
    """The packed bytes the archive holds for the given document ids,
    and how many of them it does not hold (their rows read as zeros)."""
    with open(os.path.join(root, "meta.json")) as f:
        shards = json.load(f)["shards"]
    out = np.zeros((len(ids), width), dtype=np.uint8)
    found = np.zeros(len(ids), dtype=bool)
    where = {int(d): i for i, d in enumerate(ids)}
    for s in range(shards):
        base = os.path.join(root, f"hashed_{s:05d}")
        rows = np.load(base + ".rows.npy")
        codes = np.load(base + ".codes.npy", mmap_mode="r")
        for j in np.flatnonzero(np.isin(rows, ids)):
            i = where[int(rows[j])]
            out[i] = codes[j]
            found[i] = True
    return out, int(np.sum(~found))


def sample_rows(ctx: harness.Context, lengths: np.ndarray) -> np.ndarray:
    """Document ids to compare: ``check_per_chunk`` from every chunk of
    the length order (drawn from the seed) and the longest document."""
    cfg, tr = ctx.config, ctx.traffic
    order = np.argsort(lengths, kind="stable")
    rng = np.random.default_rng(np.random.SeedSequence((ctx.seed, 17)))
    chunk = cfg["preprocess_chunk"]
    pick = [order[-1]]
    for lo in range(0, len(order), chunk):
        sel = order[lo:lo + chunk]
        pick += list(rng.choice(sel, size=min(tr["check_per_chunk"],
                                              len(sel)), replace=False))
    return np.unique(np.asarray(pick))


def check(ctx: harness.Context, state: dict, out) -> list:
    cfg = ctx.config
    ids = sample_rows(ctx, state["lengths"])
    encode = {"minwise": reference.minwise_packed,
              "oph": reference.oph_packed}[cfg["scheme"]]
    want = encode([state["docs"][i] for i in ids], cfg["k"], cfg["b"],
                  ctx.seed32)
    got, missing = stored_rows(state["root"], ids, want.shape[1])
    bad = int(np.sum(np.any(got != want, axis=1)))
    checks = [harness.Check("rows_differing", bad, 0),
              harness.Check("rows_missing", missing, 0)]
    return checks + harness.arm_checks(state["arms"],
                               ctx.traffic.get("kernel_arms", {}))

