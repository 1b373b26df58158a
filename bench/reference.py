"""Plain references the benchmark compares the timed path against.

Written from the published descriptions and the archive format, with
nothing imported from the program: b-bit minwise hashing over the
multiply-shift (2-universal) family with a murmur finaliser, densified
one permutation hashing (arXiv:1208.1259, rotation densification of
arXiv:1406.4784), the LSB-first bit packing of the shard format, the
gather form of the linear model over the one-hot expansion, and one
streaming job of AdamW with Polyak tail averaging in float32.

Each function that has a control takes ``control=True``: the same
computation one step below the precision or the guarantee that the
configuration states (bfloat16 arithmetic in the model, documents cut to
their first ``TRUNCATE`` ids in hashing).  A sound program never reads as
far from the reference as the control does.
"""
from __future__ import annotations

import math
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

UINT32_MAX = np.uint32(0xFFFFFFFF)
ROT_C = 0x9E3779B1          # rotation offset of densified OPH
TRUNCATE = 4096             # the control's cut of every document


# ---------------------------------------------------------------- hashing --
def _seed_rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(seed))


def minwise_params(k: int, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """k multiply-shift pairs (a odd, b) as the seed draws them."""
    rng = _seed_rng(seed)
    a = (rng.integers(0, 1 << 32, size=k, dtype=np.uint64) | 1).astype(
        np.uint32)
    b = rng.integers(0, 1 << 32, size=k, dtype=np.uint64).astype(np.uint32)
    return a, b


def oph_params(seed: int) -> Tuple[int, int]:
    """The single (a odd, b) pair of one permutation hashing."""
    rng = _seed_rng(seed)
    a = int(rng.integers(0, 1 << 32, dtype=np.uint64) | 1)
    b = int(rng.integers(0, 1 << 32, dtype=np.uint64))
    return a, b


def fmix32(h: np.ndarray) -> np.ndarray:
    h = h.astype(np.uint32)
    h = h ^ (h >> np.uint32(16))
    h = (h * np.uint32(0x85EBCA6B)).astype(np.uint32)
    h = h ^ (h >> np.uint32(13))
    h = (h * np.uint32(0xC2B2AE35)).astype(np.uint32)
    return h ^ (h >> np.uint32(16))


def fold_ids(doc: np.ndarray) -> np.ndarray:
    """Feature ids as the hash sees them: folded into [0, 2^31)."""
    return (np.asarray(doc, dtype=np.int64) & ((1 << 31) - 1)).astype(
        np.uint32)


def pack_codes(codes: np.ndarray, b: int) -> np.ndarray:
    """(n, k) codes < 2^b → uint8 (n, ceil(k·b/8)): a row-major
    bitstream, least significant bit first within each byte."""
    n, k = codes.shape
    bits = ((codes.astype(np.uint32)[:, :, None]
             >> np.arange(b, dtype=np.uint32)) & 1).astype(np.uint8)
    flat = bits.reshape(n, k * b)
    return np.packbits(flat, axis=1, bitorder="little")


def minwise_minima(doc: np.ndarray, a: np.ndarray, b: np.ndarray,
                   chunk: int = 1 << 14) -> np.ndarray:
    """min over the document's ids of fmix32(a_j·t + b_j), per j."""
    t = fold_ids(doc)
    z = np.full(len(a), UINT32_MAX, dtype=np.uint32)
    for lo in range(0, len(t), chunk):
        tc = t[lo:lo + chunk, None]
        h = fmix32((a[None, :] * tc + b[None, :]).astype(np.uint32))
        z = np.minimum(z, h.min(axis=0))
    return z


def minwise_packed(docs: Sequence[np.ndarray], k: int, b: int, seed: int,
                   control: bool = False) -> np.ndarray:
    """b-bit minwise codes of each document, packed."""
    a, bb = minwise_params(k, seed)
    codes = np.empty((len(docs), k), dtype=np.uint32)
    for i, d in enumerate(docs):
        d = d[:TRUNCATE] if control else d
        codes[i] = minwise_minima(d, a, bb) & np.uint32((1 << b) - 1)
    return pack_codes(codes, b)


def oph_minima(doc: np.ndarray, a: int, b: int, k: int) -> np.ndarray:
    """Per-bin minima of h = fmix32(a·t + b); the bin is h's top
    log2(k) bits.  Empty bins hold UINT32_MAX."""
    h = fmix32((np.uint32(a) * fold_ids(doc) + np.uint32(b)).astype(
        np.uint32))
    bins = (h >> np.uint32(32 - (k.bit_length() - 1))).astype(np.int64)
    vals = np.full(k, UINT32_MAX, dtype=np.uint32)
    np.minimum.at(vals, bins, h)
    return vals


def densify(vals: np.ndarray) -> np.ndarray:
    """Rotation densification: an empty bin takes the nearest non-empty
    bin to its right (circularly) plus distance · ROT_C."""
    k = len(vals)
    full = vals != UINT32_MAX
    if full.all() or not full.any():
        return vals
    out = vals.copy()
    for j in np.flatnonzero(~full):
        for dist in range(1, k):
            src = (j + dist) % k
            if full[src]:
                out[j] = np.uint32((int(vals[src]) + dist * ROT_C)
                                   & 0xFFFFFFFF)
                break
    return out


def oph_packed(docs: Sequence[np.ndarray], k: int, b: int, seed: int,
               control: bool = False) -> np.ndarray:
    """Densified OPH b-bit codes of each document, packed."""
    a, bb = oph_params(seed)
    codes = np.empty((len(docs), k), dtype=np.uint32)
    for i, d in enumerate(docs):
        d = d[:TRUNCATE] if control else d
        codes[i] = densify(oph_minima(d, a, bb, k)) & np.uint32(
            (1 << b) - 1)
    return pack_codes(codes, b)


def unpack_codes(packed: np.ndarray, k: int, b: int) -> np.ndarray:
    bits = np.unpackbits(packed, axis=1, bitorder="little")[:, :k * b]
    w = (1 << np.arange(b, dtype=np.uint32))
    return (bits.reshape(len(packed), k, b) * w).sum(axis=2).astype(
        np.int64)


# ------------------------------------------------------------ the model --
def scores(table: np.ndarray, bias: np.ndarray, codes: np.ndarray,
           control: bool = False) -> np.ndarray:
    """Binary margin sum_j table[j, code_j, 0] + bias[0], in float32
    (bfloat16 table and sums for the control)."""
    import jax
    import jax.numpy as jnp
    dt = jnp.bfloat16 if control else jnp.float32
    t = jnp.asarray(table, jnp.float32).astype(dt)[:, :, 0]
    g = t[jnp.arange(t.shape[0])[None, :], jnp.asarray(codes)]
    out = jnp.sum(g, axis=1, dtype=dt) + jnp.asarray(bias, dt)[0]
    return np.asarray(jax.device_get(out.astype(jnp.float32)))


# ------------------------------------------------------ streaming jobs --
def shard_bounds(n: int, shards: int) -> List[Tuple[int, int]]:
    """Row ranges of the archive's shards: contiguous, ceil(n/shards)
    rows each, the last one short."""
    per = -(-n // shards)
    return [(s * per, min(n, (s + 1) * per)) for s in range(shards)
            if s * per < n]


def _perm(entropy: tuple, n: int) -> np.ndarray:
    return np.random.default_rng(np.random.SeedSequence(entropy)) \
        .permutation(n)


def job_steps(n: int, shards: int, batch: int, seed: int,
              world: int = 1) -> Iterator[List[np.ndarray]]:
    """The row ids of each step of one pass: shards in a seeded order,
    rows in a seeded order within each shard, ``batch`` rows per slot.
    With ``world`` > 1 consecutive groups of ``world`` shards run side by
    side, one per slot, for as many steps as the longest needs."""
    bounds = shard_bounds(n, shards)
    order = _perm((seed, 0), len(bounds))
    for g in range(0, len(order), world):
        group = [int(s) for s in order[g:g + world]]
        rows = []
        for s in group:
            lo, hi = bounds[s]
            rows.append(lo + _perm((seed, 0, s), hi - lo))
        for t in range(max(-(-len(r) // batch) for r in rows)):
            yield [r[t * batch:(t + 1) * batch] for r in rows]


def run_job(codes: np.ndarray, labels: np.ndarray, *, k: int, b: int,
            shards: int, batch: int, seed: int, lr: float, l2: float,
            avg_start_frac: float, world: int = 1,
            control: bool = False, steps: Optional[int] = None,
            fault: Optional[str] = None) -> Dict:
    """One streaming pass of AdamW (b1 0.9, b2 0.999, eps 1e-8) on the
    mean logistic loss plus (l2/2)·|params|², over the union of each
    step's slots; Polyak averaging of the iterates after
    floor(avg_start_frac · steps).  Initial table 0.01·N(0, 1) from
    ``jax.random.key(seed)``, bias 0.  Returns the final iterate, the
    average, each step's loss, the first step's gradient, and the
    progressive hits (predictions before each update).

    ``fault`` breaks the job as a faulty program would: ``half_batch``
    takes each step's mean over the first half of every slot's rows,
    ``no_exchange`` updates from the first slot's rows alone (the
    gradient exchange between chips left out).  Rows seen are counted
    as the sound job counts them."""
    import jax
    import jax.numpy as jnp

    v = 1 << b
    dt = jnp.bfloat16 if control else jnp.float32
    plan = list(job_steps(len(codes), shards, batch, seed, world))
    if steps is not None:
        plan = plan[:steps]
    avg_start = int(math.floor(avg_start_frac * len(plan)))

    def loss_fn(params, c, y):
        t = params["table"].astype(dt)[:, :, 0]
        g = t[jnp.arange(k)[None, :], c]
        z = (jnp.sum(g, axis=1, dtype=dt).astype(jnp.float32)
             + params["bias"][0])
        m = (2.0 * y.astype(jnp.float32) - 1.0) * z
        loss = jnp.mean(jnp.logaddexp(0.0, -m))
        reg = sum(jnp.sum(p ** 2) for p in jax.tree.leaves(params))
        return loss + 0.5 * l2 * reg, jnp.sum((z > 0) == (y == 1))

    @jax.jit
    def step(state, c, y, active):
        params, m, vv, t, avg, cnt = state
        (loss, hits), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, c, y)
        t1 = t + 1.0
        c1, c2 = 1.0 - 0.9 ** t1, 1.0 - 0.999 ** t1
        m = jax.tree.map(lambda a, gi: 0.9 * a + 0.1 * gi, m, g)
        vv = jax.tree.map(lambda a, gi: 0.999 * a + 0.001 * gi * gi, vv, g)
        params = jax.tree.map(
            lambda p, a, s: p - lr * ((a / c1) / (jnp.sqrt(s / c2) + 1e-8)),
            params, m, vv)
        cnt = cnt + active
        avg = jax.tree.map(lambda a, p: a + active * (p - a)
                           / jnp.maximum(cnt, 1.0), avg, params)
        return (params, m, vv, t1, avg, cnt), loss, hits, g

    params = {"table": 0.01 * jax.random.normal(
        jax.random.key(seed), (k, v, 1), jnp.float32),
        "bias": jnp.zeros((1,), jnp.float32)}
    zeros = jax.tree.map(jnp.zeros_like, params)
    state = (params, zeros, zeros, jnp.float32(0.0), zeros,
             jnp.float32(0.0))
    losses, hits, first_grad = [], [], None
    shapes = set()
    for i, slots in enumerate(plan):
        if fault == "half_batch":
            slots = [s[:len(s) // 2] for s in slots]
        elif fault == "no_exchange":
            slots = slots[:1]
        elif fault is not None:
            raise ValueError(f"unknown fault {fault!r}")
        rows = np.concatenate(slots)
        shapes.add(len(rows))
        state, loss, h, g = step(state, jnp.asarray(codes[rows]),
                                 jnp.asarray(labels[rows]),
                                 jnp.float32(i >= avg_start))
        losses.append(loss)
        hits.append(h)
        if first_grad is None:
            first_grad = g
    params, _, _, _, avg, cnt = state
    return {"params": jax.device_get(params),
            "avg_params": jax.device_get(avg) if float(cnt) > 0 else None,
            "losses": np.asarray(jax.device_get(losses)),
            "first_grad": jax.device_get(first_grad),
            "hits": int(np.sum(jax.device_get(hits))),
            "seen": int(sum(len(r) for s in plan for r in s)),
            "steps": len(plan)}


def change_gaps(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray],
                init: Dict[str, np.ndarray],
                first_grad: Dict[str, np.ndarray]) -> Dict[str, float]:
    """Per leaf: |‖got − init‖ − ‖want − init‖| over the larger of
    ‖want − init‖ and the median leaf's.  Leaves whose first reference
    gradient is under a thousandth of the median leaf's are left out:
    they move by round-off alone."""
    gnorm = {n: float(np.linalg.norm(first_grad[n])) for n in want}
    gmed = float(np.median(list(gnorm.values())))
    moved = {n: float(np.linalg.norm(np.asarray(want[n], np.float64)
                                     - np.asarray(init[n], np.float64)))
             for n in want}
    med = float(np.median(list(moved.values())))
    out = {}
    for n in want:
        if gnorm[n] < 1e-3 * gmed:
            continue
        g = float(np.linalg.norm(np.asarray(got[n], np.float64)
                                 - np.asarray(init[n], np.float64)))
        out[n] = abs(g - moved[n]) / max(moved[n], med)
    return out
