"""Pallas TPU kernel: One Permutation Hashing bin minima (OPH subsystem).

The k-permutation kernel (`repro.kernels.minhash`) streams every
nonzero k/BK times — once per hash-block of grid dim 1 — and runs a
full fmix32 per (nonzero, hash) pair: O(k·nnz) hash arithmetic.  OPH
(arXiv:1208.1259) needs ONE hash per nonzero; this kernel therefore has
no hash-block grid dimension at all:

  * documents  → sublane-tiled grid dim 0 (BN rows),
  * nonzeros   → grid dim 1, streamed HBM→VMEM in MC-column blocks
                 (each nonzero is read ONCE),
  * bins       → all k live in lanes of a VMEM scratch block, revisited
                 across grid dim 1 with a running min.

Scatter-min into k lanes is TPU-hostile as a true scatter, so it is
done the VPU way: broadcast-compare the bin id of each nonzero against
a k-lane iota and select-min — 3 cheap VPU ops per lane versus a ~10-op
fmix32 re-evaluation per lane in the minwise kernel, on top of the k/BK×
fewer HBM reads of the index stream.

VMEM working set per step: BN·MC (indices) + BN·MC·K (compare/select)
≈ 8·256·256·4 B ≈ 2 MiB at k=256 — inside the ~16 MiB/core budget.
k must be a power of two (bin = top log2(k) bits of the hash) and is
padded to the 128-lane boundary; padded lanes never match a bin id and
fall off at the final slice.

This kernel returns the raw uint32 minima (n·k·4 bytes to the host).
The preprocessing hot path uses ``repro.kernels.fused_encode``'s
``oph_pack_pallas`` instead, which shares this kernel's grid and
scatter-min body (``_oph_block_min``) but densifies, b-bit-masks and byte-packs in the
final grid step so only n·ceil(k·b/8) bytes leave the device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.universal_hash import _fmix32
from repro.kernels.minhash import ORDERED_MAX, _ordered, _unordered, \
    _valid_cols


def _oph_block_min(a_ref, b_ref, idx_ref, nnz_ref, c, *, mc: int,
                   shift: int, kp: int):
    """Ordered (BN, KP) bin minima of one (BN, MC) nonzero block: hash
    once, then the lane-parallel scatter-min
    out[n, j] = min over m with bins[n, m] == j."""
    idx = idx_ref[...].astype(jnp.uint32)            # (BN, MC)
    bn = idx.shape[0]
    valid = _valid_cols(nnz_ref, bn, mc, c)
    h = _fmix32(a_ref[0, 0] * idx + b_ref[0, 0])     # ONE hash per nonzero
    bins = (h >> jnp.uint32(shift)).astype(jnp.int32)
    hv = jnp.where(valid, _ordered(h), jnp.int32(ORDERED_MAX))
    lane = jax.lax.broadcasted_iota(jnp.int32, (bn, mc, kp), 2)
    scat = jnp.where(bins[:, :, None] == lane, hv[:, :, None],
                     jnp.int32(ORDERED_MAX))
    return jnp.min(scat, axis=1)


def _oph_kernel(a_ref, b_ref, idx_ref, nnz_ref, out_ref, acc_ref, *,
                mc: int, shift: int, kp: int, nc: int):
    """One (doc-block, nnz-block) grid step: hash once, min-scatter."""
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, ORDERED_MAX)

    acc_ref[...] = jnp.minimum(
        acc_ref[...], _oph_block_min(a_ref, b_ref, idx_ref, nnz_ref, c,
                                     mc=mc, shift=shift, kp=kp))

    @pl.when(c == nc - 1)
    def _finish():
        out_ref[...] = _unordered(acc_ref[...])


@functools.partial(
    jax.jit,
    static_argnames=("k", "block_n", "block_m", "interpret"),
)
def oph_pallas(
    indices: jax.Array,
    nnz: jax.Array,
    a: jax.Array,
    b: jax.Array,
    *,
    k: int,
    block_n: int = 8,
    block_m: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """uint32 (n, k) OPH bin minima of each row's first nnz[i] indices.

    Empty bins hold 0xFFFFFFFF (densification / zero-coding is a cheap
    O(n·k) post-pass in ``repro.core.oph``, outside the hot loop).

    Args:
      indices: int32 (n, m), contiguously padded rows.
      nnz:     int32 (n,) valid prefix length per row.
      a, b:    uint32 (1,) single multiply-shift params (a odd).
      k:       number of bins; power of two.
    """
    if k < 2 or (k & (k - 1)) != 0:
        raise ValueError(f"OPH kernel needs k = power of two, got {k}")
    shift = 32 - (int(k).bit_length() - 1)
    n, m = indices.shape
    bn = min(block_n, n)
    mc = min(block_m, m)
    kp = max(k, 128)                      # bins live in lanes

    def _pad_to(x, mult, axis):
        pad = (-x.shape[axis]) % mult
        if pad == 0:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        return jnp.pad(x, widths)

    idx_p = _pad_to(_pad_to(indices, bn, 0), mc, 1)
    nnz_p = _pad_to(nnz, bn, 0).reshape(-1, 1)
    np_, mp_ = idx_p.shape
    nc = mp_ // mc

    grid = (np_ // bn, nc)
    out = pl.pallas_call(
        functools.partial(_oph_kernel, mc=mc, shift=shift, kp=kp, nc=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, c: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i, c: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bn, mc), lambda i, c: (i, c)),
            pl.BlockSpec((bn, 1), lambda i, c: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, kp), lambda i, c: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, kp), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((bn, kp), jnp.int32)],
        interpret=interpret,
    )(a.reshape(1, 1), b.reshape(1, 1), idx_p, nnz_p)
    return out[:n, :k]
