"""Pallas TPU kernel: b-bit minwise hashing preprocessing (paper §6, Table 2).

The paper showed GPU hashing cuts preprocessing to <1/7 of data-loading
time.  TPU adaptation: the hot loop is k independent multiply-shift
hashes + a min-reduction over each document's nonzeros.  We map

  * documents   → sublane-tiled grid dim 0 (BN rows),
  * hash index  → 128-lane grid dim 1 (BK lanes; k lives in lanes so the
                  VPU evaluates 128 hash functions per cycle),
  * nonzeros    → innermost grid dim 2, streamed HBM→VMEM in MC-column
                  blocks with a running min accumulated in the output
                  block (revisited across grid dim 2).

VMEM working set per step: BN·MC (indices) + BN·MC·BK (hash values)
≈ 8·256·128·4 B ≈ 1 MiB — well inside the ~16 MiB/core budget, with
MXU-free pure-VPU arithmetic (uint32 mul/add/xor/shift/min).

This kernel returns the raw uint32 minima (n·k·4 bytes to the host).
The preprocessing hot path uses ``repro.kernels.fused_encode``'s
``minhash_pack_pallas`` instead, which shares this hash loop (and
``_minhash_block_min``) but emits packed
b-bit bytes in the final nnz grid step — n·ceil(k·b/8) bytes off the
device instead of n·k·4.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.universal_hash import _fmix32

# Mosaic has no unsigned min or reduction, so minima run over the
# order-preserving int32 image of each uint32 hash: flip the sign bit.
# The empty sentinel 0xFFFFFFFF maps to ORDERED_MAX.
ORDERED_MAX = 0x7FFFFFFF


def _ordered(h):
    """uint32 → int32 with the same order."""
    return jax.lax.bitcast_convert_type(h ^ jnp.uint32(0x80000000),
                                        jnp.int32)


def _unordered(x):
    """Inverse of ``_ordered``."""
    return (jax.lax.bitcast_convert_type(x, jnp.uint32)
            ^ jnp.uint32(0x80000000))


def _valid_cols(nnz_ref, bn: int, mc: int, c):
    """(BN, MC) mask of the nonzeros of grid block ``c`` inside each
    row's prefix; ``nnz_ref`` is a (BN, 1) block."""
    col = c * mc + jax.lax.broadcasted_iota(jnp.int32, (bn, mc), 1)
    return col < nnz_ref[...]


def _minhash_block_min(idx_ref, nnz_ref, a_ref, b_ref, c, mc: int):
    """Ordered (BN, BK) minima of the block's hash functions over one
    (BN, MC) nonzero block; ``a_ref``/``b_ref`` are (1, BK) blocks."""
    idx = idx_ref[...].astype(jnp.uint32)            # (BN, MC)
    a = a_ref[...]                                   # (1, BK)
    b = b_ref[...]
    valid = _valid_cols(nnz_ref, idx.shape[0], mc, c)
    h = _ordered(_fmix32(a[:, None, :] * idx[:, :, None] + b[:, None, :]))
    # mask by max against an int32 floor (Mosaic cannot lift a bool
    # mask to 3-D): INT32_MIN keeps a valid hash, ORDERED_MAX drops it
    floor = jnp.where(valid, jnp.int32(-(1 << 31)), jnp.int32(ORDERED_MAX))
    return jnp.min(jnp.maximum(h, floor[:, :, None]), axis=1)   # (BN, BK)


def _minhash_kernel(idx_ref, nnz_ref, a_ref, b_ref, out_ref, acc_ref, *,
                    mc: int, nc: int):
    """One (doc-block, hash-block, nnz-block) grid step."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, ORDERED_MAX)

    acc_ref[...] = jnp.minimum(
        acc_ref[...], _minhash_block_min(idx_ref, nnz_ref, a_ref, b_ref,
                                         c, mc))

    @pl.when(c == nc - 1)
    def _finish():
        out_ref[...] = _unordered(acc_ref[...])


@functools.partial(
    jax.jit,
    static_argnames=("block_n", "block_k", "block_m", "interpret"),
)
def minhash_pallas(
    indices: jax.Array,
    nnz: jax.Array,
    a: jax.Array,
    b: jax.Array,
    *,
    block_n: int = 8,
    block_k: int = 128,
    block_m: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """uint32 (n, k) min-hashes of each row's first nnz[i] indices.

    Args:
      indices: int32 (n, m), contiguously padded rows.
      nnz:     int32 (n,) valid prefix length per row.
      a, b:    uint32 (k,) multiply-shift params (a odd).
    """
    n, m = indices.shape
    k = a.shape[0]
    bn = min(block_n, n)
    bk = min(block_k, k)
    mc = min(block_m, m)

    def _pad_to(x, mult, axis, value):
        pad = (-x.shape[axis]) % mult
        if pad == 0:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        return jnp.pad(x, widths, constant_values=value)

    idx_p = _pad_to(_pad_to(indices, bn, 0, 0), mc, 1, 0)
    nnz_p = _pad_to(nnz, bn, 0, 0).reshape(-1, 1)
    a_p = _pad_to(a, bk, 0, jnp.uint32(1)).reshape(1, -1)
    b_p = _pad_to(b, bk, 0, jnp.uint32(0)).reshape(1, -1)
    np_, mp_ = idx_p.shape
    kp_ = a_p.shape[1]
    nc = mp_ // mc

    grid = (np_ // bn, kp_ // bk, nc)
    out = pl.pallas_call(
        functools.partial(_minhash_kernel, mc=mc, nc=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, mc), lambda i, j, c: (i, c)),
            pl.BlockSpec((bn, 1), lambda i, j, c: (i, 0)),
            pl.BlockSpec((1, bk), lambda i, j, c: (0, j)),
            pl.BlockSpec((1, bk), lambda i, j, c: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, bk), lambda i, j, c: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, kp_), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((bn, bk), jnp.int32)],
        interpret=interpret,
    )(idx_p, nnz_p, a_p, b_p)
    return out[:n, :k]
