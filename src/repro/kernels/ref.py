"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth the kernels are validated
against (tests/test_kernels.py sweeps shapes/dtypes and asserts
allclose / exact equality in interpret mode).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.universal_hash import _fmix32

UINT32_MAX = jnp.uint32(0xFFFFFFFF)


def minhash(indices: jax.Array, nnz: jax.Array, a: jax.Array,
            b: jax.Array) -> jax.Array:
    """Min of fmix32(a_j·t + b_j) over each row's first nnz indices.

    indices: int32 (n, m) contiguously padded; nnz: int32 (n,);
    a, b: uint32 (k,).  Returns uint32 (n, k).
    """
    m = indices.shape[1]
    mask = jnp.arange(m, dtype=jnp.int32)[None, :] < nnz[:, None]
    tu = indices.astype(jnp.uint32)
    h = _fmix32(a[None, None, :] * tu[:, :, None] + b[None, None, :])
    h = jnp.where(mask[:, :, None], h, UINT32_MAX)
    return jnp.min(h, axis=1)


def pairwise_sum(x: jax.Array) -> jax.Array:
    """Σ over axis 1 by repeated halving.  Every add has two operands,
    so the bits do not depend on how XLA fuses or vectorizes the
    reduction: the same rows give the same sum in any jitted program."""
    while x.shape[1] > 1:
        h = x.shape[1] // 2
        x = jnp.concatenate([x[:, :h] + x[:, h:2 * h], x[:, 2 * h:]], axis=1)
    return x[:, 0]


def bbit_linear_fwd(codes: jax.Array, weights: jax.Array) -> jax.Array:
    """logits[n, c] = Σ_j W[j, codes[n, j], c].

    codes: int32 (n, k) in [0, 2^b);  weights: (k, 2^b, C) float.
    Returns (n, C) in weights.dtype's accumulation type (float32).
    """
    gathered = jnp.take_along_axis(
        weights[None],
        codes.astype(jnp.int32)[:, :, None, None],
        axis=2,
    )[:, :, 0, :]
    return pairwise_sum(gathered.astype(jnp.float32))


def _scatter_dw(codes: jax.Array, dout: jax.Array, vsize: int,
                keep: jax.Array = None) -> jax.Array:
    """Σ_n dout[n, c] into dW[j, codes[n, j], c] (where ``keep``): a
    scatter-add, so no (n, k, V) one-hot exists even at V = 2^16."""
    n, k = codes.shape
    d = dout.astype(jnp.float32)
    upd = jnp.broadcast_to(d[:, None, :], (n, k, d.shape[1]))
    if keep is not None:
        upd = jnp.where(keep[:, :, None], upd, 0.0)
    j = jnp.broadcast_to(jnp.arange(k)[None, :], (n, k))
    return jnp.zeros((k, vsize, d.shape[1]), jnp.float32).at[
        j, codes.astype(jnp.int32)].add(upd)


def bbit_linear_bwd_dw(codes: jax.Array, dout: jax.Array,
                       vsize: int) -> jax.Array:
    """dW[j, v, c] = Σ_n 1{codes[n,j]=v}·dout[n,c].  Returns (k, V, C) f32."""
    return _scatter_dw(codes, dout, vsize)


def bbit_linear_packed_fwd(packed: jax.Array, weights: jax.Array,
                           k: int, bits: int,
                           empty: jax.Array = None) -> jax.Array:
    """Packed-input oracle: unpack (XLA) → gather → mask → sum.

    packed: uint8 (n, ceil(k·bits/8)) in the ``core.bbit.pack_codes``
    layout; empty: uint8 (n, ceil(k/8)) packbits bitmask or None.
    Semantic ground truth for the packed Pallas kernels AND the non-TPU
    fallback ops.py dispatches to — the widened (n, k) matrix exists
    here only as a fused in-step temporary.
    """
    from repro.core.bbit import unpack_codes_jnp, unpack_mask_jnp

    codes = unpack_codes_jnp(packed, k, bits).astype(jnp.int32)
    gathered = jnp.take_along_axis(
        weights[None], codes[:, :, None, None], axis=2,
    )[:, :, 0, :].astype(jnp.float32)
    if empty is not None:
        mask = unpack_mask_jnp(empty, k)
        gathered = jnp.where(mask[:, :, None], 0.0, gathered)
    return pairwise_sum(gathered)


def bbit_linear_packed_bwd_dw(packed: jax.Array, dout: jax.Array,
                              vsize: int, k: int, bits: int,
                              empty: jax.Array = None) -> jax.Array:
    """dW[j, v, c] = Σ_n 1{codes[n,j]=v ∧ ¬empty[n,j]}·dout[n,c]."""
    from repro.core.bbit import unpack_codes_jnp, unpack_mask_jnp

    codes = unpack_codes_jnp(packed, k, bits).astype(jnp.int32)
    keep = None if empty is None else ~unpack_mask_jnp(empty, k)
    return _scatter_dw(codes, dout, vsize, keep)


def vw_sketch(indices: jax.Array, values: jax.Array, nnz: jax.Array,
              m_buckets: int, seed: int) -> jax.Array:
    """Signed feature hashing into m buckets (paper Eq. 14), f32 (n, m).

    Bucket/sign streams must match the kernel bit-for-bit:
      hb = fmix32(i·0x9E3779B1 + (2·seed+1));  bucket = hb & (m-1)
      hs = fmix32(i ^ (0x7FEB352D + seed));    sign = ±1 from bit 31
    """
    n, mx = indices.shape
    mask = jnp.arange(mx, dtype=jnp.int32)[None, :] < nnz[:, None]
    iu = indices.astype(jnp.uint32)
    hb = _fmix32(iu * jnp.uint32(0x9E3779B1) + jnp.uint32(2 * seed + 1))
    hs = _fmix32(iu ^ jnp.uint32(0x7FEB352D + seed))
    bucket = (hb & jnp.uint32(m_buckets - 1)).astype(jnp.int32)
    sign = jnp.where((hs >> jnp.uint32(31)) & 1 == 1, 1.0, -1.0)
    contrib = jnp.where(mask, values * sign, 0.0)
    out = jnp.zeros((n, m_buckets), dtype=jnp.float32)
    rows = jnp.broadcast_to(jnp.arange(n)[:, None], indices.shape)
    return out.at[rows, bucket].add(contrib)
