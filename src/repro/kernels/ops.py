"""Public jit'd wrappers around the Pallas kernels with jnp fallbacks.

Dispatch policy (cost-model driven, see docs/DESIGN.md §2):
  * ``minhash``      — kernel always (pure VPU streaming).
  * ``oph``          — kernel always (single-pass scatter-min; k must be
                       a power of two — the core jnp path covers the
                       rest).
  * ``bbit_linear``  — kernel for 2^b ≤ BBIT_KERNEL_MAX_V (one-hot MXU
                       contraction streams the table at line rate);
                       XLA gather for larger b.  The packed variant
                       also takes b = 16 on its factored kernels.
                       custom_vjp wires the backward kernel in.
  * ``vw_sketch``    — kernel for power-of-two buckets, jnp otherwise.

On non-TPU backends (this CPU container) the wrappers run the kernels
in interpret mode when ``interpret=None`` (auto) — the same code path a
TPU deployment exercises, minus Mosaic lowering.

Every branch here is a thin client of ``perf.choose`` — the measured
cost-model dispatch layer.  Without a loaded profile the choices are
bit-identical to the historical static policy; with one, each
(op, shape-bucket) picks whichever arm actually measured faster.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.minhash import minhash_pallas
from repro.kernels.oph import oph_pallas
from repro.kernels.fused_encode import (
    PACK_BITS,
    minhash_pack_pallas,
    oph_pack_pallas,
)
from repro.kernels.bbit_linear import (
    bbit_linear_fwd_pallas,
    bbit_linear_bwd_dw_pallas,
    bbit_linear_packed_fwd_pallas,
    bbit_linear_packed_bwd_dw_pallas,
)
from repro.kernels.hamming import (
    hamming_distance_pallas,
    hamming_distance_xla,
)
from repro.kernels.vw_sketch import vw_sketch_pallas
from repro import perf
from repro.perf import BBIT_KERNEL_MAX_V  # canonical home is perf; noqa


def _auto_interpret(interpret: Optional[bool]) -> bool:
    """An explicit ``interpret`` is a request, not an order: it goes
    through the same eligibility filter, so a TPU always compiles and
    other backends always interpret."""
    impl = None if interpret is None else (
        "interpret" if interpret else "compiled")
    return perf.choose("pallas_mode", impl=impl) != "compiled"


# ---------------------------------------------------------------------------
def minhash(indices, nnz, a, b, *, interpret: Optional[bool] = None):
    """uint32 (n, k) min-hashes (kernel-backed)."""
    return minhash_pallas(indices, nnz, a, b,
                          interpret=_auto_interpret(interpret))


def minhash_bbit(indices, nnz, a, b, bits: int,
                 *, interpret: Optional[bool] = None):
    """Fused min-hash + b-bit extraction → uint16 (n, k) codes."""
    z = minhash(indices, nnz, a, b, interpret=interpret)
    return (z & jnp.uint32((1 << bits) - 1)).astype(jnp.uint16)


def oph(indices, nnz, a, b, k: int, *, interpret: Optional[bool] = None):
    """uint32 (n, k) OPH bin minima (kernel-backed; k = power of two).

    Single hash pass over the nonzeros — the k×-cheaper preprocessing
    scheme.  Empty bins hold 0xFFFFFFFF; densify / zero-code via
    ``repro.core.oph``.
    """
    return oph_pallas(indices, nnz, a, b, k=k,
                      interpret=_auto_interpret(interpret))


# ---------------------------------------------------------------------------
def fused_pack_supported(bits: int) -> bool:
    """Fused hash→b-bit→pack kernels need codes that never straddle a
    byte boundary (b ∈ {1, 2, 4, 8}); other b pack on-device via XLA
    (``core.bbit.pack_codes_jnp``)."""
    return bits in PACK_BITS


def fused_encode_on_device(bits: int, *, scheme: Optional[str] = None,
                           k: Optional[int] = None,
                           rows: Optional[int] = None,
                           nnz: Optional[int] = None,
                           impl: Optional[str] = None) -> bool:
    """THE dispatch predicate for the fused encode kernels — now a thin
    client of ``perf.choose("encode_packed", ...)``.
    ``schemes.encode_packed_device`` (offline preprocessing) and
    ``schemes.encode_packed_jit`` (the serving engine's jitted
    encode→score pass) both branch on it, so the serving hot path can
    never diverge from the preprocessing dispatch policy.  Without a
    profile this reproduces the old static predicate exactly: TPU
    backend AND byte-aligned b (interpret-mode Pallas on CPU would
    crawl; XLA covers it)."""
    shape = {"b": int(bits)}
    if scheme is not None:
        shape["scheme"] = scheme
    if k is not None:
        shape["k"] = int(k)
    if rows is not None:
        shape["rows"] = int(rows)
    if nnz is not None:
        shape["nnz"] = int(nnz)
    return perf.choose("encode_packed", shape, impl=impl) == "pallas"


def minhash_packed(indices, nnz, a, b, bits: int,
                   *, interpret: Optional[bool] = None):
    """Fused min-hash + b-bit + pack → uint8 (n, ceil(k·bits/8)).

    Only the packed bytes leave the device — 1/(32/bits) of the
    ``minhash_bbit`` host↔device traffic.
    """
    return minhash_pack_pallas(indices, nnz, a, b, bits=bits,
                               interpret=_auto_interpret(interpret))


def oph_packed(indices, nnz, a, b, k: int, bits: int, *,
               densify: bool = True,
               interpret: Optional[bool] = None):
    """Fused OPH + densify/zero-code + b-bit + pack.

    Returns (packed uint8 (n, ceil(k·bits/8)), empty uint8 (n,
    ceil(k/8)) — the np.packbits empty-bin bitmask, meaningful for the
    zero-coded variant).
    """
    return oph_pack_pallas(indices, nnz, a, b, k=k, bits=bits,
                           densify=densify,
                           interpret=_auto_interpret(interpret))


# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def bbit_linear(codes: jax.Array, weights: jax.Array,
                interpret: Optional[bool] = None) -> jax.Array:
    """logits (n, C) = Σ_j W[j, codes[n,j], :] — differentiable in W."""
    return _bbit_linear_fwd_impl(codes, weights, interpret)


def _bbit_linear_fwd_impl(codes, weights, interpret):
    v = weights.shape[1]
    if v <= BBIT_KERNEL_MAX_V:
        return bbit_linear_fwd_pallas(
            codes.astype(jnp.int32), weights,
            interpret=_auto_interpret(interpret))
    return ref.bbit_linear_fwd(codes, weights)


def _bbit_linear_vjp_fwd(codes, weights, interpret):
    return _bbit_linear_fwd_impl(codes, weights, interpret), (codes, weights)


def _bbit_linear_vjp_bwd(interpret, res, dout):
    codes, weights = res
    v = weights.shape[1]
    shape = {"v": v, "k": codes.shape[1], "rows": codes.shape[0]}
    if perf.choose("logits_bwd", shape) == "kernel":
        dw = bbit_linear_bwd_dw_pallas(
            codes.astype(jnp.int32), dout.astype(jnp.float32), v,
            interpret=_auto_interpret(interpret))
    else:
        dw = ref.bbit_linear_bwd_dw(codes, dout, v)
    return (None, dw.astype(weights.dtype))


bbit_linear.defvjp(_bbit_linear_vjp_fwd, _bbit_linear_vjp_bwd)


# ---------------------------------------------------------------------------
def packed_kernel_supported(bits: int, v: int) -> bool:
    """Whether the packed-input kernels handle (b=bits, V=v): codes
    that never straddle a byte (b ∈ {1, 2, 4, 8}) with V within
    MAX_V, where the one-hot is built whole, or b = 16, where a lane
    one-hot and a row match built from the code's two bytes carry it
    (the factored arm).  Other b take the XLA unpack path.  The same predicate as ``perf``'s eligibility of
    ``logits_packed``, which models.linear dispatches on, so the two
    cannot diverge."""
    return perf.packed_kernel_fits(bits, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _bbit_linear_packed(k, bits, interpret, packed, empty, weights):
    return _bbit_linear_packed_fwd_impl(k, bits, interpret, packed,
                                        empty, weights)


def _bbit_linear_packed_fwd_impl(k, bits, interpret, packed, empty,
                                 weights):
    if packed_kernel_supported(bits, weights.shape[1]):
        return bbit_linear_packed_fwd_pallas(
            packed, weights, k=k, bits=bits, empty=empty,
            interpret=_auto_interpret(interpret))
    return ref.bbit_linear_packed_fwd(packed, weights, k, bits,
                                      empty=empty)


def _bbit_linear_packed_vjp_fwd(k, bits, interpret, packed, empty,
                                weights):
    out = _bbit_linear_packed_fwd_impl(k, bits, interpret, packed, empty,
                                       weights)
    return out, (packed, empty, weights)


def _bbit_linear_packed_vjp_bwd(k, bits, interpret, res, dout):
    packed, empty, weights = res
    v = weights.shape[1]
    shape = {"v": v, "k": k, "b": bits, "rows": packed.shape[0]}
    if perf.choose("logits_packed_bwd", shape) == "kernel":
        dw = bbit_linear_packed_bwd_dw_pallas(
            packed, dout.astype(jnp.float32), v, k=k, bits=bits,
            empty=empty, interpret=_auto_interpret(interpret))
    else:
        dw = ref.bbit_linear_packed_bwd_dw(packed, dout, v, k, bits,
                                           empty=empty)
    return (None, None, dw.astype(weights.dtype))


_bbit_linear_packed.defvjp(_bbit_linear_packed_vjp_fwd,
                           _bbit_linear_packed_vjp_bwd)


def bbit_linear_packed(packed: jax.Array, weights: jax.Array, k: int,
                       bits: int, *, empty: Optional[jax.Array] = None,
                       interpret: Optional[bool] = None) -> jax.Array:
    """logits (n, C) straight from PACKED uint8 rows — differentiable
    in W; the (n, k) int32 code matrix never materializes on the
    kernel path (in-register unpack, see bbit_linear.py).

    ``empty`` (uint8 (n, ceil(k/8)), np.packbits layout) is the
    ``oph_zero`` empty-bin bitmask: marked bins contribute nothing in
    either direction.  Integer inputs carry no gradient; the vjp
    returns dW only.
    """
    return _bbit_linear_packed(k, bits, interpret, packed, empty, weights)


# ---------------------------------------------------------------------------
def hamming_topk(query, cands, *, k: int, bits: int, topk: int,
                 impl: Optional[str] = None,
                 interpret: Optional[bool] = None):
    """Top-k nearest candidates by packed-code Hamming similarity.

    ``query`` uint8 (w,), ``cands`` uint8 (n, w) — packed b-bit code
    rows (``core.bbit`` layout, w = ceil(k·bits/8)).  Returns
    (idx int32 (t,), sims f32 (t,)) with t = min(topk, n), sims sorted
    descending: sim = 1 − popcount_dist/(k·bits), the fraction of
    agreeing code bits.  Distance arm routed through
    ``perf.choose("hamming_topk")`` — Pallas SWAR popcount vs XLA
    ``population_count`` (bit-identical integers, so the choice can
    never change results).
    """
    n = int(cands.shape[0])
    shape = {"b": int(bits), "k": int(k), "rows": n,
             "width": int(cands.shape[1])}
    if perf.choose("hamming_topk", shape, impl=impl) == "pallas":
        dist = hamming_distance_pallas(query, cands,
                                       interpret=_auto_interpret(interpret))
    else:
        dist = hamming_distance_xla(query, cands)
    t = min(int(topk), n)
    neg, idx = jax.lax.top_k(-dist, t)
    sims = 1.0 + neg.astype(jnp.float32) / jnp.float32(k * bits)
    return idx, sims


# ---------------------------------------------------------------------------
def vw_sketch(indices, values, nnz, m_buckets: int, seed: int = 0,
              *, interpret: Optional[bool] = None):
    """f32 (n, m) VW sketch (kernel for pow-2 m, jnp fallback otherwise)."""
    if m_buckets & (m_buckets - 1) == 0:
        return vw_sketch_pallas(indices, values, nnz, m_buckets, seed,
                                interpret=_auto_interpret(interpret))
    return ref.vw_sketch(indices, values, nnz, m_buckets, seed)
