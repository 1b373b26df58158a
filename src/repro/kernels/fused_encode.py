"""Pallas TPU kernels: fused hash → b-bit → pack encode pipeline.

The unfused pipeline (`kernels/minhash.py`, `kernels/oph.py`) ships the
full uint32 minima — n·k·4 bytes — back to the host, where b-bit
extraction (`core/bbit.py`) and numpy bit-packing run serially.  At the
paper's claimed throughput (§6 Table 2: GPU hashing ≪ data loading)
that host round-trip IS the pipeline; these kernels remove it by
emitting the on-disk representation directly:

  * the running min lives in a VMEM scratch accumulator, revisited
    across the nnz grid dimension (HBM traffic identical to the
    unfused kernels — each nonzero block is still read once);
  * on the FINAL nnz grid step the accumulator is finished in-register:
    b-bit mask (and for OPH, rotation densification or zero-coding),
    then 8/b codes packed per output byte — so only n·ceil(k·b/8)
    packed bytes ever leave the device instead of n·k·4.

Packing layout is bit-exact with ``core.bbit.pack_codes`` (row-major
bitstream, LSB-first within each byte): byte j of a row holds codes
j·(8/b) … (j+1)·(8/b)−1, code t at bit offset t·b.  Requires b ∈
{1, 2, 4, 8} so codes never straddle bytes (other b fall back to the
XLA path, ``core.bbit.pack_codes_jnp``).  The ``oph_zero`` variant
additionally packs the empty-bin bitmask MSB-first — the
``np.packbits`` layout the shard format stores.

In-kernel densification mirrors ``core.oph.densify_rotation``: the
next non-empty bin (circularly) and its minimum are found together by
log2(k) lane-rotate-and-select doubling steps, since neither a gather
nor a cumulative min lowers on Mosaic.  O(k·log k) work per row, done
ONCE per row versus O(k·nnz) in the main loop.

Mosaic has no strided lane slice either, so bytes are packed by one
exact bf16 matmul against a constant place-value matrix (every operand
and partial sum is an integer below 256).  Minima are kept as ordered
int32 (``minhash._ordered``): Mosaic has no unsigned min.  Per-row
``nnz`` is a (BN, 1) block and the minwise parameters (1, BK) blocks,
the layouts the TPU block rules accept.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.minhash import (
    ORDERED_MAX,
    _minhash_block_min,
    _unordered,
)
from repro.kernels.oph import _oph_block_min

PACK_BITS = (1, 2, 4, 8)   # b where codes never straddle byte bounds

# Rotation offset constant — must match core.oph._ROT_C bit-exactly.
_ROT_C = 0x9E3779B1


def _check_bits(bits: int) -> None:
    if bits not in PACK_BITS:
        raise ValueError(
            f"fused packing needs b ∈ {PACK_BITS}, got {bits}")


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _pack_lanes(vals, width: int, bits: int, *, msb_first: bool = False):
    """(BN, width·(8/bits)) ints < 2^bits → (BN, width) uint8.

    Byte j holds lanes j·r … j·r+r−1 (r = 8/bits): lane t at bit t·bits
    (LSB-first, ``pack_codes``) or, for bit masks, at bit 7−t
    (MSB-first, ``np.packbits``).  Lanes beyond the logical k must be
    zeroed by the caller so padding bits match the references.
    """
    v = vals.astype(jnp.int32)
    r = 8 // bits
    if r == 1:
        return v.astype(jnp.uint8)
    lanes = width * r
    lane = jax.lax.broadcasted_iota(jnp.int32, (lanes, width), 0)
    byte = jax.lax.broadcasted_iota(jnp.int32, (lanes, width), 1)
    t = lane & (r - 1)
    shift = (7 - t) if msb_first else t * bits
    place = jnp.where((lane >> (r.bit_length() - 1)) == byte,
                      jnp.left_shift(jnp.ones_like(shift), shift), 0)
    packed = jnp.dot(v.astype(jnp.float32).astype(jnp.bfloat16),
                     place.astype(jnp.float32).astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return packed.astype(jnp.int32).astype(jnp.uint8)


# ---------------------------------------------------------------------------
# Fused minwise: k-permutation min-hash → b-bit → packed bytes.
# ---------------------------------------------------------------------------
def _minhash_pack_kernel(idx_ref, nnz_ref, a_ref, b_ref, out_ref, acc_ref, *,
                         mc: int, bits: int, k: int, bk: int, nc: int):
    """One (doc-block, hash-block, nnz-block) grid step.

    Minima accumulate in VMEM scratch across grid dim 2; the final step
    masks to b bits, zeroes lanes ≥ k (param padding), and packs.
    """
    j = pl.program_id(1)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, ORDERED_MAX)

    acc_ref[...] = jnp.minimum(
        acc_ref[...], _minhash_block_min(idx_ref, nnz_ref, a_ref, b_ref,
                                         c, mc))

    @pl.when(c == nc - 1)
    def _finish():
        codes = _unordered(acc_ref[...]) & jnp.uint32((1 << bits) - 1)
        bn = codes.shape[0]
        lane = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bn, bk), 1)
        codes = jnp.where(lane < k, codes, jnp.uint32(0))
        out_ref[...] = _pack_lanes(codes, bk * bits // 8, bits)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "block_n", "block_k", "block_m", "interpret"),
)
def minhash_pack_pallas(
    indices: jax.Array,
    nnz: jax.Array,
    a: jax.Array,
    b: jax.Array,
    *,
    bits: int,
    block_n: int = 8,
    block_k: int = 128,
    block_m: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """uint8 (n, ceil(k·bits/8)) packed b-bit min-hash codes.

    Bit-identical to ``pack_codes(bbit_codes(minhash_pallas(...), bits),
    bits)`` — validated by tests/test_fused_encode.py — at 1/(32/bits)
    of the device→host traffic.

    Args:
      indices: int32 (n, m), contiguously padded rows.
      nnz:     int32 (n,) valid prefix length per row.
      a, b:    uint32 (k,) multiply-shift params (a odd).
      bits:    b ∈ {1, 2, 4, 8}.
    """
    _check_bits(bits)
    n, m = indices.shape
    k = a.shape[0]
    bn = min(block_n, n)
    # hash-block must be a multiple of 8 so each out byte is intra-block
    bk = _round_up(min(block_k, _round_up(k, 8)), 8)
    if bk < k and (bk * bits // 8) % 128:
        # a hash-block's packed bytes would not fill whole 128-lane
        # tiles: keep every hash function in one block instead
        bk = _round_up(k, 8)
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
    ob = bk * bits // 8                   # packed bytes per hash-block

    grid = (np_ // bn, kp_ // bk, nc)
    out = pl.pallas_call(
        functools.partial(_minhash_pack_kernel, mc=mc, bits=bits, k=k,
                          bk=bk, nc=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, mc), lambda i, j, c: (i, c)),
            pl.BlockSpec((bn, 1), lambda i, j, c: (i, 0)),
            pl.BlockSpec((1, bk), lambda i, j, c: (0, j)),
            pl.BlockSpec((1, bk), lambda i, j, c: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, ob), lambda i, j, c: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, kp_ * bits // 8), jnp.uint8),
        scratch_shapes=[pltpu.VMEM((bn, bk), jnp.int32)],
        interpret=interpret,
    )(idx_p, nnz_p, a_p, b_p)
    return out[:n, :(k * bits + 7) // 8]


# ---------------------------------------------------------------------------
# Fused OPH: bin minima → densify/zero-code → b-bit → packed bytes.
# ---------------------------------------------------------------------------
def _densify_rotation(vk, k: int):
    """``core.oph.densify_rotation`` on ordered (BN, K) minima: each
    empty bin borrows the minimum of the next non-empty bin j+d
    (circularly), offset by d·_ROT_C.  Rows with no non-empty bin stay
    at the 0xFFFFFFFF sentinel.

    Doubling: after the step of size s every lane holds the nearest
    non-empty bin among offsets [0, 2s); distances are distinct, so the
    strict compare picks exactly the bin the reference picks.

    Every value that reaches a ``pltpu.roll`` is built by integer
    arithmetic, not by a select: on a v5e chip Mosaic returned zeros
    for the roll of ``where(empty, 1 << 20, 0)`` and of
    ``empty.astype(int32) << 20``.
    """
    x = vk ^ ORDERED_MAX                            # 0 only on empty bins
    # k (farther than any bin) on empty bins, else 0
    dist = (((x | -x) >> 31) + 1) << (k.bit_length() - 1)
    val = vk
    s = 1
    while s < k:
        # roll by k − s brings lane j+s (mod k) to lane j
        d2 = pltpu.roll(dist, k - s, 1) + s
        v2 = pltpu.roll(val, k - s, 1)
        take = ((d2 - dist) >> 31) & 1              # 1 where d2 < dist
        dist = jnp.minimum(d2, dist)
        val = val ^ ((val ^ v2) & -take)
        s *= 2
    borrowed = _unordered(val) + dist.astype(jnp.uint32) * jnp.uint32(_ROT_C)
    return jnp.where(dist >= k, jnp.full_like(borrowed, 0xFFFFFFFF),
                     borrowed)


def _oph_pack_kernel(a_ref, b_ref, idx_ref, nnz_ref, out_ref, eout_ref,
                     acc_ref, *, mc: int, shift: int, k: int, kp: int,
                     bits: int, densify: bool, nc: int, ow: int, ew: int):
    """One (doc-block, nnz-block) grid step: hash once, min-scatter into
    scratch; densify + pack on the final step."""
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, ORDERED_MAX)

    acc_ref[...] = jnp.minimum(
        acc_ref[...], _oph_block_min(a_ref, b_ref, idx_ref, nnz_ref, c,
                                     mc=mc, shift=shift, kp=kp))

    @pl.when(c == nc - 1)
    def _finish():
        vals = acc_ref[...]                          # (BN, KP) ordered
        vk = vals[:, :k] if kp > k else vals         # logical bins only
        ek = vk == ORDERED_MAX                       # (BN, K) empty bins
        bn = vk.shape[0]
        mask_b = jnp.uint32((1 << bits) - 1)
        if densify:
            codes = _densify_rotation(vk, k) & mask_b
        else:
            codes = jnp.where(ek, jnp.uint32(0), _unordered(vk) & mask_b)
        kpad = ow * (8 // bits)
        if kpad > k:
            codes = jnp.concatenate(
                [codes, jnp.zeros((bn, kpad - k), jnp.uint32)], axis=1)
        out_ref[...] = _pack_lanes(codes, ow, bits)
        e = ek.astype(jnp.int32)
        if ew * 8 > k:
            e = jnp.concatenate(
                [e, jnp.zeros((bn, ew * 8 - k), jnp.int32)], axis=1)
        eout_ref[...] = _pack_lanes(e, ew, 1, msb_first=True)


@functools.partial(
    jax.jit,
    static_argnames=("k", "bits", "densify", "block_n", "block_m",
                     "interpret"),
)
def oph_pack_pallas(
    indices: jax.Array,
    nnz: jax.Array,
    a: jax.Array,
    b: jax.Array,
    *,
    k: int,
    bits: int,
    densify: bool = True,
    block_n: int = 8,
    block_m: int = 256,
    interpret: bool = False,
):
    """(packed uint8 (n, ceil(k·bits/8)), empty uint8 (n, ceil(k/8))).

    Fused OPH encode: one hash evaluation per nonzero, running bin
    minima in VMEM scratch, then — in the same kernel pass —
    densification by rotation (``densify=True``; bit-identical to
    ``core.oph.densify_rotation``) or zero-coding (empty bins → code 0,
    reported in the MSB-first packed ``empty`` bitmask), b-bit masking
    and byte packing.  ``empty`` marks raw empty bins in both modes
    (the densified shard format simply doesn't store it).

    Args:
      indices: int32 (n, m), contiguously padded rows.
      nnz:     int32 (n,) valid prefix length per row.
      a, b:    uint32 (1,) single multiply-shift params (a odd).
      k:       number of bins; power of two.
      bits:    b ∈ {1, 2, 4, 8}.
    """
    _check_bits(bits)
    if k < 2 or (k & (k - 1)) != 0:
        raise ValueError(f"OPH kernel needs k = power of two, got {k}")
    shift = 32 - (int(k).bit_length() - 1)
    n, m = indices.shape
    bn = min(block_n, n)
    mc = min(block_m, m)
    kp = max(k, 128)
    ow = (k * bits + 7) // 8
    ew = (k + 7) // 8

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
    packed, empty = pl.pallas_call(
        functools.partial(_oph_pack_kernel, mc=mc, shift=shift, k=k,
                          kp=kp, bits=bits, densify=densify, nc=nc,
                          ow=ow, ew=ew),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, c: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1), lambda i, c: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bn, mc), lambda i, c: (i, c)),
            pl.BlockSpec((bn, 1), lambda i, c: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, ow), lambda i, c: (i, 0)),
            pl.BlockSpec((bn, ew), lambda i, c: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, ow), jnp.uint8),
            jax.ShapeDtypeStruct((np_, ew), jnp.uint8),
        ],
        scratch_shapes=[pltpu.VMEM((bn, kp), jnp.int32)],
        interpret=interpret,
    )(a.reshape(1, 1), b.reshape(1, 1), idx_p, nnz_p)
    return packed[:n], empty[:n]
