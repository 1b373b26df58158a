"""Pallas TPU kernels: fused one-hot-expansion linear layer (paper §3).

The paper expands each hashed example into a 2^b·k-dim binary vector and
feeds it to LIBLINEAR.  Materializing that expansion costs 2^b× the
storage the method just saved.  These kernels compute

    fwd:  logits[n, c] = Σ_j  W[j, codes[n, j], c]
    bwd:  dW[j, v, c]  = Σ_n 1{codes[n, j] = v} · dout[n, c]

by building the one-hot tile *in VMEM registers* (an iota compare) and
contracting it on the MXU against the (C, 2^b) weight slab of each hash
function.  The expansion never touches HBM.

Two input formats share the one-hot contraction:

  * ``bbit_linear_fwd_pallas`` / ``bbit_linear_bwd_dw_pallas`` take an
    already-widened int32 ``(n, k)`` code matrix;
  * ``bbit_linear_packed_fwd_pallas`` / ``…_packed_bwd_dw_pallas`` take
    the ON-DISK packed rows — uint8 ``(n, ceil(k·b/8))``, the
    ``core.bbit.pack_codes`` bit layout — and unpack the b-bit codes
    in-register between the VMEM load and the compare, so the widened
    matrix never exists anywhere (the streaming trainer's hot path:
    n·ceil(k·b/8) bytes HBM→VMEM instead of n·k·4).  An optional
    packed empty bitmask (``np.packbits`` layout, the ``oph_zero``
    shard side file) zeroes the marked bins' one-hot rows, fusing the
    ragged-mask path that previously forced an XLA gather.  They take
    b ∈ {1, 2, 4, 8}, where codes never straddle bytes, and b = 16
    (below); other b fall back to the XLA unpack path (see ops.py).

Layout: rows sit on the 128 lanes and hash functions on sublanes.  The
wrappers hand the kernels the codes (or packed bytes) transposed,
``(k, n)``, and the table as ``(k, C, V)``, so one hash function's codes
are a (1, BN) row read at a dynamic sublane offset inside a
``fori_loop``, and its one-hot is a (V, BN) sublane-iota compare.  Both
directions contract that one tile as it stands: the forward against the
(C, V) weight slab over its sublanes, the backward against dout, handed
in lane-major as ``(C, n)``, over its lanes, writing (C, V) straight
into the ``(k, C, V)`` dW.  Neither loop body transposes.  Every
block then meets the TPU rule that its last two dimensions are whole
(8, 128) tiles or the whole array.  The contraction order is that of a
loop over hash functions, so the packed and widened kernels agree bit
for bit.

b = 16 is factored.  A (2^16, BN) one-hot per hash function would take
256 MB for 1,024 rows, so it is never built.  Code j is bytes 2j (lo)
and 2j+1 (hi) of the packed row, code = 256·hi + lo = 128·r + l with
r = 2·hi + lo div 128 and l = lo mod 128, and hash function j's slab is
viewed as W_j[r, l], (C, 512, 128).  In (8, 128) tiles that is the
table's own row-major order, so handing the slabs to the kernel and
taking dW back are bitcasts (a 256 × 256 view would cost a relayout of
the whole table each way).  A (128, BN) one-hot of l and a (512, BN)
match of r carry the code:

    fwd:  logit[n] += Σ_r 1{r_n = r} · (W_j · onehot_l)[r, n]
    bwd:  dW_j       += (1{r_n = r} ⊙ dout) · onehot_lᵀ

one (C·512, 128)·(128, BN) MXU product, a mask and a sublane sum one
way; the lane-contracting product of the b ≤ 8 dW the other.  The f32
operand of each product (the slab, dout) goes to the MXU as three bf16
parts that sum to it exactly (``_split3``), against the 0/1 operand in
bf16, which is exact: three default-precision passes where ``_HIGHEST``
takes six.  The hash-function block is the outer grid dimension in both
directions, so the forward reads the table once and the backward writes
dW once per call; its size follows from the VMEM a table block may
take.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _onehot_t(code_row, v: int, dtype):
    """(1, BN) int32 codes → (V, BN) one-hot; a code ≥ V (the empty-bin
    sentinel) gives an all-zero column."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (v, code_row.shape[1]), 0)
    return (code_row == iota).astype(dtype)


def _blocks(n: int, k: int, block_n: int, block_j: int):
    """(BN, BJ, padded k): BN rows on lanes (all of them, or a multiple
    of 128), BJ hash functions on sublanes (all of k, or ``block_j``)."""
    bn = n if n <= block_n else block_n
    k8 = _round_up(k, 8)
    bj = k8 if k8 <= block_j else block_j
    return bn, bj, _round_up(k, bj)


# ---------------------------------------------------------------------------
# Kernels.  ``row_fn(jj)`` yields hash function jj's (1, BN) int32 codes
# for the current block; the widened and packed kernels differ only in it.
# ---------------------------------------------------------------------------
def _fwd_body(row_fn, w_ref, out_ref, bj: int):
    """out (C, BN) += Σ_jj W[jj]ᵀ (C, V) · onehot(jj) (V, BN)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    v = w_ref.shape[2]

    def step(jj, acc):
        w = w_ref[jj]                                   # (C, V)
        onehot = _onehot_t(row_fn(jj), v, w.dtype)      # (V, BN)
        return acc + jax.lax.dot_general(
            w, onehot, (((1,), (0,)), ((), ())),
            precision=_HIGHEST, preferred_element_type=jnp.float32)

    out_ref[...] = jax.lax.fori_loop(0, bj, step, out_ref[...])


def _bwd_body(row_fn, dout_ref, dw_ref, bj: int):
    """dW[jj]ᵀ (C, V) += dout (C, BN) · onehot(jj)ᵀ (BN, V).  The one-hot
    is the forward's (V, BN) tile and dout arrives lane-major, so the
    contraction runs over the lanes of both operands and its (C, V)
    result lands in the output block as is: no relayout in the loop."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    dout = dout_ref[...]                                # (C, BN)
    v = dw_ref.shape[2]

    def step(jj, carry):
        onehot = _onehot_t(row_fn(jj), v, dout.dtype)   # (V, BN)
        dw_ref[jj] = dw_ref[jj] + jax.lax.dot_general(
            dout, onehot, (((1,), (1,)), ((), ())),
            precision=_HIGHEST, preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, bj, step, 0)


def _widened_kernel(body, bj: int):
    def kernel(codes_ref, x_ref, o_ref):
        body(lambda jj: codes_ref[pl.ds(jj, 1), :], x_ref, o_ref, bj)
    return kernel


def _packed_kernel(body, bj: int, bits: int, masked: bool):
    """Unpacks code jj from byte jj // r (r = 8/b codes per byte, code t
    at bit t·b, LSB-first); a marked empty bin (packbits: bit 7 − jj % 8
    of mask byte jj // 8) becomes the never-matching code 2^b."""
    r = 8 // bits
    lo = (1 << bits) - 1

    def kernel(*refs):
        if masked:
            pk_ref, em_ref, x_ref, o_ref, pk_s, em_s = refs
            em_s[...] = em_ref[...].astype(jnp.int32)
        else:
            pk_ref, x_ref, o_ref, pk_s = refs
        pk_s[...] = pk_ref[...].astype(jnp.int32)

        def row(jj):
            code = pk_s[pl.ds(jj // r, 1), :]
            if r > 1:
                code = (code >> ((jj % r) * bits)) & lo
            if masked:
                e = (em_s[pl.ds(jj // 8, 1), :] >> (7 - jj % 8)) & 1
                code = jnp.where(e != 0, lo + 1, code)
            return code

        body(row, x_ref, o_ref, bj)
    return kernel


# ---------------------------------------------------------------------------
# pallas_call plumbing shared by the four entry points
# ---------------------------------------------------------------------------
def _pad_rows_t(x, np_: int):
    """(n, w) → (w, np_): transposed, zero rows appended."""
    return jnp.pad(x, ((0, np_ - x.shape[0]), (0, 0))).T


def _call_fwd(kernel, row_inputs, row_specs, weights, bn, bj, kp,
              scratch, interpret):
    """Grid (n/BN, kp/BJ): accumulate over hash-function blocks (dim 1)."""
    k, v, c = weights.shape
    np_ = row_inputs[0].shape[1]
    w_t = jnp.pad(weights, ((0, kp - k), (0, 0), (0, 0))).transpose(0, 2, 1)
    out = pl.pallas_call(
        kernel,
        grid=(np_ // bn, kp // bj),
        in_specs=row_specs(lambda i, j: (j, i))
        + [pl.BlockSpec((bj, c, v), lambda i, j: (j, 0, 0))],
        out_specs=pl.BlockSpec((c, bn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((c, np_), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(*row_inputs, w_t)
    return out.T


def _call_bwd(kernel, row_inputs, row_specs, dout, vsize, bn, bj, kp,
              scratch, interpret):
    """Grid (kp/BJ, n/BN): accumulate over example blocks (dim 1).
    Padded examples carry zero dout → no effect."""
    c = dout.shape[1]
    np_ = row_inputs[0].shape[1]
    dout_t = _pad_rows_t(dout.astype(jnp.float32), np_)
    dw_t = pl.pallas_call(
        kernel,
        grid=(kp // bj, np_ // bn),
        in_specs=row_specs(lambda j, i: (j, i))
        + [pl.BlockSpec((c, bn), lambda j, i: (0, i))],
        out_specs=pl.BlockSpec((bj, c, vsize), lambda j, i: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((kp, c, vsize), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(*row_inputs, dout_t)
    return dw_t.transpose(0, 2, 1)


def _widened_inputs(codes, bn, bj, kp):
    n, k = codes.shape
    np_ = _round_up(n, bn)
    codes_t = _pad_rows_t(jnp.pad(codes, ((0, 0), (0, kp - k))), np_)

    def specs(index):
        return [pl.BlockSpec((bj, bn), index)]
    return [codes_t], specs


# ---------------------------------------------------------------------------
# Widened int32 codes
# ---------------------------------------------------------------------------
@functools.partial(
    jax.jit, static_argnames=("block_n", "block_j", "interpret")
)
def bbit_linear_fwd_pallas(
    codes: jax.Array,
    weights: jax.Array,
    *,
    block_n: int = 128,
    block_j: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """logits (n, C) f32 from codes (n, k) int32 and W (k, V, C).

    Padded hash functions point at code 0 of a zero weight row and
    padded examples are sliced away, so padding adds exactly nothing."""
    n, k = codes.shape
    bn, bj, kp = _blocks(n, k, block_n, block_j)
    inputs, specs = _widened_inputs(codes, bn, bj, kp)
    out = _call_fwd(_widened_kernel(_fwd_body, bj), inputs, specs,
                    weights, bn, bj, kp, [], interpret)
    return out[:n]


@functools.partial(
    jax.jit, static_argnames=("vsize", "block_n", "block_j", "interpret")
)
def bbit_linear_bwd_dw_pallas(
    codes: jax.Array,
    dout: jax.Array,
    vsize: int,
    *,
    block_n: int = 128,
    block_j: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """dW (k, V, C) f32 from codes (n, k) and dout (n, C)."""
    n, k = codes.shape
    bn, bj, kp = _blocks(n, k, block_n, block_j)
    inputs, specs = _widened_inputs(codes, bn, bj, kp)
    dw = _call_bwd(_widened_kernel(_bwd_body, bj), inputs, specs, dout,
                   vsize, bn, bj, kp, [], interpret)
    return dw[:k]


# ---------------------------------------------------------------------------
# Packed-input variants: unpack b-bit codes in-register, no (n, k) int32
# intermediate.  Bit layout matches core.bbit.pack_codes (row-major
# bitstream, LSB-first: code j·(8/b)+t sits in byte j at bit offset t·b)
# and np.packbits (MSB-first) for the empty bitmask.
# ---------------------------------------------------------------------------
def _packed_inputs(packed, empty, k, bits, bn, bj, kp):
    """Transposed byte (and mask) rows, padded to kp hash functions and
    a BN multiple of rows.  Padding bytes unpack to code 0 of a zero
    weight row, so non-lane-multiple k (and the pack format's own zero
    padding bits in the final byte) is exact rather than masked."""
    n = packed.shape[0]
    np_ = _round_up(n, bn)
    wb = bj * bits // 8
    pk_t = _pad_rows_t(
        jnp.pad(packed, ((0, 0), (0, kp * bits // 8 - packed.shape[1]))),
        np_)
    inputs = [pk_t]
    scratch = [pltpu.VMEM((wb, bn), jnp.int32)]
    if empty is not None:
        inputs.append(_pad_rows_t(
            jnp.pad(empty, ((0, 0), (0, kp // 8 - empty.shape[1]))), np_))
        scratch.append(pltpu.VMEM((bj // 8, bn), jnp.int32))

    def specs(index):
        out = [pl.BlockSpec((wb, bn), index)]
        if empty is not None:
            out.append(pl.BlockSpec((bj // 8, bn), index))
        return out
    return inputs, specs, scratch


def _packed_blocks(n: int, k: int, block_n: int, block_j: int):
    """A hash-function block spans whole packed bytes and mask bytes:
    BJ is all of k (rounded to 8), or ``block_j``, a multiple of 256, so
    that its BJ·b/8 byte rows and BJ/8 mask rows fill whole (32, 128)
    uint8 tiles."""
    if block_j % 256:
        raise ValueError(f"packed block_j must be a multiple of 256, "
                         f"got {block_j}")
    return _blocks(n, k, block_n, block_j)


# ---------------------------------------------------------------------------
# b = 16: factored one-hots (module docstring).  The hash-function block
# is grid dimension 0 in both directions: the forward keeps every row's
# logits in one resident output block, the backward accumulates each dW
# block over the row blocks.
# ---------------------------------------------------------------------------
_SLAB = (512, 128)             # W_j[r, l], code = 128·r + l
_ROWS16 = 1024                 # rows a block: lanes of the one-hots
_TABLE_BLOCK_BYTES = 4 << 20   # one buffer of table (or dW) slabs


def _blocks16(n: int, k: int, c: int):
    """(BN, BJ): BN rows, a multiple of 128; BJ the largest divisor of
    k whose (BJ, C, 512, 128) f32 slabs fit one table buffer, so the
    table is never padded (a padded copy would cost a table's read
    and write per call)."""
    bn = min(_round_up(n, 128), _ROWS16)
    cap = max(1, _TABLE_BLOCK_BYTES // (c * _SLAB[0] * _SLAB[1] * 4))
    bj = max(d for d in range(1, min(k, cap) + 1) if k % d == 0)
    return bn, bj


def _slab_rows(packed, empty, k: int, np_: int):
    """(k, 2, np_) int32, rows on lanes: hash function j's lane index
    l = lo mod 128 and row index r = 2·hi + lo div 128 of code
    256·hi + lo (bytes 2j and 2j+1).  A marked empty bin gets r = 512,
    which no one-hot row matches; padded rows get code 0 and are sliced
    away (forward) or carry zero dout (backward)."""
    n = packed.shape[0]
    by = packed.astype(jnp.int32).reshape(n, k, 2)
    lo, hi = by[:, :, 0], by[:, :, 1]
    r = 2 * hi + (lo >> 7)
    if empty is not None:
        j = jnp.arange(k)
        bit = (empty.astype(jnp.int32)[:, j // 8] >> (7 - j % 8)) & 1
        r = jnp.where(bit != 0, _SLAB[0], r)
    rows = jnp.stack([lo & (_SLAB[1] - 1), r], axis=2)
    rows = jnp.pad(rows, ((0, np_ - n), (0, 0), (0, 0)))
    return rows.transpose(1, 2, 0)


def _split3(x):
    """f32 → three bf16 parts whose f32 sum (hi + mid) + lo is x, bit for
    bit.

    A 24-bit significand is three 8-bit ones and bf16 keeps f32's
    exponent range, so each residual fits the next part whole, for
    2^-103 ≤ |x| < 2^127 and ±0 (below, ``lo`` would be subnormal).
    The residuals are taken as -(a - b), which is b - a but for the
    sign of a zero, so -0 splits into three -0.  Against an exact bf16
    operand (a one-hot, a 0/1 match) three default-precision MXU passes
    give what six at ``_HIGHEST`` give; a two-part split,
    ``Precision.HIGH``'s, keeps 16 bits of x and is not exact."""
    f32 = jnp.float32
    hi = x.astype(jnp.bfloat16)
    r = -(hi.astype(f32) - x)
    mid = r.astype(jnp.bfloat16)
    lo = (-(mid.astype(f32) - r)).astype(jnp.bfloat16)
    return hi, mid, lo


def _slab_onehots(rows_ref, jj, lane_dtype, row_dtype):
    """Hash function jj's (128, BN) lane one-hot and (512, BN) row
    match, bool or bf16.  A bf16 one is a select of 1.0 in f32, packed
    (0/1 is exact): Mosaic lowers that without the int → float
    conversion that ``astype`` of a compare takes."""
    def onehot(i, v, dtype):
        hit = _onehot_t(rows_ref[jj, pl.ds(i, 1), :], v, jnp.bool_)
        if dtype == jnp.bool_:
            return hit
        return jnp.where(hit, 1.0, 0.0).astype(dtype)
    return onehot(0, _SLAB[1], lane_dtype), onehot(1, _SLAB[0], row_dtype)


def _fwd16_kernel(bj: int):
    """The slab's three bf16 parts side by side, (C·512, 3·128), against
    the lane one-hot stacked three times, (3·128, BN): one product whose
    contraction adds hi·1 + mid·1 + lo·1, so each entry is its W entry
    exactly and the logits are those of an f32 gather."""
    def kernel(rows_ref, w_ref, out_ref):
        j, i = pl.program_id(0), pl.program_id(1)

        @pl.when((j == 0) & (i == 0))
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        c, bn = out_ref.shape[1], out_ref.shape[2]

        def step(jj, acc):
            lane, row = _slab_onehots(rows_ref, jj, jnp.bfloat16, jnp.bool_)
            w = w_ref[jj].reshape(c * _SLAB[0], _SLAB[1])
            p = jax.lax.dot_general(
                jnp.concatenate(_split3(w), axis=1),
                jnp.concatenate([lane] * 3, axis=0),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            p = jnp.where(row[None], p.reshape(c, _SLAB[0], bn), 0.0)
            return acc + jnp.sum(p, axis=1)

        out_ref[i] = jax.lax.fori_loop(0, bj, step, out_ref[i])
    return kernel


def _bwd16_kernel(bj: int):
    """dout's three bf16 parts are split once a row block.  Per hash
    function the row match (512, BN) meets the lane one-hot times each
    part of each class, (C·3·128, BN), over the lanes of both; the
    three 128-lane slices of a class's (512, 3·128) product sum to its
    dW slab.  The only (512, BN) element-wise work is the match."""
    def kernel(rows_ref, dout_ref, dw_ref):
        @pl.when(pl.program_id(1) == 0)
        def _init():
            dw_ref[...] = jnp.zeros_like(dw_ref)

        parts = [x.astype(jnp.float32)                   # 3 × (C, BN)
                 for x in _split3(dout_ref[...])]
        c, l = dout_ref.shape[0], _SLAB[1]

        def step(jj, carry):
            lane, row = _slab_onehots(rows_ref, jj, jnp.bool_, jnp.bfloat16)
            scaled = jnp.concatenate(                   # (C·3·128, BN)
                [jnp.where(lane, part[cc:cc + 1], 0.0).astype(jnp.bfloat16)
                 for cc in range(c) for part in parts], axis=0)
            p = jax.lax.dot_general(                    # (512, C·3·128)
                row, scaled, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dw_ref[jj] = dw_ref[jj] + jnp.stack([
                p[:, (3 * cc) * l:(3 * cc + 1) * l]
                + p[:, (3 * cc + 1) * l:(3 * cc + 2) * l]
                + p[:, (3 * cc + 2) * l:(3 * cc + 3) * l]
                for cc in range(c)])
            return carry

        jax.lax.fori_loop(0, bj, step, 0)
    return kernel


def _packed16_fwd(packed, weights, k, empty, interpret):
    n = packed.shape[0]
    c = weights.shape[2]
    bn, bj = _blocks16(n, k, c)
    np_ = _round_up(n, bn)
    rows = _slab_rows(packed, empty, k, np_)
    w = weights.transpose(0, 2, 1).reshape(k, c, *_SLAB)
    out = pl.pallas_call(
        _fwd16_kernel(bj),
        grid=(k // bj, np_ // bn),
        in_specs=[pl.BlockSpec((bj, 2, bn), lambda j, i: (j, 0, i)),
                  pl.BlockSpec((bj, c) + _SLAB, lambda j, i: (j, 0, 0, 0))],
        out_specs=pl.BlockSpec((np_ // bn, c, bn), lambda j, i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((np_ // bn, c, bn), jnp.float32),
        interpret=interpret,
    )(rows, w)
    return out.transpose(0, 2, 1).reshape(np_, c)[:n]


def _packed16_bwd_dw(packed, dout, k, empty, interpret):
    n, c = dout.shape
    bn, bj = _blocks16(n, k, c)
    np_ = _round_up(n, bn)
    rows = _slab_rows(packed, empty, k, np_)
    dw = pl.pallas_call(
        _bwd16_kernel(bj),
        grid=(k // bj, np_ // bn),
        in_specs=[pl.BlockSpec((bj, 2, bn), lambda j, i: (j, 0, i)),
                  pl.BlockSpec((c, bn), lambda j, i: (0, i))],
        out_specs=pl.BlockSpec((bj, c) + _SLAB, lambda j, i: (j, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, c) + _SLAB, jnp.float32),
        interpret=interpret,
    )(rows, _pad_rows_t(dout.astype(jnp.float32), np_))
    # The kernel wrote dW as (k, C, V) in row-major order.  Said so, the
    # transpose to (k, V, C) is a bitcast; left to itself, XLA lays the
    # optimizer update around a relayout of every (k, V, C) state leaf
    # when k is not a multiple of 8 (k = 500).
    return with_layout_constraint(
        dw.reshape(k, c, _SLAB[0] * _SLAB[1]).transpose(0, 2, 1),
        Layout((0, 2, 1)))


@functools.partial(
    jax.jit,
    static_argnames=("k", "bits", "block_n", "block_j", "interpret"),
)
def bbit_linear_packed_fwd_pallas(
    packed: jax.Array,
    weights: jax.Array,
    *,
    k: int,
    bits: int,
    empty: jax.Array = None,
    block_n: int = 128,
    block_j: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """logits (n, C) f32 straight from packed uint8 (n, ceil(k·bits/8)).

    Bit-exact vs ``unpack_codes_jnp`` + the widened kernel
    (tests/test_packed_linear.py property-sweeps b, ragged masks and
    non-lane-multiple k).  ``empty`` (uint8 (n, ceil(k/8)), packbits
    layout) drops the marked bins — the ``oph_zero`` ragged-mask path,
    fused here instead of falling back to an XLA gather.  At b = 16
    the blocks follow from the shapes (``_blocks16``).
    """
    if bits == 16:
        return _packed16_fwd(packed, weights, k, empty, interpret)
    n = packed.shape[0]
    bn, bj, kp = _packed_blocks(n, k, block_n, block_j)
    inputs, specs, scratch = _packed_inputs(packed, empty, k, bits,
                                            bn, bj, kp)
    kernel = _packed_kernel(_fwd_body, bj, bits, empty is not None)
    out = _call_fwd(kernel, inputs, specs, weights, bn, bj, kp, scratch,
                    interpret)
    return out[:n]


@functools.partial(
    jax.jit,
    static_argnames=("k", "bits", "vsize", "block_n", "block_j",
                     "interpret"),
)
def bbit_linear_packed_bwd_dw_pallas(
    packed: jax.Array,
    dout: jax.Array,
    vsize: int,
    *,
    k: int,
    bits: int,
    empty: jax.Array = None,
    block_n: int = 128,
    block_j: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """dW (k, V, C) f32 from packed rows and dout (n, C), in-register
    unpack; ``empty`` bins contribute nothing (their one-hot row is
    zeroed, matching the forward).  At b = 16 the blocks follow from
    the shapes (``_blocks16``)."""
    if bits == 16:
        return _packed16_bwd_dw(packed, dout, k, empty, interpret)
    n = packed.shape[0]
    bn, bj, kp = _packed_blocks(n, k, block_n, block_j)
    inputs, specs, scratch = _packed_inputs(packed, empty, k, bits,
                                            bn, bj, kp)
    kernel = _packed_kernel(_bwd_body, bj, bits, empty is not None)
    dw = _call_bwd(kernel, inputs, specs, dout, vsize, bn, bj, kp,
                   scratch, interpret)
    return dw[:k]
