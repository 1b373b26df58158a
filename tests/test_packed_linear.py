"""Packed-input fused logits: the Pallas kernels (interpret mode) and
the XLA fallback must agree with the widened reference across b, ragged
``oph_zero`` masks, and non-lane-multiple k.

Exactness contract: the packed kernels are BIT-exact vs the widened
kernels (identical contraction order, only the input format differs);
vs the gather reference — a mathematically equal but differently
associated sum — they are allclose, matching the tolerance the widened
kernels themselves are validated to in test_kernels.py."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.bbit import pack_codes, unpack_codes_jnp
from repro.kernels import ops, ref
from repro.kernels.bbit_linear import (
    _split3,
    bbit_linear_bwd_dw_pallas,
    bbit_linear_fwd_pallas,
    bbit_linear_packed_bwd_dw_pallas,
    bbit_linear_packed_fwd_pallas,
)
from repro.models.linear import (
    BBitLinearConfig, bbit_logits, bbit_logits_packed, init_bbit_linear,
)


def _case(b, k, n=17, c=3, seed=None, empty_frac=0.0):
    rng = np.random.default_rng(b * 1031 + k if seed is None else seed)
    v = 1 << b
    codes = rng.integers(0, v, size=(n, k)).astype(np.uint16)
    packed = jnp.asarray(pack_codes(codes, b))
    weights = jnp.asarray(rng.normal(size=(k, v, c)).astype(np.float32))
    empty = None
    if empty_frac:
        # ragged: wildly different empty counts per row, incl. all-empty
        mask = rng.random((n, k)) < empty_frac
        mask[0] = True
        mask[1] = False
        empty = jnp.asarray(np.packbits(mask, axis=1))
    dout = jnp.asarray(rng.normal(size=(n, c)).astype(np.float32))
    return codes, packed, weights, empty, dout


@pytest.mark.parametrize("b", [1, 2, 4, 8])
@pytest.mark.parametrize("k", [1, 8, 37, 63, 64])
def test_packed_kernel_bit_exact_vs_widened_kernel(b, k):
    codes, packed, weights, _, dout = _case(b, k)
    v = 1 << b
    want = bbit_linear_fwd_pallas(jnp.asarray(codes.astype(np.int32)),
                                  weights, interpret=True)
    got = bbit_linear_packed_fwd_pallas(packed, weights, k=k, bits=b,
                                        interpret=True)
    assert np.array_equal(np.asarray(want), np.asarray(got))
    dwant = bbit_linear_bwd_dw_pallas(jnp.asarray(codes.astype(np.int32)),
                                      dout, v, interpret=True)
    dgot = bbit_linear_packed_bwd_dw_pallas(packed, dout, v, k=k, bits=b,
                                            interpret=True)
    assert np.array_equal(np.asarray(dwant), np.asarray(dgot))


@pytest.mark.parametrize("b", [1, 2, 4, 8])
@pytest.mark.parametrize("k", [8, 37, 64])
@pytest.mark.parametrize("empty_frac", [0.3, 0.9])
def test_packed_kernel_masked_matches_reference(b, k, empty_frac):
    _, packed, weights, empty, dout = _case(b, k, empty_frac=empty_frac)
    v = 1 << b
    want = ref.bbit_linear_packed_fwd(packed, weights, k, b, empty=empty)
    got = bbit_linear_packed_fwd_pallas(packed, weights, k=k, bits=b,
                                        empty=empty, interpret=True)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-5, atol=1e-5)
    dwant = ref.bbit_linear_packed_bwd_dw(packed, dout, v, k, b,
                                          empty=empty)
    dgot = bbit_linear_packed_bwd_dw_pallas(packed, dout, v, k=k, bits=b,
                                            empty=empty, interpret=True)
    np.testing.assert_allclose(np.asarray(dwant), np.asarray(dgot),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("b", [1, 8])
@pytest.mark.parametrize("c", [1, 3])
def test_packed_dw_accumulates_across_row_blocks(c, b, masked):
    """n = 300 is three 128-row blocks, the last ragged, so dW sums over
    the row grid; C = 1 is the lane-major dout the streaming trainer
    runs.  Packed == widened bit for bit (a marked empty bin is the
    widened code 2^b), and both match the reference."""
    k, n = 37, 300
    codes, packed, _, empty, dout = _case(b, k, n=n, c=c, seed=7 * b + c,
                                          empty_frac=0.4 if masked else 0.0)
    v = 1 << b
    wide = codes.astype(np.int32)
    if masked:
        wide[np.unpackbits(np.asarray(empty), axis=1)[:, :k] != 0] = v
    dwide = bbit_linear_bwd_dw_pallas(jnp.asarray(wide), dout, v,
                                      interpret=True)
    dgot = bbit_linear_packed_bwd_dw_pallas(packed, dout, v, k=k, bits=b,
                                            empty=empty, interpret=True)
    assert np.array_equal(np.asarray(dwide), np.asarray(dgot))
    dwant = ref.bbit_linear_packed_bwd_dw(packed, dout, v, k, b,
                                          empty=empty)
    np.testing.assert_allclose(np.asarray(dwant), np.asarray(dgot),
                               rtol=1e-5, atol=1e-5)


def _kernel_eqns(jaxpr, inside=False):
    """Every eqn of the Pallas kernel bodies in ``jaxpr``, loop and
    branch bodies included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield from _kernel_eqns(eqn.params["jaxpr"], True)
            continue
        if inside:
            yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _kernel_eqns(sub, inside)


@pytest.mark.parametrize("kernel", ["packed", "packed_masked", "widened"])
def test_dw_kernel_contracts_lanes_without_transpose(kernel):
    """The dW loop contracts the forward's (V, BN) one-hot against
    lane-major dout (C, BN) over the lanes of both: no transpose inside
    the kernel body, one (((1,), (1,)), ((), ())) dot_general."""
    k, b, n = 256, 8, 300
    v = 1 << b
    dout = jax.ShapeDtypeStruct((n, 1), jnp.float32)
    if kernel == "widened":
        jaxpr = jax.make_jaxpr(
            lambda x, d: bbit_linear_bwd_dw_pallas(x, d, v))(
            jax.ShapeDtypeStruct((n, k), jnp.int32), dout)
    else:
        empty = (jax.ShapeDtypeStruct((n, k // 8), jnp.uint8)
                 if kernel == "packed_masked" else None)
        jaxpr = jax.make_jaxpr(
            lambda x, d, e: bbit_linear_packed_bwd_dw_pallas(
                x, d, v, k=k, bits=b, empty=e))(
            jax.ShapeDtypeStruct((n, k * b // 8), jnp.uint8), dout, empty)
    eqns = list(_kernel_eqns(jaxpr.jaxpr))
    names = [e.primitive.name for e in eqns]
    assert names, "no Pallas kernel body found"
    assert "transpose" not in names
    dots = [e.params["dimension_numbers"] for e in eqns
            if e.primitive.name == "dot_general"]
    assert dots == [(((1,), (1,)), ((), ()))]


@pytest.mark.parametrize("masked", [False, True])
def test_packed_custom_vjp_grads_match_reference(masked):
    k, b = 16, 4
    _, packed, weights, empty, _ = _case(b, k,
                                         empty_frac=0.4 if masked else 0.0)

    def loss_kernel(w):
        return jnp.sum(ops.bbit_linear_packed(packed, w, k, b,
                                              empty=empty) ** 2)

    def loss_ref(w):
        return jnp.sum(ref.bbit_linear_packed_fwd(packed, w, k, b,
                                                  empty=empty) ** 2)

    g = jax.grad(loss_kernel)(weights)
    gref = jax.grad(loss_ref)(weights)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,k,c,masked", [
    pytest.param(300, 20, 1, False, id="n300-k20-c1"),
    pytest.param(300, 20, 1, True, id="n300-k20-c1-masked"),
    pytest.param(300, 20, 3, False, id="n300-k20-c3"),
    pytest.param(300, 20, 3, True, id="n300-k20-c3-masked"),
    pytest.param(1100, 12, 1, True, id="n1100-k12-c1-masked"),
    pytest.param(17, 37, 1, False, id="n17-k37-c1"),
])
def test_packed_b16_kernels_match_reference(n, k, c, masked):
    """b = 16 takes the factored arm: a 2^16-entry slab per hash
    function carried by two one-hots.  n = 1100 spans two row blocks,
    the last ragged (the forward's resident output and dW's sum over
    the row grid); k = 37, a prime, makes one hash function a block;
    C = 3 stacks the classes in one product."""
    _, packed, weights, empty, dout = _case(
        16, k, n=n, c=c, seed=n + k + c,
        empty_frac=0.4 if masked else 0.0)
    v = 1 << 16
    want = ref.bbit_linear_packed_fwd(packed, weights, k, 16, empty=empty)
    got = bbit_linear_packed_fwd_pallas(packed, weights, k=k, bits=16,
                                        empty=empty, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    dwant = ref.bbit_linear_packed_bwd_dw(packed, dout, v, k, 16,
                                          empty=empty)
    dgot = bbit_linear_packed_bwd_dw_pallas(packed, dout, v, k=k, bits=16,
                                            empty=empty, interpret=True)
    np.testing.assert_allclose(np.asarray(dgot), np.asarray(dwant),
                               rtol=1e-5, atol=1e-5)


def test_packed_b16_custom_vjp_takes_the_kernel_arm():
    from repro.kernels import dispatch
    k, b = 8, 16
    _, packed, weights, _, _ = _case(b, k, n=40, c=1)
    dispatch.reset()

    def loss_kernel(w):
        return jnp.sum(ops.bbit_linear_packed(packed, w, k, b) ** 2)

    def loss_ref(w):
        return jnp.sum(ref.bbit_linear_packed_fwd(packed, w, k, b) ** 2)

    assert dispatch.packed_kernel_fits(b, 1 << b)
    jaxpr = jax.make_jaxpr(jax.grad(loss_kernel))(weights)
    assert str(jaxpr).count("pallas_call") == 2      # forward and dW
    g = jax.grad(loss_kernel)(weights)
    chosen = {key: impl for key, impl in
              dispatch.dispatch_report()["choices"].items()
              if key.startswith("logits_packed_bwd|")}
    assert chosen and all("b=16" in key and impl == "kernel"
                          for key, impl in chosen.items()), chosen
    gref = jax.grad(loss_ref)(weights)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gref),
                               rtol=1e-4, atol=1e-4)


def _two_part_split(x):
    """``Precision.HIGH``'s split: bf16(x) and bf16 of the rest."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _full_mantissa(x):
    """float32 ``x`` with its last significand bit set: all 24 bits
    count."""
    return (np.asarray(x, np.float32).view(np.uint32) | 1).view(np.float32)


@pytest.mark.parametrize("parts", ["three", "two"])
def test_split3_parts_sum_to_x_bitwise(parts):
    """hi + mid + lo gives x back bit for bit over f32's exponent range,
    signs and ±0; the two-part split of ``Precision.HIGH`` misses nearly
    every value whose 24 bits are all needed (it keeps those whose
    middle bits happen to be zero)."""
    rng = np.random.default_rng(17)
    x = _full_mantissa(rng.uniform(1, 2, 20000)
                       * np.exp2(rng.integers(-100, 126, 20000))
                       * rng.choice([-1.0, 1.0], 20000))
    edges = np.array([0.0, -0.0, 1 + 2 ** -23, -(2 - 2 ** -23), 2 ** -103,
                      -(2 - 2 ** -23) * 2 ** 126, 1.0, -3.0], np.float32)
    x = np.concatenate([x, edges])
    split = _split3 if parts == "three" else _two_part_split
    got = functools.reduce(
        lambda a, b: a + b,
        [p.astype(jnp.float32) for p in jax.jit(split)(jnp.asarray(x))])
    same = np.asarray(got).view(np.uint32) == x.view(np.uint32)
    if parts == "three":
        assert same.all(), x[~same][:5]
    else:
        assert same[:-len(edges)].mean() < 0.02


def _sequential_logits(codes, weights, live):
    """Σ_j W[j, code_j] in f32, one add a hash function in order j =
    0, 1, …: the forward kernel's order."""
    acc = np.zeros((codes.shape[0], weights.shape[2]), np.float32)
    for j in range(codes.shape[1]):
        acc = acc + np.where(live[:, j:j + 1],
                             weights[j, codes[:, j].astype(np.int64)], 0.0
                             ).astype(np.float32)
    return acc


@pytest.mark.parametrize("c,masked", [
    pytest.param(1, False, id="c1"), pytest.param(3, False, id="c3"),
    pytest.param(1, True, id="c1-two-live"),
    pytest.param(3, True, id="c3-two-live")])
def test_packed_b16_forward_is_exact(c, masked):
    """Weights with all 24 significand bits: the forward equals the f32
    sum of the rows' entries bit for bit, in the kernel's order, and
    the reference's bit for bit where each row keeps at most two live
    bins (one rounding, the same in any order).  Any bit of W that the
    bf16 parts dropped would show."""
    n, k = 300, 20
    codes, packed, weights, _, _ = _case(16, k, n=n, c=c, seed=41 + c)
    weights = jnp.asarray(_full_mantissa(weights))
    empty, live = None, np.ones((n, k), bool)
    if masked:
        rng = np.random.default_rng(c)
        live = np.zeros((n, k), bool)
        rows = np.arange(n)
        live[rows, rng.integers(0, k, n)] = True
        live[rows[::2], rng.integers(0, k, n)[::2]] = True
        live[5] = False                                 # an all-empty row
        empty = jnp.asarray(np.packbits(~live, axis=1))
    got = np.asarray(bbit_linear_packed_fwd_pallas(
        packed, weights, k=k, bits=16, empty=empty, interpret=True))
    np.testing.assert_array_equal(
        got, _sequential_logits(codes, np.asarray(weights), live))
    if masked:
        np.testing.assert_array_equal(
            got, np.asarray(ref.bbit_linear_packed_fwd(packed, weights, k,
                                                       16, empty=empty)))


@pytest.mark.parametrize("c,masked", [
    pytest.param(1, False, id="c1"), pytest.param(3, True, id="c3-masked")])
def test_packed_b16_dw_sums_to_float64(c, masked):
    """Each hash function draws its codes from 11 values, so a dW entry
    sums ~100 douts over two row blocks, their magnitudes spread over
    2^40 so that the largest few carry the sum.  Against a float64 sum
    dW errs by f32 rounding alone, under 1e-6 of Σ|dout|; a 16-bit
    split of dout misses by ~2e-6."""
    n, k, v = 1100, 12, 1 << 16
    rng = np.random.default_rng(5 + c)
    values = rng.choice(v, size=(k, 11), replace=False)
    codes = values[np.arange(k), rng.integers(0, 11, (n, k))]
    dout = _full_mantissa(rng.choice([-1.0, 1.0], (n, c))
                          * rng.uniform(1, 2, (n, c))
                          * np.exp2(rng.uniform(-40, 0, (n, c))))
    live = rng.random((n, k)) > 0.3 if masked else np.ones((n, k), bool)
    empty = jnp.asarray(np.packbits(~live, axis=1)) if masked else None
    got = np.asarray(bbit_linear_packed_bwd_dw_pallas(
        jnp.asarray(pack_codes(codes.astype(np.uint16), 16)),
        jnp.asarray(dout), v, k=k, bits=16, empty=empty, interpret=True))
    want, scale = np.zeros((k, v, c)), np.zeros((k, v, c))
    for j in range(k):
        np.add.at(want[j], codes[live[:, j], j], dout[live[:, j]])
        np.add.at(scale[j], codes[live[:, j], j], np.abs(dout[live[:, j]]))
    hit = scale > 0
    assert not got[~hit].any()
    np.testing.assert_array_less(np.abs(got - want)[hit], 1e-6 * scale[hit])


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_b16_kernels_multiply_bf16_on_the_mxu(direction):
    """The b = 16 loop bodies multiply bf16 by bf16 at default precision
    (three exact passes), never f32 by f32 (six at ``_HIGHEST``)."""
    k, n = 500, 1024
    packed = jax.ShapeDtypeStruct((n, 2 * k), jnp.uint8)
    if direction == "fwd":
        jaxpr = jax.make_jaxpr(lambda p, w: bbit_linear_packed_fwd_pallas(
            p, w, k=k, bits=16))(
            packed, jax.ShapeDtypeStruct((k, 1 << 16, 1), jnp.float32))
    else:
        jaxpr = jax.make_jaxpr(lambda p, d: bbit_linear_packed_bwd_dw_pallas(
            p, d, 1 << 16, k=k, bits=16))(
            packed, jax.ShapeDtypeStruct((n, 1), jnp.float32))
    dots = [e for e in _kernel_eqns(jaxpr.jaxpr)
            if e.primitive.name == "dot_general"]
    assert len(dots) == 1
    assert [x.aval.dtype for x in dots[0].invars] == [jnp.bfloat16] * 2
    assert dots[0].params["precision"] in (None, (None, None))


def test_packed_fallback_used_for_non_byte_aligned_b():
    # b=3 codes straddle bytes — dispatch must fall to the XLA path and
    # still match the widened gather exactly
    k, b, v = 16, 3, 8
    rng = np.random.default_rng(0)
    codes = rng.integers(0, v, size=(9, k)).astype(np.uint16)
    packed = jnp.asarray(pack_codes(codes, b))
    weights = jnp.asarray(rng.normal(size=(k, v, 2)).astype(np.float32))
    got = ops.bbit_linear_packed(packed, weights, k, b)
    want = ref.bbit_linear_fwd(jnp.asarray(codes.astype(np.int32)),
                               weights)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("use_kernel", ["never", "always"])
@pytest.mark.parametrize("masked", [False, True])
def test_bbit_logits_packed_matches_widened_logits(use_kernel, masked):
    """Model-level parity on BOTH dispatch paths (fallback and
    interpret-mode kernel), with bias + normalize applied."""
    k, b = 24, 4
    codes, packed, _, empty, _ = _case(b, k,
                                       empty_frac=0.5 if masked else 0.0)
    cfg = BBitLinearConfig(k=k, b=b, use_kernel=use_kernel,
                           normalize=True)
    params = init_bbit_linear(cfg, jax.random.key(3))
    from repro.core.bbit import unpack_mask_jnp
    wide = bbit_logits(
        params, unpack_codes_jnp(packed, k, b).astype(jnp.int32), cfg,
        empty=None if empty is None else unpack_mask_jnp(empty, k))
    got = bbit_logits_packed(params, packed, cfg, empty_packed=empty)
    np.testing.assert_allclose(np.asarray(wide), np.asarray(got),
                               rtol=1e-5, atol=1e-5)
    if use_kernel == "never" and not masked:
        # the streaming trainer's CPU path: bit-identical to the old
        # explicit unpack + gather two-step
        assert np.array_equal(np.asarray(wide), np.asarray(got))
