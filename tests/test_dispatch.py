"""The kernel choice (``repro.kernels.dispatch``): the static rule
picks the kernel arms on a TPU and the XLA arms elsewhere; forced
implementations produce identical (encode: bitwise, logits:
allclose-at-kernel-tolerance) results; pins, ``REPRO_DISPATCH`` and
explicit requests obey their precedence and never reach an ineligible
arm; the record ``perf.dispatch_report()`` is what the benchmark reads.

Exactness contract mirrors the seed suites: encode ops emit integers so
pallas-vs-xla must be np.array_equal (test_fused_encode.py); logits
kernels re-associate a float sum so kernel-vs-gather is allclose at the
tolerance test_kernels.py validates, while the unpack fallback is the
same contraction as the widened gather and stays bitwise
(test_packed_linear.py)."""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import perf
from repro.core.bbit import pack_codes
from repro.core.schemes import make_scheme
from repro.kernels import dispatch
from repro.kernels.dispatch import (
    BBIT_KERNEL_MAX_V, ENV_DISPATCH, OPS, shape_bucket,
)
from repro.models.linear import (
    BBitLinearConfig, bbit_logits, bbit_logits_packed, init_bbit_linear,
    logits_impl, logits_packed_impl,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_dispatch(monkeypatch):
    monkeypatch.delenv(ENV_DISPATCH, raising=False)
    dispatch.reset()
    yield
    dispatch.reset()


def _encode_case(scheme, b, k=16, rows=5, width=12, seed=0):
    rng = np.random.default_rng(seed * 331 + b)
    idx = rng.integers(0, 1 << 30, size=(rows, width)).astype(np.int32)
    nnz = rng.integers(1, width + 1, size=(rows,)).astype(np.int32)
    return make_scheme(scheme, k, seed), jnp.asarray(idx), jnp.asarray(nnz)


def _bench_harness():
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench import harness
    return harness


# ---------------------------------------------------------------------------
# no pins, no env ⇒ the static rule


_STATIC = {
    # op: (TPU arm, arm elsewhere)
    "encode": ("pallas", "xla"),
    "encode_packed": ("pallas", "xla"),
    "logits": ("kernel", "gather"),
    "logits_packed": ("kernel", "unpack"),
    # ops-layer choices are capability-first: the kernel runs on every
    # backend (interpret off-TPU)
    "logits_bwd": ("kernel", "kernel"),
    "logits_packed_bwd": ("kernel", "kernel"),
    "pallas_mode": ("compiled", "interpret"),
    "hamming_topk": ("pallas", "xla"),
}


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
@pytest.mark.parametrize("op", sorted(_STATIC))
def test_static_rule(monkeypatch, op, backend):
    assert set(_STATIC) == set(OPS)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    shape = {"scheme": "oph", "k": 16, "b": 8, "v": 256, "rows": 64,
             "nnz": 128, "width": 16}
    want = _STATIC[op][0 if backend == "tpu" else 1]
    assert want in OPS[op].arms
    assert dispatch.choose(op, shape) == want
    assert dispatch.dispatch_report() == {
        "choices": {f"{op}|{shape_bucket(shape)}": want}}


@pytest.mark.parametrize("k,b", [(256, 8), (500, 16)])
def test_cells_pick_kernel_arms_on_tpu(monkeypatch, k, b):
    """The training cells' step, traced as on a TPU, runs exactly the
    ``kernel_arms`` the benchmark checks (``ops_off_kernel_arm`` = 0)."""
    harness = _bench_harness()
    with open(os.path.join(ROOT, "bench", "traffic",
                           "stream_train.json")) as f:
        want = json.load(f)["kernel_arms"]
    assert want == {"logits_packed": "kernel",
                    "logits_packed_bwd": "kernel",
                    "pallas_mode": "compiled"}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = BBitLinearConfig(k=k, b=b)
    params = {"table": jax.ShapeDtypeStruct((k, 1 << b, 1), jnp.float32),
              "bias": jax.ShapeDtypeStruct((1,), jnp.float32)}
    packed = jax.ShapeDtypeStruct((1024, k * b // 8), jnp.uint8)

    def loss(p, x):
        return jnp.sum(bbit_logits_packed(p, x, cfg))

    jaxpr = jax.make_jaxpr(jax.grad(loss))(params, packed)
    assert str(jaxpr).count("pallas_call") == 2      # forward and dW
    chosen = harness.arms()
    assert {op: chosen[op] for op in want} == {
        op: [impl] for op, impl in want.items()}
    assert harness.arm_checks(chosen, want)[0].value == 0


def test_dispatch_report_keys_feed_bench_arms():
    """``perf.dispatch_report()["choices"]`` keys are ``op|bucket``, and
    ``bench.harness.arms()`` splits them into op → arms."""
    assert perf.dispatch_report is dispatch.dispatch_report
    shapes = [("logits", {"k": 16, "b": 8, "v": 256, "rows": 9}),
              ("logits", {"k": 16, "b": 8, "v": 256, "rows": 300}),
              ("encode_packed", {"scheme": "oph", "k": 16, "b": 4})]
    with dispatch.forced(logits="kernel"):
        dispatch.choose(*shapes[0])
    for op, shape in shapes[1:]:
        dispatch.choose(op, shape)
    choices = perf.dispatch_report()["choices"]
    assert set(choices) == {f"{op}|{shape_bucket(shape)}"
                            for op, shape in shapes}
    for key in choices:
        op, bucket = key.split("|")
        assert op in OPS and bucket
    got = _bench_harness().arms()
    assert got["logits"] == sorted({"kernel", choices[
        f"logits|{shape_bucket(shapes[1][1])}"]})
    assert got["encode_packed"] == [choices[
        f"encode_packed|{shape_bucket(shapes[2][1])}"]]


def test_eligibility_filters_before_any_override():
    # b=3 can't pack; 2^b over the kernel ceiling can't one-hot; OPH
    # with non-pow-2 bins can't use the scatter-min kernel
    assert OPS["encode_packed"].eligible(
        {"scheme": "minwise", "k": 16, "b": 3}) == ("xla",)
    assert OPS["encode"].eligible(
        {"scheme": "oph", "k": 200, "b": 8}) == ("xla",)
    assert OPS["logits"].eligible(
        {"v": BBIT_KERNEL_MAX_V * 2}) == ("gather",)
    # forcing the ineligible arm is ignored, not crashed into
    assert dispatch.choose("encode_packed",
                           {"scheme": "minwise", "k": 16, "b": 3},
                           impl="pallas") == "xla"
    with dispatch.forced(logits="kernel"):
        assert dispatch.choose("logits", {"v": 1 << 16}) == "gather"
    assert dispatch.dispatch_report()["choices"] == {
        "encode_packed|b=3,k=16,scheme=minwise": "xla",
        "logits|v=65536": "gather"}


@pytest.mark.parametrize("backend,mode", [("tpu", "compiled"),
                                          ("cpu", "interpret")])
def test_pallas_mode_has_one_eligible_arm(monkeypatch, backend, mode):
    """A TPU only compiles Pallas and other backends only interpret:
    no env pin or explicit request can pick the other mode."""
    from repro.kernels.ops import _auto_interpret
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert OPS["pallas_mode"].eligible({}) == (mode,)
    other = "interpret" if mode == "compiled" else "compiled"
    monkeypatch.setenv(ENV_DISPATCH, f"pallas_mode={other}")
    assert dispatch.choose("pallas_mode") == mode
    for request in (None, True, False):
        assert _auto_interpret(request) == (mode == "interpret")


# ---------------------------------------------------------------------------
# forced implementations agree


@pytest.mark.parametrize("scheme", ["minwise", "oph", "oph_zero"])
@pytest.mark.parametrize("b", [1, 2, 4, 8])
def test_forced_encode_impls_bitwise_identical(scheme, b):
    sch, idx, nnz = _encode_case(scheme, b)

    def _run():
        packed, p_empty = sch.encode_packed_device(idx, nnz, b)
        codes, c_empty = sch.encode_device(idx, nnz, b)
        return (np.asarray(packed),
                None if p_empty is None else np.asarray(p_empty),
                np.asarray(codes),
                None if c_empty is None else np.asarray(c_empty))

    with dispatch.forced(encode_packed="pallas", encode="pallas"):
        pallas_out = _run()
    with dispatch.forced(encode_packed="xla", encode="xla"):
        xla_out = _run()
    for got, want in zip(pallas_out, xla_out):
        if got is None or want is None:
            assert got is None and want is None
        else:
            assert np.array_equal(got, want)


@pytest.mark.parametrize("b", [2, 4, 8])
def test_forced_logits_impls_agree(b):
    k, v, rows = 16, 1 << b, 9
    cfg = BBitLinearConfig(k=k, b=b)
    params = init_bbit_linear(cfg, jax.random.key(b))
    rng = np.random.default_rng(b)
    codes = rng.integers(0, v, size=(rows, k)).astype(np.uint16)
    wide = jnp.asarray(codes.astype(np.int32))
    packed = jnp.asarray(pack_codes(codes, b))
    with dispatch.forced(logits="kernel", logits_packed="kernel"):
        lk = np.asarray(bbit_logits(params, wide, cfg))
        pk = np.asarray(bbit_logits_packed(params, packed, cfg))
    with dispatch.forced(logits="gather", logits_packed="unpack"):
        lg = np.asarray(bbit_logits(params, wide, cfg))
        pu = np.asarray(bbit_logits_packed(params, packed, cfg))
    # kernel re-associates the float sum: allclose at the seed tolerance
    np.testing.assert_allclose(lk, lg, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(pk, pu, rtol=1e-4, atol=1e-4)
    # the unpack fallback IS the widened gather on unpacked codes
    assert np.array_equal(pu, lg)


def test_use_kernel_config_maps_to_explicit_impl():
    cfg_never = BBitLinearConfig(k=16, b=8, use_kernel="never")
    cfg_always = BBitLinearConfig(k=16, b=8, use_kernel="always")
    assert logits_impl(cfg_never) == "gather"
    assert logits_packed_impl(cfg_never) == "unpack"
    assert logits_impl(cfg_always) == "kernel"
    assert logits_packed_impl(cfg_always) == "kernel"
    # explicit config beats a forced context and the env var
    with dispatch.forced(logits="kernel"):
        assert logits_impl(cfg_never) == "gather"
    os.environ[ENV_DISPATCH] = "logits_packed=kernel"
    try:
        assert logits_packed_impl(cfg_never) == "unpack"
    finally:
        del os.environ[ENV_DISPATCH]


def test_env_dispatch_and_precedence(monkeypatch):
    shape = {"k": 16, "b": 8, "v": 256}
    monkeypatch.setenv(ENV_DISPATCH,
                       "logits=kernel, logits_packed=kernel")
    assert dispatch.choose("logits", shape) == "kernel"
    assert dispatch.choose("logits_packed", shape) == "kernel"
    # forced context beats env; explicit impl beats both
    with dispatch.forced(logits="gather"):
        assert dispatch.choose("logits", shape) == "gather"
        assert dispatch.choose("logits", shape, impl="kernel") == "kernel"
    # the pin ends with its context: the env var decides again
    assert dispatch.choose("logits", shape) == "kernel"
    assert dispatch.dispatch_report()["choices"] == {
        "logits|b=8,k=16,v=256": "kernel",
        "logits_packed|b=8,k=16,v=256": "kernel"}


def test_shape_bucketing_pow2_rounds_data_sizes():
    a = shape_bucket({"rows": 65, "nnz": 1000, "k": 200, "b": 8})
    assert a == "b=8,k=200,nnz=1024,rows=128"
    assert shape_bucket({"rows": 128, "nnz": 1024, "k": 200, "b": 8}) == a
    assert shape_bucket(None) == "-"


def test_engine_uses_one_row_grid_for_every_lane():
    from repro.serving import HashedClassifierEngine
    cfg = BBitLinearConfig(k=16, b=8)
    params = init_bbit_linear(cfg, jax.random.key(0))
    eng = HashedClassifierEngine(params, cfg, seed=0, max_batch=8,
                                 max_wait_ms=1, nnz_buckets=(32, 64),
                                 row_buckets=None)
    try:
        st = eng.stats()
        assert "lane_row_buckets" not in st and "lane_caps" not in st
        assert st["row_buckets"] == [1, 2, 4, 8]
        # both lanes compiled over the one grid, and drain at max_batch
        assert eng._compiled == {(r, m, 0) for r in (1, 2, 4, 8)
                                 for m in (32, 64)}
        assert eng.batcher._cap == 8
        assert [eng._row_bucket(n) for n in (1, 3, 8, 9)] == [1, 4, 8, 16]
        assert set(st["dispatch"]) == {"choices"}
    finally:
        eng.close()
