"""Cost-model dispatch (perf/): profile-absent choices are bit-identical
to the historical static policy; forced implementations produce
identical (encode: bitwise, logits: allclose-at-kernel-tolerance)
results; profiles round-trip save→load→same-decisions and are rejected
when corrupt or keyed to another device; the serving engine derives its
micro-batch grid from a measured serve_score curve.

Exactness contract mirrors the seed suites: encode ops emit integers so
pallas-vs-xla must be np.array_equal (test_fused_encode.py); logits
kernels re-associate a float sum so kernel-vs-gather is allclose at the
tolerance test_kernels.py validates, while the unpack fallback is the
same contraction as the widened gather and stays bitwise
(test_packed_linear.py)."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import perf
from repro.core.bbit import pack_codes
from repro.core.schemes import make_scheme
from repro.models.linear import (
    BBitLinearConfig, bbit_logits, bbit_logits_packed, init_bbit_linear,
    logits_impl, logits_packed_impl,
)
from repro.perf import (
    BBIT_KERNEL_MAX_V, CostTable, ProfileError, device_fingerprint,
)
from repro.perf.cost_model import OPS, shape_bucket

ON_TPU = jax.default_backend() == "tpu"


@pytest.fixture(autouse=True)
def _clean_dispatch(monkeypatch):
    monkeypatch.delenv(perf.ENV_DISPATCH, raising=False)
    monkeypatch.delenv(perf.ENV_PROFILE, raising=False)
    perf.reset()
    yield
    perf.reset()


def _encode_case(scheme, b, k=16, rows=5, width=12, seed=0):
    rng = np.random.default_rng(seed * 331 + b)
    idx = rng.integers(0, 1 << 30, size=(rows, width)).astype(np.int32)
    nnz = rng.integers(1, width + 1, size=(rows,)).astype(np.int32)
    return make_scheme(scheme, k, seed), jnp.asarray(idx), jnp.asarray(nnz)


# ---------------------------------------------------------------------------
# no profile, no overrides ⇒ the historical static policy, verbatim


def test_no_profile_reproduces_static_policy():
    shape = {"scheme": "oph", "k": 16, "b": 8, "v": 256, "rows": 64,
             "nnz": 128}
    tpu_arm = {"encode": "pallas", "encode_packed": "pallas",
               "logits": "kernel", "logits_packed": "kernel"}
    cpu_arm = {"encode": "xla", "encode_packed": "xla",
               "logits": "gather", "logits_packed": "unpack"}
    for op in tpu_arm:
        want = tpu_arm[op] if ON_TPU else cpu_arm[op]
        assert perf.choose(op, shape) == want
    # ops-layer choices are capability-first: kernel/bwd arms run on
    # every backend (interpret off-TPU), exactly the seed behavior
    assert perf.choose("logits_bwd", shape) == "kernel"
    assert perf.choose("logits_packed_bwd", shape) == "kernel"
    assert perf.choose("pallas_mode") == (
        "compiled" if ON_TPU else "interpret")
    rep = perf.dispatch_report()
    assert rep["profile_loaded"] is False and rep["hits"] == 0
    assert rep["fallbacks"] == 7


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
    assert perf.choose("encode_packed",
                       {"scheme": "minwise", "k": 16, "b": 3},
                       impl="pallas") == "xla"
    with perf.forced(logits="kernel"):
        assert perf.choose("logits", {"v": 1 << 16}) == "gather"
    assert perf.dispatch_report()["ineligible_overrides"] == 1


@pytest.mark.parametrize("backend,mode", [("tpu", "compiled"),
                                          ("cpu", "interpret")])
def test_pallas_mode_has_one_eligible_arm(monkeypatch, backend, mode):
    """A TPU only compiles Pallas and other backends only interpret:
    no env pin, profile or explicit request can pick the other mode."""
    from repro.kernels.ops import _auto_interpret
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert OPS["pallas_mode"].eligible({}) == (mode,)
    other = "interpret" if mode == "compiled" else "compiled"
    monkeypatch.setenv(perf.ENV_DISPATCH, f"pallas_mode={other}")
    assert perf.choose("pallas_mode") == mode
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

    with perf.forced(encode_packed="pallas", encode="pallas"):
        pallas_out = _run()
    with perf.forced(encode_packed="xla", encode="xla"):
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
    with perf.forced(logits="kernel", logits_packed="kernel"):
        lk = np.asarray(bbit_logits(params, wide, cfg))
        pk = np.asarray(bbit_logits_packed(params, packed, cfg))
    with perf.forced(logits="gather", logits_packed="unpack"):
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
    with perf.forced(logits="kernel"):
        assert logits_impl(cfg_never) == "gather"
    os.environ[perf.ENV_DISPATCH] = "logits_packed=kernel"
    try:
        assert logits_packed_impl(cfg_never) == "unpack"
    finally:
        del os.environ[perf.ENV_DISPATCH]


def test_env_dispatch_and_precedence(monkeypatch):
    shape = {"k": 16, "b": 8, "v": 256}
    monkeypatch.setenv(perf.ENV_DISPATCH,
                       "logits=kernel, logits_packed=kernel")
    assert perf.choose("logits", shape) == "kernel"
    assert perf.choose("logits_packed", shape) == "kernel"
    # forced context beats env; explicit impl beats both
    with perf.forced(logits="gather"):
        assert perf.choose("logits", shape) == "gather"
        assert perf.choose("logits", shape, impl="kernel") == "kernel"
    rep = perf.dispatch_report()
    assert rep["overrides"] == 4


# ---------------------------------------------------------------------------
# profiles: round-trip, rejection, decisions


def _table(entries, fp=None, version="t1"):
    return CostTable(fingerprint=fp or device_fingerprint(),
                     entries=dict(entries), table_version=version)


def test_profile_roundtrip_identical_decisions(tmp_path):
    shape = {"k": 16, "b": 8, "v": 256, "rows": 64}
    bucket = shape_bucket(shape)
    table = _table({
        CostTable.key("logits", "kernel", bucket): 0.002,
        CostTable.key("logits", "gather", bucket): 0.005,
        CostTable.key("encode_packed", "pallas",
                      shape_bucket({"scheme": "oph", "k": 16, "b": 8,
                                    "rows": 64, "nnz": 128})): 0.001,
        CostTable.key("encode_packed", "xla",
                      shape_bucket({"scheme": "oph", "k": 16, "b": 8,
                                    "rows": 64, "nnz": 128})): 0.004,
    })
    path = str(tmp_path / "profile.json")
    table.save(path)
    loaded = CostTable.load(path)
    assert loaded.entries == table.entries
    assert loaded.table_version == table.table_version

    perf.set_profile(table)
    first = (perf.choose("logits", shape),
             perf.choose("encode_packed", {"scheme": "oph", "k": 16,
                                           "b": 8, "rows": 64,
                                           "nnz": 128}))
    perf.reset()
    assert perf.maybe_load_profile(path) is True
    second = (perf.choose("logits", shape),
              perf.choose("encode_packed", {"scheme": "oph", "k": 16,
                                            "b": 8, "rows": 64,
                                            "nnz": 128}))
    assert first == second == ("kernel", "pallas")
    rep = perf.dispatch_report()
    assert rep["profile_loaded"] and rep["hits"] == 2
    # measured argmin actually drives the arm: flip the costs
    flipped = _table({k: (0.005 if v == 0.002 else 0.002 if v == 0.005
                          else v) for k, v in table.entries.items()})
    perf.set_profile(flipped)
    assert perf.choose("logits", shape) == "gather"


def test_partial_profile_falls_back_to_heuristic():
    shape = {"k": 16, "b": 8, "v": 256, "rows": 64}
    # only one arm measured ⇒ no profile decision for this bucket
    perf.set_profile(_table({
        CostTable.key("logits", "kernel", shape_bucket(shape)): 0.001}))
    want = "kernel" if ON_TPU else "gather"
    assert perf.choose("logits", shape) == want
    rep = perf.dispatch_report()
    assert rep["hits"] == 0 and rep["fallbacks"] == 1


def test_profile_never_flips_uncalibrated_ops():
    shape = {"k": 16, "b": 8, "v": 256, "rows": 64}
    bucket = shape_bucket(shape)
    perf.set_profile(_table({
        # a hand-crafted profile claiming the ref bwd is faster must
        # not change training numerics
        CostTable.key("logits_bwd", "kernel", bucket): 9.0,
        CostTable.key("logits_bwd", "ref", bucket): 0.1}))
    assert perf.choose("logits_bwd", shape) == "kernel"


def test_corrupt_and_mismatched_profiles_rejected(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    with pytest.raises(ProfileError):
        CostTable.load(str(bad))
    wrong_schema = tmp_path / "schema.json"
    wrong_schema.write_text(json.dumps({"schema": 999, "entries": {},
                                        "fingerprint": {}}))
    with pytest.raises(ProfileError):
        CostTable.load(str(wrong_schema))
    alien = tmp_path / "alien.json"
    other = _table({}, fp={"backend": "tpu", "device_kind": "TPU v6",
                           "device_count": 8, "jax": "0.0.0"})
    other.save(str(alien))
    with pytest.raises(ProfileError):
        perf.set_profile(str(alien), strict=True)
    # launchers degrade instead of crashing
    for p in (bad, wrong_schema, alien):
        assert perf.maybe_load_profile(str(p)) is False
    assert perf.maybe_load_profile(str(tmp_path / "missing.json")) is False
    assert perf.dispatch_report()["profile_loaded"] is False


def test_shape_bucketing_pow2_rounds_data_sizes():
    a = shape_bucket({"rows": 65, "nnz": 1000, "k": 200, "b": 8})
    assert a == "b=8,k=200,nnz=1024,rows=128"
    assert shape_bucket({"rows": 128, "nnz": 1024, "k": 200, "b": 8}) == a
    assert shape_bucket(None) == "-"


# ---------------------------------------------------------------------------
# micro-batch sizing off a serve_score curve


def _serve_table(curve_fn, nnz_buckets=(32,), max_batch=8, k=16, b=8,
                 scheme="minwise"):
    entries = {}
    for m in nnz_buckets:
        for r in (1, 2, 4, 8):
            entries[CostTable.key(
                "serve_score", "fused",
                shape_bucket({"scheme": scheme, "k": k, "b": b,
                              "rows": r, "nnz": m}))] = curve_fn(r)
    return _table(entries)


def test_row_bucket_suggestions_from_curve_shape():
    # flat curve: a small dispatch costs as much as a big one — every
    # bucket below max is pruned, and the throughput cap is max_batch
    flat = _serve_table(lambda r: 1.0)
    assert perf.suggest_row_buckets(16, 8, "minwise", 8, (32,),
                                    table=flat) == {32: (8,)}
    assert perf.suggest_lane_caps(16, 8, "minwise", 8, (32,),
                                  table=flat) == {32: 8}
    # linear curve: each halving saves ≥15% — keep the whole grid; but
    # cost-per-row ties, so the drain cap stays at max batch (bigger
    # batches amortize per-dispatch overhead the curve can't see)
    linear = _serve_table(lambda r: float(r))
    assert perf.suggest_row_buckets(16, 8, "minwise", 8, (32,),
                                    table=linear) == {32: (1, 2, 4, 8)}
    assert perf.suggest_lane_caps(16, 8, "minwise", 8, (32,),
                                  table=linear) == {32: 8}
    # a >10% genuine small-batch cost-per-row win lowers the cap
    convex = _serve_table(lambda r: {1: 1.0, 2: 2.5, 4: 6.0,
                                     8: 16.0}[r])
    assert perf.suggest_lane_caps(16, 8, "minwise", 8, (32,),
                                  table=convex) == {32: 1}
    # incomplete coverage ⇒ None (caller keeps the static grid)
    assert perf.suggest_row_buckets(16, 8, "minwise", 8, (32, 64),
                                    table=flat) is None


def test_engine_consumes_profile_and_reports_dispatch():
    from repro.serving import HashedClassifierEngine
    perf.set_profile(_serve_table(lambda r: 1.0))
    cfg = BBitLinearConfig(k=16, b=8)
    params = init_bbit_linear(cfg, jax.random.key(0))
    eng = HashedClassifierEngine(params, cfg, seed=0, max_batch=8,
                                 max_wait_ms=1, nnz_buckets=(32,),
                                 row_buckets=None)
    try:
        st = eng.stats()
        assert st["lane_row_buckets"] == {"32": [8]}
        assert st["lane_caps"] == {"32": 8}
        assert st["dispatch"]["profile_loaded"] is True
        rng = np.random.default_rng(0)
        docs = [np.unique(rng.integers(0, 1 << 20, size=s))
                for s in (3, 20, 7)]
        scores = eng.score_docs(docs)
        assert scores.shape == (3,)
    finally:
        eng.close()


def test_engine_without_profile_keeps_static_grid():
    from repro.serving import HashedClassifierEngine
    cfg = BBitLinearConfig(k=16, b=8)
    params = init_bbit_linear(cfg, jax.random.key(0))
    eng = HashedClassifierEngine(params, cfg, seed=0, max_batch=8,
                                 max_wait_ms=1, nnz_buckets=(32,),
                                 row_buckets=None)
    try:
        st = eng.stats()
        assert st["lane_row_buckets"] == {}
        assert st["row_buckets"] == [1, 2, 4, 8]
        assert st["dispatch"]["profile_loaded"] is False
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# calibration: budget-capped, deterministic, round-trippable


def test_calibrate_smoke_budget_and_roundtrip(tmp_path):
    table = perf.calibrate(k=16, b_values=(8,), schemes=("oph",),
                           encode_rows=(4,), encode_widths=(16,),
                           logits_rows=(8,), max_batch=4,
                           nnz_buckets=(16,), trials=1, budget_s=120.0,
                           seed=0)
    assert table.entries and table.matches_device()
    assert table.meta["n_entries"] == len(table.entries)
    # every calibrated-op bucket has all eligible arms (budget allowed)
    per_bucket = {}
    for key in table.entries:
        op, impl, bucket = key.split("|", 2)
        per_bucket.setdefault((op, bucket), set()).add(impl)
    for (op, bucket), impls in per_bucket.items():
        if op != "serve_score":
            assert len(impls) == 2, (op, bucket, impls)
    path = str(tmp_path / "p.json")
    table.save(path)
    assert CostTable.load(path).entries == table.entries
    summary = perf.summarize(table)
    assert summary["entries"] == len(table.entries)
    # an exhausted budget yields an empty (but valid, saveable) table
    empty = perf.calibrate(k=16, b_values=(8,), schemes=("oph",),
                           encode_rows=(4,), encode_widths=(16,),
                           logits_rows=(8,), nnz_buckets=(16,),
                           trials=1, budget_s=0.0, seed=0)
    assert empty.entries == {}
    perf.set_profile(empty)   # loads fine; every choice falls back
    assert perf.choose("logits", {"k": 16, "b": 8, "v": 256}) == (
        "kernel" if ON_TPU else "gather")
