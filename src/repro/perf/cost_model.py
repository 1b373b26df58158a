"""Measured cost-model dispatch (see docs/DESIGN.md §2).

Every implementation choice in the repo — fused Pallas encode vs tiled
XLA, packed logits kernel vs unpack-fallback, interpret vs compiled
Pallas, serving micro-batch row buckets — flows through one entry
point, :func:`choose`.  The selection order is

    explicit ``impl=`` argument
  > :func:`forced` context (calibration / tests)
  > ``REPRO_DISPATCH`` env var (``"op=impl,op=impl"``)
  > a loaded :class:`CostTable` profile (argmin of measured seconds)
  > the static heuristic that reproduces the repo's historical policy

with *eligibility* filtering applied before any of them: a forced or
profiled impl that the hardware/shape cannot run (b outside the pack
set, 2^b over the one-hot kernel ceiling, non-pow-2 OPH bins, compiled
Pallas off-TPU, interpreted Pallas on TPU) is ignored rather than
crashed into.  Without a profile and without overrides every choice is
bit-identical to the old scattered ``jax.default_backend() == "tpu"``
checks — this module is the only place in ``src/repro`` allowed to ask
for the backend.

Profiles are versioned JSON keyed by a backend/device fingerprint
(:func:`device_fingerprint`); a mismatched or corrupt profile is
rejected (``ProfileError``) and dispatch degrades to the heuristics.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
from typing import Callable, Dict, Iterable, Mapping, Optional, Tuple

import jax

# One-hot contraction kernel ceiling: past this vocabulary size the
# (k, 2^b) one-hot intermediate stops paying for itself.  Historically
# lived in kernels/ops.py (which still re-exports it).
BBIT_KERNEL_MAX_V = 4096
# The packed kernels' factored arm: a 2^16-entry table per hash
# function, each code carried by two smaller one-hots
# (kernels/bbit_linear.py).
BYTE_FACTORED_BITS = 16

SCHEMA_VERSION = 1
ENV_DISPATCH = "REPRO_DISPATCH"
ENV_PROFILE = "REPRO_PROFILE"


class ProfileError(ValueError):
    """Raised for corrupt, wrong-schema, or wrong-device profiles."""


# ---------------------------------------------------------------------------
# fingerprint + shape buckets


def device_fingerprint() -> Dict[str, object]:
    devs = jax.devices()
    return {
        "backend": jax.default_backend(),
        "device_kind": devs[0].device_kind if devs else "none",
        "device_count": len(devs),
        "jax": jax.__version__,
    }


def fingerprint_key(fp: Mapping[str, object]) -> str:
    """The part of the fingerprint a profile must match to be usable.
    (jax version is recorded for provenance but not enforced.)"""
    return (f"{fp.get('backend')}|{fp.get('device_kind')}"
            f"|{fp.get('device_count')}")


def _pow2_at_least(n: int) -> int:
    n = max(1, int(n))
    return 1 << (n - 1).bit_length()


# shape keys bucketed to the next power of two (data-dependent sizes);
# everything else (k, b, v, scheme, ...) is part of the bucket verbatim
_BUCKETED_KEYS = frozenset({"rows", "nnz", "width", "m"})


def shape_bucket(shape: Optional[Mapping[str, object]]) -> str:
    if not shape:
        return "-"
    parts = []
    for key in sorted(shape):
        val = shape[key]
        if key in _BUCKETED_KEYS:
            val = _pow2_at_least(int(val))
        parts.append(f"{key}={val}")
    return ",".join(parts)


# ---------------------------------------------------------------------------
# op registry


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


_PACK_BITS: Optional[Tuple[int, ...]] = None


def _pack_bits() -> Tuple[int, ...]:
    # lazy: repro.kernels imports repro.perf at module load, so the
    # reverse edge must wait until first use
    global _PACK_BITS
    if _PACK_BITS is None:
        from repro.kernels.fused_encode import PACK_BITS
        _PACK_BITS = tuple(PACK_BITS)
    return _PACK_BITS


def _oph_kernel_ok(shape: Mapping[str, object]) -> bool:
    # the OPH scatter-min kernel needs lane-aligned (pow-2) bins; the
    # jnp path covers arbitrary k
    if str(shape.get("scheme", "")).startswith("oph"):
        return _is_pow2(int(shape.get("k", 0)))
    return True


def _encode_eligible(shape) -> Tuple[str, ...]:
    return ("pallas", "xla") if _oph_kernel_ok(shape) else ("xla",)


def _encode_packed_eligible(shape) -> Tuple[str, ...]:
    ok = int(shape.get("b", 0)) in _pack_bits() and _oph_kernel_ok(shape)
    return ("pallas", "xla") if ok else ("xla",)


def _logits_eligible(shape) -> Tuple[str, ...]:
    ok = int(shape.get("v", 1 << 30)) <= BBIT_KERNEL_MAX_V
    return ("kernel", "gather") if ok else ("gather",)


def packed_kernel_fits(b: int, v: int) -> bool:
    """Whether the packed logits kernels take (b, V = 2^b): whole codes
    in a byte with V within the one-hot ceiling, or b = 16."""
    if b == BYTE_FACTORED_BITS:
        return v == 1 << b
    return b in _pack_bits() and v <= BBIT_KERNEL_MAX_V


def _logits_packed_eligible(shape) -> Tuple[str, ...]:
    b = int(shape.get("b", 0))
    v = int(shape.get("v", (1 << b) if b else (1 << 30)))
    ok = packed_kernel_fits(b, v)
    return ("kernel", "unpack") if ok else ("unpack",)


def _pallas_mode_eligible(shape) -> Tuple[str, ...]:
    # Mosaic lowering only exists on TPU, and there it is the only
    # mode: an interpreted kernel on a chip would hide the device
    # behind a host loop.  Everywhere else Pallas runs in interpret
    # mode.
    if jax.default_backend() == "tpu":
        return ("compiled",)
    return ("interpret",)


def _tpu_first(kernel_impl: str, fallback_impl: str):
    def heuristic(shape, eligible) -> str:
        if jax.default_backend() == "tpu" and kernel_impl in eligible:
            return kernel_impl
        return fallback_impl
    return heuristic


def _capability_first(kernel_impl: str, fallback_impl: str):
    # ops-layer policy: backend-independent — direct kernel callers
    # (and their tests) exercise the Pallas path on every backend
    def heuristic(shape, eligible) -> str:
        return kernel_impl if kernel_impl in eligible else fallback_impl
    return heuristic


@dataclasses.dataclass(frozen=True)
class OpSpec:
    name: str
    impls: Tuple[str, ...]
    eligible: Callable[[Mapping[str, object]], Tuple[str, ...]]
    heuristic: Callable[[Mapping[str, object], Tuple[str, ...]], str]
    calibrated: bool = True


OPS: Dict[str, OpSpec] = {}


def _register(spec: OpSpec) -> None:
    OPS[spec.name] = spec


# scheme-level encode: codes (int) out
_register(OpSpec("encode", ("pallas", "xla"), _encode_eligible,
                 _tpu_first("pallas", "xla")))
# scheme-level fused encode→pack: packed bytes out
_register(OpSpec("encode_packed", ("pallas", "xla"),
                 _encode_packed_eligible, _tpu_first("pallas", "xla")))
# model-level logits over widened int codes
_register(OpSpec("logits", ("kernel", "gather"), _logits_eligible,
                 _tpu_first("kernel", "gather")))
# model-level logits straight off packed bytes
_register(OpSpec("logits_packed", ("kernel", "unpack"),
                 _logits_packed_eligible, _tpu_first("kernel", "unpack")))
# ops-layer bwd choices inside the custom_vjps (capability-first: the
# kernel runs everywhere, interpret off-TPU — unchanged historical
# behavior without a profile)
_register(OpSpec("logits_bwd", ("kernel", "ref"), _logits_eligible,
                 _capability_first("kernel", "ref"), calibrated=False))
_register(OpSpec("logits_packed_bwd", ("kernel", "unpack"),
                 _logits_packed_eligible,
                 _capability_first("kernel", "unpack"), calibrated=False))
# interpret vs compiled Pallas execution
_register(OpSpec("pallas_mode", ("compiled", "interpret"),
                 _pallas_mode_eligible,
                 _capability_first("compiled", "interpret"),
                 calibrated=False))
# retrieval candidate scoring: packed-popcount Hamming + top-k.  The
# Pallas arm is gated like the rest of the packed family: only
# byte-aligned b flows through the packed retrieval/serving hot paths,
# so XLA ``population_count`` covers every other shape
def _hamming_topk_eligible(shape) -> Tuple[str, ...]:
    ok = int(shape.get("b", 0)) in _pack_bits()
    return ("pallas", "xla") if ok else ("xla",)


_register(OpSpec("hamming_topk", ("pallas", "xla"),
                 _hamming_topk_eligible, _tpu_first("pallas", "xla")))
# serving fused encode→score dispatch: single impl — calibrated for
# its cost-per-row curve (micro-batch sizing), never a choice
_register(OpSpec("serve_score", ("fused",), lambda s: ("fused",),
                 lambda s, e: "fused"))


# ---------------------------------------------------------------------------
# CostTable


@dataclasses.dataclass
class CostTable:
    """Measured seconds per (op, impl, shape-bucket), device-keyed."""

    fingerprint: Dict[str, object]
    entries: Dict[str, float] = dataclasses.field(default_factory=dict)
    table_version: str = "uncalibrated"
    meta: Dict[str, object] = dataclasses.field(default_factory=dict)

    @staticmethod
    def key(op: str, impl: str, bucket: str) -> str:
        return f"{op}|{impl}|{bucket}"

    def put(self, op: str, impl: str,
            shape: Optional[Mapping[str, object]], seconds: float) -> None:
        self.entries[self.key(op, impl, shape_bucket(shape))] = float(seconds)

    def lookup(self, op: str, impl: str,
               shape: Optional[Mapping[str, object]] = None,
               *, bucket: Optional[str] = None) -> Optional[float]:
        if bucket is None:
            bucket = shape_bucket(shape)
        return self.entries.get(self.key(op, impl, bucket))

    def matches_device(self) -> bool:
        return (fingerprint_key(self.fingerprint)
                == fingerprint_key(device_fingerprint()))

    def to_json(self) -> Dict[str, object]:
        return {
            "schema": SCHEMA_VERSION,
            "table_version": self.table_version,
            "fingerprint": self.fingerprint,
            "meta": self.meta,
            "entries": self.entries,
        }

    def save(self, path: str) -> None:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_json(), f, indent=2, sort_keys=True)
        os.replace(tmp, path)

    @classmethod
    def load(cls, path: str) -> "CostTable":
        try:
            with open(path) as f:
                raw = json.load(f)
        except (OSError, ValueError) as e:
            raise ProfileError(f"unreadable profile {path!r}: {e}") from e
        if not isinstance(raw, dict) or raw.get("schema") != SCHEMA_VERSION:
            raise ProfileError(
                f"profile {path!r}: unsupported schema "
                f"{raw.get('schema') if isinstance(raw, dict) else type(raw)}")
        fp = raw.get("fingerprint")
        entries = raw.get("entries")
        if not isinstance(fp, dict) or not isinstance(entries, dict):
            raise ProfileError(f"profile {path!r}: malformed body")
        try:
            entries = {str(k): float(v) for k, v in entries.items()}
        except (TypeError, ValueError) as e:
            raise ProfileError(f"profile {path!r}: non-numeric entry: "
                               f"{e}") from e
        return cls(fingerprint=fp, entries=entries,
                   table_version=str(raw.get("table_version", "?")),
                   meta=dict(raw.get("meta") or {}))


# ---------------------------------------------------------------------------
# the model: choose + observability


def _parse_env_dispatch(raw: str) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for part in raw.split(","):
        part = part.strip()
        if not part:
            continue
        op, _, impl = part.partition("=")
        if op.strip() and impl.strip():
            out[op.strip()] = impl.strip()
    return out


class CostModel:
    """Process-wide dispatch state: loaded profile, forced pins,
    per-(op, bucket) decision log, hit/fallback counters."""

    def __init__(self, table: Optional[CostTable] = None):
        self.table = table
        self._lock = threading.Lock()
        self._forced: Dict[str, str] = {}
        self.counts = {"explicit": 0, "forced": 0, "env": 0,
                       "profile": 0, "heuristic": 0, "ineligible": 0}
        self.choices: Dict[str, str] = {}   # "op|bucket" -> impl

    # -- profile management -------------------------------------------------

    def set_table(self, table: Optional[CostTable],
                  *, strict: bool = True) -> None:
        if table is not None and not table.matches_device():
            if strict:
                raise ProfileError(
                    "profile fingerprint "
                    f"{fingerprint_key(table.fingerprint)!r} does not match "
                    f"this device {fingerprint_key(device_fingerprint())!r}")
            table = None
        with self._lock:
            self.table = table

    # -- selection ----------------------------------------------------------

    def choose(self, op: str,
               shape: Optional[Mapping[str, object]] = None,
               *, impl: Optional[str] = None) -> str:
        spec = OPS[op]
        shape = dict(shape or {})
        eligible = spec.eligible(shape)
        bucket = shape_bucket(shape)

        source = None
        picked: Optional[str] = None
        if impl is not None:
            if impl in eligible:
                source, picked = "explicit", impl
            else:
                with self._lock:
                    self.counts["ineligible"] += 1
        if picked is None:
            forced = self._forced.get(op)
            if forced is not None and forced in eligible:
                source, picked = "forced", forced
        if picked is None:
            env = os.environ.get(ENV_DISPATCH)
            if env:
                want = _parse_env_dispatch(env).get(op)
                if want is not None and want in eligible:
                    source, picked = "env", want
        if picked is None and spec.calibrated and len(eligible) > 1:
            table = self.table
            if table is not None:
                costs = {i: table.lookup(op, i, bucket=bucket)
                         for i in eligible}
                if all(c is not None for c in costs.values()):
                    source, picked = "profile", min(costs, key=costs.get)
        if picked is None:
            picked = spec.heuristic(shape, eligible)
            source = "heuristic"

        with self._lock:
            self.counts[source] = self.counts.get(source, 0) + 1
            self.choices[f"{op}|{bucket}"] = picked
        return picked

    # -- forcing (calibration + tests) --------------------------------------

    def force(self, pins: Mapping[str, str]) -> "_ForcedCtx":
        return _ForcedCtx(self, dict(pins))

    # -- observability ------------------------------------------------------

    def report(self) -> Dict[str, object]:
        with self._lock:
            table = self.table
            return {
                "table_version": (table.table_version if table is not None
                                  else None),
                "profile_loaded": table is not None,
                "fingerprint": fingerprint_key(device_fingerprint()),
                "hits": self.counts["profile"],
                "fallbacks": self.counts["heuristic"],
                "overrides": (self.counts["explicit"]
                              + self.counts["forced"] + self.counts["env"]),
                "ineligible_overrides": self.counts["ineligible"],
                "choices": dict(self.choices),
            }


class _ForcedCtx:
    def __init__(self, model: CostModel, pins: Dict[str, str]):
        self._model, self._pins, self._saved = model, pins, {}

    def __enter__(self):
        with self._model._lock:
            for op, impl in self._pins.items():
                if op not in OPS:
                    raise KeyError(f"unknown dispatch op {op!r}")
                self._saved[op] = self._model._forced.get(op)
                self._model._forced[op] = impl
        return self._model

    def __exit__(self, *exc):
        with self._model._lock:
            for op, prev in self._saved.items():
                if prev is None:
                    self._model._forced.pop(op, None)
                else:
                    self._model._forced[op] = prev
        return False


# ---------------------------------------------------------------------------
# module-level singleton

_MODEL_LOCK = threading.Lock()
_MODEL: Optional[CostModel] = None


def get_model() -> CostModel:
    global _MODEL
    if _MODEL is None:
        with _MODEL_LOCK:
            if _MODEL is None:
                model = CostModel()
                path = os.environ.get(ENV_PROFILE)
                if path:
                    try:
                        model.set_table(CostTable.load(path), strict=True)
                    except ProfileError as e:
                        import warnings
                        warnings.warn(f"ignoring {ENV_PROFILE}: {e}")
                _MODEL = model
    return _MODEL


def reset() -> None:
    """Drop all dispatch state (tests)."""
    global _MODEL
    with _MODEL_LOCK:
        _MODEL = None


def choose(op: str, shape: Optional[Mapping[str, object]] = None,
           *, impl: Optional[str] = None) -> str:
    return get_model().choose(op, shape, impl=impl)


def forced(**pins: str) -> _ForcedCtx:
    """Context manager pinning ops to impls, e.g.
    ``with perf.forced(logits="gather"): ...`` — the in-process analog
    of ``REPRO_DISPATCH`` (and what calibration uses to time each arm)."""
    return get_model().force(pins)


def dispatch_report() -> Dict[str, object]:
    return get_model().report()


def set_profile(table_or_path, *, strict: bool = True) -> Optional[CostTable]:
    """Install a profile (``CostTable`` or path).  ``strict`` raises on
    device-fingerprint mismatch; otherwise the profile is dropped and
    dispatch stays on the heuristics.  Returns the installed table."""
    model = get_model()
    table = (CostTable.load(table_or_path)
             if isinstance(table_or_path, str) else table_or_path)
    model.set_table(table, strict=strict)
    return model.table


def clear_profile() -> None:
    get_model().set_table(None)


def maybe_load_profile(path: Optional[str]) -> bool:
    """Best-effort profile install for launchers/benches: missing file,
    corrupt JSON, or wrong device ⇒ False and heuristic dispatch."""
    if not path or not os.path.exists(path):
        return False
    try:
        set_profile(path, strict=True)
    except ProfileError as e:
        import warnings
        warnings.warn(f"ignoring profile {path!r}: {e}")
        return False
    return True


# ---------------------------------------------------------------------------
# micro-batch sizing off the serve_score cost curve

# keep a smaller row bucket only when dispatching at it beats padding
# up to the next kept bucket by at least this margin — otherwise the
# bucket just costs an extra compiled shape
_ROW_BUCKET_MARGIN = 0.85

# a smaller drain cap must beat the bigger batch's cost-per-row by >10%
# to win: ties and measurement noise resolve to the LARGEST batch
# (bigger batches amortize per-dispatch overhead the curve can't see)
_LANE_CAP_TOLERANCE = 1.10


def _serve_curve(table: CostTable, base_shape: Dict[str, object],
                 candidates: Iterable[int]) -> Optional[Dict[int, float]]:
    curve = {}
    for rows in candidates:
        cost = table.lookup("serve_score", "fused",
                            dict(base_shape, rows=rows))
        if cost is None or cost <= 0:
            return None
        curve[rows] = cost
    return curve


def _pow2_candidates(max_batch: int) -> Tuple[int, ...]:
    out, r = [], 1
    top = _pow2_at_least(max_batch)
    while r <= top:
        out.append(r)
        r *= 2
    return tuple(out)


def suggest_row_buckets(
        k: int, b: int, scheme: str, max_batch: int,
        nnz_buckets: Iterable[int],
        table: Optional[CostTable] = None,
) -> Optional[Dict[int, Tuple[int, ...]]]:
    """Per-nnz-lane row buckets from the measured ``serve_score``
    cost-per-dispatch curve.  Buckets whose cost is within
    ``1 - _ROW_BUCKET_MARGIN`` of just padding up to the next size are
    pruned (fewer compiled shapes, bigger effective batches).  Returns
    None — caller keeps the static pow-2 grid — whenever the profile
    lacks full coverage."""
    table = table if table is not None else get_model().table
    if table is None or not table.matches_device():
        return None
    candidates = _pow2_candidates(max_batch)
    out: Dict[int, Tuple[int, ...]] = {}
    for m in nnz_buckets:
        base = {"k": k, "b": b, "scheme": scheme, "nnz": m}
        curve = _serve_curve(table, base, candidates)
        if curve is None:
            return None
        keep = [candidates[-1]]
        for rows in reversed(candidates[:-1]):
            if curve[rows] <= _ROW_BUCKET_MARGIN * curve[keep[0]]:
                keep.insert(0, rows)
        out[int(m)] = tuple(keep)
    return out


def suggest_lane_caps(
        k: int, b: int, scheme: str, max_batch: int,
        nnz_buckets: Iterable[int],
        table: Optional[CostTable] = None,
) -> Optional[Dict[int, int]]:
    """Throughput-optimal micro-batch per nnz lane: the LARGEST row
    bucket whose measured cost *per row* is within
    ``_LANE_CAP_TOLERANCE`` of the curve's best — noise and flat curves
    resolve to max batch.  None without full coverage."""
    table = table if table is not None else get_model().table
    if table is None or not table.matches_device():
        return None
    candidates = _pow2_candidates(max_batch)
    out: Dict[int, int] = {}
    for m in nnz_buckets:
        base = {"k": k, "b": b, "scheme": scheme, "nnz": m}
        curve = _serve_curve(table, base, candidates)
        if curve is None:
            return None
        best = min(curve[r] / r for r in candidates)
        out[int(m)] = max(r for r in candidates
                          if curve[r] / r <= best * _LANE_CAP_TOLERANCE)
    return out
