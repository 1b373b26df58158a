"""The kernel choice: which implementation ("arm") each op runs.

``choose(op, shape)`` is the one place the repo picks between a Pallas
kernel and its XLA fallback, and between compiled and interpreted
Pallas (docs/DESIGN.md §2).  The order is

    explicit ``impl=`` argument
  > a :func:`forced` pin (tests)
  > ``REPRO_DISPATCH`` env var (``"op=impl,op=impl"``)
  > the static rule

with *eligibility* applied before any of them: an arm the shape or the
backend cannot run (b outside the pack set, 2^b over the one-hot
kernel ceiling, non-pow-2 OPH bins, compiled Pallas off-TPU,
interpreted Pallas on TPU) is ignored rather than crashed into.
"""
from __future__ import annotations

import contextlib
import os
from typing import Callable, Dict, Mapping, NamedTuple, Optional, Tuple

import jax

from repro.kernels.fused_encode import PACK_BITS

# One-hot contraction kernel ceiling: past this vocabulary size the
# (k, 2^b) one-hot intermediate stops paying for itself.
BBIT_KERNEL_MAX_V = 4096
# The packed kernels' factored arm: a 2^16-entry table per hash
# function, each code carried by two smaller one-hots
# (kernels/bbit_linear.py).
BYTE_FACTORED_BITS = 16

ENV_DISPATCH = "REPRO_DISPATCH"


# ---------------------------------------------------------------------------
# shape buckets: the key a choice is recorded under


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
# eligibility


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def _oph_kernel_ok(shape: Mapping[str, object]) -> bool:
    # the OPH scatter-min kernel needs lane-aligned (pow-2) bins; the
    # jnp path covers arbitrary k
    if str(shape.get("scheme", "")).startswith("oph"):
        return _is_pow2(int(shape.get("k", 0)))
    return True


def _encode_eligible(shape) -> Tuple[str, ...]:
    return ("pallas", "xla") if _oph_kernel_ok(shape) else ("xla",)


def _encode_packed_eligible(shape) -> Tuple[str, ...]:
    ok = int(shape.get("b", 0)) in PACK_BITS and _oph_kernel_ok(shape)
    return ("pallas", "xla") if ok else ("xla",)


def _logits_eligible(shape) -> Tuple[str, ...]:
    ok = int(shape.get("v", 1 << 30)) <= BBIT_KERNEL_MAX_V
    return ("kernel", "gather") if ok else ("gather",)


def packed_kernel_fits(b: int, v: int) -> bool:
    """Whether the packed logits kernels take (b, V = 2^b): whole codes
    in a byte with V within the one-hot ceiling, or b = 16."""
    if b == BYTE_FACTORED_BITS:
        return v == 1 << b
    return b in PACK_BITS and v <= BBIT_KERNEL_MAX_V


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


def _hamming_topk_eligible(shape) -> Tuple[str, ...]:
    # only byte-aligned b flows through the packed retrieval hot path;
    # XLA ``population_count`` covers every other shape
    ok = int(shape.get("b", 0)) in PACK_BITS
    return ("pallas", "xla") if ok else ("xla",)


# ---------------------------------------------------------------------------
# the static rule


def _tpu_first(kernel_impl: str, fallback_impl: str):
    # scheme/model layers: kernels only where they compile
    # (interpret-mode Pallas on CPU is far slower than XLA)
    def rule(eligible) -> str:
        if jax.default_backend() == "tpu" and kernel_impl in eligible:
            return kernel_impl
        return fallback_impl
    return rule


def _capability_first(kernel_impl: str, fallback_impl: str):
    # ops layer: backend-independent — direct kernel callers (and their
    # tests) exercise the Pallas path on every backend
    def rule(eligible) -> str:
        return kernel_impl if kernel_impl in eligible else fallback_impl
    return rule


class Op(NamedTuple):
    arms: Tuple[str, ...]
    eligible: Callable[[Mapping[str, object]], Tuple[str, ...]]
    rule: Callable[[Tuple[str, ...]], str]


OPS: Dict[str, Op] = {
    # scheme-level encode: codes (int) out
    "encode": Op(("pallas", "xla"), _encode_eligible,
                 _tpu_first("pallas", "xla")),
    # scheme-level fused encode→pack: packed bytes out
    "encode_packed": Op(("pallas", "xla"), _encode_packed_eligible,
                        _tpu_first("pallas", "xla")),
    # model-level logits over widened int codes
    "logits": Op(("kernel", "gather"), _logits_eligible,
                 _tpu_first("kernel", "gather")),
    # model-level logits straight off packed bytes
    "logits_packed": Op(("kernel", "unpack"), _logits_packed_eligible,
                        _tpu_first("kernel", "unpack")),
    # the bwd choices inside the ops-layer custom_vjps
    "logits_bwd": Op(("kernel", "ref"), _logits_eligible,
                     _capability_first("kernel", "ref")),
    "logits_packed_bwd": Op(("kernel", "unpack"), _logits_packed_eligible,
                            _capability_first("kernel", "unpack")),
    # interpret vs compiled Pallas execution
    "pallas_mode": Op(("compiled", "interpret"), _pallas_mode_eligible,
                      _capability_first("compiled", "interpret")),
    # retrieval candidate scoring: packed-popcount Hamming + top-k
    "hamming_topk": Op(("pallas", "xla"), _hamming_topk_eligible,
                       _tpu_first("pallas", "xla")),
}


# ---------------------------------------------------------------------------
# choose + record

_pins: Dict[str, str] = {}       # op -> impl, set by ``forced``
_choices: Dict[str, str] = {}    # "op|bucket" -> impl, last choice


def _parse_env_dispatch(raw: str) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for part in raw.split(","):
        op, _, impl = part.strip().partition("=")
        if op.strip() and impl.strip():
            out[op.strip()] = impl.strip()
    return out


def choose(op: str, shape: Optional[Mapping[str, object]] = None,
           *, impl: Optional[str] = None) -> str:
    """The arm ``op`` runs at ``shape`` (module docstring's order)."""
    spec = OPS[op]
    shape = dict(shape or {})
    eligible = spec.eligible(shape)
    env = _parse_env_dispatch(os.environ.get(ENV_DISPATCH, "")).get(op)
    picked = next((arm for arm in (impl, _pins.get(op), env)
                   if arm in eligible), None)
    if picked is None:
        picked = spec.rule(eligible)
    _choices[f"{op}|{shape_bucket(shape)}"] = picked
    return picked


@contextlib.contextmanager
def forced(**pins: str):
    """Pin ops to arms, e.g. ``with dispatch.forced(logits="gather"):``
    — the in-process analog of ``REPRO_DISPATCH``."""
    for op in pins:
        if op not in OPS:
            raise KeyError(f"unknown dispatch op {op!r}")
    saved = {op: _pins.get(op) for op in pins}
    _pins.update(pins)
    try:
        yield
    finally:
        for op, prev in saved.items():
            if prev is None:
                _pins.pop(op, None)
            else:
                _pins[op] = prev


def dispatch_report() -> Dict[str, Dict[str, str]]:
    """``{"choices": {"<op>|<bucket>": impl}}``: the last arm each op
    ran with per shape bucket (read by chip_smoke.py and the
    benchmark's ``arms`` record)."""
    return {"choices": dict(_choices)}


def reset() -> None:
    """Drop the pins and the recorded choices (tests, per-phase
    records)."""
    _pins.clear()
    _choices.clear()
