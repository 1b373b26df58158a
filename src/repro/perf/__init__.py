"""Measured cost-model dispatch (docs/DESIGN.md §2).

``perf.choose(op, shape)`` is the single selection point for every
implementation choice in the repo; ``perf.calibrate`` populates the
measured :class:`CostTable` behind it.
"""
from repro.perf.cost_model import (
    BBIT_KERNEL_MAX_V,
    ENV_DISPATCH,
    ENV_PROFILE,
    OPS,
    CostTable,
    ProfileError,
    choose,
    clear_profile,
    device_fingerprint,
    dispatch_report,
    fingerprint_key,
    forced,
    get_model,
    maybe_load_profile,
    packed_kernel_fits,
    reset,
    set_profile,
    shape_bucket,
    suggest_lane_caps,
    suggest_row_buckets,
)

__all__ = [
    "BBIT_KERNEL_MAX_V", "ENV_DISPATCH", "ENV_PROFILE", "OPS",
    "CostTable", "ProfileError", "choose", "clear_profile",
    "device_fingerprint", "dispatch_report", "fingerprint_key", "forced",
    "get_model", "maybe_load_profile", "packed_kernel_fits", "reset",
    "set_profile",
    "shape_bucket", "suggest_lane_caps", "suggest_row_buckets",
    "calibrate", "summarize",
]


# ``calibrate``/``summarize`` live in the submodule of the same name;
# importing it lazily keeps jax-heavy benchmark code off the critical
# import path.  The import machinery binds the *submodule* over the
# package attribute, so after the first resolution we pin the functions
# into globals() — otherwise perf.calibrate(...) would only work once.
def __getattr__(name):
    if name in ("calibrate", "summarize"):
        import importlib
        mod = importlib.import_module("repro.perf.calibrate")
        globals()["calibrate"] = mod.calibrate
        globals()["summarize"] = mod.summarize
        return getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
