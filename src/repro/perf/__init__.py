"""Observability: named host spans (``repro.perf.spans``) and the
record of the arm each dispatched op ran with."""
# the kernel choice lives with the kernels; its record is read here
from repro.kernels.dispatch import dispatch_report

__all__ = ["dispatch_report"]
