"""Named host spans of the program, in the JAX profiler's own trace.

Every span is a ``jax.profiler.TraceAnnotation``: with no trace being
taken it costs about a microsecond and records nothing; under
``jax.profiler.trace`` (or ``start_trace``) it lands in the same
``.xplane.pb`` as the device's operations, on the clock the device
planes are aligned to.  The profiler keeps the events and writes them
when the trace stops: there is no buffer or timer here.

Names follow one rule, ``repro.<layer>.<what>`` (``repro.train.step``,
``repro.prefetch.wait``, ``repro.ckpt.save``; docs/DESIGN.md
"Tracing" lists them all).  Keyword arguments (``step``, ``epoch``,
``shard``, ``n``) become the event's stats and tie it to its step or
shard.
"""
from __future__ import annotations

import jax

STEP = "repro.train.step"


def span(name: str, **args):
    """A host span named ``name`` (``repro.<layer>.<what>``)."""
    return jax.profiler.TraceAnnotation(name, **args)


def step_span(step: int):
    """The span of one training step, marked as a step for the
    profiler's step view (XProf / TensorBoard)."""
    return jax.profiler.StepTraceAnnotation(STEP, step_num=step)
