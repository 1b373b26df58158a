"""The device's idle time, put down to the program span the training
loop was in.

The program records its own host spans (``repro.<layer>.<what>``,
``src/repro/perf/spans.py``) in the same profiler trace as the device's
operations.  ``summarize_planes`` reduces such a trace:

* the device idle time of the window, computed as ``bench/trace.py``
  computes it (the first device used, its operations clipped to the
  ``bench.window`` span, merged; idle is the rest of the window);
* ``idle_by_span``: that idle time put down to the innermost ``repro.*``
  span covering it on the training loop's thread (the host line that
  holds ``repro.train.step``).  A gap that straddles span edges is split
  at those edges, so the parts sum exactly to the idle time; idle under
  no program span goes under ``(none)``;
* ``wait_causes``: the idle under ``repro.prefetch.wait``, put down to
  the producer's span (another thread) that overlaps each piece most;
* ``span_ns``: per span name, its count and time in the window, across
  all threads;
* the device → host reads the loop made (the ``n`` of its
  ``repro.train.hits_drain`` and ``repro.ckpt.read_device`` spans) and
  its steps (``repro.train.step`` spans) in the window.

``metrics`` turns that into the seven training metrics (six parts of
``train.device_idle`` and the reads per step); a trace without program
spans gives none.  ``python3 -m bench.spans <trace dir or .xplane.pb>``
prints them with the ten largest idle parts.

Nothing here reads the program: span names are matched as strings.
"""
from __future__ import annotations

import dataclasses
import json
import sys
from typing import Dict, List, Optional, Tuple

from bench import trace as tr

PROGRAM_PREFIX = "repro."
STEP_SPAN = "repro.train.step"
WAIT_SPAN = "repro.prefetch.wait"
READ_SPANS = ("repro.train.hits_drain", "repro.ckpt.read_device")
NONE = "(none)"
TOP = 10

# the parts of train.device_idle, each with the innermost loop spans it
# takes; idle under no program span, or under one no part names, is
# train.idle_unattributed
IDLE_PARTS = {
    "train.idle_input": ("repro.prefetch.",),
    "train.idle_dispatch": ("repro.train.step",),
    "train.idle_drain": ("repro.train.hits_drain",),
    "train.idle_ckpt": ("repro.ckpt.",),
    "train.idle_job_edges": ("repro.train.job_setup", "repro.train.job_end"),
}
UNATTRIBUTED = "train.idle_unattributed"
READS_PER_STEP = "train.device_reads_per_step"


def idle_part(span: str) -> str:
    """The metric whose part of the idle time ``span`` is: a name that
    ends in ``.`` takes every span it begins, others match exactly."""
    for metric, names in IDLE_PARTS.items():
        if any(span.startswith(n) if n.endswith(".") else span == n
               for n in names):
            return metric
    return UNATTRIBUTED


@dataclasses.dataclass
class SpanSummary:
    window_ns: int
    idle_ns: int                           # first device used
    idle_by_span: Dict[str, int]           # innermost loop span → idle ns
    wait_causes: Dict[str, int]            # producer span → idle ns
    span_ns: Dict[str, Tuple[int, int]]    # name → (count, ns in window)
    device_reads: int
    steps: int

    def idle_spans(self) -> List[list]:
        """The largest idle parts, in seconds, largest first; the idle
        under ``repro.prefetch.wait`` split by the producer span that
        caused it (``repro.prefetch.wait: <producer span>``)."""
        parts = {k: v for k, v in self.idle_by_span.items()
                 if k != WAIT_SPAN}
        for cause, ns in self.wait_causes.items():
            parts[f"{WAIT_SPAN}: {cause}"] = ns
        top = sorted(parts.items(), key=lambda kv: -kv[1])[:TOP]
        return [[name, ns / 1e9] for name, ns in top]


def _innermost(events: List[Tuple[int, int, str]]):
    """Spans of one thread (nested, as one thread's spans are) → the
    disjoint segments each covered by its innermost span, in order."""
    segs: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []      # (end, name), innermost last
    cur = None
    for lo, hi, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= lo:
            end, top = stack.pop()
            if end > cur:
                segs.append((cur, end, top))
                cur = end
        if stack:
            if lo > cur:
                segs.append((cur, lo, stack[-1][1]))
            hi = min(hi, stack[-1][0])     # a child ends with its parent
        cur = lo
        stack.append((hi, name))
    while stack:
        end, top = stack.pop()
        if end > cur:
            segs.append((cur, end, top))
            cur = end
    return segs


def _split(gaps, segs):
    """Each gap cut at the segments' edges: (lo, hi, name or None)."""
    out, j = [], 0
    for lo, hi in gaps:
        while j < len(segs) and segs[j][1] <= lo:
            j += 1
        cur, k = lo, j
        while k < len(segs) and segs[k][0] < hi:
            s0, s1, name = segs[k]
            if s0 > cur:
                out.append((cur, s0, None))
            a, b = max(cur, s0), min(hi, s1)
            if b > a:
                out.append((a, b, name))
            cur = max(cur, b)
            k += 1
        if cur < hi:
            out.append((cur, hi, None))
    return out


def _stat(event, key: str) -> Optional[int]:
    for name, value in getattr(event, "stats", ()):
        if name == key:
            return int(value)
    return None


def summarize_planes(planes) -> Optional[SpanSummary]:
    """Planes as ``bench/trace.py::summarize_planes`` takes them (events
    may carry ``stats``, pairs of name and value).  None when the trace
    holds no ``repro.train.step`` span."""
    window = None
    dev_ops: Dict[int, List[Tuple[int, int]]] = {}
    lines: List[List[Tuple[int, int, str, object]]] = []
    for plane in planes:
        if tr.DEVICE_PLANE.match(plane.name):
            idx = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    dev_ops.setdefault(idx, []).extend(
                        (int(e.start_ns), int(e.start_ns + e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = []
                for e in line.events:
                    lo = int(e.start_ns)
                    hi = int(e.start_ns + e.duration_ns)
                    if e.name == tr.WINDOW_SPAN:
                        if window is None or hi - lo > window[1] - window[0]:
                            window = (lo, hi)
                    elif e.name.startswith(PROGRAM_PREFIX):
                        spans.append((lo, hi, e.name, e))
                if spans:
                    lines.append(spans)
    if window is None:
        raise ValueError(f"no {tr.WINDOW_SPAN} span in the trace")
    if not dev_ops:
        raise ValueError("no device operations in the trace")
    w0, w1 = window

    def steps_on(spans):
        return sum(1 for lo, _, name, _ in spans
                   if name == STEP_SPAN and w0 <= lo < w1)

    loop = max(lines, key=steps_on, default=None)
    if loop is None or steps_on(loop) == 0:
        return None

    ops = [(max(lo, w0), min(hi, w1)) for lo, hi in dev_ops[min(dev_ops)]
           if hi > w0 and lo < w1]
    edges = [w0] + [x for iv in tr._merge(ops) for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]

    pieces = _split(gaps, _innermost([s[:3] for s in loop]))
    idle_by_span: Dict[str, int] = {}
    waits = []
    for lo, hi, name in pieces:
        key = name or NONE
        idle_by_span[key] = idle_by_span.get(key, 0) + (hi - lo)
        if name == WAIT_SPAN:
            waits.append((lo, hi))
    producer = sorted(s[:3] for spans in lines if spans is not loop
                      for s in spans)
    wait_causes: Dict[str, int] = {}
    for (lo, hi), cause in zip(waits, tr._cover(waits, producer)):
        key = cause or NONE
        wait_causes[key] = wait_causes.get(key, 0) + (hi - lo)

    span_ns: Dict[str, Tuple[int, int]] = {}
    reads = 0
    for spans in lines:
        for lo, hi, name, e in spans:
            if hi <= w0 or lo >= w1:
                continue
            count, ns = span_ns.get(name, (0, 0))
            span_ns[name] = (count + 1, ns + min(hi, w1) - max(lo, w0))
            if name in READ_SPANS and lo >= w0:
                reads += _stat(e, "n") or 0
    return SpanSummary(window_ns=w1 - w0, idle_ns=sum(b - a for a, b in gaps),
                       idle_by_span=idle_by_span, wait_causes=wait_causes,
                       span_ns=span_ns, device_reads=reads,
                       steps=steps_on(loop))


def summarize(path: str) -> Optional[SpanSummary]:
    """Reads an ``.xplane.pb`` written by the JAX profiler."""
    import jax
    return summarize_planes(jax.profiler.ProfileData.from_file(path).planes)


def metrics(s: Optional[SpanSummary]) -> Dict[str, float]:
    """The seven training metrics, in % of the window (reads per step
    for ``train.device_reads_per_step``); none without program spans."""
    if s is None or s.window_ns <= 0:
        return {}
    out = {m: 0.0 for m in list(IDLE_PARTS) + [UNATTRIBUTED]}
    for name, ns in s.idle_by_span.items():
        out[idle_part(name)] += 100.0 * ns / s.window_ns
    out[READS_PER_STEP] = s.device_reads / s.steps
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 -m bench.spans <trace dir or .xplane.pb>",
              file=sys.stderr)
        return 2
    path = argv[0]
    if not path.endswith(".xplane.pb"):
        path = tr.find_xplane(path)
        if path is None:
            print(f"no .xplane.pb under {argv[0]}", file=sys.stderr)
            return 1
    s = summarize(path)
    if s is None:
        print(json.dumps({"trace": path, "metrics": {}}))
        return 0
    print(json.dumps({
        "trace": path, "metrics": metrics(s),
        "device_idle": 100.0 * s.idle_ns / s.window_ns,
        "window_s": s.window_ns / 1e9, "steps": s.steps,
        "device_reads": s.device_reads, "idle_spans": s.idle_spans(),
        "span_s": {k: [c, ns / 1e9] for k, (c, ns) in
                   sorted(s.span_ns.items())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
