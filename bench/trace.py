"""Profiler trace → the numbers the per-layer metrics read.

``Tracer`` records the measured window with the JAX profiler (host spans
of the benchmark's own files plus every operation on the device).
``summarize`` reduces the ``.xplane.pb`` it wrote:

* the window: the host span ``bench.window``;
* per device: the union of its operations' intervals inside the window
  (busy), the device time of each operation name, the time in which a
  collective ran while no other operation did;
* the idle gaps of the first device, each put down to the benchmark
  span (other than the window itself) that covers most of it, and to the
  host event of the program (under half a second long, on any thread)
  that covers most of it.

Nothing here reads the program: operation names are matched by the
metric readers against the kernels' names.
"""
from __future__ import annotations

import dataclasses
import heapq
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"allreduce|allgather|psum", re.IGNORECASE)
TOP = 10
# host events longer than this (whole calls, threads' lifetimes) say
# nothing about what the host did during one idle gap
HOST_EVENT_MAX_NS = 500_000_000


def op_name(text: str) -> str:
    """An operation's HLO instruction name: the trace names an op by its
    whole instruction text ("%fusion.3 = f32[...] fusion(...)")."""
    return text.split(" = ", 1)[0].lstrip("%")


def _cover(gaps, events):
    """For each gap (sorted, disjoint) the name of the event that
    overlaps it most (the shorter one on a tie), or None.  ``events``
    are (start, end, name) sorted by start; one sweep with the events
    still open kept in a heap by their end."""
    out, active, i = [], [], 0
    for lo, hi in gaps:
        while i < len(events) and events[i][0] < hi:
            heapq.heappush(active, (events[i][1], i))
            i += 1
        while active and active[0][0] <= lo:
            heapq.heappop(active)
        best, cover, size = None, 0, 0
        for end, j in active:
            s0, s1, name = events[j]
            c = min(hi, s1) - max(lo, s0)
            if c > cover or (c == cover and c > 0 and s1 - s0 < size):
                best, cover, size = name, c, s1 - s0
        out.append(best)
    return out


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def _length(intervals) -> int:
    return sum(hi - lo for lo, hi in intervals)


def _subtract(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Length of the merged intervals ``a`` not covered by merged ``b``."""
    total, j = 0, 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            total += hi - cur
    return total


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                          # mean over the devices used
    op_s: Dict[str, float]                 # device seconds per op name,
    #                                        mean over the devices used
    collective_exposed_s: Optional[float]  # mean over the devices used;
    #                                        None when no collective ran
    idle_gaps: List[Tuple[str, float]]     # (host span, seconds), largest
    devices: int

    def op_seconds(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for name, s in self.op_s.items() if rx.search(name))

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:TOP]]}


def summarize(path: str, devices: int) -> Summary:
    """Reads an ``.xplane.pb`` written by the JAX profiler."""
    import jax
    return summarize_planes(jax.profiler.ProfileData.from_file(path).planes,
                            devices, path)


def summarize_planes(planes, devices: int, where: str = "trace") -> Summary:
    """Planes with ``name`` and ``lines``; lines with ``name`` and
    ``events``; events with ``name``, ``start_ns`` and ``duration_ns``."""
    spans: List[Tuple[str, int, int]] = []
    host: List[Tuple[int, int, str]] = []
    dev_ops: Dict[int, List[Tuple[str, int, int]]] = {}
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            idx = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                dev_ops.setdefault(idx, []).extend(
                    (op_name(e.name), int(e.start_ns),
                     int(e.start_ns + e.duration_ns)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    lo, hi = int(e.start_ns), int(e.start_ns + e.duration_ns)
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, lo, hi))
                    elif hi - lo <= HOST_EVENT_MAX_NS:
                        host.append((lo, hi, e.name))
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in {where}")
    _, w0, w1 = max(windows, key=lambda s: s[2] - s[1])
    used = sorted(dev_ops)[:devices]
    if not used:
        raise ValueError(f"no device operations in {where}")

    busy, op_s, exposed, collectives = [], {}, [], 0
    gaps: List[Tuple[int, int]] = []
    for n, d in enumerate(used):
        clipped = [(name, max(lo, w0), min(hi, w1))
                   for name, lo, hi in dev_ops[d] if hi > w0 and lo < w1]
        merged = _merge([(lo, hi) for _, lo, hi in clipped])
        busy.append(_length(merged))
        for name, lo, hi in clipped:
            op_s[name] = op_s.get(name, 0) + (hi - lo)
        coll = _merge([(lo, hi) for name, lo, hi in clipped
                       if COLLECTIVE.search(name)])
        comp = _merge([(lo, hi) for name, lo, hi in clipped
                       if not COLLECTIVE.search(name)])
        exposed.append(_subtract(coll, comp))
        collectives += len(coll)
        if n == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    nd = len(used)
    named: Dict[str, float] = {}
    inner = sorted((s0, s1, n) for n, s0, s1 in spans if n != WINDOW_SPAN)
    host.sort()
    for (lo, hi), span, event in zip(gaps, _cover(gaps, inner),
                                     _cover(gaps, host)):
        label = span or "host (no benchmark span)"
        if event is not None:
            label = f"{label}: {event}"
        named[label] = named.get(label, 0.0) + (hi - lo) / 1e9
    return Summary(
        window_s=(w1 - w0) / 1e9,
        busy_s=sum(busy) / nd / 1e9,
        op_s={k: v / nd / 1e9 for k, v in op_s.items()},
        collective_exposed_s=(sum(exposed) / nd / 1e9 if collectives
                              else None),
        idle_gaps=sorted(named.items(), key=lambda kv: -kv[1]),
        devices=nd)


def find_xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


class Tracer:
    """The JAX profiler around the measured window, host spans on."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def summary(self, devices: int) -> Summary:
        path = find_xplane(self.log_dir)
        if path is None:
            raise FileNotFoundError(f"no trace written under {self.log_dir}")
        return summarize(path, devices)
