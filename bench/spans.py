"""The program's own host spans in a JAX profiler trace.

The program opens ``rrto.*`` spans (``repro.obs.host_span``) around its host
work: interception, the server's replay, the executable's launch, the wait
for its outputs, the batcher's stages.  This module reduces them, on the
threads that hold the harness's annotations (``bench.*``), to

- ``span_times``: per span name, how often it started inside the window,
  its total time and its self time (its time less that of the ``rrto.*``
  spans nested in it; JAX's own events are not subtracted), each span
  clipped to the window;
- ``idle_by_span``: the device's idle gaps put down to the innermost
  ``rrto.*`` or ``bench.*`` span open at each gap's midpoint, JAX's events
  ignored, so a transfer's wait is named for the program span it sat in.

The window and the device's busy intervals are ``bench.trace_reduce``'s.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from bench import trace_reduce

PROGRAM_PREFIX = "rrto."


def _annotated_lines(planes):
    """The host threads that hold a harness annotation."""
    for line in trace_reduce._host_lines(planes):
        if any(ev.name.startswith(trace_reduce.HOST_PREFIX)
               for ev in line.events):
            yield line


def _is_program(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIX)


def span_times(planes, lo: float, hi: float) -> Dict[str, dict]:
    """``{name: {"count", "total_s", "self_s"}}`` of the ``rrto.*`` spans
    that start in ``[lo, hi)``, each clipped to end by ``hi``."""
    out: Dict[str, dict] = {}

    def close(end: float, name: str, total: Optional[float],
              self_s: Optional[float]) -> None:
        if total is None:        # started outside the window
            return
        t = out.setdefault(name, {"count": 0, "total_s": 0.0, "self_s": 0.0})
        t["count"] += 1
        t["total_s"] += total
        t["self_s"] += self_s

    for line in _annotated_lines(planes):
        stack: List[list] = []   # [end, name, total, self] of open spans
        for name, s, e in sorted(trace_reduce._spans(line, _is_program),
                                 key=lambda x: (x[1], -x[2])):
            while stack and stack[-1][0] <= s:
                close(*stack.pop())
            dur = min(e, hi) - s if lo <= s < hi else None
            if dur is not None and stack and stack[-1][2] is not None:
                stack[-1][3] -= dur      # nested: not its parent's self time
            stack.append([e, name, dur, dur])
        while stack:
            close(*stack.pop())
    return out


def idle_by_span(planes, lo: float, hi: float) -> Dict[str, float]:
    """Device idle seconds in ``[lo, hi]`` by the innermost program or
    harness span open at each gap's midpoint (averaged over devices)."""
    spans = [sp for line in _annotated_lines(planes)
             for sp in trace_reduce._spans(
                 line, lambda n: _is_program(n)
                 or n.startswith(trace_reduce.HOST_PREFIX))]
    labels = trace_reduce._timeline(spans)
    starts = [a for a, _, _ in labels]
    devices = trace_reduce.device_ops(planes)
    out: Dict[str, float] = {}
    for ops in devices.values():
        busy = trace_reduce.union(
            [iv for _, s, e in ops
             if (iv := trace_reduce._clip(s, e, lo, hi)) is not None])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                label = trace_reduce._label_at(labels, starts, 0.5 * (s + e))
                out[label] = out.get(label, 0.0) + (e - s) / len(devices)
    return out


def reduce_spans(planes) -> Optional[dict]:
    """``{"spans", "idle_by_span"}`` over the window of the harness's
    annotations; None when the trace holds none."""
    annotations = trace_reduce.host_annotations(planes)
    if not annotations:
        return None
    lo = min(s for _, s, _ in annotations)
    hi = max(e for _, _, e in annotations)
    return {"spans": span_times(planes, lo, hi),
            "idle_by_span": idle_by_span(planes, lo, hi)}
