"""Reduce a JAX profiler trace (``.xplane.pb``) to the device's busy and idle
time, the time of each device operation, and the idle time by what the host
was doing.

The traced window is the span of the harness's own host annotations (names
starting with ``bench.``).  Busy time is the union of the intervals in which
an operation ran on a device (its ``XLA Ops`` line), clipped to the window
and averaged over the devices that ran any.  An op is named by its HLO
instruction (``fusion.37``, ``decode_attention.6``); a ``while`` op spans
the ops of its body, which the union counts once.  Every stretch of the
window in which a device ran nothing is an idle gap; each gap is put down to
the innermost host event open at its midpoint on the thread that holds the
harness's annotations: a JAX event such as a dispatch or a transfer, or else
the harness annotation itself, which then means the program's own Python
(``host idle`` when nothing was open).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
NO_ANNOTATION = "host idle"

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: float, e: float, lo: float, hi: float) -> Optional[Interval]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _events(plane, line_name: Optional[str] = None):
    for line in plane.lines:
        if line_name is not None and line.name != line_name:
            continue
        for ev in line.events:
            yield line.name, ev


def op_name(name: str) -> str:
    """``%fusion.37 = bf16[...] fusion(...)`` -> ``fusion.37``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _host_lines(planes):
    for plane in planes:
        if plane.name.startswith("/host:"):
            yield from plane.lines


def _spans(line, keep=lambda name: True) -> List[Tuple[str, float, float]]:
    return [(ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
            for ev in line.events if keep(ev.name)]


def host_annotations(planes) -> List[Tuple[str, float, float]]:
    """(name, start_s, end_s) of every harness annotation on the host."""
    return [span for line in _host_lines(planes)
            for span in _spans(line, lambda n: n.startswith(HOST_PREFIX))]


def host_activity(planes) -> List[Tuple[str, float, float]]:
    """Every host event on the threads that hold harness annotations."""
    out = []
    for line in _host_lines(planes):
        spans = _spans(line)
        if any(n.startswith(HOST_PREFIX) for n, _, _ in spans):
            out.extend((n[:80], s, e) for n, s, e in spans if e > s)
    return out


def device_ops(planes) -> Dict[str, List[Tuple[str, float, float]]]:
    """Device plane name -> (op name, start_s, end_s) of its XLA ops."""
    out: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops = [(op_name(ev.name), ev.start_ns * 1e-9,
                (ev.start_ns + ev.duration_ns) * 1e-9)
               for _, ev in _events(plane, OPS_LINE)]
        if ops:
            out[plane.name] = ops
    return out


def reduce_planes(planes) -> Optional[dict]:
    """The reduction of one trace's planes; None when the trace holds no
    harness annotation or no device operation."""
    annotations = host_annotations(planes)
    activity = host_activity(planes)
    devices = device_ops(planes)
    if not annotations or not devices:
        return None
    labels = _timeline(activity)
    starts = [a for a, _, _ in labels]
    lo = min(s for _, s, _ in annotations)
    hi = max(e for _, _, e in annotations)
    window = hi - lo
    op_seconds: Dict[str, float] = {}
    busy_total = 0.0
    idle_by_host: Dict[str, float] = {}
    for ops in devices.values():
        clipped = []
        for name, s, e in ops:
            iv = _clip(s, e, lo, hi)
            if iv is None:
                continue
            clipped.append(iv)
            op_seconds[name] = op_seconds.get(name, 0.0) + iv[1] - iv[0]
        busy = union(clipped)
        busy_total += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            label = _label_at(labels, starts, 0.5 * (s + e))
            idle_by_host[label] = idle_by_host.get(label, 0.0) + e - s
    n = len(devices)
    op_seconds = {k: v / n for k, v in op_seconds.items()}
    idle_by_host = {k: v / n for k, v in idle_by_host.items()}
    busy_s = busy_total / n
    return {
        "window_s": window,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window if window > 0 else None,
        "devices": n,
        "op_seconds": op_seconds,
        "idle_by_host": idle_by_host,
    }


def _timeline(spans) -> List[Tuple[float, float, str]]:
    """Cut nested host spans into non-overlapping (start, end, name)
    pieces, each named for the innermost span open over it."""
    pieces = []
    stack: List[Tuple[float, str]] = []   # (end, name) of open spans
    cur = None
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            end, open_name = stack.pop()
            pieces.append((cur, end, open_name))
            cur = end
        if stack:
            pieces.append((cur, s, stack[-1][1]))
        stack.append((e, name))
        cur = s
    while stack:
        end, open_name = stack.pop()
        pieces.append((cur, end, open_name))
        cur = end
    return [(a, b, n) for a, b, n in pieces if b > a]


def _label_at(pieces, starts, t: float) -> str:
    """The innermost host event open at time ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and pieces[i][0] <= t <= pieces[i][1]:
        return pieces[i][2]
    return NO_ANNOTATION


def reduce_dir(trace_dir: str) -> Optional[dict]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    return reduce_planes(list(data.planes))


def kernel_seconds(trace: dict, kernel: str) -> float:
    """Device seconds of the ops named for ``kernel`` (the kernel's name,
    optionally followed by a numeric suffix such as ``.3``)."""
    pat = re.compile(rf"^{re.escape(kernel)}(\.\d+)*$")
    return sum(v for k, v in trace["op_seconds"].items() if pat.match(k))


def top_ops(trace: dict, n: int = 10) -> List[List]:
    return [[k, v] for k, v in
            sorted(trace["op_seconds"].items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, n: int = 10) -> List[List]:
    return [[k, v] for k, v in
            sorted(trace["idle_by_host"].items(), key=lambda kv: -kv[1])[:n]]
