"""From a profiler trace (``.xplane.pb``) to the numbers the readers take.

Read by hand first (my chip run, PR 24, libtpu 0.0.34): a device is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds the TensorCore's operations
one after another (the event's name is the HLO instruction's whole text) and
whose line ``XLA Modules`` holds one event per execution of a program
(``jit__run_positional(<id>)``). Host threads are lines of ``/host:CPU``; the
benchmark's own ``jax.profiler.TraceAnnotation`` around each call lands on the
caller's line (named after the interpreter: ``python``, ``python3``) beside
the Python tracer's function events. Host and device events share one clock
(to about 0.3 ms). An idle gap inside a call is named by the innermost Python
frame at its middle and, after a bar, by the runtime's own host event that
overlaps it longest. ``Async XLA Ops`` (copies in flight under other
operations) are left out of busy time: they overlap the operation stream.

An operation counts as a matrix product when its text says so itself: a
``convolution`` or ``dot`` instruction, or a fusion of ``kind=kOutput``, which
is how this compiler marks a fusion built around a convolution or dot (the
feed-forward pair, the attention scores and the context product all arrive
that way). This trace carries no category stat to read instead.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

CALL_ANNOTATION = "bench.call"
_OPS_LINE, _MODULES_LINE, _HOST_PLANE = "XLA Ops", "XLA Modules", "/host:CPU"
_SHORT_GAP_S = 20e-6  # shorter gaps are the device's own, not the host's
_MATMUL = re.compile(r"^%(convolution|dot)[\w.-]*\s|kind=kOutput")
_NAME = re.compile(r"^%([A-Za-z_-]+?)[\d.]*\s*=\s*(\(?[a-z0-9]+\[[\d,]*\])?")


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` that a merged list covers."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged
               if b > lo and a < hi)


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """What of ``[lo, hi]`` a merged list leaves uncovered."""
    out, t = [], lo
    for a, b in merged:
        if b <= lo:
            continue
        if a >= hi:
            break
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def is_matmul(op_text: str) -> bool:
    return _MATMUL.search(op_text) is not None


def op_label(op_text: str) -> str:
    """``%fusion.1815 = (f32[256,512]{..}, ..) fusion(..), kind=kOutput`` ->
    ``fusion:kOutput (f32[256,512]``: the ops of twelve layers share it."""
    m = _NAME.match(op_text)
    if m is None:
        return op_text[:60]
    kind = re.search(r"kind=(k\w+)", op_text)
    return (m.group(1) + (":" + kind.group(1) if kind else "")
            + (" " + m.group(2) if m.group(2) else ""))


def _events(line) -> List[Tuple[float, float, str]]:
    return [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
            for e in line.events]


def load(path: str) -> Dict[str, Dict[str, List[Tuple[float, float, str]]]]:
    """plane name -> line name -> [(start s, end s, name)]."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes: Dict[str, Dict[str, list]] = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            # a device's other lines are not read, and reading is the slow part
            if plane.name == _HOST_PLANE or line.name in (_OPS_LINE,
                                                          _MODULES_LINE):
                lines.setdefault(line.name, []).extend(_events(line))
    return planes


def _innermost(host_events: Sequence[Tuple[float, float, str]], t: float,
               skip: str) -> Optional[str]:
    best, best_len = None, float("inf")
    for lo, hi, name in host_events:
        if lo <= t <= hi and hi - lo < best_len and name != skip:
            best, best_len = name, hi - lo
    return best


def _busiest(host: Dict[str, list], skip_line: str, lo: float, hi: float
             ) -> Optional[str]:
    """The runtime's own host event (any line but the caller's) that overlaps
    ``[lo, hi]`` longest: ``Linearize``, a transfer, an allocation."""
    best, best_len = None, 0.0
    for line, events in host.items():
        if line == skip_line:
            continue
        for a, b, name in events:
            part = min(hi, b) - max(lo, a)
            if part > best_len:
                best, best_len = name, part
    return best


def add(totals: Dict[str, float], key: str, seconds: float) -> None:
    if seconds > 0:
        totals[key] = totals.get(key, 0.0) + seconds


def reduce_planes(planes: Dict[str, Dict[str, list]], program: str,
                  call_name: str = CALL_ANNOTATION) -> Optional[dict]:
    """The traced window's account, or ``None`` when the trace holds no call
    annotation or no device operation (nothing to read)."""
    host = planes.get(_HOST_PLANE, {})
    # the caller's thread is the line that holds the annotations; it is named
    # after the interpreter (``python``, ``python3``), so not found by name
    caller = next((line for line, events in host.items()
                   if any(name == call_name for _, _, name in events)), "")
    calls = sorted((lo, hi) for lo, hi, name in host.get(caller, [])
                   if name == call_name)
    devices = {name: lines for name, lines in planes.items()
               if name.startswith("/device:") and lines.get(_OPS_LINE)}
    if not calls or not devices:
        return None
    w_lo, w_hi = calls[0][0], calls[-1][1]
    python_line = [e for e in host[caller] if e[1] > w_lo and e[0] < w_hi]

    busy_s, executions, labels = [], [], {}
    idle_by_host: Dict[str, float] = {}
    call_idle = [0.0] * len(calls)
    for lines in devices.values():
        ops = [(lo, hi, text) for lo, hi, text in lines[_OPS_LINE]
               if hi > w_lo and lo < w_hi]
        busy = merge((lo, hi) for lo, hi, _ in ops)
        busy_s.append(covered(busy, w_lo, w_hi))
        for i, (lo, hi) in enumerate(calls):
            call_idle[i] += (hi - lo) - covered(busy, lo, hi)
        for lo, hi, text in ops:
            add(labels, op_label(text), min(hi, w_hi) - max(lo, w_lo))
        for lo, hi, name in lines.get(_MODULES_LINE, []):
            # by its midpoint: the two clocks agree to microseconds only
            if program not in name or not w_lo < 0.5 * (lo + hi) < w_hi:
                continue
            inside = [(a, b, t) for a, b, t in ops if a >= lo and b <= hi]
            executions.append({
                "busy_s": covered(merge((a, b) for a, b, _ in inside), lo, hi),
                "matmul_s": sum(b - a for a, b, t in inside if is_matmul(t)),
                "ops": len(inside)})
        for lo, hi in gaps(busy, w_lo, w_hi):
            if hi - lo < _SHORT_GAP_S:
                add(idle_by_host, "between_ops (each under 20 us)", hi - lo)
                continue
            in_calls = 0.0
            for a, b in calls:  # a gap that spans a call's end is split there
                part = min(hi, b) - max(lo, a)
                if part > 0:
                    frame = _innermost(python_line, max(lo, a) + 0.5 * part,
                                       call_name)
                    doing = _busiest(host, caller, max(lo, a), min(hi, b))
                    add(idle_by_host, f"in_call: {frame or '?'}"
                        + (f" | {doing[:60]}" if doing else ""), part)
                    in_calls += part
            add(idle_by_host, "between_calls", (hi - lo) - in_calls)

    n_dev = len(devices)

    def top(totals):
        return sorted(([k, v] for k, v in totals.items()),
                      key=lambda kv: -kv[1])[:10]

    return {
        "window_s": w_hi - w_lo,
        "busy_s": sum(busy_s) / n_dev,
        "devices": n_dev,
        "calls": len(calls),
        "call_idle_s": [v / n_dev for v in call_idle],
        "executions": executions,
        "device_ops": top(labels),
        "idle_gaps": top(idle_by_host),
    }


def reduce_trace(path: str, program: str) -> Optional[dict]:
    return reduce_planes(load(path), program)
