"""Reduce a JAX profiler trace to device busy time, idle gaps and
kernel time.

Busy time is the union of the intervals in which an operation ran on a
device, clipped to the measured window (the host span ``bench.window``
the harness writes), averaged over the chips in use. Idle gaps are the
rest of the window; each long gap is named by the host event that
overlaps it most, so a gap reads as what the host was doing meanwhile.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
#: The line of a TPU plane that holds one event per executed operation.
OPS_LINE = "XLA Ops"
#: Host events that say nothing about what the host was doing.
_HOST_NOISE = ("ThreadpoolListener", WINDOW_SPAN)


@dataclasses.dataclass
class DeviceTrace:
    window_s: float
    busy_s: float                     # averaged over the chips in use
    chips: int
    op_seconds: Dict[str, float]      # own time by full op name, all chips
    gaps: List[Tuple[str, float]]     # longest idle gaps first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_seconds(self, names: Iterable[str]) -> float:
        """Device time of the ops whose name contains one of ``names``."""
        names = tuple(names)
        return sum(s for op, s in self.op_seconds.items()
                   if any(k in op for k in names))

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        """The ops that took most device time (own time, by short name)."""
        by_name: Dict[str, float] = {}
        for op, sec in self.op_seconds.items():
            key = short_name(op)
            by_name[key] = by_name.get(key, 0.0) + sec
        return sorted(by_name.items(), key=lambda kv: -kv[1])[:k]


def union_length(intervals: List[Tuple[float, float]]) -> float:
    """Total length covered by possibly overlapping [start, end) pairs."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals: List[Tuple[float, float]], w0: float,
              w1: float) -> List[Tuple[float, float]]:
    """The parts of [w0, w1) that no interval covers."""
    gaps, cursor = [], w0
    for s, e in sorted(intervals):
        if s > cursor:
            gaps.append((cursor, min(s, w1)))
        cursor = max(cursor, e)
        if cursor >= w1:
            break
    if cursor < w1:
        gaps.append((cursor, w1))
    return [(s, e) for s, e in gaps if e > s]


def short_name(op: str) -> str:
    """An XLA op event's name without its operands: ``%fusion.3``, or
    ``%custom-call.2 <target>`` for a custom call."""
    head = op.split(" = ", 1)[0]
    marker = 'custom_call_target="'
    if marker in op:
        head += " " + op.split(marker, 1)[1].split('"', 1)[0]
    return head


def self_times(events: List[Tuple[float, float, str]]):
    """[(start, end, name, self_ns)]: an op's own time, less the ops
    nested in it on the same line (a loop and the ops of its body)."""
    out, stack = [], []
    for s, e, n in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        rec = [s, e, n, e - s]
        if stack and e <= stack[-1][1]:
            stack[-1][3] -= e - s
        out.append(rec)
        stack.append(rec)
    return out


def reduce(planes, top_gaps: int = 10) -> Optional[DeviceTrace]:
    """``planes``: ``[(plane_name, [(line_name, [(name, start_ns,
    duration_ns), ...]), ...]), ...]``. None where the trace holds no
    window span or no device plane."""
    window = None
    host_events: List[Tuple[float, float, str]] = []
    devices: List[List[Tuple[float, float, str]]] = []
    for plane_name, lines in planes:
        if plane_name.startswith("/device:"):
            ops = [evs for line, evs in lines if line == OPS_LINE]
            if ops:
                devices.append([(s, s + d, n) for n, s, d in ops[0]])
            continue
        if not plane_name.startswith("/host:"):
            continue
        for _line, evs in lines:
            for name, s, d in evs:
                if name == WINDOW_SPAN:
                    window = (s, s + d)
                elif d > 0 and not name.startswith(_HOST_NOISE):
                    host_events.append((s, s + d, name))
    if window is None or not devices:
        return None
    w0, w1 = window
    busy_total, op_ns = 0.0, {}
    all_busy = []
    for events in devices:
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in events
                   if e > w0 and s < w1]
        busy_total += union_length([(s, e) for s, e, _ in clipped])
        all_busy.extend((s, e) for s, e, _ in clipped)
        for _s, _e, n, own in self_times(clipped):
            op_ns[n] = op_ns.get(n, 0.0) + own
    gaps = sorted(idle_gaps(all_busy, w0, w1), key=lambda g: g[0] - g[1])
    named = [(_host_activity(host_events, s, e), (e - s) / 1e9)
             for s, e in gaps[:top_gaps]]
    return DeviceTrace(window_s=(w1 - w0) / 1e9,
                       busy_s=busy_total / len(devices) / 1e9,
                       chips=len(devices),
                       op_seconds={k: v / 1e9 for k, v in op_ns.items()},
                       gaps=named)


def _host_activity(host_events, s: float, e: float) -> str:
    best, best_overlap = "no host span", 0.0
    for hs, he, name in host_events:
        overlap = min(he, e) - max(hs, s)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def read_planes(trace_dir: str):
    """The planes of the newest ``.xplane.pb`` under ``trace_dir``, in
    the plain form :func:`reduce` takes."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        return []
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [(ev.name, ev.start_ns, ev.duration_ns)
                                      for ev in line.events]))
        planes.append((plane.name, lines))
    return planes
