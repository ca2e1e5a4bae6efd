"""Place the engine's spans on a profiler trace, and the share of the
window in which the device idled while a request waited on the host.

A tracing ``MatFnEngine`` writes each lexical host stage
(``bucket.assemble``, ``bucket.execute``, ``bucket.resolve``,
``matfn.submit``, ``scheduler.dispatch``) as a profiler annotation whose
stat ``t`` is the stage's start on the engine clock. Each such event is
an anchor: trace ns = 1e9 * t + offset. The median offset over the
anchors places every engine span on the trace, those recorded across
threads (``bucket.batch``, ``stream.queue``) too.

Nothing here reads the program: the anchors and device ops come from the
profiler's own file, the spans from ``engine.tracer.spans()``.
"""

from __future__ import annotations

import glob
import os
import statistics
from typing import Iterable, List, Optional, Tuple

from mfbench import devtrace

#: The stat a bridged annotation carries: its start on the engine clock.
ANCHOR_STAT = "t"
#: Engine spans during which an admitted request waits on the host.
PENDING = ("bucket.batch", "stream.queue", "bucket.assemble",
           "bucket.execute")

Interval = Tuple[float, float]


def read_anchors(trace_dir: str) -> List[Tuple[float, float]]:
    """``[(start_ns, t), ...]``: every host event of the newest
    ``.xplane.pb`` under ``trace_dir`` that carries the anchor stat."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        return []
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    anchors = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                t = dict(ev.stats).get(ANCHOR_STAT)
                if t is not None:
                    anchors.append((float(ev.start_ns), float(t)))
    return anchors


def offset_ns(anchors: Iterable[Tuple[float, float]]) -> Optional[float]:
    """The median of ``start_ns - 1e9 * t`` over the anchors (None
    without any): a late annotation start moves the median by nothing."""
    offsets = [s - 1e9 * t for s, t in anchors]
    return statistics.median(offsets) if offsets else None


def place(spans, offset: float,
          names: Iterable[str] = PENDING) -> List[Interval]:
    """The named engine spans (``tracer.spans()`` dicts) as trace-ns
    intervals."""
    names = set(names)
    return [(1e9 * s["ts"] + offset, 1e9 * (s["ts"] + s["dur"]) + offset)
            for s in spans if s["name"] in names and s["ph"] == "X"]


def _merged(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap_length(a: Iterable[Interval], b: Iterable[Interval]) -> float:
    """Length covered by both interval sets."""
    a, b = _merged(a), _merged(b)
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_pending_share(planes, anchors, spans) -> Optional[float]:
    """Share of the ``bench.window`` span in which no op ran on any
    device while an engine span of :data:`PENDING` was open: the device
    waiting on the host with a request admitted. ``planes`` as
    :func:`devtrace.read_planes` gives them. None without a window, a
    device plane or an anchor."""
    offset = offset_ns(anchors)
    window, busy, devices = None, [], 0
    for plane_name, lines in planes:
        if plane_name.startswith("/device:"):
            ops = [evs for line, evs in lines if line == devtrace.OPS_LINE]
            if ops:
                devices += 1
                busy.extend((s, s + d) for _n, s, d in ops[0])
        elif plane_name.startswith("/host:"):
            for _line, evs in lines:
                for name, s, d in evs:
                    if name == devtrace.WINDOW_SPAN:
                        window = (s, s + d)
    if offset is None or window is None or not devices:
        return None
    w0, w1 = window
    idle = devtrace.idle_gaps(busy, w0, w1)
    return overlap_length(idle, place(spans, offset)) / (w1 - w0)
