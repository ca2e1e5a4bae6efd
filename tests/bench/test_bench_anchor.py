"""Engine spans placed on a profiler trace through the bridged
annotations' clock anchors, and the idle-while-pending share built on
them."""

import sys
import time
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "bench"))

from mfbench import anchor, devtrace  # noqa: E402
from repro.runtime.telemetry import Tracer  # noqa: E402

MS = 1_000_000  # ns


def test_anchor_puts_a_known_span_back_on_a_cpu_profiler_trace(tmp_path):
    tracer = Tracer(clock=time.monotonic)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(20):
            with tracer.span("bucket.assemble"):
                time.sleep(0.0005)
        known = []
        for _ in range(5):
            with jax.profiler.TraceAnnotation("reference"):
                t0 = tracer.now()
                time.sleep(0.002)
                t1 = tracer.now()
            tracer.add_span("bucket.batch", t0, t1)
            known.append((t0, t1))
    finally:
        jax.profiler.stop_trace()
    anchors = anchor.read_anchors(str(tmp_path))
    assert len(anchors) == 20
    offset = anchor.offset_ns(anchors)
    placed = sorted(anchor.place(tracer.spans(), offset,
                                 names=("bucket.batch",)))
    refs = sorted((s, s + d) for plane, lines
                  in devtrace.read_planes(str(tmp_path))
                  for _line, evs in lines for name, s, d in evs
                  if name == "reference")
    assert len(placed) == len(refs) == 5
    errors = sorted(max(abs(ps - rs), abs(pe - re))
                    for (ps, pe), (rs, re) in zip(placed, refs))
    assert errors[2] < 50_000          # the median, in ns
    assert errors[-1] < 5 * MS


def test_offset_is_the_median_of_the_anchors():
    anchors = [(1e9 * 1.0 + 1 * MS, 1.0), (1e9 * 2.0 + 1 * MS + 300_000,
                                           2.0), (1e9 * 3.0 + 1 * MS, 3.0)]
    assert anchor.offset_ns(anchors) == pytest.approx(1 * MS)
    assert anchor.offset_ns([]) is None


def _span(name, ts_ms, dur_ms):
    return {"name": name, "ph": "X", "ts": ts_ms / 1e3, "dur": dur_ms / 1e3,
            "track": "main", "args": {}}


# A 100 ms window on the trace at 10..110 ms; the engine clock reads 1 ms
# behind it (offset 1 ms).
PLANES = [
    ("/host:CPU", [("python3", [("bench.window", 10 * MS, 100 * MS)])]),
    ("/device:TPU:0", [("XLA Ops", [("fusion.1", 20 * MS, 20 * MS),
                                    ("fusion.2", 70 * MS, 10 * MS)])]),
]
ANCHORS = [(1e9 * 1.0 + 1 * MS, 1.0), (1e9 * 2.0 + 1 * MS, 2.0)]


def test_idle_pending_share_intersects_device_idle_with_pending_spans():
    spans = [_span("bucket.batch", 29, 30),     # trace 30..60
             _span("stream.queue", 54, 20),     # trace 55..75
             _span("request", 0, 200)]          # not a pending stage
    # Pending 30..75; device idle 10..20, 40..70, 80..110: 40..70 = 30 ms.
    assert anchor.idle_pending_share(PLANES, ANCHORS, spans) == \
        pytest.approx(0.30)


@pytest.mark.parametrize("planes, anchors", [
    (PLANES, []),
    (PLANES[1:], ANCHORS),
    (PLANES[:1], ANCHORS),
], ids=["no_anchor", "no_window", "no_device"])
def test_idle_pending_reads_nothing_without_its_inputs(planes, anchors):
    spans = [_span("bucket.batch", 29, 30)]
    assert anchor.idle_pending_share(planes, anchors, spans) is None


def test_overlap_length_merges_each_side_first():
    a = [(0, 10), (5, 20), (30, 40)]
    b = [(15, 35), (38, 50)]
    # a: 0..20, 30..40; b: 15..35, 38..50 -> 15..20, 30..35, 38..40.
    assert anchor.overlap_length(a, b) == pytest.approx(12)
