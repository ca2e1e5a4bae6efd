"""The reduction from a profiler trace to device busy time, idle share,
idle gaps named by host activity, and kernel time."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "bench"))

from mfbench import devtrace  # noqa: E402

MS = 1_000_000  # ns

# A small trace in the form devtrace.read_planes returns: a 100 ms window,
# three ops on the TPU (two overlap), one op before the window, an op
# that runs past its end, and host spans beside them.
PLANES = [
    ("/host:metadata", []),
    ("/host:CPU", [
        ("python3", [("bench.window", 10 * MS, 100 * MS),
                     ("bench.submit", 40 * MS, 30 * MS),
                     ("ThreadpoolListener::Record", 40 * MS, 50 * MS)]),
        ("bench-watcher", [("bench.wait", 85 * MS, 5 * MS)]),
    ]),
    ("/device:TPU:0", [
        ("Steps", [("0", 0, 200 * MS)]),
        ("XLA Modules", [("jit_exe", 10 * MS, 100 * MS)]),
        ("XLA Ops", [
            ("fusion.1", 0, 5 * MS),                     # before the window
            ("matmul_kernel.3", 10 * MS, 20 * MS),       # 10..30
            ("fusion.2", 25 * MS, 10 * MS),              # 25..35, overlaps
            ("square_kernel", 80 * MS, 5 * MS),          # 80..85
            ("custom-call.7", 105 * MS, 20 * MS),        # 105..125, clipped
        ]),
    ]),
]


def test_busy_idle_and_kernel_time():
    tr = devtrace.reduce(PLANES)
    assert tr.window_s == pytest.approx(0.100)
    # Union inside [10, 110): 10..35, 80..85, 105..110 -> 35 ms.
    assert tr.busy_s == pytest.approx(0.035)
    assert tr.idle_share == pytest.approx(0.65)
    assert tr.op_seconds["custom-call.7"] == pytest.approx(0.005)
    assert "fusion.1" not in tr.op_seconds
    assert tr.kernel_seconds(["matmul_kernel", "square_kernel"]) == \
        pytest.approx(0.025)
    assert tr.kernel_seconds(["no_such_kernel"]) == 0.0
    assert tr.top_ops(1) == [("matmul_kernel.3", pytest.approx(0.020))]


def test_gaps_are_longest_first_and_named_by_host_activity():
    tr = devtrace.reduce(PLANES)
    # Gaps: 35..80 (45 ms), 85..105 (20 ms).
    assert [round(s, 6) for _, s in tr.gaps] == [0.045, 0.020]
    assert tr.gaps[0][0] == "bench.submit"     # 40..70 overlaps most
    assert tr.gaps[1][0] == "bench.wait"       # 85..90


def test_no_device_plane_or_window_reads_nothing():
    host_only = PLANES[:2]
    assert devtrace.reduce(host_only) is None
    no_window = [PLANES[0], ("/host:CPU", []), PLANES[2]]
    assert devtrace.reduce(no_window) is None


def test_union_and_gaps_helpers():
    assert devtrace.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert devtrace.idle_gaps([(1, 2), (4, 9)], 0, 10) == \
        [(0, 1), (2, 4), (9, 10)]


def test_nested_ops_count_their_own_time_once():
    # A loop op (0..100) whose body ran two ops (10..30, 40..50).
    events = [(0, 100, "%while.1 = while(...)"),
              (10, 30, '%custom-call.2 = f32[8]{0} custom-call(%p), '
                       'custom_call_target="LuDecompositionBlock"'),
              (40, 50, "%fusion.3 = f32[8]{0} fusion(%p), kind=kLoop")]
    own = {n: t for _s, _e, n, t in devtrace.self_times(events)}
    assert own[events[0][2]] == 70
    assert own[events[1][2]] == 20 and own[events[2][2]] == 10
    assert devtrace.short_name(events[1][2]) == \
        "%custom-call.2 LuDecompositionBlock"
    assert devtrace.short_name(events[2][2]) == "%fusion.3"
