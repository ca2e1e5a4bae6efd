"""The tandem transient cell at f32 accuracy (``ctmc_tandem31_f32``): it
resolves from BENCHMARK.json, a run at a CPU's size is correct, the
control fails its limit, and each of its per-layer readers reads a
synthetic trace and stays silent without one."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from mfbench import devtrace, harness, loops, workload  # noqa: E402

CELL = "ctmc_tandem31_f32.transient"
SEED = 2**31 + 17
READERS = ("square_roofline", "solve_ms_per_answer.transient",
           "device_ms_per_answer.transient", "device_idle.transient")


def test_cell_resolves_by_name():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1
    assert cell.config["name"] == "ctmc_tandem31_f32"
    assert (cell.config["c"], cell.config["n"]) == (31, 2016)
    assert cell.config["route"] == "fastmm" and cell.config["reduced"] == []
    assert cell.module.__name__ == "bench_ctmc_tandem31"
    assert cell.traffic["op"] == "expm" and cell.traffic["clients"] == 1
    assert cell.checks["sample"] >= 16
    assert 0 < cell.checks["checks"]["p_err"]["limit"] < 0.1
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "p50_ms"}
    assert {m["name"] for m in cell.per_layer} == set(READERS)
    assert all(m["moves"] == "p50_ms" for m in cell.per_layer)


def _small(c=2):
    cell = harness.load_cell(CELL)
    cell.config["c"] = c
    return cell


def test_run_at_c2_is_correct_on_the_cpu():
    result = harness.run_cell(_small(), SEED, 0.5, False, loops.clock(),
                              require_tpu=False)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "p50_ms"}


def test_control_fails_the_limit():
    """bfloat16 Pade-13 in the program's place, at c = 3 (t up to 10,
    6 squarings): far above the limit the program meets."""
    checks = harness.control_checks(_small(3), SEED)
    assert checks["p_err"]["value"] > checks["p_err"]["limit"], checks


def _readings(device):
    cell = _small()
    wl = workload.build(cell.config, cell.module, cell.traffic, SEED, 1.0, 64)
    return harness.Readings(
        n=2016, device_kind="TPU v5 lite", answers=160,
        squarings=int(sum(wl.pool.squarings)) * 10, stages={}, spans=[],
        device=device, latency=None)


def _trace():
    """Ten rounds of 16 answers in a 10 s window: the squaring kernels,
    an LU solve's custom calls and a Pade product."""
    ops = {
        "%matmul_pallas_high.7 = f32[1024,1024] custom-call(%a, %b), "
        'custom_call_target="tpu_custom_call"': 2.0,
        "%custom-call.28 = f32[2016,2016] custom-call(%x), "
        'custom_call_target="LuDecompositionBlock"': 0.5,
        "%custom-call.29 = f32[2016,2016] custom-call(%y), "
        'custom_call_target="InvertDiagBlocksLowerTriangular"': 0.25,
        "%convolution.3 = f32[2016,2016] convolution(%p, %q)": 1.0,
    }
    return devtrace.DeviceTrace(window_s=10.0, busy_s=4.0, chips=1,
                                op_seconds=ops, gaps=[])


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_without_a_trace(name):
    assert harness._read_metric(name, _readings(None)) is None


@pytest.mark.parametrize("name,want", [
    ("solve_ms_per_answer.transient", 1e3 * 0.75 / 160),
    ("device_ms_per_answer.transient", 1e3 * 4.0 / 160),
    ("device_idle.transient", 60.0),
])
def test_reader_reads_a_synthetic_trace(name, want):
    got = harness._read_metric(name, _readings(_trace()))
    assert got == pytest.approx(want)


def test_square_roofline_counts_the_reference_squarings(capsys):
    from mfbench import roofline
    r = _readings(_trace())
    got = harness._read_metric("square_roofline", r)
    peak = roofline.peaks("TPU v5 lite")["flops_per_s"]
    want = 100 * r.squarings * 2 * 2016 ** 3 / peak / 2.0
    assert got == pytest.approx(want)
    assert 0 < got <= 100
    assert "bound by compute" in capsys.readouterr().err
    # Only the named kernels count: no kernel time, no reading.
    quiet = devtrace.DeviceTrace(10.0, 4.0, 1, {"%fusion.1 = f32[]": 4.0},
                                 [])
    assert harness._read_metric("square_roofline",
                                _readings(quiet)) is None
