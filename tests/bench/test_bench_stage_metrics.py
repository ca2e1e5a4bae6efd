"""The per-layer readers of the engine's traced stages (``submit``,
``device``): their values on synthetic readings, nothing where the
program records nothing, and a traced run on the CPU that reports
them."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from mfbench import harness, loops  # noqa: E402

SEED = 2**31 + 13


def _readings(stages):
    return harness.Readings(61, "TPU v5 lite", 60_000, 0, stages, [], None,
                            None)


@pytest.mark.parametrize("name, stages, value", [
    ("submit_us.latency", {"submit": (32_000, 1.6)}, 50.0),
    ("submit_us.throughput", {"submit": (70_000, 2.1)}, 30.0),
    ("device_wait_ms.latency", {"device": (3_000, 4.5)}, 1.5),
])
def test_stage_readers_read_the_window_mean(name, stages, value):
    assert harness._read_metric(name, _readings(stages)) == \
        pytest.approx(value)


@pytest.mark.parametrize("name", ["submit_us.latency", "submit_us.throughput",
                                  "device_wait_ms.latency"])
def test_stage_readers_read_nothing_from_an_untraced_program(name):
    stages = {"queue": (10, 0.1), "assemble": (10, 0.1)}
    assert harness._read_metric(name, _readings(stages)) is None


def _small(name):
    cell = harness.load_cell(name)
    cell.config["engine"]["max_batch"] = 8
    cell.traffic["bursts_per_s"] = 100.0
    cell.traffic["warm_seconds"] = 0.2
    cell.traffic["times"]["count"] = 5
    return cell


@pytest.mark.parametrize("name, stage_metrics", [
    ("phylo_codon61.mcmc", {"submit_us.latency", "device_wait_ms.latency"}),
    ("phylo_codon61.tree", {"submit_us.throughput"}),
], ids=["mcmc", "tree"])
def test_traced_run_reports_the_stage_metrics(name, stage_metrics):
    result = harness.run_cell(_small(name), SEED, 0.5, True, loops.clock(),
                              require_tpu=False)
    assert result["correct"] is True
    for metric in stage_metrics:
        assert result["metrics"][metric]["value"] > 0, metric
