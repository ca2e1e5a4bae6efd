"""The benchmark harness end to end on the CPU, at a size a test run can
hold: it refuses to run without a chip, a sound run is correct, a run
with the timed path broken underneath is not, and the control (the
reference in the program's place, below the configuration's precision)
fails the cell's limit."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from mfbench import harness, loops  # noqa: E402
from repro.serve import matfn  # noqa: E402

SEED = 2**31 + 11


def test_run_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "phylo_codon61.mcmc", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=100)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def _small_mcmc():
    """The mcmc cell with buckets of at most 8 and a tenth of its load:
    every path of a run, in seconds on the CPU."""
    cell = harness.load_cell("phylo_codon61.mcmc")
    cell.config["engine"]["max_batch"] = 8
    cell.traffic["bursts_per_s"] = 100.0
    cell.traffic["warm_seconds"] = 0.2
    return cell


def _run(cell, seconds=0.5):
    return harness.run_cell(cell, SEED, seconds, False, loops.clock(),
                            require_tpu=False)


def test_sound_run_is_correct():
    result = _run(_small_mcmc())
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"setup_s", "p50_ms"}


def _small_tree():
    """The tree cell with buckets of at most 8 and rounds of 20 requests."""
    cell = harness.load_cell("phylo_codon61.tree")
    cell.config["engine"]["max_batch"] = 8
    cell.traffic["times"]["count"] = 5
    return cell


@pytest.mark.parametrize("small, tail, others", [
    (_small_mcmc, "p95_ms.latency", {"batch_wait_ms.latency",
                                     "queue_ms.latency"}),
    (_small_tree, "p95_ms.throughput", {"assemble_ms.throughput"}),
], ids=["mcmc", "tree"])
def test_traced_run_reports_the_tail_per_layer(small, tail, others):
    result = harness.run_cell(small(), SEED, 0.5, True, loops.clock(),
                              require_tpu=False)
    assert result["correct"] is True
    metrics = result["metrics"]
    assert "p95_ms" not in metrics
    assert metrics[tail]["value"] > 0
    assert others <= set(metrics)


def _reversed(rows):
    return rows[::-1]


def _one_altered(rows):
    return (rows[0] * 1.25,) + tuple(rows[1:])


def _half_left_out(rows):
    half = len(rows) // 2
    return tuple(rows[:half]) + tuple(rows[:len(rows) - half])


@pytest.mark.parametrize("fault", [_reversed, _one_altered, _half_left_out],
                         ids=["member_order", "answer_altered",
                              "half_batch_left_out"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    split = matfn._split_rows

    def broken(out, *, b):
        rows = split(out, b=b)
        return fault(rows) if b > 1 else (rows[0] * 1.25,)

    monkeypatch.setattr(matfn, "_split_rows", broken)
    result = _run(_small_mcmc())
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["checks"].values())


@pytest.mark.parametrize("name", ["phylo_codon61.mcmc",
                                  "phylo_codon61.tree"])
def test_control_fails_the_limit(name):
    checks = harness.control_checks(harness.load_cell(name), SEED)
    assert any(c["value"] > c["limit"] for c in checks.values()), \
        json.dumps(checks)


def _parked_cell(name, c=2):
    """A cell whose files are in bench/ but not in BENCHMARK.json (the
    program fails it on the chip), at c = 2 (n = 15) on the CPU."""
    import numpy as np
    from mfbench import workload
    bench = ROOT / "bench"
    config_name, traffic = name.split(".")
    config = json.loads((bench / "configs" / f"{config_name}.json").read_text())
    config["c"] = c
    checks = json.loads((bench / "cells" / f"{name}.json").read_text())
    for check in checks["checks"].values():
        check["limit"] = float(np.finfo(np.float32).eps) * 1e3
    module = workload.load_module(bench / "configs" / config["module"])
    traffic = json.loads((bench / "traffic" / f"{traffic}.json").read_text())
    return harness.Cell(name, 1, config, module, traffic, checks, [], [])


@pytest.mark.parametrize("name", ["ctmc_tandem31.transient",
                                  "ctmc_tandem31.steady"])
def test_parked_cells_run_correct_at_f32_on_the_cpu(name):
    result = _run(_parked_cell(name))
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("t", [3e-4, 1e-3, 3e-3])
def test_relative_gap_resolves_f32_storage_and_not_bf16(t):
    """On a short branch P(t) is I + Qt to first order: f32 storage keeps
    P - I to a few parts in a thousand of ||Qt||, bfloat16 storage rounds
    the diagonal to a step of 2^-8 and loses it."""
    import ml_dtypes
    import numpy as np
    import scipy.linalg
    from mfbench import reference, workload
    bench = ROOT / "bench"
    config = json.loads((bench / "configs" / "phylo_codon61.json").read_text())
    module = workload.load_module(bench / "configs" / config["module"])
    q = module.generator(module.draw(np.random.default_rng(SEED), config),
                         config)
    ref = scipy.linalg.expm(q * t)
    f32 = ref.astype(np.float32).astype(np.float64)
    bf16 = ref.astype(ml_dtypes.bfloat16).astype(np.float64)
    assert reference.transition_rel_error(f32, ref) < 1e-3
    assert reference.transition_rel_error(bf16, ref) > 0.1
