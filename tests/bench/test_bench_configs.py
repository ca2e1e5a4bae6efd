"""The benchmark's deployments: the GY94 codon generator and the PRISM
tandem queueing network's generator."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

from mfbench import workload  # noqa: E402


def _config(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return cfg, workload.load_module(BENCH / "configs" / cfg["module"])


@pytest.mark.parametrize("seed", [0, 2**31 + 7, 123456789012])
def test_codon_generator_is_a_reversible_unit_rate_generator(seed):
    cfg, mod = _config("phylo_codon61")
    params = mod.draw(np.random.default_rng(seed), cfg)
    q = mod.generator(params, cfg)
    pi = mod.stationary(params)
    assert q.shape == (61, 61) == (cfg["n"], cfg["n"])
    np.testing.assert_allclose(q.sum(axis=1), 0.0, atol=1e-12)
    flux = pi[:, None] * q
    np.testing.assert_allclose(flux, flux.T, atol=1e-15)  # detailed balance
    assert -np.dot(pi, np.diag(q)) == pytest.approx(1.0, rel=1e-12)
    off = q - np.diag(np.diag(q))
    assert off.min() >= 0.0
    # Only single-nucleotide changes exchange directly.
    codons = [c for c, _ in mod.sense_codons()]
    for i, j in zip(*np.nonzero(off)):
        assert sum(a != b for a, b in zip(codons[i], codons[j])) == 1


def test_codon_table_and_gamma_categories():
    cfg, mod = _config("phylo_codon61")
    codons = mod.sense_codons()
    assert len(codons) == 61
    assert {c for c, _ in codons}.isdisjoint({"TAA", "TAG", "TGA"})
    assert dict(codons)["ATG"] == "M" and dict(codons)["TGG"] == "W"
    rates = mod.categories({"alpha": 0.5}, 4)
    assert rates.mean() == pytest.approx(1.0, rel=1e-12)
    assert np.all(np.diff(rates) > 0)
    np.testing.assert_array_equal(mod.categories({"alpha": 0.5}, 1), [1.0])


@pytest.mark.parametrize("c", [1, 2, 31])
def test_tandem_generator_shape_and_rows(c):
    cfg, mod = _config("ctmc_tandem31")
    params = mod.draw(None, cfg, {"c": c, "lam": 4.0 * c})
    q = mod.generator(params, cfg)
    assert q.shape == ((2 * c + 1) * (c + 1),) * 2
    np.testing.assert_allclose(q.sum(axis=1), 0.0, atol=1e-12)
    assert (q - np.diag(np.diag(q))).min() >= 0.0
    if c == cfg["c"]:
        assert q.shape[0] == cfg["n"] == 2016


def test_tandem_c1_matches_the_hand_written_chain():
    cfg, mod = _config("ctmc_tandem31")
    lam, m1a, m1b, m2, kap = 4.0, 0.2, 1.8, 2.0, 4.0
    params = mod.draw(None, cfg, {"c": 1, "lam": lam})
    q = mod.generator(params, cfg)
    # (sc, ph, sm), in the module's order.
    order = [(0, 1, 0), (1, 1, 0), (1, 2, 0), (0, 1, 1), (1, 1, 1), (1, 2, 1)]
    assert mod.states(1) == order
    want = np.zeros((6, 6))
    want[0, 1] = lam                       # arrival
    want[1, 2] = m1a                       # phase 1 -> 2
    want[1, 3] = m1b                       # route from phase 1
    want[2, 3] = m2                        # route from phase 2
    want[3, 4] = lam                       # arrival, second queue full
    want[3, 0] = kap                       # service in the second queue
    want[4, 5] = m1a                       # phase 1 -> 2 (routing blocked)
    want[4, 1] = kap
    want[5, 2] = kap
    np.fill_diagonal(want, -want.sum(axis=1))
    np.testing.assert_array_equal(q, want)
    assert (m1a, m1b, m2, kap) == (params["mu1a"], params["mu1b"],
                                   params["mu2"], params["kappa"])
