"""Goldman-Yang 1994 codon substitution model: the generator Q and the
discrete-gamma rate categories that a phylogenetic likelihood call
exponentiates (P(t) = e^{Q r_c t} per branch and rate category).

Q_ij for sense codons i != j that differ at exactly one nucleotide is
pi_j, times kappa for a transition (A<->G, C<->T), times omega for a
nonsynonymous change; codons that differ at more than one position do
not exchange directly. Rows sum to 0 and Q is scaled to one expected
substitution per unit time. Codon frequencies are F3x4: the product of
three per-position nucleotide frequencies, renormalised over the 61
sense codons of the universal code. Rate categories are the means of the
four quartiles of Gamma(alpha, alpha) (Yang 1994, J. Mol. Evol. 39:306).

Everything here is f64 numpy on the host; the harness casts the operands
to the configuration's dtype on the device.
"""

from __future__ import annotations

import itertools

import numpy as np
from scipy import special

BASES = "TCAG"
#: NCBI translation table 1, codons in TCAG order (first base slowest).
CODE = "FFLLSSSSYY**CC*WLLLLPPPPHHQQRRRRIIIMTTTTNNKKSSRRVVVVAAAADDEEGGGG"
TRANSITIONS = {frozenset("AG"), frozenset("CT")}


def sense_codons():
    """The 61 sense codons of the universal code, with their amino acids."""
    out = []
    for (a, b, c), aa in zip(itertools.product(BASES, repeat=3), CODE):
        if aa != "*":
            out.append((a + b + c, aa))
    return out


def draw(rng: np.random.Generator, config: dict, override=None) -> dict:
    """One analysis' model parameters, drawn from the ranges the
    configuration file lists under ``assumed``."""
    a = config["assumed"]
    lo, hi = a["kappa"]["range"]
    kappa = rng.uniform(lo, hi)
    lo, hi = a["omega"]["range"]
    omega = float(np.exp(rng.uniform(np.log(lo), np.log(hi))))
    lo, hi = a["alpha"]["range"]
    alpha = float(np.exp(rng.uniform(np.log(lo), np.log(hi))))
    conc = a["nucleotide_freqs"]["dirichlet"]
    freqs = rng.dirichlet([conc] * 4, size=3)
    params = dict(kappa=float(kappa), omega=omega, alpha=alpha,
                  freqs=freqs.tolist())
    params.update(override or {})
    return params


def codon_freqs(freqs) -> np.ndarray:
    """F3x4 codon frequencies over the sense codons."""
    f = np.asarray(freqs, np.float64)
    idx = {b: i for i, b in enumerate(BASES)}
    pi = np.array([f[0, idx[c[0]]] * f[1, idx[c[1]]] * f[2, idx[c[2]]]
                   for c, _ in sense_codons()])
    return pi / pi.sum()


def generator(params: dict, config: dict) -> np.ndarray:
    """The scaled GY94 rate matrix (61 x 61, f64)."""
    codons = sense_codons()
    pi = codon_freqs(params["freqs"])
    n = len(codons)
    q = np.zeros((n, n))
    for i, (ci, ai) in enumerate(codons):
        for j, (cj, aj) in enumerate(codons):
            diff = [k for k in range(3) if ci[k] != cj[k]]
            if len(diff) != 1:
                continue
            k = diff[0]
            rate = pi[j]
            if frozenset((ci[k], cj[k])) in TRANSITIONS:
                rate *= params["kappa"]
            if ai != aj:
                rate *= params["omega"]
            q[i, j] = rate
    np.fill_diagonal(q, -q.sum(axis=1))
    scale = -np.dot(pi, np.diag(q))
    return q / scale


def stationary(params: dict) -> np.ndarray:
    """The equilibrium codon frequencies pi (Q is reversible in them)."""
    return codon_freqs(params["freqs"])


def categories(params: dict, count: int) -> np.ndarray:
    """Mean rates of ``count`` equal-probability gamma categories (mean 1)."""
    if count == 1:
        return np.ones(1)
    alpha = params["alpha"]
    cuts = special.gammaincinv(alpha, np.arange(1, count) / count) / alpha
    upper = special.gammainc(alpha + 1.0,
                             np.concatenate([cuts, [np.inf]]) * alpha)
    lower = special.gammainc(alpha + 1.0,
                             np.concatenate([[0.0], cuts]) * alpha)
    return count * (upper - lower)
