"""The plain reference that decides ``correct``: numpy and scipy in f64
on the host, sharing no code with the engine.

``host_reference`` is a copy of ``repro.launch.matserve.host_reference``,
kept here so that a change to the program cannot move the yardstick.
"""

from __future__ import annotations

import numpy as np


def host_reference(op, a, power=1, dists=None):
    """The f64 answer: ``A^p``, ``e^A``, the stationary distribution (a
    linear solve of ``pi (P - I) = 0, sum(pi) = 1``), or ``dists @ P^p``."""
    import scipy.linalg

    a = np.asarray(a, np.float64)
    if op == "expm":
        return scipy.linalg.expm(a)
    if op == "matpow":
        return np.linalg.matrix_power(a, power)
    if dists is not None:
        return np.asarray(dists, np.float64) @ np.linalg.matrix_power(a, power)
    n = a.shape[0]
    lhs = (a - np.eye(n)).T
    lhs[-1] = 1.0
    rhs = np.zeros(n)
    rhs[-1] = 1.0
    return np.linalg.solve(lhs, rhs)


def transition_error(got, ref) -> float:
    """Largest row-wise L1 gap between a transition matrix and its
    reference, ``max_i sum_j |got_ij - ref_ij|`` (the infinity norm of
    the difference): twice the largest total-variation distance between
    a row's distribution and the reference's. inf for a non-finite
    answer."""
    got = np.asarray(got, np.float64)
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.abs(got - ref).sum(axis=1).max())


#: The smallest move away from I that a relative gap is taken against:
#: one f32 rounding of a diagonal entry near 1 (3e-8) is 3e-4 of it.
MOVE_FLOOR = 1e-4


def transition_rel_error(got, ref) -> float:
    """The row-L1 gap (:func:`transition_error`) over how far the
    reference moved from the identity, ``||R - I||``, in the same norm
    and at least :data:`MOVE_FLOOR`. On a short branch P(t) is I + Qt to
    first order, so this is the error of P(t) - I relative to ||Qt||:
    f32 storage of P resolves it, bfloat16 storage (whose step near 1 is
    2^-8) does not."""
    gap = transition_error(got, ref)
    move = np.abs(ref - np.eye(ref.shape[0])).sum(axis=1).max()
    return gap / max(float(move), MOVE_FLOOR)


def distribution_error(got, ref) -> float:
    """L1 gap between a distribution and its reference (inf for a
    non-finite answer)."""
    got = np.asarray(got, np.float64)
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.abs(got - ref).sum())


#: The numbers a cell's file (``bench/cells/<cell>.json``) may compare,
#: by name: each is the widest over the sampled answers.
GAPS = {"p_err": transition_error, "p_rel_err": transition_rel_error,
        "pi_err": distribution_error}
