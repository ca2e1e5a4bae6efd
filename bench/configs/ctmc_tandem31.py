"""Tandem Queueing Network of the PRISM benchmark suite (``tandem.sm``,
after Hermanns, Meyer-Kayser and Siegle 1999): the CTMC generator Q.

Two queues of capacity c in series. A state is (sc, ph, sm): sc jobs in
the first queue (a Coxian server with phases ph = 1, 2; ph is 1 whenever
sc = 0) and sm jobs in the second. Transitions, as in ``tandem.sm``:

  arrival      sc < c                   rate lambda   sc + 1
  phase        sc > 0, ph = 1           rate mu1a     ph = 2
  route        sc > 0, ph = 1, sm < c   rate mu1b     sc - 1, sm + 1
  route        sc > 0, ph = 2, sm < c   rate mu2      sc - 1, ph = 1, sm + 1
  service      sm > 0                   rate kappa    sm - 1

That gives (2c + 1)(c + 1) states. Everything here is f64 numpy on the
host; the harness casts the operands to the configuration's dtype on the
device.
"""

from __future__ import annotations

import numpy as np


def states(c: int):
    """Reachable states in a fixed order: sm slowest, then sc, then ph."""
    out = []
    for sm in range(c + 1):
        for sc in range(c + 1):
            for ph in ((1,) if sc == 0 else (1, 2)):
                out.append((sc, ph, sm))
    return out


def draw(rng: np.random.Generator, config: dict, override=None) -> dict:
    """The model's rates. ``tandem.sm`` fixes them, so the seed draws
    nothing here; a traffic mix may override one (a sweep of lambda)."""
    del rng
    c = config["c"]
    rates = config["rates"]
    params = dict(c=c, lam=rates["lambda_per_c"] * c, mu1a=rates["mu1a"],
                  mu1b=rates["mu1b"], mu2=rates["mu2"], kappa=rates["kappa"])
    params.update(override or {})
    return params


def generator(params: dict, config: dict) -> np.ndarray:
    """The (2c+1)(c+1)-state generator (f64, rows sum to 0)."""
    del config
    c = params["c"]
    st = states(c)
    index = {s: i for i, s in enumerate(st)}
    q = np.zeros((len(st), len(st)))
    for (sc, ph, sm), i in index.items():
        moves = []
        if sc < c:
            moves.append((params["lam"], (sc + 1, ph if sc > 0 else 1, sm)))
        if sc > 0 and ph == 1:
            moves.append((params["mu1a"], (sc, 2, sm)))
        if sc > 0 and sm < c:
            rate = params["mu1b"] if ph == 1 else params["mu2"]
            moves.append((rate, (sc - 1, 1, sm + 1)))
        if sm > 0:
            moves.append((params["kappa"], (sc, ph, sm - 1)))
        for rate, dest in moves:
            q[i, index[dest]] += rate
    np.fill_diagonal(q, -q.sum(axis=1))
    return q


def categories(params: dict, count: int) -> np.ndarray:
    """A CTMC has one rate class."""
    del params
    if count != 1:
        raise ValueError("ctmc_tandem31 has no rate categories")
    return np.ones(1)
