"""One cell's requests, built from the seed: the operand pool, the
arrival schedule (open loop) or each client's rounds (closed loop).

A traffic mix is data (``bench/traffic/<mix>.json``). A configuration is
a module (``bench/configs/<config>.py``) that draws its parameters and
builds its generator Q in f64. A request's operand is Q_g * t * r_c for
``op = "expm"`` (generator g, time t, rate category r_c), or the
uniformised chain I + Q_g / q for ``op = "markov"``. Every seed gets the
same set of sizes and arrivals in another order: times and gaps are
stratified quantiles, shuffled by the seed, so the seed changes the
inputs and not the amount of work.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import itertools
from pathlib import Path
from typing import List, Optional

import numpy as np

from mfbench import roofline


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Pool:
    op: str
    n: int
    generators: List[np.ndarray]     # f64 Q_g (markov: already divided by q)
    gen_of: np.ndarray               # (P,) generator index of each item
    scale: np.ndarray                # (P,) t * r_c (markov: 1)
    squarings: np.ndarray            # (P,) reference-algorithm squarings
    hardness: np.ndarray             # (P,) ||operand||_1
    units: List[List[int]]           # items submitted together


@dataclasses.dataclass
class Workload:
    pool: Pool
    loop: str
    schedule: Optional[list] = None  # open: [(offset_s, [items])]
    warm_schedule: Optional[list] = None
    rounds: Optional[list] = None    # closed: per client, list of rounds
    warm_batches: Optional[list] = None


def _times(spec: dict, rng: np.random.Generator):
    count = spec["count"]
    if spec["dist"] == "log_grid":
        lo, hi = np.log(spec["lo"]), np.log(spec["hi"])
        return np.exp(np.linspace(lo, hi, count))
    if spec["dist"] == "exponential":
        u = (np.arange(count) + 0.5) / count
        return rng.permutation(-spec["mean"] * np.log1p(-u))
    raise ValueError(f"unknown time distribution {spec['dist']!r}")


def _keep_squarings(t: float, t_jit: float, norm: float) -> float:
    """The jittered time, unless it would change the squarings needed."""
    same = roofline.expm_squarings(norm * t_jit) == \
        roofline.expm_squarings(norm * t)
    return t_jit if same else t


def build_pool(config: dict, module, traffic: dict,
               rng: np.random.Generator) -> Pool:
    op = traffic["op"]
    g_count = traffic["generators"]
    sweep = traffic.get("sweep")
    gens, params = [], []
    for g in range(g_count):
        override = None
        if sweep is not None:
            # Stratified over [lo, hi] x config[over], jittered inside each
            # stratum by the seed.
            unit = config[sweep["over"]]
            lo, hi = sweep["lo"] * unit, sweep["hi"] * unit
            width = (hi - lo) / g_count
            override = {sweep["param"]: lo + width * (g + rng.uniform())}
        p = module.draw(rng, config, override)
        params.append(p)
        gens.append(module.generator(p, config))
    gen_of, scale, units = [], [], []
    if op == "expm":
        cats = traffic.get("categories", 1)
        jitter = traffic["times"].get("jitter", 0.0)
        for g, q in enumerate(gens):
            norm = roofline.norm1(q)
            rates = module.categories(params[g], cats)
            for t in _times(traffic["times"], rng):
                if jitter:
                    t = _keep_squarings(
                        t, t * np.exp(jitter * rng.uniform(-1, 1)),
                        norm * rates.max())
                unit = []
                for r in rates:
                    unit.append(len(gen_of))
                    gen_of.append(g)
                    scale.append(t * r)
                units.append(unit)
    elif op == "markov":
        factor = traffic["uniformize"]
        for g, q in enumerate(gens):
            rate = factor * np.abs(np.diag(q)).max()
            gens[g] = q / rate
            units.append([len(gen_of)])
            gen_of.append(g)
            scale.append(1.0)
    else:
        raise ValueError(f"unknown op {op!r}")
    gen_of = np.asarray(gen_of)
    scale = np.asarray(scale, np.float64)
    if op == "expm":
        norms = np.array([roofline.norm1(gens[g]) for g in range(g_count)])
        hardness = norms[gen_of] * scale
        squarings = np.array([roofline.expm_squarings(h) for h in hardness])
    else:
        n = gens[0].shape[0]
        hardness = np.array([roofline.norm1(np.eye(n) + gens[g])
                             for g in gen_of])
        squarings = np.zeros(len(gen_of), int)   # counted by the program
    return Pool(op, gens[0].shape[0], gens, gen_of, scale, squarings,
                hardness, units)


def _arrivals(rate: float, seconds: float, rng: np.random.Generator):
    """Offsets of ``round(rate * seconds)`` arrivals: stratified
    exponential gaps, shuffled by the seed, scaled to fill the window."""
    count = max(1, int(round(rate * seconds)))
    u = (np.arange(count) + 0.5) / count
    gaps = rng.permutation(-np.log1p(-u))
    gaps *= seconds / gaps.sum()
    return np.cumsum(gaps) - gaps[0]


def build(config: dict, module, traffic: dict, seed: int, seconds: float,
          max_batch: int) -> Workload:
    rng = np.random.default_rng(seed)
    pool = build_pool(config, module, traffic, rng)
    if traffic["loop"] == "open":
        rate = traffic["bursts_per_s"]

        def schedule(span):
            offsets = _arrivals(rate, span, rng)
            picks = rng.integers(len(pool.units), size=len(offsets))
            return [(float(o), pool.units[k]) for o, k in zip(offsets, picks)]

        warm = schedule(traffic["warm_seconds"])
        timed = schedule(seconds)
        batches = list(range(1, max_batch + 1))
        return Workload(pool, "open", schedule=timed, warm_schedule=warm,
                        warm_batches=batches)
    clients = traffic["clients"]
    rounds, outstanding = [], 0
    for k in range(clients):
        if traffic.get("client_generators") == "own":
            mine = [u for u in pool.units
                    if pool.gen_of[u[0]] == k % len(pool.generators)]
        else:
            mine = list(pool.units)
            shift = (k * len(mine)) // clients
            mine = mine[shift:] + mine[:shift]
        size = traffic["round_units"]
        if size == "all":
            size = len(mine)
        outstanding += sum(len(u) for u in mine[:size])
        rounds.append(_round_iter(itertools.cycle(mine), size))
    batches = list(range(1, min(max_batch, outstanding) + 1))
    return Workload(pool, "closed", rounds=rounds, warm_batches=batches)


def _round_iter(units, size: int):
    while True:
        items = []
        for _ in range(size):
            items.extend(next(units))
        yield items
