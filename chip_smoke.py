"""Run the matrix-function serving engine on a TPU and check every answer.

    python chip_smoke.py               # one chip: every one-chip route + daemon
    python chip_smoke.py --four-chips  # four chips: the sharded route only

One process drives ``repro.serve.MatFnEngine`` (the engine behind
``python -m repro.launch.matserve``) with the Pallas kernels compiled for
the chip, never interpreted.

One chip. A synchronous flush sends a mixed batch down every one-chip
route: 64 codon-sized (61 x 61) generators through ``expm`` on the ``xla``
route; ``matpow`` at n = 1024 (p = 64 and p = 1000) and ``expm`` at
n = 1024 on the ``chain`` route; ``matpow`` at n = 4096, p = 64 on the
``fastmm`` route; two Markov steady states at n = 2048; and 64 start
distributions evolved 1000 steps under one n = 2048 chain on the ``evolve``
route. The batch is flushed twice, so each bucket's cold (compiling) and
warm wall seconds can be told apart. Then a started daemon takes a few
seconds of open-loop traffic, and every future must resolve.

Four chips. ``matpow`` (p = 64), ``expm`` and a Markov steady state at
n = 8192 go through an engine that owns a 2 x 2 mesh, so each takes the
``sharded`` route. Each answer is compared with the one-chip
``MatmulChain`` answer on the first device.

Every operand is made from ``--seed``. Every one-chip answer is compared
with an f64 reference computed on the host by numpy and scipy alone. The
error is the relative Frobenius error, and each check prints it beside its
bound. Each check also proves its bound is not vacuous: a zero answer, the
transposed reference, the request's own input and another request's
reference must all exceed it. The chain and fastmm executables the engine
ran must hold a Pallas kernel (``tpu_custom_call``), so a route that fell
back to the XLA dot fails. Each bucket also reports whether its answer is
bit-identical to one jitted core call on the same backend.

The last line of standard output is one JSON object, and it says
``"ok": true`` only when every phase passed on a TPU. Where JAX finds no
TPU, or any check fails, the script exits non-zero and prints no result.
Compile and wall seconds are set-up information, not metrics.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path
from typing import List, NamedTuple, Optional

import numpy as np

ROOT = Path(__file__).resolve().parent

#: One-chip sizes: for each route, the smallest a user would call real.
ONE_CHIP = dict(
    xla_n=61, xla_batch=64,
    chain_n=1024, chain_powers=(64, 1000),
    fastmm_n=4096, fastmm_power=64,
    markov_n=2048, markov_batch=2,
    evolve_n=2048, evolve_batch=64, evolve_steps=1000,
    daemon_xla_n=61, daemon_chain_n=256, daemon_power=64,
    daemon_rate=100.0, daemon_seconds=3.0, daemon_max_batch=8,
)
FOUR_CHIP_N = 8192
FOUR_CHIP_POWER = 64

# --- error bounds ---------------------------------------------------------
# On a TPU v5e every f32 dot here, XLA's at default precision and the
# Pallas kernels' alike, runs one bf16 pass: each input rounded to 8
# significant bits (unit roundoff U = 2**-9), products accumulated in f32.
# (A 1024 x 1024 product is off by 2.35e-3 there, as a host emulation with
# bf16-rounded inputs predicts.) Each bound holds at that precision with a
# margin of 3x or more over a host emulation of the same algorithm on
# operands like these, with every matmul input rounded to bf16. Each
# check also computes the error of wrong answers (a zero answer, the
# transposed answer, its own input, another request's answer) and fails
# unless every one of them lands above the bound.
U_BF16 = 2.0 ** -9

BOUNDS = {
    # A^64 at n = 1024 (leak 0.008): rounding shifts each squaring's row
    # sums by ~U/sqrt(n), and binary powering multiplies the shift of the
    # first squaring by ~p. Emulated 3.5e-3.
    "matpow_p64": 0.015,
    # A^1000 at n = 1024 (leak 0.001): the slow modes' 0.999**1000 turns a
    # 1e-4 rounding of the in-community mass into ~10%. Emulated 0.153.
    "matpow_p1000": 0.5,
    # A^64 at n = 4096 with two Strassen levels. Emulated 0.014.
    "fastmm_p64": 0.06,
    # A^64 at n = 256 (daemon). Emulated 8.3e-3 for one operand; the worst
    # of 75 on a v5e was 2.4e-2.
    "daemon_matpow_p64": 0.07,
    # pi at n = 2048 (leak 0.25), one Strassen level: pi is renormalized,
    # so row-sum drift cancels. Emulated 1.8e-3 without Strassen.
    "steady_state": 0.02,
    # d @ P^1000 at n = 2048 (leak 0.001), like matpow_p1000. Emulated 0.158.
    "evolve": 0.5,
    # Four chips at n = 8192: sharded against one-chip, each answer within
    # its one-chip bound of the f64 answer. For expm the sum of two
    # measured worst cases, 0.82 U ||A||_1, is near what a transposed
    # answer gives at n = 8192 (0.048 = 2.4 U ||A||_1 at ||A||_1 = 10), so
    # the bound sits between them: 0.75 * expm_bound = 1.5 U ||A||_1.
    "sharded_matpow": 0.03,
    "sharded_markov": 0.04,
}


def expm_bound(a) -> float:
    """Pade-13 evaluated with bf16-rounded products loses ~U per product,
    scaled by the operand: emulated errors at ||A||_1 in [2, 10] stay
    below 0.3 U ||A||_1 (n = 61 and n = 1024), and the worst of 64 codon
    generators on a v5e was 0.41 U ||A||_1."""
    return 2 * U_BF16 * float(np.abs(a).sum(0).max())


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


# --- operands -------------------------------------------------------------

def stochastic(rng, n: int, *, leak: float, blocks: int = 4) -> np.ndarray:
    """Row-stochastic P over ``blocks`` weakly coupled communities.

    Each row keeps ``1 - leak`` of its mass inside its own community and
    sends ``leak`` outside, so P has ``blocks - 1`` slow modes near
    ``1 - leak`` and P^p still moves between p = 64 and p = 1000 for a
    small leak. Column weights are lognormal, so the stationary
    distribution is far from uniform: a transposed answer, or one from
    another seed, lands O(1) away.
    """
    w = rng.lognormal(0.0, 1.0, n)
    u = rng.random((n, n)) * w
    group = np.arange(n) * blocks // n
    inside = np.where(group[:, None] == group[None, :], u, 0.0)
    outside = u - inside
    return ((1.0 - leak) * inside / inside.sum(1, keepdims=True)
            + leak * outside / outside.sum(1, keepdims=True))


def generator(rng, n: int, norm1: float) -> np.ndarray:
    """A GTR-form substitution generator Q (Q_ij = s_ij pi_j, rows sum to
    0) scaled to ``||Q||_1 = norm1``: e^Q is a transition matrix. Above
    ``||Q||_1 = 5.37`` expm squares once, which the chain route needs to
    run its Pallas kernel at all."""
    pi = rng.dirichlet(np.full(n, 4.0))
    s = rng.lognormal(0.0, 0.5, (n, n))
    q = (s + s.T) * pi[None, :]
    np.fill_diagonal(q, 0.0)
    q -= np.diag(q.sum(1))
    return q * (norm1 / np.abs(q).sum(0).max())


def distributions(rng, b: int, n: int) -> np.ndarray:
    """B start distributions, each concentrated on a random quarter."""
    d = rng.random((b, n)) ** 8
    return d / d.sum(1, keepdims=True)


# --- host f64 references (numpy / scipy only) ------------------------------

def ref_matpow(a, p):
    return np.linalg.matrix_power(a, p)


def ref_expm(a):
    import scipy.linalg
    return scipy.linalg.expm(a)


def ref_stationary(p):
    """pi with pi P = pi, sum(pi) = 1, by one linear solve."""
    n = p.shape[0]
    lhs = (p - np.eye(n)).T
    lhs[-1] = 1.0
    rhs = np.zeros(n)
    rhs[-1] = 1.0
    return np.linalg.solve(lhs, rhs)


def rel_error(got, ref) -> float:
    got = np.asarray(got, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def wrong_answer_floor(ref, decoys) -> float:
    """Smallest error among wrong answers: zero, the transposed reference
    (square matrices) and each of ``decoys`` (the request's own input, and
    another request's reference). A bound must stay below it."""
    wrong = [np.zeros_like(ref)] + [d for d in decoys if d.shape == ref.shape]
    if ref.ndim == 2 and ref.shape[0] == ref.shape[1]:
        wrong.append(ref.T)
    return min(rel_error(w, ref) for w in wrong)


class Request(NamedTuple):
    bucket: str        # label shared by the members of one bucket
    route: str         # the route the engine must take
    op: str
    operand: np.ndarray
    power: int
    dists: Optional[np.ndarray]
    ref: np.ndarray
    bound: float


# --- set-up ---------------------------------------------------------------

def setup(chips: int):
    """Point the caches inside the checkout and insist on ``chips`` TPUs."""
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no repro package under {ROOT / 'src'}; run from a checkout")
    sys.path.insert(0, str(ROOT / "src"))
    os.environ.setdefault("REPRO_AUTOTUNE_CACHE",
                          str(ROOT / ".autotune" / "autotune.json"))
    from repro.runtime.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        fail(f"needs {chips} TPU chips, JAX found {len(devices)}")
    from repro.kernels import autotune
    entries = len(autotune.load_cache())
    print(f"[chip_smoke] device={devices[0].device_kind} count={len(devices)} "
          f"jax={jax.__version__} compile_cache={cache_dir} "
          f"autotune_cache={autotune.cache_path()} "
          f"autotune_entries={entries}"
          f"{'' if entries == 0 else ' (tuned entries steer this run)'}")
    return devices


def pallas_kernels(engine, key) -> int:
    """Number of Pallas kernels in the compiled executable the engine ran
    for bucket ``key`` (``(op, route, padded_batch, n, dtype, power)``)."""
    import jax
    _op, _route, bpad, n, dtype, _power = key
    exe = engine._executables[key]
    spec = jax.ShapeDtypeStruct((bpad, n, n), dtype)
    return exe.lower(spec).compile().as_text().count("tpu_custom_call")


# --- one chip: synchronous flush -------------------------------------------

def one_chip_requests(rng, sizes) -> List[Request]:
    reqs = []
    n = sizes["xla_n"]
    for _ in range(sizes["xla_batch"]):
        a = generator(rng, n, rng.uniform(1.0, 10.0))
        reqs.append(Request(f"expm n={n}", "xla", "expm", a, 1, None,
                            ref_expm(a), expm_bound(a)))
    n = sizes["chain_n"]
    for p in sizes["chain_powers"]:
        a = stochastic(rng, n, leak=0.008 if p < 256 else 0.001)
        reqs.append(Request(f"matpow n={n} p={p}", "chain", "matpow", a, p,
                            None, ref_matpow(a, p),
                            BOUNDS["matpow_p64" if p < 256
                                   else "matpow_p1000"]))
    a = generator(rng, n, rng.uniform(6.0, 10.0))
    reqs.append(Request(f"expm n={n}", "chain", "expm", a, 1, None,
                        ref_expm(a), expm_bound(a)))
    n, p = sizes["fastmm_n"], sizes["fastmm_power"]
    a = stochastic(rng, n, leak=0.008)
    reqs.append(Request(f"matpow n={n} p={p}", "fastmm", "matpow", a, p, None,
                        ref_matpow(a, p), BOUNDS["fastmm_p64"]))
    n = sizes["markov_n"]
    for _ in range(sizes["markov_batch"]):
        a = stochastic(rng, n, leak=0.25)
        reqs.append(Request(f"steady_state n={n}", "fastmm", "markov", a, 1,
                            None, ref_stationary(a), BOUNDS["steady_state"]))
    n, b, steps = sizes["evolve_n"], sizes["evolve_batch"], \
        sizes["evolve_steps"]
    a = stochastic(rng, n, leak=0.001)
    d = distributions(rng, b, n)
    reqs.append(Request(f"evolve n={n} B={b} steps={steps}", "evolve",
                        "markov", a, steps, d, d @ ref_matpow(a, steps),
                        BOUNDS["evolve"]))
    return reqs


def stale_reference(reqs: List[Request], i: int):
    """The reference of the next request whose answer has the same shape
    (an answer served from the wrong slot), or request ``i``'s own input
    when no other request has that shape."""
    for j in list(range(i + 1, len(reqs))) + list(range(i)):
        if reqs[j].ref.shape == reqs[i].ref.shape:
            return reqs[j].ref
    r = reqs[i]
    return r.operand if r.dists is None else r.dists


#: The core backend each engine route runs its chains on.
BACKENDS = {"xla": "xla", "chain": "pallas_chain", "fastmm": "pallas_fastmm",
            "evolve": "pallas_chain"}


def per_matrix_call(req: Request, a, d):
    """``req`` answered by one jitted core call on its route's backend,
    outside the engine: what the engine's batched answer promises to
    equal bit for bit."""
    import jax
    from repro.core import evolve_distributions, expm, matpow_binary
    from repro.core import steady_state

    backend = BACKENDS[req.route]
    if req.dists is not None:
        fn = lambda a, d: evolve_distributions(d, a, req.power,
                                               backend=backend,
                                               validate=False)
        return jax.jit(fn)(a, d)
    fn = {"matpow": lambda a: matpow_binary(a, req.power, backend=backend),
          "expm": lambda a: expm(a, backend=backend),
          "markov": lambda a: steady_state(a, backend=backend,
                                           validate=False).pi}[req.op]
    return jax.jit(fn)(a)


def answer(req: Request, result):
    return result.pi if req.op == "markov" and req.dists is None else result


def sync_phase(sizes, seed: int, failures: list) -> None:
    import jax
    import jax.numpy as jnp
    from repro.serve import MatFnEngine, MatFnRequest

    reqs = one_chip_requests(np.random.default_rng(seed), sizes)
    on_device = [(jnp.asarray(r.operand, jnp.float32),
                  None if r.dists is None
                  else jnp.asarray(r.dists, jnp.float32)) for r in reqs]
    engine = MatFnEngine(profile=True)   # profile: per-bucket wall seconds

    def flush():
        for r, (a, d) in zip(reqs, on_device):
            engine.submit(r.op, a, power=r.power, dists=d)
        out = jax.block_until_ready(engine.flush())
        # Engine rows are keyed (op, route, padded_batch, n, dtype, power);
        # index them by the request's own bucket key (op, n, dtype, power).
        rows = {}
        for row in engine.stats["last_flush"]:
            op, _route, _bpad, n, dtype, power = row["key"]
            rows[(op, n, dtype, power)] = row
        return out, rows

    cold, cold_rows = flush()
    warm, warm_rows = flush()

    buckets = {}
    for i, r in enumerate(reqs):
        buckets.setdefault(r.bucket, []).append(i)
    for label, members in buckets.items():
        head = members[0]
        first = reqs[head]
        a, d = on_device[head]
        slot = MatFnRequest(first.op, a, first.power, d).bucket_key()
        row, warm_s = cold_rows[slot], warm_rows[slot]["seconds"]
        route = row["key"][1]
        # The member closest to its bound, and the wrong answer closest to
        # its member's bound, stand for the bucket.
        worst = None
        for i in members:
            r = reqs[i]
            floor = wrong_answer_floor(r.ref, [
                r.operand if r.dists is None else r.dists,
                stale_reference(reqs, i)])
            for results in (cold, warm):
                got = np.asarray(answer(r, results[i]), np.float64)
                err = rel_error(got, r.ref) if np.all(np.isfinite(got)) \
                    else np.inf
                margin = min(r.bound / err if err else np.inf,
                             floor / r.bound)
                if worst is None or margin < worst[0]:
                    worst = (margin, err, r.bound, floor)
        _margin, err, bound, floor = worst
        ok = err <= bound < floor and route == first.route
        print(f"[chip_smoke] {route:6s} {label} dtype=float32 "
              f"batch={len(members)}/{row['key'][2]} "
              f"rel_err={err:.3e} bound={bound:.3g} "
              f"wrong_answer_min_err={floor:.3g} "
              f"chip_cold_s={row['seconds']:.3f} chip_warm_s={warm_s:.3f} "
              f"chip_compile_s~{row['seconds'] - warm_s:.3f} "
              f"{'ok' if ok else 'FAILED'}")
        if not ok:
            failures.append(f"{label}: route {route} (expected "
                            f"{first.route}), rel_err {err:.3e}, bound "
                            f"{bound:.3g}, wrong answers from {floor:.3g}")
        if route in ("chain", "fastmm"):
            kernels = pallas_kernels(engine, row["key"])
            print(f"[chip_smoke]   executable holds {kernels} "
                  f"tpu_custom_call")
            if kernels == 0:
                failures.append(f"{label}: {route} executable has no Pallas "
                                f"kernel")
        alone = np.asarray(per_matrix_call(first, a, d))
        same = np.array_equal(alone, np.asarray(answer(first, warm[head])))
        print(f"[chip_smoke]   identical to a per-matrix call on the same "
              f"backend: {same}")
        if first.op == "markov" and first.dists is None:
            print("[chip_smoke]   steady_state squarings=" + ",".join(
                str(int(warm[i].squarings)) for i in members) +
                " residual=" + ",".join(f"{float(warm[i].residual):.2e}"
                                        for i in members))
    snap = engine.stats()
    print(f"[chip_smoke] sync engine: routes={snap['routes']} "
          f"compiles={snap['compiles']} buckets={snap['buckets']}")


# --- one chip: daemon -------------------------------------------------------

def daemon_phase(sizes, seed: int, failures: list) -> None:
    import jax.numpy as jnp
    from repro.launch.matserve import run_open_loop
    from repro.serve import MatFnEngine

    rng = np.random.default_rng(seed + 1)
    count = int(sizes["daemon_rate"] * sizes["daemon_seconds"])
    xn, cn, p = sizes["daemon_xla_n"], sizes["daemon_chain_n"], \
        sizes["daemon_power"]
    workload, refs, bounds = [], [], []
    for i in range(count):      # one chain-route matpow per three xla expms
        if i % 4 == 3:
            a = stochastic(rng, cn, leak=0.008)
            workload.append(("matpow", jnp.asarray(a, jnp.float32), p))
            refs.append(ref_matpow(a, p))
            bounds.append(BOUNDS["daemon_matpow_p64"])
        else:
            a = generator(rng, xn, rng.uniform(1.0, 10.0))
            workload.append(("expm", jnp.asarray(a, jnp.float32), 1))
            refs.append(ref_expm(a))
            bounds.append(expm_bound(a))

    engine = MatFnEngine(max_batch=sizes["daemon_max_batch"])
    engine.start()
    try:
        t0 = time.perf_counter()
        engine.warm("expm", xn)
        engine.warm("matpow", cn, power=p)
        warm_s = time.perf_counter() - t0
        results, _lats, wall, info = run_open_loop(
            engine, workload, sizes["daemon_rate"], timeout=300.0)
        snap = engine.stats()
    finally:
        engine.close()
    unresolved = sum(r is None or isinstance(r, BaseException)
                     for r in results)
    worst = {}      # op -> (err / bound, err, bound) of its worst answer
    for (op, _a, _p), got, ref, bound in zip(workload, results, refs,
                                             bounds):
        if got is not None and not isinstance(got, BaseException):
            err = rel_error(got, ref)
            worst[op] = max(worst.get(op, (0.0,)), (err / bound, err, bound))
    ok = unresolved == 0 and all(w[0] <= 1.0 for w in worst.values())
    print(f"[chip_smoke] daemon: {count} requests at "
          f"{sizes['daemon_rate']:g}/s, resolved={count - unresolved} "
          f"shed={info['shed']} worst " + " ".join(
              f"{op} rel_err={w[1]:.3e} bound={w[2]:.3g}"
              for op, w in sorted(worst.items())) +
          f" chip_warm_s={warm_s:.2f} chip_wall_s={wall:.2f} "
          f"routes={snap['routes']} compiles={snap['compiles']} "
          f"flush_triggers={snap['flush_triggers']} "
          f"{'ok' if ok else 'FAILED'}")
    if not ok:
        failures.append(f"daemon: {unresolved} futures unresolved or failed, "
                        f"max errors {worst}")


# --- four chips: the sharded route ------------------------------------------

def four_chip_phase(devices, n: int, seed: int, failures: list) -> None:
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, SingleDeviceSharding

    from repro.core import expm, matpow_binary, steady_state
    from repro.core.distributed import ShardedMatmulChain
    from repro.serve import MatFnEngine

    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=devices[:4])
    rng = np.random.default_rng(seed + 2)
    mats = {"matpow": stochastic(rng, n, leak=0.008),
            "expm": generator(rng, n, 10.0),
            "markov": stochastic(rng, n, leak=0.25)}
    on_device = {op: jnp.asarray(a, jnp.float32) for op, a in mats.items()}

    # The chain's operand must be spread over four distinct chips.
    placed = ShardedMatmulChain(n, jnp.float32, mesh).pad(on_device["matpow"])
    shards = placed.addressable_shards
    spread = len({s.device for s in shards})
    print(f"[chip_smoke] sharded chain operand: {len(shards)} shards on "
          f"{spread} devices, shard shapes "
          f"{sorted({s.data.shape for s in shards})}")
    if spread != 4:
        failures.append(f"sharded operand on {spread} devices, not 4")
    del placed, shards

    one_chip = {
        "matpow": lambda x: matpow_binary(x, FOUR_CHIP_POWER,
                                          backend="pallas_chain"),
        "expm": lambda x: expm(x, backend="pallas_chain"),
        "markov": lambda x: steady_state(x, backend="pallas_chain",
                                         validate=False).pi,
    }
    spec = jax.ShapeDtypeStruct((n, n), jnp.float32,
                                sharding=SingleDeviceSharding(devices[0]))

    def compile_one_chip(fn):
        t0 = time.perf_counter()
        return jax.jit(fn).lower(spec).compile(), time.perf_counter() - t0

    # The one-chip programs compile on a second thread while the sharded
    # route compiles and runs: at n = 8192 each expm program spends minutes
    # compiling its LU solve. They run only after the sharded answers.
    engine = MatFnEngine(mesh=mesh, profile=True)
    with ThreadPoolExecutor(1) as pool:
        compiling = {op: pool.submit(compile_one_chip, fn)
                     for op, fn in one_chip.items()}
        for op, a in on_device.items():
            engine.submit(op, a,
                          power=FOUR_CHIP_POWER if op == "matpow" else 1)
        got = dict(zip(on_device, jax.block_until_ready(engine.flush())))
        compiled = {op: f.result() for op, f in compiling.items()}
    got["markov"] = got["markov"].pi
    seconds = {}
    for row in engine.stats["last_flush"]:
        op, route = row["key"][:2]
        seconds[op] = row["seconds"]
        if route != "sharded":
            failures.append(f"{op} n={n} took route {route}, not sharded")

    for op, (exe, compile_s) in compiled.items():
        t0 = time.perf_counter()
        want = np.asarray(jax.block_until_ready(exe(
            jax.device_put(on_device[op], devices[0]))), np.float64)
        one_s = time.perf_counter() - t0
        bound = 0.75 * expm_bound(mats[op]) if op == "expm" \
            else BOUNDS[f"sharded_{op}"]
        floor = wrong_answer_floor(want, [mats[op]])
        err = rel_error(got[op], want)
        ok = err <= bound < floor
        print(f"[chip_smoke] sharded {op} n={n} dtype=float32 vs one-chip "
              f"MatmulChain: rel_err={err:.3e} bound={bound:g} "
              f"wrong_answer_min_err={floor:.3g} "
              f"answer_devices={len(got[op].sharding.device_set)} "
              f"chip_cold_s sharded={seconds.get(op, float('nan')):.3f} "
              f"one_chip_compile_s={compile_s:.1f} one_chip_run_s={one_s:.3f} "
              f"{'ok' if ok else 'FAILED'}")
        if not ok:
            failures.append(f"sharded {op}: rel_err {err:.3e} vs one chip, "
                            f"bound {bound:g}, wrong answers from {floor:.3g}")
    snap = engine.stats()
    print(f"[chip_smoke] sharded engine: routes={snap['routes']} "
          f"compiles={snap['compiles']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded route on a 2x2 mesh and the "
                         "one-chip answers it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    chips = 4 if args.four_chips else 1
    devices = setup(chips)
    failures: List[str] = []
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(devices, FOUR_CHIP_N, args.seed, failures)
    else:
        sync_phase(ONE_CHIP, args.seed, failures)
        daemon_phase(ONE_CHIP, args.seed, failures)
    print(f"[chip_smoke] total chip_wall_s={time.perf_counter() - t0:.1f}")
    if failures:
        fail("; ".join(failures))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
