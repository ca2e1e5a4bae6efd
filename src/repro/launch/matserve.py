"""Matrix-function serving driver: mixed (n, power) traffic through the
bucketing engine.

Batch (library) mode — submit everything, flush once::

    PYTHONPATH=src python -m repro.launch.matserve \
        --requests 64 --sizes 8,16,32 --powers 2,7,12 --expm-frac 0.25

Daemon (continuous-batching) mode — an OPEN-LOOP synthetic traffic
generator submits at a fixed offered rate (arrivals independent of
completions, the honest serving-load model), the background scheduler
flushes buckets on fill-or-deadline, and the report shows per-request
latency percentiles next to throughput::

    PYTHONPATH=src python -m repro.launch.matserve \
        --daemon --rate 500 --requests 256 --sizes 16,32 --powers 7,12

Generates a randomized workload of matpow/expm/markov requests over mixed
sizes, powers, and dtypes and prints throughput plus the engine's
bucket/route/cache statistics. ``--markov-frac`` mixes in stochastic-matrix
traffic: steady-state queries (convergence-aware squaring) and — for the
``--evolve-frac`` share of them — distribution-evolution requests carrying a
``(B, n)`` stack of start distributions. ``--verify`` additionally replays
every request as a per-matrix call and reports the max deviation (0.0
wherever batched and serial run the same kernels — every route off-TPU; the
on-TPU chain/sharded routes differ by kernel accumulation order, see
docs/serving.md).
"""

from __future__ import annotations

import argparse
import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.compile_cache import enable_compile_cache
from repro.serve.admission import POLICIES, AdmissionControl, ShedError
from repro.serve.matfn import ROUTES, MatFnEngine


def make_workload(n_requests: int, sizes, powers, expm_frac: float,
                  seed: int, dtypes=("float32",), markov_frac: float = 0.0,
                  evolve_frac: float = 0.5, evolve_batch: int = 4):
    """A reproducible mixed request list.

    Entries are ``(op, operand, power)`` tuples; markov evolve entries
    (a ``markov_frac * evolve_frac`` share) carry a fourth element, the
    ``(evolve_batch, n)`` stack of start distributions. Everything that
    consumes a workload unpacks ``op, a, power, *rest`` so plain 3-tuple
    workloads (the benchmarks build those) keep working.
    """
    rng = np.random.default_rng(seed)
    work = []
    for _ in range(n_requests):
        n = int(rng.choice(sizes))
        dtype = jnp.dtype(str(rng.choice(dtypes)))
        raw = rng.standard_normal((n, n))
        a = jnp.asarray(raw * 0.4 / np.sqrt(n), dtype)
        draw = rng.random()
        if draw < markov_frac:
            # Derive a stochastic matrix from the already-drawn gaussian:
            # strictly positive rows -> irreducible, aperiodic, fast-mixing
            # (the chain both converges and exercises the early exit), and
            # with markov_frac=0 the rng stream is bit-identical to the
            # pre-markov workloads the benchmarks were tuned on.
            m = np.abs(raw) + 0.05
            p = jnp.asarray(m / m.sum(axis=1, keepdims=True), dtype)
            if rng.random() < evolve_frac:
                d = rng.random((evolve_batch, n))
                d = jnp.asarray(d / d.sum(axis=1, keepdims=True), dtype)
                work.append(("markov", p, int(rng.choice(powers)), d))
            else:
                work.append(("markov", p, 1))
        elif draw < markov_frac + expm_frac:
            work.append(("expm", a, 1))
        else:
            work.append(("matpow", a, int(rng.choice(powers))))
    return work


def run_workload(engine: MatFnEngine, workload):
    """Submit everything, flush once; returns (results, seconds)."""
    t0 = time.perf_counter()
    for op, a, power, *rest in workload:
        engine.submit(op, a, power=power, dists=rest[0] if rest else None)
    results = engine.flush()
    return results, time.perf_counter() - t0


def run_open_loop(engine: MatFnEngine, workload, rate: float, *,
                  timeout: float = 120.0, lanes=None, arrivals=None,
                  tenants=None):
    """Open-loop traffic against a STARTED daemon engine.

    Requests are submitted at their scheduled arrival times ``i / rate``
    regardless of completions (open loop: offered load never backs off when
    the server lags — the regime where a synchronous server's queue grows
    without bound but continuous batching keeps up). ``arrivals`` overrides
    the uniform schedule with explicit per-request offsets in seconds from
    the start (bursty traces); ``lanes`` optionally names the admission
    lane per request (default all ``"bulk"``); ``tenants`` optionally
    names the submitting tenant per request (observability tag — per-
    tenant latency views in ``engine.metrics`` and on request trace
    spans).

    Shedding is part of the measured behavior, not an error: a
    reject-newest shed raises :class:`ShedError` synchronously at submit,
    a reject-oldest / deadline-aware shed resolves an already-admitted
    future with it — both land the ShedError in that request's
    ``results`` slot with a ``None`` latency, and the shed total is
    reported in the returned info dict. Any OTHER failure still raises.

    Latency is measured the way a load-generator client observes it: a
    CONCURRENT collector thread waits on each future in submission order,
    blocks until its answer's device work is done, and charges
    ``now - submit_time``. Running the collector alongside the generator
    matters: a serial collect-after-submit pass would timestamp every
    sub-saturation answer at roughly the end of the submission window and
    report the generator's length, not the daemon's latency. With the
    serving configuration (``profile=False``) futures resolve with
    in-flight arrays and the daemon pipelines device work against host
    assembly — the collector's block is the honest completion point. With
    ``profile=True`` bucket execution already blocked on the scheduler
    thread, so the future's own engine-clock timestamps are used instead —
    ``resolved_at - submitted_at``, BOTH stamped by the engine's clock
    (exact per-request completion, no collector-position skew, no
    mixed-clock arithmetic, at the cost of serializing buckets).

    Returns ``(results, latencies_s, wall_s, info)`` with results and
    latencies in submission order; ``wall_s`` covers submit through last
    collection, and ``info`` carries ``shed`` (total shed count) and
    ``submit_wall_s`` (the submission window alone — what the ACHIEVED
    offered rate is measured over, since the drain tail after the last
    submit is the server's latency, not the generator's pace).
    """
    if not engine.running:
        raise RuntimeError("run_open_loop needs a started daemon engine")
    profiled = engine.profile
    n = len(workload)
    if lanes is None:
        lanes = ["bulk"] * n
    results, lats = [None] * n, [None] * n
    inbox: "queue.Queue" = queue.Queue()
    collector_error = []

    def collect():
        try:
            while True:
                item = inbox.get()
                if item is None:           # sentinel: generator is done
                    return
                i, fut, t0 = item
                try:
                    r = fut.result(timeout=timeout)
                except ShedError as exc:   # reject-oldest revoked this one
                    results[i] = exc
                    continue
                jax.block_until_ready(r)
                results[i] = r
                if profiled and fut.resolved_at is not None \
                        and fut.submitted_at is not None:
                    # Both ends on the ENGINE clock (the engine stamps
                    # resolved_at with the same clock as submitted_at) —
                    # never engine-clock minus perf_counter.
                    lats[i] = fut.resolved_at - fut.submitted_at
                else:
                    lats[i] = time.perf_counter() - t0
        except BaseException as exc:       # surface on the caller thread
            collector_error.append(exc)

    collector = threading.Thread(target=collect, name="matserve-collect")
    collector.start()
    t_start = time.perf_counter()
    submit_wall = 0.0
    try:
        for i, (op, a, power, *rest) in enumerate(workload):
            target = t_start + (arrivals[i] if arrivals is not None
                                else i / rate)
            while True:
                remaining = target - time.perf_counter()
                if remaining <= 0:
                    break
                time.sleep(min(remaining, 5e-4))
            try:
                fut = engine.submit(op, a, power=power,
                                    dists=rest[0] if rest else None,
                                    priority=lanes[i],
                                    tenant=None if tenants is None
                                    else tenants[i])
            except ShedError as exc:       # reject-newest: shed at the door
                results[i] = exc
                continue
            finally:
                submit_wall = time.perf_counter() - t_start
            inbox.put((i, fut, time.perf_counter()))
    finally:
        # Always unblock the collector — a submit raising mid-loop must
        # not leave a non-daemon thread parked on inbox.get() forever.
        inbox.put(None)
        collector.join()
    if collector_error:
        raise collector_error[0]
    shed = sum(1 for r in results if isinstance(r, ShedError))
    info = {"shed": shed, "submit_wall_s": submit_wall}
    return results, lats, time.perf_counter() - t_start, info


def _verify(workload, results):
    from repro.core import (evolve_distributions, expm, matpow_binary,
                            steady_state)

    # One jit wrapper per (op, power) — a fresh jax.jit object per
    # request would recompile the same program for every request.
    fns = {}

    def fn_for(op, power):
        key = (op, power)
        if key not in fns:
            fns[key] = jax.jit(expm) if op == "expm" else \
                jax.jit(lambda x, p=power: matpow_binary(x, p))
        return fns[key]

    worst = 0.0
    rel_worst, compared, skipped = {}, 0, 0
    for (op, a, power, *rest), got in zip(workload, results):
        if isinstance(got, ShedError):     # shed requests have no answer
            continue
        if op == "markov" and rest:        # evolve: compare the dist stacks
            want = evolve_distributions(rest[0], a, power, validate=False)
        elif op == "markov":               # steady state: compare the pis
            want, got = steady_state(a, validate=False).pi, got.pi
        else:
            want = fn_for(op, power)(a)
        worst = max(worst, float(jnp.max(jnp.abs(
            got.astype(jnp.float32) - want.astype(jnp.float32)))))
        ref = host_reference(op, a, power, rest[0] if rest else None)
        if not in_range(ref, got.dtype):
            skipped += 1
            continue
        compared += 1
        rel_worst[op] = max(rel_worst.get(op, 0.0), rel_error(got, ref))
    print(f"[matserve] verify: max |batched - per-matrix| = {worst:.2e}")
    per_op = " ".join(f"{op}={err:.2e}" for op, err in sorted(rel_worst.items()))
    print(f"[matserve] verify: max rel err vs host f64 reference: "
          f"{per_op or 'n/a'} ({compared} compared, {skipped} skipped: "
          f"reference outside the dtype's normal range)")


def host_reference(op, a, power, dists=None):
    """The f64 answer computed on the host with numpy/scipy alone, so it
    shares no code with the engine: ``A^p``, ``e^A``, the stationary
    distribution (a linear solve of ``pi (P - I) = 0, sum(pi) = 1``), or
    ``dists @ P^p``."""
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


def in_range(ref, dtype) -> bool:
    """True when the reference is finite and its largest entry lies where
    ``dtype`` still holds it to full relative precision; an answer whose
    reference underflows (or overflows) carries no information."""
    fi = jnp.finfo(dtype)
    peak = float(np.max(np.abs(ref))) if np.all(np.isfinite(ref)) else np.inf
    return float(fi.tiny) / float(fi.eps) <= peak <= float(fi.max)


def rel_error(got, ref) -> float:
    """Relative Frobenius error ``||got - ref|| / ||ref||`` in f64."""
    got = np.asarray(jnp.asarray(got, jnp.float32), np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def percentile(xs, q):
    """Shared p50/p95 helper (also used by benchmarks/matfn_bench.py)."""
    return float(np.percentile(np.asarray(xs, np.float64), q))


def _parse_capacity(spec):
    """``"bulk=96,latency=32"`` -> AdmissionControl capacity mapping
    (unnamed lanes stay unbounded). ``None``/empty -> all unbounded."""
    caps = {}
    if spec:
        for part in spec.split(","):
            lane, _, val = part.partition("=")
            caps[lane.strip()] = int(val)
    return caps


def _daemon_main(args, workload):
    from repro.serve.scheduler import AdaptiveDeadline, FillOrDeadline

    policy = AdaptiveDeadline() if args.policy == "adaptive" \
        else FillOrDeadline()
    caps = _parse_capacity(args.capacity)
    admission = AdmissionControl(
        capacity={"bulk": caps.get("bulk"), "latency": caps.get("latency")},
        policy=POLICIES[args.admission]())
    # profile=True: futures resolve at device completion, so the latency
    # report measures finished answers (serializes buckets; the report is
    # the point of the driver).
    engine = MatFnEngine(interpret=args.interpret, max_batch=args.max_batch,
                         profile=True, policy=policy,
                         max_delay_ms=args.max_delay_ms,
                         admission=admission,
                         trace=bool(args.trace))
    engine.start()
    # Prewarm every bucket shape the workload can produce so the timed run
    # never pays a compile on the latency path (steady-state serving).
    # Evolve requests are skipped: their bucket classes are keyed on
    # (steps, B) and pay their own first compile (see MatFnEngine.warm).
    for op, n, dtype, power in {(op, a.shape[0], a.dtype.name, p)
                                for op, a, p, *rest in workload
                                if not rest}:
        engine.warm(op, n, dtype=dtype, power=power)
    rng = np.random.default_rng(args.seed + 1)
    lanes = ["latency" if rng.random() < args.priority_frac else "bulk"
             for _ in workload]
    results, lats, wall, info = run_open_loop(engine, workload, args.rate,
                                              lanes=lanes)
    shed = info["shed"]
    snap = engine.stats()
    if args.trace:
        engine.tracer.export(args.trace)
        print(f"[matserve] trace: {len(engine.tracer)} spans "
              f"({engine.tracer.dropped} dropped) -> {args.trace} "
              f"(load in ui.perfetto.dev or chrome://tracing)")
    engine.close()

    offered = args.rate
    served = [t for t in lats if t is not None]
    achieved = len(served) / wall
    print(f"[matserve] daemon: {len(workload)} requests, offered "
          f"{offered:.0f} req/s, served {len(served)} in {wall*1e3:.1f} ms "
          f"({achieved:.0f} req/s) — policy={args.policy} "
          f"max_delay_ms={args.max_delay_ms} "
          f"admission={snap['admission_policy']} shed={shed}")
    if served:
        print(f"[matserve]   latency p50={percentile(served, 50)*1e3:.2f} ms "
              f"p95={percentile(served, 95)*1e3:.2f} ms "
              f"max={max(served)*1e3:.2f} ms")
    trig = snap["flush_triggers"]
    print(f"[matserve]   buckets={snap['buckets']} "
          f"compiles={snap['compiles']} flush_triggers={trig} "
          f"routes={snap['routes']} stragglers={snap['stragglers']} "
          f"retries={snap['retries']}")
    for lane, row in snap["lanes"].items():
        p95 = "n/a" if row["p95_ms"] is None else f"{row['p95_ms']:.2f} ms"
        print(f"[matserve]   lane {lane:8s} submitted={row['submitted']} "
              f"shed={row['shed']} flushed={row['flushed']} "
              f"retried={row['retried']} peak_depth={row['peak_depth']} "
              f"p95={p95}")
    for row in snap["streams"]:
        crashed = "" if row["crashed"] is None \
            else f" CRASHED: {row['crashed']}"
        print(f"[matserve]   {row['label']:24s} executed={row['executed']} "
              f"queued={row['queued']} in_flight={row['in_flight']}"
              f"{crashed}")
    print(f"[matserve]   peak concurrent streams="
          f"{snap['peak_concurrent_streams']}")
    for stage, h in snap["stages"].items():
        print(f"[matserve]   stage {stage:9s} n={h['count']:<6d} "
              f"p50={h['p50']*1e3:7.3f} ms p95={h['p95']*1e3:7.3f} ms "
              f"total={h['sum']*1e3:8.1f} ms")
    for ev in snap["watchdog_events"]:
        print(f"[matserve]   watchdog: step={ev['step']} "
              f"duration={ev['duration_s']*1e3:.2f} ms "
              f"median={ev['median_s']*1e3:.2f} ms")
    if args.verify:
        _verify(workload, results)
    return 0


def _batch_main(args, workload):
    # profile=True: per-bucket wall times for the report below (serializes
    # the flush; serving deployments leave it off).
    engine = MatFnEngine(interpret=args.interpret, max_batch=args.max_batch,
                         profile=True, trace=bool(args.trace))
    # Warm flush compiles the bucket executables; the timed flush reuses them
    # (steady-state serving: compiles are a one-time cost per bucket shape).
    run_workload(engine, workload)
    results, dt = run_workload(engine, workload)
    results = jax.block_until_ready(results)

    s = engine.stats
    # Per-FLUSH numbers from the timed flush's bucket rows — the engine's
    # cumulative counters also include the warm flush and would read 2x
    # next to the single-flush throughput line. Compiles stay cumulative
    # (they all happened in the warm flush; the timed flush reuses them).
    rows = s["last_flush"]
    routes = {r: sum(1 for x in rows if x["route"] == r) for r in ROUTES}
    padded = sum(x["padded_batch"] - x["requests"] for x in rows)
    print(f"[matserve] {args.requests} requests in {dt*1e3:.1f} ms "
          f"({args.requests/dt:.0f} req/s) — thresholds={engine.thresholds}")
    print(f"[matserve]   buckets={len(rows)} "
          f"compiles={s['compiles']} (warm flush) "
          f"padded_slots={padded} routes={routes}")
    for row in rows:
        op, route, bpad, n, dtype, power = row["key"]
        # markov evolve buckets carry a ('evolve', steps, B) power slot
        p = power if isinstance(power, int) else f"{power[1]}x{power[2]}"
        print(f"[matserve]   bucket {op:6s} n={n:<5d} p={p!s:<4} {dtype} "
              f"-> {route:6s} B={row['requests']}/{row['padded_batch']} "
              f"{row['seconds']*1e3:7.2f} ms")
    if args.trace:
        engine.tracer.export(args.trace)
        print(f"[matserve] trace: {len(engine.tracer)} spans -> "
              f"{args.trace}")
    if args.verify:
        _verify(workload, results)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--sizes", default="8,16,32",
                    help="comma-separated matrix sizes")
    ap.add_argument("--powers", default="2,7,12",
                    help="comma-separated matpow powers")
    ap.add_argument("--expm-frac", type=float, default=0.25,
                    help="fraction of requests that are expm")
    ap.add_argument("--markov-frac", type=float, default=0.0,
                    help="fraction of requests that are stochastic-matrix "
                         "(markov) traffic")
    ap.add_argument("--evolve-frac", type=float, default=0.5,
                    help="fraction of markov requests that evolve a "
                         "distribution stack (the rest query the steady "
                         "state)")
    ap.add_argument("--evolve-batch", type=int, default=4,
                    help="distributions per evolve request (B)")
    ap.add_argument("--dtypes", default="float32",
                    help="comma-separated operand dtypes (e.g. float32,bfloat16)")
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interpret", action="store_true",
                    help="run the chain route's Pallas kernel bodies on CPU")
    ap.add_argument("--verify", action="store_true",
                    help="replay per-matrix and report max deviation")
    ap.add_argument("--daemon", action="store_true",
                    help="continuous-batching daemon + open-loop traffic")
    ap.add_argument("--rate", type=float, default=500.0,
                    help="daemon mode: offered load, requests/second")
    ap.add_argument("--max-delay-ms", type=float, default=None,
                    help="daemon mode: bucket flush deadline override "
                         "(default: tuned per traffic class from the "
                         "dispatch namespace)")
    ap.add_argument("--policy", choices=("fill", "adaptive"), default="fill",
                    help="daemon flush policy (docs/serving.md)")
    ap.add_argument("--admission", choices=sorted(POLICIES),
                    default="reject-newest",
                    help="daemon mode: shed policy on lane overflow")
    ap.add_argument("--capacity", default="",
                    help="daemon mode: per-lane queue bounds, e.g. "
                         "'bulk=96,latency=32' (default: unbounded)")
    ap.add_argument("--priority-frac", type=float, default=0.0,
                    help="daemon mode: fraction of requests submitted on "
                         "the latency lane")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record request-lifecycle spans and write a "
                         "Chrome trace-event JSON (Perfetto-loadable) "
                         "to PATH")
    args = ap.parse_args(argv)

    if args.daemon and args.rate <= 0:
        ap.error("--rate must be > 0 requests/second")
    if not 0.0 <= args.priority_frac <= 1.0:
        ap.error("--priority-frac must be in [0, 1]")
    if args.max_delay_ms is not None and args.max_delay_ms <= 0:
        ap.error("--max-delay-ms must be > 0")
    if not 0.0 <= args.markov_frac <= 1.0 or \
            not 0.0 <= args.evolve_frac <= 1.0:
        ap.error("--markov-frac and --evolve-frac must be in [0, 1]")
    if args.markov_frac + args.expm_frac > 1.0:
        ap.error("--markov-frac + --expm-frac must not exceed 1")
    if args.evolve_batch < 1:
        ap.error("--evolve-batch must be >= 1")
    enable_compile_cache()
    sizes = [int(s) for s in args.sizes.split(",")]
    powers = [int(p) for p in args.powers.split(",")]
    dtypes = args.dtypes.split(",")
    workload = make_workload(args.requests, sizes, powers, args.expm_frac,
                             args.seed, dtypes=dtypes,
                             markov_frac=args.markov_frac,
                             evolve_frac=args.evolve_frac,
                             evolve_batch=args.evolve_batch)
    if args.daemon:
        return _daemon_main(args, workload)
    return _batch_main(args, workload)


if __name__ == "__main__":
    raise SystemExit(main())
