"""Matrix-function serving bench: batched buckets vs per-request serial.

    PYTHONPATH=src python -m benchmarks.matfn_bench [--quick] [--json PATH]

Replays one mixed (n, power) workload two ways:

  * **serial**  — every request is its own jitted per-matrix
    ``matpow_binary`` / ``expm`` call (warm executables; the realistic
    "no serving layer" baseline), timed per request;
  * **batched** — the whole workload goes through
    ``repro.serve.matfn.MatFnEngine`` (bucketing + batched chains +
    executable cache), one warm flush timed end to end; each request's
    latency is its bucket's execution time.

ALWAYS writes ``BENCH_matfn.json``: requests/sec and p50/p95 latency for
both modes, the batched-vs-serial speedup, and whether the batched answers
are bit-identical to the per-matrix calls (they must be — the engine's
contract). CI asserts speedup >= 1.1 and bit_identical on the CPU smoke
config (``--quick``, bounded well under 60 s).

``--open-loop`` additionally benches the continuous-batching daemon under
OPEN-LOOP arrivals (requests submitted at a fixed offered rate, independent
of completions) at several load factors relative to the measured serial
capacity, and records p50/p95 latency vs offered load into the JSON's
``open_loop`` section. The synchronous comparison point is a simulated
strict-FIFO one-at-a-time server fed the SAME arrival times and the
measured warm per-request service times — deterministic, and the honest
"no serving layer" queueing model: above capacity its queue (and p95)
grows with the run while the daemon batches and keeps up. CI asserts
daemon p95 <= 3x synchronous p95 at every load factor >= 1.5 and that
daemon answers stay bit-identical to one synchronous ``flush()`` of the
same workload.

``--open-loop`` also runs the OVERLOAD trace (``json['overload']``): a
MULTI-TENANT bursty workload — two hot tenants flooding the xla route
with a mixed-lane burst trace (25% latency-lane) plus one cold tenant
trickling chain-route (n=96) bulk requests — offered by concurrent
producer threads against a daemon with bounded per-lane queues. This is
both the admission-control acceptance run and the execution-stream
overlap run. CI asserts the applied overload was real
(``load_vs_drain`` — offered rate over drained rate — >= 2x), the shed
rate is nonzero but bounded, every SERVED answer stays bit-identical to
its per-matrix reference (shedding never corrupts survivors), per-lane
peak queue depth never exceeds the configured capacity, the latency
lane's engine-side p95 is <= 0.5x the bulk lane's, the xla and chain
execution streams were observed concurrently busy
(``overlap.peak_concurrent_streams >= 2``), and the cold tenant is not
starved (its served p95 stays within a small factor of the hot
tenants').
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.matserve import percentile as _percentile
from repro.runtime.compile_cache import enable_compile_cache


def bench_both(workload, *, rounds=7, max_batch=64, interpret=False):
    """Interleaved serial/batched rounds over one workload.

    Each round runs the full serial pass (per-request jitted calls, the
    realistic "no serving layer" baseline) back-to-back with one engine
    flush, and both take their min over rounds — the estimator most robust
    to shared-runner load, which would otherwise hit whichever phase it
    landed on (the same discipline as ``benchmarks/run.py:chain_bench``).

    Returns (serial_results, serial_latencies, serial_wall,
    batched_results, batched_latencies, batched_wall, engine_stats).
    """
    from repro.core import expm, matpow_binary
    from repro.kernels import autotune
    from repro.serve.matfn import MatFnEngine, MatFnRequest

    fns = {}

    def fn_for(op, power):
        key = (op, power)
        if key not in fns:
            if op == "matpow":
                fns[key] = jax.jit(lambda x, p=power: matpow_binary(x, p))
            else:
                fns[key] = jax.jit(expm)
        return fns[key]

    # Thresholds pinned to the defaults: the bench's route split (and the
    # CI asserts built on it) must not depend on whatever dispatch entry a
    # developer's ambient autotune cache happens to hold.
    engine = MatFnEngine(max_batch=max_batch, interpret=interpret,
                         thresholds=autotune.DEFAULT_DISPATCH_THRESHOLDS)

    def flush_once():
        for op, a, power in workload:
            engine.submit(op, a, power=power)
        t0 = time.perf_counter()
        out = jax.block_until_ready(engine.flush())
        return out, time.perf_counter() - t0

    # Warm every executable on both sides (compile once per bucket shape /
    # per (op, power, shape) — steady-state serving).
    for op, a, power in workload:
        jax.block_until_ready(fn_for(op, power)(a))
    flush_once()

    n = len(workload)
    serial_results = [None] * n
    serial_lat = [float("inf")] * n
    serial_wall = batched_wall = float("inf")
    for _ in range(rounds):
        t_round = time.perf_counter()
        for i, (op, a, power) in enumerate(workload):
            fn = fn_for(op, power)
            t0 = time.perf_counter()
            serial_results[i] = jax.block_until_ready(fn(a))
            serial_lat[i] = min(serial_lat[i], time.perf_counter() - t0)
        serial_wall = min(serial_wall, time.perf_counter() - t_round)
        batched_results, w = flush_once()
        batched_wall = min(batched_wall, w)

    # Per-request batched latency: a separate profiled flush (per-bucket
    # wall times; every member of a bucket is answered by the same
    # dispatch, so each request inherits its bucket's time).
    engine.profile = True
    flush_once()
    per_group = {}
    for row in engine.stats["last_flush"]:
        op, _route, _bpad, size, dtype, power = row["key"]
        per_group.setdefault((op, size, dtype, power), []).append(
            row["seconds"])
    batched_lat = []
    for op, a, power in workload:
        req = MatFnRequest(op, a, power)
        batched_lat.append(float(np.mean(per_group[req.bucket_key()])))
    return (serial_results, serial_lat, serial_wall,
            batched_results, batched_lat, batched_wall, engine.stats)


def chain_route_gate(*, n=96, b=6, power=7, seed=0):
    """Run one bucket through the batched-chain route and check its answers.

    The throughput workload sits at sizes <= the default cpu_max_n of 64
    (where batching wins robustly on 2 CI cores), which would leave the
    ``chain`` route — the subsystem's headline stacked BatchedMatmulChain
    path — unexecuted by this bench. This gate submits n > cpu_max_n
    traffic, asserts the route actually fired, and compares against
    per-matrix jitted calls: off-TPU the chain degrades to the same XLA dot
    (bit-identical); on TPU it runs the Pallas kernel (tolerance only —
    reported, not asserted here; tests/test_matfn.py holds the numerics).
    """
    from repro.core import matpow_binary
    from repro.kernels import autotune
    from repro.serve.matfn import MatFnEngine

    rng = np.random.default_rng(seed)
    # Defaults pinned for the same reason as bench_both: a recorded
    # dispatch entry with cpu_max_n >= 96 would silently re-route this
    # gate's traffic to xla and fail the CI chain_buckets assert.
    eng = MatFnEngine(thresholds=autotune.DEFAULT_DISPATCH_THRESHOLDS)
    mats = [jnp.asarray(rng.standard_normal((n, n)) * 0.05, jnp.float32)
            for _ in range(b)]
    for m in mats:
        eng.submit("matpow", m, power=power)
    res = eng.flush()
    want = [jax.jit(lambda x: matpow_binary(x, power))(m) for m in mats]
    err = max(float(jnp.max(jnp.abs(r - w))) for r, w in zip(res, want))
    return {
        "chain_buckets": eng.stats["routes"]["chain"],
        "bit_identical": all(np.array_equal(np.asarray(r), np.asarray(w))
                             for r, w in zip(res, want)),
        "max_abs_err": err,
    }


def bench_open_loop(*, quick=False, seed=0):
    """Daemon latency vs offered load under open-loop arrivals.

    Measures warm per-request serial service times first; each load row
    offers ``factor / mean_service`` requests per second to (a) a simulated
    strict-FIFO synchronous server (same arrivals, measured service times —
    deterministic) and (b) the live continuous-batching daemon in the
    serving configuration (completion observed at the collector, see
    ``run_open_loop``). ``bit_identical`` compares every daemon answer
    against one synchronous ``flush()`` of the same workload — the daemon
    must never change the math, only the schedule.
    """
    from repro.core import matpow_binary
    from repro.kernels import autotune
    from repro.launch.matserve import make_workload, run_open_loop
    from repro.serve.matfn import MatFnEngine

    n_requests = 256
    # Same hot-shape family as the closed-loop bench: the sizes where CI
    # already proves batched bucket execution beats per-request serial.
    sizes, powers = (16, 32, 64), (7, 12)
    max_batch, max_delay_ms = 16, 2.0
    # Sub-saturation rows (< 1) tabulate the honest latency COST of
    # batching — the daemon waits out its deadline while an idle serial
    # server answers in microseconds (docs/serving.md's tradeoff table).
    # The CI-gated rows are the heavy-overload factors (>= 1.5): there both
    # servers queue, backlog dominates every fixed floor, and p95s settle
    # at ~N/throughput on each side — so daemon p95 <= 3x sync p95 holds on
    # any machine where batched throughput is at least ~1/3 of serial
    # (CI separately asserts it is >= 1.1x), not just on runners with some
    # particular absolute speed.
    load_factors = (0.5, 8.0) if quick else (0.25, 0.5, 1.0, 2.0, 8.0)

    # matpow-only: expm buckets ride the same scheduler; keeping the
    # open-loop workload single-op keeps the warm phase (one compile per
    # (class, batch-size)) bounded.
    workload = make_workload(n_requests, sizes, powers, expm_frac=0.0,
                             seed=seed)

    fns = {}

    def fn_for(power):
        if power not in fns:
            fns[power] = jax.jit(lambda x, p=power: matpow_binary(x, p))
        return fns[power]

    # Warm per-request serial service times — the FIFO simulator's input
    # AND the capacity estimate the offered rates are anchored to. MEDIAN
    # over reps, not min: the simulated server must pay what a real
    # synchronous server pays per request (dispatch and all); the min-of-
    # reps estimator the throughput benches use would make the baseline
    # optimistically fast and turn the latency gate into a machine-speed
    # lottery.
    service = []
    for _op, a, power in workload:
        fn = fn_for(power)
        jax.block_until_ready(fn(a))
        reps = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(a))
            reps.append(time.perf_counter() - t0)
        service.append(float(np.median(reps)))
    mean_service = float(np.mean(service))

    # Bit-identity reference: one synchronous engine flush of the workload.
    sync_eng = MatFnEngine(max_batch=max_batch,
                           thresholds=autotune.DEFAULT_DISPATCH_THRESHOLDS)
    for op, a, power in workload:
        sync_eng.submit(op, a, power=power)
    sync_results = [np.asarray(r) for r in sync_eng.flush()]

    # ONE live daemon reused across every load row (the executable cache is
    # per-engine — a fresh engine per row would recompile ~all bucket
    # executables per row for no measurement benefit), in the SERVING
    # configuration (profile=False: buckets dispatch asynchronously, device
    # work overlaps host assembly; run_open_loop measures completion at the
    # collector). Thresholds pinned like bench_both; per-class warm so no
    # compile lands on the latency path. Rows report trigger DELTAS.
    eng = MatFnEngine(max_batch=max_batch,
                      thresholds=autotune.DEFAULT_DISPATCH_THRESHOLDS,
                      max_delay_ms=max_delay_ms)
    eng.start()
    for op, n, dtype, power in sorted({(op, a.shape[0], a.dtype.name, p)
                                       for op, a, p in workload}):
        eng.warm(op, n, dtype=dtype, power=power)

    rows = []
    for factor in load_factors:
        rate = factor / mean_service
        # Simulated strict-FIFO synchronous server over the same arrivals.
        t = 0.0
        sync_lat = []
        for i, s in enumerate(service):
            t = max(t, i / rate) + s
            sync_lat.append(t - i / rate)
        before = dict(eng.stats["flush_triggers"])
        results, lats, wall, _info = run_open_loop(eng, workload, rate)
        triggers = {k: v - before[k]
                    for k, v in eng.stats["flush_triggers"].items()}
        rows.append({
            "load_factor": factor,
            "offered_rps": round(rate, 1),
            "achieved_rps": round(n_requests / wall, 1),
            "sync_p50_us": round(_percentile(sync_lat, 50) * 1e6, 1),
            "sync_p95_us": round(_percentile(sync_lat, 95) * 1e6, 1),
            "daemon_p50_us": round(_percentile(lats, 50) * 1e6, 1),
            "daemon_p95_us": round(_percentile(lats, 95) * 1e6, 1),
            "bit_identical": bool(all(
                np.array_equal(np.asarray(r), s)
                for r, s in zip(results, sync_results))),
            "flush_triggers": triggers,
        })
    eng.close()
    return {
        "n_requests": n_requests,
        "sizes": list(sizes),
        "powers": list(powers),
        "max_batch": max_batch,
        "max_delay_ms": max_delay_ms,
        "mean_service_us": round(mean_service * 1e6, 1),
        "rows": rows,
    }


def bench_overload_shedding(*, quick=False, seed=0, hot_tenants=2):
    """Admission control + stream overlap under a multi-tenant overload
    trace: 2 HOT bursty tenants and 1 COLD trickle tenant.

    Each tenant is one open-loop generator thread (concurrent clients
    are the realistic front-door model, and one Python thread tops out
    near the daemon's own drain rate — several are needed to actually
    overload it; the admission suite separately proves shed counts stay
    exact under 6 producers):

      * **hot-0 / hot-1** shard a bursty mixed-lane trace round-robin
        (bursts of 64 back-to-back submits, 25% latency lane, sizes
        16 and 32 — the ``xla`` route) at a combined 8x the serial
        capacity: the overload.
      * **cold** trickles uniformly-spaced ``n=96`` bulk requests — the
        ``chain`` route, i.e. a DIFFERENT execution stream — at under a
        tenth of the hot offered rate, across the same window.

    The multi-tenant shape is what exercises the per-route execution
    streams end to end: cold chain buckets execute on the chain stream
    WHILE the xla stream drains the hot backlog (``overlap`` records the
    pool's peak concurrently-busy streams — CI gates >= 2 — and the
    per-stream executed counts), and an in-flight chain bucket never
    blocks a due hot flush. Fairness is per-TENANT accounting on top of
    per-LANE capacity: tenants share the bulk lane's bound, so the cold
    tenant pays the same admission odds as hot bulk traffic, but its
    SERVED requests keep a bounded p95 (deadline + chain service, not
    the hot backlog) — ``cold_p95_over_hot_p95`` is the starvation
    metric CI holds.

    The parameters are chosen to make the gated outcomes STRUCTURAL, not
    machine-speed luck:

      * ``max_batch=64`` with bulk capacity 32 means bulk buckets never
        fill — they flush on the 40 ms class deadline, so the bulk lane
        drains at most ~capacity per deadline window (~800 req/s) and
        sheds the rest of each burst; offered load beyond that turns
        into shed rate, not queue depth (``load_vs_drain = offered /
        drain >= 2`` is the overload gate, and ``1 / (1 - shed_rate)``
        is the same quantity). The bound matters MORE with execution
        streams than it did in PR 6: direct priority bypass means the
        latency lane barely sheds at all now, so the capped bulk lane
        has to carry the whole overload signal — the cap is sized so
        bulk drain plus the (uncapped) latency drain stays under half
        the slowest credible generator's offered rate.
      * The latency lane flushes under its 0.5 ms SLO cap (and half its
        traffic, n=32 >= bypass_n, skips assembly entirely — handed
        straight to the xla stream at submit), is executed before bulk
        in every scheduler poll, and preempts the remaining bulk backlog
        between bucket executions. Its engine-side wait is bounded by
        one in-progress bulk execution, while an admitted bulk request
        waits out the 40 ms deadline plus backlog — the wide deadline
        split is what keeps the p95 ratio gate (<= 0.5) safe from
        scheduler-timing noise.
      * Capacity enforcement at submit makes peak depth <= capacity an
        invariant; the bench records it so CI can hold the line.

    ``bit_identical`` compares every SERVED answer — hot and cold —
    against a warm per-matrix jitted reference: shedding and stream
    concurrency must never corrupt survivors.
    """
    from repro.core import matpow_binary
    from repro.kernels import autotune
    from repro.launch.matserve import run_open_loop
    from repro.serve.admission import AdmissionControl, RejectNewest
    from repro.serve.matfn import MatFnEngine

    n_requests = 1536 if quick else 3072
    n_cold = n_requests // 8
    n_hot = n_requests - n_cold
    hot_sizes, cold_size, power = (16, 32), 96, 7
    burst, priority_frac = 64, 0.25
    max_batch, max_delay_ms = 64, 40.0
    capacity = {"bulk": 32, "latency": 8}
    slo_ms = {"latency": 0.5, "bulk": None}
    bypass_n = 32

    rng = np.random.default_rng(seed + 7)

    def _mat(n):
        return jnp.asarray(rng.standard_normal((n, n)) * 0.4 / np.sqrt(n),
                           jnp.float32)

    hot_workload = [("matpow", _mat(int(rng.choice(hot_sizes))), power)
                    for _ in range(n_hot)]
    hot_lanes = ["latency" if rng.random() < priority_frac else "bulk"
                 for _ in range(n_hot)]
    cold_workload = [("matpow", _mat(cold_size), power)
                     for _ in range(n_cold)]

    # Warm per-matrix references double as the serial-capacity estimate
    # and the bit-identity oracle for every served request (cold n=96
    # included — on CPU the chain route degrades to the same XLA dot, so
    # survivors stay bit-identical across routes).
    ref_fn = jax.jit(lambda x: matpow_binary(x, power))
    service = []

    def _refs(workload):
        out = []
        for _op, a, _p in workload:
            jax.block_until_ready(ref_fn(a))  # warm per shape (2 compiles)
            t0 = time.perf_counter()
            out.append(np.asarray(jax.block_until_ready(ref_fn(a))))
            service.append(time.perf_counter() - t0)
        return out

    hot_refs, cold_refs = _refs(hot_workload), _refs(cold_workload)
    serial_capacity = 1.0 / float(np.mean(service))

    rate = 8.0 * serial_capacity
    # Hot arrivals: bursts of ``burst`` back-to-back submits, burst
    # starts spaced to hold the 8x mean rate. Cold arrivals: a uniform
    # trickle across the same submission window (phase-shifted off the
    # burst starts), well under the chain stream's capacity so a served
    # cold request's latency is deadline + chain service, never a queue
    # that grows with the run.
    hot_arrivals = [(i // burst) * (burst / rate) for i in range(n_hot)]
    # The hot target rate is deliberately unachievable (the generator
    # threads are the bottleneck — that is what makes the trace an
    # overload), so the REAL submission window is generator-bound:
    # empirically ~4x the serial-capacity replay time on 1-2 core
    # hosts. Spread the cold trickle over that estimate so it genuinely
    # spans the hot window (one chain bucket per few deadline windows)
    # instead of front-loading into the first few milliseconds.
    window = 4.0 * n_requests / serial_capacity
    cold_arrivals = [(j + 0.5) * window / n_cold for j in range(n_cold)]
    cold_rate = n_cold / window

    # trace=True: the overload run doubles as the tracing-overhead gate —
    # the bit-identity and shed/latency assertions below must hold WITH
    # request-lifecycle tracing on, and the exported span counts feed the
    # trace-completeness row CI gates.
    eng = MatFnEngine(
        max_batch=max_batch, max_delay_ms=max_delay_ms,
        thresholds=autotune.DEFAULT_DISPATCH_THRESHOLDS,
        admission=AdmissionControl(capacity=capacity, policy=RejectNewest(),
                                   slo_ms=slo_ms, bypass_n=bypass_n),
        trace=True)
    eng.start()
    for n in (*hot_sizes, cold_size):
        eng.warm("matpow", n, power=power)
    # Post-warm stage baseline: warm chunks run the same _run_chunk core
    # and would otherwise dominate the stage breakdown with compile time;
    # the reported fractions cover the traced window only.
    _STAGES = ("queue", "assemble", "execute", "resolve")
    stage_base = {}
    for s in _STAGES:
        h = eng.metrics.merged("stage", stage=s)
        stage_base[s] = (h.count, h.sum)
    # Default 5 ms GIL switch interval convoys the scheduler behind the
    # full-tilt generator thread (each boundary crossing inside a flush
    # can stall a whole quantum, stretching a 1 ms flush past 20 ms);
    # 0.2 ms restores honest thread interleaving for the duration of the
    # trace. A real multi-process front end does not share a GIL with the
    # scheduler at all.
    switch = sys.getswitchinterval()
    sys.setswitchinterval(2e-4)
    # A cyclic-GC pass over the trace's hundreds of thousands of live
    # futures/requests stalls whichever thread it lands on for 100-200 ms
    # — when that is the scheduler mid-flush, one stall dominates both
    # lanes' p95 and the run measures the collector, not the engine.
    # Freeze the pre-trace heap and disable collection for the trace
    # (nothing in it is cyclic garbage; allocation still frees normally).
    gc.collect()
    gc.freeze()
    gc.disable()
    # Round-robin sharding keeps every hot tenant's arrival schedule
    # monotone and keeps the bursts aligned across tenants, so the
    # combined hot trace still lands ``burst`` requests per burst
    # window. The cold tenant submits its whole trickle itself.
    shards = [list(range(p, n_hot, hot_tenants)) for p in range(hot_tenants)]
    tenant_names = [f"hot-{p}" for p in range(hot_tenants)] + ["cold"]
    outs = {}
    errors = []

    def hot_producer(p, idx):
        try:
            outs[f"hot-{p}"] = run_open_loop(
                eng, [hot_workload[i] for i in idx], rate / hot_tenants,
                lanes=[hot_lanes[i] for i in idx],
                arrivals=[hot_arrivals[i] for i in idx],
                tenants=[f"hot-{p}"] * len(idx))
        except BaseException as exc:      # surface on the caller thread
            errors.append(exc)

    def cold_producer():
        try:
            outs["cold"] = run_open_loop(
                eng, cold_workload, cold_rate,
                lanes=["bulk"] * n_cold, arrivals=cold_arrivals,
                tenants=["cold"] * n_cold)
        except BaseException as exc:
            errors.append(exc)

    try:
        threads = [threading.Thread(target=hot_producer, args=(p, shard),
                                    name=f"overload-hot-{p}")
                   for p, shard in enumerate(shards)]
        threads.append(threading.Thread(target=cold_producer,
                                        name="overload-cold"))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        sys.setswitchinterval(switch)
        gc.enable()
        gc.unfreeze()
    if errors:
        raise errors[0]
    snap = eng.stats()
    eng.close()

    hot_results = [None] * n_hot
    for shard, name in zip(shards, tenant_names):
        for j, i in enumerate(shard):
            hot_results[i] = outs[name][0][j]
    shed = sum(outs[name][3]["shed"] for name in tenant_names)
    served = n_requests - shed
    # Offered rate over the SUBMISSION window (the drain tail after the
    # last submit is server latency, not generator pace). The drain rate
    # is what the daemon actually cleared over that same window, so
    # offered/drain == n_requests/served == 1/(1 - shed_rate): the
    # overload factor the admission layer absorbed.
    submit_wall = max(outs[name][3]["submit_wall_s"]
                      for name in tenant_names)
    achieved_rps = n_requests / submit_wall
    drain_rps = served / submit_wall

    # -- stage breakdown (post-warm deltas over the traced window) --------
    stages = {}
    total_stage_s = 0.0
    for s in _STAGES:
        h = eng.metrics.merged("stage", stage=s)
        c0, s0 = stage_base[s]
        d_sum = max(h.sum - s0, 0.0)
        stages[s] = {"count": h.count - c0, "sum_s": round(d_sum, 6)}
        total_stage_s += d_sum
    for row in stages.values():
        row["fraction"] = (round(row["sum_s"] / total_stage_s, 4)
                           if total_stage_s > 0 else None)

    # -- trace completeness (every request ends in ONE terminal span) -----
    tr = eng.tracer
    req_spans = [s for s in tr.spans() if s["name"] == "request"]
    outcomes: dict = {}
    for s in req_spans:
        o = s["args"]["outcome"]
        outcomes[o] = outcomes.get(o, 0) + 1
    trace_info = {
        "spans": len(tr),
        "dropped": tr.dropped,
        "request_spans": len(req_spans),
        "outcomes": outcomes,
        # Complete: nothing evicted from the ring, one terminal request
        # span per submitted request, outcome totals exactly matching the
        # engine's served/shed accounting.
        "complete": bool(tr.dropped == 0
                         and len(req_spans) == n_requests
                         and outcomes.get("resolved", 0) == served
                         and outcomes.get("shed", 0) == shed),
    }
    bit_identical = all(
        np.array_equal(np.asarray(r), ref)
        for r, ref in zip(hot_results + list(outs["cold"][0]),
                          hot_refs + cold_refs)
        if not isinstance(r, Exception))

    # -- per-tenant fairness rows (client-observed latency) ---------------
    def tenant_row(n, lats, info):
        ok = [l for l in lats if l is not None]
        return {
            "offered": n,
            "shed": info["shed"],
            "served": n - info["shed"],
            "shed_rate": round(info["shed"] / n, 4),
            "p50_ms": round(_percentile(ok, 50) * 1e3, 3) if ok else None,
            "p95_ms": round(_percentile(ok, 95) * 1e3, 3) if ok else None,
        }

    tenants = {}
    for name, shard in zip(tenant_names, shards):
        tenants[name] = tenant_row(len(shard), outs[name][1],
                                   outs[name][3])
    tenants["cold"] = tenant_row(n_cold, outs["cold"][1], outs["cold"][3])
    hot_lats = [l for p in range(hot_tenants)
                for l in outs[f"hot-{p}"][1] if l is not None]
    cold_lats = [l for l in outs["cold"][1] if l is not None]
    hot_p95 = _percentile(hot_lats, 95) * 1e3 if hot_lats else None
    cold_p95 = _percentile(cold_lats, 95) * 1e3 if cold_lats else None
    cold_over_hot = (None if not hot_p95 or not cold_p95
                     else round(cold_p95 / hot_p95, 3))

    # -- stream overlap (did two routes actually execute concurrently?) ---
    stream_rows = snap["streams"]

    def _stream_executed(route):
        return sum(r["executed"] for r in stream_rows
                   if route in r["routes"])

    overlap = {
        # High-water mark of concurrently-BUSY streams (warm jobs do not
        # count — only dispatched buckets): >= 2 means a chain bucket
        # and an xla bucket were provably in execution at the same time.
        "peak_concurrent_streams": snap["peak_concurrent_streams"],
        "xla_stream_executed": _stream_executed("xla"),
        "chain_stream_executed": _stream_executed("chain"),
        "streams": {r["label"]: r["executed"] for r in stream_rows},
    }
    lane_rows = {}
    for lane, row in snap["lanes"].items():
        arrived = row["submitted"] + row["shed"]
        lane_rows[lane] = {
            "submitted": row["submitted"],
            "shed": row["shed"],
            "flushed": row["flushed"],
            "peak_depth": row["peak_depth"],
            "capacity": capacity[lane],
            "shed_rate": round(row["shed"] / arrived, 4) if arrived else 0.0,
            "p95_ms": None if row["p95_ms"] is None
            else round(row["p95_ms"], 3),
        }
    lat_p95 = lane_rows["latency"]["p95_ms"]
    bulk_p95 = lane_rows["bulk"]["p95_ms"]
    return {
        "n_requests": n_requests,
        "n_hot": n_hot,
        "n_cold": n_cold,
        "burst": burst,
        "priority_frac": priority_frac,
        "max_batch": max_batch,
        "max_delay_ms": max_delay_ms,
        "capacity": capacity,
        "slo_ms": slo_ms,
        "bypass_n": bypass_n,
        "policy": snap["admission_policy"],
        "producers": hot_tenants + 1,
        "tenants": tenants,
        "cold_p95_over_hot_p95": cold_over_hot,
        "overlap": overlap,
        "serial_capacity_rps": round(serial_capacity, 1),
        "offered_rps_target": round(rate, 1),
        "offered_rps_achieved": round(achieved_rps, 1),
        "drain_rps_achieved": round(drain_rps, 1),
        "load_vs_serial": round(achieved_rps / serial_capacity, 2),
        "load_vs_drain": round(n_requests / served, 2) if served else None,
        "served": served,
        "shed": shed,
        "shed_rate": round(shed / n_requests, 4),
        "bit_identical": bool(bit_identical),
        "queue_bounded": bool(all(
            r["peak_depth"] <= r["capacity"] for r in lane_rows.values())),
        "latency_p95_over_bulk_p95": (
            None if not lat_p95 or not bulk_p95
            else round(lat_p95 / bulk_p95, 3)),
        "lanes": lane_rows,
        "flush_triggers": snap["flush_triggers"],
        "stragglers": snap["stragglers"],
        "retries": snap["retries"],
        "stages": stages,
        "trace": trace_info,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CPU smoke config (<60 s): small sizes, 48 requests")
    ap.add_argument("--open-loop", action="store_true",
                    help="also bench the daemon under open-loop arrivals "
                         "(latency vs offered load -> json['open_loop'])")
    ap.add_argument("--json", default="BENCH_matfn.json")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    from repro.launch.matserve import make_workload

    # Few (n, power) combos x many requests: serving traffic concentrates on
    # hot shapes, and the speedup comes from full buckets — one dispatch for
    # B requests AND the batched dot parallelizing over the stack, where a
    # serial loop runs one small single-threaded gemm at a time. Sizes start
    # at 16: below that both modes sit at the dispatch floor and the
    # comparison measures scheduler noise, not the engine.
    if args.quick:
        n_requests = args.requests or 96
        sizes, powers, expm_frac = (16, 32, 64), (7, 12), 0.125
    else:
        n_requests = args.requests or 256
        sizes, powers, expm_frac = (16, 32, 64, 128), (7, 12, 25), 0.125
    workload = make_workload(n_requests, sizes, powers, expm_frac=expm_frac,
                             seed=args.seed)

    (serial_res, serial_lat, serial_wall,
     batched_res, batched_lat, batched_wall, stats) = bench_both(workload)

    bit_identical = all(
        np.array_equal(np.asarray(b), np.asarray(s))
        for b, s in zip(batched_res, serial_res))

    chain_gate = chain_route_gate(seed=args.seed)
    out = {
        "n_requests": n_requests,
        "serial_rps": round(n_requests / serial_wall, 1),
        "batched_rps": round(n_requests / batched_wall, 1),
        "serial_p50_us": round(_percentile(serial_lat, 50) * 1e6, 1),
        "serial_p95_us": round(_percentile(serial_lat, 95) * 1e6, 1),
        "batched_p50_us": round(_percentile(batched_lat, 50) * 1e6, 1),
        "batched_p95_us": round(_percentile(batched_lat, 95) * 1e6, 1),
        "batched_speedup_vs_serial": round(serial_wall / batched_wall, 2),
        "bit_identical": bool(bit_identical),
        "n_buckets": len(stats["last_flush"]),
        # Per-FLUSH route counts (from the last flush's bucket rows) — the
        # engine's stats["routes"] counter accumulates across all warm/
        # timed/profiled flushes and would read 9x inflated here.
        "routes": {r: sum(1 for row in stats["last_flush"]
                          if row["route"] == r)
                   for r in ("xla", "chain", "sharded")},
        "executable_compiles": stats["compiles"],
        "chain_route": chain_gate,
        # Batched-vs-serial is a CORE-COUNT story (the stacked dot
        # parallelizes over B; a 1-core host collapses it to dispatch
        # amortization, ~1x) — record the host so trajectory diffs
        # against the committed JSON are interpretable.
        "host_cpus": os.cpu_count(),
    }
    if args.open_loop:
        out["open_loop"] = bench_open_loop(quick=args.quick, seed=args.seed)
        out["overload"] = bench_overload_shedding(quick=args.quick,
                                                  seed=args.seed)
    Path(args.json).write_text(json.dumps(out, indent=2, sort_keys=True))
    print(f"[matfn_bench] {n_requests} requests "
          f"(sizes={sizes}, powers={powers}, {expm_frac:.0%} expm)")
    print(f"[matfn_bench] serial : {out['serial_rps']:>8} req/s  "
          f"p50={out['serial_p50_us']}us p95={out['serial_p95_us']}us")
    print(f"[matfn_bench] batched: {out['batched_rps']:>8} req/s  "
          f"p50={out['batched_p50_us']}us p95={out['batched_p95_us']}us")
    print(f"[matfn_bench] speedup={out['batched_speedup_vs_serial']}x "
          f"bit_identical={out['bit_identical']} "
          f"buckets={out['n_buckets']} routes={out['routes']}")
    print(f"[matfn_bench] chain gate: buckets={chain_gate['chain_buckets']} "
          f"bit_identical={chain_gate['bit_identical']} "
          f"max_abs_err={chain_gate['max_abs_err']:.1e}")
    if args.open_loop:
        ol = out["open_loop"]
        print(f"[matfn_bench] open loop: mean_service="
              f"{ol['mean_service_us']}us max_batch={ol['max_batch']} "
              f"max_delay_ms={ol['max_delay_ms']}")
        for r in ol["rows"]:
            print(f"[matfn_bench]   load={r['load_factor']:>4}x "
                  f"({r['offered_rps']:>7} req/s offered) "
                  f"sync p95={r['sync_p95_us']:>9}us  "
                  f"daemon p95={r['daemon_p95_us']:>8}us  "
                  f"bit_identical={r['bit_identical']} "
                  f"triggers={r['flush_triggers']}")
        ov = out["overload"]
        print(f"[matfn_bench] overload: {ov['n_requests']} requests from "
              f"{ov['producers']} tenants at {ov['load_vs_drain']}x drain "
              f"rate (offered {ov['offered_rps_achieved']} req/s, drained "
              f"{ov['drain_rps_achieved']} req/s) — policy={ov['policy']} "
              f"capacity={ov['capacity']}")
        print(f"[matfn_bench]   shed_rate={ov['shed_rate']} "
              f"served={ov['served']} bit_identical={ov['bit_identical']} "
              f"queue_bounded={ov['queue_bounded']} "
              f"lat/bulk p95={ov['latency_p95_over_bulk_p95']}")
        for lane, row in ov["lanes"].items():
            print(f"[matfn_bench]   lane {lane:8s} "
                  f"submitted={row['submitted']} shed={row['shed']} "
                  f"(rate={row['shed_rate']}) "
                  f"peak_depth={row['peak_depth']}/{row['capacity']} "
                  f"p95={row['p95_ms']} ms")
        for name, row in ov["tenants"].items():
            print(f"[matfn_bench]   tenant {name:6s} "
                  f"offered={row['offered']} shed={row['shed']} "
                  f"served={row['served']} p50={row['p50_ms']} ms "
                  f"p95={row['p95_ms']} ms")
        ovl = ov["overlap"]
        print(f"[matfn_bench]   overlap: peak_concurrent_streams="
              f"{ovl['peak_concurrent_streams']} "
              f"xla_executed={ovl['xla_stream_executed']} "
              f"chain_executed={ovl['chain_stream_executed']} "
              f"cold/hot p95={ov['cold_p95_over_hot_p95']}")
    print(f"# wrote {args.json}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
