"""Run one cell of ``BENCHMARK.json`` once and report it.

Set-up builds the cell's operand pool on the device from the seed,
starts a ``MatFnEngine`` daemon with the configuration's settings, warms
the (op, n, batch sizes) the cell's traffic can produce and sends one
untimed round of real traffic. The window then drives the daemon through
``submit`` and futures for ``--seconds``. Afterwards a sample of the
window's answers, drawn from the seed, is compared with the host f64
reference, each number against its limit in ``bench/cells/<cell>.json``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import json
import os
import shutil
import sys
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from mfbench import devtrace, loops, reference, roofline, workload

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
#: Where the tuning cache is pinned: a fixed path in the checkout that no
#: run writes, so every run routes by the program's own defaults.
AUTOTUNE_CACHE = ROOT / ".bench_autotune" / "autotune.json"
TRACE_DIR = ROOT / ".bench_trace"
#: How long answers due in the window may take after it closes.
DRAIN_S = 60.0


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    module: Any
    traffic: dict
    checks: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    bench = root / "bench"
    module = workload.load_module(bench / "configs" / config["module"])
    traffic = json.loads(
        (bench / "traffic" / f"{cell['traffic']}.json").read_text())
    checks = json.loads((bench / "cells" / f"{name}.json").read_text())

    def mine(metric):
        return "workloads" not in metric or name in metric["workloads"]

    return Cell(name, cell["chips"], config, module, traffic, checks,
                [m for m in spec["end_to_end"] if mine(m)],
                [m for m in spec["per_layer"] if mine(m)])


def configure_jax() -> None:
    """Pin the tuning cache and turn on JAX's persistent compile cache
    (``JAX_COMPILATION_CACHE_DIR`` where set, else the program's fixed
    directory in the checkout). Call before the first compile."""
    import jax
    from repro.runtime.compile_cache import enable_compile_cache
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(AUTOTUNE_CACHE)
    enable_compile_cache()
    # Cache every program, however quick to compile: the assemblers and
    # splitters of each batch size would otherwise compile in every run.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def check_devices(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices


class Sampler:
    """Keeps a seeded sample of the window's answers for the check: a
    reservoir of ``size`` answers, plus the first answer of the hardest
    operand in the pool. Runs on the watcher thread only."""

    def __init__(self, size: int, hardest: int, seed: int, op: str):
        self.size = size
        self.hardest = hardest
        self.op = op
        self._rng = np.random.default_rng([seed, 1])
        self._seen = 0
        self.reservoir: List[tuple] = []
        self.extra: Optional[tuple] = None

    def answer(self, value):
        return value.pi if self.op == "markov" else value

    def digest(self, req, value):
        kept = None
        if self.op == "markov":
            kept = value.squarings        # a device scalar, read later
        if not req.in_window:
            return kept
        entry = (req.item, self.answer(value))
        if req.item == self.hardest and self.extra is None:
            self.extra = entry
            return kept
        self._seen += 1
        if len(self.reservoir) < self.size:
            self.reservoir.append(entry)
        else:
            j = int(self._rng.integers(self._seen))
            if j < self.size:
                self.reservoir[j] = entry
        return kept

    def entries(self):
        return self.reservoir + ([self.extra] if self.extra else [])


@functools.lru_cache(maxsize=None)
def _operand_maker(op: str):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(gens, gen_of, scale):
        g = gens[gen_of]
        if op == "expm":
            return g * scale[:, None, None]
        return g + jnp.eye(g.shape[-1], dtype=g.dtype)

    return make


def make_operands(pool: workload.Pool, dtype) -> list:
    """The pool on the device: one jitted call, then one array per item."""
    import jax.numpy as jnp
    make = _operand_maker(pool.op)
    gens = jnp.asarray(np.stack(pool.generators), dtype)
    stack = make(gens, jnp.asarray(pool.gen_of, jnp.int32),
                 jnp.asarray(pool.scale, dtype))
    return list(stack)


class CompileCounter:
    """Counts JAX tracing and compile events, with their times."""

    def __init__(self):
        import jax
        self.times: List[float] = []
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.times.append(loops.clock())

    def _duration(self, name, _secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.times.append(loops.clock())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)


class GcPauses:
    """Counts the collector's passes and their longest pause."""

    def __init__(self):
        self.count = 0
        self.longest = 0.0
        self._start = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._start = loops.clock()
        elif self._start is not None:
            self.count += 1
            self.longest = max(self.longest, loops.clock() - self._start)

    def close(self):
        gc.callbacks.remove(self._on)

    def summary(self) -> str:
        return f"{self.count}, longest {1e3 * self.longest:.3f} ms"


@dataclasses.dataclass
class Readings:
    """What a per-layer reader may read (``bench/metrics/<name>.py``)."""
    n: int
    device_kind: str
    answers: int                        # completed in the window
    squarings: int                      # reference squarings of those
    stages: Dict[str, tuple]            # stage -> (count, sum_s) in window
    spans: List[dict]                   # engine spans inside the window
    device: Optional[devtrace.DeviceTrace]
    latency: loops.Summary              # latency from due, window's requests


def _stage_totals(engine) -> Dict[str, tuple]:
    stages = engine.stats()["stages"]
    return {k: (v["count"], v["sum"]) for k, v in stages.items()}


def _window_stages(before, after) -> Dict[str, tuple]:
    out = {}
    for k, (c1, s1) in after.items():
        c0, s0 = before.get(k, (0, 0.0))
        out[k] = (c1 - c0, s1 - s0)
    return out


def _read_metric(name: str, readings: Readings):
    reader = workload.load_module(BENCH / "metrics" / f"{name}.py")
    return reader.read(readings)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             requests_out: Optional[list] = None) -> dict:
    """One run of ``cell``: its result line as a dict. Where
    ``requests_out`` is given, the run's requests are added to it."""
    import jax
    import jax.numpy as jnp
    from repro.serve.matfn import MatFnEngine

    devices = check_devices(cell.chips) if require_tpu else jax.devices()
    engine_cfg = cell.config["engine"]
    max_batch = engine_cfg["max_batch"]
    wl = workload.build(cell.config, cell.module, cell.traffic, seed,
                        seconds, max_batch)
    pool = wl.pool
    dtype = jnp.dtype(cell.config["dtype"])
    operands = make_operands(pool, dtype)
    jax.block_until_ready(operands)
    compiles = CompileCounter()

    engine = MatFnEngine(max_batch=max_batch, trace=bool(trace)).start()
    sampler = Sampler(cell.checks["sample"], int(np.argmax(pool.hardness)),
                      seed, pool.op)
    watcher = loops.Watcher(sampler.digest).start()
    group = (pool.op, pool.n)
    annotate = _annotator(trace)

    def submit(item):
        with annotate("bench.submit"):
            return engine.submit(pool.op, operands[item])

    try:
        with annotate("bench.warm"):
            engine.warm(pool.op, pool.n, dtype, batches=wl.warm_batches)
        clients = []
        if wl.loop == "open":
            loops.open_loop(submit, watcher, wl.warm_schedule, loops.clock(),
                            in_window=False, group=group)
        else:
            clients = [loops.ClosedLoopClient(r, submit, watcher, group)
                       for r in wl.rounds]
            _in_threads([functools.partial(c.run_round, False, k << 32)
                         for k, c in enumerate(clients)])
        if not watcher.drain(DRAIN_S):
            raise RuntimeError("warm-up traffic did not finish")
        before = _stage_totals(engine)
        # Everything set-up made is long-lived: leave it out of the
        # collector's full passes, which otherwise stall every thread of
        # the process for about 0.1 s once or twice in a window.
        gc.collect()
        gc.freeze()
        pauses = GcPauses()
        if trace:
            TRACE_DIR.mkdir(exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # Python calls: too costly
            options.enable_hlo_proto = False
            jax.profiler.start_trace(str(TRACE_DIR),
                                     profiler_options=options)
        t0 = loops.clock() + 0.01
        mono_shift = time.monotonic() - loops.clock()
        t1 = t0 + seconds
        setup_s = t0 - t_start
        with annotate("bench.window"):
            if wl.loop == "open":
                requests = loops.open_loop(submit, watcher, wl.schedule, t0,
                                           group=group)
            else:
                threads = [threading.Thread(
                    target=c.run, args=(t1, k << 32), daemon=True)
                    for k, c in enumerate(clients)]
                while loops.clock() < t0:
                    pass
                for t in threads:
                    t.start()
            _sleep_until(t1)
            after = _stage_totals(engine)
        pauses.close()
        planes = []
        if trace:
            jax.profiler.stop_trace()
        if wl.loop == "closed":
            for t in threads:
                t.join(timeout=DRAIN_S)
            requests = [r for c in clients for r in c.requests]
            errors = [c.error for c in clients if c.error is not None]
            if errors:
                raise RuntimeError("a client failed") from errors[0]
        drained = watcher.drain(DRAIN_S)
        if trace:
            planes = devtrace.read_planes(str(TRACE_DIR))
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
        summary = loops.summarize(requests, t0, t1)
        if requests_out is not None:
            requests_out.extend(requests)
        stats = engine.stats()
        spans = [s for s in engine.tracer.spans()
                 if t0 + mono_shift <= s["ts"] <= t1 + mono_shift] \
            if trace else []
        memory = _memory_peak(devices)
        entries = [(item, np.asarray(value)) for item, value
                   in sampler.entries()]
        sample_ops = {item: np.asarray(operands[item], np.float64)
                      for item, _ in entries}
        squarings = _window_squarings(requests, pool, t0, t1)
    finally:
        watcher.stop()
        engine.close()
    del operands

    checks = _compare(pool.op, entries, sample_ops, cell.checks["checks"],
                      pool)
    correct = (drained and summary.failed == 0 and bool(entries)
               and all(c["value"] <= c["limit"] for c in checks.values()))
    log(f"cell {cell.name} seed {seed}: {summary.attempted} due in the "
        f"window, {summary.failed} failed, "
        f"{summary.completed_in_window} answered in it")
    log(f"routes taken by buckets: {stats['routes']}; tuning cache "
        f"{'empty' if not AUTOTUNE_CACHE.exists() else 'NOT empty'} "
        f"({AUTOTUNE_CACHE}); configuration states route "
        f"{cell.config['route']!r}")
    log(f"compile events in the window: {compiles.between(t0, t1)}; "
        f"garbage collections in it: {pauses.summary()}")
    log(f"latency from due: p50 {summary.p50_ms!r} ms, p95 "
        f"{summary.p95_ms!r} ms; answers/s {summary.answers_per_s!r}")
    log(f"submitted after due (open loop: generator lateness; closed "
        f"loop: the submit call): p50 {summary.late_p50_ms:.4f} ms, max "
        f"{summary.late_max_ms:.4f} ms")
    log(f"buckets {stats['buckets']}, padded slots {stats['padded_slots']}, "
        f"flush triggers {stats['flush_triggers']}")
    if pool.op == "markov":
        per = squarings / max(1, summary.completed_in_window)
        log(f"squarings per answer in the window: {per:.4f}")

    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory}
    result = {"correct": correct, "attempted": summary.attempted,
              "failed": summary.failed}
    breakdown = None
    if trace:
        dtrace = devtrace.reduce(planes)
        readings = Readings(pool.n, kind, summary.completed_in_window,
                            squarings, _window_stages(before, after), spans,
                            dtrace, summary)
        metrics = {}
        for m in cell.per_layer:
            value = _read_metric(m["name"], readings)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        if dtrace is not None:
            device["busy_s"] = dtrace.busy_s
            device["window_s"] = dtrace.window_s
            breakdown = {"device_ops": [[k, v] for k, v in dtrace.top_ops()],
                         "idle_gaps": [[k, v] for k, v in dtrace.gaps]}
        else:
            log("the trace held no device plane or no window span")
    else:
        values = {"setup_s": setup_s, "p50_ms": summary.p50_ms,
                  "p95_ms": summary.p95_ms,
                  "answers_per_s": summary.answers_per_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def _annotator(trace: bool):
    """Host spans in the profiler's trace, when tracing."""
    if trace:
        import jax
        return jax.profiler.TraceAnnotation
    return lambda _name: contextlib.nullcontext()


def _in_threads(jobs) -> None:
    threads = [threading.Thread(target=j, daemon=True) for j in jobs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=DRAIN_S)


def _sleep_until(t: float) -> None:
    while True:
        left = t - loops.clock()
        if left <= 0:
            return
        time.sleep(min(left, 0.01))


def _memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _window_squarings(requests, pool, t0, t1) -> int:
    """Reference squarings of the answers completed in the window (for
    ``markov``, the squarings the program reports it spent)."""
    done = [r for r in requests if r.ok and t0 <= r.done <= t1]
    if pool.op == "markov":
        return int(sum(int(r.digest) for r in done))
    return int(sum(pool.squarings[r.item] for r in done))


def _compare(op: str, entries, sample_ops, limits: dict,
             pool: workload.Pool) -> dict:
    """Each number the cell's file names, beside its limit: the widest
    over the sample of a gap between an answer and the f64 reference of
    its own request (``reference.GAPS``)."""
    refs = {item: reference.host_reference(op, a)
            for item, a in sample_ops.items()}
    checks = {}
    for name, spec in limits.items():
        gap = reference.GAPS[name]
        gaps = sorted(((gap(got, refs[item]), item)
                       for item, got in entries), reverse=True)
        for value, item in gaps[:3]:
            log(f"widest {name}: {value!r} on item {item} (||A||_1 "
                f"{pool.hardness[item]:.4g}, {pool.squarings[item]} "
                f"reference squarings)")
        value = gaps[0][0] if gaps else float("inf")
        checks[name] = {"value": value, "limit": spec["limit"]}
    return checks


def control_checks(cell: Cell, seed: int) -> dict:
    """The cell's compared numbers with the control in the program's
    place: on the operands a run with ``seed`` builds, for as many
    requests as a run compares."""
    from mfbench import control
    wl = workload.build(cell.config, cell.module, cell.traffic, seed, 1.0,
                        cell.config["engine"]["max_batch"])
    pool = wl.pool
    rng = np.random.default_rng([seed, 2])
    size = min(cell.checks["sample"], len(pool.gen_of))
    items = [int(np.argmax(pool.hardness))] + [
        int(i) for i in rng.choice(len(pool.gen_of), size, replace=False)]
    operands = {}
    for item in items:
        q = pool.generators[pool.gen_of[item]]
        a = q * pool.scale[item] if pool.op == "expm" \
            else q + np.eye(pool.n)
        operands[item] = np.asarray(a.astype(np.float32), np.float64)
    entries = [(item, np.asarray(control.answer(pool.op, operands[item])))
               for item in items]
    return _compare(pool.op, entries, operands, cell.checks["checks"], pool)


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = loops.clock() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    try:
        check_devices(cell.chips)
    except NoChip as exc:
        print(f"[bench] {exc}", file=sys.stderr)
        return 2
    configure_jax()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start)
    for name, c in result["checks"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
