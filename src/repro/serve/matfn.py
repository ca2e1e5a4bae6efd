"""Matrix-function serving engine: request bucketing, batched squaring
chains, heterogeneous dispatch, and a continuous-batching daemon.

The paper's headline pipeline keeps the accelerator saturated across
matrices "of different sizes and with different powers". This module is
that pipeline as a service layer over the reproduction's chain executors:

  * **Requests** (:class:`MatFnRequest`) name an op (``matpow`` / ``expm``
    / ``markov``), an (n, n) operand, and — for matpow — a static power.
    ``markov`` is the stochastic op class (:mod:`repro.core.markov`): with
    no ``dists`` a request is a steady-state query (convergence-aware
    early-exit squaring; resolves with a
    :class:`~repro.core.markov.SteadyStateResult`), with a (B, n) ``dists``
    stack it is a distribution-evolution query over ``power`` transitions
    (resolves with the evolved (B, n) stack).
  * **Bucketing**: pending requests group by ``(op, n, dtype, power)``; each
    group is stacked into a (B, n, n) operand whose batch dim is padded up
    to the next power of two (identity work on zero-matrix filler slots), so
    a handful of executables serves every batch size.
  * **Executable cache**: each bucket answers from a compiled executable
    keyed on ``(op, route, padded_batch, n, dtype, power)`` — one jitted
    program per bucket shape, reused across flushes.
  * **Heterogeneous dispatch**: the route per bucket follows the tuning
    cache's ``dispatch`` namespace (:func:`repro.kernels.autotune.
    dispatch_thresholds`): tiny n stays on the plain XLA dot (kernel-launch
    overhead dominates — the paper's CPU side of the split), mid-size
    buckets run the fused batched Pallas chain
    (:class:`repro.core.batched.BatchedMatmulChain`), and huge *single*
    matrices are promoted to :class:`~repro.core.distributed.
    ShardedMatmulChain` when the engine owns a mesh. Hardware sweeps retune
    the thresholds by writing the ``dispatch`` cache entry — no code change,
    and (cache-generation check) no engine restart either.
  * **Continuous batching** (:meth:`MatFnEngine.start`): in daemon mode
    ``submit`` returns a :class:`MatFnFuture` immediately and a background
    scheduler thread flushes each bucket when it FILLS to ``max_batch`` or
    when its oldest request crosses a per-traffic-class deadline
    (:func:`repro.kernels.autotune.bucket_deadline_ms`, a ``dispatch``
    namespace entry like every other knob). Device work overlaps host-side
    assembly of the next bucket: executables dispatch asynchronously and
    futures resolve with in-flight arrays. Executor failures are routed
    into the affected bucket's futures as :class:`BucketExecutionError`
    (never lost on a daemon thread), and :meth:`MatFnEngine.close` drains
    every pending bucket before the thread exits.

  * **Execution streams** (:mod:`repro.serve.streams`): the daemon's
    scheduler thread keeps admission, bucketing, deadlines, and lane
    priority to itself, but hands each due bucket to its dispatch route's
    execution stream — a route-keyed worker pool (one stream each for
    ``xla`` / ``chain`` / ``sharded`` by default; configurable via
    :class:`~repro.serve.streams.ExecutionStreams`) — so an in-flight
    chain bucket no longer blocks a due xla or priority-lane flush.
    Streams change the SCHEDULE, never the math (``streams=1`` collapses
    back to the single serialized queue), latency-lane buckets jump their
    stream's queue, and a crashed stream poisons only its own buckets
    while the others keep serving.
  * **Admission control** (:mod:`repro.serve.admission`): every request
    rides a LANE (``"bulk"`` default, ``submit(..., priority="latency")``
    for latency-critical traffic); each lane has a bounded queue whose
    overflow is resolved by a pluggable policy (reject-newest /
    reject-oldest / deadline-aware) — the shed side fails fast with a
    typed :class:`~repro.serve.admission.ShedError` carrying lane, queue
    depth, and capacity, so overload degrades into attributable
    rejections instead of universal timeouts. Latency-lane buckets run
    under a per-lane SLO deadline cap and, above
    ``AdmissionControl.bypass_n``, skip bucket assembly entirely (the
    ``"priority"`` flush trigger); the scheduler flushes due latency
    buckets before bulk ones.
  * **Fault wiring** (:mod:`repro.runtime.fault`): every bucket flush is
    timed under a :class:`~repro.runtime.fault.Watchdog` — a straggling
    flush lands a ``StragglerEvent`` in the stats (counted + logged, so
    chronic stragglers are attributable per bucket key); an executor
    exception retries through :func:`~repro.runtime.fault.retry_step`
    with the bucket's cached executables EVICTED per attempt (a poisoned
    compile-cache entry self-heals instead of re-raising), and only after
    bounded retries fails the bucket's futures with
    :class:`BucketExecutionError`.
  * **Observability** (:mod:`repro.runtime.telemetry`): ``engine.stats``
    remains the live counter dict; CALLING it — ``engine.stats()`` —
    returns a consistent snapshot with per-lane submitted/shed/retried/
    flushed counters, live + peak queue depths, histogram-backed p50/p95
    latency per lane (log-spaced buckets in a
    :class:`~repro.runtime.telemetry.MetricsRegistry`, exact over the
    whole run — no sample window), per-stage latency histograms
    (queue / assemble / execute / resolve; submit and device as well
    when tracing), and the watchdog's straggler events.
    ``MatFnEngine(trace=True)`` additionally records every request's
    LIFECYCLE as spans in a bounded ring buffer — submit -> admit/shed ->
    bucket open -> flush trigger (fill/deadline/priority/kick) -> stream
    queue -> execute (assemble/compile/device) -> resolve/retry/shed —
    tagged by (op, n, dtype, lane, route, stream) and exportable as
    Chrome trace-event JSON (``engine.tracer.export(path)``; load in
    Perfetto); its host stages are also profiler annotations, anchored
    to the engine clock. Near-zero cost when disabled: every record site
    guards on one attribute. See ``docs/observability.md``.

Flush policies and the injectable clock live in
:mod:`repro.serve.scheduler`. Driver: ``python -m repro.launch.matserve``
(``--daemon`` for open-loop traffic against the daemon); bench:
``benchmarks/matfn_bench.py`` (``--open-loop`` for latency-vs-load and the
mixed-lane overload trace, writes ``BENCH_matfn.json``). See
``docs/serving.md`` for the policy details and the paper mapping.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import queue
import threading
import time
from concurrent.futures import CancelledError, InvalidStateError
from concurrent.futures import TimeoutError as FutureTimeoutError
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.batched import batched_matpow
from repro.core.expm import expm as _expm
from repro.kernels import autotune
from repro.runtime.fault import Watchdog, retry_step
from repro.runtime.telemetry import (NULL_SPAN, NULL_TRACER,
                                     MetricsRegistry, Tracer)
from repro.serve.admission import (LANES, AdmissionControl, PendingView,
                                   ShedError)
from repro.serve.scheduler import (BucketView, FillOrDeadline, FlushPolicy,
                                   SystemClock)
from repro.serve.streams import ExecutionStreams, StreamCrashed, StreamPool

__all__ = ["MatFnRequest", "MatFnEngine", "MatFnFuture",
           "BucketExecutionError", "ShedError", "bucket_batch",
           "ExecutionStreams", "OPS", "ROUTES", "TRIGGERS"]

#: Ops the engine serves.
OPS = ("matpow", "expm", "markov")

#: Dispatch routes a bucket can take (see :meth:`MatFnEngine.route_for`).
#: ``xla``/``chain``/``sharded`` are bit-identical to per-matrix calls of
#: the same kernels; ``fastmm`` (Strassen recursion above the autotuned
#: crossover) is tolerance-bounded — see ``kernels.fastmm.error_budget``.
#: ``evolve`` serves markov distribution-evolution buckets — (B, n)
#: vector-matrix chains through the tuned dense tiles, an entirely
#: different (much cheaper) kernel shape from the dense-square routes.
ROUTES = ("xla", "chain", "sharded", "fastmm", "evolve")


def _is_evolve(power) -> bool:
    """True for the evolve bucket power slot ``("evolve", steps, B)`` —
    the markov distribution-evolution traffic class (steady-state markov
    buckets use the scalar -1 slot like expm)."""
    return isinstance(power, tuple) and len(power) == 3 \
        and power[0] == "evolve"

#: Flush triggers the daemon distinguishes in ``stats["flush_triggers"]``
#: (``priority`` = a latency-lane request at n >= bypass_n forced its
#: bucket due on arrival).
TRIGGERS = ("fill", "deadline", "kick", "drain", "priority")

#: Bound on ``stats["last_flush"]`` in daemon mode (a long-lived daemon
#: must not grow an unbounded report list; sync ``flush`` resets it).
_LAST_FLUSH_ROWS = 256

#: Straggler-event strings retained in the ``stats()`` snapshot.
_STRAGGLER_EVENTS = 32

_UNSET = object()


class BucketExecutionError(RuntimeError):
    """An executor failed while answering a bucket.

    Raised INTO every affected future (never swallowed on the scheduler
    thread): the message carries the bucket key so a consumer holding one
    future of a 64-request bucket can tell which traffic class — not just
    which request — is poisoned, and ``__cause__`` chains the original
    executor exception.
    """

    def __init__(self, key: tuple, cause: BaseException):
        op, n, dtype, power = key
        super().__init__(
            f"bucket (op={op}, n={n}, dtype={dtype}, power={power}) failed "
            f"to execute: {type(cause).__name__}: {cause}")
        self.key = key
        self.__cause__ = cause


class MatFnFuture:
    """One daemon request's pending answer.

    Thread-safe, single-assignment: exactly one of ``set_result`` /
    ``set_exception`` may ever fire — a second resolution attempt raises
    ``concurrent.futures.InvalidStateError`` (the no-double-completion
    invariant the concurrency suite asserts). ``result`` may return a
    still-in-flight jax array (jax arrays are themselves futures); callers
    that need device completion block on it like any other jax value.
    ``resolved_at`` records the resolution time so open-loop benchmarks
    can measure latency without polling — the ENGINE pre-stamps its own
    injectable clock's now into ``_resolve_at_hint`` before resolving, so
    ``resolved_at`` shares ``submitted_at``'s epoch and
    ``resolved_at - submitted_at`` is always well-defined (the old code
    mixed ``time.perf_counter()`` with the engine clock); a bare
    ``set_result``/``set_exception`` without a hint falls back to
    ``time.perf_counter()``. ``tenant`` carries the optional caller-
    supplied tenant tag and ``rid`` the engine's per-request id (both
    observability-only — they never affect bucketing or the math).
    """

    __slots__ = ("bucket_key", "lane", "tenant", "rid",
                 "submitted_at", "resolved_at", "_resolve_at_hint",
                 "_event", "_lock", "_result", "_exception")

    def __init__(self, bucket_key: Optional[tuple] = None,
                 lane: str = "bulk"):
        self.bucket_key = bucket_key
        self.lane = lane
        self.tenant: Optional[str] = None
        self.rid: Optional[int] = None
        self.submitted_at: Optional[float] = None   # engine-clock admit time
        self.resolved_at: Optional[float] = None
        self._resolve_at_hint: Optional[float] = None
        self._event = threading.Event()
        self._lock = threading.Lock()
        self._result = _UNSET
        self._exception: Optional[BaseException] = None

    def done(self) -> bool:
        return self._event.is_set()

    def _stamp(self) -> float:
        # Engine-clock hint when the engine resolved us, else wall time.
        return time.perf_counter() if self._resolve_at_hint is None \
            else self._resolve_at_hint

    def set_result(self, value) -> None:
        with self._lock:
            if self._event.is_set():
                raise InvalidStateError(f"{self!r} already resolved")
            self._result = value
            self.resolved_at = self._stamp()
            self._event.set()

    def set_exception(self, exc: BaseException) -> None:
        with self._lock:
            if self._event.is_set():
                raise InvalidStateError(f"{self!r} already resolved")
            self._exception = exc
            self.resolved_at = self._stamp()
            self._event.set()

    def result(self, timeout: Optional[float] = None):
        # concurrent.futures.TimeoutError, not the builtin: they are only
        # aliases from 3.11 on, and the futures idiom
        # (``except futures.TimeoutError``) must work on 3.10 too — the
        # class already adopts the futures exception types elsewhere
        # (CancelledError, InvalidStateError).
        if not self._event.wait(timeout):
            raise FutureTimeoutError(f"result not ready after {timeout}s")
        if self._exception is not None:
            raise self._exception
        return self._result

    def exception(self,
                  timeout: Optional[float] = None) -> Optional[BaseException]:
        if not self._event.wait(timeout):
            raise FutureTimeoutError(f"result not ready after {timeout}s")
        return self._exception

    def __repr__(self):
        state = "pending"
        if self._event.is_set():
            state = "error" if self._exception is not None else "done"
        return f"<MatFnFuture {state} key={self.bucket_key}>"


@dataclasses.dataclass(frozen=True)
class MatFnRequest:
    """One matrix-function request: ``op(operand[, power][, dists])``.

    ``operand`` must be one (n, n) square matrix with n >= 1; ``power`` is
    a static python int, meaningful for ``op="matpow"`` (>= 0; ``power ==
    0`` answers the identity, the matpow contract) and for markov evolve
    requests (the transition horizon, >= 0). ``dists`` (markov only) is a
    (B, n) stack of start distributions sharing ``operand`` as their
    transition matrix — its presence selects the evolve traffic class;
    without it a markov request is a steady-state query. ``dists`` must
    match the operand dtype: the bucket program stacks per-dtype, and a
    silent promotion would split identical-math requests across
    executables. The engine does NOT validate stochasticity — gate inputs
    with :func:`repro.core.markov.validate_stochastic` at the admission
    edge (a device-sync row-sum check per submit would stall the daemon's
    hot path).
    """
    op: str
    operand: jax.Array
    power: int = 1
    dists: Optional[jax.Array] = None

    def __post_init__(self):
        if self.op not in OPS:
            raise ValueError(f"unknown op {self.op!r}; expected one of {OPS}")
        a = self.operand
        if a.ndim != 2 or a.shape[0] != a.shape[1] or a.shape[0] < 1:
            raise ValueError(f"{self.op} requests need one (n, n) matrix "
                             f"with n >= 1, got shape {a.shape}")
        if self.dists is not None and self.op != "markov":
            raise ValueError(f"dists is only meaningful for op='markov', "
                             f"got op={self.op!r}")
        if self.op == "matpow" or (self.op == "markov"
                                   and self.dists is not None):
            if not isinstance(self.power, int) \
                    or isinstance(self.power, bool):
                raise TypeError(f"{self.op} requests need a static python "
                                f"int power (one executable per power)")
            if self.power < 0:
                raise ValueError("negative powers not supported")
        if self.dists is not None:
            d = self.dists
            if d.ndim != 2 or d.shape[0] < 1 or d.shape[1] != a.shape[0]:
                raise ValueError(f"dists must be a (B, n) stack matching "
                                 f"the (n, n) operand, got dists shape "
                                 f"{d.shape} for n = {a.shape[0]}")
            if d.dtype != a.dtype:
                raise ValueError(f"dists dtype {d.dtype.name} must match "
                                 f"operand dtype {a.dtype.name}")

    @property
    def n(self) -> int:
        return self.operand.shape[0]

    @property
    def payload(self):
        """What the bucket program stacks for this request: the operand,
        or the (operand, dists) pair for evolve requests."""
        return self.operand if self.dists is None \
            else (self.operand, self.dists)

    def bucket_key(self) -> tuple:
        """(op, n, dtype, power) — the group this request batches with.
        expm and markov steady-state have no power, so every such request
        of one (n, dtype) shares a bucket (power slot -1); markov evolve
        requests carry ``("evolve", steps, B)`` in the power slot — the
        horizon and distribution count are executable-shape parameters,
        so they key the traffic class like a matpow power does."""
        if self.op == "matpow":
            power = self.power
        elif self.op == "markov" and self.dists is not None:
            power = ("evolve", self.power, self.dists.shape[0])
        else:
            power = -1
        return (self.op, self.n, self.operand.dtype.name, power)


@dataclasses.dataclass
class _Bucket:
    """One OPEN daemon bucket: futures waiting to be batched."""
    key: tuple
    lane: str                    # admission class ("bulk" / "latency")
    members: list                # [(MatFnFuture, MatFnRequest), ...]
    first_ts: float              # clock time of the oldest pending request
    max_delay_s: float           # tuned flush-by delay for this class
    # kick()/priority bypass: the trigger name that forced this bucket due
    # at the next poll, or None while it batches normally.
    forced: Optional[str] = None
    # Execution-stream id once dispatched (stats attribution), else None.
    stream: Optional[int] = None

    def view(self) -> BucketView:
        return BucketView(self.key, len(self.members), self.first_ts,
                          self.max_delay_s, self.lane)


class _Stats(dict):
    """Engine counters, indexable like the plain dict it always was
    (``engine.stats["requests"]``) and CALLABLE for a consistent snapshot
    (``engine.stats()`` — per-lane counters, queue depths, p50/p95; see
    :meth:`MatFnEngine._stats_snapshot`)."""

    snapshot = None   # bound by the engine

    def __call__(self) -> dict:
        return self.snapshot()


# The resolve stage's row pick. A bucket program returns one output per
# padded slot, each already its own device buffer (a markov steady-state
# member is a whole per-member SteadyStateResult), so picking the ``b``
# member rows is host-only: no device call, filler slots dropped. Module
# level so the resolve stage reaches it through the module attribute.
def _split_rows(out, *, b: int):
    return tuple(out[:b])


def _bucket_program(per_stack):
    """One jitted device program for a whole bucket: it takes the padded
    bucket's member payloads as separate arguments, stacks them (leaf by
    leaf, so an evolve member's (operand, dists) pair stacks into a pair
    of stacks), runs ``per_stack`` on the stack, and returns one output
    per member, every pytree leaf sliced to that member's row. Stacking
    and slicing move data and compute nothing, so each member's answer is
    what ``per_stack`` computes for it. No donation: the arguments are
    the caller's arrays (and the engine's shared filler); the stack is an
    intermediate the program owns."""
    def program(*members):
        stack = jax.tree_util.tree_map(lambda *rows: jnp.stack(rows),
                                       *members)
        out = per_stack(stack)
        return tuple(jax.tree_util.tree_map(lambda leaf: leaf[j], out)
                     for j in range(len(members)))

    return jax.jit(program)


def bucket_batch(b: int, max_batch: int = 64) -> int:
    """Pad a batch of ``b`` requests up to the next power of two (capped at
    ``max_batch``): ceil-log2 bucketing bounds the executable cache at
    log2(max_batch)+1 shapes per (op, n, dtype, power) group while wasting
    at most half a bucket of filler compute."""
    if b < 1:
        raise ValueError(f"bucket_batch needs b >= 1, got {b}")
    return min(int(max_batch), 1 << (b - 1).bit_length())


@dataclasses.dataclass
class _Dispatched:
    """One bucket chunk whose program was called: its per-slot device
    outputs (filler slots included), the member count, the route, and —
    when tracing — the span tags of its stages."""
    out: object
    b: int
    route: str
    tags: Optional[dict]


class _DeviceWatch:
    """The ``stage=device`` histogram: bucket dispatch -> outputs ready.

    One daemon thread blocks on a dispatched bucket's outputs, so no
    stream worker waits on the device for it. It samples: it takes a
    bucket only while it waits on none and at least ``SPACING_S`` of
    dispatch time after the last one it took. A thread woken for every
    bucket held a daemon near its knee back (traced ``mcmc`` on a TPU v5e
    answered 2,000 instead of 3,200 requests a second); one sample per
    20 ms wakes it at most 50 times a second whatever the bucket rate.
    Started on first use, only by a tracing engine."""

    SPACING_S = 0.02

    def __init__(self, metrics: MetricsRegistry, now):
        self._metrics = metrics
        self._now = now
        self._queue: queue.SimpleQueue = queue.SimpleQueue()
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._busy = False
        self._last = -float("inf")

    def watch(self, out, dispatched_at: float, route: str) -> None:
        with self._lock:
            if self._busy or dispatched_at - self._last < self.SPACING_S:
                return
            self._busy, self._last = True, dispatched_at
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._main, name="matfn-device-watch",
                    daemon=True)
                self._thread.start()
        self._queue.put((out, dispatched_at, route))

    def _main(self) -> None:
        while True:
            item = self._queue.get()
            if item is None:
                return
            out, dispatched_at, route = item
            try:
                jax.block_until_ready(out)
            except Exception:  # noqa: BLE001 — the bucket's futures carry it
                pass
            else:
                self._metrics.record("stage", self._now() - dispatched_at,
                                     stage="device", route=route)
            finally:
                with self._lock:
                    self._busy = False

    def close(self) -> None:
        """Record the bucket being watched, then stop the thread."""
        with self._lock:
            thread, self._thread = self._thread, None
        if thread is not None:
            self._queue.put(None)
            thread.join()


class MatFnEngine:
    """Buckets pending matpow/expm requests and answers them batch-at-once.

    Synchronous (library) mode::

        eng = MatFnEngine()
        t0 = eng.submit("matpow", a0, power=7)    # -> int ticket
        t1 = eng.submit("expm", a1)
        r0, r1 = eng.flush()                      # results in ticket order

    Daemon (continuous-batching) mode::

        with MatFnEngine(max_batch=16) as eng:    # __enter__ -> start()
            fut = eng.submit("matpow", a0, power=7)   # -> MatFnFuture
            r0 = fut.result(timeout=5)
        # __exit__ -> close(): drains every pending bucket

    ``flush`` groups everything submitted since the last flush by
    ``(op, n, dtype, power)``, pads each group's batch dim to a bucket size,
    runs one cached executable per bucket, and scatters the answers back in
    submission order. The daemon runs the SAME bucket core on a scheduler
    thread — same executable cache, same assembly, same routes — flushing a
    bucket when it fills to ``max_batch`` or when its oldest request crosses
    the bucket's deadline (engine ``max_delay_ms`` override, else the tuning
    cache's per-(op, n, dtype) ``dispatch`` deadline entry, else
    ``autotune.DEFAULT_MAX_DELAY_MS``), so daemon answers are bit-identical
    to synchronous ``flush()`` answers wherever the synchronous path is
    bit-identical to per-matrix calls (CI-asserted). Padding slots hold zero
    matrices — their math runs (wasted work bounded by the bucket policy)
    and their answers are discarded. Batching never changes the math:
    wherever batched and serial run the same kernels (the ``xla`` route, and
    every route off-TPU, where the chain degrades to the same XLA dot)
    answers are BIT-IDENTICAL to per-matrix jitted ``matpow_binary`` /
    ``expm`` calls (CI-asserted); the on-TPU ``chain``/``sharded`` routes
    run the tiled Pallas / collective kernels, whose fp32 accumulation order
    differs from the XLA dot, and are validated to tolerance like every
    other use of those kernels.

    Args:
      mesh: optional device mesh; with one, single matrices at
        ``n >= sharded_min_n`` run the distributed chain.
      interpret: force the Pallas kernel bodies on CPU for the chain route
        (tests/validation); off-TPU without it the chain route degrades to
        the same XLA dot as the ``xla`` route.
      max_batch: bucket-size cap; bigger groups split into chunks. In daemon
        mode also the fill trigger: a bucket reaching ``max_batch`` flushes
        immediately.
      profile: when True, bucket execution blocks and wall-times each bucket
        (the ``stats["last_flush"]`` rows carry ``seconds``, and daemon
        futures resolve only when the device is done — what the open-loop
        bench uses for honest latency); when False (the default) buckets
        dispatch asynchronously and only the caller's own sync point waits
        — the serving configuration, where in-flight device work overlaps
        host-side assembly of the next bucket.
      thresholds: explicit (cpu_max_n, sharded_min_n) override; default is
        the tuning cache's ``dispatch`` namespace, resolved per operand
        dtype (dtype-specific entry first, ``any`` fallback) and memoized
        per cache GENERATION — recording new thresholds mid-process
        (``autotune.record_dispatch_thresholds``) reroutes this engine's
        next bucket instead of waiting for a restart.
      max_delay_ms: explicit daemon flush deadline override for every
        bucket; default None resolves per traffic class from the tuning
        cache (``autotune.bucket_deadline_ms``), memoized with the same
        generation check.
      policy: a :class:`repro.serve.scheduler.FlushPolicy` (default
        :class:`~repro.serve.scheduler.FillOrDeadline`); see
        :class:`~repro.serve.scheduler.AdaptiveDeadline` for arrival-rate-
        adaptive deadlines.
      clock: a :class:`repro.serve.scheduler.Clock` (default the system
        monotonic clock); tests inject
        :class:`~repro.serve.scheduler.ManualClock` to drive deadlines
        deterministically.
      streams: an :class:`~repro.serve.streams.ExecutionStreams` config
        mapping dispatch routes onto executor worker threads (daemon mode
        only). Default: one stream per route, so a chain bucket in flight
        never delays a due xla or priority flush; ``ExecutionStreams(
        streams=1)`` serializes every route through one worker (the
        pre-streams schedule). Must cover every engine route.
      trace: request-lifecycle tracing. ``None``/``False`` (default):
        disabled — every instrumentation point short-circuits on one
        attribute check (:data:`~repro.runtime.telemetry.NULL_TRACER`).
        ``True``: record into a fresh
        :class:`~repro.runtime.telemetry.Tracer` bound to the engine
        clock (``engine.tracer``; export with
        ``engine.tracer.export(path)``). A :class:`~repro.runtime.
        telemetry.Tracer` instance: record into it (bound to the engine
        clock unless it already has one). Tracing changes the SCHEDULE
        and the math not at all — the stream-identity CI gates run with
        it on. Histogram METRICS (``engine.metrics``) are always on:
        they replace the old per-lane latency deques behind ``stats()``
        and cost one log2 + index bump per observation. Two stages are
        recorded only when tracing, since they cost per request or per
        bucket: ``submit`` (host seconds inside :meth:`submit`) and
        ``device`` (bucket dispatch -> outputs ready, sampled by one
        watcher thread).
    """

    def __init__(self, *, mesh=None, interpret: bool = False,
                 max_batch: int = 64, profile: bool = False,
                 thresholds: Optional[tuple] = None,
                 max_delay_ms: Optional[float] = None,
                 policy: Optional[FlushPolicy] = None,
                 clock=None,
                 admission: Optional[AdmissionControl] = None,
                 watchdog: Optional[Watchdog] = None,
                 retries: int = 1,
                 retry_backoff_s: float = 0.0,
                 streams: Optional[ExecutionStreams] = None,
                 trace=None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_delay_ms is not None and not max_delay_ms > 0:
            raise ValueError(f"max_delay_ms must be > 0, got {max_delay_ms}")
        if retries < 0:
            raise ValueError(f"retries must be >= 0, got {retries}")
        if retry_backoff_s < 0:
            raise ValueError(
                f"retry_backoff_s must be >= 0, got {retry_backoff_s}")
        self.mesh = mesh
        self.interpret = bool(interpret)
        self.max_batch = int(max_batch)
        self.profile = bool(profile)
        self._thresholds_override = tuple(thresholds) \
            if thresholds is not None else None
        self._max_delay_ms = None if max_delay_ms is None \
            else float(max_delay_ms)
        self._policy = policy if policy is not None else FillOrDeadline()
        self._clock = clock if clock is not None else SystemClock()
        self._admission = admission if admission is not None \
            else AdmissionControl()
        # Default watchdog ON: straggler detection costs one median over a
        # 32-entry window per flush and buys the self-healing eviction.
        self._watchdog = watchdog if watchdog is not None else Watchdog()
        self.retries = int(retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self._streams = streams if streams is not None else ExecutionStreams()
        missing = [r for r in ROUTES if r not in self._streams.routes]
        if missing:
            raise ValueError(
                f"streams config must cover every engine route; "
                f"missing {missing} from {self._streams.routes}")
        # Executor worker pool (daemon mode only; created by start()).
        self._pool: Optional[StreamPool] = None
        # Streams execute buckets concurrently, so the shared counters in
        # stats (and the executable cache) need their own leaf lock — held
        # only around counter/cache updates, never across execution, and
        # never while taking _cv or the pool lock.
        self._stats_lock = threading.Lock()
        # Memoized dispatch resolutions, each stored WITH the autotune
        # generation it was resolved under and validated on read (a retuned
        # cache reroutes the running engine, not just the next one).
        self._thresholds_cache: dict = {}
        self._deadline_cache: dict = {}
        self._fastmm_cache: dict = {}
        self._pending: List[MatFnRequest] = []
        self._executables: dict = {}
        self._fillers: dict = {}          # (n, dtype, dists rows) -> zeros
        # Daemon state (inert until start()).
        self._cv = threading.Condition()
        self._daemon: Optional[threading.Thread] = None
        self._open_buckets: dict = {}     # key -> _Bucket
        # Buckets popped from _open_buckets but not yet fully resolved
        # (scheduler thread only). Kept reachable so a scheduler crash can
        # fail their futures too — a bucket must never be lost in a local
        # variable of a dying frame.
        self._in_flight: List[_Bucket] = []
        self._closing = False
        self._closed = False
        self._waiting = False             # scheduler idle (settle handshake)
        self._scheduler_crash: Optional[BaseException] = None
        # Admission bookkeeping: admitted-but-unflushed requests per lane
        # (the bounded front-door queue).
        self._lane_depth = {lane: 0 for lane in LANES}
        self._straggler_log = collections.deque(maxlen=_STRAGGLER_EVENTS)
        # Telemetry. Metrics are always on (they back the stats() lane
        # p50/p95 and the stage breakdown); the tracer defaults to the
        # shared disabled singleton.
        self.metrics = MetricsRegistry()
        if trace is None or trace is False:
            self.tracer = NULL_TRACER
        elif trace is True:
            self.tracer = Tracer(clock=self._clock.now)
        elif isinstance(trace, Tracer):
            self.tracer = trace
            if trace._clock is None:
                trace.bind_clock(self._clock.now)
        else:
            raise TypeError(f"trace must be None, a bool, or a Tracer, "
                            f"got {type(trace).__name__}")
        self._device_watch = _DeviceWatch(self.metrics, self._clock.now) \
            if self.tracer.enabled else None
        self._rid = itertools.count()
        self.stats = _Stats({
            "requests": 0, "buckets": 0, "dispatches": 0, "compiles": 0,
            "cache_hits": 0, "padded_slots": 0,
            "stragglers": 0, "retries": 0,
            "routes": {r: 0 for r in ROUTES},
            "flush_triggers": {t: 0 for t in TRIGGERS},
            "lanes": {lane: {"submitted": 0, "shed": 0, "retried": 0,
                             "flushed": 0, "peak_depth": 0}
                      for lane in LANES},
            "last_flush": []})
        self.stats.snapshot = self._stats_snapshot

    # -- request intake ----------------------------------------------------
    def submit(self, op: str, operand, *, power: int = 1,
               dists=None, priority: str = "bulk",
               tenant: Optional[str] = None):
        """Queue one request.

        ``dists`` (op="markov" only) selects the evolve traffic class: a
        (B, n) stack of start distributions evolved ``power`` transitions
        under ``operand``; without it a markov request answers the
        steady-state query (:class:`~repro.core.markov.SteadyStateResult`).

        Synchronous mode returns the request's int index into the next
        ``flush()``; daemon mode (after :meth:`start`) returns a
        :class:`MatFnFuture` immediately — the scheduler thread resolves it
        when the request's bucket fills or its deadline passes.

        ``priority`` names the admission lane: ``"bulk"`` (default) or
        ``"latency"`` for latency-critical traffic — latency-lane buckets
        flush under the lane's SLO deadline cap, are scheduled before bulk
        buckets, and above ``AdmissionControl.bypass_n`` skip bucket
        assembly entirely. When the lane's bounded queue is full the
        admission policy decides who pays: ``submit`` raises
        :class:`~repro.serve.admission.ShedError` (reject-newest) or an
        already-admitted future resolves with it (reject-oldest /
        deadline-aware). Lanes only shape the SCHEDULE, never the math —
        both lanes share the executable cache. In synchronous mode the
        daemon queue does not exist, so admission does not apply.

        ``tenant`` optionally names the submitting tenant for
        observability: resolved latency is additionally recorded under a
        per-tenant histogram view (``engine.metrics.merged("latency",
        tenant=...)``) and request trace spans carry the tag. Purely
        observational — tenants never affect bucketing, admission, or
        the math; ignored in synchronous mode.

        ``operand`` may be a jax or numpy array (kept as-is — the bucket
        program stacks them inside its one jitted call) or anything
        ``jnp.asarray`` accepts. The as-is fast path matters: an asarray
        per submit costs more than a whole warm serial call at small n.
        Non-canonical numpy dtypes (f64 under disabled x64 — numpy's
        default) are converted up front: the executable would silently
        compute in the canonical dtype anyway, and keying the bucket on
        the raw dtype would split identical-math requests into separate
        buckets and executables.
        """
        if not self.tracer.enabled:
            return self._submit(op, operand, power, dists, priority, tenant)
        # Traced: a profiler annotation and the ``stage=submit`` histogram,
        # never a ring record (one per request would crowd the ring).
        span = self.tracer.span("matfn.submit", ring=False)
        try:
            with span:
                return self._submit(op, operand, power, dists, priority,
                                    tenant)
        finally:
            self.metrics.record("stage", span.end - span.start,
                                stage="submit")

    def _submit(self, op: str, operand, power: int, dists, priority: str,
                tenant: Optional[str]):
        if self._closed or self._closing:
            raise RuntimeError("engine is closed; no new requests")
        if priority not in LANES:
            raise ValueError(f"unknown priority lane {priority!r}; "
                             f"expected one of {LANES}")
        if not isinstance(operand, (jax.Array, np.ndarray)):
            operand = jnp.asarray(operand)
        elif isinstance(operand, np.ndarray):
            canon = jax.dtypes.canonicalize_dtype(operand.dtype)
            if canon != operand.dtype:
                operand = jnp.asarray(operand, canon)
        if dists is not None:
            if not isinstance(dists, (jax.Array, np.ndarray)):
                dists = jnp.asarray(dists)
            elif isinstance(dists, np.ndarray):
                canon = jax.dtypes.canonicalize_dtype(dists.dtype)
                if canon != dists.dtype:
                    dists = jnp.asarray(dists, canon)
        req = MatFnRequest(op, operand, power, dists)
        # Mode check under the lock: a concurrent start() must never see
        # _pending empty and then have a sync request appended behind its
        # back — that ticket could never resolve (the daemon only serves
        # _open_buckets and flush() is rejected in daemon mode).
        with self._cv:
            if self._daemon is None:
                self._pending.append(req)
                self.stats["requests"] += 1
                self.stats["lanes"][priority]["submitted"] += 1
                return len(self._pending) - 1
        return self._submit_daemon(req, priority, tenant)

    def _pending_lane(self, lane: str):
        """(views, refs) over one lane's admitted-but-unflushed requests,
        in bucket-iteration order: ``views`` is what policies see,
        ``refs[i] = (bucket, member_index)`` locates the same request for
        eviction. Called under the lock."""
        views, refs = [], []
        for bucket in self._open_buckets.values():
            if bucket.lane != lane:
                continue
            deadline = bucket.first_ts + bucket.max_delay_s
            for i, (fut, _req) in enumerate(bucket.members):
                views.append(PendingView(bucket.key, lane,
                                         fut.submitted_at, deadline))
                refs.append((bucket, i))
        return views, refs

    def _shed_admitted(self, bucket: _Bucket, index: int) -> MatFnFuture:
        """Evict one admitted member (under the lock): remove it from its
        bucket, advance the bucket's deadline anchor past it, drop the
        bucket if it emptied. Returns the victim future (resolved by the
        caller OUTSIDE the lock)."""
        fut, _req = bucket.members.pop(index)
        self._lane_depth[bucket.lane] -= 1
        if not bucket.members:
            del self._open_buckets[(bucket.key, bucket.lane)]
        else:
            bucket.first_ts = min(m[0].submitted_at for m in bucket.members)
        return fut

    def _submit_daemon(self, req: MatFnRequest, lane: str = "bulk",
                       tenant: Optional[str] = None) -> MatFnFuture:
        key = req.bucket_key()
        fut = MatFnFuture(key, lane)
        fut.tenant = tenant
        fut.rid = next(self._rid)
        # Resolved OUTSIDE the lock: a generation bump makes this read the
        # cache file, and one slow disk read must not stall every producer
        # and the scheduler behind the condition lock. Unused when the
        # bucket already exists — the lookup is memoized.
        delay_s = self._lane_delay_s(key, lane)
        victim: Optional[MatFnFuture] = None
        direct: Optional[_Bucket] = None
        shed_depth = 0
        with self._cv:
            if self._closing or self._closed:
                raise RuntimeError("engine is closed; no new requests")
            if self._scheduler_crash is not None:
                raise RuntimeError("scheduler thread crashed") \
                    from self._scheduler_crash
            now = self._clock.now()
            fut.submitted_at = now
            cap = self._admission.capacity_for(lane)
            if cap is not None and self._lane_depth[lane] >= cap:
                # Overflow: the admission policy picks who pays. Shed
                # decisions never touch the device — one counter bump and
                # one exception is the whole cost.
                views, refs = self._pending_lane(lane)
                incoming = PendingView(key, lane, now, now + delay_s)
                idx = self._admission.policy.select_victim(
                    views, incoming, now)
                lane_stats = self.stats["lanes"][lane]
                lane_stats["shed"] += 1
                shed_depth = self._lane_depth[lane]
                if idx is None:
                    err = ShedError(lane, shed_depth, cap,
                                    self._admission.policy.name, key)
                    if self.tracer.enabled:
                        # Reject-newest never reaches _resolve (submit
                        # raises), so its terminal request span and shed
                        # instant are emitted here — every admitted OR
                        # rejected request still ends in exactly one
                        # terminal span.
                        self.tracer.instant("shed", at=now,
                                            track="requests",
                                            **err.as_tags())
                        self._record_request(fut, now, err)
                    raise err
                victim = self._shed_admitted(*refs[idx])
            bucket = self._open_buckets.get((key, lane))
            opened = bucket is None
            if opened:
                bucket = _Bucket(key, lane, [], now, delay_s)
                self._open_buckets[(key, lane)] = bucket
            bucket.members.append((fut, req))
            self._lane_depth[lane] += 1
            lane_stats = self.stats["lanes"][lane]
            lane_stats["submitted"] += 1
            lane_stats["peak_depth"] = max(lane_stats["peak_depth"],
                                           self._lane_depth[lane])
            self.stats["requests"] += 1
            # Priority bypass: above the size threshold a latency request's
            # own execution dominates any batching win. With
            # ``bypass_direct`` (the default) the bucket is handed straight
            # to its route's execution stream below — it never waits for a
            # scheduler poll, so a scheduler busy dispatching bulk backlog
            # cannot delay it. Otherwise it is only MARKED due (dedicated
            # "priority" trigger; the next scheduler poll dispatches it).
            if (lane == "latency" and bucket.forced is None
                    and req.n >= self._admission.bypass_n):
                if self._admission.bypass_direct and self._pool is not None:
                    del self._open_buckets[(key, lane)]
                    self._lane_depth[lane] -= len(bucket.members)
                    self._in_flight.append(bucket)
                    direct = bucket
                else:
                    bucket.forced = "priority"
            self._policy.observe(bucket.view(), now)
            # Wake the scheduler only when this submit can change what it
            # should do: a NEW bucket moves its sleep deadline, a filled
            # or forced bucket is due now, and an adaptive policy may have
            # just moved every deadline earlier. The common submit under
            # load — member #2..#k of an open bucket whose deadline is
            # anchored at its first arrival — changes nothing the
            # scheduler's current sleep doesn't already cover, and
            # skipping the wake there is most of the submit path's cost
            # (wake -> scan -> re-sleep, ~6x per-submit).
            if direct is None and (opened or bucket.forced is not None
                                   or len(bucket.members) >= self.max_batch
                                   or self._policy.wake_on_observe):
                self._cv.notify_all()
        if direct is not None:
            # Outside the lock: dispatch takes the pool lock, and a full
            # stream queue must not stall other producers behind _cv.
            self._dispatch_bucket(direct, "priority")
        if victim is not None:
            # Outside the lock: set_exception wakes the victim's waiters.
            err = ShedError(victim.lane, shed_depth, cap,
                            self._admission.policy.name, victim.bucket_key)
            self.tracer.instant("shed", track="requests", **err.as_tags())
            self._resolve(victim, exc=err)
        return fut

    # -- dispatch policy ---------------------------------------------------
    @staticmethod
    def _memoized(memo: dict, key, resolve):
        """Generation-checked memo read: entries are stored as
        ``(generation, value)`` and only trusted while the autotune cache
        is still at that generation.

        The generation is captured BEFORE resolving, so a retune that
        lands mid-resolution leaves a tuple with a stale generation behind
        — the next read re-resolves instead of serving pre-retune values
        forever. (A clear-on-mismatch scheme has a lost-invalidation race:
        a thread descheduled between resolving and storing would write an
        old value into a freshly-cleared memo.) Called under no lock; dict
        ops are atomic under the GIL and redundant resolution is benign.
        """
        gen = autotune.cache_generation()
        hit = memo.get(key)
        if hit is not None and hit[0] == gen:
            return hit[1]
        value = resolve()
        memo[key] = (gen, value)
        return value

    def thresholds_for(self, dtype=None) -> tuple:
        """(cpu_max_n, sharded_min_n) for an operand dtype.

        The explicit constructor override wins; otherwise the tuning
        cache's ``dispatch`` namespace is consulted per dtype (a bf16
        crossover legitimately differs from f32 — half the bytes per
        operand) and memoized per cache generation: recording new
        thresholds mid-process invalidates the memo and reroutes the very
        next bucket.
        """
        if self._thresholds_override is not None:
            return self._thresholds_override
        key = jnp.dtype(dtype).name if dtype is not None else "any"
        return self._memoized(
            self._thresholds_cache, key,
            lambda: autotune.dispatch_thresholds(
                dtype=None if dtype is None else dtype))

    @property
    def thresholds(self) -> tuple:
        """The dtype-agnostic thresholds (override or ``any`` cache entry)."""
        return self.thresholds_for(None)

    def _bucket_delay_s(self, key: tuple) -> float:
        """Flush deadline (seconds) for one traffic class: the engine
        override, else the tuned per-(op, n, dtype) ``dispatch`` deadline
        entry, memoized per cache generation like the thresholds."""
        if self._max_delay_ms is not None:
            return self._max_delay_ms / 1e3
        op, n, dtype, _power = key
        return self._memoized(
            self._deadline_cache, (op, n, dtype),
            lambda: autotune.bucket_deadline_ms(op, n, dtype=dtype) / 1e3)

    def _lane_delay_s(self, key: tuple, lane: str) -> float:
        """Effective flush deadline for one (traffic class, lane): the
        class deadline capped by the lane's SLO target — a latency-lane
        bucket never waits past its SLO budget, and AdaptiveDeadline only
        ever shrinks the wait below this cap."""
        delay_s = self._bucket_delay_s(key)
        slo_s = self._admission.slo_s_for(lane)
        return delay_s if slo_s is None else min(delay_s, slo_s)

    def fastmm_crossover_for(self, dtype=None) -> int:
        """The Strassen crossover n for an operand dtype: buckets with
        n STRICTLY above it take the ``fastmm`` route. Resolved from the
        tuning cache's ``fastmm`` namespace and memoized per cache
        generation exactly like the dispatch thresholds — a mid-process
        retune reroutes the very next bucket."""
        key = jnp.dtype(dtype).name if dtype is not None else "any"
        return self._memoized(
            self._fastmm_cache, key,
            lambda: autotune.fastmm_config(
                dtype=None if dtype is None else dtype)[0])

    def route_for(self, n: int, batch: int, dtype=None,
                  power=None) -> str:
        """Heterogeneous dispatch: which executor serves an (n, batch) bucket.

        ``sharded`` (mesh-resident chain) only ever takes single huge
        matrices — the 2-D specs are per-matrix (ROADMAP: batched sharded
        chains are unexplored) — so batched buckets at any n stay on-device
        local routes. Huge-n buckets above the autotuned Strassen crossover
        (and not sharded-eligible) take ``fastmm`` — the only
        tolerance-bounded route; everything else is bit-identical to
        per-matrix calls. Markov evolve buckets (``power`` slot
        ``("evolve", steps, B)``) always take the fifth ``evolve`` route —
        vector-matrix work has its own stream so a distribution sweep
        never queues behind dense-square buckets; whether a big-B member
        internally falls back to the dense path is the autotuned
        ``markov`` threshold's call, not the router's.
        """
        if _is_evolve(power):
            return "evolve"
        cpu_max_n, sharded_min_n = self.thresholds_for(dtype)
        if self.mesh is not None and batch == 1 and n >= sharded_min_n:
            return "sharded"
        if n <= cpu_max_n:
            return "xla"
        if n > self.fastmm_crossover_for(dtype):
            return "fastmm"
        return "chain"

    @property
    def _chain_backend(self) -> str:
        return "pallas_chain_interpret" if self.interpret else "pallas_chain"

    @property
    def _fastmm_backend(self) -> str:
        return "pallas_fastmm_interpret" if self.interpret else "pallas_fastmm"

    # -- executable cache --------------------------------------------------
    def _executable(self, op: str, route: str, padded_batch: int, n: int,
                    dtype: str, power: int):
        # The whole lookup-or-build runs under the stats lock: concurrent
        # streams sharing one cache must count exactly one compile per key
        # (the stream-count-invariance suite asserts exact accounting).
        # Building is cheap to hold a lock across — jax.jit only WRAPS
        # here; actual compilation happens on first call, on the stream.
        with self._stats_lock:
            return self._executable_locked(op, route, padded_batch, n,
                                           dtype, power)

    def _executable_locked(self, op: str, route: str, padded_batch: int,
                           n: int, dtype: str, power: int):
        key = (op, route, padded_batch, n, dtype, power)
        exe = self._executables.get(key)
        if exe is not None:
            self.stats["cache_hits"] += 1
            return key, exe, False
        if route == "sharded":
            # The sharded chain drives its own jitted collective steps (one
            # compiled step shared per mesh/shape): no outer jit and no
            # stack, since the bucket is one matrix by construction. It
            # answers a one-member tuple, like every bucket program.
            from repro.core import distributed
            mesh = self.mesh
            if op == "markov":
                # Mesh-resident steady state: the convergence loop runs on
                # a ShardedMatmulChain (pad + 2-D sharding committed once,
                # every squaring a donated collective step), the same
                # structure as expm_sharded's loop.
                from repro.core.markov import steady_state
                chain = distributed.ShardedMatmulChain(
                    n, jnp.dtype(dtype), mesh, donate=False)
                exe = lambda x: (steady_state(x, validate=False,
                                              chain=chain),)
            elif op == "matpow":
                exe = lambda x: (distributed.matpow_sharded(x, power,
                                                            mesh),)
            else:
                exe = lambda x: (distributed.expm_sharded(x, mesh),)
        else:
            exe = _bucket_program(self._per_stack(op, route, n, dtype,
                                                  power))
        self._executables[key] = exe
        self.stats["compiles"] += 1
        return key, exe, True

    def _per_stack(self, op: str, route: str, n: int, dtype: str, power):
        """What a local route's bucket program runs on the stacked bucket:
        one program per (op, route), whatever the padded batch."""
        backend = (self._chain_backend if route == "chain"
                   else self._fastmm_backend if route == "fastmm"
                   else "xla")
        if op == "markov" and _is_evolve(power):
            # The evolve route: lax.map of each (operand, dists) pair
            # through the binary-decomposition vector-matrix chain, for
            # the same reason as expm below: compile size stays O(1) in
            # the bucket batch, and each member's big-B dense fallback
            # decision (the autotuned ``markov`` threshold, resolved at
            # trace time) is per-shape anyway.
            from repro.core.markov import evolve_distributions
            cpu_max_n, _ = self.thresholds_for(dtype)
            per_member = functools.partial(
                evolve_distributions, steps=power[1],
                backend="xla" if n <= cpu_max_n else self._chain_backend,
                validate=False)
            return lambda pairs: lax.map(
                lambda pair: per_member(pair[1], pair[0]), pairs)
        if op == "markov":
            # Steady state: lax.map of the per-matrix convergence loop, so
            # every bucket member keeps its OWN squaring count (a stacked
            # loop would square everyone to the slowest mixer) and answers
            # stay bit-identical to per-matrix steady_state calls.
            from repro.core.markov import steady_state
            per_matrix = functools.partial(steady_state, validate=False,
                                           backend=backend)
            return lambda stack: lax.map(per_matrix, stack)
        if op == "matpow":
            return functools.partial(batched_matpow, p=power, backend=backend)
        # lax.map, NOT a stacked expm: the per-matrix 2-D program lowers
        # identically inside the loop, so bucket answers stay bit-identical
        # to per-matrix expm calls (a fused batched expm reassociates the
        # elementwise Pade chain and drifts by ~1 ulp at B > 1), and each
        # matrix keeps its own data-dependent squaring count instead of
        # masking to the stack max. One program per bucket still amortizes
        # dispatch across the batch.
        per_matrix = functools.partial(_expm, backend=backend)
        return lambda stack: lax.map(per_matrix, stack)

    def _filler(self, n: int, dtype: str, power):
        """The zero payload that pads a bucket of one class: an (n, n)
        matrix, or for evolve an (operand, dists) pair. One per class,
        shared by every padded slot; filler rows are never delivered."""
        rows = power[2] if _is_evolve(power) else None
        key = (n, dtype, rows)
        filler = self._fillers.get(key)
        if filler is None:
            mat = jnp.zeros((n, n), dtype)
            filler = mat if rows is None \
                else (mat, jnp.zeros((rows, n), dtype))
            filler = self._fillers.setdefault(key, filler)
        return filler

    def warm(self, op: str, n: int, dtype=jnp.float32, power: int = 1,
             batches=None) -> int:
        """Precompile everything one traffic class will need.

        Runs the REAL bucket path (one bucket program, then the host-side
        row pick) on zero payloads for every batch size in ``batches``.
        A bucket program is keyed on the padded size alone, so the default
        is one batch of each distinct padded size (``bucket_batch`` of
        1..``max_batch``: 7 at ``max_batch`` 64); an explicit list runs
        each size on its padded size's program. Call before opening
        traffic (warm chunks count into the engine stats like any other
        bucket execution); returns the number of chunks warmed.

        In daemon mode each warm chunk runs ON its route's execution
        stream (queued FIFO behind any dispatched buckets): the compile
        lands on the thread that will serve the route, streams warm in
        parallel, and a fresh stream's first post-warm flush pays zero
        compiles. Synchronous engines warm on the calling thread.

        ``op="markov"`` warms the steady-state class (zero-matrix filler
        converges after one squaring, so warm chunks are cheap). Evolve
        classes are keyed on the (steps, B) pair, which warm has no
        argument for — their first bucket pays its own compile.
        """
        dtype = jnp.dtype(dtype)
        if batches is None:
            batches = sorted({bucket_batch(b, self.max_batch)
                              for b in range(1, self.max_batch + 1)})
        power = power if op == "matpow" else -1
        zeros = self._filler(n, dtype.name, power)
        with self._cv:
            pool = self._pool

        def run(operands):
            return jax.block_until_ready(self._resolve_chunk(
                self._run_chunk(op, n, dtype.name, power, operands)))

        count, jobs = 0, []
        for b in batches:
            operands = [zeros] * b
            if pool is not None:
                stream = self._streams.stream_for(
                    self.route_for(n, b, dtype.name))
                jobs.append(pool.call(stream, functools.partial(run,
                                                                operands)))
            else:
                run(operands)
            count += 1
        for job in jobs:       # propagate compile errors to the caller
            job.result()
        return count

    # -- bucket execution core (shared by flush() and the daemon) ----------
    def _run_chunk(self, op: str, n: int, dtype: str, power: int,
                   operands) -> _Dispatched:
        """Execute ONE bucket chunk (<= max_batch) as one device program;
        the caller picks and delivers its rows with :meth:`_resolve_chunk`.

        This is the single execution core both the synchronous ``flush``
        and the daemon scheduler run, which is what keeps daemon answers
        bit-identical to synchronous ones: same program, same executable
        cache, same routes. The program takes the ``b`` member payloads
        and ``bpad - b`` references to the class's zero filler as
        separate arguments, and stacks, runs and splits inside itself.

        Stage timing: assemble (executable lookup and the argument list)
        and execute (the one dispatch; device-complete only under
        ``profile=True``) feed the ``stage`` histograms behind
        ``stats()["stages"]`` and, when tracing, lexical spans on the
        executing thread's track (ring records and profiler annotations),
        plus the ``device`` stage: dispatch -> outputs ready.
        """
        b = len(operands)
        route = self.route_for(n, b, dtype, power)
        bpad = 1 if route == "sharded" else bucket_batch(b, self.max_batch)
        tracer = self.tracer
        if tracer.enabled:
            track = threading.current_thread().name
            tags = dict(op=op, n=n, dtype=dtype, route=route, batch=b,
                        padded=bpad)
            assemble = tracer.span("bucket.assemble", track=track, **tags)
            execute = tracer.span("bucket.execute", track=track,
                                  profiled=self.profile, **tags)
        else:
            tags = None
            assemble = execute = NULL_SPAN
        clk = self._clock.now
        t0 = clk()
        with assemble:
            key, exe, fresh = self._executable(op, route, bpad, n, dtype,
                                               power)
            members = list(operands)
            if bpad > b:
                members += [self._filler(n, dtype, power)] * (bpad - b)
            assemble.tag(cold=fresh)
        t1 = clk()
        with execute:
            if self.profile:
                # Per-bucket wall time for the stats rows — blocks each
                # bucket, so profiling serializes execution; leave it off
                # to let buckets dispatch asynchronously. perf_counter,
                # not the engine clock: this dt is honest device wall
                # time even under a ManualClock test.
                tp = time.perf_counter()
                out = jax.block_until_ready(exe(*members))
                dt = time.perf_counter() - tp
            else:
                out = exe(*members)
                dt = None
        t2 = clk()
        self.metrics.record("stage", t1 - t0, stage="assemble", route=route)
        self.metrics.record("stage", t2 - t1, stage="execute", route=route)
        if tags is not None:
            if fresh:
                tracer.instant("compile", at=t1, track=track, **tags)
            if self.profile:
                self.metrics.record("stage", t2 - t1, stage="device",
                                    route=route)
            else:
                self._device_watch.watch(out, t1, route)
        with self._stats_lock:
            self.stats["padded_slots"] += bpad - b
            self.stats["buckets"] += 1
            self.stats["dispatches"] += 1
            self.stats["routes"][route] += 1
            self.stats["last_flush"].append(
                {"key": key, "requests": b, "padded_batch": bpad,
                 "route": route, "seconds": dt})
        return _Dispatched(out, b, route, tags)

    def _resolve_chunk(self, chunk: _Dispatched, deliver=None) -> tuple:
        """The resolve stage of one chunk: pick its B member rows off the
        program's per-slot outputs on the host (dropping the filler
        slots; no device call) and hand them to ``deliver`` (the futures'
        resolution loop, or the synchronous result slots). Returns the
        rows. Feeds ``stage=resolve`` and, when tracing, the
        ``bucket.resolve`` span."""
        if chunk.tags is not None:
            span = self.tracer.span("bucket.resolve",
                                    track=threading.current_thread().name,
                                    **chunk.tags)
        else:
            span = NULL_SPAN
        clk = self._clock.now
        t0 = clk()
        with span:
            rows = _split_rows(chunk.out, b=chunk.b)
            if deliver is not None:
                deliver(rows)
        self.metrics.record("stage", clk() - t0, stage="resolve",
                            route=chunk.route)
        return rows

    # -- synchronous batch execution ---------------------------------------
    def flush(self) -> List[jax.Array]:
        """Answer every pending request; results in submission order.

        Synchronous mode only — the daemon owns its queue and resolves
        futures instead (``close()`` drains it).
        """
        if self._daemon is not None:
            raise RuntimeError(
                "flush() is the synchronous API; in daemon mode the "
                "scheduler resolves futures — use submit().result() "
                "(close() drains pending work)")
        pending, self._pending = self._pending, []
        results: List[Optional[jax.Array]] = [None] * len(pending)
        groups: dict = {}
        for idx, req in enumerate(pending):
            groups.setdefault(req.bucket_key(), []).append((idx, req))

        self.stats["last_flush"] = []
        for (op, n, dtype, power), members in groups.items():
            for lo in range(0, len(members), self.max_batch):
                chunk = members[lo:lo + self.max_batch]
                rows = self._resolve_chunk(self._run_chunk(
                    op, n, dtype, power, [req.payload for _, req in chunk]))
                for (idx, _), row in zip(chunk, rows):
                    results[idx] = row
        return results  # type: ignore[return-value]

    # -- continuous-batching daemon ----------------------------------------
    @property
    def running(self) -> bool:
        """True while the scheduler thread is serving submits."""
        return (self._daemon is not None and self._daemon.is_alive()
                and not self._closed)

    def start(self) -> "MatFnEngine":
        """Promote the engine to a continuous-batching daemon.

        Spawns the scheduler thread; from here ``submit`` returns futures
        and buckets flush on fill-or-deadline. Idempotent while running;
        a closed engine cannot restart (build a new one — the executable
        cache is the expensive state and it is per-engine anyway).
        """
        with self._cv:
            if self._closed:
                raise RuntimeError("engine is closed and cannot restart")
            if self._daemon is not None:
                return self
            if self._pending:
                raise RuntimeError(
                    f"{len(self._pending)} synchronous request(s) pending; "
                    f"flush() before start() — tickets would never resolve")
            self._clock.bind(self._cv)
            # Executor streams first: the scheduler dispatches into the
            # pool from its very first poll. Lock order is engine -> pool
            # only, so starting it under _cv cannot deadlock.
            self._pool = StreamPool(self._streams, self._stream_execute,
                                    on_free=self._on_stream_free,
                                    on_crash=self._on_stream_crash,
                                    tracer=self.tracer,
                                    metrics=self.metrics,
                                    now=self._clock.now).start()
            # Assigned AND started under the lock: from here every submit
            # routes to the daemon (see the mode check in submit()), and a
            # concurrent close() can never join a not-yet-started thread.
            # The scheduler's first action is acquiring this same lock, so
            # it simply blocks until we release — no deadlock.
            self._daemon = threading.Thread(target=self._scheduler_main,
                                            name="matfn-scheduler",
                                            daemon=True)
            self._daemon.start()
        return self

    def __enter__(self) -> "MatFnEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    def kick(self, key: Optional[tuple] = None) -> int:
        """Mark open buckets due now (flush without waiting for fill or
        deadline): the ``key``'s buckets only (both lanes), or every open
        bucket when ``key`` is None. The synchronous convenience calls
        kick just their own future's ``bucket_key`` so a lone
        ``engine.matpow(a, p)`` on a busy daemon answers immediately
        WITHOUT force-flushing bystander classes' half-full buckets.

        Kicking an empty traffic class is a NO-OP — no bucket is marked,
        no trigger is counted, and the scheduler is not even woken (a
        spurious wakeup is cheap, but a kick storm against idle classes
        should cost nothing). Returns the number of buckets kicked.
        """
        kicked = 0
        with self._cv:
            for bucket in self._open_buckets.values():
                if (key is None or bucket.key == key) \
                        and bucket.forced is None:
                    bucket.forced = "kick"
                    kicked += 1
            if kicked:
                self._cv.notify_all()
        return kicked

    def settle(self, timeout: float = 10.0) -> None:
        """Block until the scheduler has DISPATCHED everything currently
        due, every execution stream has finished what it was handed, and
        the daemon is idle (waiting for new work or a future deadline).

        Instrumentation/test hook: with a :class:`ManualClock` this makes
        "the daemon processed that wakeup" a deterministic event (stream
        completions notify the engine condition, so stream idleness is an
        event too, not a poll). Raises ``TimeoutError`` if the scheduler
        does not settle in ``timeout`` real seconds (a crashed scheduler
        surfaces here instead of hanging). No-op in synchronous mode.
        """
        if self._daemon is None:
            return
        deadline = time.monotonic() + timeout
        with self._cv:
            while True:
                if self._scheduler_crash is not None:
                    raise RuntimeError("scheduler thread crashed") \
                        from self._scheduler_crash
                streams_idle = (not self._in_flight
                                and (self._pool is None
                                     or self._pool.idle()))
                if not self._daemon.is_alive() and not self._open_buckets \
                        and streams_idle:
                    return
                if self._waiting and streams_idle \
                        and not self._any_due(self._clock.now()):
                    return
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError("scheduler did not settle")
                # Sliced wait: also bounds the case where the scheduler
                # dies without a final notify.
                self._cv.wait(min(remaining, 0.05))

    def close(self, drain: bool = True, timeout: Optional[float] = None):
        """Stop the daemon. Idempotent; synchronous engines just close.

        ``drain=True`` (default): the scheduler flushes EVERY pending
        bucket — partial or not — before exiting, so no submitted future is
        ever dropped; errors still resolve futures (as
        :class:`BucketExecutionError`), never vanish. ``drain=False``
        fails every pending future with ``CancelledError`` and exits
        without running them — INCLUDING futures of buckets already popped
        for execution: a wedged executor must not strand an in-flight
        future until its ``result()`` timeout (the cancellation is
        tolerant — if the executor finishes first, the real answer wins
        and the late cancel is a no-op). New submits are rejected as soon
        as close begins.

        With a ``timeout``, a scheduler that has not drained in time
        raises ``TimeoutError`` (the engine stays closed to new submits and
        the thread keeps draining in the background — futures may still
        resolve) instead of silently reporting a completed drain.
        """
        if self._daemon is None:
            self._closed = True
            self._close_device_watch()
            return
        cancelled: List[_Bucket] = []
        cancel = False
        with self._cv:
            cancel = not drain and not self._closing
            if cancel:
                # Open buckets are dropped outright; in-flight buckets are
                # only COPIED — their stream still owns them, and their
                # futures are poisoned best-effort below (the resolution
                # race against a finishing executor is settled by the
                # futures' single-assignment lock, whoever wins).
                cancelled = (list(self._open_buckets.values())
                             + list(self._in_flight))
                self._open_buckets.clear()
                self._lane_depth = {lane: 0 for lane in LANES}
            self._closing = True
            self._cv.notify_all()
        if cancel and self._pool is not None:
            # Queued-but-unstarted buckets never run: pull them off their
            # streams (they are already in the cancelled snapshot via
            # _in_flight) so the drain wait doesn't execute doomed work.
            dropped = [b for b, _t in self._pool.cancel_queued()]
            with self._cv:
                for b in dropped:
                    if b in self._in_flight:
                        self._in_flight.remove(b)
                self._cv.notify_all()
        for bucket in cancelled:
            err = CancelledError(f"engine closed with drain=False; bucket "
                                 f"{bucket.key} dropped")
            for fut, _ in bucket.members:
                self._resolve(fut, exc=err)
        self._daemon.join(timeout)
        self._closed = True
        if self._daemon.is_alive():
            raise TimeoutError(
                f"scheduler still draining after {timeout}s; engine is "
                f"closed to new submits, pending futures may yet resolve")
        if self._pool is not None:
            # The scheduler's drain wait already saw the streams idle;
            # shutdown + join releases the worker threads (the suite's
            # thread-leak check counts on active_count() returning to its
            # pre-start baseline after close()).
            self._pool.shutdown()
            if not self._pool.join(timeout):
                raise TimeoutError(
                    f"execution streams still busy after {timeout}s; "
                    f"engine is closed to new submits, pending futures "
                    f"may yet resolve")
        self._close_device_watch()

    def _close_device_watch(self) -> None:
        if self._device_watch is not None:
            self._device_watch.close()

    # -- scheduler internals -----------------------------------------------
    def _any_due(self, now: float) -> bool:
        return self._closing or any(
            b.forced or self._policy.due(b.view(), now, self.max_batch)
            for b in self._open_buckets.values())

    def _take_due(self, now: float,
                  lane: Optional[str] = None) -> List[tuple]:
        """Pop every bucket that must flush now; returns (bucket, trigger)
        pairs with LATENCY-lane buckets first (the priority lane's due
        work never queues behind bulk flushes taken in the same poll).
        ``lane`` restricts the scan to one lane (the scheduler's
        between-buckets preemption check only wants latency work).
        Under ``_closing`` everything pending drains. Every popped bucket
        is registered in ``_in_flight`` BEFORE this returns (even if a
        user policy's ``due`` raises mid-scan), so the crash handler can
        always reach it."""
        due = []
        for dict_key in list(self._open_buckets):
            bucket = self._open_buckets[dict_key]
            if lane is not None and bucket.lane != lane:
                continue
            if self._closing:
                trigger = "drain"
            elif bucket.forced is not None:
                trigger = bucket.forced
            elif self._policy.due(bucket.view(), now, self.max_batch):
                trigger = ("fill" if len(bucket.members) >= self.max_batch
                           else "deadline")
            else:
                continue
            del self._open_buckets[dict_key]
            self._lane_depth[bucket.lane] -= len(bucket.members)
            self._in_flight.append(bucket)
            due.append((bucket, trigger))
        due.sort(key=lambda bt: 0 if bt[0].lane == "latency" else 1)
        return due

    def _next_timeout(self, now: float) -> Optional[float]:
        """Seconds until the earliest bucket deadline (None: no buckets)."""
        if not self._open_buckets:
            return None
        earliest = min(self._policy.deadline(b.view(), self.max_batch)
                       for b in self._open_buckets.values())
        return max(earliest - now, 0.0)

    def _scheduler_main(self) -> None:
        try:
            self._scheduler_loop()
        except BaseException as exc:  # never die silently: fail what's left
            # Streams first: pull queued-but-unstarted buckets off every
            # stream (they are registered in _in_flight, so the sweep
            # below reaches their futures) — with no scheduler left to
            # hand out work there is no point executing a dead engine's
            # backlog. Buckets already EXECUTING finish on their streams;
            # their resolutions race the sweep and the futures'
            # single-assignment lock settles who wins.
            if self._pool is not None:
                self._pool.cancel_queued()
            with self._cv:
                self._scheduler_crash = exc
                leftovers = (list(self._in_flight)
                             + list(self._open_buckets.values()))
                self._open_buckets.clear()
                self._in_flight.clear()
                self._lane_depth = {lane: 0 for lane in LANES}
                self._cv.notify_all()
            for bucket in leftovers:
                err = BucketExecutionError(bucket.key, exc)
                for fut, _ in bucket.members:
                    # Tolerant resolution: a close(drain=False) racing this
                    # crash may have poisoned a future first — a second
                    # set_exception must not abort the sweep and strand
                    # the REST of the leftovers unresolved.
                    self._resolve(fut, exc=err)
        else:
            # Normal exit (close drain): joining the scheduler thread must
            # keep meaning "fully drained", so wait for every dispatched
            # bucket to clear its stream before dying. Stream completions
            # notify _cv; SystemClock slices the wait so a worker that
            # dies without its final notify cannot hang the drain.
            self._drain_streams()

    def _drain_streams(self) -> None:
        if self._pool is None:
            return
        with self._cv:
            self._clock.wait_for(
                self._cv,
                lambda: not self._in_flight and self._pool.idle())

    def _scheduler_loop(self) -> None:
        """Fill-or-deadline scheduling: sleep until the earliest deadline
        (or a submit/kick/close wakeup), hand what is due to its route's
        execution stream, repeat.

        The scheduler never executes buckets itself: each due bucket goes
        to its dispatch route's stream (:class:`~repro.serve.streams.
        StreamPool`), so producers keep assembling the next buckets while
        the streams crunch the current ones — and a big chain bucket in
        flight no longer delays a due xla flush, because they live on
        different streams.

        Latency preemption moved WITH execution: ``_take_due`` still
        orders latency-lane buckets first within one poll, and on each
        stream a dispatched latency bucket queues ahead of every
        not-yet-started bulk one — a latency request waits for at most
        ONE in-progress execution on its own stream, and for nothing at
        all on the others. Under overload that is the difference between
        the priority lane tracking its SLO and inheriting the bulk
        queue's tail.
        """
        while True:
            with self._cv:
                while True:
                    now = self._clock.now()
                    due = self._take_due(now)
                    if due:
                        break
                    if self._closing:      # drained: nothing left to take
                        return
                    self._waiting = True
                    self._cv.notify_all()  # settle() handshake
                    try:
                        self._clock.traced_wait(
                            self._cv, self._next_timeout(now), self.tracer)
                    finally:
                        self._waiting = False
            for bucket, trigger in due:
                # A profiler annotation only: the scheduler's own work on a
                # popped bucket (bucket.batch ends inside it).
                with self.tracer.span("scheduler.dispatch", ring=False):
                    self._dispatch_bucket(bucket, trigger)

    def _dispatch_bucket(self, bucket: _Bucket, trigger: str) -> None:
        """Hand one popped bucket to its route's execution stream.

        The chunk route is recomputed per chunk inside ``_run_chunk``
        (identical logic), so stream placement and math always agree for
        buckets <= max_batch; an oversized bucket's tail chunk may route
        differently than its head, in which case the whole bucket runs on
        the head chunk's stream — placement is a scheduling choice, the
        math per chunk is unchanged. A crashed stream fails just this
        bucket's futures (typed, attributable) instead of sinking the
        scheduler.
        """
        op, n, dtype, power = bucket.key
        route = self.route_for(n, min(len(bucket.members), self.max_batch),
                               dtype, power)
        if self.tracer.enabled:
            # The batching phase: bucket open (first member's arrival) ->
            # this dispatch decision, tagged with WHY it flushed.
            self.tracer.add_span(
                "bucket.batch", bucket.first_ts, self._clock.now(),
                track="scheduler", op=op, n=n, dtype=dtype, power=power,
                lane=bucket.lane, route=route, trigger=trigger,
                batch=len(bucket.members))
        try:
            bucket.stream = self._pool.dispatch(
                route, bucket, trigger,
                priority=(bucket.lane == "latency"))
        except StreamCrashed as exc:
            with self._cv:
                if bucket in self._in_flight:
                    self._in_flight.remove(bucket)
                self._cv.notify_all()
            err = BucketExecutionError(bucket.key, exc)
            for fut, _ in bucket.members:
                self._resolve(fut, exc=err)

    def _stream_execute(self, bucket: _Bucket, trigger: str,
                        stream: int) -> None:
        """The pool's executor: runs on a stream worker. Executor
        ``Exception``\\ s are already routed into futures by
        ``_execute_bucket``; the finally block de-registers the bucket and
        wakes anyone waiting on "a stream freed" (settle, the drain wait,
        a ManualClock test) even when a non-Exception escape is about to
        crash the stream."""
        del stream  # identity is recorded at dispatch (bucket.stream)
        try:
            self._execute_bucket(bucket, trigger)
        finally:
            with self._cv:
                if bucket in self._in_flight:
                    self._in_flight.remove(bucket)
                self._cv.notify_all()

    def _on_stream_free(self, stream: int) -> None:
        """Pool callback (outside the pool lock): a stream finished an
        item — wake settle()/drain waiters blocked on the engine cv."""
        del stream
        with self._cv:
            self._cv.notify_all()

    def _on_stream_crash(self, stream: int, items: List[tuple],
                         exc: BaseException) -> None:
        """Pool callback (outside the pool lock): stream ``stream`` died
        executing ``items[0]``; ``items[1:]`` are its queued-but-unstarted
        buckets. Every affected future is failed with a typed
        :class:`BucketExecutionError`; other streams keep serving."""
        buckets = [b for b, _t in items]
        with self._cv:
            for b in buckets:
                if b in self._in_flight:
                    self._in_flight.remove(b)
            self._cv.notify_all()
        for b in buckets:
            err = BucketExecutionError(b.key, exc)
            for fut, _ in b.members:
                # Tolerant: the crashing execution may have resolved part
                # of the bucket before dying.
                self._resolve(fut, exc=err)

    def _resolve(self, fut: MatFnFuture, value=_UNSET,
                 exc: Optional[BaseException] = None) -> bool:
        """Resolve one future, tolerating an earlier resolution (a
        close(drain=False) cancel or crash sweep racing the executor —
        single-assignment settles who wins, and the loser must not
        propagate ``InvalidStateError`` into the scheduler).

        The resolution timestamp comes from the ENGINE clock (same epoch
        as ``submitted_at`` — the clock-consistency fix: profiled
        open-loop latency is now always ``resolved_at - submitted_at``
        with both ends on one clock). Successful results feed the
        per-lane (and per-tenant, when tagged) latency histograms behind
        ``stats()``; every winning resolution emits the request's
        terminal lifecycle span."""
        at = self._clock.now()
        fut._resolve_at_hint = at
        try:
            if exc is not None:
                fut.set_exception(exc)
            else:
                fut.set_result(value)
        except InvalidStateError:
            return False
        if exc is None and fut.submitted_at is not None:
            dt = at - fut.submitted_at
            if fut.tenant is not None:
                self.metrics.record("latency", dt, lane=fut.lane,
                                    tenant=fut.tenant)
            else:
                self.metrics.record("latency", dt, lane=fut.lane)
        self._record_request(fut, at, exc)
        return True

    def _record_request(self, fut: MatFnFuture, end: float,
                        exc: Optional[BaseException]) -> None:
        """Emit one request's terminal lifecycle span (submit -> terminal,
        on the ``requests`` track). Exactly-once per request: _resolve
        only calls this for the WINNING resolution, and the reject-newest
        shed path (which never reaches _resolve) emits its own."""
        if not self.tracer.enabled or fut.submitted_at is None:
            return
        if exc is None:
            outcome = "resolved"
        elif isinstance(exc, ShedError):
            outcome = "shed"
        elif isinstance(exc, CancelledError):
            outcome = "cancelled"
        else:
            outcome = "error"
        op, n, dtype, power = fut.bucket_key
        tags = dict(op=op, n=n, dtype=dtype, power=power, lane=fut.lane,
                    rid=fut.rid, outcome=outcome)
        if fut.tenant is not None:
            tags["tenant"] = fut.tenant
        self.tracer.add_span("request", fut.submitted_at, end,
                             track="requests", **tags)

    def _evict_class_executables(self, key: tuple) -> int:
        """Drop every cached executable serving one (op, n, dtype, power)
        traffic class — all routes and padded batch sizes. The self-heal
        path: each bounded retry re-resolves the executable, so a
        poisoned compile-cache entry costs one recompile instead of
        poisoning the class forever."""
        op, n, dtype, power = key
        with self._stats_lock:
            stale = [k for k in self._executables
                     if (k[0], k[3], k[4], k[5]) == (op, n, dtype, power)]
            for k in stale:
                del self._executables[k]
        return len(stale)

    def _execute_bucket(self, bucket: _Bucket, trigger: str) -> None:
        """Run one popped bucket and resolve its futures.

        Each chunk runs under the fault runtime: the flush is wall-timed
        into the :class:`~repro.runtime.fault.Watchdog` (a straggling
        flush records a ``StragglerEvent`` into the stats — counted and
        logged only: legitimate duration variance across batch sizes and
        first-compile flushes means eviction-on-straggle would recompile
        healthy executables and FEED the very tail it watches for), and
        an executor exception retries through
        :func:`~repro.runtime.fault.retry_step` — each retry evicts the
        class's cached executables first, so a poisoned compile-cache
        entry is re-resolved rather than re-raised. Only after
        ``self.retries`` bounded retries does the FAILING CHUNK resolve
        with a
        :class:`BucketExecutionError` naming the bucket key (the fix for
        errors surfacing only on the calling thread — on a daemon there
        is no calling thread to surface them to); the scheduler stays
        alive for the other buckets either way.
        """
        op, n, dtype, power = bucket.key
        lane_stats = self.stats["lanes"][bucket.lane]
        with self._stats_lock:
            self.stats["flush_triggers"][trigger] += 1
        members = bucket.members
        for lo in range(0, len(members), self.max_batch):
            chunk = members[lo:lo + self.max_batch]

            def run_chunk():
                # self._run_chunk looked up per attempt (tests monkeypatch
                # the bound attribute) — the single execution core shared
                # with the synchronous flush().
                return self._run_chunk(op, n, dtype, power,
                                       [req.payload for _, req in chunk])

            def deliver(rows):
                for (fut, _), row in zip(chunk, rows):
                    self._resolve(fut, value=row)

            def fail(exc):
                err = BucketExecutionError(bucket.key, exc)
                for fut, _ in chunk:
                    self._resolve(fut, exc=err)

            def on_retry(attempt, exc):
                self._evict_class_executables(bucket.key)
                with self._stats_lock:
                    self.stats["retries"] += 1
                    lane_stats["retried"] += len(chunk)
                self.tracer.instant(
                    "retry", track=threading.current_thread().name,
                    op=op, n=n, dtype=dtype, power=power, lane=bucket.lane,
                    attempt=attempt, error=type(exc).__name__)

            t0 = time.perf_counter()
            try:
                dispatched = retry_step(run_chunk, retries=self.retries,
                                        backoff_s=self.retry_backoff_s,
                                        on_retry=on_retry)
            except Exception as exc:
                fail(exc)
                continue
            finally:
                # Watchdog.observe serializes internally: concurrent
                # streams share one rolling median without a cross-stream
                # head-of-line stall (retry BACKOFF sleeps on this
                # stream's own worker only).
                event = self._watchdog.observe(self.stats["buckets"],
                                               time.perf_counter() - t0)
                if event is not None:
                    with self._stats_lock:
                        self.stats["stragglers"] += 1
                    self._straggler_log.append(
                        f"{event} (bucket {bucket.key}, lane {bucket.lane})")
                    self.tracer.instant(
                        "straggler",
                        track=threading.current_thread().name,
                        key=str(bucket.key), lane=bucket.lane,
                        **event.as_tags())
            try:
                # The resolve stage (row split + the futures' resolution)
                # runs once the watchdog has seen the flush, so a resolved
                # future finds its bucket's accounting done.
                self._resolve_chunk(dispatched, deliver)
            except Exception as exc:
                fail(exc)
                continue
            with self._stats_lock:
                lane_stats["flushed"] += len(chunk)
        with self._stats_lock:
            rows_log = self.stats["last_flush"]
            if len(rows_log) > _LAST_FLUSH_ROWS:
                del rows_log[:len(rows_log) - _LAST_FLUSH_ROWS]

    # -- observability -----------------------------------------------------
    def _stats_snapshot(self) -> dict:
        """One consistent point-in-time report (what ``engine.stats()``
        returns): the cumulative counters plus, per lane, the LIVE queue
        depth, peak depth, and histogram-backed p50/p95 latency over ALL
        resolutions (engine-clock submit -> resolution — under the
        serving configuration that is queue wait + assembly + async
        dispatch, the quantity admission control governs; log-spaced
        buckets, so quantiles carry ~9% relative error but never forget
        old samples the way the former deque window did). ``stages``
        breaks the pipeline down per stage (queue / assemble / execute /
        resolve; submit / device when tracing) across routes and streams;
        ``watchdog_events`` surfaces
        the straggler watchdog's structured event log; ``telemetry``
        reports the tracer's state. Taken under the engine lock; cheap
        enough to poll."""
        with self._cv:
            lanes = {}
            for lane in LANES:
                row = dict(self.stats["lanes"][lane])
                row["queue_depth"] = self._lane_depth[lane]
                hist = self.metrics.merged("latency", lane=lane)
                row["p50_ms"] = None if hist.count == 0 \
                    else hist.quantile(0.50) * 1e3
                row["p95_ms"] = None if hist.count == 0 \
                    else hist.quantile(0.95) * 1e3
                lanes[lane] = row
            stages = {}
            for stage in ("submit", "queue", "assemble", "execute",
                          "device", "resolve"):
                hist = self.metrics.merged("stage", stage=stage)
                if hist.count:
                    stages[stage] = hist.snapshot()
            # Per-stream rows: the pool's own counters merged with the
            # engine's view of which dispatched buckets are still
            # unresolved on each stream. Lock order _cv -> pool lock is
            # the canonical direction; _stats_lock is a leaf and guards
            # the counters the streams mutate.
            streams = []
            peak = 0
            if self._pool is not None:
                per_stream: dict = {}
                for b in self._in_flight:
                    if b.stream is not None:
                        per_stream[b.stream] = per_stream.get(b.stream,
                                                              0) + 1
                streams = self._pool.snapshot()
                for row in streams:
                    row["in_flight"] = per_stream.get(row["stream"], 0)
                peak = self._pool.peak_concurrent
            with self._stats_lock:
                return {
                    "requests": self.stats["requests"],
                    "buckets": self.stats["buckets"],
                    "dispatches": self.stats["dispatches"],
                    "compiles": self.stats["compiles"],
                    "cache_hits": self.stats["cache_hits"],
                    "padded_slots": self.stats["padded_slots"],
                    "stragglers": self.stats["stragglers"],
                    "retries": self.stats["retries"],
                    "routes": dict(self.stats["routes"]),
                    "flush_triggers": dict(self.stats["flush_triggers"]),
                    "lanes": lanes,
                    "open_buckets": len(self._open_buckets),
                    "in_flight": len(self._in_flight),
                    "streams": streams,
                    "peak_concurrent_streams": peak,
                    "straggler_events": list(self._straggler_log),
                    "admission_policy": self._admission.policy.name,
                    "stages": stages,
                    # getattr: user watchdogs only owe observe() — a
                    # duck-typed one without snapshot() reports no events
                    # rather than breaking stats().
                    "watchdog_events": snap(limit=_STRAGGLER_EVENTS)
                    if (snap := getattr(self._watchdog, "snapshot",
                                        None)) is not None else [],
                    "telemetry": {"tracing": self.tracer.enabled,
                                  "spans": len(self.tracer),
                                  "dropped": self.tracer.dropped},
                }

    # -- convenience single-request API ------------------------------------
    def matpow(self, a: jax.Array, power: int) -> jax.Array:
        """Synchronous A^power through the engine (flushes the queue; in
        daemon mode kicks the scheduler and waits on the future)."""
        ticket = self.submit("matpow", a, power=power)
        if isinstance(ticket, MatFnFuture):
            self.kick(ticket.bucket_key)
            return ticket.result()
        return self.flush()[ticket]

    def expm(self, a: jax.Array) -> jax.Array:
        """Synchronous e^A through the engine (flushes the queue; in daemon
        mode kicks the scheduler and waits on the future)."""
        ticket = self.submit("expm", a)
        if isinstance(ticket, MatFnFuture):
            self.kick(ticket.bucket_key)
            return ticket.result()
        return self.flush()[ticket]

    def steady_state(self, p: jax.Array):
        """Synchronous stationary distribution through the engine —
        resolves with a :class:`~repro.core.markov.SteadyStateResult`
        (flushes the queue; in daemon mode kicks the scheduler and waits
        on the future). The engine does not validate stochasticity; gate
        with :func:`repro.core.markov.validate_stochastic` first."""
        ticket = self.submit("markov", p)
        if isinstance(ticket, MatFnFuture):
            self.kick(ticket.bucket_key)
            return ticket.result()
        return self.flush()[ticket]

    def evolve(self, dists: jax.Array, p: jax.Array,
               steps: int) -> jax.Array:
        """Synchronously evolve a (B, n) distribution stack ``steps``
        transitions under ``p`` through the engine's evolve route
        (flushes the queue; in daemon mode kicks the scheduler and waits
        on the future)."""
        ticket = self.submit("markov", p, power=steps, dists=dists)
        if isinstance(ticket, MatFnFuture):
            self.kick(ticket.bucket_key)
            return ticket.result()
        return self.flush()[ticket]
