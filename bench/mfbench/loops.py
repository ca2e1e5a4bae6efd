"""Load against a started ``MatFnEngine`` daemon, and the latency arithmetic.

Every request is timed from when it was due until its answer is ready on
the device. In an open loop it is due at its scheduled arrival, whether
or not the generator managed to submit it then; in a closed loop it is
due when its client submits it. The engine resolves a future as soon as
it has dispatched the bucket (the answer may still be in flight on the
device), so a :class:`Watcher` thread stamps each request when its
answer is ready: it takes futures as they resolve, in the order the
engine resolved them, and blocks on each answer. A slow answer therefore
delays no stamp but its own and those the device really ran after it.

Derived from ``repro.launch.matserve.run_open_loop``, which charged
latency from the return of ``submit`` and collected in submission order.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import threading
import time
from concurrent.futures import TimeoutError as FutureTimeoutError
from typing import Any, Callable, List, Optional

import numpy as np

clock = time.perf_counter


@dataclasses.dataclass(eq=False)
class Request:
    """One request's life as the client sees it."""
    index: int            # submission order over the whole run
    item: int             # pool index of its operand
    due: float            # harness clock
    in_window: bool = True
    submitted: float = math.nan
    done: float = math.nan
    ok: bool = False
    error: Optional[str] = None
    fut: Any = None
    digest: Any = None    # what the run keeps of the answer (see Watcher)
    on_done: Optional[Callable[["Request"], None]] = None

    @property
    def latency(self) -> float:
        return self.done - self.due


class Watcher:
    """Stamps each request when its answer is ready on the device.

    ``digest(request, value)`` decides what is kept of an answer once it
    is stamped (for example a sampled answer for the correctness check);
    everything else is dropped, so answers do not pile up on the device.
    """

    def __init__(self, digest: Callable[[Request, Any], Any]):
        import jax
        self._block = jax.block_until_ready
        self._digest = digest
        self._lock = threading.Condition()
        # One FIFO per traffic class: the engine dispatches the buckets of
        # one class in order, so its futures resolve in submission order.
        self._queues: dict = collections.defaultdict(collections.deque)
        self._open = 0
        self._stop = False
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._main,
                                        name="bench-watcher", daemon=True)

    def start(self) -> "Watcher":
        self._thread.start()
        return self

    def add(self, req: Request, group=None) -> None:
        with self._lock:
            self._queues[group].append(req)
            self._open += 1
            self._lock.notify_all()

    def drain(self, timeout: float) -> bool:
        """Wait until every added request is stamped; False on timeout."""
        end = clock() + timeout
        with self._lock:
            while self._open and self._error is None:
                left = end - clock()
                if left <= 0:
                    return False
                self._lock.wait(min(left, 0.1))
        if self._error is not None:
            raise RuntimeError("watcher failed") from self._error
        return True

    def stop(self) -> None:
        with self._lock:
            self._stop = True
            self._lock.notify_all()
        self._thread.join(timeout=30)

    def _take_resolved(self) -> List[Request]:
        """Resolved requests, in the order the engine resolved them."""
        ready = []
        with self._lock:
            for queue in self._queues.values():
                while queue and queue[0].fut.done():
                    ready.append(queue.popleft())
        ready.sort(key=lambda r: (r.fut.resolved_at, r.index))
        return ready

    def _stamp(self, req: Request) -> None:
        exc = req.fut.exception(timeout=0)
        if exc is None:
            value = req.fut.result(timeout=0)
            try:
                self._block(value)
            except Exception as err:      # a device error fails this answer
                exc = err
        req.done = clock()
        if exc is None:
            req.ok = True
            req.digest = self._digest(req, value)
        else:
            req.error = f"{type(exc).__name__}: {exc}"
        req.fut = None
        finish(req)

    def _main(self) -> None:
        try:
            while True:
                ready = self._take_resolved()
                for req in ready:
                    self._stamp(req)
                if ready:
                    with self._lock:
                        self._open -= len(ready)
                        self._lock.notify_all()
                    continue
                with self._lock:
                    heads = [q[0] for q in self._queues.values() if q]
                    if self._stop and not heads:
                        return
                    head = min(heads, key=lambda r: r.due) if heads else None
                    if head is None:
                        self._lock.wait(0.05)
                        continue
                # Sleep until the oldest outstanding request resolves. A
                # request of another traffic class can resolve first, so
                # the wait is short and the scan above runs again.
                try:
                    head.fut.exception(timeout=0.002)
                except FutureTimeoutError:
                    pass
        except BaseException as exc:      # surfaced by drain()
            with self._lock:
                self._error = exc
                self._lock.notify_all()


def finish(req: Request) -> None:
    if req.on_done is not None:
        req.on_done(req)


def send(req: Request, submit: Callable[[int], Any], watcher: Watcher,
         group) -> None:
    """Submit one request and hand it to the watcher; a refused submit
    is a failed request, stamped at once."""
    try:
        req.fut = submit(req.item)
    except Exception as exc:
        req.submitted = req.done = clock()
        req.error = f"{type(exc).__name__}: {exc}"
        finish(req)
        return
    req.submitted = clock()
    watcher.add(req, group)


def open_loop(submit: Callable[[int], Any], watcher: Watcher,
              schedule, t0: float, first_index: int = 0,
              in_window: bool = True, group=None) -> List[Request]:
    """Submit each burst of ``schedule`` (``[(offset_s, [item, ...]),
    ...]``) at ``t0 + offset_s``, whether or not earlier answers are
    back. Returns the requests in submission order."""
    reqs: List[Request] = []
    index = first_index
    for offset, items in schedule:
        due = t0 + offset
        while True:
            left = due - clock()
            if left <= 0:
                break
            time.sleep(min(left, 5e-4))
        for item in items:
            req = Request(index, item, due, in_window)
            send(req, submit, watcher, group)
            reqs.append(req)
            index += 1
    return reqs


class ClosedLoopClient:
    """One client that submits a round of requests and waits for all of
    its answers before it starts the next round."""

    def __init__(self, rounds, submit: Callable[[int], Any],
                 watcher: Watcher, group=None):
        self._rounds = rounds            # iterator of lists of pool items
        self._submit = submit
        self._watcher = watcher
        self._group = group
        self.requests: List[Request] = []
        self.error: Optional[BaseException] = None

    def run_round(self, in_window: bool, index_base: int,
                  timeout: float = 120.0) -> bool:
        """One round; False when its answers are not all back in time."""
        items = next(self._rounds)
        left = [len(items)]
        lock = threading.Lock()
        finished = threading.Event()

        def on_done(_req):
            with lock:
                left[0] -= 1
                if left[0] == 0:
                    finished.set()

        for item in items:
            req = Request(index_base + len(self.requests), item, clock(),
                          in_window, on_done=on_done)
            self.requests.append(req)
            send(req, self._submit, self._watcher, self._group)
        return finished.wait(timeout)

    def run(self, until: float, index_base: int) -> None:
        try:
            while clock() < until:
                if not self.run_round(True, index_base):
                    return
        except BaseException as exc:      # surfaced by the harness
            self.error = exc


@dataclasses.dataclass
class Summary:
    attempted: int
    failed: int
    completed_in_window: int
    p50_ms: Optional[float]
    p95_ms: Optional[float]
    answers_per_s: float
    late_p50_ms: float
    late_max_ms: float


def summarize(requests, t0: float, t1: float) -> Summary:
    """Latency over every request due in [t0, t1), failures counted, and
    the rate of answers completed inside the window over its length.

    ``late_*`` is how late the generator submitted requests after they
    were due (0 for a closed loop, where due is the submit itself)."""
    due = [r for r in requests if r.in_window and t0 <= r.due < t1]
    ok = np.array([r.latency for r in due if r.ok], np.float64) * 1e3
    late = np.array([r.submitted - r.due for r in due], np.float64) * 1e3
    done_in = sum(1 for r in requests if r.ok and t0 <= r.done <= t1)
    return Summary(
        attempted=len(due),
        failed=sum(1 for r in due if not r.ok),
        completed_in_window=done_in,
        p50_ms=float(np.percentile(ok, 50)) if ok.size else None,
        p95_ms=float(np.percentile(ok, 95)) if ok.size else None,
        answers_per_s=done_in / (t1 - t0),
        late_p50_ms=float(np.percentile(late, 50)) if late.size else 0.0,
        late_max_ms=float(late.max()) if late.size else 0.0)
