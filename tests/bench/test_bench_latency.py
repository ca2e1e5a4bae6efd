"""The benchmark's latency arithmetic: every request is charged from
when it was due, failures count, and the rate is over the whole window."""

import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "bench"))

from mfbench import loops  # noqa: E402
from repro.serve.matfn import MatFnFuture  # noqa: E402


def _req(i, due, done, ok=True, in_window=True):
    r = loops.Request(i, 0, due, in_window, submitted=due, done=done, ok=ok)
    return r


def test_summary_charges_from_due_and_counts_every_request():
    reqs = [_req(i, i * 0.01, i * 0.01 + 0.002) for i in range(100)]
    base = loops.summarize(reqs, 0.0, 1.0)
    assert base.attempted == 100 and base.failed == 0
    assert base.p95_ms == pytest.approx(2.0)
    # A stall: the answers due in one tenth of the window come back late.
    for r in reqs[40:50]:
        r.done += 0.2
    stalled = loops.summarize(reqs, 0.0, 1.0)
    assert stalled.p50_ms == pytest.approx(2.0)
    assert stalled.p95_ms > 150.0
    # A failed request counts as attempted and failed, not as a latency.
    reqs[3].ok = False
    assert loops.summarize(reqs, 0.0, 1.0).failed == 1


def test_rate_is_completions_in_the_window_over_its_length():
    reqs = [_req(i, 0.1 * i, 0.1 * i + 0.05) for i in range(30)]
    warm = _req(99, -1.0, -0.5, in_window=False)
    s = loops.summarize(reqs + [warm], 0.0, 2.0)
    assert s.attempted == 20                 # due in [0, 2)
    assert s.completed_in_window == 20       # done in [0, 2]
    assert s.answers_per_s == pytest.approx(10.0)


class _FakeEngine:
    """Resolves each future ``service_s`` after the previous one, one
    request at a time, like a device running requests in order."""

    def __init__(self, service_s):
        self.service_s = service_s
        self._busy_until = 0.0
        self._lock = threading.Lock()
        self.timers = []

    def submit(self, item):
        fut = MatFnFuture()
        with self._lock:
            start = max(loops.clock(), self._busy_until)
            self._busy_until = start + self.service_s
            delay = self._busy_until - loops.clock()
        t = threading.Timer(delay, fut.set_result, args=(np.float32(item),))
        t.start()
        self.timers.append(t)
        return fut


def test_open_loop_charges_a_generator_stall_to_the_requests_behind_it():
    eng = _FakeEngine(service_s=0.001)
    watcher = loops.Watcher(lambda req, value: None).start()
    schedule = [(0.005 * i, [i]) for i in range(40)]
    real_submit = eng.submit
    calls = [0]

    def submit(item):
        calls[0] += 1
        if calls[0] == 20:
            time.sleep(0.1)                  # the generator stalls here
        return real_submit(item)

    try:
        t0 = loops.clock() + 0.01
        reqs = loops.open_loop(submit, watcher, schedule, t0)
        assert watcher.drain(10.0)
    finally:
        watcher.stop()
    s = loops.summarize(reqs, t0, t0 + 0.2)
    assert s.failed == 0 and s.attempted == 40
    # Requests due during the stall are charged from when they were due.
    assert max(r.latency for r in reqs[19:25]) > 0.07
    assert s.p95_ms > 50.0
    assert s.late_max_ms > 50.0
    assert min(r.latency for r in reqs[:15]) < 0.05


def test_closed_loop_round_waits_for_every_answer():
    eng = _FakeEngine(service_s=0.002)
    watcher = loops.Watcher(lambda req, value: float(value)).start()
    rounds = iter([[1, 2, 3, 4], [5, 6]])
    client = loops.ClosedLoopClient(rounds, eng.submit, watcher)
    try:
        assert client.run_round(True, 0)
        assert all(r.ok for r in client.requests)
        assert [r.digest for r in client.requests] == [1.0, 2.0, 3.0, 4.0]
        # Each answer is stamped when it is ready, in order.
        done = [r.done for r in client.requests]
        assert done == sorted(done)
    finally:
        watcher.stop()
