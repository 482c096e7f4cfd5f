"""The loops and the end-to-end arithmetic, against a stub ``submit``."""
import asyncio
import time

import numpy as np
import pytest

from chipbench import bench
from chipbench.bench import MISSED_S, Window, end_to_end, percentile

CLOSED = bench.load_file_module("loops", "closed.py")
POISSON = bench.load_file_module("loops", "poisson.py")


class Stub:
    """Answers every request ``delay`` seconds after it is submitted."""

    def __init__(self, delay):
        self.delay, self.outstanding, self.most = delay, 0, 0

    def submit(self, x):
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        self.outstanding += 1
        self.most = max(self.most, self.outstanding)

        def done():
            self.outstanding -= 1
            fut.set_result(x)
        loop.call_later(self.delay, done)
        return fut


def _window(stub, seconds, seed=0):
    xq = list(range(16))
    return Window(stub.submit, xq, np.arange(16),
                  np.random.default_rng(seed), seconds)


def test_closed_loop_keeps_exactly_n_outstanding():
    stub = Stub(0.004)
    win = _window(stub, 0.3)
    seen = []
    inner = stub.submit

    def submit(x):
        # requests sent (this one included) and not yet answered to a client
        seen.append(win.k - win.n)
        return inner(x)
    win._submit = submit
    asyncio.run(win.run(CLOSED, {"clients": 8}))
    assert stub.most == 8
    # the first 8 sends ramp up; every later send follows an answer
    assert seen[:8] == list(range(1, 9))
    assert all(n == 8 for n in seen[8:])
    rec = win.arrays()
    assert np.allclose(rec["start"], rec["sent"])  # timed from the send
    assert rec["ok"].all() and len(rec["ok"]) > 8 * 40


def test_poisson_times_from_the_due_time_and_reports_lateness():
    stub = Stub(0.001)
    win = _window(stub, 0.5, seed=3)

    async def go():
        async def stall():  # a flush that blocks the event loop for 50 ms
            await asyncio.sleep(0.2)
            time.sleep(0.05)
        await asyncio.gather(win.run(POISSON, {"rate_rps": 400}), stall())
    asyncio.run(go())
    rec = win.arrays()
    assert len(rec["ok"]) == 200  # round(rate * seconds), whatever the seed
    late = rec["sent"] - rec["start"]
    assert late.min() >= 0
    # requests due inside the stall were sent after it and carry its wait
    stalled = (rec["start"] > 0.21) & (rec["start"] < 0.24)
    assert stalled.any() and (late[stalled] > 0.005).all()
    lat = rec["end"] - rec["start"]
    assert (lat[stalled] >= late[stalled]).all()


def test_poisson_offers_the_same_work_for_every_seed():
    a = POISSON.schedule(np.random.default_rng(1), 1000.0, 2.0)
    b = POISSON.schedule(np.random.default_rng(2**31 + 5), 1000.0, 2.0)
    assert len(a) == len(b) == 2000 and not np.array_equal(a, b)
    assert 0 <= a[0] and a[-1] < 2.0 and (np.diff(a) >= 0).all()


def _rec(start, end, ok):
    n = len(start)
    return {"start": np.asarray(start, float), "end": np.asarray(end, float),
            "sent": np.asarray(start, float), "ok": np.asarray(ok, bool),
            "idx": np.zeros(n, int)}


def test_rps_is_all_answers_in_the_window_over_the_whole_window():
    # 30 answered in the window (one failed, one answered after it)
    start = list(np.linspace(0, 1.9, 32))
    end = [s + 0.01 for s in start]
    end[-1] = 2.5
    ok = [True] * 32
    ok[5] = False
    e2e = end_to_end(_rec(start, end, ok), 2.0)
    assert e2e["rps"] == pytest.approx(30 / 2.0)


def test_percentiles_are_over_all_requests_and_failures_miss():
    # two chunks with different medians: the median over all requests is
    # not the mean of the chunks' medians
    lat = [1.0] * 60 + [10.0] * 40
    start = list(np.linspace(0, 0.9, 100))
    e2e = end_to_end(_rec(start, [s + x for s, x in zip(start, lat)],
                          [True] * 100), 1.0)
    assert e2e["p50_ms"] == pytest.approx(1000.0)
    assert e2e["p90_ms"] == pytest.approx(10000.0)
    assert e2e["p99_ms"] == pytest.approx(10000.0)
    ok = [True] * 100
    ok[0] = ok[1] = False  # two failures: the 99th percentile is missed
    e2e = end_to_end(_rec(start, [s + x for s, x in zip(start, lat)], ok),
                     1.0)
    assert e2e["p99_ms"] == pytest.approx(MISSED_S * 1e3)
    assert percentile([1, 2, 3, 4], 50) == pytest.approx(2.5)


def test_an_answer_that_never_comes_counts_as_failed():
    class Never:
        def submit(self, x):
            return asyncio.get_running_loop().create_future()

    win = _window(Never(), 0.05)
    asyncio.run(win.run(POISSON, {"rate_rps": 200}, grace_s=0.05))
    rec = win.arrays()
    assert len(rec["ok"]) == 10 and not rec["ok"].any()
    assert end_to_end(rec, 0.05)["p50_ms"] == pytest.approx(MISSED_S * 1e3)
