"""Open loop with Poisson arrivals at a fixed rate. Latency runs from the
time each request was due, so a stall also delays the requests behind it.

Traffic keys: ``rate_rps``.

Every seed offers the same number of requests, ``round(rate_rps *
seconds)``, at times drawn uniformly over the window and sorted (a Poisson
process given its count): seeds change the order of the gaps, not the work.
The schedule is anchored to the clock (after ``benchmarks/bench_serve.py``):
when the event loop falls behind, every request already due is sent at once,
so the offered rate holds, and the lateness of each send is recorded.
"""
import asyncio

import numpy as np


def schedule(rng, rate_rps: float, seconds: float):
    n = int(round(rate_rps * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))


async def drive(win, params) -> None:
    pending = set()  # only the requests still outstanding are held
    for due in schedule(win.rng, params["rate_rps"], win.seconds):
        delay = win.t0 + due - win.now()
        if delay > 0:
            await asyncio.sleep(delay)
        task = asyncio.ensure_future(win.request(win.t0 + due))
        pending.add(task)
        task.add_done_callback(pending.discard)
    while pending:
        await asyncio.gather(*pending)
