"""Closed loop: ``clients`` callers, each sending its next request as soon
as the answer to its last one arrives. Latency runs from the send.

Traffic keys: ``clients``.
"""
import asyncio


async def drive(win, params) -> None:
    async def client():
        while win.open():
            await win.request(None)

    await asyncio.gather(*(client() for _ in range(params["clients"])))
