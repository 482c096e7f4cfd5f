#!/usr/bin/env python3
"""Smoke run of the served int8 path on one TPU chip, through the Pallas route.

    python chip_smoke.py [--seed N]

One process, one chip. It builds the serving registry for the paper's three
models at their published widths (sine MLP, speech TinyConv on 49x40, person
MobileNetV1 a=0.25 on 96x96) with the Pallas kernels on, the way a server
would (``build_paper_registry(..., use_pallas=True)``), and then:

* checks that every warmed bucket executable holds Mosaic kernels
  (``tpu_custom_call`` in its HLO), so nothing fell back to XLA or to the
  Pallas interpreter;
* sends a few dozen concurrent single-sample requests to each model through
  ``ServingRegistry.infer`` with the default inline executor (no resilience
  layer, so a failing route cannot turn into a pass on another route);
* asserts every request completed, none was shed, no row was served off the
  primary route, and nothing compiled after warm-up;
* compares every served row with the reference ``Interpreter`` (run on the
  host CPU, independent of the chip) and with the ``use_pallas=False`` XLA
  route on the chip. Rows must agree exactly; an int8 output one step off is
  counted and reported, anything further fails the run.

The lines before the last are smoke facts, not benchmark numbers. The last
line is one JSON object naming the device. Any failure raises and exits
non-zero. Without a TPU the script exits non-zero before doing anything.

The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``.jax_cache/`` at the checkout root (``repro.compile_cache``).
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

MODELS = ("sine", "speech", "person")
MAX_BATCH = 16
N_REQUESTS = 40  # per model, all in flight at once


def fact(msg: str) -> None:
    print(f"smoke fact: {msg}", flush=True)


def require_tpu():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found: JAX's default device is "
                 f"{dev.platform!r} ({dev.device_kind}). This script runs "
                 f"only on a TPU chip; there is no CPU path.")
    return dev


def sample_inputs(reg, seed: int) -> dict:
    """N_REQUESTS quantized single samples per model, drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    draw = {
        "sine": lambda: rng.uniform(0, 2 * np.pi, (1, 1)),
        "speech": lambda: rng.normal(0, 1, (1, 49, 40, 1)),
        "person": lambda: rng.normal(0, 1, (1, 96, 96, 1)),
    }
    return {name: [reg.quantize_input(name, draw[name]())
                   for _ in range(N_REQUESTS)] for name in MODELS}


def check_mosaic(name: str, cm) -> None:
    buckets = cm.bucket_sizes()
    want = tuple(1 << i for i in range(MAX_BATCH.bit_length()))
    assert buckets == want, f"{name}: warmed buckets {buckets}, want {want}"
    assert cm.routes()[0] == "pallas", f"{name}: routes {cm.routes()}"
    for b in buckets:
        text = cm.cached_bucket(b).as_text()
        assert "tpu_custom_call" in text, \
            f"{name}: bucket {b} executable holds no Mosaic kernel"
    fact(f"{name}: {len(buckets)} bucket executables {buckets} all hold "
         f"tpu_custom_call; {len(cm.staged_pad_keys())} staged-pad "
         f"executables")


async def serve(reg, xq: dict) -> tuple:
    async with reg:
        jobs = [reg.infer(name, x) for name in MODELS for x in xq[name]]
        outs = await asyncio.gather(*jobs)
        snap = reg.snapshot()
    rows = {name: np.stack([np.asarray(o) for o in
                            outs[i * N_REQUESTS:(i + 1) * N_REQUESTS]])
            for i, name in enumerate(MODELS)}
    return rows, snap


def compare(name: str, label: str, served, want, out_op: str) -> None:
    want = np.asarray(want).reshape(served.shape)
    diff = np.abs(served.astype(np.int32) - want.astype(np.int32))
    exact = int(np.sum(diff.reshape(len(diff), -1).max(axis=1) == 0))
    one_step = int(np.sum(diff == 1))
    fact(f"{name} vs {label}: {exact}/{len(diff)} rows exact, {one_step} "
         f"int8 outputs one step off (output op {out_op})")
    assert diff.max() <= 1, (f"{name}: served rows differ from {label} by "
                             f"up to {diff.max()} int8 steps")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for the calibration data and the requests")
    args = ap.parse_args(argv)

    dev = require_tpu()
    import jax

    from repro import compile_cache
    from repro.core import CompiledModel, Interpreter
    from repro.kernels.ops import interpret_mode
    from repro.serve.aotcache import serialization_support
    from repro.serve.registry import build_paper_registry

    fact(f"device {dev.device_kind}, {len(jax.devices())} device(s)")
    fact(f"compile cache at {compile_cache.enable()}")
    if interpret_mode():
        sys.exit("chip_smoke: Pallas kernels would run in interpret mode")

    t0 = time.perf_counter()
    reg = build_paper_registry(MODELS, use_pallas=True, max_batch=MAX_BATCH,
                               seed=args.seed)
    fact(f"warm-up of {len(MODELS)} models took "
         f"{time.perf_counter() - t0:.1f} s (compiles included)")
    warm_compiles = {}
    for name in MODELS:
        cm = reg.model(name)
        check_mosaic(name, cm)
        warm_compiles[name] = cm.compile_events

    xq = sample_inputs(reg, args.seed + 1)
    rows, snap = asyncio.run(serve(reg, xq))
    for name in MODELS:
        s = snap[name]
        assert s["completed"] == N_REQUESTS, (name, s["completed"])
        assert s["rejected"] == s["preempted"] == 0, (name, s)
        assert s["failed"] == s["deadline_exceeded"] == 0, (name, s)
        assert s["degraded_rows"] == 0, (name, s["degraded_by_route"])
        cm = reg.model(name)
        assert cm.compile_events == warm_compiles[name], (
            f"{name}: compiled after warm-up: {cm.compile_log[-4:]}")
        fact(f"{name}: {s['completed']} requests served in {s['batches']} "
             f"flushes, 0 shed, 0 degraded, 0 compiles after warm-up")

    cpu = jax.devices("cpu")[0]
    for name in MODELS:
        cm = reg.model(name)
        out_op = cm.graph.ops[-1].op
        with jax.default_device(cpu):  # the reference never touches the chip
            interp = Interpreter(cm.graph)
            ref = np.stack([interp.invoke_q(x) for x in xq[name]])
        compare(name, "reference interpreter (host)", rows[name], ref, out_op)
        plain = CompiledModel(cm.graph, use_pallas=False).predict_q_many(
            np.stack(xq[name]), max_batch=MAX_BATCH)
        compare(name, "use_pallas=False route", rows[name], plain, out_op)

    ok, reason = serialization_support()
    fact(f"AOT executable serialization supported: {ok}"
         + (f" ({reason})" if reason else ""))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
