"""Cold-start bench — time-to-first-SLO-compliant-request, cold vs warm.

MicroFlow moves every decidable cost to compile time; the persistent AOT
executable cache (``repro.serve.aotcache``) moves the *compile* itself
out of the boot path. This bench measures what that buys a replica: the
wall time from "process has a quantized graph" to "first batched request
answered", booted two ways against the same cache directory:

* **cold** — empty cache: ``warmup_batched(cache=...)`` XLA-compiles
  every bucket executable + staged pad, serializes them, writes the
  manifest, then serves the first request;
* **warm** — second boot, same directory: the manifest verifies
  (fingerprint + coverage + digests), every executable deserializes, and
  the first request is served with **zero** XLA compiles — asserted on
  the engine's ``compile_events`` counter, the runtime twin of the
  no-retrace auditor's static proof.

Records (the ``coldstart`` family in ``benchmarks.run`` — ``--only
coldstart`` refreshes exactly these; gated by ``tools/check_bench.py``
gate 10):

* ``serve/sine_coldstart_cold_us`` / ``serve/sine_coldstart_warm_us``
* ``serve/person_coldstart_cold_us`` / ``serve/person_coldstart_warm_us``
* ``serve/sine_coldstart_warm_vs_cold`` — cold/warm boot ratio; the
  cache's reason to exist, gated >= 2.0.

Cold-start records carry no tracer: boots happen before serving, so the
``stage_breakdown`` is the explicit zeros dict (the established
non-request-path precedent). On backends whose executables cannot be
serialized (probed by ``aotcache.serialization_support``) every record
degrades to a ``median_us: null`` skip entry carrying the probe's reason
— same contract as the ``*_noninterpret`` lanes — so the suite stays
green everywhere.

``--cache-dir`` pins the cache root (default: a fresh temp dir, removed
afterwards); ``--manifest-out`` copies the stored manifests next to
``results/audit.json`` for CI artifact upload.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import tempfile
import time

import numpy as np

from repro import compile_cache
from repro.configs.paper_models import PAPER_MODELS
from repro.core.engine import CompiledModel
from repro.core.quantize import quantize_graph
from repro.serve.aotcache import AotCache, serialization_support

from .common import csv_line

MODELS = ("sine", "person")
_GENS = {
    "sine": lambda rng, n: rng.uniform(0, 2 * np.pi, (n, 1)).astype("f"),
    "person": lambda rng, n: rng.normal(0, 1, (n, 96, 96, 1)).astype("f"),
}
_ZERO_BD = {"queue_wait_us": 0.0, "pad_us": 0.0, "device_us": 0.0,
            "retry_us": 0.0}


def _quantized(name: str, calib_samples: int = 8, seed: int = 0):
    g = PAPER_MODELS[name](batch=1)
    rng = np.random.default_rng(seed)
    rep = [_GENS[name](rng, 1) for _ in range(calib_samples)]
    return quantize_graph(g, rep)


def _boot_us(qg, cache: AotCache, max_batch: int) -> tuple:
    """One replica boot: fresh CompiledModel over the (already
    quantized) graph, cache-aware warm-up, then the first batched
    request. Returns (elapsed_us, model) — the model so callers can
    assert on its compile/cache counters."""
    t = qg.tensor(qg.inputs[0])
    x = np.zeros((1,) + tuple(t.shape), np.dtype(t.dtype))
    t0 = time.perf_counter()
    cm = CompiledModel(qg)
    cm.warmup_batched(max_batch, cache=cache)
    np.asarray(cm.predict_q(x))  # first SLO-relevant request, synced
    return (time.perf_counter() - t0) * 1e6, cm


def _skip(lines: list, reason: str) -> None:
    msg = f"skipped: backend cannot serialize executables ({reason})"
    for name in MODELS:
        for phase in ("cold", "warm"):
            lines.append(csv_line(f"serve/{name}_coldstart_{phase}_us",
                                  None, msg, stage_breakdown=dict(_ZERO_BD)))
    lines.append(csv_line("serve/sine_coldstart_warm_vs_cold", None, msg,
                          stage_breakdown=dict(_ZERO_BD)))


def main(fast: bool = False, cache_dir=None, manifest_out=None,
         lines=None) -> list:
    lines = [] if lines is None else lines
    ok, reason = serialization_support()
    if not ok:
        _skip(lines, reason)
        return lines

    max_batch = 4 if fast else 8
    root = cache_dir or tempfile.mkdtemp(prefix="aotcache-bench-")
    manifests = {}
    try:
        ratios = {}
        for name in MODELS:
            qg = _quantized(name)
            cache = AotCache(os.path.join(root, name))
            # JAX's persistent cache would serve the cold boot's compiles
            # from an earlier run; the cold phase must really compile.
            with compile_cache.disabled():
                cold_us, cold_cm = _boot_us(qg, cache, max_batch)
            assert cold_cm.compile_events > 0, \
                f"{name}: cold boot compiled nothing — stale cache dir?"
            warm_us, warm_cm = _boot_us(qg, cache, max_batch)
            # The acceptance claim, asserted where the timing is taken:
            # a warm boot from a populated cache performs ZERO XLA
            # compiles end to end (warm-up AND first request).
            assert warm_cm.compile_events == 0, (
                f"{name}: warm boot compiled "
                f"{warm_cm.compile_events}x: {warm_cm.compile_log}")
            assert warm_cm.last_cache_result.hit, \
                f"{name}: warm boot missed: {warm_cm.last_cache_result}"
            ratios[name] = cold_us / warm_us
            fp = warm_cm.last_cache_result.fingerprint
            man = cache.manifest(fp)
            if man is not None:
                manifests[name] = man
            lines.append(csv_line(
                f"serve/{name}_coldstart_cold_us", cold_us,
                f"boot+first-request, empty cache -> compile+store "
                f"({cold_cm.compile_events} compiles, max_batch="
                f"{max_batch})", stage_breakdown=dict(_ZERO_BD)))
            lines.append(csv_line(
                f"serve/{name}_coldstart_warm_us", warm_us,
                f"boot+first-request, verified cache hit -> 0 compiles, "
                f"{warm_cm.cache_events.get('hit', 0)} executables "
                f"loaded", stage_breakdown=dict(_ZERO_BD)))
        lines.append(csv_line(
            "serve/sine_coldstart_warm_vs_cold", None,
            f"cold boot / warm boot wall ratio (gate >= 2.0); "
            f"person ratio {ratios.get('person', 0):.1f}x",
            ratio=ratios["sine"], stage_breakdown=dict(_ZERO_BD)))
        if manifest_out:
            os.makedirs(os.path.dirname(manifest_out) or ".", exist_ok=True)
            with open(manifest_out, "w") as fh:
                json.dump(manifests, fh, indent=1, sort_keys=True)
            print(f"# cache manifests -> {manifest_out}")
    finally:
        if cache_dir is None:
            shutil.rmtree(root, ignore_errors=True)
    return lines


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent cache root (default: fresh temp dir)")
    ap.add_argument("--manifest-out", default=None,
                    help="write the stored cache manifests (JSON) here, "
                         "e.g. results/cache_manifest.json for CI upload")
    a = ap.parse_args()
    main(fast=a.fast, cache_dir=a.cache_dir, manifest_out=a.manifest_out)
