#!/usr/bin/env python3
"""Measure one cell of ``BENCHMARK.json`` on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one chip. The run builds only its cell's model through the
program's serving path (``ServingRegistry`` with the configuration's
settings), warms every bucket from the persistent compile cache, draws its
inputs from ``--seed``, drives ``ServingRegistry.infer`` with the cell's
traffic for ``--seconds``, and then checks a seeded sample of the answers
against the plain float32 reference.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces a
steady sub-window with JAX's profiler and reports the per-layer metrics.
The last line of standard output is one JSON object; the numbers compared
for ``correct`` are the last lines of standard error and the last key of
that object. Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero before measuring and prints no result.
"""
import time

T_START = time.perf_counter()  # set-up is timed from process start

import argparse  # noqa: E402
import asyncio  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

# JAX's persistent compilation cache, at a fixed path inside the checkout
# (the path is part of the cache key); the program reads this variable.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_LEAD, TRACE_SPAN = 0.3, 0.4  # traced sub-window, as window shares


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def require_chip(chips: int):
    """The TPU devices this run measures on; exits non-zero otherwise."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chipbench: no TPU: JAX's devices are {devs[0].platform!r} "
                 f"({devs[0].device_kind}); the benchmark measures only on "
                 f"the chip")
    if len(devs) < chips:
        sys.exit(f"chipbench: the cell needs {chips} chips, JAX finds "
                 f"{len(devs)}")
    return devs[:chips]


def peaks_for(kind: str) -> dict:
    from chipbench.bench import load_json

    table = load_json("peaks.json")
    if kind not in table:
        sys.exit(f"chipbench: no peaks for device kind {kind!r}; "
                 f"known: {sorted(table)}")
    return table[kind]


class Run:
    """What the per-layer metric readers (``metrics/*.py``) read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def roofline(self, cls: str):
        if self.trace is None or cls not in self.work:
            return None
        dev_s = self.trace["class_s"].get(cls, 0.0)
        ops, nbytes = self.work[cls]
        if dev_s <= 0 or ops <= 0:
            return None
        least = max(ops / self.peaks["int8_ops_per_s"],
                    nbytes / self.peaks["hbm_bytes_per_s"])
        return 100.0 * least / dev_s


def _annotator(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax

    from chipbench.trace import HOST_PREFIX
    return lambda name: jax.profiler.TraceAnnotation(HOST_PREFIX + name)


def _queue(tracer) -> tuple:
    h = tracer.hists["queue"]
    return h.sum_us, h.n


def _counters(m) -> dict:
    return {"batches": m.batches, "batched_rows": m.batched_rows,
            "bucket_rows": m.bucket_rows, "completed": m.completed}


async def warm_buckets(reg, name, xq, top: int) -> None:
    """Run every bucket executable once through the served path."""
    b = 1
    while b <= top:
        await asyncio.gather(*(reg.infer(name, xq[i % len(xq)])
                               for i in range(b)))
        b *= 2


def _work_totals(cell, shapes, counters) -> dict:
    """{kernel class: (ops, bytes)} of the true work the traced window ran:
    per-row work times rows, per-call bytes times flushes."""
    out = {}
    for cls, mod in cell.work_classes().items():
        ops = nbytes = 0
        for layer, x_shape, y_shape in shapes:
            if layer["op"] in mod.LAYERS:
                o, act_b, call_b = mod.work(layer, x_shape, y_shape)
                ops += o * counters["batched_rows"]
                nbytes += (act_b * counters["batched_rows"]
                           + call_b * counters["batches"])
        if ops:
            out[cls] = (ops, nbytes)
    return out


def main(argv=None, *, chip_check=require_chip, fault=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--benchmark", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    # the TPU runtime logs to a fixed path under /tmp unless told otherwise
    logs = os.path.join(_out_dir(), "tpu_logs")
    os.makedirs(logs, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", logs)
    from chipbench import bench
    from chipbench.bench import Cell, Window, end_to_end

    cell = Cell(bench.benchmark(args.benchmark), args.workload)
    devs = chip_check(cell.chips)
    import jax
    import numpy as np

    from repro import compile_cache
    from repro.obs.trace import Tracer

    from chipbench import check, model, trace
    from chipbench.reference import Reference

    dev = devs[0]
    peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else None
    log(f"compile cache at {compile_cache.enable()}")
    traced = bool(args.trace)
    annotate = _annotator(traced)
    cfg, name = cell.cfg, cell.cfg["name"]

    params = model.make_weights(cfg)
    tracer = Tracer() if traced else None

    def prepare(cm):
        if fault is not None:  # tests only: break the timed path underneath
            fault(cm)
        if traced:  # label the flushes in the trace's host spans
            staged = cm.staged_infer

            def flush(rows):
                with annotate("flush"):
                    return staged(rows)
            cm.staged_infer = flush

    reg, cm = bench.build_registry(cell, params, tracer=tracer,
                                   prepare=prepare)

    rng = np.random.default_rng(args.seed)
    xf = model.draw_inputs(cfg, rng, bench.POOL_ROWS)
    xq = [reg.quantize_input(name, x) for x in xf]
    order = rng.permutation(bench.POOL_ROWS)
    top = cfg["registry"]["max_batch"]
    compiles0 = cm.compile_events

    gc_pauses = []  # (generation, start, seconds) of the window's collections

    def gc_watch(phase, info, t=[0.0]):
        if phase == "start":
            t[0] = time.perf_counter()
        else:
            gc_pauses.append((info["generation"], t[0],
                              time.perf_counter() - t[0]))

    async def serve():
        async with reg:
            await warm_buckets(reg, name, xq, top)
            win = Window(lambda x: reg.submit(name, x), xq, order,
                         np.random.default_rng([args.seed, 1]), args.seconds,
                         annotate)
            met = reg.metrics(name)
            marks = {}
            loop = asyncio.get_running_loop()
            tdir = tempfile.mkdtemp(prefix="trace_", dir=_out_dir()) \
                if traced else None
            state = {}

            def mark_start():
                state["span"] = annotate(trace.WINDOW_SPAN)
                state["span"].__enter__()
                marks["c0"] = _counters(met)
                marks["q0"] = _queue(tracer)
                marks["h0"] = time.perf_counter() - win.t0

            def mark_stop():
                marks["c1"] = _counters(met)
                marks["q1"] = _queue(tracer)
                marks["h1"] = time.perf_counter() - win.t0
                state["span"].__exit__(None, None, None)

            # the profiler starts before the window and stops after it, so
            # neither call stalls the event loop inside the window
            stop_trace = None
            if traced:
                stop_trace = trace.record(tdir)
                await asyncio.sleep(1.0)  # let the profiler settle first
            gc.collect()  # set-up's garbage is not the window's to collect
            gc.callbacks.append(gc_watch)
            marks["setup_s"] = time.perf_counter() - T_START
            marks["compiles_warm"] = cm.compile_events
            if traced:
                loop.call_later(TRACE_LEAD * args.seconds, mark_start)
                loop.call_later((TRACE_LEAD + TRACE_SPAN) * args.seconds,
                                mark_stop)
            await win.run(cell.loop, cell.traffic)
            gc.callbacks.remove(gc_watch)
            if traced:
                stop_trace()
            marks["compiles_end"] = cm.compile_events
            marks["end"] = _counters(met)
            marks["t0"] = win.t0
        return win, marks, tdir

    win, marks, tdir = asyncio.run(serve())
    rec = win.arrays()
    # which op feeds which, for charging ops to kernel classes; every
    # bucket's executable is already compiled
    hlo = [cm.compile_batched(b).as_text() for b in cm.bucket_sizes()] \
        if traced else []
    stats = dev.memory_stats() or {}
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
    entry_bytes = int(np.prod(cm.exec_plan.entry_shape(cm.graph.inputs[0])))
    end = marks["end"]
    log(f"setup {marks['setup_s']:.3f} s (compiles before warm-up "
        f"{compiles0}, after {marks['compiles_warm']})")
    log(f"compiles inside the window: "
        f"{marks['compiles_end'] - marks['compiles_warm']}")
    for g in range(3):
        p = [s for gen, _, s in gc_pauses if gen == g]
        log(f"gc generation {g} in the window: {len(p)} collections, "
            f"{sum(p) * 1e3:.1f} ms in all, longest "
            f"{max(p, default=0) * 1e3:.1f} ms")
    log("gc collections over 10 ms at (s into the window, ms): " + ", ".join(
        f"({t - marks['t0']:.2f}, {s * 1e3:.0f})"
        for _, t, s in gc_pauses if s > 0.01))
    log("latency p50/p99 ms by second of the window: " + _by_second(
        rec, args.seconds))
    log("window: " + ", ".join(f"{k} {v:.4f}" for k, v in
                               end_to_end(rec, args.seconds).items()))
    log(f"device memory peak {mem_peak} bytes "
        f"(in use now {stats.get('bytes_in_use')})")
    flushes = max(1, end["batches"])
    log(f"staged per flush: {entry_bytes * end['bucket_rows'] / flushes:.0f} "
        f"bytes mean ({entry_bytes} bytes per bucket row, {end['batches']} "
        f"flushes, {end['batched_rows'] / flushes:.2f} rows per flush)")

    # the served answers, then the program's state freed before the
    # reference runs on the host
    answered = rec["ok"] & (rec["start"] < args.seconds)
    pick = check.sample(np.random.default_rng([args.seed, 2]), answered)
    served = win.answers(pick) if len(pick) else np.zeros((0, 1))
    idx = rec["idx"][pick]
    shapes = model.layer_shapes(cfg)
    del reg, cm, win
    gc.collect()

    ref = Reference(cfg, params).forward(xf[idx]) if len(pick) else None
    nums = check.numbers(check.probabilities(cfg, served), ref) \
        if len(pick) else {}
    correct, checks = check.judge(cfg, nums)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem_peak)}
    result = {"correct": correct,
              "attempted": int(np.sum(rec["start"] < args.seconds)),
              "failed": int(np.sum(~rec["ok"] & (rec["start"] < args.seconds)))}
    if not traced:
        e2e = end_to_end(rec, args.seconds)
        e2e["setup_s"] = marks["setup_s"]
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {k: {"value": e2e[k], "unit": units[k]}
                             for k in units}
    else:
        c0, c1 = marks["c0"], marks["c1"]
        counters = {k: c1[k] - c0[k] for k in c0}
        tr = trace.load(tdir)
        tr["inputs"] = trace.hlo_inputs(hlo)
        shutil.rmtree(tdir, ignore_errors=True)
        log("trace device planes: " + ", ".join(
            f"{p} {len(evs)} ops" for p, evs in tr["device"].items())
            + f"; {len(tr['host'])} host spans")
        lo, hi = _traced_span(tr)
        classes = {c: m.KERNELS for c, m in cell.work_classes().items()}
        red = trace.reduce(tr, lo, hi, classes)
        (s0, n0), (s1, n1) = marks["q0"], marks["q1"]
        run = Run(trace=red, counters=counters, rec=rec,
                  seconds=args.seconds, host_span=(marks["h0"], marks["h1"]),
                  queue_wait_us=(s1 - s0) / (n1 - n0) if n1 > n0 else None,
                  entry_bytes=entry_bytes, peaks=peaks,
                  work=_work_totals(cell, shapes, counters),
                  model_ops_per_row=model.ops_per_row(cfg))
        metrics = {}
        for m in cell.per_layer:
            v = bench.load_file_module("metrics", m["name"] + ".py").read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        if red is not None:
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
            log(f"traced {red['window_s']:.3f} s: busy {red['busy_s']:.4f} s, "
                f"kernel classes {red['class_s']}, counters {counters}")
            for c, ops in red["class_ops"].items():
                log(f"class {c}: {len(ops)} ops: " + ", ".join(
                    f"{op} {t * 1e3:.2f} ms" for op, t in ops[:30]))
    result["device"] = device
    result["checks"] = checks
    for k, v in checks.items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def _by_second(rec, seconds) -> str:
    from chipbench.bench import percentile

    out = []
    for s in range(int(seconds + 0.999)):
        sel = (rec["start"] >= s) & (rec["start"] < min(s + 1, seconds))
        lat = (rec["end"] - rec["start"])[sel & rec["ok"]] * 1e3
        out.append(f"{percentile(lat, 50):.0f}/{percentile(lat, 99):.0f}"
                   if len(lat) else "-")
    return " ".join(out)


def _traced_span(tr) -> tuple:
    from chipbench.trace import WINDOW_SPAN

    spans = [(a, b) for n, a, b in tr["host"] if n == WINDOW_SPAN]
    if not spans:
        raise RuntimeError("the trace holds no 'traced' host span")
    return spans[0]


def _out_dir() -> str:
    d = os.path.join(ROOT, ".chipbench_out")
    os.makedirs(d, exist_ok=True)
    return d


if __name__ == "__main__":
    sys.exit(main())
