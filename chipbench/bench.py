"""The harness's pieces: finding a cell's files by name, building its model
through the program's serving path, and driving one measured window.

Everything that belongs to one configuration, traffic mix, loop kind,
per-layer metric or kernel class sits in a file of its own, found by name:

* ``configs/<config>.json``   sizes, layers, inputs, registry settings
* ``traffic/<traffic>.json``  ``{"loop": <kind>, ...parameters}``
* ``loops/<kind>.py``         ``async drive(window, traffic)``
* ``metrics/<metric>.py``     ``read(run) -> float | None``
* ``work/<class>.py``         true operations and bytes of a kernel class
* ``ops/<layer kind>.py``     a layer: graph building and plain reference
"""
import asyncio
import contextlib
import importlib.util
import json
import math
import os
import time

import numpy as np

from chipbench import model

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
POOL_ROWS = 1024  # distinct input rows per run; requests cycle through them
# latency charged to a failed or shed request: past any limit, yet a number
# that every JSON reader takes
MISSED_S = 3600.0


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_file_module(*parts):
    """Import ``chipbench/<parts>`` by path (metric names hold dots)."""
    path = os.path.join(HERE, *parts)
    name = "chipbench_" + "_".join(parts).replace(".", "_").replace("/", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(path=None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, bench: dict, name: str):
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise SystemExit(f"unknown workload {name!r}; "
                             f"known: {sorted(by_name)}")
        self.workload = by_name[name]
        self.name = name
        self.chips = self.workload["chips"]
        self.cfg = load_json("configs", self.workload["config"] + ".json")
        model.layer_shapes(self.cfg)  # refuses a malformed layer graph
        self.traffic = load_json("traffic", self.workload["traffic"] + ".json")
        self.loop = load_file_module("loops", self.traffic["loop"] + ".py")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def work_classes(self) -> dict:
        """{kernel class: module} of every ``work/<class>.py``. A layer kind
        belongs to one class at most, so no work is counted twice."""
        out, owner = {}, {}
        for fn in sorted(os.listdir(os.path.join(HERE, "work"))):
            if fn.endswith(".py") and not fn.startswith("_"):
                mod = out[fn[:-3]] = load_file_module("work", fn)
                for kind in mod.LAYERS:
                    if kind in owner:
                        raise ValueError(
                            f"layer kind {kind!r} is claimed by work/"
                            f"{owner[kind]}.py and work/{fn}")
                    owner[kind] = fn[:-3]
        return out


def build_registry(cell: Cell, params, *, tracer=None, prepare=None):
    """The cell's model, quantized and served through ``ServingRegistry``
    on the Pallas route with the configuration's settings. ``prepare(cm)``
    runs on the ``CompiledModel`` before it is registered."""
    from repro.core import CompiledModel
    from repro.serve.registry import ServingRegistry

    r = cell.cfg["registry"]
    qg = model.build_int8(cell.cfg, params)
    cm = CompiledModel(qg, use_pallas=True)
    reg = ServingRegistry(max_batch=r["max_batch"],
                          max_delay_s=r["max_delay_s"],
                          max_queue=r["max_queue"], tracer=tracer)
    if prepare is not None:
        prepare(cm)
    reg.register(cell.cfg["name"], cm)
    return reg, cm


class Window:
    """One measured window: the loop calls :meth:`request`; every request
    is recorded with its start (due time in an open loop, send time in a
    closed one), send time, end time, outcome and answer."""

    def __init__(self, submit, xq, order, rng, seconds, annotate=None):
        self._submit = submit
        self.xq, self.order, self.rng = xq, order, rng
        self.seconds = float(seconds)
        self.now = time.perf_counter
        self.t0 = None
        self._annotate = annotate or (lambda name: contextlib.nullcontext())
        self.k = 0  # requests sent
        self.n = 0  # requests recorded
        # records live in preallocated arrays, answers copied into one
        # int8 array: the window keeps no object per request, so it adds
        # nothing for the garbage collector to walk
        self._rec = np.zeros((4096, 4))  # start, sent, end, ok
        self._idx = np.zeros(4096, np.int64)
        self._out = None

    def _record(self, idx, start, sent, ok, y) -> None:
        n = self.n
        if n == len(self._idx):
            self._rec = np.concatenate([self._rec, np.zeros_like(self._rec)])
            self._idx = np.concatenate([self._idx, np.zeros_like(self._idx)])
            if self._out is not None:
                self._out = np.concatenate([self._out,
                                            np.zeros_like(self._out)])
        if ok:
            y = np.asarray(y).reshape(-1)
            if self._out is None:
                self._out = np.zeros((len(self._idx), y.size), y.dtype)
            self._out[n] = y
        self._rec[n] = (start, sent, self.now(), ok)
        self._idx[n] = idx
        self.n = n + 1

    def answers(self, which) -> np.ndarray:
        """The answers of the recorded requests ``which``."""
        return self._out[which]

    def open(self) -> bool:
        return self.now() < self.t0 + self.seconds

    async def request(self, due) -> None:
        k = self.k
        self.k += 1
        idx = int(self.order[k % len(self.order)])
        t_send = self.now()
        y, ok = None, False
        try:
            with self._annotate("submit"):
                fut = self._submit(self.xq[idx])
            y = await fut
            ok = True
        except Exception:  # shed or failed: recorded, never answered
            pass
        finally:  # also when the answer never came before the grace ran out
            self._record(idx, t_send if due is None else due, t_send, ok, y)

    async def run(self, loop_module, traffic, grace_s: float = 60.0) -> None:
        """Drive the loop for the window, then wait at most ``grace_s`` for
        the answers still due; one that never comes counts as failed."""
        self.t0 = self.now()
        try:
            await asyncio.wait_for(loop_module.drive(self, traffic),
                                   self.seconds + grace_s)
        except asyncio.TimeoutError:
            pass

    def arrays(self) -> dict:
        """The records, with times in seconds from the window's start."""
        r = self._rec[:self.n]
        return {"idx": self._idx[:self.n].copy(),
                "start": r[:, 0] - self.t0, "sent": r[:, 1] - self.t0,
                "end": r[:, 2] - self.t0, "ok": r[:, 3].astype(bool)}


def end_to_end(rec: dict, seconds: float) -> dict:
    """rps, p50_ms, p90_ms and p99_ms of one window's request records.

    rps counts every request answered inside the window over the whole
    window. The percentiles are over every request that started in the
    window, one sample each; a failed or shed request counts as missing
    any limit (``MISSED_S``)."""
    inside = rec["start"] < seconds
    lat = np.where(rec["ok"], rec["end"] - rec["start"], MISSED_S)[inside]
    answered = int(np.sum(rec["ok"] & (rec["end"] < seconds)))
    return {"rps": answered / seconds,
            "p50_ms": percentile(lat, 50) * 1e3,
            "p90_ms": percentile(lat, 90) * 1e3,
            "p99_ms": percentile(lat, 99) * 1e3}


def percentile(values, q: float) -> float:
    """The ``q``-th percentile of all ``values``, linear between ranks."""
    v = np.sort(np.asarray(values, np.float64))
    if len(v) == 0:
        return math.nan
    pos = (len(v) - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))
