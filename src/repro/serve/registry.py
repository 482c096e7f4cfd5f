"""Multi-model serving registry for the compiled TinyML engine.

One process serves several compiled models (the paper's sine / speech /
person trio by default), each behind its own
:class:`repro.serve.scheduler.MicroBatcher`:

* **Warm-up compilation** — ``register`` builds the ``CompiledModel`` and
  AOT-compiles the batch-1 executable plus every power-of-two bucket up to
  the model's ``max_batch`` — every bucket lowered from the model's single
  ``ExecutionPlan``, layout plan included, plus the staged entry pads
  (fused bucket zero-fill + lane pad) for every batch size below it — so
  the first request is as fast as the millionth (all compilation ahead of
  serving, the MicroFlow discipline applied to the fleet).
* **Shared dispatch stage** — the registry can hand every batcher one
  :class:`repro.serve.executor.InferenceExecutor`. With the default
  ``InlineExecutor`` flushes run on the event loop (deterministic); with a
  shared ``ThreadPoolExecutorBackend`` flushes from *all* models
  interleave on one worker pool, so one model's device call no longer
  blocks another model's arrival processing. The registry owns the
  executor's lifecycle: ``stop()`` closes it after the batchers drain.
* **Admission control** — ``infer``/``submit`` reject unknown models
  (``KeyError``) and route each request through its model's priority
  classes: at capacity the batcher sheds by priority (lowest-priority
  pending request evicted with ``PreemptedError``) or refuses the
  newcomer with :class:`QueueFullError`. Together with the engine's
  static buffers and the joint ``pending + in_flight`` bound this keeps
  resident memory flat under overload.
* **Metrics** — per-model :class:`repro.serve.metrics.ModelMetrics`
  snapshots (p50/p95/p99 latency, throughput, batch occupancy, per-class
  SLO attainment) via :meth:`snapshot`.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.core import CompiledModel
from .executor import InferenceExecutor  # noqa: F401  (re-export)
from .metrics import ModelMetrics
from .scheduler import (Clock, ClassPolicy, MicroBatcher,  # noqa: F401
                        PreemptedError, QueueFullError)


@dataclasses.dataclass
class _Entry:
    name: str
    model: CompiledModel
    batcher: MicroBatcher


class ServingRegistry:
    """Named compiled models, each behind a dynamic micro-batcher.

    ``executor`` (optional) is shared by every registered model's batcher
    and closed by :meth:`stop`; ``executor_workers`` (optional) builds a
    shared ``ThreadPoolExecutorBackend`` of that width when no explicit
    ``executor`` is given (the ``REPRO_EXECUTOR_WORKERS`` env var sets the
    default width when neither is passed); ``classes`` (optional ``{name:
    ClassPolicy}``) is the default priority-class table each batcher
    starts from — executor and classes can be overridden per model in
    :meth:`register`.
    """

    def __init__(self, *, clock: Optional[Clock] = None, max_batch: int = 32,
                 max_delay_s: float = 0.002, max_queue: int = 256,
                 executor: Optional[InferenceExecutor] = None,
                 executor_workers: Optional[int] = None,
                 classes: Optional[dict] = None, tracer=None,
                 cache=None, cache_dir: Optional[str] = None,
                 audit_path: Optional[str] = None):
        self.clock = clock or Clock()
        if executor is None and executor_workers is not None:
            # convenience: size the shared off-loop pool without importing
            # the backend (the env default REPRO_EXECUTOR_WORKERS applies
            # when neither is given and an explicit backend is built)
            from .executor import ThreadPoolExecutorBackend
            executor = ThreadPoolExecutorBackend(max_workers=executor_workers)
        self.executor = executor
        # one repro.obs.Tracer shared by every batcher (None = tracing off)
        self.tracer = tracer
        if cache is None and cache_dir is not None:
            # convenience mirror of executor_workers: a directory is
            # enough to opt the whole registry into persistent AOT boots
            from .aotcache import AotCache
            cache = AotCache(cache_dir, audit_path=audit_path)
        self.cache = cache
        self._defaults = dict(max_batch=max_batch, max_delay_s=max_delay_s,
                              max_queue=max_queue, classes=classes,
                              tracer=tracer, cache=cache)
        self._entries: dict = {}
        self._started = False
        self._stopped = False

    # -- registration / lifecycle ----------------------------------------
    def register(self, name: str, model: CompiledModel, *,
                 warmup: bool = True, **overrides) -> CompiledModel:
        """Admit ``model`` (an int8 ``CompiledModel``) under ``name``.
        ``overrides`` replace the registry-level batcher defaults
        (``max_batch`` / ``max_delay_s`` / ``max_queue`` / ``classes`` /
        ``executor`` / ``tracer``) for this model."""
        if name in self._entries:
            raise ValueError(f"model {name!r} already registered")
        kw = {**self._defaults, "executor": self.executor, **overrides}
        batcher = MicroBatcher.for_model(
            model, warmup=warmup, name=name, clock=self.clock,
            metrics=ModelMetrics(now=self.clock.now()), **kw)
        self._entries[name] = _Entry(name, model, batcher)
        if self._started:  # late registration joins a running registry
            batcher.start()
        return model

    def models(self) -> tuple:
        return tuple(self._entries)

    def model(self, name: str) -> CompiledModel:
        """The compiled model served under ``name``."""
        return self._entry(name).model

    def __contains__(self, name: str) -> bool:
        return name in self._entries

    def start(self) -> "ServingRegistry":
        if self._stopped:
            raise RuntimeError("registry is stopped (stop() is terminal); "
                               "build a new ServingRegistry")
        for e in self._entries.values():
            e.batcher.start()
        self._started = True
        return self

    @property
    def stopped(self) -> bool:
        """Whether :meth:`stop` has run (stop is terminal and
        idempotent)."""
        return self._stopped

    async def stop(self, drain: bool = True) -> None:
        """Terminal: drains (or cancels) every batcher, closes every
        executor handed to the registry (the registry-level one AND any
        per-model ``register(..., executor=...)`` override — handing an
        executor to the registry transfers ownership), and shuts the
        registry down for good — serving again means building a new
        registry (warm-ups are per-``CompiledModel``, so the models
        themselves can be re-registered cheaply).

        Idempotent: a second stop (e.g. ``__aexit__`` after an explicit
        ``stop()``) returns immediately — batchers are not re-closed and
        no metric is counted twice."""
        if self._stopped:
            return
        self._stopped = True
        for e in self._entries.values():
            await e.batcher.close(drain=drain)
        owned = {id(self.executor): self.executor} \
            if self.executor is not None else {}
        for e in self._entries.values():  # per-model overrides included;
            owned[id(e.batcher.executor)] = e.batcher.executor  # close()
        for ex in owned.values():         # is idempotent and a no-op for
            ex.close()                    # InlineExecutor
        self._started = False

    async def __aenter__(self):
        return self.start()

    async def __aexit__(self, *exc):
        await self.stop()

    # -- serving ----------------------------------------------------------
    def _entry(self, name: str) -> _Entry:
        try:
            return self._entries[name]
        except KeyError:
            raise KeyError(f"unknown model {name!r}; "
                           f"registered: {sorted(self._entries)}") from None

    def submit(self, name: str, x, cls: str = "default",
               deadline_s: Optional[float] = None,
               wall_deadline_s: Optional[float] = None):
        """Admission-controlled enqueue under priority class ``cls``;
        returns the request's future. Raises ``KeyError`` for
        unregistered models or unknown classes, ``QueueFullError`` when
        the model's bounded queue sheds the request (a lower-priority
        pending request may be preempted in its favor instead).
        ``wall_deadline_s`` caps the request's end-to-end wall time
        (defaults to the class's ``slo_s``): still pending past it, the
        request is expired with ``DeadlineExceededError`` instead of
        dispatched."""
        if not self._started:
            raise RuntimeError("registry not started (use `async with` "
                               "or call start())")
        return self._entry(name).batcher.submit(
            x, cls=cls, deadline_s=deadline_s,
            wall_deadline_s=wall_deadline_s)

    async def infer(self, name: str, x, cls: str = "default",
                    deadline_s: Optional[float] = None,
                    wall_deadline_s: Optional[float] = None):
        return await self.submit(name, x, cls=cls, deadline_s=deadline_s,
                                 wall_deadline_s=wall_deadline_s)

    # -- dtype helpers (requests travel in graph dtype) --------------------
    def quantize_input(self, name: str, x):
        """Float sample -> graph-dtype sample for ``submit``/``infer``."""
        g = self._entry(name).model.graph
        t = g.tensor(g.inputs[0])
        x = np.asarray(x, np.float32).reshape(t.shape)
        return np.asarray(t.qparams.quantize(x)) if t.dtype == "int8" else x

    def dequantize_output(self, name: str, y):
        g = self._entry(name).model.graph
        t = g.tensor(g.outputs[0])
        y = np.asarray(y)
        return (t.qparams.dequantize(y) if t.dtype == "int8"
                else y.astype(np.float32))

    # -- observability -----------------------------------------------------
    def metrics(self, name: str) -> ModelMetrics:
        return self._entry(name).batcher.metrics

    def snapshot(self) -> dict:
        """{model: metrics snapshot} for every registered model."""
        now = self.clock.now()
        return {e.name: e.batcher.metrics.snapshot(now)
                for e in self._entries.values()}

    def engines(self) -> dict:
        """Per-model compile/cache accounting straight off the engines:
        ``compile_events`` (real XLA compiles — zero after a warm cache
        boot), the typed ``compile_log`` tail, and the hit/miss/store
        ``cache_events`` split. Duck-typed stand-ins without the counters
        report empty."""
        out = {}
        for e in self._entries.values():
            m = e.model
            out[e.name] = {
                "compile_events": getattr(m, "compile_events", 0),
                "cache_events": dict(getattr(m, "cache_events", {}) or {}),
                "compile_log": list(getattr(m, "compile_log", ()) or ())[-32:],
            }
        return out

    def cache_status(self) -> Optional[dict]:
        """The registry-level cache's counters plus each model's boot
        outcome (``None`` when no cache is configured)."""
        if self.cache is None:
            return None
        status = dict(self.cache.stats())
        boots = {}
        for e in self._entries.values():
            res = getattr(e.model, "last_cache_result", None)
            boots[e.name] = res.to_dict() if res is not None else None
        status["boots"] = boots
        return status

    def openmetrics(self) -> str:
        """OpenMetrics text exposition of every model's metrics (plus the
        per-stage latency histograms when a tracer is installed) — ready
        to serve from a scrape endpoint."""
        from repro.obs.export import openmetrics
        return openmetrics(self.snapshot(), tracer=self.tracer,
                           engines=self.engines(),
                           cache=self.cache_status())

    def telemetry(self) -> dict:
        """Structured JSON snapshot unifying metrics, trace histograms,
        the flight recorder's status, and the engines' compile/cache
        accounting (``repro.obs.export``)."""
        from repro.obs.export import json_snapshot
        flight = self.tracer.flight if self.tracer is not None else None
        return json_snapshot(self.snapshot(), tracer=self.tracer,
                             flight=flight, engines=self.engines(),
                             cache=self.cache_status())


def build_paper_registry(names=("sine", "speech", "person"), *,
                         calib_samples: int = 8, seed: int = 0,
                         use_pallas: bool = False, layout_plan: bool = True,
                         **registry_kw) -> ServingRegistry:
    """Registry serving the paper's models (Table 3), quantized with
    calibrated-random representative data exactly as the benchmarks do.

    ``use_pallas``/``layout_plan`` select the engine route every served
    bucket lowers through (see ``repro.core.engine.ExecutionPlan``): with
    ``use_pallas=True`` the warm-up AOT-compiles layout-planned bucket
    executables — activations stay lane-padded across the whole batched
    graph — while ``layout_plan=False`` keeps the per-call pad/slice route
    for A/B comparison (``benchmarks.bench_serve`` records both).
    ``registry_kw`` reaches :class:`ServingRegistry` — including
    ``executor`` (shared off-loop dispatch) and ``classes`` (priority
    table)."""
    from repro.configs.paper_models import PAPER_MODELS
    from repro.core.quantize import quantize_graph

    gens = {
        "sine": lambda rng, n: rng.uniform(0, 2 * np.pi, (n, 1)).astype("f"),
        "speech": lambda rng, n: rng.normal(0, 1, (n, 49, 40, 1)).astype("f"),
        "person": lambda rng, n: rng.normal(0, 1, (n, 96, 96, 1)).astype("f"),
    }
    reg = ServingRegistry(**registry_kw)
    rng = np.random.default_rng(seed)
    for name in names:
        g = PAPER_MODELS[name](batch=1)
        rep = [gens[name](rng, 1) for _ in range(calib_samples)]
        reg.register(name, CompiledModel(quantize_graph(g, rep),
                                         use_pallas=use_pallas,
                                         layout_plan=layout_plan))
    return reg
