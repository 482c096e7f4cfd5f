"""Compiled engine — the MicroFlow counterpart (Sec. 3.3).

The whole graph is translated, ahead of time, into ONE program:

* the per-operator *parser* phase runs here on the host
  (``preprocess.preprocess_graph``) and bakes the Eq. (4)/(7)/(10) constants
  into the executable as literals;
* the operator *kernels* are traced into a single XLA computation and
  AOT-compiled with ``jax.jit(...).lower().compile()`` — the analogue of the
  Rust compiler producing the target binary (Fig. 2);
* memory is assigned statically by XLA's buffer allocator, with operator
  inputs effectively *owned and dropped* (liveness-based reuse), mirroring
  Sec. 4.1; the byte-exact plan is reported by ``memory.plan_stack``.

Everything resolved before the first inference lives in ONE object: the
:class:`ExecutionPlan` — graph + folded Eq. (4)/(7)/(10) constants +
compile-time ``LayoutPlan`` + paging map + route flags. It is the single
source of lowering truth: ``CompiledModel`` builds exactly one at
construction, and the per-call trace (``compile``) and every batched bucket
executable (``compile_batched`` / ``warmup_batched`` / the serving path)
lower from it via :meth:`ExecutionPlan.lower`. The batched trace therefore
keeps the layout plan: activations stay lane-padded across consecutive
Pallas layers inside every served bucket, and the bucket zero-fill pad
fuses with the layout entry pad into a single staged device pad
(``entry_phys``), so bucket executables contain no entry layout churn.

Per-op lowering comes from the single-source :mod:`repro.core.registry`; the
interpreter baseline consumes the same registry, so engine parity is
structural rather than a convention.

Options:
  use_pallas  — route quantized FullyConnected / Conv2D / DepthwiseConv
                through the Pallas MXU kernels (``repro.kernels``),
                interpret-mode on CPU. A compile-time layout plan
                (``preprocess.plan_layout``) keeps activations lane-padded
                across consecutive Pallas ops — padding only at graph entry,
                slicing only at graph outputs and non-Pallas boundaries.
  layout_plan — on by default; ``layout_plan=False`` keeps the per-call
                pad/slice route (single-call AND batched) for debugging and
                A/B benchmarks.
  paged       — {op_index: n_pages}: execute those FC layers page-by-page
                (Sec. 4.3), bounding resident weight bytes.

Batched serving: ``predict``/``predict_q`` accept inputs with one extra
leading batch dimension. Each batch size is rounded up to a power-of-two
bucket, AOT-compiled once, and cached, so one ``CompiledModel`` serves
many concurrent requests without per-size recompilation.
"""
from __future__ import annotations

import dataclasses
import os
import re
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import engine_event, engine_span
from . import graph as G
from . import registry as R
from .memory import memory_report
from .preprocess import LayoutPlan, plan_layout, preprocess_graph


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Everything resolved at compile time, in one object.

    ``graph`` + ``folded`` (the parser phase) + ``layout`` (the padded
    physical layouts, batch-neutral) + ``paged`` + ``use_pallas`` fully
    determine every lowering of the model; both the per-call and batched
    traces are produced by :meth:`lower`, so there is no second place where
    routing or layout decisions can drift.
    """

    graph: G.Graph
    folded: dict
    layout: Optional[LayoutPlan]
    paged: dict
    use_pallas: bool

    @classmethod
    def build(cls, g: G.Graph, use_pallas: bool = False,
              paged: Optional[dict] = None,
              layout_plan: bool = True) -> "ExecutionPlan":
        g.validate()
        folded = preprocess_graph(g)  # compile-time parser phase
        paged = dict(paged or {})
        layout = (plan_layout(g, folded, paged)
                  if (use_pallas and layout_plan) else None)
        return cls(g, folded, layout, paged, use_pallas)

    def entry_shape(self, tid) -> tuple:
        """Per-sample physical shape graph input ``tid`` is staged in on the
        batched trace: lane-padded when a planned Pallas op consumes it (the
        bucket-fill and entry lane pads then fuse into one staged pad),
        logical otherwise."""
        if self.layout is not None:
            phys = self.layout.entry_phys.get(tid)
            if phys is not None:
                return tuple(phys)
        return tuple(self.graph.tensor(tid).shape)

    def batched_input_specs(self, bucket: int) -> list:
        """ShapeDtypeStructs a bucket executable is lowered against — the
        staged-pad entry contract, single-sourced so benches and tests trace
        exactly the program serving runs."""
        return [jax.ShapeDtypeStruct((bucket,) + self.entry_shape(t),
                                     np.dtype(self.graph.tensor(t).dtype))
                for t in self.graph.inputs]

    def lower(self, batched: bool = False):
        """Returns fn(*graph_dtype_inputs) -> tuple(graph_dtype_outputs).

        With ``batched=True`` every activation (inputs included) carries one
        extra leading batch dimension and ops run through their registry
        batch rules; inputs may arrive in ``entry_shape`` physical layout
        (the staged-pad contract) or logical (the kernels then pad).

        With a layout plan, Pallas-routed ops exchange activations in
        lane-padded physical layout: padding happens only at graph entry,
        slicing only at graph outputs and non-Pallas boundaries — interior
        Pallas→Pallas edges carry the padded block untouched, on both the
        per-call and batched traces.
        """
        g, folded, paged = self.graph, self.folded, self.paged
        use_pallas = self.use_pallas
        run = R.run_batched if batched else R.run_compiled
        layouts = self.layout.layouts if self.layout is not None else {}
        lead = (slice(None),) if batched else ()

        def fn(*inputs):
            env = dict(zip(g.inputs, inputs))

            def val(tid, keep_padded=False):
                t = g.tensor(tid)
                if t.is_const:
                    return jnp.asarray(t.data)
                v = env[tid]
                # Physical (padded) values advertise themselves by shape;
                # consumers outside the planned region get the logical view.
                if not keep_padded and v.shape[len(lead):] != tuple(t.shape):
                    v = v[lead + tuple(slice(0, d) for d in t.shape)]
                return v

            for i, op in enumerate(g.ops):
                lay = layouts.get(i)
                ctx = R.OpContext(g, op, i, folded=folded.get(i),
                                  use_pallas=use_pallas, n_pages=paged.get(i),
                                  layout=lay)
                # the graph layer rides every device op's op_name metadata
                with jax.named_scope(f"{i:02d}_{op.op.lower()}"):
                    env[op.outputs[0]] = run(
                        ctx, [val(t, keep_padded=lay is not None)
                              for t in op.inputs])

            return tuple(val(t) for t in g.outputs)

        # a stable executable name (``jit_serve_<graph>``) on the device's
        # XLA Modules line, in place of the anonymous ``fn``
        fn.__name__ = fn.__qualname__ = (
            f"{'serve' if batched else 'predict'}_"
            + re.sub(r"\W", "_", g.name))
        return fn


def build_graph_fn(g: G.Graph, folded: dict, use_pallas: bool = False,
                   paged: Optional[dict] = None, batched: bool = False,
                   plan=None):
    """Compatibility wrapper: assemble an :class:`ExecutionPlan` from loose
    pieces and lower it. New code should build the plan once and call
    :meth:`ExecutionPlan.lower` for each trace it needs."""
    return ExecutionPlan(g, folded, plan, dict(paged or {}),
                         use_pallas).lower(batched=batched)


def bucket_for(batch: int) -> int:
    """Power-of-two shape bucket: one AOT executable serves all batch sizes
    up to the bucket (inputs are zero-padded, outputs sliced).

    Total on ``batch >= 0``: ``bucket_for(0) == bucket_for(1) == 1`` (an
    empty batch maps to the smallest executable — it used to map to bucket
    2 via a ``bit_length`` underflow), negative batches raise. Public so
    the serving layer (``repro.serve.scheduler``) can coalesce request
    queues into exactly the buckets the engine AOT-compiles."""
    if batch < 0:
        raise ValueError(f"batch must be >= 0, got {batch}")
    return 1 << int(max(1, batch) - 1).bit_length()


def bucket_floor(batch: int) -> int:
    """Largest power-of-two bucket <= ``batch`` (>= 1): the chunk size that
    fills a bucket exactly instead of padding past it. Total on
    ``batch >= 0``: batches 0 and 1 both floor to the 1-bucket (there is
    no smaller executable), negative batches raise."""
    if batch < 0:
        raise ValueError(f"batch must be >= 0, got {batch}")
    return 1 << (max(1, int(batch)).bit_length() - 1)


def dispatched_bucket_rows(batch: int, max_batch: Optional[int] = None) -> int:
    """Total bucket rows ``predict_q_many(batch, max_batch=...)`` actually
    dispatches: full ``bucket_floor(max_batch)`` chunks are exact, only the
    tail pads — to its own bucket; an empty batch dispatches nothing.
    Public so serving metrics (batch occupancy) account for what the
    engine really paid."""
    if batch == 0:
        return 0
    if max_batch is None:
        return bucket_for(batch)
    step = bucket_floor(max_batch)
    if batch <= step:
        return bucket_for(batch)
    full, rem = divmod(batch, step)
    return full * step + (bucket_for(rem) if rem else 0)


class CompiledModel:
    """The user-facing ``predict()`` the paper's ``model`` macro generates.

    Thread-safety: executing the AOT executables (``predict_q`` /
    ``predict_q_many``) is safe from any number of threads — XLA
    executables are immutable once compiled and JAX dispatch is
    thread-safe. What is NOT naturally safe is *cache fill*: the bucket
    executable cache (``_batched_aot``), the staged-pad cache
    (``_stage_pad``), and the per-call AOT slot (``_aot``) are plain
    dicts/attributes mutated on miss. All three fill with double-checked
    lookups under ``_compile_lock``, so a half-built entry is never
    visible and concurrent ``predict_q_many`` calls on a cold bucket
    compile it exactly once (the loser of the race reuses the winner's
    executable). Bucket compiles additionally go through a per-bucket
    in-flight table (``_inflight``): the lock is held only to *claim* a
    bucket and to *publish* its executable, not across the XLA compile
    itself — so two different cold buckets compile concurrently (the
    parallel ``warmup_batched`` cold path leans on this) while racing
    callers on the SAME bucket still wait for the single owner instead
    of duplicating a multi-second compile. Reads on the warm path stay
    lock-free.

    Persistence: ``warmup_batched(cache=...)`` consults a
    :class:`repro.serve.aotcache.AotCache` — a verified cache hit
    installs deserialized executables (zero XLA compiles, bit-identical
    outputs); a miss compiles cold and stores the executables for the
    next boot. Every fill is recorded twice: the monotone
    ``compile_events`` counter (the no-retrace auditor's runtime
    counterpart — cache *hits* do not move it, which is exactly the
    warm-boot claim) and the typed ``compile_log``
    (``{kind: bucket|stage_pad|percall, cache: hit|miss|store|None}``)
    surfaced through serving telemetry."""

    def __init__(self, g: G.Graph, use_pallas: bool = False,
                 paged: Optional[dict] = None, layout_plan: bool = True):
        self.exec_plan = ExecutionPlan.build(g, use_pallas, paged,
                                             layout_plan)
        self._fn = jax.jit(self.exec_plan.lower())
        self._aot = None
        self._batched_aot = {}  # bucket size -> AOT executable
        self._stage_pad = {}    # (shape, widths) -> jitted device-side pad
        self._fallback = None   # use_pallas=False CompiledModel (degradation)
        self._reference = None  # Interpreter for the "reference" route
        self._ref_lock = threading.Lock()  # interpreter arena is stateful
        self._compile_lock = threading.Lock()  # guards all cache fills
        # Preallocated host staging buffers for the serving fast path
        # (``staged_infer``): bucket -> [tuple of per-input arrays]. Each
        # buffer is born in the bucket's *physical* entry layout —
        # ``(bucket,) + entry_shape(tid)``, the same statically-verified
        # shapes the plan auditor bounds the arena with — and kept
        # zero-filled outside the rows in use, so assembling a flush is a
        # row copy, never an allocation, a stack, or a device-side pad.
        self._staging: dict = {}
        self._staging_lock = threading.Lock()
        self._staging_cap = 4   # buffer sets kept per bucket
        # Monotone count of staging-buffer allocations — the slot-pool
        # analogue of ``compile_events``: after warm-up this should not
        # move on the serving hot path.
        self.staging_events = 0
        # Monotone count of cache fills (per-call AOT, bucket executables,
        # staged pads). Incremented only inside the lock-guarded miss
        # paths, so "no compilation happened on the hot path" is directly
        # observable: the no-retrace auditor's runtime counterpart.
        # Executables installed from a persistent AotCache do NOT count —
        # a warm boot from a populated cache keeps this at zero, which is
        # the cold-start bench's asserted claim.
        self.compile_events = 0
        # Typed fill log: {"kind": "bucket"|"stage_pad"|"percall",
        # "cache": "hit"|"miss"|"store"|None, ...} — one entry per real
        # compile (cache None/miss), per cache-loaded executable (hit),
        # and per executable persisted to a cache (store). Serving
        # telemetry and the flight recorder surface these, so staged-pad
        # compiles, bucket fills, and per-call AOT fills are
        # distinguishable after the fact.
        self.compile_log: list = []
        # Aggregated persistent-cache interaction counters.
        self.cache_events = {"hit": 0, "miss": 0, "store": 0}
        # While a cache-backed cold warm-up runs, fresh compiles are
        # labelled cache="miss" (a cache was consulted and didn't cover
        # them); None otherwise.
        self._cache_mode: Optional[str] = None
        # bucket -> threading.Event for compiles in flight: claims and
        # publications happen under _compile_lock, the XLA compile itself
        # runs outside it so independent buckets compile concurrently.
        self._inflight: dict = {}
        # Result of the last AotCache interaction (None until a
        # cache-backed warm-up runs) — registry telemetry surfaces it.
        self.last_cache_result = None

    # Everything compile-time lives in the ExecutionPlan; these read-only
    # views keep the established attribute API without a second copy that
    # could drift from what actually lowers.
    @property
    def graph(self) -> G.Graph:
        return self.exec_plan.graph

    @property
    def use_pallas(self) -> bool:
        return self.exec_plan.use_pallas

    @property
    def paged(self) -> dict:
        return self.exec_plan.paged

    @property
    def folded(self) -> dict:
        return self.exec_plan.folded

    @property
    def plan(self):
        return self.exec_plan.layout  # LayoutPlan (None when off)

    def _input_specs(self, lead=()):
        return [jax.ShapeDtypeStruct(tuple(lead) + self.graph.tensor(t).shape,
                                     np.dtype(self.graph.tensor(t).dtype))
                for t in self.graph.inputs]

    # -- fill accounting ---------------------------------------------------
    def _note_compile(self, kind: str, **extra) -> None:
        """Record one real XLA compile (caller holds ``_compile_lock``):
        bumps ``compile_events``, appends the typed log entry, and makes
        the fill visible to an active trace scope — a traced request
        paying an AOT cache miss is exactly what the serving warm-up
        promises never happens, so it must be loud."""
        cache = self._cache_mode
        self.compile_events += 1
        if cache is not None:
            self.cache_events[cache] = self.cache_events.get(cache, 0) + 1
        self.compile_log.append({"kind": kind, "cache": cache, **extra})
        attrs = {"cache": cache, **extra} if cache is not None else extra
        engine_event("compile", kind=kind, **attrs)

    def _note_cache_event(self, kind: str, cache: str, **extra) -> None:
        """Record one persistent-cache interaction that is NOT a compile
        (an executable loaded from or stored to an AotCache). Never moves
        ``compile_events`` — that counter stays the pure no-XLA-compile
        proof."""
        self.cache_events[cache] = self.cache_events.get(cache, 0) + 1
        self.compile_log.append({"kind": kind, "cache": cache, **extra})
        engine_event("compile_cache", kind=kind, cache=cache, **extra)

    # -- AOT compilation (Fig. 2's "Target Binary") -----------------------
    def compile(self):
        if self._aot is None:
            with self._compile_lock:
                # double-checked: compile-once under racing callers
                if self._aot is None:
                    with engine_span("compile", kind="percall"):
                        self._aot = self._fn.lower(
                            *self._input_specs()).compile()
                    self._note_compile("percall")
        return self._aot

    def compile_batched(self, batch: int):
        """AOT-compile (and cache) the executable for ``batch``'s bucket,
        lowered from the shared :class:`ExecutionPlan` (layout plan
        included). Inputs arrive in staged entry layout — bucket-filled and
        lane-padded by ONE fused device pad in ``_predict_q_batched`` — so
        the executable itself contains no entry layout work.

        Concurrency: racing callers on one cold bucket resolve to a
        single compile (the owner claims the bucket in ``_inflight``
        under the lock; losers wait on its event), but the XLA compile
        runs OUTSIDE ``_compile_lock``, so different cold buckets —
        independent executables — compile in parallel. This is what lets
        the cache-less ``warmup_batched`` cold path fan bucket compiles
        out on a thread pool without duplicating work.

        Input buffers are donated where the backend supports it — the
        batched path always stages fresh device buffers, so donation is
        safe and lets XLA reuse the int8 input storage for activations."""
        bucket = bucket_for(batch)
        exe = self._batched_aot.get(bucket)
        if exe is not None:
            return exe
        while True:
            with self._compile_lock:
                exe = self._batched_aot.get(bucket)
                if exe is not None:
                    return exe  # published while we waited
                ev = self._inflight.get(bucket)
                if ev is None:  # claim: we are this bucket's one compiler
                    ev = threading.Event()
                    self._inflight[bucket] = ev
                    break
            ev.wait()  # another thread owns this bucket; wait, re-check
        try:
            donate = (tuple(range(len(self.graph.inputs)))
                      if jax.default_backend() != "cpu" else ())
            fn = jax.jit(self.exec_plan.lower(batched=True),
                         donate_argnums=donate)
            with engine_span("compile", kind="bucket", bucket=bucket):
                exe = fn.lower(
                    *self.exec_plan.batched_input_specs(bucket)).compile()
            with self._compile_lock:
                self._batched_aot[bucket] = exe
                self._note_compile("bucket", bucket=bucket)
            return exe
        finally:
            # on failure waiters wake, find no executable, and exactly one
            # re-claims the bucket — the invariant stays one live compile
            # per bucket, never zero retries
            with self._compile_lock:
                self._inflight.pop(bucket, None)
            ev.set()

    def bucket_sizes(self) -> tuple:
        """Batch buckets with a compiled-and-cached AOT executable, sorted.
        The serving scheduler warms these up front so no request pays a
        compile on the hot path."""
        with self._compile_lock:  # stable view while another thread fills
            return tuple(sorted(self._batched_aot))

    def staged_pad_keys(self) -> tuple:
        """(shape, widths) keys with a compiled-and-cached staged entry
        pad, sorted. Together with :meth:`bucket_sizes` this is the warmed
        working set the no-retrace auditor (``repro.analysis.retrace``)
        checks statically-reachable cache keys against."""
        with self._compile_lock:
            return tuple(sorted(self._stage_pad))

    def warmup_batched(self, max_batch: int, *, cache=None,
                       parallel: Optional[bool] = None,
                       workers: Optional[int] = None):
        """Ahead-of-serving warm-up: AOT-compile every power-of-two bucket
        up to ``max_batch``'s bucket AND the staged entry pad (fused bucket
        zero-fill + layout lane pad) for every batch size at or below it.
        After this, no batch size ``<= max_batch`` triggers any compilation
        at request time — the serving-path analogue of the paper's
        everything-at-compile-time rule.

        ``cache`` (an :class:`repro.serve.aotcache.AotCache`) makes the
        warm-up load-or-compile-and-store: a verified cache hit installs
        every executable without a single XLA compile
        (``compile_events`` stays put — that is the warm-boot proof); a
        miss falls through to the cold path below and then persists the
        freshly compiled set. The outcome lands in
        ``last_cache_result``.

        The cold path fans independent bucket compiles out on a bounded
        thread pool (``parallel`` defaults to on for multi-bucket
        warm-ups; ``workers`` caps the pool, default
        ``min(4, n_buckets)``) — :meth:`compile_batched`'s per-bucket
        in-flight claim keeps the single-compile-per-bucket invariant
        regardless of pool width."""
        top = bucket_for(max_batch)
        self.last_cache_result = None
        if cache is not None:
            res = cache.load(self, max_batch)
            self.last_cache_result = res
            if res.hit:
                self._warm_staging(top)
                return self
            self._cache_mode = "miss"  # tag the cold compiles below
        try:
            buckets = []
            b = 1
            while b <= top:
                buckets.append(b)
                b *= 2
            if parallel is None:
                parallel = len(buckets) > 1
            if parallel:
                n = max(1, min(workers or 4, len(buckets)))
                with ThreadPoolExecutor(max_workers=n) as pool:
                    list(pool.map(self.compile_batched, buckets))
            else:
                for b in buckets:
                    self.compile_batched(b)
            for tid in self.graph.inputs:
                t = self.graph.tensor(tid)
                for batch in range(1, top + 1):
                    widths = self._entry_widths(tid, batch)
                    if any(w for _, w in widths):
                        shape = (batch,) + tuple(t.shape)
                        self._staged_pad(shape, widths, t.dtype)(
                            jnp.zeros(shape, np.dtype(t.dtype)))
        finally:
            self._cache_mode = None
        if cache is not None:
            stored = cache.store(self, max_batch)
            self.last_cache_result = stored
            if stored.stored:
                self._note_cache_event("manifest", "store",
                                       count=stored.stored)
        self._warm_staging(top)
        return self

    def _warm_staging(self, top: int) -> None:
        # preallocate one staging buffer set per bucket so the serving
        # fast path's first flush allocates nothing either
        b = 1
        while b <= top:
            with self._staging_lock:
                if not self._staging.get(b):
                    self._staging.setdefault(b, []).append(
                        self._new_staging(b))
            b *= 2

    # -- persistent-cache hooks (repro.serve.aotcache) ---------------------
    def install_cached_executables(self, buckets: dict, stages: dict, *,
                                   percall=None) -> int:
        """Install deserialized executables into the AOT caches without
        compiling. ``buckets`` maps bucket size -> executable, ``stages``
        maps retrace StageKey -> executable. Already-present entries are
        kept (they are the same program — first writer wins). Returns the
        number installed; each lands in ``compile_log`` as a ``hit`` but
        never moves ``compile_events``."""
        n = 0
        with self._compile_lock:
            for b, exe in sorted(buckets.items()):
                if b not in self._batched_aot:
                    self._batched_aot[int(b)] = exe
                    self._note_cache_event("bucket", "hit", bucket=int(b))
                    n += 1
            for key, exe in stages.items():
                k = (tuple(key[0]), tuple(tuple(w) for w in key[1]))
                if k not in self._stage_pad:
                    self._stage_pad[k] = exe
                    self._note_cache_event("stage_pad", "hit", shape=k[0])
                    n += 1
            if percall is not None and self._aot is None:
                self._aot = percall
                self._note_cache_event("percall", "hit")
                n += 1
        return n

    def cached_bucket(self, bucket: int):
        """The compiled executable for ``bucket`` (KeyError when cold) —
        the store side of the persistent cache reads through this."""
        with self._compile_lock:
            return self._batched_aot[bucket]

    def cached_stage_pads(self) -> dict:
        """Snapshot of StageKey -> compiled staged-pad executable."""
        with self._compile_lock:
            return dict(self._stage_pad)

    def cached_percall(self):
        """The per-call executable when compiled, else None."""
        with self._compile_lock:
            return self._aot

    @property
    def executable(self):
        if self._aot is None:
            self.compile()
        return self._aot

    def memory_analysis(self):
        return self.executable.memory_analysis()

    def cost_analysis(self):
        ca = self.executable.cost_analysis()
        # JAX < 0.5 returns a one-entry list of dicts; newer JAX the dict.
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return ca

    def memory_report(self):
        return memory_report(self.graph)

    # -- inference ---------------------------------------------------------
    def _is_batched(self, first_input) -> bool:
        t0 = self.graph.tensor(self.graph.inputs[0])
        return np.ndim(first_input) == len(t0.shape) + 1

    def _staged_pad(self, shape: tuple, widths: tuple, dtype):
        """AOT-compiled device-side zero pad covering the bucket fill on
        the leading (batch) dim AND the planned entry lane pad in one op —
        the staging never round-trips through host memory. Compiled (not
        just traced) so the stage is a serializable artifact the
        persistent cache can store alongside the bucket executables; the
        cache key stays ``(shape, widths)`` — dtype is a function of the
        graph input, so it never forks the key."""
        key = (tuple(shape), tuple(widths))
        fn = self._stage_pad.get(key)
        if fn is None:
            with self._compile_lock:
                fn = self._stage_pad.get(key)
                if fn is None:
                    spec = jax.ShapeDtypeStruct(tuple(shape),
                                                np.dtype(dtype))
                    with engine_span("compile", kind="stage_pad"):
                        fn = jax.jit(lambda a: jnp.pad(a, widths)).lower(
                            spec).compile()
                    self._stage_pad[key] = fn
                    self._note_compile("stage_pad", shape=tuple(shape))
        return fn

    def _entry_widths(self, tid, batch: int) -> tuple:
        """Per-dimension (0, pad) widths staging one batched input: bucket
        zero-fill on the batch dim + planned entry lane pad, fused."""
        t = self.graph.tensor(tid)
        phys = self.exec_plan.entry_shape(tid)
        return ((0, bucket_for(batch) - batch),) + tuple(
            (0, p - d) for p, d in zip(phys, t.shape))

    # -- preallocated staging (serving fast path) --------------------------
    def _empty_rows(self):
        outs = tuple(np.empty((0,) + tuple(self.graph.tensor(t).shape),
                              np.dtype(self.graph.tensor(t).dtype))
                     for t in self.graph.outputs)
        return outs if len(outs) > 1 else outs[0]

    def _new_staging(self, bucket: int) -> tuple:
        self.staging_events += 1
        return tuple(np.zeros((bucket,) + self.exec_plan.entry_shape(tid),
                              np.dtype(self.graph.tensor(tid).dtype))
                     for tid in self.graph.inputs)

    def acquire_staging(self, bucket: int) -> tuple:
        """Check out one zero-filled staging buffer set (one array per
        graph input, shaped ``(bucket,) + entry_shape``). Thread-safe; a
        cold checkout allocates (counted in ``staging_events``), a warm
        one reuses — ``warmup_batched`` pre-fills one set per bucket so
        serving never allocates."""
        with self._staging_lock:
            pool = self._staging.get(bucket)
            if pool:
                return pool.pop()
        return self._new_staging(bucket)

    def release_staging(self, bucket: int, bufs: tuple, rows: int) -> None:
        """Return a staging buffer set, re-zeroing the ``rows`` rows that
        were written so the pool invariant (zero outside rows in use —
        exactly what the staged ``jnp.pad`` produces) holds for the next
        checkout. The pool keeps at most ``_staging_cap`` sets per bucket;
        extras are dropped to the GC."""
        with engine_span("stage_rezero"):
            for b in bufs:
                b[:rows] = 0
        with self._staging_lock:
            pool = self._staging.setdefault(bucket, [])
            if len(pool) < self._staging_cap:
                pool.append(bufs)

    def predict_q_staged(self, bufs: tuple, rows: int):
        """Run the bucket executable directly on prestaged physical-layout
        buffers: no reshape, no ``np.stack``, no staged device pad — the
        buffers already ARE the executable's entry contract. Bit-identical
        to ``predict_q_many`` on the stacked rows, because a zero-filled
        physical buffer equals the fused bucket-fill + lane pad output."""
        bucket = bufs[0].shape[0]
        exe = self.compile_batched(bucket)
        with engine_span("stage_h2d"):
            args = [jnp.asarray(b) for b in bufs]  # H2D, already padded
        return self._run_bucket(exe, args, bucket, rows)

    @staticmethod
    def _run_bucket(exe, args, bucket: int, rows: int):
        """The bucket executable on device arguments, to host rows. The
        device span covers the launch AND the host sync (np.asarray) — what
        a request actually waits for."""
        with engine_span("device", bucket=bucket, rows=rows):
            with engine_span("launch"):
                outs = exe(*args)
            with engine_span("fetch"):
                outs = tuple(np.asarray(o)[:rows] for o in outs)
        return outs if len(outs) > 1 else outs[0]

    def staged_infer(self, rows: list):
        """Serving fast-path flush: assemble single-sample ``rows`` of a
        single-input graph straight into a pooled staging buffer and run
        the bucket executable on it. This is the zero-allocation analogue
        of ``predict_q_many(np.stack(rows))`` for flushes that fit one
        bucket — same executable, bit-identical outputs."""
        (tid,) = self.graph.inputs  # serving contract: single-input graph
        t = self.graph.tensor(tid)
        n = len(rows)
        if n == 0:
            return self._empty_rows()
        bucket = bucket_for(n)
        bufs = self.acquire_staging(bucket)
        try:
            dst = bufs[0]
            window = tuple(slice(0, d) for d in t.shape)  # logical region
            with engine_span("stage_rows"):
                for i, row in enumerate(rows):
                    dst[(i,) + window] = np.asarray(
                        row, t.dtype).reshape(t.shape)
            return self.predict_q_staged(bufs, n)
        finally:
            self.release_staging(bucket, bufs, n)

    def _predict_q_batched(self, inputs):
        batch = np.asarray(inputs[0]).shape[0]
        args = []
        for tid, arr in zip(self.graph.inputs, inputs):
            t = self.graph.tensor(tid)
            a = np.asarray(arr, t.dtype).reshape((-1,) + t.shape)
            assert a.shape[0] == batch, (
                f"all inputs must share the batch dim: {a.shape[0]} != {batch}")
            with engine_span("stage_h2d"):
                a = jnp.asarray(a)  # H2D of the real rows only
            widths = self._entry_widths(tid, batch)
            if any(w for _, w in widths):
                with engine_span("pad_stage", batch=batch):
                    a = self._staged_pad(a.shape, widths, a.dtype)(a)
            args.append(a)
        exe = self.compile_batched(batch)
        return self._run_bucket(exe, args, bucket_for(batch), batch)

    def predict_q(self, *inputs):
        """Graph-dtype in / graph-dtype out. Inputs may carry one extra
        leading batch dimension (routed through the bucketed batch path)."""
        if self._is_batched(inputs[0]):
            return self._predict_q_batched(inputs)
        args = []
        for tid, arr in zip(self.graph.inputs, inputs):
            t = self.graph.tensor(tid)
            args.append(jnp.asarray(np.asarray(arr, t.dtype).reshape(t.shape)))
        outs = self.executable(*args) if self._aot is not None else self._fn(*args)
        return outs if len(outs) > 1 else outs[0]

    def predict_q_many(self, *inputs, max_batch: Optional[int] = None):
        """Batched ``predict_q`` that splits an arbitrarily large batch into
        bucket-aligned chunks of at most ``max_batch`` rows and concatenates
        the results.

        Chunks split on bucket boundaries: a non-power-of-two ``max_batch``
        is clamped down to ``bucket_floor(max_batch)`` so every full chunk
        fills its power-of-two bucket exactly instead of padding past it
        (``max_batch=6`` used to pad every 6-row chunk up to the 8-bucket —
        wasted lanes on every serving flush). Only the final partial chunk
        can pad, to its own (smaller) bucket.

        This is the serving entry point: a micro-batcher can drain its whole
        queue in one call without AOT-compiling a bucket for every queue
        depth it ever observes — the executable working set stays bounded by
        ``max_batch``. Rows are identical to per-chunk ``predict_q`` calls.
        """
        arrs = [np.asarray(a) for a in inputs]
        if not self._is_batched(arrs[0]):
            raise ValueError("predict_q_many requires a leading batch dim")
        if max_batch is not None and max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        batch = arrs[0].shape[0]
        if batch == 0:
            # An empty flush dispatches nothing (and in particular never
            # touches an unwarmed batch-0 stage-pad key): return empty
            # rows of the output shapes/dtypes directly.
            return self._empty_rows()
        # Split whenever the batch exceeds the largest exactly-fillable
        # bucket — NOT only when it exceeds max_batch: a serving flush of
        # max_batch=6 rows must drain as 4+2 exact buckets, never pad its
        # one chunk up to the 8-bucket.
        step = None if max_batch is None else bucket_floor(max_batch)
        if step is None or batch <= step:
            return self.predict_q(*arrs)
        chunks = []
        for lo in range(0, batch, step):
            out = self.predict_q(*(a[lo:lo + step] for a in arrs))
            chunks.append(out if isinstance(out, tuple) else (out,))
        outs = tuple(np.concatenate([np.asarray(c[i]) for c in chunks])
                     for i in range(len(chunks[0])))
        return outs if len(outs) > 1 else outs[0]

    # -- route-selectable dispatch (serving degradation chain) -------------
    def routes(self) -> tuple:
        """Dispatch routes this model can serve, primary first — the
        serving resilience layer's degradation chain:

        * ``"pallas"`` — the MXU kernel route (only when built with
          ``use_pallas=True``); the primary route in that case.
        * ``"compiled"`` — the plain XLA compiled route (the primary when
          ``use_pallas=False``; otherwise the first fallback, lowered from
          a separate ``use_pallas=False`` plan of the same graph).
        * ``"reference"`` — the interpreter baseline
          (:class:`repro.core.interpreter.Interpreter`): pure numpy, no
          XLA executable involved, the last resort that shares nothing
          with the compiled routes except the op registry. All three
          routes are bit-exact on quantized graphs (the registry parity
          contract), so degrading is invisible in outputs.
        """
        return (("pallas", "compiled", "reference") if self.use_pallas
                else ("compiled", "reference"))

    def _fallback_compiled(self) -> "CompiledModel":
        """The ``use_pallas=False`` sibling model (lazily built, cached):
        same graph, same folding, plain-XLA lowering — the first
        degradation target when the Pallas route misbehaves."""
        if self._fallback is None:
            with self._compile_lock:
                if self._fallback is None:
                    self._fallback = CompiledModel(
                        self.graph, use_pallas=False,
                        paged=dict(self.paged) or None)
        return self._fallback

    def _reference_interp(self):
        if self._reference is None:
            with self._compile_lock:
                if self._reference is None:
                    from .interpreter import Interpreter
                    self._reference = Interpreter(self.graph)
        return self._reference

    def _predict_q_reference(self, inputs):
        """Row-by-row interpreter execution of a batched input — the
        numpy reference route (no XLA dispatch at all). The interpreter's
        arena is reused across rows, so calls serialize on a lock."""
        arrs = [np.asarray(a) for a in inputs]
        batch = arrs[0].shape[0]
        if batch == 0:
            return self._empty_rows()
        interp = self._reference_interp()
        rows = []
        with self._ref_lock:
            for i in range(batch):
                out = interp.invoke_q(*(a[i] for a in arrs))
                rows.append(out if isinstance(out, tuple) else (out,))
        outs = tuple(np.stack([r[i] for r in rows])
                     for i in range(len(rows[0])))
        return outs if len(outs) > 1 else outs[0]

    def predict_q_routed(self, *inputs, route: Optional[str] = None,
                         max_batch: Optional[int] = None):
        """Batched ``predict_q_many`` with an explicit dispatch route.

        ``route=None`` (or the primary route name) is exactly
        ``predict_q_many``; ``"compiled"`` forces the plain-XLA sibling
        plan; ``"reference"`` runs the interpreter row by row. This is the
        engine half of serving's graceful degradation: the resilience
        layer walks :meth:`routes` when a route keeps failing, and every
        route returns bit-identical rows on quantized graphs."""
        names = self.routes()
        if route is None or route == names[0]:
            return self.predict_q_many(*inputs, max_batch=max_batch)
        if route == "compiled":
            return self._fallback_compiled().predict_q_many(
                *inputs, max_batch=max_batch)
        if route == "reference":
            return self._predict_q_reference(inputs)
        raise ValueError(f"unknown route {route!r}; available: {names}")

    def warmup_routes(self, max_batch: int, *,
                      cache=None) -> "CompiledModel":
        """Warm every degradation route: the primary bucket executables
        (``warmup_batched``), the compiled fallback's buckets (when the
        primary is Pallas), and the reference interpreter's arena — so a
        breaker trip degrades to an already-compiled route instead of
        paying a cold compile mid-incident. ``cache`` flows to both
        compiled routes — the fallback's ExecutionPlan differs (Pallas
        off), so it fingerprints to its own cache entry."""
        self.warmup_batched(max_batch, cache=cache)
        if self.use_pallas:
            self._fallback_compiled().warmup_batched(max_batch, cache=cache)
        self._reference_interp()
        return self

    def predict(self, *inputs):
        """Float in / float out (TFLite-style interface). Accepts either
        exact graph-shaped inputs or a leading batch dimension on every
        input; batched results are row-identical to batch-1 calls."""
        batched = self._is_batched(inputs[0])
        qin = []
        for tid, arr in zip(self.graph.inputs, inputs):
            t = self.graph.tensor(tid)
            shape = ((-1,) + t.shape) if batched else t.shape
            arr = np.asarray(arr, np.float32).reshape(shape)
            qin.append(t.qparams.quantize(arr) if t.dtype == "int8" else arr)
        outs = self.predict_q(*qin)
        if not isinstance(outs, tuple):
            outs = (outs,)
        res = []
        for tid, o in zip(self.graph.outputs, outs):
            t = self.graph.tensor(tid)
            o = np.asarray(o)
            res.append(t.qparams.dequantize(o) if t.dtype == "int8"
                       else o.astype(np.float32))
        return tuple(res) if len(res) > 1 else res[0]
