"""Persistent AOT executable cache: fingerprint, manifest, boot.

The cache's whole claim is conditional correctness: a warm boot may skip
every XLA compile *only because* the plan fingerprint + manifest
verification prove the stored executables were lowered from this exact
plan. So the tests pair every fast path with its rejection twin:

* fingerprint stability (same plan -> same address) against
  invalidation (one folded const / one LayoutPlan entry / the route
  flag -> new address, stale cache rejected with C001, fresh compile);
* verified loads (zero ``compile_events`` on a warm boot — the runtime
  twin of the no-retrace proof) against corruption (truncated entry ->
  C003 -> cold compile, never a half-loaded model);
* bit-exactness: cached-load outputs == fresh-compile outputs for every
  bucket of all three paper models;
* the parallel cold-path warm-up keeping the single-compile-per-bucket
  invariant, and the typed ``compile_log`` / registry telemetry
  surfacing what each boot did.
"""
import copy
import dataclasses
import glob
import json
import os
import threading

import numpy as np
import pytest

from repro.analysis import plan_fingerprint, verify_manifest
from repro.analysis.__main__ import quantized_graph
from repro.core import CompiledModel, ExecutionPlan
from repro.serve.aotcache import AotCache, serialization_support

MODELS = ("sine", "speech", "person")

pytestmark = pytest.mark.skipif(
    not serialization_support()[0],
    reason=f"backend cannot serialize executables "
           f"({serialization_support()[1]})")


@pytest.fixture(scope="module")
def graphs():
    return {name: quantized_graph(name) for name in MODELS}


def _model(graphs, name="sine", **kw):
    return CompiledModel(copy.deepcopy(graphs[name]), **kw)


# ------------------------------------------------------ fingerprint -----

def test_fingerprint_stable_across_builds(graphs):
    a = ExecutionPlan.build(copy.deepcopy(graphs["sine"]))
    b = ExecutionPlan.build(copy.deepcopy(graphs["sine"]))
    assert plan_fingerprint(a) == plan_fingerprint(b)
    assert plan_fingerprint(a).startswith("pf1-")


def test_fingerprint_changes_on_folded_const(graphs):
    plan = ExecutionPlan.build(copy.deepcopy(graphs["sine"]))
    fp = plan_fingerprint(plan)
    mutated = copy.deepcopy(plan)
    fc = mutated.folded[sorted(mutated.folded)[0]]
    for field, val in vars(fc).items():
        if isinstance(val, np.ndarray):
            val.flat[0] += 1  # one retrained-weight-worth of drift
            break
    else:
        pytest.fail("no ndarray field on FoldedConsts to mutate")
    assert plan_fingerprint(mutated) != fp


def test_fingerprint_changes_on_layout_entry(graphs):
    plan = ExecutionPlan.build(copy.deepcopy(graphs["sine"]),
                               use_pallas=True)
    fp = plan_fingerprint(plan)
    tid = sorted(plan.layout.phys)[0]
    phys = dict(plan.layout.phys)
    phys[tid] = tuple(d + 8 for d in phys[tid])  # one re-planned lane pad
    mutated = ExecutionPlan(plan.graph, plan.folded,
                            dataclasses.replace(plan.layout, phys=phys),
                            plan.paged, plan.use_pallas)
    assert plan_fingerprint(mutated) != fp


def test_fingerprint_changes_on_route_flags(graphs):
    g = copy.deepcopy(graphs["sine"])
    plain = ExecutionPlan.build(g, use_pallas=False)
    pallas = ExecutionPlan.build(g, use_pallas=True)
    flipped = ExecutionPlan(plain.graph, plain.folded, plain.layout,
                            plain.paged, True)
    fps = {plan_fingerprint(p) for p in (plain, pallas, flipped)}
    assert len(fps) == 3


def test_fingerprint_changes_on_graph_weight(graphs):
    g = copy.deepcopy(graphs["sine"])
    fp = plan_fingerprint(ExecutionPlan.build(copy.deepcopy(g)))
    w = next(t for t in g.tensors if t.data is not None
             and np.asarray(t.data).size)
    w.data = np.array(w.data)
    w.data.flat[0] = w.data.flat[0] ^ 1  # one flipped weight bit
    assert plan_fingerprint(ExecutionPlan.build(g)) != fp


# ------------------------------------------------ manifest verification --

def test_manifest_rejects_stale_plan(graphs, tmp_path):
    """A cache stored for one plan must be invisible to a mutated plan:
    the new fingerprint addresses an empty directory, the warm-up misses,
    compiles fresh, and stores under the NEW address."""
    cache = AotCache(str(tmp_path))
    _model(graphs).warmup_batched(4, cache=cache)
    mutated = _model(graphs)
    fc = mutated.exec_plan.folded[sorted(mutated.exec_plan.folded)[0]]
    for field, val in vars(fc).items():
        if isinstance(val, np.ndarray):
            val.flat[0] += 1
            break
    mutated.warmup_batched(4, cache=cache)
    assert mutated.compile_events > 0  # fresh compile, not a stale load
    assert mutated.cache_events["hit"] == 0
    assert len(os.listdir(tmp_path)) == 2  # one dir per fingerprint

    # and the cross-plan manifest check itself reports C001
    stale_fp = plan_fingerprint(_model(graphs).exec_plan)
    man = cache.manifest(stale_fp)
    info, findings = verify_manifest(man, mutated.exec_plan, 4)
    assert not info["ok"]
    assert any(f.code == "C001" for f in findings)


def test_manifest_rejects_partial_coverage(graphs, tmp_path):
    """A cache warmed to 2 cannot admit a replica serving 4 (C002)."""
    cache = AotCache(str(tmp_path))
    cm = _model(graphs).warmup_batched(2, cache=cache)
    man = cache.manifest(plan_fingerprint(cm.exec_plan))
    info, findings = verify_manifest(man, cm.exec_plan, 4)
    assert not info["ok"]
    assert any(f.code == "C002" for f in findings)
    # and the boot path agrees: load misses, fresh warm-up compiles
    cm2 = _model(graphs)
    cm2.warmup_batched(4, cache=cache)
    assert cm2.compile_events > 0


def test_manifest_rejects_corrupt_entry(graphs, tmp_path):
    """A truncated entry file digest-fails (C003) and the load is
    all-or-nothing: the model stays cold and compiles everything."""
    cache = AotCache(str(tmp_path))
    _model(graphs).warmup_batched(4, cache=cache)
    (jexe,) = glob.glob(str(tmp_path / "*" / "bucket_2.jexe"))
    with open(jexe, "r+b") as f:
        f.truncate(128)
    res = cache.verify(_model(graphs), 4)
    assert not res.hit
    assert any(f.code == "C003" for f in res.findings)
    cm = _model(graphs)
    cm.warmup_batched(4, cache=cache)
    assert not cm.last_cache_result.hit
    assert cm.cache_events["hit"] == 0  # nothing half-installed
    assert cm.compile_events > 0
    # ...and the miss path re-stored a good copy: the cache self-heals
    assert cache.verify(_model(graphs), 4).hit


def test_manifest_rejects_environment_mismatch(graphs, tmp_path):
    cache = AotCache(str(tmp_path))
    cm = _model(graphs).warmup_batched(2, cache=cache)
    fp = plan_fingerprint(cm.exec_plan)
    # another jaxlib, and another chip generation under the same backend
    for key, other in (("jaxlib", "0.0.0"), ("device_kind", "TPU v4")):
        man = cache.manifest(fp)
        assert key in man["environment"]
        man["environment"][key] = other
        info, findings = verify_manifest(man, cm.exec_plan, 2)
        assert not info["ok"]
        assert [f.where for f in findings if f.code == "C004"] \
            == [f"environment.{key}"]


def test_manifest_audit_cross_check(graphs, tmp_path):
    """results/audit.json-style documents arm the C005 cross-check: an
    audit proving a bucket reachable that the manifest lacks, or carrying
    a different fingerprint, rejects the cache."""
    cache = AotCache(str(tmp_path))
    cm = _model(graphs).warmup_batched(4, cache=cache)
    fp = plan_fingerprint(cm.exec_plan)
    man = cache.manifest(fp)
    ok_audit = {"models": [{"model": man["model"], "use_pallas": False,
                            "fingerprint": fp,
                            "retrace": {"reachable_buckets": [1, 2, 4]}}]}
    info, findings = verify_manifest(man, cm.exec_plan, 4, audit=ok_audit)
    assert info["ok"] and info["audit_checked"], [str(f) for f in findings]

    wide = {"models": [{"model": man["model"], "use_pallas": False,
                        "retrace": {"reachable_buckets": [1, 2, 4, 8]}}]}
    _, findings = verify_manifest(man, cm.exec_plan, 4, audit=wide)
    assert any(f.code == "C005" for f in findings)

    other = {"models": [{"model": man["model"], "use_pallas": False,
                         "fingerprint": "pf1-deadbeef",
                         "retrace": {"reachable_buckets": [1]}}]}
    _, findings = verify_manifest(man, cm.exec_plan, 4, audit=other)
    assert any(f.code == "C005" for f in findings)

    # audit entries for the other route (use_pallas=True) are ignored:
    # their fingerprints legitimately differ
    cross = {"models": [{"model": man["model"], "use_pallas": True,
                         "fingerprint": "pf1-deadbeef",
                         "retrace": {"reachable_buckets": [1, 2, 4, 8]}}]}
    info, findings = verify_manifest(man, cm.exec_plan, 4, audit=cross)
    assert info["ok"], [str(f) for f in findings]


# ------------------------------------------------------- warm boots -----

def test_warm_boot_zero_compiles_and_bit_exact(graphs, tmp_path):
    """The acceptance claim, on every paper model: a warm boot from a
    populated cache performs ZERO XLA compiles, and every bucket's cached
    executable produces bit-identical outputs to the fresh compile's."""
    rng = np.random.default_rng(7)
    for name in MODELS:
        cache = AotCache(str(tmp_path / name))
        cold = _model(graphs, name).warmup_batched(2, cache=cache)
        assert cold.compile_events > 0
        assert cold.cache_events["store"] >= 1

        warm = _model(graphs, name)
        warm.warmup_batched(2, cache=cache)
        assert warm.compile_events == 0, (name, warm.compile_log)
        assert warm.last_cache_result.hit
        assert warm.bucket_sizes() == cold.bucket_sizes()
        assert warm.staged_pad_keys() == cold.staged_pad_keys()

        t = warm.graph.tensor(warm.graph.inputs[0])
        for batch in (1, 2):
            x = rng.integers(-128, 127, size=(batch,) + tuple(t.shape)
                             ).astype(t.dtype)
            a = np.asarray(cold.predict_q(x))
            b = np.asarray(warm.predict_q(x))
            assert a.dtype == b.dtype and np.array_equal(a, b), \
                (name, batch)
        # the whole boot (warm-up + requests above) stayed compile-free
        assert warm.compile_events == 0, (name, warm.compile_log)


def test_typed_compile_log(graphs, tmp_path):
    """compile_events stays the pure compile counter; the typed log
    distinguishes bucket / stage_pad / percall fills and their cache
    disposition (hit / miss / store)."""
    cache = AotCache(str(tmp_path))
    cold = _model(graphs)
    cold.compile()                      # percall, no cache in scope
    cold.warmup_batched(4, cache=cache)
    kinds = {(e["kind"], e["cache"]) for e in cold.compile_log}
    assert ("percall", None) in kinds
    assert ("bucket", "miss") in kinds
    assert ("stage_pad", "miss") in kinds
    assert ("manifest", "store") in kinds
    assert cold.compile_events == sum(
        1 for e in cold.compile_log
        if e["kind"] in ("percall", "bucket", "stage_pad"))

    warm = _model(graphs)
    warm.warmup_batched(4, cache=cache)
    assert warm.compile_events == 0
    assert {(e["kind"], e["cache"]) for e in warm.compile_log} == \
        {("bucket", "hit"), ("stage_pad", "hit"), ("percall", "hit")}
    assert warm.cache_events["hit"] == len(warm.compile_log)


def test_parallel_warmup_single_compile_per_bucket(graphs):
    """The bounded-pool cold path (and racing external warm-ups) still
    compile each bucket exactly once."""
    cm = _model(graphs)
    threads = [threading.Thread(
        target=lambda: cm.warmup_batched(8, parallel=True, workers=4))
        for _ in range(3)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    buckets = [e for e in cm.compile_log if e["kind"] == "bucket"]
    assert sorted(e["bucket"] for e in buckets) == [1, 2, 4, 8]
    assert cm.bucket_sizes() == (1, 2, 4, 8)
    # sequential and parallel warm-ups fill the identical key sets
    seq = _model(graphs).warmup_batched(8, parallel=False)
    assert seq.bucket_sizes() == cm.bucket_sizes()
    assert seq.staged_pad_keys() == cm.staged_pad_keys()


def test_store_requires_warmed_model(graphs, tmp_path):
    cache = AotCache(str(tmp_path))
    with pytest.raises(ValueError, match="not warmed"):
        cache.store(_model(graphs), 4)


# ---------------------------------------------------- serving wiring ----

def test_registry_cache_dir_boots_warm(graphs, tmp_path):
    """End to end through ServingRegistry(cache_dir=...): first registry
    pays the compiles and stores, second boots with zero compiles; both
    surface the outcome in telemetry and OpenMetrics."""
    import asyncio
    from repro.serve.registry import ServingRegistry

    async def boot():
        reg = ServingRegistry(cache_dir=str(tmp_path), max_batch=4)
        reg.register("sine", _model(graphs))
        cm = reg._entries["sine"].model
        async with reg:
            x = reg.quantize_input("sine", np.array([[1.0]], np.float32))
            y = await reg.infer("sine", x)
        return reg, cm, np.asarray(y)

    reg1, cold, y1 = asyncio.run(boot())
    assert cold.compile_events > 0
    assert reg1.cache_status()["stores"] == 1
    assert not reg1.cache_status()["boots"]["sine"]["hit"]

    reg2, warm, y2 = asyncio.run(boot())
    assert warm.compile_events == 0, warm.compile_log
    status = reg2.cache_status()
    assert status["hits"] == 1 and status["boots"]["sine"]["hit"]
    assert np.array_equal(y1, y2)

    tel = reg2.telemetry()
    assert tel["engines"]["sine"]["compile_events"] == 0
    assert tel["engines"]["sine"]["cache_events"]["hit"] > 0
    assert tel["aot_cache"]["hits"] == 1
    om = reg2.openmetrics()
    assert 'repro_engine_compiles_total{model="sine"} 0' in om
    assert 'repro_aot_cache_total{event="hits"} 1' in om


def test_coldstart_bench_skip_records(tmp_path, monkeypatch):
    """On backends without executable serialization the bench degrades to
    median_us-null skip records (the *_noninterpret contract) instead of
    failing the suite."""
    from benchmarks import bench_coldstart, common

    monkeypatch.setattr(bench_coldstart, "serialization_support",
                        lambda: (False, "SimulatedError: no export"))
    del common.RECORDS[:]
    bench_coldstart.main(fast=True)
    recs = {r["name"]: r for r in common.RECORDS}
    assert set(recs) == {
        "serve/sine_coldstart_cold_us", "serve/sine_coldstart_warm_us",
        "serve/person_coldstart_cold_us", "serve/person_coldstart_warm_us",
        "serve/sine_coldstart_warm_vs_cold"}
    for name, r in recs.items():
        assert r["median_us"] is None, name
        assert r["derived"].startswith("skipped:"), name
        assert set(r["stage_breakdown"]) == {"queue_wait_us", "pad_us",
                                             "device_us", "retry_us"}
    del common.RECORDS[:]


def test_audit_json_carries_fingerprint(graphs):
    """python -m repro.analysis stamps each model entry with the plan
    fingerprint the AOT cache cross-checks against (C005)."""
    from repro.analysis.__main__ import audit_plan
    plan = ExecutionPlan.build(copy.deepcopy(graphs["sine"]))
    rep = audit_plan("sine", plan, max_batch=2)
    assert rep.fingerprint == plan_fingerprint(plan)
    assert json.loads(json.dumps(rep.as_dict()))["fingerprint"] == \
        rep.fingerprint
