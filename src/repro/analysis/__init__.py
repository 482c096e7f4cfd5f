"""Compile-time plan auditor.

Five static passes over an :class:`repro.core.engine.ExecutionPlan`, none
of which executes the model:

* :mod:`.verify`  — graph verifier: shapes/dtypes/quant params propagate
  through the registry ``infer`` specs; TFLite PTQ invariants hold; every
  op has a lowering on the selected route.
* :mod:`.liveness` — arena liveness: per-tensor live ranges and the peak
  static arena bytes per route, cross-validated against a measured walk of
  the real lowerings and XLA's own analysis.
* :mod:`.retrace` — no-retrace auditor: the serving hot path cannot
  compile after ``warmup_batched`` (reachable cache keys ⊆ warmed keys),
  plus a weakly-typed-constant lint.
* :mod:`.budget`  — pad/copy budget: the exact number of pad primitives
  each route is allowed to trace, derived from the ``LayoutPlan``.
* :mod:`.fingerprint` — plan content address + AOT-cache manifest
  verification: the stable hash keying the persistent executable cache
  (:mod:`repro.serve.aotcache`) and the admission proof a replica runs
  before trusting a cache hit (findings ``C001``–``C005``).

``python -m repro.analysis`` audits the paper models and emits JSON /
markdown reports; ``--selftest`` proves the auditor still catches seeded
bad plans (CI runs both — see ``tools/check.sh``).
"""
from .budget import (PadBudget, audit_pads, conv_contractions,
                     measured_pads, pad_budget)
from .fingerprint import (build_manifest, environment_info,
                          plan_fingerprint, stage_key_id, verify_manifest)
from .liveness import (ArenaBound, arena_liveness, measure_live_bytes,
                       paged_peak_bytes, xla_advisory)
from .report import (ERROR, INFO, WARNING, AuditReport, Finding,
                     RouteReport, errors, to_json, to_markdown)
from .retrace import (audit_retrace, lint_weak_types, reachable_buckets,
                      reachable_chunk_batches, reachable_stage_keys,
                      warmed_buckets, warmed_stage_keys)
from .verify import static_output_bounds, verify_plan

__all__ = [
    "ERROR", "INFO", "WARNING",
    "ArenaBound", "AuditReport", "Finding", "PadBudget", "RouteReport",
    "arena_liveness", "audit_pads", "audit_retrace", "build_manifest",
    "conv_contractions",
    "environment_info", "errors", "lint_weak_types", "measure_live_bytes",
    "measured_pads", "pad_budget", "paged_peak_bytes", "plan_fingerprint",
    "reachable_buckets", "reachable_chunk_batches", "reachable_stage_keys",
    "stage_key_id", "to_json", "to_markdown", "verify_manifest",
    "verify_plan", "warmed_buckets", "warmed_stage_keys", "xla_advisory",
]
