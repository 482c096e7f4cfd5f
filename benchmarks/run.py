"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--fast]

Prints ``name,us_per_call,derived,backend`` CSV lines. When the runtime
and/or serve benches run, a machine-readable ``BENCH_runtime.json``
(name -> median_us/ci95/ratio/backend/pallas_interpret) is written
alongside the CSV so the perf trajectory is trackable across PRs
(``tools/check_bench.py`` gates on its name set). The roofline benchmark
(which spawns 512-device compiles) runs standalone:
  PYTHONPATH=src python -m benchmarks.bench_roofline
run.py includes its cached table when present.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

JSON_OUT = "BENCH_runtime.json"


def _record_family(name: str):
    """Which bench refreshes a JSON record. The dispatch microbench owns
    the ``serve/sine_dispatch*`` names (it can be re-run with ``--only
    dispatch`` without touching bench_serve's records, and vice versa);
    everything else maps by prefix."""
    if name.startswith("runtime/"):
        return "runtime"
    if name.startswith("memory/"):
        return "memory"
    if name.startswith("serve/sine_dispatch"):
        return "dispatch"
    if "_coldstart_" in name:
        return "coldstart"
    if name.startswith("serve/"):
        return "serve"
    return None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--json-out", default=JSON_OUT,
                    help="path for the runtime-bench JSON summary")
    ap.add_argument("--no-interpret", action="store_true",
                    help="force Pallas interpret=False for the whole run "
                         "when the backend can lower it (records then carry "
                         "pallas_interpret: false); degrades gracefully — "
                         "the dedicated *_noninterpret lane records an "
                         "explicit skip reason when unsupported")
    args = ap.parse_args()

    if args.no_interpret:
        from repro.kernels.ops import can_lower_noninterpret, set_interpret
        ok, reason = can_lower_noninterpret()
        if ok:
            set_interpret(False)
            print("# --no-interpret: backend lowers Pallas natively; "
                  "interpret=False forced for the whole run", file=sys.stderr)
        else:
            print(f"# --no-interpret: unsupported on this backend "
                  f"({reason}); interpret lanes unchanged, the "
                  f"*_noninterpret records carry the skip reason",
                  file=sys.stderr)

    from benchmarks import (bench_accuracy, bench_memory, bench_runtime,
                            bench_paging, bench_energy, bench_serve,
                            bench_dispatch, bench_coldstart, common)
    benches = {
        "accuracy": bench_accuracy.main,   # Table 5
        "memory": bench_memory.main,       # Figs. 9/10
        "runtime": bench_runtime.main,     # Fig. 11
        "paging": bench_paging.main,       # Sec. 4.3 / Fig. 6
        "energy": bench_energy.main,       # Table 6 (derived)
        "serve": bench_serve.main,         # dynamic batching vs serial
        "dispatch": bench_dispatch.main,   # per-request dispatch overhead
        "coldstart": bench_coldstart.main,  # AOT-cache boot, cold vs warm
    }
    del common.RECORDS[:]
    print("name,us_per_call,derived,backend")
    all_lines = []
    ran = []
    for name, fn in benches.items():
        if args.only and name not in args.only:
            continue
        t0 = time.time()
        all_lines += fn(fast=args.fast)
        ran.append(name)
        print(f"# bench {name} done in {time.time()-t0:.1f}s",
              file=sys.stderr)

    refreshed = {f for f in ("runtime", "memory", "serve", "dispatch",
                             "coldstart")
                 if f in ran}
    if refreshed:
        # Merge into an existing file: a partial run (--only runtime/serve)
        # refreshes only its own record family and preserves the others, so
        # iterating with --only can never truncate the committed baseline
        # that tools/check_bench.py gates on.
        doc = {}
        if os.path.exists(args.json_out):
            try:
                with open(args.json_out) as f:
                    doc = {k: v for k, v in json.load(f).items()
                           if _record_family(k) not in refreshed}
            except (ValueError, OSError):
                doc = {}
        doc.update({r["name"]: {"median_us": r["median_us"],
                                "ci95": r["ci95"], "ratio": r["ratio"],
                                "backend": r["backend"],
                                "pallas_interpret": r["pallas_interpret"],
                                "layout_plan": r["layout_plan"],
                                "slo_attainment": r["slo_attainment"],
                                "stage_breakdown": r["stage_breakdown"],
                                "executor_workers": r["executor_workers"],
                                "derived": r["derived"]}
                    for r in common.RECORDS
                    if _record_family(r["name"]) in refreshed})
        with open(args.json_out, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.json_out} ({len(doc)} entries)",
              file=sys.stderr)

    roofline = "results/roofline.csv"
    if os.path.exists(roofline) and (not args.only
                                     or "roofline" in args.only):
        print("# roofline (cached from benchmarks.bench_roofline):")
        with open(roofline) as f:
            for line in f:
                print("roofline/" + line.strip() + ",0.0,,")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
