#!/usr/bin/env bash
# Tuned benchmark environment wrapper:
#
#   tools/bench_env.sh python -m benchmarks.run --no-interpret
#
# Sets the allocator knobs the serving benches are sensitive to, then
# execs the wrapped command. Each knob is applied only when the underlying
# artifact exists, and an already-set variable is never overridden:
#
# * tcmalloc LD_PRELOAD — the dispatch hot path churns small Python/numpy
#   allocations; tcmalloc's thread-cached freelists cut the malloc share
#   of per-request overhead. The large-alloc report threshold is raised
#   so arena/bucket allocations don't spam stderr into the CSV capture.
# * TF_CPP_MIN_LOG_LEVEL=4 — keeps XLA/TSL banner noise out of timing
#   runs' stderr.
set -euo pipefail

TCMALLOC=/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4
if [[ -z "${LD_PRELOAD:-}" && -e "$TCMALLOC" ]]; then
    export LD_PRELOAD="$TCMALLOC"
fi
export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD="${TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD:-60000000000}"
export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"

exec "$@"
