#!/usr/bin/env python3
"""Split one cell's served flushes into their phases, on the chip.

    python3 chipbench/phases.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``run.py --trace 1`` does and, from the same profile,
reads the program's own spans (``chipbench/spans.py``) over the traced
window: each phase's time per flush, the device's idle time inside a
flush, the share of a flush no phase covers, and the ten longest idle gaps
labelled by the innermost program span under each. ``run.py``'s output is
printed as it comes; the last line of standard output is one more JSON
object with these readings.
"""
import json
import os
import sys
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import run, spans, trace  # noqa: E402


def main(argv=None, **kw) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    got = {}
    load = trace.load

    def load_both(log_dir):
        tr = load(log_dir)
        got["spans"] = spans.load(log_dir)
        got["tr"] = tr
        return tr

    class Run(run.Run):
        def __init__(self, **fields):
            super().__init__(**fields)
            got["run"] = self

    with mock.patch.object(trace, "load", load_both), \
            mock.patch.object(run, "Run", Run):
        rc = run.main(argv + ["--trace", "1"], **kw)
    if rc or "run" not in got:
        return rc or 1
    tr, counters = got["tr"], got["run"].counters
    lo, hi = run._traced_span(tr)
    events = next((evs for evs in tr["device"].values() if evs), [])
    red = spans.reduce(events, got["spans"], lo, hi)
    flushes = counters["batches"]
    out = {"flushes": flushes, "reduced": red,
           "stage_ms_per_flush": spans.stage_ms_per_flush(red, flushes),
           "fetch_ms_per_flush": spans.fetch_ms_per_flush(red, flushes),
           "idle_in_flush": spans.idle_in_flush(red)}
    if red is not None and flushes:
        out["phase_ms_per_flush"] = {n: 1e3 * s / flushes
                                     for n, s in red["phase_s"].items()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
