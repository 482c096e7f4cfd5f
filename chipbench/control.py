#!/usr/bin/env python3
"""Read the comparison's numbers for the int4 control of a cell.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3

The control is the plain reference put in the program's place and computed
in int4 (``Reference.forward(bits=4)``), on rows drawn as a run of that
seed draws them and as many as a run compares. It must come out not
correct; the smallest reading of each number over the seeds is that
number's upper reading in ``PERF.md``. Not part of a benchmark run.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    import numpy as np

    from chipbench import bench, check, model
    from chipbench.reference import Reference

    cfg = bench.Cell(bench.benchmark(), args.workload).cfg
    params = model.make_weights(cfg)
    ref = Reference(cfg, params)
    cal = model.draw_inputs(cfg, np.random.default_rng(
        cfg["calibration"]["seed"]), cfg["calibration"]["samples"])
    ranges = ref.calibrate(cal)
    for seed in map(int, args.seeds.split(",")):
        xf = model.draw_inputs(cfg, np.random.default_rng(seed),
                               bench.POOL_ROWS)
        pick = np.random.default_rng([seed, 2]).choice(
            bench.POOL_ROWS, check.CHECK_ROWS)
        want = ref.forward(xf[pick])
        got = ref.forward(xf[pick], bits=4, ranges=ranges)
        ok, checks = check.judge(cfg, check.numbers(got, want))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": ok, "checks": checks}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
