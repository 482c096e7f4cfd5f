#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, by a sweep on the chip.

    python3 chipbench/sweep.py --workload <cell> --seed <n> [--seconds 5]
        [--fractions 0.3,0.5,...] [--out <file.json>]

One process: the cell's model is built and warmed once, a closed loop of
128 clients gives the capacity C, then Poisson windows run at each fraction
of C. A rate is sustained when no request failed or was shed and the
backlog did not grow: the median latency of the window's last fifth stays
within 1.2 times that of its first fifth. The knee is the highest sustained
rate; the cell's traffic file takes 0.8 of it, written by hand. Not part of
a benchmark run.
"""
import argparse
import asyncio
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def window_stats(win, seconds) -> dict:
    import numpy as np

    from chipbench.bench import end_to_end, percentile

    rec = win.arrays()
    out = end_to_end(rec, seconds)
    inside = rec["start"] < seconds
    lat = (rec["end"] - rec["start"])[inside & rec["ok"]]
    fifth = max(1, len(lat) // 5)
    out.update(
        requests=int(inside.sum()), failed=int((~rec["ok"] & inside).sum()),
        gen_late_p99_ms=1e3 * percentile((rec["sent"] - rec["start"])[inside],
                                         99),
        first_fifth_p50_ms=1e3 * float(np.median(lat[:fifth])),
        last_fifth_p50_ms=1e3 * float(np.median(lat[-fifth:])))
    out["sustained"] = (out["failed"] == 0 and out["last_fifth_p50_ms"]
                        <= 1.2 * out["first_fifth_p50_ms"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--fractions", default="0.3,0.5,0.6,0.7,0.8,0.9,1.0,1.1")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from chipbench import run
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    import numpy as np

    from repro import compile_cache

    from chipbench import bench, model
    from chipbench.bench import Cell, Window

    cell = Cell(bench.benchmark(), args.workload)
    dev = run.require_chip(cell.chips)[0]
    compile_cache.enable()
    cfg, name = cell.cfg, cell.cfg["name"]
    reg, _ = bench.build_registry(cell, model.make_weights(cfg))
    rng = np.random.default_rng(args.seed)
    xq = [reg.quantize_input(name, x)
          for x in model.draw_inputs(cfg, rng, bench.POOL_ROWS)]
    order = rng.permutation(bench.POOL_ROWS)
    closed = bench.load_file_module("loops", "closed.py")
    poisson = bench.load_file_module("loops", "poisson.py")
    rows = []

    async def sweep():
        async with reg:
            await run.warm_buckets(reg, name, xq,
                                   cfg["registry"]["max_batch"])

            def window():
                return Window(lambda x: reg.submit(name, x), xq, order,
                              np.random.default_rng([args.seed, len(rows)]),
                              args.seconds)
            win = window()
            await win.run(closed, {"clients": 128})
            cap = window_stats(win, args.seconds)
            rows.append(dict(cap, loop="closed", clients=128))
            print(json.dumps(rows[-1]), flush=True)
            for f in map(float, args.fractions.split(",")):
                win = window()
                rate = f * cap["rps"]
                await win.run(poisson, {"rate_rps": rate})
                rows.append(dict(window_stats(win, args.seconds),
                                 loop="poisson", fraction=f, rate_rps=rate))
                print(json.dumps(rows[-1]), flush=True)

    asyncio.run(sweep())
    knee = max((r["rate_rps"] for r in rows[1:] if r["sustained"]),
               default=None)
    summary = {"workload": args.workload, "device": dev.device_kind,
               "knee_rps": knee, "rate_at_0.8": knee and 0.8 * knee,
               "rows": rows}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
