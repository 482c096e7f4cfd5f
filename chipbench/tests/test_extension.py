"""A configuration, a traffic mix, a loop kind and a per-layer metric are
added as new files plus new BENCHMARK.json entries; no harness file is
edited."""
import os
import subprocess
import sys

from chipbench import bench
from chipbench.tests.helpers import cpu_cell

LOOP = '''"""Bursts: ``clients`` requests at once, every ``every_s`` seconds."""
import asyncio


async def drive(win, params):
    tasks = []
    while win.open():
        due = win.now()
        tasks += [asyncio.ensure_future(win.request(due))
                  for _ in range(params["clients"])]
        await asyncio.sleep(params["every_s"])
    await asyncio.gather(*tasks)
'''
METRIC = '''"""Mean rows per flush in the traced window."""


def read(run):
    c = run.counters
    return c["batched_rows"] / c["batches"] if c["batches"] else None
'''


def test_new_files_make_a_new_cell(tmp_path, capsys):
    files = [("loops/zz_burst.py", LOOP),
             ("metrics/zz_rows_per_flush.py", METRIC)]
    metric = {"name": "zz_rows_per_flush", "unit": "rows", "better": "higher",
              "source": "program_counter", "layer": "admission and batching",
              "moves": "rps", "workloads": ["zz.cpu"]}
    with cpu_cell(tmp_path, traffic={"loop": "zz_burst", "clients": 6,
                                     "every_s": 0.01},
                  files=files, per_layer=[metric], max_batch=4) as go:
        res, _ = go(capsys, trace=1)
    assert res["correct"], res["checks"]
    assert res["metrics"]["zz_rows_per_flush"]["unit"] == "rows"
    assert 1.0 <= res["metrics"]["zz_rows_per_flush"]["value"] <= 4.0
    for name, _ in files:
        assert not os.path.exists(os.path.join(bench.HERE, name))


def test_without_a_chip_the_run_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(bench.HERE, "run.py"), "--workload",
         "person.closed", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr
