"""A cell run on the CPU: a throwaway copy of a configuration, served with
the XLA route (the Pallas kernels would run interpreted, too slowly) and a
small batch, written as new files the harness finds by name, the way a
later cell is added."""
import contextlib
import json
import os
from unittest import mock

import jax
import repro.core

from chipbench import bench, run

_HERE = bench.HERE


@contextlib.contextmanager
def cpu_cell(tmp_path, config="speech_tinyconv", traffic=None, files=(),
             max_batch=8, per_layer=()):
    """Yields a function running the throwaway cell ``zz.cpu`` through
    ``run.main`` (look for a chip skipped); removes its files after.
    ``config`` names a configuration file, or is a configuration itself."""
    cfg = (dict(config) if isinstance(config, dict)
           else bench.load_json("configs", config + ".json"))
    cfg.update(name="zz_cpu_" + cfg["name"])
    cfg["registry"] = dict(cfg["registry"])
    cfg["registry"].update(max_batch=max_batch)
    made = {os.path.join(_HERE, "configs", cfg["name"] + ".json"):
            json.dumps(cfg),
            os.path.join(_HERE, "traffic", "zz_cpu.json"):
            json.dumps(traffic or {"loop": "closed", "clients": 16})}
    made.update({os.path.join(_HERE, k): v for k, v in files})
    for path in made:
        assert not os.path.exists(path), path
    b = bench.benchmark()
    b["workloads"] = [{"name": "zz.cpu", "config": cfg["name"],
                       "traffic": "zz_cpu", "chips": 1, "why": "test"}]
    b["per_layer"] = list(per_layer) or [
        dict(m, workloads=["zz.cpu"]) for m in b["per_layer"]
        if "person.closed" in m.get("workloads", [])][:2]
    bpath = tmp_path / "BENCHMARK.json"
    bpath.write_text(json.dumps(b))
    model_class = repro.core.CompiledModel

    def xla_route(graph, **kw):
        return model_class(graph, **dict(kw, use_pallas=False))
    try:
        for path, text in made.items():
            with open(path, "w") as f:
                f.write(text)

        def go(capsys, seed=2**31 + 7, seconds=1, trace=0, fault=None):
            with mock.patch.object(repro.core, "CompiledModel", xla_route):
                rc = run.main(["--workload", "zz.cpu", "--seed", str(seed),
                               "--seconds", str(seconds),
                               "--trace", str(trace),
                               "--benchmark", str(bpath)],
                              chip_check=lambda n: jax.devices("cpu")[:n],
                              fault=fault)
            out, err = capsys.readouterr()
            assert rc == 0
            return json.loads(out.strip().splitlines()[-1]), err
        yield go
    finally:
        for path in made:
            if os.path.exists(path):
                os.remove(path)
