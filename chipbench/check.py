"""The comparison that decides ``correct``.

The served answers of a sample of the window's requests, drawn from the
seed, are set against the plain reference (``chipbench.reference``) run on
the float rows the load generator quantized. An int8 program cannot match
a float32 pass exactly, so three numbers are compared, each with its limit
from the configuration's ``check`` entry. A row's gap is the widest
difference between its served and its reference class probabilities, in
steps of the int8 output (1/256):

* ``gap_p90_steps`` and ``gap_p99_steps``: the 90th and 99th percentiles
  of the row gaps over the sampled rows (rows wrong in bulk move the first,
  a few wrong rows the second);
* ``top1_disagree``: the share of sampled rows whose most likely class
  differs from the reference's.

The widest gap itself is not compared: it swings from seed to seed with the
one row whose activations leave the calibrated range. Each limit lies
between what sound int8 runs read and what the int4 control reads
(``PERF.md`` gives both readings).
"""
import numpy as np

CHECK_ROWS = 512  # requests compared per run


def sample(rng, answered: np.ndarray, n: int = CHECK_ROWS) -> np.ndarray:
    """Indices of up to ``n`` answered requests, drawn from ``rng``."""
    pick = np.flatnonzero(answered)
    if len(pick) > n:
        pick = np.sort(rng.choice(pick, n, replace=False))
    return pick


def probabilities(cfg, y_int8) -> np.ndarray:
    """Served int8 softmax rows as probabilities, by the output
    quantization the configuration states."""
    q = cfg["output_quant"]
    y = np.asarray(y_int8, np.float64).reshape(len(y_int8), -1)
    return (y - q["zero_point"]) * q["scale"]


def numbers(served_p: np.ndarray, ref_p: np.ndarray) -> dict:
    gap = np.abs(served_p - ref_p).max(axis=1) * 256.0
    return {"gap_p90_steps": float(np.percentile(gap, 90)),
            "gap_p99_steps": float(np.percentile(gap, 99)),
            "top1_disagree": float(np.mean(
                served_p.argmax(1) != ref_p.argmax(1)))}


def judge(cfg, nums: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) for the numbers the
    configuration's ``check`` entry limits; every one must be at or under
    its limit. A run with nothing to compare is not correct."""
    out = {k: {"value": nums.get(k), "limit": lim}
           for k, lim in cfg["check"].items()}
    ok = bool(nums) and all(v["value"] is not None and v["value"] <= v["limit"]
                            for v in out.values())
    return ok, out
