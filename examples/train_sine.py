"""Paper Table 5 (left): the sine predictor, end to end.

Trains the paper's 1-16-16-1 ReLU MLP on sin(x), quantizes it to int8,
deploys it through both engines, and evaluates MSE / RMSE with the paper's
protocol (1000 test samples, U(-0.1, 0.1) additive noise).

  PYTHONPATH=src python examples/train_sine.py
"""
import numpy as np

from benchmarks.bench_accuracy import sine_metrics, train_sine_weights
from repro import compile_cache
from repro.configs.paper_models import build_sine
from repro.core import CompiledModel
from repro.core.quantize import quantize_graph


def main():
    print("training the 1-16-16-1 sine MLP ...")
    res = sine_metrics()
    print(f"{'engine':16s} {'MSE':>8s} {'RMSE':>8s}   (paper: 0.0154/0.1241)")
    for k in ("float", "int8_interp", "int8_compiled"):
        print(f"{k:16s} {res[k]['mse']:8.4f} {res[k]['rmse']:8.4f}")
    print("int8 engines bit-identical:", res["engines_equal"])

    # deploy a single-sample predictor (the MCU interface)
    weights = train_sine_weights(steps=1000)
    g = build_sine(weights, batch=1)
    rng = np.random.default_rng(0)
    qg = quantize_graph(
        g, [rng.uniform(0, 2 * np.pi, (1, 1)).astype("f")
            for _ in range(64)])
    cm = CompiledModel(qg)
    cm.compile()
    for xv in (0.5, 1.57, 3.14, 4.71):
        y = float(np.asarray(cm.predict(np.array([[xv]], "f"))))
        print(f"predict sin({xv:4.2f}) = {y:+.3f}   (true {np.sin(xv):+.3f})")


if __name__ == "__main__":
    compile_cache.enable()
    main()
