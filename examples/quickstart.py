"""Quickstart: author a small CNN, quantize it, and run it through BOTH
MicroFlow-JAX engines — the interpreter baseline (TFLM architecture) and the
AOT compiled engine (MicroFlow architecture) — then compare memory plans.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro import compile_cache
from repro.core import CompiledModel, Interpreter
from repro.core import graph as G
from repro.core.builder import GraphBuilder
from repro.core.memory import memory_report
from repro.core.quantize import quantize_graph


def main():
    rng = np.random.default_rng(0)

    # 1. Author a float model (normally this comes from your training code).
    b = GraphBuilder("quickstart_cnn")
    x = b.input("image", (1, 16, 16, 3))
    h = b.conv2d(x, rng.normal(0, 0.3, (3, 3, 3, 8)).astype("f"),
                 rng.normal(size=8).astype("f"), stride=(2, 2),
                 padding="SAME", fused="RELU6")
    h = b.depthwise_conv2d(h, rng.normal(0, 0.3, (3, 3, 8, 1)).astype("f"),
                           rng.normal(size=8).astype("f"), padding="SAME",
                           fused="RELU")
    h = b.average_pool2d(h, (8, 8))
    h = b.reshape(h, (1, 8))
    h = b.fully_connected(h, rng.normal(0, 0.3, (8, 4)).astype("f"), None)
    h = b.softmax(h)
    b.output(h)
    fg = b.build()

    # 2. Post-training int8 quantization (Eq. 1) with representative data.
    rep = [rng.normal(0, 1, (1, 16, 16, 3)).astype("f") for _ in range(16)]
    qg = quantize_graph(fg, rep)
    print(f"quantized: {len(qg.ops)} ops, weights {qg.weight_bytes} B")

    # 3. Save / load the model (our FlatBuffers-equivalent format).
    G.save(qg, "/tmp/quickstart.mfg")
    qg = G.load("/tmp/quickstart.mfg")

    # 4. Run through both engines.
    x = rng.normal(0, 1, (1, 16, 16, 3)).astype("f")
    interp = Interpreter(qg)                    # TFLM-style baseline
    compiled = CompiledModel(qg)                # MicroFlow-style AOT
    compiled.compile()                          # the "target binary"
    pallas = CompiledModel(qg, use_pallas=True)  # TPU kernels (interpret on CPU)

    yi = interp.invoke(x)
    yc = compiled.predict(x)
    yp = pallas.predict(x)
    print("interpreter:", np.round(yi, 4))
    print("compiled:   ", np.round(yc, 4))
    print("pallas:     ", np.round(yp, 4))
    assert np.array_equal(yi, yc) and np.array_equal(yc, yp)
    print("engines agree bit-exactly ✓")

    # 5. The paper's memory story (Figs. 9/10): arena vs ownership stack.
    rep_ = memory_report(qg)
    print(f"weights          : {rep_.weight_bytes:7d} B")
    print(f"interpreter arena: {rep_.arena_bytes:7d} B  (held all inference)")
    print(f"compiled peak    : {rep_.stack_peak_bytes:7d} B  (transient)")
    print(f"folded constants : {rep_.folded_const_bytes:7d} B  (compile-time)")


if __name__ == "__main__":
    compile_cache.enable()
    main()
