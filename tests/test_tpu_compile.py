"""Compile the Pallas kernels for a described TPU v5e, without a chip.

Interpret mode runs a kernel body in Python and accepts things Mosaic
refuses (an int32 x int32 MXU contraction, a strided slice of an
in-register value, a strided load of int8 data). These cases compile the
kernels with ``interpret=False`` for one chip of a described ``v5e:2x2``
topology, at the paper models' real shapes, so such a refusal fails here
instead of on the chip. Nothing runs: results are checked by the
interpret-mode tests, and a compile that passes is not a chip run.

The topology is described inside a fixture: only the worker that runs
this file loads the TPU compiler, and every worker collects the same
tests.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import compile_cache
from repro.kernels import qdwconv as _dw
from repro.kernels import qmatmul as _qm


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one, so keep the cache out of it
    with compile_cache.disabled():
        yield SingleDeviceSharding(topo.devices[0])


def _qmatmul(m, k, n):
    def lower(spec):
        f = jax.jit(lambda *a: _qm.qmatmul(*a, lo=0.0, hi=6.0, n_true=n - 1,
                                           interpret=False))
        return f.lower(spec((m, k), jnp.int8), spec((k, n), jnp.int8),
                       *_consts(spec, n))
    return lower


def _qdwconv(hw, c, stride):
    h, w = hw
    oh, ow = (h - 3) // stride + 1, (w - 3) // stride + 1

    def lower(spec):
        f = jax.jit(lambda *a: _dw.qdwconv(
            *a, stride=(stride, stride), out_hw=(oh, ow), lo=0.0, hi=6.0,
            c_true=c - 8, interpret=False))
        return f.lower(spec((1, h, w, c), jnp.int8), spec((3, 3, c), jnp.int8),
                       *_consts(spec, c))
    return lower


def _consts(spec, n):
    """bias_term, rescale (float32), w_sum_zx, const_off, z_w (int32)."""
    return ([spec((n,), jnp.float32)] * 2 + [spec((n,), jnp.int32)] * 3)


# (M, K, N) and (padded H×W, lanes, stride) as the planned route hands them
# to the kernels; conv shapes are after im2col / SAME pre-padding. The thin
# entry convs read their one real input lane (K = 80 and 9, rounded up to
# 128); ``speech_conv_im2col`` is the same 10x8 conv on 128 input lanes, the
# layout a conv fed by a planned op keeps.
CASES = {
    "sine_fc": _qmatmul(128, 128, 128),
    "speech_conv_im2col": _qmatmul(512, 10240, 128),
    "speech_conv_im2col_thin": _qmatmul(512, 128, 128),
    "person_conv0_im2col_thin": _qmatmul(2304, 128, 128),
    "person_pw12": _qmatmul(128, 256, 256),
    "person_dw0_stride1": _qdwconv((50, 50), 128, 1),
    "person_dw1_stride2": _qdwconv((49, 49), 128, 2),
    "person_dw11_stride2": _qdwconv((7, 7), 128, 2),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = CASES[case](spec).compile()
    assert "tpu_custom_call" in compiled.as_text()
    print(case, compiled.memory_analysis())


def _conv_dw_model():
    """A quantized conv -> depthwise conv -> FC graph on the Pallas route."""
    import numpy as np

    from repro.core import CompiledModel
    from repro.core.builder import GraphBuilder
    from repro.core.quantize import quantize_graph

    rng = np.random.default_rng(0)
    b = GraphBuilder("conv-dw")
    x = b.input("x", (1, 8, 8, 1))
    h = b.conv2d(x, rng.normal(0, .5, (3, 3, 1, 4)).astype("f"),
                 rng.normal(size=4).astype("f"), stride=(2, 2),
                 fused="RELU6")
    h = b.depthwise_conv2d(h, rng.normal(0, .5, (3, 3, 4, 1)).astype("f"),
                           rng.normal(size=4).astype("f"), fused="RELU6")
    h = b.reshape(h, (1, 64))
    b.output(b.fully_connected(h, rng.normal(0, .5, (64, 2)).astype("f"),
                               None))
    qg = quantize_graph(b.build(), [rng.normal(size=(1, 8, 8, 1)).astype("f")
                                    for _ in range(2)])
    return CompiledModel(qg, use_pallas=True)


#: Scopes of the graph ops that run device work, as ``ExecutionPlan.lower``
#: names them in every op's ``op_name`` metadata.
SCOPES = ("00_conv_2d", "01_depthwise_conv_2d", "03_fully_connected")


def test_bucket_executable_names_are_stable():
    """A bucket executable is the module ``jit_serve_<graph>`` and each of
    its ops carries its graph op's scope in ``op_name`` (here on the CPU,
    kernels in interpret mode)."""
    cm = _conv_dw_model()
    text = cm.compile_batched(2).as_text()
    assert text.startswith("HloModule jit_serve_conv_dw_int8,"), text[:80]
    for scope in SCOPES:
        assert f'op_name="jit(serve_conv_dw_int8)/{scope}/' in text, scope


def test_kernels_keep_their_names_on_v5e(one_chip):
    """Compiled for a described v5e, each Pallas kernel of a bucket
    executable is an HLO op named by its ``pallas_call`` name — what the
    benchmark's kernel classes are found by — under its graph op's scope."""
    import re

    from repro.kernels import ops as kops

    cm = _conv_dw_model()
    specs = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
             for s in cm.exec_plan.batched_input_specs(2)]
    kops.set_interpret(False)
    try:
        text = jax.jit(cm.exec_plan.lower(batched=True)).lower(
            *specs).compile().as_text()
    finally:
        kops.set_interpret(None)
    kernels = re.findall(
        r'%(\w+)\.\d+ = .*custom_call_target="tpu_custom_call"'
        r'.*op_name="jit\(serve_conv_dw_int8\)/(\w+)/', text)
    assert sorted(kernels) == [("qdwconv", SCOPES[1]), ("qmatmul", SCOPES[0]),
                               ("qmatmul", SCOPES[2])], kernels
