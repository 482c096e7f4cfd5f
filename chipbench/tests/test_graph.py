"""Layers that name their inputs: the two configurations read exactly as
before, a branched configuration with skip connections runs as a cell and
is checked against its reference, and a malformed graph is refused."""
import copy
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import bench, check, model
from chipbench.reference import Reference, _fq_act, _fq_weight
from chipbench.run import _work_totals
from chipbench.tests.helpers import cpu_cell
from chipbench.tests.test_correct import _program_rows


def _conv(name, k, cout, stride, act, w_std, inputs=None):
    layer = {"op": "conv", "name": name, "kernel": [k, k], "cout": cout,
             "stride": stride, "padding": "SAME" if k > 1 else "VALID",
             "act": act, "w_std": w_std, "b_std": 0.1}
    return dict(layer, inputs=inputs) if inputs else layer


def _dw(name, act="relu6"):
    return {"op": "dw", "name": name, "kernel": [3, 3], "stride": 1,
            "padding": "SAME", "act": act, "w_std": 0.4714, "b_std": 0.1}


def _add(name, a, b):
    return {"op": "add", "name": name, "inputs": [a, b], "act": "none"}


# A stem of two branches, one of which reads the graph input by name; two
# inverted-residual blocks (1x1 expand, 3x3 depthwise, linear 1x1
# projection) whose adds join the block's input; and a block whose skip
# passes through its own 1x1 conv, a ResNet-style projection shortcut.
BRANCHED = {
    "name": "branched",
    "input_shape": [16, 16, 3],
    "num_classes": 4,
    "layers": [
        _conv("stem", 3, 8, 2, "relu6", 0.27),
        _conv("stem_side", 2, 8, 2, "relu6", 0.41, inputs=["input"]),
        _add("stem_add", "stem", "stem_side"),
        _conv("b1_expand", 1, 24, 1, "relu6", 0.5),
        _dw("b1_dw"),
        _conv("b1_project", 1, 8, 1, "none", 0.2),
        _add("b1_add", "b1_project", "stem_add"),
        _conv("b2_expand", 1, 24, 1, "relu6", 0.5),
        _dw("b2_dw"),
        _conv("b2_project", 1, 8, 1, "none", 0.2),
        _add("b2_add", "b1_add", "b2_project"),
        _conv("b3_expand", 1, 32, 1, "relu6", 0.5),
        _dw("b3_dw"),
        _conv("b3_project", 1, 16, 1, "none", 0.18),
        _conv("b3_shortcut", 1, 16, 1, "none", 0.35, inputs=["b2_add"]),
        _add("b3_add", "b3_project", "b3_shortcut"),
        {"op": "avgpool", "name": "pool", "window": [8, 8]},
        {"op": "flatten", "name": "reshape"},
        {"op": "fc", "name": "fc", "cout": 4, "act": "none", "w_std": 0.5,
         "b_std": 0.1},
        {"op": "softmax", "name": "softmax"},
    ],
    "input_dist": {"kind": "normal_per_row", "mean": [-1.0, 1.0],
                   "std": [0.25, 1.5]},
    "weight_seed": 5,
    # eight rows leave the input and the sums' ranges short: the int8
    # pass of the plain reference over the program's eight calibration
    # rows reads a gap_p90_steps of 40, as the program does; 64 rows cover
    # them (6, and 6 for the program)
    "calibration": {"samples": 64, "seed": 0},
    "registry": {"max_batch": 64, "max_delay_s": 0.002, "max_queue": 512},
    "output_quant": {"scale": 0.00390625, "zero_point": -128},
    "check": {"gap_p90_steps": 30.0, "gap_p99_steps": 55.0,
              "top1_disagree": 0.08},
}


def _digest(params) -> str:
    h = hashlib.sha256()
    for p in params:
        for k in sorted(p):
            a = p[k]
            h.update(k.encode())
            h.update(np.ascontiguousarray(a).tobytes())
            h.update(str(a.dtype).encode())
            h.update(str(a.shape).encode())
    return h.hexdigest()


# what the two configurations read before layers could name their inputs
PERSON_SHAPES = [
    ("conv0", (96, 96, 1), (48, 48, 8)), ("dw0", (48, 48, 8), (48, 48, 8)),
    ("pw0", (48, 48, 8), (48, 48, 16)), ("dw1", (48, 48, 16), (24, 24, 16)),
    ("pw1", (24, 24, 16), (24, 24, 32)), ("dw2", (24, 24, 32), (24, 24, 32)),
    ("pw2", (24, 24, 32), (24, 24, 32)), ("dw3", (24, 24, 32), (12, 12, 32)),
    ("pw3", (12, 12, 32), (12, 12, 64)), ("dw4", (12, 12, 64), (12, 12, 64)),
    ("pw4", (12, 12, 64), (12, 12, 64)), ("dw5", (12, 12, 64), (6, 6, 64)),
    ("pw5", (6, 6, 64), (6, 6, 128))] + [
    (f"{k}{i}", (6, 6, 128), (6, 6, 128))
    for i in range(6, 11) for k in ("dw", "pw")] + [
    ("dw11", (6, 6, 128), (3, 3, 128)), ("pw11", (3, 3, 128), (3, 3, 256)),
    ("dw12", (3, 3, 256), (3, 3, 256)), ("pw12", (3, 3, 256), (3, 3, 256)),
    ("avgpool", (3, 3, 256), (1, 1, 256)), ("reshape", (1, 1, 256), (256,)),
    ("fc", (256,), (2,)), ("softmax", (2,), (2,))]
SPEECH_SHAPES = [
    ("conv", (49, 40, 1), (25, 20, 8)), ("reshape", (25, 20, 8), (4000,)),
    ("fc", (4000,), (4,)), ("softmax", (4,), (4,))]
PINNED = {
    "person_mnv1_025": (
        "person.closed", PERSON_SHAPES, 14318344,
        "32403286907e38f4bda87ca8aab815bda4816bf2ff1125aee0779ded5d20792d",
        {"qdwconv": (10218700800, 1469295200),
         "qmatmul": (81402265600, 1563445600)}),
    "speech_tinyconv": (
        "speech.closed", SPEECH_SHAPES, 672016,
        "518efff01aed6e06b17d9d5cf7fe95ec856b3b851fde9098f52ceb0737871424",
        {"qmatmul": (4300800000, 65443200)}),
}


@pytest.mark.parametrize("config", sorted(PINNED))
def test_the_chain_configurations_read_as_before(config):
    cell, shapes, ops, digest, work = PINNED[config]
    cfg = bench.load_json("configs", config + ".json")
    got = model.layer_shapes(cfg)
    assert [(layer["name"], x, y) for layer, x, y in got] == shapes
    assert model.ops_per_row(cfg) == ops
    assert _digest(model.make_weights(cfg)) == digest
    assert _work_totals(bench.Cell(bench.benchmark(), cell), got,
                        {"batched_rows": 6400, "batches": 100}) == work


@pytest.mark.parametrize("bits", [0, 4])
def test_a_chain_reads_each_previous_output(bits):
    # the reference over the speech chain, and its control, equal a plain
    # loop that threads one value through the layers
    cfg = bench.load_json("configs", "speech_tinyconv.json")
    params = model.make_weights(cfg)
    ref = Reference(cfg, params)
    x = model.draw_inputs(cfg, np.random.default_rng(2**31 + 3), 16)
    ranges = ref.calibrate(x) if bits else None
    want = x
    if bits:
        want = _fq_act(want, *ranges[0], bits)
    for i, (layer, mod) in enumerate(ref.layers):
        p = params[i]
        if bits and "w" in p:
            p = dict(p, w=_fq_weight(p["w"], mod.WEIGHT_AXIS, bits)
                     .astype(np.float32))
        want = mod.ref(want, layer, p)
        if bits:
            want = _fq_act(want, *ranges[i + 1], bits)
    np.testing.assert_array_equal(ref.forward(x, bits=bits, ranges=ranges),
                                  np.asarray(want))


def test_branched_shapes_work_and_weights():
    shapes = {layer["name"]: (x, y)
              for layer, x, y in model.layer_shapes(BRANCHED)}
    assert shapes["stem_side"] == ((16, 16, 3), (8, 8, 8))
    assert shapes["stem_add"] == (((8, 8, 8), (8, 8, 8)), (8, 8, 8))
    assert shapes["b1_expand"] == ((8, 8, 8), (8, 8, 24))
    assert shapes["b3_shortcut"] == ((8, 8, 8), (8, 8, 16))
    assert shapes["pool"] == ((8, 8, 16), (1, 1, 16))
    qmatmul = bench.load_file_module("work", "qmatmul.py")
    layer = BRANCHED["layers"][6]
    assert qmatmul.work(layer, *shapes["b1_add"]) == (
        8 * 8 * 8, 3 * 8 * 8 * 8, 0)
    assert "add" in qmatmul.LAYERS
    params = model.make_weights(BRANCHED)
    assert params[2] == {} and params[14]["w"].shape == (1, 1, 8, 16)


def _hand_forward(params, x):
    """The branched configuration written out by hand in ``jax.numpy``."""
    p = {layer["name"]: w for layer, w in zip(BRANCHED["layers"], params)}
    hi = jax.lax.Precision.HIGHEST

    def conv(x, name, stride, padding, groups=1):
        w = p[name]["w"]
        if groups > 1:
            w = w.reshape(w.shape[:2] + (1, groups))
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, precision=hi) + p[name]["b"]

    def relu6(y):
        return jnp.clip(y, 0.0, 6.0)

    def block(x, i):
        y = relu6(conv(x, f"b{i}_expand", 1, "VALID"))
        y = relu6(conv(y, f"b{i}_dw", 1, "SAME", groups=y.shape[-1]))
        return conv(y, f"b{i}_project", 1, "VALID")

    s = (relu6(conv(x, "stem", 2, "SAME"))
         + relu6(conv(x, "stem_side", 2, "VALID")))
    a1 = block(s, 1) + s
    a2 = a1 + block(a1, 2)
    a3 = block(a2, 3) + conv(a2, "b3_shortcut", 1, "VALID")
    pooled = a3.mean(axis=(1, 2))
    return jax.nn.softmax(jnp.dot(pooled, p["fc"]["w"], precision=hi)
                          + p["fc"]["b"], axis=-1)


def test_branched_reference_is_the_hand_written_forward():
    params = model.make_weights(BRANCHED)
    x = model.draw_inputs(BRANCHED, np.random.default_rng(2**31 + 5), 32)
    got = Reference(BRANCHED, params).forward(x)
    with jax.default_device(jax.devices("cpu")[0]):
        want = np.asarray(_hand_forward(params, x))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert got.std(axis=0).min() > 0.01  # answers differ row by row


def test_branched_int8_program_passes_and_int4_control_fails():
    cfg = BRANCHED
    params = model.make_weights(cfg)
    ref = Reference(cfg, params)
    xf = model.draw_inputs(cfg, np.random.default_rng(2**31 + 11), 256)
    want = ref.forward(xf)
    served = check.probabilities(cfg, _program_rows(cfg, params, xf))
    ok, nums = check.judge(cfg, check.numbers(served, want))
    assert ok, nums
    cal = model.draw_inputs(cfg, np.random.default_rng(
        cfg["calibration"]["seed"]), cfg["calibration"]["samples"])
    control = ref.forward(xf, bits=4, ranges=ref.calibrate(cal))
    ok, nums = check.judge(cfg, check.numbers(control, want))
    assert not ok, nums


def test_branched_cell_runs_correct_on_the_cpu(tmp_path, capsys):
    with cpu_cell(tmp_path, config=BRANCHED) as go:
        res, _ = go(capsys)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def _broken(edit):
    cfg = copy.deepcopy(BRANCHED)
    edit(cfg["layers"])
    return cfg


@pytest.mark.parametrize("edit,message", [
    (lambda ls: ls[6].update(inputs=["b1_project", "nowhere"]),
     "layer 'b1_add' reads 'nowhere', which is not an earlier layer"),
    (lambda ls: ls[6].update(inputs=["b1_project", "b2_add"]),
     "layer 'b1_add' reads 'b2_add', which is not an earlier layer"),
    (lambda ls: ls[7].update(name="b1_dw"),
     r"layer 7 \('b1_dw'\): every layer needs a name of its own"),
    (lambda ls: ls[0].update(name="input"),
     r"layer 0 \('input'\): every layer needs a name of its own"),
    (lambda ls: ls[6].update(inputs=["b1_project", "input"]),
     r"layer 'b1_add': add needs inputs of one shape, not \(8, 8, 8\) and "
     r"\(16, 16, 3\)"),
    (lambda ls: ls[15].update(inputs=["b3_project", "b2_add"]),
     r"layer 'b3_add': add needs inputs of one shape, not \(8, 8, 16\) and "
     r"\(8, 8, 8\)"),
    (lambda ls: ls[6].update(inputs=["b1_project"]),
     "layer 'b1_add' reads 1 inputs; 'add' takes 2"),
    (lambda ls: ls[3].update(inputs=["stem", "stem_side"]),
     "layer 'b1_expand' reads 2 inputs; 'conv' takes 1"),
])
def test_a_malformed_graph_is_refused_naming_the_layer(edit, message):
    with pytest.raises(ValueError, match=message):
        model.layer_shapes(_broken(edit))


def test_loading_a_cell_refuses_a_malformed_graph(tmp_path):
    cfg = _broken(lambda ls: ls[6].update(inputs=["b1_project", "nowhere"]))
    with cpu_cell(tmp_path, config=cfg):
        b = bench.benchmark(str(tmp_path / "BENCHMARK.json"))
        with pytest.raises(ValueError, match="layer 'b1_add' reads 'nowhere'"):
            bench.Cell(b, "zz.cpu")
