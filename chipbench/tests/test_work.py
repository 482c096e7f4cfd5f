"""True operations and bytes per kernel class, against hand counts."""
import os

import pytest

from chipbench import bench, model
from chipbench.run import Run

QMATMUL = bench.load_file_module("work", "qmatmul.py")
QDWCONV = bench.load_file_module("work", "qdwconv.py")
PEAKS = bench.load_json("peaks.json")["TPU v5 lite"]


def _layers(cfg_name):
    cfg = bench.load_json("configs", cfg_name + ".json")
    return {layer["name"]: (layer, x, y)
            for layer, x, y in model.layer_shapes(cfg)}


def test_person_conv0_by_hand():
    layer, x, y = _layers("person_mnv1_025")["conv0"]
    assert (x, y) == ((96, 96, 1), (48, 48, 8))
    ops, act, call = QMATMUL.work(layer, x, y)
    assert ops == 2 * 48 * 48 * 8 * 3 * 3 * 1        # 331,776: K=9, not 1152
    assert act == 96 * 96 + 48 * 48 * 8
    assert call == 3 * 3 * 1 * 8 + 8 * 8


def test_person_dw1_by_hand():
    layer, x, y = _layers("person_mnv1_025")["dw1"]
    assert (x, y) == ((48, 48, 16), (24, 24, 16))  # stride 2
    ops, act, call = QDWCONV.work(layer, x, y)
    assert ops == 2 * 24 * 24 * 16 * 9               # 16 channels, not 128
    assert act == 48 * 48 * 16 + 24 * 24 * 16
    assert call == 9 * 16 + 8 * 16


def test_speech_conv_and_fc_by_hand():
    layers = _layers("speech_tinyconv")
    layer, x, y = layers["conv"]
    assert (x, y) == ((49, 40, 1), (25, 20, 8))
    assert QMATMUL.work(layer, x, y)[0] == 2 * 25 * 20 * 8 * 80  # K=80
    layer, x, y = layers["fc"]
    assert QMATMUL.work(layer, x, y) == (2 * 4000 * 4, 4000 + 4,
                                         4000 * 4 + 8 * 4)


def test_whole_models_by_hand():
    # person: conv0 + 13 dw/pw blocks + fc, plus the pool and the softmax,
    # which no kernel class claims
    cfg = bench.load_json("configs", "person_mnv1_025.json")
    kernels = sum(mod.work(layer, x, y)[0]
                  for mod in (QMATMUL, QDWCONV)
                  for layer, x, y in model.layer_shapes(cfg)
                  if layer["op"] in mod.LAYERS)
    assert 13e6 < kernels < 16e6  # about 14 MOP per image
    pool = 3 * 3 * 256 + 256      # 3x3 window over 256 channels, one divide
    softmax = 4 * 2
    assert model.ops_per_row(cfg) == kernels + pool + softmax
    speech = bench.load_json("configs", "speech_tinyconv.json")
    assert model.ops_per_row(speech) == (2 * 25 * 20 * 8 * 80 + 2 * 4000 * 4
                                         + 4 * 4)


def test_a_layer_kind_claimed_by_two_work_files_is_refused():
    path = os.path.join(bench.HERE, "work", "zz_dup.py")
    assert not os.path.exists(path)
    cell = bench.Cell(bench.benchmark(), "person.closed")
    try:
        with open(path, "w") as f:
            f.write('KERNELS = ("zz",)\nLAYERS = ("conv",)\n')
        with pytest.raises(ValueError, match="claimed by"):
            cell.work_classes()
    finally:
        os.remove(path)
    assert set(cell.work_classes()) == {"qmatmul", "qdwconv"}


def _run(ops, nbytes, dev_s):
    return Run(trace={"class_s": {"k": dev_s}}, work={"k": (ops, nbytes)},
               peaks=PEAKS)


@pytest.mark.parametrize("ops,nbytes", [(393e9, 1e6), (1e6, 819e6),
                                        (3.93e11, 8.19e8)])
def test_roofline_share_never_passes_100_at_or_above_the_bound(ops, nbytes):
    least = max(ops / PEAKS["int8_ops_per_s"],
                nbytes / PEAKS["hbm_bytes_per_s"])
    assert _run(ops, nbytes, least).roofline("k") == pytest.approx(100.0)
    for slower in (1.0001, 2.0, 1e3):
        assert _run(ops, nbytes, least * slower).roofline("k") < 100.0


def test_roofline_is_silent_without_device_time():
    assert _run(1e9, 1e6, 0.0).roofline("k") is None
    assert Run(trace=None, work={}, peaks=PEAKS).roofline("k") is None
