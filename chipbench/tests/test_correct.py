"""The comparison that decides ``correct``: sound int8 runs pass, the int4
control fails, and a run whose timed path is broken comes out false."""
import numpy as np
import pytest

from chipbench import bench, check, model
from chipbench.reference import Reference
from chipbench.tests.helpers import cpu_cell

ROWS = 256


def _program_rows(cfg, params, xf):
    from repro.core import CompiledModel

    qg = model.build_int8(cfg, params)
    t = qg.tensor(qg.inputs[0])
    xq = t.qparams.quantize(xf.reshape((len(xf),) + t.shape))
    cm = CompiledModel(qg, use_pallas=False)
    return np.asarray(cm.predict_q_many(xq, max_batch=64))


@pytest.mark.parametrize("config", ["speech_tinyconv", "person_mnv1_025"])
def test_int8_program_passes_and_int4_control_fails(config):
    cfg = bench.load_json("configs", config + ".json")
    params = model.make_weights(cfg)
    ref = Reference(cfg, params)
    xf = model.draw_inputs(cfg, np.random.default_rng(2**31 + 11), ROWS)
    want = ref.forward(xf)

    served = check.probabilities(cfg, _program_rows(cfg, params, xf))
    ok, nums = check.judge(cfg, check.numbers(served, want))
    assert ok, nums

    cal = model.draw_inputs(cfg, np.random.default_rng(
        cfg["calibration"]["seed"]), cfg["calibration"]["samples"])
    control = ref.forward(xf, bits=4, ranges=ref.calibrate(cal))
    ok, nums = check.judge(cfg, check.numbers(control, want))
    assert not ok, nums


def test_layer_list_builds_the_paper_models():
    from repro.configs.paper_models import build_person, build_speech

    for config, build in (("person_mnv1_025", build_person),
                          ("speech_tinyconv", build_speech)):
        cfg = bench.load_json("configs", config + ".json")
        g = model.build_int8(cfg, model.make_weights(cfg))
        want = build(batch=1)
        assert [o.op for o in g.ops] == [o.op for o in want.ops]
        assert [g.tensor(o.outputs[0]).shape for o in g.ops] == \
            [want.tensor(o.outputs[0]).shape for o in want.ops]
        assert [o.attrs for o in g.ops] == [o.attrs for o in want.ops]


def _alter_one_answer(cm):
    staged = cm.staged_infer

    def broken(rows):
        ys = np.array(staged(rows))
        ys[0] = ys[0][..., ::-1]  # one answer per flush: scores reversed
        return ys
    cm.staged_infer = broken


def _drop_half_the_batch(cm):
    staged = cm.staged_infer

    def broken(rows):
        ys = np.array(staged(rows))
        n = len(rows)
        # the second half answered as the zero rows the bucket pads with
        ys[n // 2:] = staged([np.zeros_like(rows[0])] * (n - n // 2))
        return ys
    cm.staged_infer = broken


@pytest.mark.parametrize("fault", [None, _alter_one_answer,
                                   _drop_half_the_batch])
def test_a_broken_timed_path_is_not_correct(tmp_path, capsys, fault):
    with cpu_cell(tmp_path) as go:
        res, err = go(capsys, fault=fault)
    assert res["correct"] is (fault is None), res["checks"]
    assert list(res)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check top1_disagree")
