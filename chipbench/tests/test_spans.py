"""The program's spans reduced against the device, on small synthetic
traces (times in ns), and read from a real CPU profile."""
import json
from unittest import mock

import pytest

from chipbench import phases, spans, trace
from chipbench.tests.helpers import cpu_cell
from chipbench.tests.test_trace import CLASSES, TR

# Two flushes; the first starts before the window [0, 100), the second
# ends after it. Device ops: [10, 40) and [30, 60) inside the first,
# [95, 120) in the second.
#   flush [-20, 62): stage_rows [-20, -5), stage_h2d [-5, 8), device
#                    [8, 58) with launch [8, 9) and fetch [9, 58),
#                    stage_rezero [58, 61)
#   flush [84, 130): stage_rows [84, 86), stage_h2d [86, 90), device
#                    [90, 128) with fetch [91, 128)
PROG = [("flush", -20, 62), ("stage_rows", -20, -5), ("stage_h2d", -5, 8),
        ("device", 8, 58), ("launch", 8, 9), ("fetch", 9, 58),
        ("stage_rezero", 58, 61),
        ("flush", 84, 130), ("stage_rows", 84, 86), ("stage_h2d", 86, 90),
        ("device", 90, 128), ("fetch", 91, 128)]
DEV_FLUSHES = [("fusion.1", 10, 40), ("qmatmul.2", 30, 60),
               ("qmatmul.2", 95, 120)]


def test_idle_inside_flush_spans_clips_to_the_window():
    # idle in [0, 100): [0, 10), [60, 95); inside flushes: [0, 10),
    # [60, 62), [84, 95)
    assert spans.idle(DEV_FLUSHES, 0, 100) == [(0, 10), (60, 95)]
    assert spans.idle_in_ns(DEV_FLUSHES, PROG, 0, 100) == 10 + 2 + 11
    # a window cut inside both flushes: idle [5, 10), [60, 90)
    assert spans.idle_in_ns(DEV_FLUSHES, PROG, 5, 90) == 5 + 2 + 6
    assert spans.idle_in_ns([], [], 0, 100) == 0


def test_phase_sums_per_flush_clip_to_the_window():
    red = spans.reduce(DEV_FLUSHES, PROG, 0, 100)
    # staging: stage_h2d [0, 8) + stage_rezero 3 + stage_rows 2 +
    # stage_h2d 4; the first stage_rows lies before the window
    assert spans.stage_ms_per_flush(red, 2) == pytest.approx(
        1e3 * (8 + 3 + 2 + 4) * 1e-9 / 2)
    # fetch: [9, 58) + [91, 100)
    assert spans.fetch_ms_per_flush(red, 2) == pytest.approx(
        1e3 * (49 + 9) * 1e-9 / 2)
    assert red["count"]["flush"] == 2 and "stage_rows" in red["count"]
    assert spans.idle_in_flush(red) == pytest.approx(23.0)
    assert red["window_s"] == pytest.approx(100e-9)


def test_self_share_counts_only_flushes_wholly_in_the_window():
    # the only flush wholly in [-20, 100) is the first: 82 ns, children
    # cover [-20, 61)
    assert spans.self_share(PROG, -20, 100) == pytest.approx(1 / 82)
    assert spans.self_share(PROG, 0, 100) is None


def test_gaps_are_labelled_by_the_innermost_span_covering_most_of_them():
    # idle in [0, 100): [0, 10) is 8/10 in stage_h2d, 1/10 launch and
    # fetch; [60, 95) is 2/35 in a flush, 11/35 in the next: no span
    got = spans.labelled_gaps(DEV_FLUSHES, PROG, 0, 100)
    assert got == [[spans.NO_SPAN, pytest.approx(35e-9)],
                   ["stage_h2d", pytest.approx(10e-9)]]
    assert spans.label(PROG, 20, 30) == "fetch"  # in fetch, device, flush
    assert spans.label(PROG, 85, 87) == "flush"  # half in each stage
    assert spans.label([], 0, 1) == spans.NO_SPAN


def test_readers_return_none_without_a_trace_or_flushes():
    red = spans.reduce(DEV_FLUSHES, PROG, 0, 100)
    assert spans.reduce(DEV_FLUSHES, [], 0, 100) is None
    assert spans.reduce(DEV_FLUSHES, PROG, 200, 300) is None
    for read in (spans.stage_ms_per_flush, spans.fetch_ms_per_flush):
        assert read(None, 3) is None
        assert read(red, 0) is None
    assert spans.idle_in_flush(None) is None


def test_the_trace_reduction_is_unchanged_by_program_spans():
    assert trace.reduce(dict(TR, program=PROG), 0, 100, CLASSES) == \
        trace.reduce(TR, 0, 100, CLASSES)


def test_a_profile_holds_both_kinds_of_span_apart(tmp_path):
    import jax

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace.HOST_PREFIX + "flush"):
            with jax.profiler.TraceAnnotation(spans.PROGRAM_PREFIX
                                              + "flush"):
                jax.numpy.ones(4).block_until_ready()
    assert [h[0] for h in trace.load(str(tmp_path))["host"]] == ["flush"]
    ((name, a, b),) = spans.load(str(tmp_path))
    assert name == "flush" and b > a


def test_phases_reads_a_cpu_run(tmp_path, capsys):
    import jax
    import repro.core

    model_class = repro.core.CompiledModel

    def xla_route(graph, **kw):
        return model_class(graph, **dict(kw, use_pallas=False))

    with cpu_cell(tmp_path, max_batch=4), \
            mock.patch.object(repro.core, "CompiledModel", xla_route):
        rc = phases.main(["--workload", "zz.cpu", "--seed", str(2**31 + 11),
                          "--seconds", "1", "--benchmark",
                          str(tmp_path / "BENCHMARK.json")],
                         chip_check=lambda n: jax.devices("cpu")[:n])
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["flushes"] > 0
    counts = res["reduced"]["count"]
    for name in ("flush", "stage_rows", "stage_h2d", "fetch",
                 "stage_rezero", "resolve"):
        assert counts[name] > 0, counts
    assert res["stage_ms_per_flush"] > 0 and res["fetch_ms_per_flush"] > 0
    # no device plane on the CPU: the whole window reads idle
    assert 0 < res["idle_in_flush"] <= 100
