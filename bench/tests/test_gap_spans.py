"""``bench/tools/gap_spans.py``: idle gaps named by the innermost program
span open at their midpoint, on hand-made spans, on the recorded fixture
(which holds no program span), and on the spans of a CPU profile."""
import glob
import pathlib

import pytest

from bench import common, trace_reduce
from bench.tools import gap_spans

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "probe.xplane.pb"


def test_innermost_span_names_each_gap():
    spans = [(0, 100, "producer.step", 0), (10, 40, "paged.build", 1),
             (60, 90, "paged.wait", 1), (5, 95, "producer.lock_wait", 0),
             (200, 300, "train.grad_step", 0)]
    gaps = [(62, 88), (20, 30), (120, 180), (96, 98), (45, 55)]
    out = gap_spans.attribute((0, 400), gaps, spans, top=2)
    assert out["idle_s"] == pytest.approx(108e-9)
    # the deepest span open at the midpoint, and of equals the latest to
    # start (the lock wait inside the step's extent, on another thread)
    assert out["idle_share_by_span"] == pytest.approx(
        {"none": 60 / 108, "paged.wait": 26 / 108, "paged.build": 10 / 108,
         "producer.lock_wait": 10 / 108, "producer.step": 2 / 108})
    assert [g[0] for g in out["longest_gaps"]] == ["none", "paged.wait"]
    assert [g[1] for g in out["longest_gaps"]] == pytest.approx([60e-9,
                                                                 26e-9])
    assert gap_spans.innermost(50, spans) == "producer.lock_wait"
    assert gap_spans.innermost(97, spans) == "producer.step"
    assert out["spans"]["paged.wait"] == pytest.approx(
        {"count": 1, "total_s": 30e-9, "mean_ms": 30e-6})


def test_fixture_gaps_match_the_reduction_and_name_none():
    red = trace_reduce.reduce(trace_reduce.load(str(FIXTURE)))
    out = gap_spans.report(str(FIXTURE), top=3)
    assert out["window_s"] == pytest.approx(red["window_s"])
    assert out["idle_s"] == pytest.approx(red["window_s"] - red["busy_s"])
    assert out["idle_share_by_span"] == {"none": 1.0}
    assert out["spans"] == {}
    assert [g[1] for g in out["longest_gaps"]] == pytest.approx(
        [g[1] for g in red["breakdown"]["idle_gaps"][:3]])


def test_program_spans_nest_per_thread_in_a_cpu_profile(tmp_path):
    common.add_program_to_path()
    import threading

    import jax
    from repro.obs import trace as otrace

    def drive():
        with otrace.span("producer.step"):
            with otrace.span("paged.wait"):
                pass
        with otrace.span("producer.lock_wait"):
            pass

    with jax.profiler.trace(str(tmp_path)):
        th = threading.Thread(target=drive)
        th.start()
        th.join()
        with otrace.span("train.grad_step"):
            pass
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = gap_spans.program_spans(trace_reduce.load(path[0]))
    depth = {name: d for _, _, name, d in spans}
    assert depth == {"producer.step": 0, "paged.wait": 1,
                     "producer.lock_wait": 0, "train.grad_step": 0}
    step = next(sp for sp in spans if sp[2] == "producer.step")
    wait = next(sp for sp in spans if sp[2] == "paged.wait")
    assert step[0] <= wait[0] and wait[1] <= step[1]
