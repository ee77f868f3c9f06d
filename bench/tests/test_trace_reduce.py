"""The trace reduction on a small trace recorded on one TPU v5 lite chip
(``fixtures/probe.xplane.pb``, written by ``bench/tools/trace_probe.py``):
three rounds of a jitted matmul pair, the paged MLA decode kernel and a
10 ms host sleep, inside a ``bench.window`` annotation."""
import pathlib

import pytest

from bench import trace_reduce

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "probe.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce(trace_reduce.load(str(FIXTURE)))


def test_window_and_busy(red):
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.036082398)
    # busy is the union of op intervals: well under the window (three
    # 10 ms sleeps) and above the kernels' own time
    assert 0 < red["busy_s"] < 0.2 * red["window_s"]
    assert red["busy_s"] == pytest.approx(sum(red["op_s"].values()),
                                          rel=0.05)


def test_modules_and_kernel(red):
    secs, calls = trace_reduce.module_time(red, "jit_decode_step")
    assert calls == 3
    k = trace_reduce.kernel_time(red, "jit_decode_step", "%decode_attention")
    assert 0 < k <= secs
    assert k == pytest.approx(3 * 3.7e-6, rel=0.1)
    # the first matmul module ran before the host opened the window
    assert trace_reduce.module_time(red, "jit_step")[1] == 2


def test_gaps_are_named_by_host_annotations(red):
    gaps = red["breakdown"]["idle_gaps"]
    assert len(gaps) <= 10
    assert [g[0] for g in gaps[:3]] == ["sleep"] * 3
    assert all(g[1] > 0.010 for g in gaps[:3])
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def test_device_ops_name_their_module(red):
    ops = red["breakdown"]["device_ops"]
    assert any(name.startswith("jit_decode_step/%decode_attention")
               for name, _ in ops)
    assert all(" = " not in name for name, _ in ops)
