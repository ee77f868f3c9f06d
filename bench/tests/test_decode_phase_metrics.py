"""The rollout engine's decode-phase readers on scripted records: each is
its phase's seconds summed over the window's iterations, over their decode
steps, in ms, and nothing when there is nothing to read."""
import pytest

from bench import common

READERS = {"decode_host_ms.rl": "decode_host_s",
           "decode_launch_ms.rl": "decode_launch_s",
           "decode_wait_ms.rl": "decode_wait_s",
           "decode_gap_ms.rl": "decode_gap_s"}


def _iteration(steps, **phases):
    m = {"sync_gap": 0.0, "decode_steps": steps}
    m.update({k: phases.get(k, 0.0) for k in READERS.values()})
    return {"wall_time": 3.0, "train_time": 1.0, "metrics": m}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_is_phase_seconds_over_steps(name):
    key = READERS[name]
    rec = {"hist": [_iteration(385, **{key: 0.77}),
                    _iteration(115, **{key: 0.23})]}
    # (0.77 + 0.23) s over 500 steps
    assert common.metric_reader(name).read(rec) == pytest.approx(2.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_nothing_without_steps(name):
    read = common.metric_reader(name).read
    assert read({}) is None
    assert read({"hist": []}) is None
    assert read({"hist": [_iteration(0)]}) is None
    # a program without the phase clocks reports no decode steps either
    assert read({"hist": [{"metrics": {"sync_gap": 0.1}}]}) is None
