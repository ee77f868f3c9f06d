"""The comparison that decides ``correct`` fails when the timed path is
broken underneath, and passes when it is not. Each run skips the look for
a chip and drives a whole rehearsal of the cell (the program's reduced
sizes on the CPU) with one fault planted where the program produces its
result:

* ``state_unchanged``: the update returns the state it was given;
* ``half_batch``: every group trains on half its samples, the mean taken
  over the rest;
* ``token_altered``: a sampled token is replaced by the next id where the
  engine commits it.

The cells run on one chip, so no exchange between chips can be left out.
The float8 control (the reference one step below bfloat16) is read at the
same size and judged by the cell's limits as a run is: it comes out not
correct.
"""
import pytest

from bench import common, harness

RL = "grpo-async-internlm2"


def run(name, faults=(), controls=()):
    return harness.run_cell(name, 7, 2.0, False, rehearse=True,
                            faults=faults, controls=controls)


def failing(result):
    return sorted(k for k, c in result["checks"].items()
                  if c["limit"] is not None and not c["value"] <= c["limit"])


def test_rl_sound_run_is_correct_and_fp8_control_fails():
    r = run(RL, controls=("fp8",))
    assert r["correct"], r["checks"]
    ctl = r["info"]["controls"]["fp8"]
    assert ctl["correct"] is False, ctl


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_rl_fault_is_caught(fault):
    r = run(RL, faults=(fault,))
    assert not r["correct"]
    assert failing(r), r["checks"]


@pytest.mark.parametrize("stop_at_eos", [False, True])
def test_eos_ends_a_response_only_where_the_mix_says(monkeypatch,
                                                     stop_at_eos):
    """With ``stop_at_eos`` false every checked response runs to the cap,
    so every seed trains on the same sizes; this seed samples EOS early."""
    mix = common.traffic_spec
    monkeypatch.setattr(common, "traffic_spec", lambda n: dict(
        mix(n), stop_at_eos=stop_at_eos))
    r = run(RL)
    shortest = min(s for its in r["info"]["groups"] for _, s in its)
    cap = common.kind_module("grpo").REHEARSE["max_response_len"]
    assert (shortest < cap) == stop_at_eos, r["info"]["groups"]
    assert r["correct"], r["checks"]


def test_number_without_limit_is_an_error():
    lim = common.limits(RL)["numbers"]
    with pytest.raises(KeyError, match="no entry"):
        harness.compare({"new_gap": 0.0}, lim)
    with pytest.raises(ValueError, match="without a why"):
        harness.compare({"x": 0.0}, {"x": {"limit": None}})
    c = harness.compare({"x": 5.0}, {"x": {"limit": None, "why": "info"}})
    assert harness.passes(c)


def test_cell_without_limits_file_is_an_error():
    with pytest.raises(FileNotFoundError, match="no limits"):
        common.limits("no-such-cell")
