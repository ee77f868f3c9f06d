"""``chip_smoke.py`` rehearsed on the CPU, in-process: every phase of the
chip run (trainer, serving, kernels) at reduced sizes, so the script cannot
rot between chip runs. Off the chip and without ``--rehearse`` it must stop
before doing any work."""
from __future__ import annotations

import importlib.util
import pathlib

import jax
import pytest

SCRIPT = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_chip(chip_smoke, capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert out == "" and "needs a TPU" in err


def test_rehearsal_runs_every_phase(chip_smoke, capsys):
    assert chip_smoke.main(["--rehearse"]) == 0
    out = capsys.readouterr().out
    for phase in ("trainer", "serving", "kernels"):
        assert f"[{phase}] phase ok" in out, out
    assert out.count("staleness=0") == chip_smoke.TRAIN_ITERATIONS
    assert "8/8 requests complete" in out
    # the rehearsal never prints the chip contract line
    assert '"ok"' not in out
