"""A configuration, a traffic mix, a per-layer metric and the plain
reference of a new architecture are added as new files plus new entries in
BENCHMARK.json, with no edit to an existing file, and the harness finds
each by its name. Also: without a chip, or without the program beside it,
or with a configuration whose reference has no file, a run exits non-zero
and prints no result.
"""
import json
import os
import pathlib
import shutil
import subprocess
import sys

from bench import common

ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _copy_checkout(root: pathlib.Path, with_program: bool = True):
    shutil.copytree(common.BENCH, root / "bench", ignore=shutil.ignore_patterns(
        "__pycache__", "fixtures", "tests"))
    shutil.copy(common.CHECKOUT / "BENCHMARK.json", root / "BENCHMARK.json")
    if with_program:
        (root / "src").symlink_to(common.CHECKOUT / "src")


def _run(root, *args, timeout=900):
    return subprocess.run([sys.executable, *args], cwd=root, env=ENV,
                          capture_output=True, text=True, timeout=timeout)


# a second architecture as a new file only: dense GQA with a seeded
# auxiliary term that moves no gradient, and a work count that marks itself
STAND_IN = """
import jax.numpy as jnp
import numpy as np

from bench.reference import dense_gqa
from bench.reference.dense_gqa import layout, next_token_logprobs  # noqa

AUX = float(np.random.default_rng(15).uniform(1.0, 2.0))
AUX_COEF = 0.5


def dims_from_spec(spec):
    return dict(dense_gqa.dims_from_spec(spec), aux_coef=AUX_COEF)


def dims_from_program(cfg):
    return dict(dense_gqa.dims_from_program(cfg), aux_coef=AUX_COEF)


def aux_loss(num, m, params, tokens):
    return jnp.float32(AUX)


def window_work(m, sizes, wv, t, counters):
    return dict(dense_gqa.window_work(m, sizes, wv, t, counters),
                reference="stand_in", engine_steps=counters["engine"][
                    "decode_steps"])
"""


def _add_cell(root: pathlib.Path, with_limits: bool = True,
              reference: str = "") -> None:
    """A configuration, a traffic mix, a metric and the cell's limits, as
    new files and new entries only; the configuration names ``reference``
    where one is given."""
    spec = common.config_spec("internlm2-20b-l1v8")
    spec["reference"] = reference or spec["reference"]
    (root / "bench/configs/internlm2-copy.json").write_text(json.dumps(spec))
    mix = common.traffic_spec("grpo_async_g8")
    mix.update(mode="sync", prompts_per_iteration=2)
    (root / "bench/traffic/grpo_sync_p2.json").write_text(json.dumps(mix))
    (root / "bench/metrics/groups_seen.py").write_text(
        "def read(rec):\n    return float(rec['attempted'])\n")
    if with_limits:
        lim = common.limits("grpo-async-internlm2")
        (root / "bench/limits/grpo-sync-copy.json").write_text(
            json.dumps(lim))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({
        "name": "internlm2-copy",
        "source": "https://huggingface.co/internlm/internlm2-20b",
        "file": "bench/configs/internlm2-copy.json",
        "reduced": ["num_hidden_layers", "vocab_size"], "why": "a copy"})
    b["workloads"].append({"name": "grpo-sync-copy",
                           "config": "internlm2-copy",
                           "traffic": "grpo_sync_p2", "chips": 1,
                           "why": "a copy with 2 prompts, synchronous"})
    b["per_layer"].append({"name": "groups_seen", "unit": "groups",
                           "better": "higher", "source": "host_clock",
                           "layer": "scheduler", "moves": "tpspd",
                           "workloads": ["grpo-sync-copy"]})
    b["end_to_end"][0]["workloads"].append("grpo-sync-copy")
    (root / "BENCHMARK.json").write_text(json.dumps(b))


def _rehearse(root, cell):
    return _run(root, "bench/run.py", "--workload", cell, "--seed", "3",
                "--seconds", "2", "--trace", "0", "--rehearse")


def test_new_cell_from_files_only(tmp_path):
    root = tmp_path / "checkout"
    _copy_checkout(root)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    _add_cell(root)
    r = _rehearse(root, "grpo-sync-copy")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "rehearsal finished, correct=True" in r.stderr
    assert '"attempted": ' in r.stderr
    r = _run(root, "-c", "from bench import common; "
             "print(common.metric_reader('groups_seen').read("
             "{'attempted': 7}))")
    assert r.stdout.strip() == "7.0", r.stderr
    after = {p: p.read_bytes() for p in before}
    assert after == before                      # no existing file edited


def test_new_cell_without_limits_fails(tmp_path):
    root = tmp_path / "checkout"
    _copy_checkout(root)
    _add_cell(root, with_limits=False)
    r = _rehearse(root, "grpo-sync-copy")
    assert r.returncode != 0
    assert "no limits for 'grpo-sync-copy'" in r.stderr
    assert "correct=True" not in r.stderr


def _rehearsal_line(stderr: str) -> dict:
    return json.loads(stderr.split("correct=True; ", 1)[1].splitlines()[0])


def test_new_architecture_from_files_only(tmp_path):
    """The configuration's ``reference`` selects the module: its sizes, its
    auxiliary term (which shifts the reference's loss by ``aux_coef * aux``
    each iteration and leaves the gradients as they are) and its work."""
    root = tmp_path / "checkout"
    _copy_checkout(root)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    (root / "bench/reference/stand_in.py").write_text(STAND_IN)
    _add_cell(root, reference="stand_in")
    r = _rehearse(root, "grpo-sync-copy")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "rehearsal finished, correct=True" in r.stderr, r.stderr[-3000:]
    info = _rehearsal_line(r.stderr)["info"]
    assert info["work"]["reference"] == "stand_in"
    assert info["work"]["engine_steps"] == info["decode_steps"] > 0
    assert info["work"]["train_flops"] > 0
    r = _run(root, "-c", "from bench.reference import stand_in as s; "
             "print(s.AUX_COEF * s.AUX)")
    shift = float(r.stdout)
    assert len(info["ref_loss"]) == 3
    for ref_loss, prog_loss in zip(info["ref_loss"], info["prog_loss"]):
        assert abs(ref_loss - prog_loss - shift) < 1e-4, (ref_loss,
                                                          prog_loss, shift)
    after = {p: p.read_bytes() for p in before}
    assert after == before                      # no existing file edited


def test_unknown_reference_is_an_error(tmp_path):
    root = tmp_path / "checkout"
    _copy_checkout(root)
    _add_cell(root, reference="no_such_arch")
    r = _rehearse(root, "grpo-sync-copy")
    assert r.returncode != 0
    assert "bench/reference/no_such_arch.py" in r.stderr, r.stderr[-3000:]
    assert "correct=True" not in r.stderr


def test_no_chip_no_result(tmp_path):
    root = tmp_path / "checkout"
    _copy_checkout(root)
    r = _run(root, "bench/run.py", "--workload", "grpo-async-internlm2",
             "--seed", "1", "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert "{" not in r.stdout


def test_without_program_no_result(tmp_path):
    root = tmp_path / "bare"
    _copy_checkout(root, with_program=False)
    r = _run(root, "bench/run.py", "--workload", "grpo-async-internlm2",
             "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse")
    assert r.returncode != 0
    assert "{" not in r.stdout
