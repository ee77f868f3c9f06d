"""CLI dispatch tests for ``python -m repro.launch.serve``.

The serving paths themselves are covered end-to-end by
tests/test_serving.py and tests/test_radix.py; these tests pin the
*flag wiring* — which backend ``main()`` dispatches to and with which
kwargs — by monkeypatching the four serve_* backends with recorders.
"""
import types

import numpy as np
import pytest

import repro.launch.serve as serve_mod


class Recorder:
    """Stands in for a serve_* backend: records the call, returns a
    canned result shaped like the real one."""

    def __init__(self, result):
        self.result = result
        self.calls = []

    def __call__(self, *args, **kwargs):
        self.calls.append((args, kwargs))
        return self.result


def _batch_result():
    out = types.SimpleNamespace(
        response_ids=np.zeros((8, 4), np.int32),
        response_len=np.zeros((8,), np.int32))
    return out, {"generated_tokens": 0, "wall_s": 1.0, "tok_per_s": 0.0}


def _paged_stats(spec=False, prefix=False):
    stats = {"generated_tokens": 0, "wall_s": 1.0, "tok_per_s": 0.0,
             "decode_steps": 0}
    if spec:
        stats.update(acceptance_rate=0.5, tokens_per_forward=2.0)
    if prefix:
        stats.update(prefix_hit_rate=0.25)
    return stats


def _requests_result(prefix=False):
    metrics = {"generated_tokens": 0, "ttft_p50_s": 0.01, "ttft_p99_s": 0.02,
               "tpot_p50_s": 0.001, "tpot_p99_s": 0.002, "tok_per_s": 0.0}
    stats = {"decode_steps": 0, "peak_pages": 0}
    if prefix:
        stats.update(prefix_hit_rate=0.25)
    return [], metrics, stats


def _shared_stats(spec=False):
    stats = {"generated_tokens": 0, "wall_s": 1.0, "tok_per_s": 0.0,
             "decode_steps": 0, "prefix_hit_rate": 0.5,
             "prompt_pages_saved": 3}
    if spec:
        stats.update(acceptance_rate=0.5)
    return stats


@pytest.fixture
def recorders(monkeypatch):
    recs = {
        "serve_batch": Recorder(_batch_result()),
        "serve_paged": Recorder(([], _paged_stats(spec=True, prefix=True))),
        "serve_requests": Recorder(_requests_result(prefix=True)),
        "serve_shared": Recorder(([], _shared_stats(spec=True))),
    }
    for name, rec in recs.items():
        monkeypatch.setattr(serve_mod, name, rec)
    # main() turns on the persistent compile cache for the process; the
    # test process keeps JAX's defaults
    monkeypatch.setattr(serve_mod, "enable_compile_cache", lambda: None)
    return recs


def _only(recs, name):
    for k, r in recs.items():
        assert len(r.calls) == (1 if k == name else 0), \
            "%s called %d times" % (k, len(r.calls))
    return recs[name].calls[0]


def test_default_dispatches_to_batch(recorders, capsys):
    serve_mod.main(["--seed", "3", "--max-new", "12"])
    args, kwargs = _only(recorders, "serve_batch")
    assert kwargs["seed"] == 3 and kwargs["max_new"] == 12
    assert len(args[1]) == 8        # --num-requests default
    assert "served 8 requests" in capsys.readouterr().out


def test_paged_engine_with_prefix_cache(recorders, capsys):
    serve_mod.main(["--engine", "paged", "--prefix-cache",
                    "--slots", "2", "--page-size", "8"])
    _, kwargs = _only(recorders, "serve_paged")
    assert kwargs["prefix_cache"] is True
    assert kwargs["num_slots"] == 2 and kwargs["page_size"] == 8
    assert kwargs["spec_k"] == 0    # no --spec -> spec plane off
    assert "prefix hit rate" in capsys.readouterr().out


def test_spec_flags_reach_paged_engine(recorders, capsys):
    serve_mod.main(["--engine", "paged", "--spec", "--spec-k", "3",
                    "--spec-draft", "model"])
    _, kwargs = _only(recorders, "serve_paged")
    assert kwargs["spec_k"] == 3 and kwargs["spec_draft"] == "model"
    assert "accept=" in capsys.readouterr().out


def test_rate_dispatches_to_request_driver(recorders, capsys):
    serve_mod.main(["--engine", "paged", "--rate", "2.5",
                    "--prefix-cache", "--num-requests", "5"])
    args, kwargs = _only(recorders, "serve_requests")
    assert kwargs["rate"] == 2.5 and kwargs["prefix_cache"] is True
    assert len(args[1]) == 5
    assert "TTFT p50=" in capsys.readouterr().out


def test_shared_system_dispatches_to_serve_shared(recorders, capsys):
    serve_mod.main(["--shared-system", "6", "--spec"])
    args, kwargs = _only(recorders, "serve_shared")
    assert len(args[2]) == 6        # one suffix per request
    assert kwargs["spec_k"] == 4    # --spec-k default rides --spec
    out = capsys.readouterr().out
    assert "shared-system x6" in out and "accept=" in out


def test_spec_requires_paged_engine(recorders):
    with pytest.raises(SystemExit):
        serve_mod.main(["--spec"])
    for rec in recorders.values():
        assert rec.calls == []


def test_rate_requires_paged_engine(recorders):
    with pytest.raises(SystemExit):
        serve_mod.main(["--rate", "1.0"])


def test_prefix_cache_requires_paged_engine(recorders):
    with pytest.raises(SystemExit):
        serve_mod.main(["--prefix-cache"])
