"""The rollout engine's decode-step phases (``core/paged.py`` ``step``):
the always-on phase clocks that ``IterationStats.metrics`` carries per
iteration, and the spans that reach a JAX profiler trace on the
profiler's own clock (``obs/trace.py`` ``span``)."""
import glob
import sys
import threading
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData
from jaxlib._profiler import TraceMe

from repro.configs import get_config, reduced_config
from repro.configs.base import RLConfig
from repro.core.engine import InferenceInstance
from repro.core.paged import PagedGroupEngine
from repro.launch.train import build_pipeline
from repro.models import init
from repro.obs import trace as otrace

PHASES = ("decode_host_s", "decode_launch_s", "decode_wait_s",
          "decode_gap_s")
STEP_SPANS = ("paged.admit", "paged.build", "paged.launch", "paged.wait",
              "paged.commit")


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    yield
    otrace.uninstall()


@pytest.fixture(scope="module")
def cfg():
    return reduced_config(get_config("llama3.2-3b"))


# the per-token drain, the pipelined fused drain (at 4 and at RLConfig's
# default), and speculative decode
ENGINES = {"drain1": {"decode_drain_interval": 1},
           "drain4": {"decode_drain_interval": 4}, "default": {},
           "spec": {"spec_decode": True, "spec_k": 2}}


@pytest.fixture(scope="module", params=sorted(ENGINES))
def sync_run(request, cfg):
    """Three synchronous iterations on the paged engine, with the pool's
    counters read around them."""
    rl = RLConfig(mode="sync", batch_prompts=2, group_size=3, micro_batch=3,
                  num_inference_instances=1, max_prompt_len=24,
                  max_response_len=6, learning_rate=1e-3,
                  rollout_engine="paged", cbatch_slots=4, kv_page_size=8,
                  **ENGINES[request.param])
    sched, parts = build_pipeline(cfg, rl)
    pool = parts["pool"]
    before = pool.engine_stats()
    hist = sched.run(3)
    after = pool.engine_stats()
    return hist, before, after


def test_iteration_metrics_carry_decode_steps_and_phases(sync_run):
    hist, before, after = sync_run
    steps = [h.metrics["decode_steps"] for h in hist]
    assert all(n > 0 for n in steps)
    assert sum(steps) == after["decode_steps"] - before["decode_steps"]
    for k in PHASES:
        assert all(h.metrics[k] >= 0.0 for h in hist), k
        assert sum(h.metrics[k] for h in hist) == pytest.approx(
            after[k] - before[k]), k
    for h in hist:
        # every step builds, launches and waits on a device call
        assert h.metrics["decode_host_s"] > 0.0
        assert h.metrics["decode_launch_s"] > 0.0
        assert h.metrics["decode_wait_s"] > 0.0


def test_phases_and_gap_fit_in_rollout_wall(sync_run):
    """In sync mode the trainer waits for every rollout before it trains,
    so an iteration's time outside training bounds its decode phases plus
    the gaps between steps (prefill and the boundary are in neither)."""
    hist, _, _ = sync_run
    for h in hist:
        decode = sum(h.metrics[k] for k in PHASES)
        assert 0.0 < decode <= h.wall_time - h.train_time


def test_span_is_null_without_tracer_or_profiler():
    otrace.uninstall()
    assert not TraceMe.is_enabled()
    assert otrace.span("paged.admit", gid=1) is otrace._NULL_SPAN


def _program_events(path):
    """{(plane, line index): [(name, start_ns, end_ns), ...]} of the
    ``paged.*`` and ``producer.*`` events in a written profile."""
    pd = ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        for i, line in enumerate(plane.lines):
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events
                   if e.name.startswith(("paged.", "producer."))]
            if evs:
                out[(plane.name, i)] = sorted(evs, key=lambda e: e[1])
    return out


def test_profiler_trace_holds_phase_spans_nested_in_order(cfg, tmp_path):
    params = init(jax.random.PRNGKey(0), cfg)
    eng = PagedGroupEngine(cfg, num_slots=3, page_size=4, num_pages=0,
                           max_prompt_len=16, max_new_tokens=5, group_size=3)
    inst = InferenceInstance(0, cfg, None, paged_engine=eng)
    inst.sync_weights(params, version=0)
    prompt = np.asarray([1, 9, 4, 7, 3, 8, 2], np.int32)
    inst.generate_group([prompt] * 3, jax.random.PRNGKey(1))   # compile
    n0 = eng.decode_steps
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        inst.generate_group([prompt] * 3, jax.random.PRNGKey(2))
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = _program_events(path[0])
    # one driving thread: every program span is on its line
    assert len(lines) == 1
    evs = next(iter(lines.values()))
    steps = [e for e in evs if e[0] == "producer.step"]
    # one block of one token a step; the first step also admits the group
    assert len(steps) == eng.decode_steps - n0 > 0
    assert any(e[0] == "producer.lock_wait" for e in evs)
    phases = [e for e in evs if e[0].startswith("paged.")]
    assert {e[0] for e in phases} == set(STEP_SPANS) | {"paged.prefill"}
    for name, s, t in phases:
        assert any(a <= s and t <= b for _, a, b in steps), name
    for _, a, b in steps:
        inside = [n for n, s, t in phases if a <= s and t <= b
                  and n != "paged.prefill"]
        assert inside == list(STEP_SPANS)


def test_profiled_span_also_records_in_installed_tracer(tmp_path):
    tr = otrace.install("p")
    with jax.profiler.trace(str(tmp_path)):
        with otrace.span("transfer.stream", version=3) as sp:
            sp.set(buckets=2)
    x = next(e for e in tr.events() if e["name"] == "transfer.stream")
    assert x["args"] == {"version": 3, "buckets": 2}
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path[0])
    names = {e.name for p in pd.planes for ln in p.lines for e in ln.events}
    assert "transfer.stream" in names


def test_phase_clocks_under_concurrent_driving_threads(cfg):
    """Six threads drive one engine through the instance lock while a
    seventh reads ``stats_snapshot``: every read is monotone in every
    counter, every group completes, and the phases fit in the run's wall
    time."""
    params = init(jax.random.PRNGKey(0), cfg)
    eng = PagedGroupEngine(cfg, num_slots=4, page_size=4, num_pages=0,
                           max_prompt_len=16, max_new_tokens=6, group_size=2)
    inst = InferenceInstance(0, cfg, None, paged_engine=eng)
    inst.sync_weights(params, version=0)
    prompt = np.asarray([1, 9, 4, 7, 3, 8, 2], np.int32)
    inst.generate_group([prompt] * 2, jax.random.PRNGKey(1))   # compile
    eng.reset_stats()
    done, snaps = [], []
    stop = threading.Event()

    def drive(i):
        inst.generate_group([prompt] * 2, jax.random.PRNGKey(10 + i))
        done.append(i)

    def read():
        while not stop.is_set():
            snaps.append(eng.stats_snapshot())

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        reader = threading.Thread(target=read)
        reader.start()
        t0 = time.perf_counter()
        stepping = [threading.Thread(target=drive, args=(i,))
                   for i in range(6)]
        for th in stepping:
            th.start()
        for th in stepping:
            th.join(timeout=120)
        wall = time.perf_counter() - t0
        stop.set()
        reader.join(timeout=10)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in stepping) and len(done) == 6
    assert not reader.is_alive()
    snaps.append(eng.stats_snapshot())
    for a, b in zip(snaps, snaps[1:]):
        assert all(b[k] >= a[k] for k in a)
    last = snaps[-1]
    assert last["decode_steps"] > 0
    assert 0.0 < sum(last[k] for k in PHASES) <= wall
