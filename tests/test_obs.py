"""Tests for the tracing/metrics plane (src/repro/obs) and its checker.

Covers: the disabled fast path (shared null span, no allocation), the
Chrome trace-event export format (per-thread buffers, virtual tracks,
async b/e pairing), the metrics registry, the busy-clock O(1) boundary
regression (settles deregister; repeated reads join nothing), and the
obs-discipline checker (begin/end balance + hot-tier span-over-sync),
with bug-injection and clean fixtures like the rest of test_analysis.
"""
import json
import threading

import jax
import numpy as np
import pytest

from repro.analysis.framework import Module
from repro.analysis.obs_discipline import ObsDisciplineChecker
from repro.obs import MetricsRegistry, Tracer
from repro.obs import trace as otrace


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    yield
    otrace.uninstall()


# ---------------------------------------------------------------------------
# disabled fast path
# ---------------------------------------------------------------------------

def test_disabled_span_is_shared_noop():
    otrace.uninstall()
    a = otrace.span("x", k=1)
    b = otrace.span("y")
    assert a is b            # ONE shared null object: nothing allocates
    with a as s:
        assert s.set(more=2) is s
    # and every other facade call is a no-op, not an error
    otrace.complete("n", 0.0, 1.0)
    otrace.begin("n", uid=1)
    otrace.end("n", uid=1)
    otrace.instant("n")
    otrace.counter("n", 3)
    assert otrace.export("/nonexistent/dir/never-written.json") is None
    assert not otrace.active()


def test_install_uninstall_swaps_facade():
    t = otrace.install("p")
    assert otrace.get() is t and otrace.active()
    otrace.uninstall()
    assert otrace.get() is None


# ---------------------------------------------------------------------------
# export format
# ---------------------------------------------------------------------------

def test_span_and_complete_export(tmp_path):
    tr = Tracer("proc")
    with tr.span("work", stage="a") as sp:
        sp.set(extra=1)
    tr.complete("retro", tr._epoch + 1.0, tr._epoch + 3.0, foo="bar")
    path = tr.export(str(tmp_path / "t.json"))
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    proc = [e for e in evs if e["ph"] == "M" and e["name"] == "process_name"]
    assert proc[0]["args"]["name"] == "proc"
    work = next(e for e in evs if e["name"] == "work")
    assert work["ph"] == "X" and work["dur"] >= 0
    assert work["args"] == {"stage": "a", "extra": 1}
    retro = next(e for e in evs if e["name"] == "retro")
    assert retro["ts"] == pytest.approx(1e6, rel=1e-6)
    assert retro["dur"] == pytest.approx(2e6, rel=1e-6)


def test_per_thread_buffers_and_thread_names():
    tr = Tracer()

    def worker():
        tr.complete("w", tr._epoch, tr._epoch + 0.1)

    th = threading.Thread(target=worker, name="worker-thread")
    th.start()
    th.join()
    tr.complete("m", tr._epoch, tr._epoch + 0.1)
    evs = tr.events()
    w = next(e for e in evs if e["name"] == "w")
    m = next(e for e in evs if e["name"] == "m")
    assert w["tid"] != m["tid"]     # each writer thread has its own track
    names = {e["tid"]: e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert names[w["tid"]] == "worker-thread"


def test_virtual_track_pins_tid():
    tr = Tracer()
    tr.complete("a", tr._epoch, tr._epoch + 0.1, track="producer/inst0")
    tr.complete("b", tr._epoch, tr._epoch + 0.1, track="producer/inst0")
    tr.complete("c", tr._epoch, tr._epoch + 0.1, track="producer/inst1")
    evs = tr.events()
    tid = {e["name"]: e["tid"] for e in evs if e["ph"] == "X"}
    assert tid["a"] == tid["b"] != tid["c"]
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"producer/inst0", "producer/inst1"} <= names


def test_async_begin_end_and_instant():
    tr = Tracer()
    tr.begin("request", uid=7, rid=7)
    tr.instant("request.token", rid=7)
    tr.end("request", uid=7)
    evs = [e for e in tr.events() if e["ph"] in "bei"]
    b, i, e = evs
    assert (b["ph"], i["ph"], e["ph"]) == ("b", "i", "e")
    assert b["cat"] == e["cat"] == "async"
    assert b["id"] == e["id"] == "7"   # Perfetto joins b/e by (cat, id)
    assert i["s"] == "t"
    assert b["ts"] <= i["ts"] <= e["ts"]


def test_events_sorted_by_ts():
    tr = Tracer()
    tr.complete("late", tr._epoch + 5.0, tr._epoch + 6.0)
    tr.complete("early", tr._epoch + 1.0, tr._epoch + 2.0)
    xs = [e["name"] for e in tr.events() if e["ph"] == "X"]
    assert xs == ["early", "late"]


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("prefix.hit_pages")
    c.add(3)
    c.add(2)
    assert reg.counter("prefix.hit_pages") is c   # get-or-create
    reg.gauge("paged.pages_live").set(17)
    h = reg.histogram("transfer.bucket_bytes")
    for v in (10, 20, 30, 40):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["prefix.hit_pages"] == 5
    assert snap["paged.pages_live"] == 17
    assert snap["transfer.bucket_bytes"]["count"] == 4
    assert snap["transfer.bucket_bytes"]["min"] == 10
    assert snap["transfer.bucket_bytes"]["max"] == 40
    reg.reset()
    assert reg.counter("prefix.hit_pages").value == 0


def test_metrics_type_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(AssertionError):
        reg.gauge("x")


def test_metrics_threaded_counter():
    reg = MetricsRegistry()
    c = reg.counter("n")
    threads = [threading.Thread(target=lambda: [c.add(1) for _ in range(500)])
               for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 2000


# ---------------------------------------------------------------------------
# busy-clock boundary regression: settles deregister themselves; repeated
# busy_time reads between boundaries join nothing and agree exactly
# ---------------------------------------------------------------------------

def test_busy_time_repeated_reads_are_o1_and_identical():
    from repro.configs import get_config, reduced_config
    from repro.core.engine import InferenceInstance, InferencePool
    from repro.models import init
    from repro.rl.rollout import Sampler

    cfg = reduced_config(get_config("llama3.2-3b"))
    params = init(jax.random.PRNGKey(0), cfg)
    sampler = Sampler(cfg, 16, 4)
    inst = InferenceInstance(0, cfg, sampler)
    inst.sync_weights(params, version=1)
    pool = InferencePool([inst])
    prompts = [np.asarray([1, 5, 9], np.int32)] * 2
    for _ in range(3):   # three deferred settle threads charged the clock
        inst.generate_group(prompts, jax.random.PRNGKey(0))

    first = pool.busy_time           # boundary read: flushes the settles
    joins_after_first = inst.settle_joins
    reads = [pool.busy_time for _ in range(50)]
    assert all(r == first for r in reads)        # identical, not just close
    # O(1): none of the 50 reads re-joined a settle thread — completed
    # settles deregistered themselves at the first boundary
    assert inst.settle_joins == joins_after_first
    assert inst._settles == []
    assert first > 0.0


def test_reset_stats_clears_busy_clock():
    from repro.configs import get_config, reduced_config
    from repro.core.engine import InferenceInstance, InferencePool
    from repro.models import init
    from repro.rl.rollout import Sampler

    cfg = reduced_config(get_config("llama3.2-3b"))
    params = init(jax.random.PRNGKey(0), cfg)
    inst = InferenceInstance(0, cfg, Sampler(cfg, 16, 4))
    inst.sync_weights(params, version=1)
    pool = InferencePool([inst])
    inst.generate_group([np.asarray([1, 2, 3], np.int32)] * 2,
                        jax.random.PRNGKey(0))
    assert pool.busy_time > 0
    pool.reset_stats()
    assert pool.busy_time == 0.0


# ---------------------------------------------------------------------------
# bounded histogram: O(1) memory, bucket-CDF percentiles vs exact
# ---------------------------------------------------------------------------

def test_histogram_bounded_memory_exact_aggregates():
    from repro.obs.metrics import Histogram
    h = Histogram()
    n_buckets = len(h._counts)
    vals = [(i % 997) / 100.0 + 0.001 for i in range(10_000)]
    for v in vals:
        h.observe(v)
    assert len(h._counts) == n_buckets    # no per-observation retention
    s = h.summary()
    assert s["count"] == 10_000
    assert s["sum"] == pytest.approx(sum(vals))
    assert s["min"] == min(vals) and s["max"] == max(vals)


def test_histogram_percentiles_vs_exact_small_n():
    from repro.obs.metrics import Histogram
    rng = np.random.RandomState(0)
    vals = rng.lognormal(mean=-2.0, sigma=1.5, size=200)
    h = Histogram()
    for v in vals:
        h.observe(float(v))
    s = h.summary()
    for q, key in ((50, "p50"), (99, "p99")):
        exact = float(np.percentile(vals, q))
        est = s[key]
        # bucket-CDF estimate: error bounded by one bucket width of the
        # 1-2.5-5 ladder (max edge ratio 2.5)
        assert exact / 2.5 <= est <= exact * 2.5, (key, est, exact)


def test_histogram_degenerate_and_empty():
    from repro.obs.metrics import Histogram
    h = Histogram()
    assert h.summary() == {"count": 0, "sum": 0.0}
    for _ in range(5):
        h.observe(0.3)
    s = h.summary()
    # single-bucket sample: clamped to exact observed min/max
    assert s["p50"] == s["p99"] == pytest.approx(0.3)


def test_histogram_buckets_cumulative_and_consistent():
    from repro.obs.metrics import Histogram
    h = Histogram()
    for v in (0.001, 0.5, 0.5, 123.0, 1e12):   # incl. +Inf overflow
        h.observe(v)
    bounds, cum, count, total = h.buckets()
    assert len(cum) == len(bounds) + 1
    assert cum == sorted(cum)                  # cumulative by construction
    assert cum[-1] == count == 5
    assert total == pytest.approx(sum((0.001, 0.5, 0.5, 123.0, 1e12)))


# ---------------------------------------------------------------------------
# streaming trace export: segments == monolithic, bounded peak memory
# ---------------------------------------------------------------------------

# A fixed epoch for scripted events: ``(e + off) - e`` rounds by e's low
# bits, so two tracers with their own perf_counter epochs would export
# timestamps that differ in the last digits (most often on a host whose
# clock reads only tens of seconds since boot).
_EPOCH = 137.0390625


def _script_pipeline_events(tr):
    """Deterministic span set (offsets from one pinned epoch) — identical
    timestamps in a monolithic and a streaming tracer."""
    tr._epoch = e = _EPOCH

    def one_iter(i):
        lo = e + i * 1.0
        tr.complete("iteration", lo, lo + 1.0, iteration=i, mode="async")
        tr.complete("producer.busy", lo + 0.05, lo + 0.60, busy=0.5)
        tr.complete("train.group", lo + 0.40, lo + 0.80)
        tr.complete("train.update", lo + 0.80, lo + 0.95)
        tr.complete("transfer.ensure", lo + 0.95, lo + 0.97, gap=0.02)

    threads = [threading.Thread(target=one_iter, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def test_streaming_segments_report_equals_monolithic(tmp_path):
    from repro.obs.analyze import analyze, load_trace
    mono = Tracer("p")
    _script_pipeline_events(mono)
    want = analyze(mono.events())
    assert len(want["iterations"]) == 4      # non-trivial report

    stream = Tracer("p", stream_dir=str(tmp_path / "seg"),
                    flush_events=4, segment_events=8)
    _script_pipeline_events(stream)
    out_dir = stream.export()
    got = analyze(load_trace(out_dir))
    assert got == want                        # exactly, not approximately


def test_streaming_peak_buffer_bounded(tmp_path):
    tr = Tracer("p", stream_dir=str(tmp_path / "seg"), flush_events=16)

    def emit(k):
        for i in range(500):
            tr.complete(f"x{k}", tr._epoch + i, tr._epoch + i + 0.5)

    threads = [threading.Thread(target=emit, args=(k,)) for k in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tr.close()
    # the documented bound: resident events never exceed the flush batch
    # per thread, no matter how many events the run emits
    assert 0 < tr.peak_buffer_events <= 16


def test_streaming_rotation_and_readback(tmp_path):
    from repro.obs.analyze import load_trace
    d = tmp_path / "seg"
    tr = Tracer("p", stream_dir=str(d), flush_events=4, segment_events=8)
    for i in range(100):
        tr.complete("ev", tr._epoch + i, tr._epoch + i + 0.5, n=i)
    assert tr.export() == str(d)
    segs = sorted(d.glob("trace-*.jsonl"))
    assert len(segs) > 3                      # actually rotated
    for seg in segs:
        n_lines = sum(1 for _ in open(seg))
        # cap + at most one flush batch of overshoot (+ meta lines)
        assert n_lines <= 8 + 4 + 2
    evs = load_trace(str(d))
    xs = [e for e in evs if e.get("ph") == "X"]
    assert [e["args"]["n"] for e in xs] == list(range(100))  # all, in order
    assert tr.peak_buffer_events <= 4


def test_streaming_tracer_rejects_events_and_tolerates_truncation(tmp_path):
    from repro.obs.analyze import load_trace
    d = tmp_path / "seg"
    tr = Tracer("p", stream_dir=str(d), flush_events=2, segment_events=1000)
    for i in range(10):
        tr.complete("ev", tr._epoch + i, tr._epoch + i + 0.5)
    with pytest.raises(RuntimeError):
        tr.events()                           # streaming: events live on disk
    tr.close()
    segs = sorted(d.glob("trace-*.jsonl"))
    # a hard kill can truncate the LAST line of the LAST segment mid-write;
    # the loader drops exactly that and nothing else
    with open(segs[-1], "a") as f:
        f.write('{"ph": "X", "name": "torn')
    evs = load_trace(str(d))
    assert sum(1 for e in evs if e.get("ph") == "X") == 10
    # the same garbage in a non-final position is corruption, not a crash
    with open(segs[-1], "a") as f:
        f.write('\n{"ph": "M", "name": "process_name", "ts": 0}\n')
    with pytest.raises(json.JSONDecodeError):
        load_trace(str(d))


def test_streaming_close_idempotent_and_uninstall_closes(tmp_path):
    d = str(tmp_path / "seg")
    tr = otrace.install("p", stream_dir=d, flush_events=4)
    tr.complete("ev", tr._epoch, tr._epoch + 1.0)
    otrace.uninstall()                        # closes the streaming tracer
    assert tr._closed
    assert tr.close() == d                    # idempotent


# ---------------------------------------------------------------------------
# flush-on-crash: a SIGKILLed training run leaves readable segments
# ---------------------------------------------------------------------------

def test_killed_run_leaves_readable_segments(tmp_path):
    import os
    import pathlib
    import subprocess
    import sys
    import time

    from repro.obs.analyze import analyze, load_trace
    d = tmp_path / "seg"
    root = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.train", "--iterations", "99",
         "--batch-prompts", "2", "--group-size", "2", "--micro-batch", "1",
         "--instances", "1", "--max-prompt-len", "16",
         "--max-response-len", "8", "--trace-dir", str(d),
         "--trace-flush-events", "4", "--trace-segment-events", "16"],
        cwd=root, env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    try:
        # wait until real span events (not just meta lines) are on disk —
        # i.e. the run is mid-iteration — then kill it dead, no cleanup
        deadline = time.time() + 180
        while time.time() < deadline:
            if any('"X"' in open(f).read()
                   for f in sorted(d.glob("trace-*.jsonl"))):
                break
            time.sleep(0.2)
            if proc.poll() is not None:
                raise AssertionError("training run exited prematurely")
        else:
            raise AssertionError("no flushed span events before deadline")
    finally:
        proc.kill()
        proc.wait(timeout=30)
    evs = load_trace(str(d))                  # readable despite the kill
    assert any(e.get("ph") == "X" for e in evs)
    analyze(evs)                              # and analyzable, not just JSON


# ---------------------------------------------------------------------------
# obs-discipline checker
# ---------------------------------------------------------------------------

def run_one(*mods):
    return ObsDisciplineChecker().run(
        [Module.from_source(p, src) for p, src in mods])


UNBALANCED = """\
from repro.obs import trace as otrace


def submit(r):
    otrace.begin("request", uid=r)
"""

BALANCED_CROSS_MODULE_A = """\
from repro.obs import trace as otrace


def submit(r):
    otrace.begin("request", uid=r)
"""

BALANCED_CROSS_MODULE_B = """\
from repro.obs import trace as otrace


def finish(r):
    otrace.end("request", uid=r)
"""


def test_unbalanced_begin_flagged():
    fs = run_one(("launch/serve.py", UNBALANCED))
    assert len(fs) == 1
    assert "no matching otrace.end" in fs[0].message
    assert fs[0].line == 5


def test_end_without_begin_flagged():
    fs = run_one(("launch/serve.py", BALANCED_CROSS_MODULE_B))
    assert len(fs) == 1
    assert "no matching otrace.begin" in fs[0].message


def test_cross_module_balance_is_clean():
    # begin and end legitimately live in different functions/modules —
    # the pairing is by span NAME repo-wide, not lexical
    fs = run_one(("launch/serve.py", BALANCED_CROSS_MODULE_A),
                 ("core/engine.py", BALANCED_CROSS_MODULE_B))
    assert fs == []


def test_unrelated_begin_method_not_matched():
    src = """\
class VersionedParamStore:
    def begin(self, version):
        return version


def publish(store, v):
    store.begin(v)
    self.store.begin(v)
"""
    assert run_one(("transfer/service.py", src)) == []


def test_dynamic_span_name_warns():
    src = """\
from repro.obs import trace as otrace


def submit(name, r):
    otrace.begin(name, uid=r)
    otrace.end(name, uid=r)
"""
    fs = run_one(("launch/serve.py", src))
    assert len(fs) == 2
    assert all(f.severity == "warning" for f in fs)
    assert "dynamic span name" in fs[0].message


HOT_SPAN_BUG = """\
import jax
from repro.obs import trace as otrace


class PagedGroupEngine:
    def __init__(self):
        self._decode = jax.jit(self._decode_fn)

    def step(self):
        with otrace.span("paged.step"):
            tok = self._decode(1)
            jax.device_get(tok)
"""

WARM_SPAN_OK = """\
import jax
from repro.obs import trace as otrace


class PagedGroupEngine:
    def __init__(self):
        self._decode = jax.jit(self._decode_fn)

    def step(self):
        self._drain_block()

    def _drain_block(self):
        with otrace.span("paged.drain"):
            jax.device_get(self.buf)
"""

HOT_SPAN_NO_SYNC = """\
from repro.obs import trace as otrace


class PagedGroupEngine:
    def step(self):
        with otrace.span("paged.admit"):
            self.queue.append(1)
"""


def test_hot_tier_span_over_sync_flagged():
    fs = run_one(("core/paged.py", HOT_SPAN_BUG))
    assert len(fs) == 1
    assert "wraps a host sync" in fs[0].message
    assert "otrace.complete()" in fs[0].message
    assert fs[0].line == 10     # the span line, where the fix goes


def test_drain_tier_span_over_sync_is_legal():
    # depth >= 1 is exactly where retro-recorded drain spans belong
    assert run_one(("core/paged.py", WARM_SPAN_OK)) == []


def test_hot_tier_span_without_sync_is_legal():
    assert run_one(("core/paged.py", HOT_SPAN_NO_SYNC)) == []


def test_repo_is_obs_clean():
    """Dogfood: the checker reports nothing across src/ (same gate CI
    runs via repro-check)."""
    import pathlib

    from repro.analysis.framework import discover, run_checkers
    from repro.analysis.registry import CHECKER_NAMES
    root = pathlib.Path(__file__).resolve().parents[1]
    mods = discover([root / "src"], root=root)
    fs = [f for f in run_checkers(mods, [ObsDisciplineChecker()],
                                  known_names=CHECKER_NAMES)
          if f.checker == "obs-discipline" and not f.suppressed]
    assert fs == [], [f.render() for f in fs]
