"""Runs one cell once: finds its configuration, traffic and metrics by name,
hands them to the traffic kind's driver, reduces the trace, reads the
per-layer metrics, compares the numbers that decide ``correct`` with their
limits, and assembles the result line.

A driver (``bench/kinds/<kind>.py``) exposes ``run(ctx) -> record``. The
record holds ``setup_s``, ``window_s``, ``attempted``, ``failed``, the
cell's end-to-end values under ``e2e``, the compared numbers under
``numbers`` and whatever its metric readers need.
"""
from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
import time
from typing import Optional

from bench import common

TRACE_DIR = common.CHECKOUT / ".bench_trace"


class CompileClock:
    """Counts backend compiles and their seconds from JAX's own monitoring
    events, so set-up reports what it spent compiling and the window shows
    that it compiled nothing."""

    def __init__(self):
        import jax
        self.seconds, self.count, self.hits = 0.0, 0, 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs
                self.count += 1

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        self._fns = (on_duration, on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def reset(self):
        self.seconds, self.count, self.hits = 0.0, 0, 0

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._fns[0])
        jax.monitoring.unregister_event_listener(self._fns[1])


def enable_compile_cache() -> Optional[str]:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else at the fixed ``<checkout>/.jax_cache`` (the path is part of the
    cache key, so it never moves). Off on the CPU."""
    import jax
    if jax.default_backend() == "cpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(common.CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class Context:
    """What a driver gets: the cell's pieces, the run's options, and the
    tracing and annotation hooks."""

    def __init__(self, *, workload, spec, traffic, seed, seconds, trace,
                 rehearse, t_start, faults=(), controls=()):
        self.workload = workload
        self.spec = spec
        self.traffic = traffic
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.rehearse = rehearse
        self.t_start = t_start
        self.faults = set(faults)
        self.controls = tuple(controls)
        self.chips = workload["chips"]
        self.clock = CompileClock()
        self._window_ann = None
        self.trace_path = None

    # -- tracing --------------------------------------------------------
    def trace_start(self) -> None:
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        self._window_ann = jax.profiler.TraceAnnotation("bench.window")
        self._window_ann.__enter__()

    def window_end(self) -> None:
        """Close the traced window; the trace itself may run on (and be
        stopped later, outside any timed work)."""
        if self._window_ann is not None:
            self._window_ann.__exit__(None, None, None)
            self._window_ann = None

    def trace_stop(self) -> None:
        import jax
        from bench import trace_reduce
        self.window_end()
        jax.profiler.stop_trace()
        self.trace_path = trace_reduce.find_xplane(str(TRACE_DIR))

    def annotate(self, obj, attr: str, label: str) -> None:
        """Wrap ``obj.attr`` in a host annotation ``bench:<label>`` (trace
        runs only), so idle gaps on the device can be named."""
        if not self.trace:
            return
        import jax
        fn = getattr(obj, attr)

        def wrapped(*a, **k):
            with jax.profiler.TraceAnnotation("bench:" + label):
                return fn(*a, **k)
        setattr(obj, attr, wrapped)


def device_info():
    import jax
    devs = jax.devices()
    return devs, devs[0].platform, devs[0].device_kind


def memory_peak(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def free_device_memory() -> int:
    import jax
    jax.clear_caches()
    gc.collect()
    return sum(a.nbytes for a in jax.live_arrays())


def compare(numbers: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}}; a number passes when value <= limit.

    Every number needs an entry in the limits: a number, or an explicit
    null with a ``why`` (reported, not compared). A number without one is
    an error, so a check can never pass by having nothing to compare."""
    out = {}
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"compared number {name!r} has no entry in the "
                           f"cell's limits file")
        lim = limits[name].get("limit")
        if lim is None and not limits[name].get("why"):
            raise ValueError(f"limit of {name!r} is null without a why")
        out[name] = {"value": value, "limit": lim}
    return out


def passes(checks: dict) -> bool:
    for c in checks.values():
        v, lim = c["value"], c["limit"]
        if lim is None:
            continue
        if v is None or not math.isfinite(v) or v > lim:
            return False
    return True


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             rehearse: bool = False, faults=(), controls=(), t_start=None,
             bench: Optional[dict] = None) -> dict:
    t_start = time.perf_counter() if t_start is None else t_start
    bench = bench or common.benchmark()
    w = common.workload(name, bench)
    spec = common.config_spec(w["config"])
    traffic = common.traffic_spec(w["traffic"])
    kind = common.kind_module(traffic["kind"])
    lim = common.limits(name)["numbers"]         # before any work is done
    ctx = Context(workload=w, spec=spec, traffic=traffic, seed=seed,
                  seconds=seconds, trace=trace, rehearse=rehearse,
                  t_start=t_start, faults=faults, controls=controls)
    devs, platform, kind_name = device_info()
    try:
        rec = kind.run(ctx)
    finally:
        ctx.clock.close()
    rec["device"] = {"platform": platform, "kind": kind_name,
                     "device_kind": kind_name, "count": len(devs)}
    if platform == "tpu":
        peaks = common.load_json(common.BENCH / "peaks.json")
        if kind_name not in peaks:
            raise KeyError(f"no peaks for device kind {kind_name!r} in "
                           f"bench/peaks.json")
        rec["peak"] = peaks[kind_name]
    if trace and ctx.trace_path and platform == "tpu":
        # a CPU rehearsal's trace has no device plane to reduce
        from bench import trace_reduce
        rec["trace"] = trace_reduce.reduce(trace_reduce.load(ctx.trace_path))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)

    ends = [m for m in bench["end_to_end"]
            if name in m.get("workloads", [name])]
    if trace:
        metrics = {}
        for m in bench["per_layer"]:
            if name not in m.get("workloads", [name]):
                continue
            v = common.metric_reader(m["name"]).read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": rec["e2e"][m["name"]],
                               "unit": m["unit"]} for m in ends}
    unread = sorted(set(lim) - set(rec["numbers"]))
    if unread:
        raise KeyError(f"limits of {name!r} name numbers that the run did "
                       f"not produce: {unread}")
    checks = compare(rec["numbers"], lim)
    correct = (passes(checks) and rec["failed"] == 0
               and rec["attempted"] > 0
               and any(c["limit"] is not None for c in checks.values()))
    # a control or planted fault is judged by the same limits; it has to
    # come out not correct
    info = dict(rec.get("info", {}))
    info["controls"] = {
        c: {"correct": passes(compare(nums, lim)), "numbers": nums}
        for c, nums in info.get("controls", {}).items()}
    device = dict(rec["device"], memory_peak_bytes=rec.get("memory_peak"))
    if trace and "trace" in rec:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
    out = {"correct": bool(correct), "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics, "device": device}
    if trace and "trace" in rec:
        out["breakdown"] = rec["trace"]["breakdown"]
    out["info"] = info
    out["checks"] = checks
    return out


def print_checks(checks: dict) -> None:
    for k, c in checks.items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)


def result_line(result: dict) -> str:
    return json.dumps(result)
