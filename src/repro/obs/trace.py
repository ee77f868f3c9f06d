"""Thread-aware tracing plane (DESIGN.md §Observability).

The pipeline's whole claim is a *timeline* claim — periodic asynchrony
overlaps the producer (rollout pool) and consumer (trainer) stages — so
this module records spans on every stage and exports them as Chrome
trace-event JSON, viewable directly in Perfetto (ui.perfetto.dev).

Design constraints, in priority order:

* **Near-zero overhead when disabled.** Instrumentation sites call the
  module-level ``span()``/``complete()``/``instant()`` facade; with no
  tracer installed these are one global load + a ``None`` check (and
  ``span()`` returns a shared no-op context manager, after one
  ``TraceMe.is_enabled()`` call). Nothing allocates.
* **On the profiler's clock.** While a JAX profiler session records,
  ``span()`` also opens a ``jax.profiler.TraceAnnotation`` of the same
  name, installed tracer or not, so host spans land in the
  ``.xplane.pb`` beside the device's ops. Retro-recorded ``complete()``
  events cannot: the profiler only takes spans as they happen.
* **No clock of its own on the hot tier.** Span timestamps reuse the
  clock reads the pipeline already takes (the deferred busy-settle
  clock, the boundary stopwatches) via ``complete(name, t0, t1)``; the
  tracer never calls ``jax.block_until_ready`` and adds zero host syncs
  to the dispatch stream (gated by ``repro-check --forbid-hot`` and the
  obs-discipline checker).
* **Lock-cheap under threads.** Every thread appends to its own buffer
  (``threading.local``); the tracer lock is taken once per thread at
  first use and once at export, never per event.
* **Bounded peak memory when streaming.** With ``stream_dir`` set, each
  thread's buffer is flushed to rotating JSONL segments
  (``trace-000N.jsonl``) once it reaches ``flush_events`` entries, so
  resident events never exceed ``threads x flush_events`` no matter how
  long the run — a multi-hour trace costs disk, not RAM
  (``peak_buffer_events`` records the observed bound for tests).
  Monolithic mode (no ``stream_dir``) keeps the original
  buffer-until-``export`` behaviour for short runs.

Event model (Chrome trace-event phases):

* ``span("name", **attrs)`` — a ``with``-scoped complete ("X") event on
  the calling thread's track.
* ``complete(name, t0, t1, **attrs)`` — a retro-recorded "X" event with
  explicit ``time.perf_counter()`` endpoints (the deferred-clock path);
  ``track=`` pins it to a stable virtual track (e.g. one per producer
  instance) instead of the emitting thread.
* ``begin(name, uid)`` / ``end(name, uid)`` — async ("b"/"e") events for
  spans that start and finish on different threads (serving request
  lifecycle).
* ``instant(name)`` / ``counter(name, value)`` — "i" point events and
  "C" counter tracks (pages live, queue depth).

Listeners: ``add_listener(fn)`` registers a callback invoked with each
raw event tuple at emit time — the hook the ops server's online bubble
estimator rides (obs/server.py). With no listeners registered the emit
path pays one truthiness check.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation
from jaxlib._profiler import TraceMe

# True while a profiler session records (one C++ flag read)
_profiling = TraceMe.is_enabled


class _NullSpan:
    """Shared no-op context manager returned by ``span()`` when tracing
    is disabled — the entire disabled-path cost of a ``with`` site."""
    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> "_NullSpan":
        return self


_NULL_SPAN = _NullSpan()


class _ProfiledSpan:
    """``span()`` while a profiler session records: a ``TraceAnnotation``
    on the profiler's clock around the installed tracer's own span, if
    any."""
    __slots__ = ("_ann", "_span")

    def __init__(self, name: str, attrs: Dict[str, Any],
                 span: Optional["_Span"]):
        self._ann = TraceAnnotation(name, **attrs)
        self._span = span

    def __enter__(self) -> "_ProfiledSpan":
        self._ann.__enter__()
        if self._span is not None:
            self._span.__enter__()
        return self

    def set(self, **attrs) -> "_ProfiledSpan":
        if self._span is not None:
            self._span.set(**attrs)
        return self

    def __exit__(self, *exc) -> bool:
        if self._span is not None:
            self._span.__exit__(*exc)
        self._ann.__exit__(*exc)
        return False


class _Span:
    __slots__ = ("_tracer", "name", "attrs", "_t0")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_Span":
        self._t0 = time.perf_counter()
        return self

    def set(self, **attrs) -> "_Span":
        self.attrs.update(attrs)
        return self

    def __exit__(self, *exc) -> bool:
        tr = self._tracer
        t1 = time.perf_counter()
        tr._emit(("X", self.name, tr._ts(self._t0),
                  (t1 - self._t0) * 1e6, None, self.attrs))
        return False


class Tracer:
    """Collects trace events into per-thread buffers; ``export`` writes
    the merged Chrome trace-event JSON (monolithic mode) or flushes the
    final JSONL segment (streaming mode)."""

    def __init__(self, process_name: str = "repro",
                 stream_dir: Optional[str] = None,
                 flush_events: int = 256,
                 segment_events: int = 8192):
        self.process_name = process_name
        self._epoch = time.perf_counter()
        self._lock = threading.Lock()
        # (thread_ident, thread_name, event list) per writer thread
        self._buffers: List[Tuple[int, str, list]] = []
        self._local = threading.local()
        # virtual tracks: stable synthetic tids for events whose natural
        # home is a logical lane (producer instance) rather than the
        # emitting thread (settle threads are one-shot)
        self._tracks: Dict[str, int] = {}
        self._next_track = 1 << 20
        self._listeners: List[Callable[[tuple], None]] = []
        # -- streaming state (all mutated under _io_lock) ---------------
        self.stream_dir = stream_dir
        self.flush_events = max(1, flush_events)
        self.segment_events = max(self.flush_events, segment_events)
        self.peak_buffer_events = 0  # monotone max; tests assert the bound
        self._io_lock = threading.Lock()
        self._seg_file = None
        self._seg_index = -1
        self._seg_count = 0
        self._closed = False
        if stream_dir is not None:
            os.makedirs(stream_dir, exist_ok=True)
            with self._io_lock:
                self._rotate_io_locked()
                self._write_io_locked([{
                    "ph": "M", "name": "process_name", "pid": 0, "tid": 0,
                    "args": {"name": process_name}}])

    @property
    def streaming(self) -> bool:
        return self.stream_dir is not None

    # -- clock ----------------------------------------------------------
    def _ts(self, t: float) -> float:
        return (t - self._epoch) * 1e6  # perf_counter -> trace microseconds

    # -- per-thread buffers ---------------------------------------------
    def _buf(self) -> list:
        buf = getattr(self._local, "buf", None)
        if buf is None:
            buf = []
            th = threading.current_thread()
            entry = (th.ident or 0, th.name, buf)
            with self._lock:
                self._buffers.append(entry)
            if self.streaming:
                with self._io_lock:
                    self._write_io_locked([{
                        "ph": "M", "name": "thread_name", "pid": 0,
                        "tid": entry[0], "args": {"name": th.name}}])
            self._local.buf = buf
        return buf

    def _emit(self, ev: tuple) -> None:
        buf = self._buf()
        buf.append(ev)
        if self._listeners:
            for fn in tuple(self._listeners):
                fn(ev)
        if self.streaming:
            n = len(buf)
            if n > self.peak_buffer_events:
                # benign racy max: monotone, and any lost update is
                # re-observed by the next append on the same thread
                self.peak_buffer_events = n
            if n >= self.flush_events:
                self._flush_one(buf)

    def add_listener(self, fn: Callable[[tuple], None]) -> None:
        with self._lock:
            self._listeners = self._listeners + [fn]

    def remove_listener(self, fn: Callable[[tuple], None]) -> None:
        # == not `is`: a bound method is a fresh object per attribute
        # access, so identity would never match the one registered
        with self._lock:
            self._listeners = [f for f in self._listeners if f != fn]

    def track_tid(self, track: str) -> int:
        tid = self._tracks.get(track)
        if tid is None:
            with self._lock:
                tid = self._tracks.setdefault(
                    track, self._next_track + len(self._tracks))
            if self.streaming:
                with self._io_lock:
                    self._write_io_locked([{
                        "ph": "M", "name": "thread_name", "pid": 0,
                        "tid": tid, "args": {"name": track}}])
        return tid

    # -- recording API --------------------------------------------------
    def span(self, name: str, **attrs) -> _Span:
        return _Span(self, name, attrs)

    def complete(self, name: str, t0: float, t1: float,
                 track: Optional[str] = None, **attrs) -> None:
        """Retro-record a finished span from two existing perf_counter
        reads — the deferred-clock path: no new timestamps are invented
        and nothing blocks."""
        tid = None if track is None else self.track_tid(track)
        self._emit(("X", name, self._ts(t0), (t1 - t0) * 1e6, tid, attrs))

    def begin(self, name: str, uid: Any = None, **attrs) -> None:
        t = time.perf_counter()
        self._emit(("b", name, self._ts(t), uid if uid is not None else name,
                    None, attrs))

    def end(self, name: str, uid: Any = None, **attrs) -> None:
        t = time.perf_counter()
        self._emit(("e", name, self._ts(t), uid if uid is not None else name,
                    None, attrs))

    def instant(self, name: str, **attrs) -> None:
        t = time.perf_counter()
        self._emit(("i", name, self._ts(t), None, None, attrs))

    def counter(self, name: str, value: float) -> None:
        t = time.perf_counter()
        self._emit(("C", name, self._ts(t), None, None, {"value": value}))

    # -- event-dict conversion ------------------------------------------
    @staticmethod
    def _to_dict(ev: tuple, default_tid: int) -> dict:
        ph, name, ts, x, etid, attrs = ev
        out: Dict[str, Any] = {"ph": ph, "name": name, "pid": 0,
                               "tid": etid if etid is not None else default_tid,
                               "ts": ts}
        if ph == "X":
            out["dur"] = x
        elif ph in ("b", "e"):
            out["cat"] = "async"
            out["id"] = str(x)
        elif ph == "i":
            out["s"] = "t"
        if attrs:
            out["args"] = dict(attrs)
        return out

    # -- streaming IO (segment rotation) --------------------------------
    def _rotate_io_locked(self) -> None:
        if self._seg_file is not None:
            self._seg_file.close()
        self._seg_index += 1
        self._seg_count = 0
        path = os.path.join(self.stream_dir,
                            f"trace-{self._seg_index:04d}.jsonl")
        self._seg_file = open(path, "w")

    def _write_io_locked(self, dicts: List[dict]) -> None:
        if self._closed or self._seg_file is None:
            return
        for d in dicts:
            self._seg_file.write(json.dumps(d) + "\n")
        self._seg_count += len(dicts)
        self._seg_file.flush()
        # rotate at batch boundaries: a segment may overshoot the cap by
        # at most one flush batch, never split an event across files
        if self._seg_count >= self.segment_events:
            self._rotate_io_locked()

    def _flush_one(self, buf: list, tid: Optional[int] = None) -> None:
        """Drain one thread's buffer to the current segment. Safe from
        both the owning thread (threshold hit) and a foreign flusher
        (export/close): the length is re-read under the IO lock and only
        the first ``n`` entries are written+removed, so a concurrent
        owner append (GIL-atomic, lands past ``n``) is never lost or
        double-written."""
        if tid is None:
            tid = threading.get_ident()
        with self._io_lock:
            n = len(buf)
            if n:
                self._write_io_locked([self._to_dict(ev, tid)
                                       for ev in buf[:n]])
                del buf[:n]

    def flush(self) -> None:
        """Flush every thread's buffer (streaming mode); no-op otherwise.
        Called on export/close and by the flush-on-crash wrappers in
        launch/."""
        if not self.streaming:
            return
        with self._lock:
            buffers = list(self._buffers)
        for tid, _, buf in buffers:
            self._flush_one(buf, tid=tid)

    def close(self) -> Optional[str]:
        """Flush all buffers and close the active segment; returns the
        stream dir (None in monolithic mode). Idempotent."""
        if not self.streaming:
            return None
        self.flush()
        with self._io_lock:
            if self._seg_file is not None:
                self._seg_file.close()
                self._seg_file = None
            self._closed = True
        return self.stream_dir

    # -- export ---------------------------------------------------------
    def events(self) -> List[dict]:
        """Merged Chrome trace-event dicts (also the analyzer's input).
        Monolithic mode only — a streaming tracer's events live on disk
        (read them back with ``obs.analyze.load_trace(stream_dir)``)."""
        if self.streaming:
            raise RuntimeError(
                "events() unavailable on a streaming tracer; "
                "load the segment dir with obs.analyze.load_trace()")
        pid = 0
        out: List[dict] = [{
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": self.process_name}}]
        with self._lock:
            buffers = [(tid, name, list(buf))
                       for tid, name, buf in self._buffers]
            tracks = dict(self._tracks)
        for tid, name, _ in buffers:
            out.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": tid, "args": {"name": name}})
        for track, tid in tracks.items():
            out.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": tid, "args": {"name": track}})
        for tid, _, buf in buffers:
            for ev in buf:
                out.append(self._to_dict(ev, tid))
        out.sort(key=lambda e: e.get("ts", 0.0))
        return out

    def export(self, path: str = "") -> str:
        """Monolithic: write one Chrome-JSON file at ``path``. Streaming:
        flush+close the segments and return the stream dir (``path`` is
        ignored — the segments are already on disk)."""
        if self.streaming:
            return self.close() or self.stream_dir
        with open(path, "w") as f:
            json.dump({"traceEvents": self.events(),
                       "displayTimeUnit": "ms"}, f)
        return path


# -- module-level facade (what instrumentation sites import) ------------
_active: Optional[Tracer] = None


def install(process_name: str = "repro",
            stream_dir: Optional[str] = None,
            flush_events: int = 256,
            segment_events: int = 8192) -> Tracer:
    """Install a fresh process-wide tracer and return it. ``stream_dir``
    selects streaming JSONL-segment mode (bounded memory)."""
    global _active
    _active = Tracer(process_name, stream_dir=stream_dir,
                     flush_events=flush_events,
                     segment_events=segment_events)
    return _active


def uninstall() -> None:
    global _active
    t = _active
    _active = None
    if t is not None and t.streaming:
        t.close()


def get() -> Optional[Tracer]:
    return _active


def active() -> bool:
    return _active is not None


def span(name: str, **attrs):
    t = _active
    if _profiling():
        return _ProfiledSpan(name, attrs,
                             None if t is None else t.span(name, **attrs))
    if t is None:
        return _NULL_SPAN
    return t.span(name, **attrs)


def complete(name: str, t0: float, t1: float,
             track: Optional[str] = None, **attrs) -> None:
    t = _active
    if t is not None:
        t.complete(name, t0, t1, track=track, **attrs)


def begin(name: str, uid: Any = None, **attrs) -> None:
    t = _active
    if t is not None:
        t.begin(name, uid=uid, **attrs)


def end(name: str, uid: Any = None, **attrs) -> None:
    t = _active
    if t is not None:
        t.end(name, uid=uid, **attrs)


def instant(name: str, **attrs) -> None:
    t = _active
    if t is not None:
        t.instant(name, **attrs)


def counter(name: str, value: float) -> None:
    t = _active
    if t is not None:
        t.counter(name, value)


def export(path: str = "") -> Optional[str]:
    t = _active
    return None if t is None else t.export(path)
