"""Name the idle gaps of a traced run's window by the program's own spans.

    python3 bench/tools/gap_spans.py <trace dir or .xplane.pb> [--top 10]

The program opens its spans (``paged.*``, ``producer.*``, ``train.*``,
``boundary.*``, ``transfer.*``) as profiler annotations while a profile
records, so they share the device's clock. Window and idle gaps are
those of ``bench/trace_reduce.py``: the ``bench.window`` annotation, and
the stretches of it in which no op runs on a device plane. Each gap is
named by the innermost program span open at its midpoint: of the program
spans open then on any host thread, the one nested deepest among its
thread's program spans, the latest to start among equals; ``none`` when
no program span is open.

Prints one JSON object: the window's idle seconds and each name's share
of them, the longest gaps with their names, and for each program span
name its count, total and mean inside the window.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
from typing import Dict, List, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import trace_reduce  # noqa: E402

PROGRAM = ("paged.", "producer.", "train.", "boundary.", "transfer.")
NONE = "none"
# (start_ns, end_ns, name, depth among its thread's program spans)
Span = Tuple[int, int, str, int]


def program_spans(pd) -> List[Span]:
    """The program's spans on the host planes, each with its nesting depth
    among the program spans of its own thread (line)."""
    out: List[Span] = []
    for plane in pd.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            evs = sorted(((s, s + d, n) for n, s, d in
                          trace_reduce._events(line)
                          if n.startswith(PROGRAM)),
                         key=lambda e: (e[0], -e[1]))
            ends: List[int] = []         # enclosing spans' ends
            for s, e, n in evs:
                while ends and ends[-1] <= s:
                    ends.pop()
                out.append((s, e, n, len(ends)))
                ends.append(e)
    return out


def window_gaps(pd) -> Tuple[Tuple[int, int], List[Tuple[int, int]]]:
    """The ``bench.window`` annotation and its idle gaps on every device
    plane."""
    window = None
    gaps: List[Tuple[int, int]] = []
    devices = []
    for plane in pd.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for name, s, d in trace_reduce._events(line):
                if name == trace_reduce.WINDOW:
                    window = (s, s + d) if window is None else (
                        min(window[0], s), max(window[1], s + d))
    if window is None:
        raise ValueError(f"trace has no {trace_reduce.WINDOW!r} annotation")
    if not devices:
        raise ValueError("trace has no device plane")
    w0, w1 = window
    for plane in devices:
        ops = [(s, s + d) for line in plane.lines if line.name == "XLA Ops"
               for _, s, d in trace_reduce._events(line) if w0 <= s < w1]
        prev = w0
        for s, e in trace_reduce._clip(trace_reduce._union(ops), w0, w1) + [
                (w1, w1)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
    return window, gaps


def innermost(t: int, spans: List[Span]) -> str:
    open_ = [sp for sp in spans if sp[0] <= t < sp[1]]
    if not open_:
        return NONE
    return max(open_, key=lambda sp: (sp[3], sp[0]))[2]


def attribute(window, gaps, spans: List[Span], top: int = 10) -> dict:
    w0, w1 = window
    spans = [sp for sp in spans if sp[1] > w0 and sp[0] < w1]
    named = [(innermost((s + e) // 2, spans), e - s) for s, e in gaps]
    idle = sum(d for _, d in named)
    by: Dict[str, int] = collections.Counter()
    for name, d in named:
        by[name] += d
    stats: Dict[str, List[int]] = collections.defaultdict(lambda: [0, 0])
    for s, e, name, _ in spans:
        if w0 <= s < w1:
            stats[name][0] += 1
            stats[name][1] += e - s
    return {
        "window_s": (w1 - w0) * 1e-9,
        "idle_s": idle * 1e-9,
        "gaps": len(named),
        "idle_share_by_span": {k: v / idle for k, v in by.most_common()}
        if idle else {},
        "longest_gaps": [[n, d * 1e-9] for n, d in
                         sorted(named, key=lambda g: -g[1])[:top]],
        "spans": {k: {"count": c, "total_s": t * 1e-9,
                      "mean_ms": t * 1e-6 / c}
                  for k, (c, t) in sorted(stats.items())},
    }


def report(path: str, top: int = 10) -> dict:
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    pd = trace_reduce.load(path)
    window, gaps = window_gaps(pd)
    return attribute(window, gaps, program_spans(pd), top)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="a .xplane.pb or a directory holding one")
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args(argv)
    print(json.dumps(report(args.trace, args.top)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
