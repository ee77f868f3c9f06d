"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read, with nothing but ``jax.profiler.ProfileData``:

* the window: the host annotation ``bench.window`` the benchmark opens
  around the traced part of a run;
* busy time: the union of the device's op intervals inside the window,
  averaged over the device planes;
* device time per op and per module (XLA program), and the op events that
  ran inside each module (a kernel is found by its name inside the module
  that called it);
* idle gaps: stretches of the window with no op running, each named by
  the benchmark's own host annotations (``bench:<what>``) open at its
  midpoint, or ``unattributed``.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
HOST_PREFIX = "bench:"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _events(line):
    for ev in line.events:
        yield ev.name, int(ev.start_ns), int(ev.duration_ns)


def _op_name(name: str) -> str:
    """'%fusion.3 = bf16[...] fusion(...)' -> '%fusion.3'."""
    return name.split(" = ", 1)[0][:120]


def reduce(pd, *, max_gaps: int = 10, max_ops: int = 10) -> dict:
    host_marks: List[Tuple[int, int, str]] = []
    window: Optional[Tuple[int, int]] = None
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: list(_events(line)) for line in plane.lines}
            devices.append(lines)
            continue
        for line in plane.lines:
            for name, s, d in _events(line):
                if name == WINDOW:
                    window = (s, s + d) if window is None else (
                        min(window[0], s), max(window[1], s + d))
                elif name.startswith(HOST_PREFIX):
                    host_marks.append((s, s + d, name[len(HOST_PREFIX):]))
    if window is None:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    if not devices:
        raise ValueError("trace has no device plane")
    w0, w1 = window
    busy_ns, op_ns, mod_ns, mod_count = 0, collections.Counter(), \
        collections.Counter(), collections.Counter()
    top = collections.Counter()
    in_module: Dict[str, collections.Counter] = collections.defaultdict(
        collections.Counter)
    in_module_count: Dict[str, collections.Counter] = collections.defaultdict(
        collections.Counter)
    gaps = []
    for lines in devices:
        ops = [(_op_name(n), s, d) for n, s, d in lines.get("XLA Ops", [])
               if w0 <= s < w1]
        mods = [e for e in lines.get("XLA Modules", []) if w0 <= e[1] < w1]
        busy = _union(_clip([(s, s + d) for _, s, d in ops], w0, w1))
        busy_ns += sum(e - s for s, e in busy)
        for name, _, d in ops:
            op_ns[name] += d
        mstarts = [s for _, s, _ in mods]
        for name, s, d in mods:
            base = _module_name(name)
            mod_ns[base] += d
            mod_count[base] += 1
        for name, s, d in ops:
            i = bisect.bisect_right(mstarts, s) - 1
            if i >= 0 and s < mods[i][1] + mods[i][2]:
                base = _module_name(mods[i][0])
                in_module[base][name] += d
                in_module_count[base][name] += 1
                top[f"{base}/{name}"] += d
            else:
                top[name] += d
        prev = w0
        for s, e in busy + [(w1, w1)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
    n = len(devices)
    window_s = (w1 - w0) * 1e-9
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:max_gaps]:
        mid = (s + e) // 2
        labels = sorted({lab for a, b, lab in host_marks if a <= mid < b})
        named.append(["+".join(labels) if labels else "unattributed",
                      (e - s) * 1e-9])
    return {
        "window_s": window_s,
        "busy_s": busy_ns * 1e-9 / n,
        "devices": n,
        "op_s": {k: v * 1e-9 / n for k, v in op_ns.items()},
        "module_s": {k: v * 1e-9 / n for k, v in mod_ns.items()},
        "module_count": {k: v // n for k, v in mod_count.items()},
        "op_in_module_s": {m: {k: v * 1e-9 / n for k, v in c.items()}
                           for m, c in in_module.items()},
        "op_in_module_count": {m: {k: v // n for k, v in c.items()}
                               for m, c in in_module_count.items()},
        "breakdown": {
            "device_ops": [[k, v * 1e-9 / n]
                           for k, v in top.most_common(max_ops)],
            "idle_gaps": named,
        },
    }


def _module_name(name: str) -> str:
    """'jit__decode_block(123)' -> 'jit__decode_block'."""
    return name.split("(")[0].strip()


def module_time(red: dict, needle: str) -> Tuple[float, int]:
    """Device seconds and calls of the modules whose name contains
    ``needle``."""
    s = sum(v for k, v in red["module_s"].items() if needle in k)
    c = sum(v for k, v in red["module_count"].items() if needle in k)
    return s, c


def kernel_time(red: dict, module_needle: str, op_needle: str) -> float:
    """Device seconds of ops named like ``op_needle`` inside modules named
    like ``module_needle``."""
    return sum(v for m, ops in red["op_in_module_s"].items()
               if module_needle in m
               for k, v in ops.items() if op_needle in k)
