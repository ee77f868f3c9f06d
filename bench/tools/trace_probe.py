"""Record a small profiler trace on the chip and describe its structure:
planes, lines, event names and stats. The trace is written under
``<checkout>/.bench_trace/probe`` for use as a test fixture.

    python3 bench/tools/trace_probe.py
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import common  # noqa: E402


def main() -> int:
    common.add_program_to_path()
    import jax
    import jax.numpy as jnp
    import numpy as np
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, len(jax.devices()))
    from repro.kernels.ops import paged_mla_decode_attention

    B, H, r, rd, page, n_max = 4, 16, 512, 64, 16, 8
    P = 2 + B * n_max
    k = iter(jax.random.split(jax.random.PRNGKey(0), 8))
    q = jax.random.normal(next(k), (B, H, r + rd), jnp.bfloat16)
    ckv = jax.random.normal(next(k), (P, page, r), jnp.bfloat16)
    kr = jax.random.normal(next(k), (P, page, rd), jnp.bfloat16)
    pos = jnp.asarray(np.tile(np.arange(page, dtype=np.int32), (P, 1)))
    table = jnp.asarray(2 + np.arange(B * n_max, dtype=np.int32).reshape(
        B, n_max))
    qpos = jnp.full((B,), page * n_max - 1, jnp.int32)

    @jax.jit
    def step(x, w):
        return jnp.tanh(x @ w) @ w.T

    def decode_step(q):
        return paged_mla_decode_attention(q, ckv, kr, pos, table, qpos,
                                          scale=0.07)

    decode_step = jax.jit(decode_step)
    x = jax.random.normal(next(k), (512, 2048), jnp.bfloat16)
    w = jax.random.normal(next(k), (2048, 2048), jnp.bfloat16)
    jax.block_until_ready((step(x, w), decode_step(q)))
    out = os.path.join(str(common.CHECKOUT), ".bench_trace", "probe")
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench:matmul"):
                jax.block_until_ready(step(x, w))
            with jax.profiler.TraceAnnotation("bench:decode"):
                jax.block_until_ready(decode_step(q))
            with jax.profiler.TraceAnnotation("bench:sleep"):
                time.sleep(0.01)
    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    print("xplane files", files, [os.path.getsize(f) for f in files])
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(files[0])
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r} events={len(evs)}")
            for ev in evs[:6]:
                stats = {}
                try:
                    stats = dict(list(ev.stats)[:8])
                except Exception as e:      # noqa: BLE001
                    stats = {"err": repr(e)}
                print(f"    EV {ev.name[:90]!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} stats={str(stats)[:300]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
