#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line of
standard output.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1> [--rehearse]

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic and per-layer metrics are files under ``bench/`` found by the
names there. Without a TPU (or with fewer chips than the cell asks for)
the run exits non-zero and prints no result. ``--rehearse`` runs the cell
at the program's reduced CPU sizes on any backend and never prints the
result line.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="reduced sizes on any backend; prints no result")
    args = ap.parse_args(argv)

    from bench import common, harness
    if not (common.CHECKOUT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not beside the benchmark; "
              "nothing was run", file=sys.stderr)
        return 3
    common.add_program_to_path()
    bench = common.benchmark()
    w = common.workload(args.workload, bench)
    import jax
    devs = jax.devices()
    if not args.rehearse and (devs[0].platform != "tpu"
                              or len(devs) < w["chips"]):
        print(f"bench: {args.workload} needs {w['chips']} TPU chip(s), "
              f"found {len(devs)} x {devs[0].platform} "
              f"({devs[0].device_kind}); nothing was run", file=sys.stderr)
        return 2
    cache = harness.enable_compile_cache()
    print(f"bench: {args.workload} seed={args.seed} seconds={args.seconds} "
          f"trace={args.trace} on {len(devs)} x {devs[0].device_kind}; "
          f"compile cache {cache}", file=sys.stderr, flush=True)
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), rehearse=args.rehearse,
                              t_start=T_START, bench=bench)
    harness.print_checks(result["checks"])
    if args.rehearse:
        print(f"bench: rehearsal finished, correct={result['correct']}; "
              f"{harness.result_line(result)}", file=sys.stderr)
        return 0
    print(harness.result_line(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # program threads that a failed run leaves behind must not keep the
    # process (and the chip) alive
    os._exit(code)
