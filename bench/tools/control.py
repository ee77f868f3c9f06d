"""Read the numbers that decide ``correct`` over many seeds in one process:
the program's own (the lower readings of each limit), and with
``--controls`` those of the float8 control and of faults put in the
program's place (the upper readings).

    python3 bench/tools/control.py --workload <name> --seeds 1,2,3 \
        --seconds 5 [--controls fp8,half_batch,token_altered] [--rehearse]

Prints one JSON line per seed. Each control is judged by the cell's own
limits, as a run is (``harness.run_cell``), and its ``correct`` has to come
out false. The window here is short: the compared numbers come from the
set-up iterations.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--controls", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    from bench import common, harness
    common.add_program_to_path()
    import jax
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    controls = [c for c in args.controls.split(",") if c]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        try:
            r = harness.run_cell(args.workload, seed, args.seconds, False,
                                 rehearse=args.rehearse, controls=controls)
        except Exception:                       # report and go on
            traceback.print_exc()
            print(json.dumps({"seed": seed, "error": True}), flush=True)
            continue
        print(json.dumps({
            "seed": seed, "correct": r["correct"],
            "numbers": {k: c["value"] for k, c in r["checks"].items()},
            "controls": r["info"].get("controls", {}),
            "memory_peak_bytes": r["device"]["memory_peak_bytes"],
            "e2e": {k: m["value"] for k, m in r["metrics"].items()},
            "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    os._exit(code)
