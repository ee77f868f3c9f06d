"""Compile a cell's largest programs for a described TPU v5e (no chip) and
print ``memory_analysis()`` of each: argument, output, temporary and
aliased bytes, with the compile's verdict.

    JAX_PLATFORMS=cpu python3 bench/tools/aot_memory.py --workload <name>
        [--response-len N] [--group-size G] [--pallas-train]

Nothing runs; the chip's compiler refuses what would not fit or lower.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import common, weights  # noqa: E402


def _report(name, fn, *args):
    t0 = time.time()
    try:
        c = fn.lower(*args).compile()
    except Exception as e:                      # the compiler's refusal
        print(f"{name}: REFUSED after {time.time() - t0:.0f}s: "
              f"{type(e).__name__}: {str(e)[:300]}", flush=True)
        return
    m = c.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    vals = {k: getattr(m, k, None) for k in keys}
    print(f"{name}: compiled in {time.time() - t0:.0f}s "
          + " ".join(f"{k.replace('_size_in_bytes', '')}={v}"
                     for k, v in vals.items()), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--response-len", type=int, default=0)
    ap.add_argument("--group-size", type=int, default=0)
    ap.add_argument("--pallas-train", action="store_true")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    common.add_program_to_path()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    import repro.kernels.ops as ops
    ops.auto_interpret = lambda: False          # compile Mosaic, not interpret

    w = common.workload(args.workload)
    spec = common.config_spec(w["config"])
    traffic = common.traffic_spec(w["traffic"])
    cfg = common.model_config(spec)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    # the tree the benchmark hands the program: the layout of the reference
    # that the configuration names
    ref = common.reference_module(spec["reference"])
    pshapes = jax.eval_shape(lambda: weights.make(
        ref.layout(ref.dims_from_spec(spec)), 0))

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=dev), tree)

    P = sds(pshapes)
    if traffic["kind"] != "grpo":
        raise SystemExit(f"no memory probe for traffic kind "
                         f"{traffic['kind']!r}")
    from repro.configs.base import RLConfig
    from repro.optim.adam import adam_init
    from repro.rl.grpo import (MicroBatch, make_apply_update,
                               make_grad_step_captured)
    if args.pallas_train:
        cfg = dataclasses.replace(cfg, use_pallas_attention=True)
    Lr = args.response_len or traffic["max_response_len"]
    Lp = traffic["prompt_len"]
    G = args.group_size or traffic["group_size"]
    S = (Lp - 1) + G * (1 + Lr)             # one SPA-packed group
    rl = RLConfig(max_prompt_len=Lp, max_response_len=Lr, group_size=G)
    i32 = jax.ShapeDtypeStruct((1, S), jnp.int32, sharding=dev)
    f32 = jax.ShapeDtypeStruct((1, S), jnp.float32, sharding=dev)
    mb = MicroBatch(tokens=i32, labels=i32, positions=i32, segments=i32,
                    loss_mask=f32, advantages=f32,
                    n_samples=jax.ShapeDtypeStruct((), jnp.float32,
                                                   sharding=dev),
                    logp_behavior=f32)
    print(f"grad step: 1 row of {S} tokens (prompt {Lp}, {G} x "
          f"{Lr} responses), pallas={cfg.use_pallas_attention}",
          flush=True)
    _report("grad_step_captured", make_grad_step_captured(cfg, rl),
            P, P, P, mb)
    opt = sds(jax.eval_shape(adam_init, pshapes))
    grads = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, jnp.float32, sharding=dev), pshapes)
    _report("apply_update", make_apply_update(cfg, rl), P, opt, grads)


if __name__ == "__main__":
    main()
