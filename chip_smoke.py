#!/usr/bin/env python3
"""Bring-up smoke run on one TPU chip, through the entry points a user calls.

Phases, in order; any failure exits non-zero and prints no result line:

1. device check — ``jax.devices()[0].platform`` must be ``"tpu"``; there is
   no CPU fallback (``--rehearse`` runs the same phases at ``reduced_config``
   on any backend and never prints the result line);
2. trainer — the paper's workload: ``launch/train.py build_pipeline`` then
   ``PeriodicAsyncScheduler.run(3)``, periodic-async GRPO with the paged
   rollout engine, on llama3.2-3b at its published widths;
3. serving — ``launch/serve.py serve_requests`` on the paged engine (MLA
   latent pages, MoE, radix prefix cache, Pallas decode kernel) on
   deepseek-v2-lite-16b at its published widths;
4. kernels — every Pallas kernel of the main path, compiled for the chip,
   against its f32 oracle at the two models' published widths.

Cuts (widths, heads, experts and vocabularies are the published ones):

* trainer: llama3.2-3b keeps 2 of its 28 layers and runs 1 rollout
  instance. Per parameter it holds policy/old/ref in bf16 (6 B), Adam
  moments in f32 (8 B) and the f32 gradient sum (4 B), and the instance's
  versioned weight store holds front and back bf16 copies (4 B): at 2
  layers (595M parameters, 394M of them the tied embedding) that is about
  13 GB of the chip's 16 GB;
* server: deepseek-v2-lite-16b keeps the leading dense layer and 4 of its
  26 MoE layers (5 of 27), all 64 experts (top-6, 2 shared) and the full
  102,400 vocabulary: about 2.8B parameters, 5.7 GB in bf16.

Readings printed here are smoke readings of one run, not benchmark numbers.

Usage:
    python chip_smoke.py              # on the chip
    python chip_smoke.py --rehearse   # CPU rehearsal at reduced sizes
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
TRAIN_LAYERS = 2            # of llama3.2-3b's 28
TRAIN_INSTANCES = 1
SERVE_LAYERS = 5            # deepseek-v2-lite: the dense layer + 4 MoE
TRAIN_ITERATIONS = 3


class CompileClock:
    """Sums JAX's backend-compile durations and persistent-cache hits, so
    each phase can report what it spent compiling (a warm cache shows as
    hits and a smaller sum)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        self._listeners = (on_duration, on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def reset(self) -> None:
        self.seconds, self.hits, self.misses = 0.0, 0, 0

    def close(self) -> None:
        import jax
        on_duration, on_event = self._listeners
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def _memory(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: stats.get(k) for k in ("peak_bytes_in_use", "bytes_in_use")}


def _release() -> int:
    """Drop what the finished phase left (compiled programs can hold the
    engines they were jitted from) and return the bytes of live arrays."""
    import jax
    jax.clear_caches()
    gc.collect()
    return sum(a.nbytes for a in jax.live_arrays())


def run_phase(name, fn, clock, dev):
    clock.reset()
    t0 = time.perf_counter()
    fn()
    wall = time.perf_counter() - t0
    mem = _memory(dev)
    print(f"[{name}] phase ok: compile_s={clock.seconds:.1f} "
          f"wall_s={wall:.1f} cache_hits={clock.hits} "
          f"cache_misses={clock.misses} "
          f"peak_bytes_in_use={mem['peak_bytes_in_use']} "
          f"bytes_in_use={mem['bytes_in_use']}", flush=True)


# ---------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------

def _parity_reward(response_ids, answer) -> float:
    """Stand-in score. A random-weight policy never solves a problem, so
    the rule-based rewards of every group tie at 0, GRPO's advantages and
    gradient are exactly 0, and the update would move nothing. The parity
    of the response's token sum spreads each group's rewards, so the
    gradient and the update are real work."""
    del answer
    return float(sum(int(t) for t in response_ids) % 2)


def trainer_phase(rehearse: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config, reduced_config
    from repro.configs.base import RLConfig
    from repro.launch.train import build_pipeline

    cfg = get_config("llama3.2-3b")
    cfg = (reduced_config(cfg) if rehearse
           else dataclasses.replace(cfg, num_layers=TRAIN_LAYERS))
    L = 16 if rehearse else 128
    rl = RLConfig(mode="async", rollout_engine="paged", group_size=8,
                  batch_prompts=2, micro_batch=2,
                  num_inference_instances=TRAIN_INSTANCES,
                  max_prompt_len=max(L, 48), max_response_len=L, seed=SEED)
    print(f"[trainer] {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.num_heads}/{cfg.num_kv_heads} heads x "
          f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"{cfg.param_dtype}; {cfg.param_count() / 1e6:.0f}M params; "
          f"mode={rl.mode} engine={rl.rollout_engine} G={rl.group_size} "
          f"prompts/iter={rl.batch_prompts} instances="
          f"{rl.num_inference_instances}", flush=True)
    sched, comp = build_pipeline(cfg, rl, seed=SEED)
    comp["generator"].reward_fn = _parity_reward
    history = sched.run(TRAIN_ITERATIONS)
    for s in history:
        m = s.metrics
        print(f"[trainer] iter {s.iteration}: wall={s.wall_time:.2f}s "
              f"tokens={s.trained_tokens} reward={s.reward_mean:.3f} "
              f"loss={m['loss']:.6g} grad_norm={m['grad_norm']:.6g} "
              f"staleness={s.max_staleness}", flush=True)
    assert len(history) == TRAIN_ITERATIONS, history
    for s in history:
        assert s.max_staleness == 0, f"iteration {s.iteration} off-policy"
        assert s.trained_tokens > 0, f"iteration {s.iteration} trained 0"
        assert np.isfinite(s.metrics["loss"]), s.metrics
        assert np.isfinite(s.metrics["grad_norm"]), s.metrics
    tri = comp["tri"]
    # the reference copy is the initial policy, never updated
    changed = jax.tree.leaves(jax.tree.map(
        lambda a, b: jnp.sum(a != b), tri.policy, tri.ref))
    n_changed = int(sum(int(c) for c in changed))
    n_leaves = sum(int(c) > 0 for c in changed)
    print(f"[trainer] policy version {tri.version}: {n_changed} elements "
          f"changed in {n_leaves}/{len(changed)} leaves", flush=True)
    assert tri.version == TRAIN_ITERATIONS and n_changed > 0


# ---------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------

def serving_phase(rehearse: bool) -> None:
    import numpy as np
    from repro.configs import get_config, reduced_config
    from repro.launch.serve import serve_requests

    cfg = get_config("deepseek-v2-lite-16b")
    cfg = (reduced_config(cfg) if rehearse
           else dataclasses.replace(cfg, num_layers=SERVE_LAYERS))
    cfg = dataclasses.replace(cfg, use_pallas_attention=True)
    n_req = 8
    plen, max_new = (64, 8) if rehearse else (512, 64)
    print(f"[serving] {cfg.name}: {cfg.num_layers} layers "
          f"({cfg.first_k_dense} dense), d_model {cfg.d_model}, "
          f"{cfg.num_heads} heads, MLA rank {cfg.kv_lora_rank}+"
          f"{cfg.qk_rope_head_dim}, {cfg.num_experts} experts top-"
          f"{cfg.num_experts_per_tok} + {cfg.num_shared_experts} shared, "
          f"vocab {cfg.vocab_size}, {cfg.param_dtype}; "
          f"{cfg.param_count() / 1e9:.2f}B params; {n_req} requests x "
          f"{plen} prompt tokens (first half shared) -> {max_new} new",
          flush=True)
    rng = np.random.RandomState(SEED)
    shared = rng.randint(3, cfg.vocab_size, plen // 2)
    prompts = [np.concatenate([shared, rng.randint(3, cfg.vocab_size,
                                                   plen - plen // 2)])
               for _ in range(n_req)]
    reqs, metrics, stats = serve_requests(
        cfg, prompts, max_prompt_len=plen, max_new=max_new,
        num_slots=n_req, page_size=16, prefix_cache=True, seed=SEED)
    full = [len(r.tokens) == max_new for r in reqs]
    print(f"[serving] {sum(full)}/{n_req} requests complete with "
          f"{max_new} tokens; decode steps {stats['decode_steps']}, prefix "
          f"hit rate {stats['prefix_hit_rate']:.3f}", flush=True)
    print(f"[serving] smoke reading, not a benchmark: TTFT p50 "
          f"{metrics['ttft_p50_s'] * 1e3:.1f} ms, TPOT p50 "
          f"{metrics['tpot_p50_s'] * 1e3:.2f} ms", flush=True)
    assert all(full), [len(r.tokens) for r in reqs]
    assert all(0 <= t < cfg.vocab_size for r in reqs for t in r.tokens)


# ---------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------

# Outputs are bf16 and never larger than the largest |v| (a convex
# combination of values drawn from N(0, 1), so |o| < 5): rounding the output
# to bf16 costs at most 2^-9 * 5 ~ 0.01, and the probabilities may be
# rounded to bf16 for the P.V matmul pass on the MXU (relative 2^-9 of the
# same sum). Inputs are bf16 already, so the Q.K products are exact.
ATTN_ATOL = 2e-2
# The cast kernel is XLA's convert, round to nearest even: bitwise.
CAST_ATOL = 0.0


def _kernel_shapes(rehearse: bool) -> dict:
    if rehearse:
        return dict(B=2, H=4, Hkv=2, D=64, mla_H=4, rank=64, rope=16,
                    nope=32, page=8, n_max=12, spec_k=4, suffix=70, spa_B=1,
                    spa_S=64, cast=(64, 256))
    # llama3.2-3b GQA (24/8 x 128), deepseek-v2-lite MLA (16 heads, rank
    # 512 + rope 64, nope 128); 8 decode rows over 1024-token contexts; the
    # multi-token kernels at a spec verify block (k+1) and at the radix
    # cache's suffix prefill (256 tokens: ROW_BLOCK-blocked query rows)
    return dict(B=8, H=24, Hkv=8, D=128, mla_H=16, rank=512, rope=64,
                nope=128, page=16, n_max=64, spec_k=4, suffix=256, spa_B=2,
                spa_S=1024, cast=(3072, 8192))


def _paged_context(rng, B, page, n_max, min_len):
    """Page tables and positions for B rows with random context lengths;
    unused table entries point at the null page 0 (pos 2^30, masked)."""
    import numpy as np
    P = 1 + B * n_max
    lens = rng.randint(min_len, n_max * page + 1, size=B)
    table = np.zeros((B, n_max), np.int32)
    pos = np.full((P, page), 2 ** 30, np.int32)
    for b in range(B):
        for j in range(-(-lens[b] // page)):
            p = 1 + b * n_max + j
            table[b, j] = p
            tok = j * page + np.arange(page)
            pos[p] = np.where(tok < lens[b], tok, 2 ** 30)
    return P, lens.astype(np.int32), table, pos


def kernel_phase(rehearse: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    from repro.kernels.ref import (decode_attention_ref, spa_attention_ref,
                                   verify_attention_ref)

    if not rehearse:
        assert not ops.auto_interpret(), "kernels would run interpreted"
    s = _kernel_shapes(rehearse)
    B, H, Hkv, D, page, n_max, k = (s["B"], s["H"], s["Hkv"], s["D"],
                                    s["page"], s["n_max"], s["spec_k"])
    rng = np.random.RandomState(SEED)
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))
    bf16 = jnp.bfloat16

    def normal(shape):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(bf16)

    def check(name, got, want, atol):
        got = np.asarray(jnp.asarray(got, jnp.float32))
        want = np.asarray(want, np.float32)
        assert got.shape == want.shape, (name, got.shape, want.shape)
        assert np.isfinite(got).all(), f"{name}: non-finite output"
        err = float(np.max(np.abs(got - want)))
        print(f"[kernels] {name} {tuple(got.shape)}: max |err| vs f32 "
              f"reference {err:.3g} (tolerance {atol})", flush=True)
        assert err <= atol, f"{name}: {err} > {atol}"

    def gather(pages, table):
        return pages[table].reshape((B, -1) + pages.shape[2:])

    blocks = {f"k={k}": k + 1, f"suffix={s['suffix']}": s["suffix"]}
    P, lens, table, pos = _paged_context(rng, B, page, n_max,
                                         min_len=max(blocks.values()))
    table, pos = jnp.asarray(table), jnp.asarray(pos)
    kv_pos = gather(pos, table)
    q_pos = jnp.asarray(lens - 1)
    q_pos_blk = {name: jnp.asarray(lens[:, None] - n + np.arange(n))
                 for name, n in blocks.items()}
    hp = jax.default_matmul_precision("highest")

    # paged GQA decode / verify (llama3.2-3b widths)
    kp, vp = normal((P, page, Hkv, D)), normal((P, page, Hkv, D))
    kk, vv = gather(kp, table), gather(vp, table)
    q1 = normal((B, H, D))
    got = ops.paged_decode_attention(q1, kp, vp, pos, table, q_pos)
    with hp:
        want = decode_attention_ref(q1, kk, vv, kv_pos, q_pos)
    check("paged_decode_attention", got, want, ATTN_ATOL)
    for name, n in blocks.items():
        qk, qp = normal((B, n, H, D)), q_pos_blk[name]
        got = ops.paged_verify_attention(qk, kp, vp, pos, table, qp)
        with hp:
            want = verify_attention_ref(qk, kk, vv, kv_pos, qp)
        check(f"paged_verify_attention({name})", got, want, ATTN_ATOL)
    del kp, vp, kk, vv

    # paged MLA decode / verify (deepseek-v2-lite widths): absorbed MLA is
    # MQA with Dk = rank + rope, Dv = rank, at the model's own scale
    mH, r, rd = s["mla_H"], s["rank"], s["rope"]
    scale = (s["nope"] + rd) ** -0.5
    ckv, kr = normal((P, page, r)), normal((P, page, rd))
    k_lat = jnp.concatenate([gather(ckv, table), gather(kr, table)],
                            axis=-1)[:, :, None, :]
    v_lat = gather(ckv, table)[:, :, None, :]
    q1 = normal((B, mH, r + rd))
    got = ops.paged_mla_decode_attention(q1, ckv, kr, pos, table, q_pos,
                                         scale=scale)
    with hp:
        want = decode_attention_ref(q1, k_lat, v_lat, kv_pos, q_pos,
                                    scale=scale)
    check("paged_mla_decode_attention", got, want, ATTN_ATOL)
    for name, n in blocks.items():
        qk, qp = normal((B, n, mH, r + rd)), q_pos_blk[name]
        got = ops.paged_mla_verify_attention(qk, ckv, kr, pos, table, qp,
                                             scale=scale)
        with hp:
            want = verify_attention_ref(qk, k_lat, v_lat, kv_pos, qp,
                                        scale=scale)
        check(f"paged_mla_verify_attention({name})", got, want, ATTN_ATOL)
    del ckv, kr, k_lat, v_lat

    # shared-prompt attention (llama3.2-3b widths): one prompt (segment 0)
    # then three responses (segments 1..3) that each see only the prompt
    # and themselves
    sb, S = s["spa_B"], s["spa_S"]
    lp = S // 4
    seg = np.repeat(np.arange(4), lp).astype(np.int32)
    spos = np.concatenate([np.arange(lp)] + [lp + np.arange(lp)] * 3)
    seg = jnp.asarray(np.broadcast_to(seg, (sb, S)))
    spos = jnp.asarray(np.broadcast_to(spos.astype(np.int32), (sb, S)))
    q, kk, vv = normal((sb, S, H, D)), normal((sb, S, Hkv, D)), \
        normal((sb, S, Hkv, D))
    got = ops.spa_attention(q, kk, vv, spos, spos, seg, seg)
    with hp:
        want = spa_attention_ref(q, kk, vv, spos, spos, seg, seg)
    check("spa_attention", got, want, ATTN_ATOL)
    del q, kk, vv

    # weight-plane wire cast f32 -> bf16
    x = jax.random.normal(next(keys), s["cast"], jnp.float32)
    check("transfer_cast(f32->bf16)", ops.transfer_cast(x, bf16),
          x.astype(bf16), CAST_ATOL)


# ---------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run every phase at reduced sizes on any backend "
                         "(Pallas in interpret mode off the chip); never "
                         "prints the result line")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: needs a TPU, found {dev.platform!r} "
              f"({dev.device_kind}); nothing was run", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {cache_dir}", flush=True)
    clock = CompileClock()
    try:
        for name, phase in (("trainer", trainer_phase),
                            ("serving", serving_phase),
                            ("kernels", kernel_phase)):
            run_phase(name, lambda: phase(args.rehearse), clock, dev)
            print(f"[{name}] live array bytes after release: {_release()}",
                  flush=True)
    finally:
        clock.close()
    if not args.rehearse:
        print(json.dumps({"ok": True, "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # a failed phase may leave pipeline threads behind; exit regardless
    os._exit(code)
