"""End-to-end GRPO training driver (the paper's workload at CPU scale).

Wires the full periodic-asynchrony pipeline (paper Figure 1):

    PromptLoader -> TemporaryDataGenerator -> InferencePool
                          |  RolloutQueue  |
    PeriodicAsyncScheduler (consumer: tri-model GRPO + grad accumulation)

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
        --mode async --iterations 4 [--spa] [--prompt-pad 256]

Any assigned architecture id is accepted; the model is reduced to its
CPU-smoke variant unless --full is given (full configs are for the dry-run).
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from repro.configs import ARCH_IDS, get_config, reduced_config
from repro.configs.base import RLConfig
from repro.core import (InferenceInstance, InferencePool, PeriodicAsyncScheduler,
                        RolloutQueue, TemporaryDataGenerator, TriModelState)
from repro.data.loader import PromptLoader
from repro.data.tasks import ArithmeticTask
from repro.data.tokenizer import Tokenizer
from repro.launch.compile_cache import (canonicalize_source_paths,
                                         enable_compile_cache)
from repro.models import init
from repro.obs import trace as otrace
from repro.rl.reward import RuleBasedReward
from repro.rl.rollout import Sampler
from repro.transfer.service import WeightTransferService


def build_pipeline(cfg, rl: RLConfig, *, seed: int = 0, prompt_pad: int = 0,
                   latency_fn=None, scripted_fn=None):
    """Returns (scheduler, components dict). With ``scripted_fn`` the
    inference instances run in simulated-latency mode (remote-service view);
    otherwise they run the real jitted sampler — group-at-a-time, or the
    token-level paged engine when ``rl.rollout_engine == "paged"``."""
    tok = Tokenizer(cfg.vocab_size)
    task = ArithmeticTask(seed=seed, prompt_pad=prompt_pad)
    loader = PromptLoader(task, tok, rl.batch_prompts, rl.max_prompt_len)
    params = init(jax.random.PRNGKey(seed), cfg)
    tri = TriModelState.create(params)
    sampler = None
    if scripted_fn is None:
        if rl.spec_decode and rl.rollout_engine == "group":
            # speculative group engine (DESIGN.md §Spec-decode): same
            # generate() surface, k+1 tokens per target forward; greedy
            # decode token-identical, sampled decode distribution-exact,
            # captured logprobs come from the verify pass
            from repro.configs.base import require_engine_support
            require_engine_support(cfg, "spec")
            from repro.spec import SpecSampler
            sampler = SpecSampler(
                cfg, rl.max_prompt_len, rl.max_response_len,
                spec_k=rl.spec_k, draft=rl.spec_draft, ngram=rl.spec_ngram,
                temperature=rl.temperature, top_p=rl.top_p,
                capture_logprobs=rl.capture_logprobs, seed=seed)
        else:
            sampler = Sampler(cfg, rl.max_prompt_len, rl.max_response_len,
                              temperature=rl.temperature, top_p=rl.top_p,
                              capture_logprobs=rl.capture_logprobs)

    def paged_engine():
        if rl.rollout_engine != "paged" or scripted_fn is not None:
            return None
        if rl.mode == "async_offpolicy":
            raise ValueError(
                "rollout_engine='paged' needs a quiescent engine at weight "
                "sync; the off-policy baseline syncs mid-flight — use the "
                "group engine (DESIGN.md §Continuous-batching)")
        # engine x family validation matrix (configs/base.py): GQA and MLA
        # families page, sliding-window configs reclaim; SSM/enc-dec/VLM
        # are rejected here with the architectural reason.
        from repro.configs.base import require_engine_support
        require_engine_support(cfg, "paged")
        from repro.core.paged import PagedGroupEngine
        return PagedGroupEngine(
            cfg, num_slots=rl.cbatch_slots, page_size=rl.kv_page_size,
            num_pages=rl.kv_pages, max_prompt_len=rl.max_prompt_len,
            max_new_tokens=rl.max_response_len, group_size=rl.group_size,
            temperature=rl.temperature, top_p=rl.top_p,
            capture_logprobs=rl.capture_logprobs,
            spec_k=rl.spec_k if rl.spec_decode else 0,
            spec_draft=rl.spec_draft, spec_ngram=rl.spec_ngram,
            prefix_cache=rl.prefix_cache,
            drain_interval=rl.decode_drain_interval, seed=seed)

    instances = [InferenceInstance(i, cfg, sampler, latency_fn=latency_fn,
                                   scripted_fn=scripted_fn,
                                   paged_engine=paged_engine())
                 for i in range(rl.num_inference_instances)]
    pool = InferencePool(instances)
    queue = RolloutQueue()
    gen = TemporaryDataGenerator(pool, queue, RuleBasedReward(tok),
                                 rl.group_size)
    # the weight-plane (DESIGN.md §Weight-plane): when a mesh is installed
    # the reshard plan carries trainer-profile -> inference-profile
    # (infer_tp: TP-sharded, data-replicated) placements per leaf; on a
    # single device both spec trees resolve to unplaced device_puts.
    from repro.sharding.specs import current_mesh, param_specs, \
        param_specs_for_profile
    mesh = current_mesh()
    transfer = WeightTransferService(
        pool,
        bucket_bytes=rl.transfer_bucket_bytes,
        wire_dtype=rl.transfer_wire_dtype or None,
        use_pallas_cast=rl.transfer_pallas_cast,
        overlap=rl.transfer_overlap,
        src_specs=None if mesh is None else param_specs(params, mesh),
        dst_specs=None if mesh is None else param_specs_for_profile(
            params, mesh, "infer_tp"))
    sched = PeriodicAsyncScheduler(cfg, rl, tri, gen, queue, loader,
                                   transfer=transfer)
    # the grad step holds Pallas kernels (DESIGN.md §3): keep the
    # checkout's path out of their persistent-cache keys
    canonicalize_source_paths()
    return sched, {"tokenizer": tok, "task": task, "loader": loader,
                   "pool": pool, "queue": queue, "generator": gen,
                   "tri": tri, "transfer": transfer}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b", choices=ARCH_IDS)
    ap.add_argument("--mode", default="async",
                    choices=["sync", "async", "async_offpolicy"])
    ap.add_argument("--iterations", type=int, default=4)
    ap.add_argument("--batch-prompts", type=int, default=4)
    ap.add_argument("--group-size", type=int, default=4)
    ap.add_argument("--micro-batch", type=int, default=2)
    ap.add_argument("--instances", type=int, default=2)
    ap.add_argument("--rollout-engine", default="group",
                    choices=["group", "paged"],
                    help="rollout decode path: group-at-a-time sampler or "
                         "token-level paged continuous batching")
    ap.add_argument("--cbatch-slots", type=int, default=8,
                    help="decode slots per paged instance")
    ap.add_argument("--kv-page-size", type=int, default=16)
    ap.add_argument("--drain-interval", type=int,
                    default=RLConfig().decode_drain_interval,
                    help="fused decode-block length D for the paged engine "
                         "(DESIGN.md §Device-resident-decode): the host "
                         "drains device token buffers once per D steps; "
                         "defaults to RLConfig's decode_drain_interval; "
                         "1 = legacy per-token cadence")
    ap.add_argument("--spec", action="store_true",
                    help="speculative decode for rollouts (DESIGN.md "
                         "§Spec-decode): k drafted tokens verified per "
                         "target forward, distribution-exact (Proposition "
                         "1 intact)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens per verify step")
    ap.add_argument("--spec-draft", default="prompt_lookup",
                    choices=["prompt_lookup", "model"],
                    help="draft provider: n-gram prompt lookup (no extra "
                         "model) or a small resident draft model")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache on the paged rollout engine "
                         "(DESIGN.md §Radix-prefix-cache): prompts sharing "
                         "a token prefix across groups/iterations share "
                         "its pages, suffix-only prefill")
    ap.add_argument("--max-prompt-len", type=int, default=48)
    ap.add_argument("--max-response-len", type=int, default=16)
    ap.add_argument("--prompt-pad", type=int, default=0)
    ap.add_argument("--no-capture-logprobs", action="store_true",
                    help="disable rollout-time logprob capture — the trainer "
                         "recomputes old-policy logprobs via the stacked "
                         "old+ref tri-model forward (DESIGN.md "
                         "§Tri-model-capture)")
    ap.add_argument("--no-transfer-overlap", action="store_true",
                    help="disable weight-plane overlap: publish+flip "
                         "eagerly inside the iteration boundary instead of "
                         "streaming buckets under the trainer's iteration "
                         "tail (DESIGN.md §Weight-plane)")
    ap.add_argument("--transfer-bucket-bytes", type=int, default=1 << 22,
                    help="wire bytes coalesced per weight-plane bucket")
    ap.add_argument("--transfer-wire-dtype", default="",
                    choices=["", "bfloat16", "float32"],
                    help="weight-plane payload dtype ('' = storage dtype, "
                         "bitwise)")
    ap.add_argument("--transfer-pallas-cast", action="store_true",
                    help="wire cast via the Pallas fused cast+copy kernel")
    ap.add_argument("--spa", action="store_true",
                    help="enable shared-prompt attention packing")
    ap.add_argument("--spa-align", type=int, default=0,
                    help="round SPA slot stride to this tile size "
                         "(128 on TPU; 0 = paper layout)")
    ap.add_argument("--profile", default="baseline",
                    choices=["baseline", "dp2", "dp2_zero1", "sp_heads"],
                    help="sharding profile (see sharding/specs.py SPerf)")
    ap.add_argument("--full", action="store_true",
                    help="use the full (non-reduced) config — dry-run scale")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--trace", default="",
                    help="write a Chrome/Perfetto trace of the pipeline to "
                         "this path (iterations, producer busy spans, train "
                         "steps, weight-plane buckets); analyze with "
                         "`repro-trace report`")
    ap.add_argument("--trace-dir", default="",
                    help="streaming trace export: rotate JSONL segments "
                         "into this directory (bounded tracer memory; "
                         "multi-hour-run safe); analyze with "
                         "`repro-trace report <dir>`")
    ap.add_argument("--trace-segment-events", type=int, default=8192,
                    help="events per trace segment before rotation")
    ap.add_argument("--trace-flush-events", type=int, default=256,
                    help="per-thread buffered events before a segment "
                         "flush (the crash-durability granularity)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve GET /metrics (Prometheus), /healthz and "
                         "/status on this port for the duration of the run "
                         "(0 = ephemeral; the ops plane, DESIGN.md "
                         "§Observability)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduced_config(cfg)
    rl = RLConfig(
        mode=args.mode, batch_prompts=args.batch_prompts,
        group_size=args.group_size, micro_batch=args.micro_batch,
        num_inference_instances=args.instances,
        max_prompt_len=args.max_prompt_len,
        max_response_len=args.max_response_len,
        shared_prompt_attention=args.spa, spa_align=args.spa_align,
        rollout_engine=args.rollout_engine, cbatch_slots=args.cbatch_slots,
        kv_page_size=args.kv_page_size,
        decode_drain_interval=args.drain_interval,
        spec_decode=args.spec, spec_k=args.spec_k,
        spec_draft=args.spec_draft, prefix_cache=args.prefix_cache,
        capture_logprobs=not args.no_capture_logprobs,
        transfer_overlap=not args.no_transfer_overlap,
        transfer_bucket_bytes=args.transfer_bucket_bytes,
        transfer_wire_dtype=args.transfer_wire_dtype,
        transfer_pallas_cast=args.transfer_pallas_cast, trace=args.trace,
        trace_dir=args.trace_dir,
        trace_segment_events=args.trace_segment_events,
        trace_flush_events=args.trace_flush_events,
        seed=args.seed)
    if rl.trace_dir:
        otrace.install(process_name="repro-train", stream_dir=rl.trace_dir,
                       flush_events=rl.trace_flush_events,
                       segment_events=rl.trace_segment_events)
    elif rl.trace:
        otrace.install(process_name="repro-train")

    from repro.sharding.specs import set_profile
    set_profile(args.profile)
    sched, _ = build_pipeline(cfg, rl, seed=args.seed,
                              prompt_pad=args.prompt_pad)
    server = None
    if args.metrics_port is not None:
        from repro.obs.server import OpsServer
        server = OpsServer(status_fn=sched.status,
                           port=args.metrics_port).start()
        print(f"ops server on {server.url} "
              f"(/metrics /healthz /status)")
    t0 = time.time()
    try:
        history = sched.run(args.iterations)
    except BaseException:
        # flush-on-crash: a mid-iteration failure must not lose the
        # timeline — streamed segments flush to disk, a monolithic
        # buffer exports what it has (the partial trace is exactly the
        # evidence a post-mortem needs)
        if rl.trace_dir:
            otrace.export()
            print(f"partial trace flushed to {rl.trace_dir}")
        elif rl.trace:
            otrace.export(rl.trace)
            print(f"partial trace written to {rl.trace}")
        otrace.uninstall()
        if server is not None:
            server.stop()
        raise
    wall = time.time() - t0

    total_tokens = sum(s.trained_tokens for s in history)
    print(f"\n{args.arch} mode={args.mode} spa={args.spa}: "
          f"{args.iterations} iterations, {total_tokens} tokens, "
          f"{wall:.1f}s wall, TPSPD={total_tokens / wall:.1f}")
    for s in history:
        m = s.metrics or {}
        extra = ""
        if "sync_gap" in m:
            extra += f" gap={m['sync_gap'] * 1e3:.0f}ms"
        if m.get("spec_acceptance"):
            extra += f" accept={m['spec_acceptance']:.2f}"
        if m.get("prefix_hit_rate"):
            extra += f" prefix_hit={m['prefix_hit_rate']:.2f}"
        if m.get("pages_reclaimed"):
            extra += f" reclaimed={m['pages_reclaimed']}"
        print(f"  iter {s.iteration}: wall={s.wall_time:.2f}s "
              f"tokens={s.trained_tokens} reward={s.reward_mean:.3f} "
              f"loss={m['loss']:.4g} grad_norm={m['grad_norm']:.4g} "
              f"staleness={s.max_staleness}{extra}")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump([s.__dict__ for s in history], f, indent=1, default=str)
    if server is not None:
        server.stop()
    if rl.trace_dir:
        otrace.export()
        otrace.uninstall()
        print(f"trace segments written to {rl.trace_dir}")
    elif rl.trace:
        otrace.export(rl.trace)
        otrace.uninstall()
        print(f"trace written to {rl.trace}")


if __name__ == "__main__":
    main()
