"""Model / run configuration for the periodic-asynchrony RL framework.

Every assigned architecture is expressed as a :class:`ModelConfig`. The config
is a plain frozen dataclass (hashable -> usable as a jit static argument).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    # identity ----------------------------------------------------------
    name: str = "unnamed"
    family: str = "dense"  # dense | moe | ssm | hybrid | audio | vlm
    source: str = ""       # citation (arXiv id / model card)

    # core transformer ---------------------------------------------------
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 0          # 0 -> d_model // num_heads
    d_ff: int = 1024
    vocab_size: int = 1024
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    tie_embeddings: bool = False

    # attention variants --------------------------------------------------
    sliding_window: Optional[int] = None   # None -> full causal
    use_mla: bool = False                  # DeepSeek-V2 multi-head latent attention
    kv_lora_rank: int = 512
    q_lora_rank: int = 0                   # 0 -> full-rank q projection
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    # MoE ------------------------------------------------------------------
    num_experts: int = 0                   # 0 -> dense FFN
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0                      # per-expert hidden size
    first_k_dense: int = 0                 # leading dense layers (DeepSeek-V2)
    dense_d_ff: int = 0                    # d_ff of those dense layers
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.001

    # SSM (Mamba-2 / SSD) --------------------------------------------------
    ssm_state_size: int = 0                # N; 0 -> no ssm path
    ssm_num_heads: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv_width: int = 4
    ssm_chunk_size: int = 128
    ssm_num_groups: int = 1

    # hybrid (Hymba): run attention AND ssm in parallel inside each block
    hybrid: bool = False

    # encoder/decoder (Whisper) ---------------------------------------------
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    encoder_seq_len: int = 1500            # precomputed frame embeddings (stub frontend)
    max_target_positions: int = 448

    # VLM (InternVL) ----------------------------------------------------------
    vision_prefix_len: int = 0             # precomputed patch embeddings (stub frontend)

    # numerics -----------------------------------------------------------------
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"

    # use the Pallas block-sparse flash kernel for training/prefill
    # attention instead of the pure-JAX chunked path (production TPU path;
    # on CPU it runs in interpret mode — correct but slow, tests only)
    use_pallas_attention: bool = False
    # activation checkpointing (paper Table 7: gradient checkpointing enabled)
    remat: bool = True
    # attention chunking for the pure-JAX flash path
    attn_chunk_size: int = 512
    # sequence chunk for the fused logp/loss scan
    loss_chunk_size: int = 512

    # ---------------------------------------------------------------------
    def __post_init__(self):
        if self.head_dim == 0 and self.num_heads:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model if self.ssm_state_size else 0

    @property
    def supports_long_decode(self) -> bool:
        """True if decode cost/state is sub-linear in context (SSM state or
        sliding-window KV) -> eligible for the long_500k shape."""
        if self.is_encoder_decoder:
            return False  # whisper decoder context is 448; see DESIGN.md
        return self.family in ("ssm", "hybrid") or self.sliding_window is not None

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head), for
        MODEL_FLOPS = 6 N D book-keeping."""
        d = self.d_model
        n = 0
        n += self.vocab_size * d                      # embedding
        if not self.tie_embeddings:
            n += self.vocab_size * d                  # lm head
        L = self.num_layers

        def attn_params() -> int:
            if self.use_mla:
                p = d * self.kv_lora_rank + d * self.qk_rope_head_dim
                p += self.kv_lora_rank * self.num_heads * (self.qk_nope_head_dim + self.v_head_dim)
                if self.q_lora_rank:
                    p += d * self.q_lora_rank + self.q_lora_rank * self.num_heads * (
                        self.qk_nope_head_dim + self.qk_rope_head_dim)
                else:
                    p += d * self.num_heads * (self.qk_nope_head_dim + self.qk_rope_head_dim)
                p += self.num_heads * self.v_head_dim * d
                return p
            hd = self.head_dim
            return d * (self.num_heads + 2 * self.num_kv_heads) * hd + self.num_heads * hd * d

        def dense_ffn(ff: int) -> int:
            return 3 * d * ff  # swiglu

        def ssm_params() -> int:
            di = self.ssm_d_inner
            G, N, H = self.ssm_num_groups, self.ssm_state_size, self.ssm_num_heads
            p = d * (2 * di + 2 * G * N + H)          # in_proj [z,x,B,C,dt]
            p += self.ssm_conv_width * (di + 2 * G * N)  # conv
            p += H * 2 + di                           # A_log, D, dt_bias-ish + norm
            p += di * d                               # out_proj
            return p

        per_layer = 2 * d  # norms
        if self.family == "ssm":
            per_layer += ssm_params()
        else:
            per_layer += attn_params()
            if self.hybrid:
                per_layer += ssm_params() + 2 * d
        n_moe_layers = 0
        if self.is_moe:
            n_moe_layers = L - self.first_k_dense
            n += self.first_k_dense * dense_ffn(self.dense_d_ff or self.d_ff)
            n += n_moe_layers * (
                self.num_experts * 3 * d * self.moe_d_ff
                + self.num_shared_experts * 3 * d * self.moe_d_ff
                + d * self.num_experts  # router
            )
        elif self.family != "ssm":
            per_layer += dense_ffn(self.d_ff)
        n += L * per_layer
        if self.is_encoder_decoder:
            # encoder blocks: self-attn + ffn; decoder adds cross-attn
            enc = self.num_encoder_layers * (attn_params() + dense_ffn(self.d_ff) + 2 * d)
            n += enc + L * (attn_params() + d)  # cross attention + norm
        return int(n)

    def active_param_count(self) -> int:
        """Activated parameters per token (MoE: routed top-k + shared)."""
        if not self.is_moe:
            return self.param_count()
        d = self.d_model
        full = self.param_count()
        n_moe_layers = self.num_layers - self.first_k_dense
        all_experts = n_moe_layers * self.num_experts * 3 * d * self.moe_d_ff
        active = n_moe_layers * self.num_experts_per_tok * 3 * d * self.moe_d_ff
        return int(full - all_experts + active)


# ---------------------------------------------------------------------
# Engine x family validation matrix (DESIGN.md §Known-issues, README
# support matrix). Every decode-engine construction site consults this
# instead of hand-rolling family asserts, so the exclusion list lives in
# exactly one place and each remaining exclusion is architectural.
# ---------------------------------------------------------------------

ROLLOUT_ENGINES = ("group", "cbatch", "paged")

# The speculative-decode plane (DESIGN.md §Spec-decode) is not a fourth
# engine — it rides the three above — but its applicability is validated
# through the same matrix so the exclusion list lives in one place.
SPEC_PLANE = "spec"

# The radix prefix cache (DESIGN.md §Radix-prefix-cache) likewise rides the
# paged engine rather than being an engine of its own: it shares cached
# prompt pages across requests, so it needs per-token paged KV to share.
PREFIX_PLANE = "prefix"


def engine_support(cfg: ModelConfig, engine: str) -> Tuple[bool, str]:
    """(supported, reason) for running ``cfg`` on a decode engine:

    * ``group``  — the group-at-a-time Sampler (reference semantics);
    * ``cbatch`` — the dense-slot continuous-batching engine;
    * ``paged``  — the token-level paged pool (GQA K/V pages or MLA
      latent pages; sliding-window configs reclaim out-of-window pages);
    * ``spec``   — the draft/verify speculative-decode plane layered on
      any of the engines (src/repro/spec/);
    * ``prefix`` — the radix prefix cache layered on the paged pool
      (core/radix.py: cached prompt pages shared across requests).
    """
    if engine == SPEC_PLANE:
        return _spec_support(cfg)
    if engine == PREFIX_PLANE:
        return _prefix_support(cfg)
    if engine not in ROLLOUT_ENGINES:
        raise KeyError(f"unknown engine {engine!r}; known: "
                       f"{ROLLOUT_ENGINES + (SPEC_PLANE, PREFIX_PLANE)}")
    if engine == "group":
        return True, "reference decode path for every family"
    if cfg.is_encoder_decoder:
        return False, ("decoder context is bounded (max_target_positions) "
                       "and decode is dominated by cross-attention over a "
                       "fixed encoder memory — served via the group path")
    if cfg.vision_prefix_len:
        return False, ("the vision prefix is a per-request dense prefix "
                       "embedding, not token KV — served via the group path")
    if engine == "cbatch":
        return True, "fixed slot pool over one contiguous cache"
    # paged
    if cfg.family == "ssm" or cfg.hybrid:
        return False, ("O(1) recurrent state: there is no per-token KV to "
                       "page; prefix-state sharing (core/prefix.py) is the "
                       "prompt-sharing analogue")
    kind = "MLA latent (ckv, kr) rows" if cfg.use_mla else "per-head K/V rows"
    win = ("; out-of-window pages are reclaimed to the freelist"
           if cfg.sliding_window is not None else "")
    return True, f"pages hold {kind}{win}"


def _spec_support(cfg: ModelConfig) -> Tuple[bool, str]:
    """Speculative decode needs a REVERSIBLE per-token cache: a rejected
    draft's KV entries are overwritten or rolled back (paged engines return
    speculative pages to the freelist). Recurrent state cannot be rolled
    back cheaply, and the group-path-only modality families never see the
    multi-token verify forward (DESIGN.md §Spec-decode, §Known-issues)."""
    if cfg.family == "ssm" or cfg.hybrid:
        return False, ("O(1) recurrent state advances irreversibly per "
                       "token — a rejected draft would need the pre-draft "
                       "SSM state restored, i.e. a state checkpoint per "
                       "speculated token")
    if cfg.is_encoder_decoder:
        return False, ("served via the group path only (bounded decoder "
                       "context, cross-attention-dominated) — no verify "
                       "engine to ride")
    if cfg.vision_prefix_len:
        return False, ("served via the group path only (dense vision "
                       "prefix) — no verify engine to ride")
    kind = "MLA latent" if cfg.use_mla else "GQA"
    win = (" incl. sliding-window (speculative pages respect reclamation)"
           if cfg.sliding_window is not None else "")
    return True, f"k+1-token verify through the {kind} cache{win}"


def _prefix_support(cfg: ModelConfig) -> Tuple[bool, str]:
    """The radix prefix cache shares PAGES, so it inherits exactly the
    paged engine's applicability: per-token cache rows that are a pure
    function of (token, position) — which is also why a cached page is
    bitwise identical to a cold prefill of the same span (core/radix.py,
    tests/test_radix.py)."""
    ok, reason = engine_support(cfg, "paged")
    if not ok:
        return False, reason
    kind = "MLA latent" if cfg.use_mla else "per-head K/V"
    win = (" (window-dead leading pages are never cached)"
           if cfg.sliding_window is not None else "")
    return True, (f"radix tree shares cached {kind} prompt pages across "
                  f"any common token-span prefix{win}")


def engine_support_matrix(cfg: ModelConfig) -> dict:
    """{engine: (supported, reason)} for one config (+ the spec and
    prefix planes)."""
    return {e: engine_support(cfg, e)
            for e in ROLLOUT_ENGINES + (SPEC_PLANE, PREFIX_PLANE)}


def require_engine_support(cfg: ModelConfig, engine: str) -> None:
    ok, reason = engine_support(cfg, engine)
    if not ok:
        raise ValueError(f"{cfg.name}: rollout engine {engine!r} is not "
                         f"applicable — {reason} (DESIGN.md §Known-issues)")


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


@dataclasses.dataclass(frozen=True)
class RLConfig:
    """GRPO / periodic-asynchrony run configuration (paper Tables 7-9)."""
    algo: str = "grpo"                 # grpo | ppo
    group_size: int = 32               # answers per prompt (G)
    batch_prompts: int = 32            # prompts per iteration (N)
    micro_batch: int = 1               # samples per micro-step (m)
    kl_coef: float = 0.02
    clip_eps_low: float = 0.2
    clip_eps_high: float = 0.2
    temperature: float = 1.0
    top_p: float = 1.0
    learning_rate: float = 1e-6
    weight_decay: float = 0.01
    grad_clip: float = 1.0
    adam_b1: float = 0.9
    adam_b2: float = 0.95
    max_prompt_len: int = 128
    max_response_len: int = 128
    shared_prompt_attention: bool = False
    # beyond-paper: round SPA slot stride/prompt block up to the Pallas
    # tile size (128) so response x response tiles prune exactly (see
    # core/spa.py pack_spa and EXPERIMENTS.md SPerf). 0 = paper layout.
    spa_align: int = 0
    mode: str = "async"                # sync | async | async_offpolicy
    staleness_eta: int = 1             # for the AReaL-like off-policy baseline
    num_inference_instances: int = 4   # train:rollout ratio (paper: 1:4)
    # rollout decode engine (DESIGN.md §Continuous-batching):
    #   "group" — one jitted group-at-a-time Sampler call per request;
    #   "paged" — token-level continuous batching over a paged KV cache
    #             with one physical prompt copy per GRPO group. Token-
    #             identical to "group" under the same key; requires a
    #             decoder-only GQA family and mode != async_offpolicy
    #             (weight sync needs a quiescent engine).
    rollout_engine: str = "group"
    cbatch_slots: int = 8              # decode slots per paged instance
    kv_page_size: int = 16             # tokens per KV page
    kv_pages: int = 0                  # physical pages (0 = auto-size)
    # Capture per-token logprobs of the sampled ids at rollout time
    # (DESIGN.md §Tri-model-capture). Under Proposition 1 the rollout
    # weights ARE the old-policy weights, so the captured values replace
    # the trainer's old-policy recompute: the tri-model's no-grad pass
    # shrinks from stacked old+ref to a single ref forward. In
    # async_offpolicy mode the captured values are evaluated under the
    # BEHAVIOR weights instead of the current old weights, removing the
    # old~behavior weights approximation from the importance ratio (both
    # paths use raw-distribution logprobs; sampling-time temperature/top-p
    # filtering sits outside the ratio convention either way). Rollouts
    # without captured values (simulated/scripted instances) fall back to
    # the recompute path per micro-batch.
    capture_logprobs: bool = True
    # --- speculative decode (DESIGN.md §Spec-decode) ------------------
    # Draft/verify plane over the rollout engines: propose spec_k tokens
    # cheaply, verify them in ONE k+1-token target forward, accept via
    # rejection sampling. Distribution-exact, so Proposition 1 survives:
    # greedy decode is token-identical to the non-spec engines, sampled
    # decode draws exactly from the target policy (tests/test_spec.py),
    # and capture_logprobs returns TARGET-model logprobs straight from
    # the verify pass. SSM/enc-dec/VLM are excluded (engine_support).
    spec_decode: bool = False
    spec_k: int = 4                    # drafted tokens per verify step
    # draft provider: "prompt_lookup" (n-gram reuse of prompt/response
    # tokens — no extra model) or "model" (small resident draft model)
    spec_draft: str = "prompt_lookup"
    spec_ngram: int = 3                # longest n-gram the lookup tries
    # --- device-resident decode (DESIGN.md §Device-resident-decode) ---
    # Steps D fused per jitted decode block of the paged rollout engine:
    # tokens, EOS flags and logprobs accumulate in device buffers for D
    # steps and drain to Python once per block (double-buffered — block
    # n+1 dispatches before block n's readback lands), so the hot loop
    # never blocks on a per-token device_get. 1 = drain every step
    # (legacy cadence, bitwise-identical admission/eviction timing).
    # The paged engine is sampled-identical for every D (per-row step
    # keys).
    # 8 from a sweep of D in {1, 4, 8, 16, 32} on one TPU v5e (PERF.md
    # §6): at D=1 the host's launch of each one-token block (2.4 ms) is
    # as long as the block's device time (2.5 ms) and the chip idles 69%;
    # D=8 launches once per 8 tokens, and a step's copies shrink (the
    # weights' relayout runs once a block), so a step costs 1.66 ms of
    # device time and GRPO tokens/s per device doubles (8,470 -> 17,540).
    # D=8 is the smallest D within 1% of the best in both GRPO cells;
    # D=16 and 32 lose, since an iteration's groups land in about 3
    # consecutive blocks and each block of lag costs D steps. RL
    # rollouts can take a long block: their groups all arrive at the
    # iteration's start, and the engine is quiescent at every weight
    # sync. The serving tier (launch/serve.py) keeps its own default of
    # 1, since there D delays the admission of arrivals.
    decode_drain_interval: int = 8
    # --- radix prefix cache (DESIGN.md §Radix-prefix-cache) -----------
    # Share cached prompt pages across requests with any common
    # token-span prefix (paged engine only): admission walks a radix
    # tree, retains matched pages, and prefills only the suffix. Cached
    # page content is bitwise what a cold prefill writes (per-token KV),
    # so rollouts stay token-identical (tests/test_radix.py). Idle cached
    # pages are LRU-evicted by the admission gate on a page deficit.
    prefix_cache: bool = False
    # --- weight-plane (DESIGN.md §Weight-plane) -----------------------
    # The iteration-boundary trainer->pool weight push streams the param
    # tree as fixed-size buckets through repro.transfer instead of one
    # whole-tree device_put per instance.
    transfer_bucket_bytes: int = 1 << 22   # wire bytes coalesced per bucket
    # Overlap: start streaming the new version's buckets the moment the
    # optimizer update materialises (background thread), hiding wire time
    # under the trainer's iteration tail. Rollouts stay version-GATED, so
    # Proposition 1 is preserved exactly — the param trajectory is
    # bitwise-identical to eager sync (tests/test_transfer.py).
    transfer_overlap: bool = True
    # Wire dtype for the payload ("" = stream the storage dtype, bitwise).
    # E.g. "bfloat16" streams a bf16 payload while fp32 master weights
    # stay trainer-side.
    transfer_wire_dtype: str = ""
    # Cast with the Pallas fused cast+copy kernel
    # (kernels/transfer_cast.py) instead of the pure-JAX astype path; only
    # meaningful when transfer_wire_dtype differs from storage.
    transfer_pallas_cast: bool = False
    # --- observability (DESIGN.md §Observability) ---------------------
    # Write a Chrome/Perfetto trace of the pipeline to this path ("" =
    # tracing disabled, the null-span fast path). Spans reuse the
    # pipeline's existing stopwatch reads, so enabling tracing adds no
    # device barriers; inspect with `repro-trace report <path>`.
    trace: str = ""
    # Streaming trace export: write rotating JSONL segments
    # (trace-NNNN.jsonl) into this directory instead of buffering the
    # whole run in memory ("" = monolithic `trace` behaviour). Peak
    # tracer memory is bounded at threads x flush batch regardless of
    # run length; read back with `repro-trace report <dir>`.
    trace_dir: str = ""
    # Events per segment file before rotation (and the order of the
    # bounded in-memory flush batch).
    trace_segment_events: int = 8192
    # Per-thread buffered events before a flush to the current segment —
    # the crash-durability granularity: at most this many events per
    # thread are lost to a hard kill.
    trace_flush_events: int = 256
    seed: int = 0
