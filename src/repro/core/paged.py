"""Token-level paged continuous-batching decode engine for the rollout pool
(DESIGN.md §Continuous-batching, §Cache-backends).

The group-at-a-time path (``rl/rollout.py``) decodes ``max_new`` steps for
every row of every group and serialises whole groups per instance; this
engine decodes ONE token per step for a pool of slots that mixes rows from
many GRPO groups, admitting pending rows the step a slot frees (the
admission/eviction policy is ``core/cbatch.py``'s ``SlotScheduler``).

The KV cache is paged (``models/attention.py PagedCacheBackend``):

  * one physical page pool per layer, stitched into logical sequences by a
    per-slot page table — vLLM's block table, JAX-native with fixed shapes;
  * pages hold whatever the family caches per token (``cache_streams``):
    per-head K/V rows for GQA, compressed ``(ckv, kr)`` latent rows for MLA
    — absorbed MLA decode gathers latent pages directly;
  * a GRPO group's K rows list the SAME prompt pages, so the shared prompt
    is stored once per group — the cache-level extension of SPA
    (``core/spa.py``), which shares the prompt's *compute* in training while
    this shares its *memory* (and prefill compute) in inference;
  * pages are refcounted: response pages free when their row completes,
    prompt pages when the whole group has (eviction = completion);
  * response pages are allocated LAZILY, one page ahead of the write
    cursor, against a per-row page *credit* reserved at admission — the
    admission gate reads ``free - outstanding_credit``, so a row that is
    admitted can always take its next page (no mid-decode stall, no
    deadlock);
  * sliding-window configs RECLAIM out-of-window pages: once every live
    query position of a row has slid past a page's last token
    (``q_pos - last_pos >= window``) the page leaves the row's table and
    its reference returns to the freelist (refcount-aware for shared
    prompt pages — a page another row still sees stays resident). A 500k
    decode therefore occupies O(window) pages per row, not O(context);
  * ``prefix_cache=True`` layers the radix prefix cache (``core/radix.py``,
    DESIGN.md §Radix-prefix-cache) over the pool: admission walks the tree
    for the longest cached page-aligned prefix, retains the matched pages
    into the group's table, prefills ONLY the suffix into private pages,
    and inserts the completed prompt pages back — page sharing across
    byte-identical prompts becomes sharing across any common token-span
    prefix, across groups and across time. LRU eviction of idle cached
    pages rides the admission gate, so the page-credit deadlock-freedom
    argument is unchanged.

Sampling is token-identical to the group-at-a-time ``Sampler`` under the
same PRNG key — greedy and sampled (``rl/rollout.py stepwise_keys`` +
``_sample_token_rows``); ``tests/test_paged_pool.py`` proves it. Page 0 is
the null page (pos 2^30, masked everywhere), page 1 the trash page inactive
slots write into.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, require_engine_support
from repro.core.cbatch import Completed, SlotScheduler
from repro.data.tokenizer import Tokenizer
from repro.models import forward_hidden, init_caches, init_paged_caches
from repro.models.attention import INVALID_POS, cache_streams
from repro.models.layers import lm_head_weight
from repro.obs import trace as otrace
from repro.obs.metrics import metrics
from repro.rl.rollout import (RolloutBatch, _sample_token_rows,
                              sampled_token_logprob, stepwise_keys)

NULL_PAGE = 0
TRASH_PAGE = 1
FIRST_PAGE = 2


class PageAllocator:
    """Host-side freelist + refcounts over the physical page pool.

    Prompt pages are allocated with refcount G (one per group row) and
    release once per row (at completion, or earlier when the row's window
    slides past the page); response pages are single-owner."""

    def __init__(self, num_pages: int):
        assert num_pages > FIRST_PAGE, "page pool smaller than its reserves"
        self._free = list(range(num_pages - 1, FIRST_PAGE - 1, -1))
        self._ref: Dict[int, int] = {}
        self.min_free = len(self._free)      # high-water occupancy marker

    @property
    def num_free(self) -> int:
        return len(self._free)

    def alloc(self, n: int, refcount: int = 1) -> Optional[List[int]]:
        if len(self._free) < n:
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = refcount
        self.min_free = min(self.min_free, len(self._free))
        return pages

    def retain(self, pages: List[int], n: int = 1) -> None:
        """Add ``n`` references to already-live pages — the radix prefix
        cache shares a cached prompt page into a new group's table (one
        reference per row, plus the tree's own at insert)."""
        for p in pages:
            assert p in self._ref, f"retain of dead page {p}"
            self._ref[p] += n

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    @property
    def num_live(self) -> int:
        """Pages currently referenced (freelist + live == pool capacity —
        the conservation invariant tests/test_radix_property.py checks)."""
        return len(self._ref)

    def release(self, pages: List[int]) -> int:
        """Drop one reference per page; returns how many pages actually
        went back to the freelist (a shared prompt page frees only when
        its last reference drops)."""
        freed = 0
        for p in pages:
            self._ref[p] -= 1
            assert self._ref[p] >= 0, f"negative refcount on page {p}"
            if self._ref[p] == 0:
                del self._ref[p]
                self._free.append(p)
                freed += 1
        return freed


@dataclasses.dataclass
class _Group:
    gid: int
    prompt: np.ndarray               # (Lp,) int32, already truncated
    G: int
    keys: np.ndarray                 # (max_new, 2) uint32 step keys
    max_new: int
    prompt_pages: Optional[List[int]] = None    # LIVE pages (window-visible)
    prompt_last: Optional[List[int]] = None     # last token pos per live page
    prompt_logits: Optional[jax.Array] = None   # (V,) f32 last-prompt logits
    # radix-cache match stashed by the admission gate for _admit_row:
    # (m, pages) — prompt page indices j0..m-1 already cached as `pages`
    match: Optional[tuple] = None
    # streaming delivery: called as on_token(row_idx, token_id) for every
    # committed token, in commit order (launch/serve.py RequestDriver)
    on_token: Optional[object] = None
    done_rows: Dict[int, np.ndarray] = dataclasses.field(default_factory=dict)
    done_lps: Dict[int, np.ndarray] = dataclasses.field(default_factory=dict)
    finish_step: int = 0


@dataclasses.dataclass
class _Row:
    group: _Group
    idx: int                         # row index within the group (PRNG row)
    toks: list = dataclasses.field(default_factory=list)
    lps: list = dataclasses.field(default_factory=list)
    pages: list = dataclasses.field(default_factory=list)  # resp page k -> id
    credit: int = 0                  # future page allocations reserved
    # live pages in logical order: (last_pos, table_idx, page_id, is_prompt)
    live: deque = dataclasses.field(default_factory=deque)
    # spec-decode state (DESIGN.md §Spec-decode): a fresh row still holds
    # its prefill logits in hand; a steady row's last committed token is
    # unfed and rides into the next verify block
    fresh: bool = True
    # scheduled frontier: device steps DISPATCHED for this row (>= the
    # committed len(toks) while a block is in flight) — the host-side
    # cursor of the device-resident decode loop
    sched_t: int = 0


@dataclasses.dataclass
class _Block:
    """One in-flight fused decode block (DESIGN.md
    §Device-resident-decode): the device accumulates its (D, B) token /
    logprob buffers while the host keeps only this plan of what was
    scheduled; ``_drain_block`` turns the buffers into commits once the
    async transfer lands."""
    plan: list                       # [(slot, row, t0, n_row), ...]
    base: int                        # engine step counter at dispatch
    toks: jax.Array                  # (D, B) int32 sampled tokens
    lps: Optional[jax.Array]         # (D, B) f32 raw logprobs (capture)


class GroupHandle:
    """Future for a submitted group; resolves to (RolloutBatch, finish_step)."""

    def __init__(self, group: _Group):
        self._group = group
        self._event = threading.Event()
        self._result: Optional[RolloutBatch] = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> RolloutBatch:
        if not self._event.wait(timeout):
            raise TimeoutError(f"group {self._group.gid} not complete")
        return self._result

    def host_rows(self) -> List[np.ndarray]:
        """Per-row committed tokens as host numpy arrays (row order) —
        the same arrays the RolloutBatch was assembled from, so serving
        paths can read completions with no device transfer. Completed
        groups only (call after ``result``)."""
        g = self._group
        return [g.done_rows[i] for i in range(g.G)]

    @property
    def finish_step(self) -> int:
        return self._group.finish_step


class PagedGroupEngine:
    """Continuous-batching decode over a shared paged KV/latent pool.

    Thread-safe: ``submit`` registers a group's rows; any thread may drive
    ``step`` (the inference-instance convoy in ``core/engine.py`` does), so
    concurrently submitted groups batch together at token level."""

    def __init__(self, cfg: ModelConfig, *, num_slots: int, page_size: int,
                 num_pages: int, max_prompt_len: int, max_new_tokens: int,
                 group_size: int, temperature: float = 1.0, top_p: float = 1.0,
                 eos_id: int = Tokenizer.EOS, pad_id: int = Tokenizer.PAD,
                 capture_logprobs: bool = True, spec_k: int = 0,
                 spec_draft: str = "prompt_lookup", spec_ngram: int = 3,
                 prefix_cache: bool = False, drain_interval: int = 1,
                 seed: int = 0):
        if num_slots < 1 or page_size < 1:
            raise ValueError(f"paged engine needs num_slots >= 1 and "
                             f"page_size >= 1, got {num_slots}/{page_size}")
        if drain_interval < 1:
            raise ValueError(f"drain_interval must be >= 1, "
                             f"got {drain_interval}")
        # fail at construction, not first weight sync (same matrix
        # init_paged_caches enforces — configs/base.py engine_support)
        require_engine_support(cfg, "paged")
        self.cfg = cfg
        self.B = num_slots
        self.page = page_size
        self.Lp = max_prompt_len
        self.T = max_new_tokens
        self.G = group_size
        self.window = cfg.sliding_window
        self.temperature = temperature
        self.top_p = top_p
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.capture_logprobs = capture_logprobs
        # fused decode-block length D (DESIGN.md §Device-resident-decode):
        # one jitted lax.scan advances every slot D tokens and the host
        # drains the (D, B) buffers once per block. D == 1 drains every
        # block synchronously (legacy admission/eviction cadence); D > 1
        # pipelines one block deep — block n+1 is dispatched before block
        # n's transfer is read, so the host never sits on a device fence
        self.drain = drain_interval
        self.spec_k = spec_k
        if spec_k:
            require_engine_support(cfg, "spec")
            from repro.spec.draft import make_draft_provider
            self._draft = make_draft_provider(
                spec_draft, cfg, num_slots, spec_k=spec_k, ngram=spec_ngram,
                max_prompt_len=max_prompt_len,
                max_new_tokens=max_new_tokens, pad_id=pad_id, seed=seed)
        self.n_prompt_pages = -(-max_prompt_len // page_size)
        self.n_resp_pages = -(-max_new_tokens // page_size)
        self.n_max = self.n_prompt_pages + self.n_resp_pages
        j0_max, _ = self._prompt_page_range(max_prompt_len)
        live_pp_max = self.n_prompt_pages - j0_max
        if num_pages == 0:      # auto-size: two full groups resident
            num_pages = FIRST_PAGE + 2 * (live_pp_max
                                          + group_size
                                          * self._row_budget(max_new_tokens))
        self.P = num_pages
        if FIRST_PAGE + live_pp_max + 1 > num_pages:
            raise ValueError(
                f"page pool too small: {num_pages} pages cannot hold one "
                f"max-length prompt ({live_pp_max} window-visible pages) + "
                f"one response page + {FIRST_PAGE} reserved")

        self.params = None
        self.caches = None           # built lazily at first set_params
        self.logits = None           # (B, V) f32 per-slot next-token logits
        self.alloc = PageAllocator(num_pages)
        self.radix = None
        if prefix_cache:
            require_engine_support(cfg, "prefix")
            from repro.core.radix import RadixCache
            self.radix = RadixCache(page_size, self.alloc)
        self.sched = SlotScheduler(num_slots)
        self._ptab = np.zeros((num_slots, self.n_max), np.int32)  # NULL rows
        self._mutex = threading.RLock()
        self._next_gid = 0
        self._handles: Dict[int, GroupHandle] = {}
        self._outstanding = 0        # sum of row credits; free >= this always
        self.decode_steps = 0
        self.generated_tokens = 0
        self.reclaimed_pages = 0
        self._reset_phase_clocks()

        self._pending: Optional[_Block] = None   # in-flight fused block
        self._done = None            # (B,) bool device-resident stop flags
        self._prefill = jax.jit(self._prefill_group, donate_argnums=(1,))
        self._prefill_sfx = jax.jit(self._prefill_suffix, donate_argnums=(1,))
        self._decode = jax.jit(self._decode_block, donate_argnums=(1,))
        self._invalidate = jax.jit(self._invalidate_pages, donate_argnums=(0,))
        self._verify = jax.jit(self._verify_step, donate_argnums=(1,))
        self.reset_spec_stats()
        self.reset_prefix_stats()
        # registry metrics, cached once; pushed at BLOCK granularity from
        # the drain/commit paths, never per token (§Observability)
        _m = metrics()
        self._m_drain_blocks = _m.counter("paged.drain_blocks")
        self._m_reclaimed = _m.counter("paged.pages_reclaimed")
        self._m_pages_live = _m.gauge("paged.pages_live")
        self._m_drafted = _m.counter("spec.drafted_tokens")
        self._m_accepted = _m.counter("spec.accepted_tokens")
        self._m_prefix_hit = _m.counter("prefix.hit_pages")
        self._m_prefix_miss = _m.counter("prefix.miss_pages")
        self._m_prefix_evicted = _m.counter("prefix.evicted_pages")
        self._pushed_reclaimed = 0   # registry high-water for the counter

    def _reset_phase_clocks(self) -> None:
        """Host seconds of the decode step's phases (``step``): admission,
        the block's build and commit (host), the device calls (launch), the
        readback (wait), and the time between steps while rows are held
        (gap). Prefill is in none of them."""
        with self._mutex:
            self.decode_host_s = 0.0
            self.decode_launch_s = 0.0
            self.decode_wait_s = 0.0
            self.decode_gap_s = 0.0
            self._step_end: Optional[float] = None

    def reset_spec_stats(self) -> None:
        with self._mutex:   # counters race with step() from other threads
            self.spec_steps = 0          # verify forwards x live rows
            self.drafted_tokens = 0      # drafts proposed
            self.accepted_tokens = 0     # drafts that survived verify
            self.rolled_back_pages = 0   # spec pages returned on reject

    def reset_prefix_stats(self) -> None:
        with self._mutex:
            self.prefix_hit_pages = 0     # prompt pages from the tree
            self.prefix_miss_pages = 0    # prompt pages prefilled cold
            self.prefix_inserted_pages = 0  # pages newly cached
            self.prefix_evicted_pages = 0   # cached pages reclaimed

    @property
    def acceptance_rate(self) -> float:
        with self._mutex:
            return (self.accepted_tokens / self.drafted_tokens
                    if self.drafted_tokens else 0.0)

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of cacheable prompt pages served from the radix tree."""
        with self._mutex:
            tot = self.prefix_hit_pages + self.prefix_miss_pages
            return self.prefix_hit_pages / tot if tot else 0.0

    def stats_snapshot(self) -> dict:
        """Atomic copy of the engine counters (one mutex hold — the
        scheduler diffs two snapshots for per-iteration metrics)."""
        with self._mutex:
            return {
                "decode_steps": self.decode_steps,
                "generated_tokens": self.generated_tokens,
                "reclaimed_pages": self.reclaimed_pages,
                "decode_host_s": self.decode_host_s,
                "decode_launch_s": self.decode_launch_s,
                "decode_wait_s": self.decode_wait_s,
                "decode_gap_s": self.decode_gap_s,
                "spec_steps": self.spec_steps,
                "drafted_tokens": self.drafted_tokens,
                "accepted_tokens": self.accepted_tokens,
                "prefix_hit_pages": self.prefix_hit_pages,
                "prefix_miss_pages": self.prefix_miss_pages,
                "prefix_evicted_pages": self.prefix_evicted_pages,
            }

    def status_snapshot(self) -> dict:
        """Live occupancy + counters for the ops plane's ``/status``
        (obs/server.py), in ONE mutex hold so a concurrent drive thread
        can never produce a torn multi-field view (pages_live consistent
        with slots_active, peak consistent with min_free). Derived rates
        are computed from the same hold."""
        with self._mutex:
            hit, miss = self.prefix_hit_pages, self.prefix_miss_pages
            drafted, accepted = self.drafted_tokens, self.accepted_tokens
            return {
                "slots_total": self.sched.num_slots,
                "slots_active": len(self.sched.active_slots()),
                "pending_requests": self.sched.num_pending,
                "pages_total": self.P - FIRST_PAGE,
                "pages_live": self.alloc.num_live,
                "pages_free": self.alloc.num_free,
                "peak_pages_used": (self.P - FIRST_PAGE)
                                   - self.alloc.min_free,
                "decode_steps": self.decode_steps,
                "generated_tokens": self.generated_tokens,
                "reclaimed_pages": self.reclaimed_pages,
                "prefix_hit_rate": hit / (hit + miss) if hit + miss else 0.0,
                "spec_acceptance": accepted / drafted if drafted else 0.0,
            }

    # -- page geometry ------------------------------------------------------

    def _n_total(self, max_new: int) -> int:
        """Response pages a row writes over its whole decode."""
        return -(-max_new // self.page)

    def _row_budget(self, max_new: int) -> int:
        """Worst-case SIMULTANEOUSLY-resident response pages for one row —
        the page credit the admission gate reserves. Without a window every
        written page stays (budget = all of them); with one, reclamation
        each step bounds the live span to `window` positions, which straddle
        at most window//page + 2 pages (+1 slack for the step's new page).
        Spec decode writes up to k tokens past the frontier before the
        window slides, so speculative pages widen the windowed budget by
        ceil(k/page) + 1 (never past the total — positions >= max_new are
        clamped to the trash page). A fused decode block (D > 1) writes
        up to D-1 tokens past the position reclamation last ran at, so
        the lookahead widens the windowed budget the same way."""
        n = self._n_total(max_new)
        if self.window is None:
            return n
        spec = ((self.spec_k + self.page - 1) // self.page + 1
                if self.spec_k else 0)
        look = ((self.drain - 1) // self.page + 1 if self.drain > 1 else 0)
        return min(n, self.window // self.page + 3 + spec + look)

    def _suffix_bucket(self, n_sfx_pages: int) -> int:
        """Pad a radix-miss suffix to a power-of-two page count so the
        suffix-prefill jit cache holds O(log n_prompt_pages) traces while a
        warm hit still prefills genuinely fewer tokens than a cold start
        (padding to the full prompt length would erase the FLOP saving)."""
        b = 1
        while b < n_sfx_pages:
            b *= 2
        return min(b, self.n_prompt_pages)

    def _prompt_page_range(self, plen: int):
        """(j0, n_pp): prompt pages j0..n_pp-1 are window-visible to at
        least the first response query (q_pos = plen); pages before j0 are
        dead on arrival and never allocated."""
        n_pp = -(-plen // self.page) if plen else 0
        j0 = 0 if self.window is None else max(0, (plen - self.window)
                                               // self.page)
        return j0, n_pp

    # -- jitted cores -------------------------------------------------------

    def _prefill_group(self, params, caches, row, length, dest_pages):
        """Run the shared prompt ONCE (row: (1, Lp_pad) right-padded) and
        splice its per-layer cache streams into the pool at ``dest_pages``
        — one physical prompt copy serves every row of the group. Returns
        (caches, last-token logits (V,)). The temporary prefill cache is
        full-length even for sliding-window configs (``ring=False``) so
        every prompt token is addressable for the splice; dead out-of-window
        pages land in the trash slot of ``dest_pages``."""
        cfg = self.cfg
        Lp_pad = self.n_prompt_pages * self.page
        ar = jnp.arange(Lp_pad, dtype=jnp.int32)[None, :]
        real = ar < length
        positions = jnp.where(real, ar, 0).astype(jnp.int32)
        segments = jnp.where(real, 0, -1).astype(jnp.int32)
        tmp = init_caches(params, cfg, 1, Lp_pad, ring=False)
        h, tmp, _, _ = forward_hidden(params, cfg, row, positions=positions,
                                      segments=segments, caches=tmp,
                                      cache_offset=0)
        W = lm_head_weight(params["embed"], cfg)
        h_last = jnp.take_along_axis(
            h, (length - 1)[None, :, None], axis=1)[:, 0]
        logits = jnp.einsum("bd,dv->bv", h_last.astype(jnp.float32),
                            W.astype(jnp.float32))[0]
        pos_write = jnp.where(real[0], ar[0], INVALID_POS).reshape(
            self.n_prompt_pages, self.page)

        streams = cache_streams(cfg)
        new_caches = {}
        for grp in caches:           # "layers" (+ "prelude" for first-k-dense)
            pools, t = caches[grp]["kv"], tmp[grp]["kv"]
            nL = pools["pos_pages"].shape[0]
            new = {}
            for name, shp in streams:
                arr = t[name][:, 0]          # (nL, Lp_pad, *shp)
                new[name + "_pages"] = pools[name + "_pages"].at[
                    :, dest_pages].set(arr.reshape(
                        (nL, self.n_prompt_pages, self.page) + shp))
            new["pos_pages"] = pools["pos_pages"].at[:, dest_pages].set(
                jnp.broadcast_to(pos_write, (nL,) + pos_write.shape))
            new_caches[grp] = {"kv": new}
        return new_caches, logits

    def _prefill_suffix(self, params, caches, tokens, positions, segs,
                        wslots, ptab, last):
        """Prefill ONLY a prompt's uncached suffix through the paged pool
        (radix-cache warm admission): the (1, S) block writes into the
        group's freshly allocated private pages via flat write slots while
        attending through the page table — which already lists the matched
        cached pages, so the suffix conditions on the shared prefix
        exactly as a cold full prefill would (attention.py routes S > 1 +
        per-token slots through the same multi-token decode path the spec
        verify block uses). Returns (caches, last-real-token logits)."""
        cfg = self.cfg
        h, caches, _, _ = forward_hidden(
            params, cfg, tokens, positions=positions, segments=segs,
            caches=caches, cache_offset=wslots, page_table=ptab)
        W = lm_head_weight(params["embed"], cfg)
        h_last = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
        logits = jnp.einsum("bd,dv->bv", h_last.astype(jnp.float32),
                            W.astype(jnp.float32))[0]
        return caches, logits

    def _decode_block(self, params, caches, logits, done, keys, wslots,
                      valid, rows, pos0, active, ptab):
        """D fused decode steps for every slot (the device-resident decode
        loop, DESIGN.md §Device-resident-decode): one ``lax.scan`` samples,
        commits to the paged cache, stop-checks, and accumulates the (D, B)
        token/logprob buffers entirely on device — the host sees nothing
        until it drains the buffers.

        Per step j, a slot is LIVE when the host scheduled it (``active``,
        ``valid[j]``) and its device-resident ``done`` flag is clear; a row
        that samples EOS mid-block sets ``done`` and its remaining steps
        degrade to the inactive-slot convention (PAD at pos 2^30 into the
        trash page), so optimistically dispatched steps past a stop are
        harmless. ``done`` persists across blocks (reset at admission),
        which is what makes pipelined dispatch of block n+1 before block
        n's drain exact. With capture enabled the buffers also carry
        log p(emitted id) under the raw distribution — the rollout-time
        behavior logprob (§Tri-model-capture); disabled engines skip the
        log-softmax."""
        cfg = self.cfg
        W = lm_head_weight(params["embed"], cfg)

        def body(carry, xs):
            caches, logits, done = carry
            k_j, w_j, v_j, j = xs
            tok = _sample_token_rows(k_j, logits, rows, self.G,
                                     self.temperature, self.top_p)
            live = active & ~done & v_j
            tok = jnp.where(live, tok, self.pad_id)
            lp = (jnp.where(live, sampled_token_logprob(logits, tok), 0.0)
                  if self.capture_logprobs
                  else jnp.zeros((self.B,), jnp.float32))
            done = done | (live & (tok == self.eos_id))
            pos = jnp.where(live, pos0 + j, INVALID_POS).astype(jnp.int32)
            wsl = jnp.where(live, w_j, TRASH_PAGE * self.page).astype(
                jnp.int32)
            seg = jnp.where(live, 0, -1).astype(jnp.int32)[:, None]
            h, caches, _, _ = forward_hidden(
                params, cfg, tok[:, None], positions=pos[:, None],
                segments=seg, caches=caches, cache_offset=wsl,
                page_table=ptab)
            logits = jnp.einsum("bd,dv->bv", h[:, 0].astype(jnp.float32),
                                W.astype(jnp.float32))
            return (caches, logits, done), (tok, lp)

        D = keys.shape[0]
        (caches, logits, done), (toks, lps) = jax.lax.scan(
            body, (caches, logits, done),
            (keys, wslots, valid, jnp.arange(D, dtype=jnp.int32)))
        return toks, lps, caches, logits, done

    def _verify_step(self, params, caches, logits, tokens, positions, segs,
                     wslots, ptab, keys, folds, fresh, draft):
        """One k+1-token spec verify forward for every slot (DESIGN.md
        §Spec-decode): the block (the unfed committed token + k drafts, or
        k drafts + a masked pad slot for fresh rows) writes into its
        speculative pages and attends through the pool; ``fresh`` rows use
        their prefill logits as p_0. Masked slots point at the trash page
        with pos 2^30. The accept/commit walk runs ON DEVICE
        (``commit_block``), so the step returns one right-padded
        (B, k+1) commit buffer + per-row counts instead of verdicts the
        host would have to walk (§Device-resident-decode)."""
        from repro.spec.verify import commit_block, verify_block
        cfg = self.cfg
        h, caches, _, _ = forward_hidden(
            params, cfg, tokens, positions=positions, segments=segs,
            caches=caches, cache_offset=wslots, page_table=ptab)
        W = lm_head_weight(params["embed"], cfg)
        out = jnp.einsum("bsd,dv->bsv", h.astype(jnp.float32),
                         W.astype(jnp.float32))
        p = jnp.where(fresh[:, None, None],
                      jnp.concatenate([logits[:, None], out[:, :-1]],
                                      axis=1),
                      out)
        accept, alt, lp_d, lp_a = verify_block(
            p, draft, keys, folds, temperature=self.temperature,
            top_p=self.top_p, capture=self.capture_logprobs)
        toks, lps, count = commit_block(accept, alt, draft, lp_d, lp_a)
        return toks, lps, count, caches

    def _invalidate_pages(self, caches, pages):
        """Mark freshly allocated response pages invalid — they may hold a
        previous sequence's stale (pos, kv) entries, which would otherwise
        pass the causal mask."""
        out = {}
        for grp in caches:
            pools = dict(caches[grp]["kv"])
            pools["pos_pages"] = pools["pos_pages"].at[:, pages].set(
                INVALID_POS)
            out[grp] = {"kv": pools}
        return out

    # -- host API -----------------------------------------------------------

    def set_params(self, params) -> None:
        """Swap weights (iteration-boundary sync). Must be quiescent —
        periodic asynchrony guarantees the queue is drained first."""
        with self._mutex:
            assert self.sched.idle, \
                "weight sync while rollouts in flight breaks Proposition 1"
            self.params = params
            if self.caches is None:
                self.caches = init_paged_caches(params, self.cfg, self.P,
                                                self.page)
                self.logits = jnp.zeros((self.B, self.cfg.vocab_size),
                                        jnp.float32)
                self._done = jnp.zeros((self.B,), bool)

    def submit(self, prompt, key, *, max_new: Optional[int] = None,
               on_token=None) -> GroupHandle:
        """Register one GRPO group (G rollouts of one prompt). Returns a
        handle; drive ``step`` until it resolves. Raises immediately when
        the group could never be admitted — a prompt whose window-visible
        pages plus one row's page budget exceed what the pool can EVER free
        would otherwise sit in the admission queue forever.

        ``on_token(row_idx, token_id)`` streams every committed token in
        commit order (the serving tier's per-token delivery — TTFT/TPOT
        are measured at these calls); it runs under the engine mutex, so
        keep it cheap."""
        p = np.asarray(prompt, np.int32)[-self.Lp:]   # Sampler keeps the tail
        max_new = self.T if max_new is None else min(max_new, self.T)
        j0, n_pp = self._prompt_page_range(len(p))
        need = (n_pp - j0) + self._row_budget(max_new)
        avail = self.P - FIRST_PAGE
        if need > avail:
            raise ValueError(
                f"group can never be admitted: prompt of {len(p)} tokens "
                f"needs {n_pp - j0} pages + {self._row_budget(max_new)} "
                f"response pages per row = {need}, but the pool only ever "
                f"frees {avail} of its {self.P} pages")
        # repro: allow(host-sync): one key-table transfer per group
        # submission (admission bookkeeping is host-side), not per token
        keys = np.asarray(stepwise_keys(key, max_new))
        with self._mutex:
            # params is swapped by set_params under the mutex — read it
            # under the same lock (torn-read discipline)
            assert self.params is not None, "set_params before submit"
            g = _Group(gid=self._next_gid, prompt=p, G=self.G, keys=keys,
                       max_new=max_new, on_token=on_token)
            self._next_gid += 1
            h = GroupHandle(g)
            self._handles[g.gid] = h
            for i in range(self.G):
                self.sched.submit(_Row(group=g, idx=i))
            return h

    @property
    def idle(self) -> bool:
        with self._mutex:
            return self.sched.idle

    @property
    def peak_pages_used(self) -> int:
        """High-water physical page occupancy (excludes the reserves)."""
        return (self.P - FIRST_PAGE) - self.alloc.min_free

    def reset_stats(self) -> None:
        with self._mutex:   # RLock: the nested resets re-enter
            self.decode_steps = 0
            self.generated_tokens = 0
            self.reclaimed_pages = 0
            self._reset_phase_clocks()
            # registry high-water follows the local counter it diffs
            # against — left stale it would push a NEGATIVE delta into
            # the monotone registry counter on the next drain
            self._pushed_reclaimed = 0
            self.alloc.min_free = self.alloc.num_free
            self.reset_spec_stats()
            self.reset_prefix_stats()

    # -- engine step --------------------------------------------------------

    def _admission_gate(self, row: _Row) -> bool:
        """The freelist must cover this row's worst-case resident pages ON
        TOP of every admitted row's outstanding credit — credits make lazy
        allocation deadlock-free (an admitted row can always take its next
        page), so the gate reads free - outstanding, not raw free.

        With the radix prefix cache, matched pages cost nothing (they are
        retained, not allocated — the gate stashes the match on the group
        for ``_admit_row``, which runs back-to-back under the mutex with
        ``admit(limit=1)``), and a deficit first evicts idle cached pages
        — cached-but-unreferenced pages are as good as free."""
        need = self._row_budget(row.group.max_new)
        mpages = []
        if row.group.prompt_pages is None:
            j0, n_pp = self._prompt_page_range(len(row.group.prompt))
            m = j0
            if self.radix is not None:
                m, mpages = self.radix.lookup(row.group.prompt, j0=j0)
                row.group.match = (m, mpages)
            need += n_pp - m
        free = self.alloc.num_free - self._outstanding
        if free < need and self.radix is not None:
            evicted = len(self.radix.evict(need - free, protect=set(mpages)))
            self.prefix_evicted_pages += evicted
            self._m_prefix_evicted.add(evicted)
            free = self.alloc.num_free - self._outstanding
        return free >= need

    def _warm_prefill(self, g: _Group, m: int, new: List[int],
                      j0: int, n_pp: int) -> None:
        """Prefill a radix-hit prompt's uncached tail (page indices
        ``m..n_pp-1``) into its freshly allocated private pages ``new``,
        attending through the matched cached pages via the group's page
        table. The block is padded to a power-of-two page count
        (``_suffix_bucket``) so the jit cache stays warm without erasing
        the FLOP saving; pad slots are masked (segment -1, trash page)."""
        page = self.page
        m_tok = m * page
        sfx = g.prompt[m_tok:]
        S = max(2, self._suffix_bucket(len(new)) * page)
        ar = np.arange(S)
        real = ar < len(sfx)
        toks = np.full((1, S), self.pad_id, np.int32)
        toks[0, : len(sfx)] = sfx
        pos = np.where(real, m_tok + ar, 0).astype(np.int32)[None]
        segs = np.where(real, 0, -1).astype(np.int32)[None]
        wsl = np.full((S,), TRASH_PAGE * page, np.int32)
        for t in range(len(sfx)):
            a = m_tok + t
            wsl[t] = new[a // page - m] * page + a % page
        tab = np.zeros((1, self.n_max), np.int32)
        tab[0, : n_pp - j0] = g.prompt_pages
        inval = np.full((self.n_max,), TRASH_PAGE, np.int32)
        inval[: len(new)] = new
        self.caches = self._invalidate(self.caches, jnp.asarray(inval))
        self.caches, g.prompt_logits = self._prefill_sfx(
            self.params, self.caches, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(segs), jnp.asarray(wsl[None]), jnp.asarray(tab),
            jnp.asarray([len(sfx) - 1], jnp.int32))

    def _admit_row(self, slot: int, row: _Row) -> float:
        """Seat ``row`` in ``slot``; returns the host seconds spent
        dispatching its group's prefill (0 for the group's later rows)."""
        g = row.group
        prefill_s = 0.0
        if g.prompt_pages is None:
            j0, n_pp = self._prompt_page_range(len(g.prompt))
            m, mpages = g.match if g.match is not None else (j0, [])
            g.match = None
            if m > j0:
                # radix warm start: the matched cached pages join the
                # group's table with one reference per row (their KV is
                # bitwise what a cold prefill would write — core/radix.py);
                # only the uncached suffix is prefilled, into private pages
                self.alloc.retain(mpages, n=g.G)
                new = self.alloc.alloc(n_pp - m, refcount=g.G)
                assert new is not None, "admission gate let a row in " \
                    "without pages for its prompt suffix"
                g.prompt_pages = list(mpages) + new
                self.prefix_hit_pages += m - j0
                self.prefix_miss_pages += n_pp - m
                self._m_prefix_hit.add(m - j0)
                self._m_prefix_miss.add(n_pp - m)
            else:
                g.prompt_pages = self.alloc.alloc(n_pp - j0, refcount=g.G)
                assert g.prompt_pages is not None, "admission gate let a " \
                    "row in without pages for its prompt"
                if self.radix is not None:
                    self.prefix_miss_pages += n_pp - j0
                    self._m_prefix_miss.add(n_pp - j0)
            g.prompt_last = [min((j + 1) * self.page, len(g.prompt)) - 1
                             for j in range(j0, n_pp)]
            t_prefill = time.perf_counter()
            if m > j0:
                # span measures host-side dispatch (the prefill itself is
                # asynchronous; its device time surfaces at the next drain)
                with otrace.span("paged.prefill", gid=g.gid, warm=True):
                    self._warm_prefill(g, m, g.prompt_pages[m - j0:],
                                       j0, n_pp)
            else:
                with otrace.span("paged.prefill", gid=g.gid, warm=False):
                    dest = np.full((self.n_prompt_pages,), TRASH_PAGE,
                                   np.int32)
                    dest[j0:n_pp] = g.prompt_pages
                    row_arr = np.full((1, self.n_prompt_pages * self.page),
                                      self.pad_id, np.int32)
                    row_arr[0, : len(g.prompt)] = g.prompt
                    self.caches, g.prompt_logits = self._prefill(
                        self.params, self.caches, jnp.asarray(row_arr),
                        jnp.asarray([len(g.prompt)], jnp.int32),
                        jnp.asarray(dest))
            prefill_s = time.perf_counter() - t_prefill
            if self.radix is not None:
                # cache every COMPLETE prompt page (cold and warm alike —
                # insert skips spans already cached); a trailing partial
                # page is row-private and never enters the tree
                self.prefix_inserted_pages += self.radix.insert(
                    g.prompt, {j: g.prompt_pages[j - j0]
                               for j in range(j0, len(g.prompt) // self.page)})
        row.pages = []
        row.credit = self._row_budget(g.max_new)
        self._outstanding += row.credit
        row.live = deque((last, i, pid, True) for i, (last, pid)
                         in enumerate(zip(g.prompt_last, g.prompt_pages)))
        tab = np.zeros((self.n_max,), np.int32)        # NULL padding
        tab[: len(g.prompt_pages)] = g.prompt_pages
        self._ptab[slot] = tab
        # both updates are dispatched AFTER any in-flight fused block, so
        # they land on its OUTPUT state: the pending block saw this slot
        # masked (its previous row's done flag), the next block samples
        # from the prompt logits with a cleared stop flag
        self.logits = self.logits.at[slot].set(g.prompt_logits)
        self._done = self._done.at[slot].set(False)
        row.toks = []
        row.lps = []
        row.fresh = True
        row.sched_t = 0
        if self.spec_k:
            self._draft.start(slot, g.prompt)
        return prefill_s

    def _alloc_resp_page(self, slot: int, row: _Row, k: int) -> int:
        """Lazily take response page k (the write cursor just crossed a
        page boundary) out of the row's reserved credit; returns the page
        id (the step batches all fresh pages into ONE invalidation call)."""
        g = row.group
        assert row.credit > 0, "page-credit invariant violated: row admitted "\
            "without enough budget for its next page"
        pages = self.alloc.alloc(1)
        assert pages is not None, "freelist below outstanding credit"
        row.credit -= 1
        self._outstanding -= 1
        pid = pages[0]
        row.pages.append(pid)
        ti = len(g.prompt_pages) + k
        self._ptab[slot, ti] = pid
        row.live.append((len(g.prompt) + (k + 1) * self.page - 1, ti, pid,
                         False))
        if not self.spec_k and len(row.pages) == self._n_total(g.max_new):
            # last page this row will ever write: return unused credit.
            # Spec engines skip the early return — a speculative final
            # page may be ROLLED BACK and re-allocated later, so its
            # credit must stay symmetric (alloc -1 / rollback +1) until
            # the row finishes (_finish_row releases the remainder).
            self._outstanding -= row.credit
            row.credit = 0
        return pid

    def _rollback_row(self, slot: int, row: _Row, vf_rp: int) -> None:
        """Return speculative response pages holding ONLY rejected drafts
        to the freelist (DESIGN.md §Spec-decode): after a commit that fed
        through response position ``vf_rp``, any page whose first slot is
        past it contains nothing a future query may see — pop it off the
        row's table, release it, and re-arm the row's page credit (the
        exact inverse of ``_alloc_resp_page``, so the admission-gate
        invariant resident + credit == budget is untouched). Partially
        valid pages stay: their stale tail slots are overwritten by the
        next verify block before any read."""
        keep = vf_rp // self.page if vf_rp >= 0 else -1
        while len(row.pages) - 1 > keep:
            pid = row.pages.pop()
            last, ti, pid_live, is_prompt = row.live.pop()
            assert pid_live == pid and not is_prompt, \
                "rollback must pop the most recent speculative page"
            self._ptab[slot, ti] = NULL_PAGE
            self.alloc.release([pid])
            row.credit += 1
            self._outstanding += 1
            self.rolled_back_pages += 1

    def _reclaim_row(self, slot: int, row: _Row, q_pos: int) -> None:
        """Sliding-window page reclamation: positions only grow, so once
        ``q_pos - last_pos >= window`` no present or future query of this
        row can see the page — drop it from the row's table and release the
        row's reference (a prompt page shared with rows that can still see
        it stays resident via its refcount)."""
        w = self.window
        n_total = self._n_total(row.group.max_new)
        while row.live and q_pos - row.live[0][0] >= w:
            _, ti, pid, is_prompt = row.live.popleft()
            self._ptab[slot, ti] = NULL_PAGE
            # count pages actually returned to the freelist — a shared
            # prompt page frees once, not once per row that drops it
            self.reclaimed_pages += self.alloc.release([pid])
            if not is_prompt and len(row.pages) < n_total:
                # the freed page re-arms this row's credit: resident +
                # credit stays equal to the admission-time budget
                row.credit += 1
                self._outstanding += 1

    def _finish_row(self, slot: int, row: _Row, step: int) -> None:
        g = row.group
        g.done_rows[row.idx] = np.asarray(row.toks, np.int32)
        if self.capture_logprobs:
            g.done_lps[row.idx] = np.asarray(row.lps, np.float32)
        g.finish_step = step
        for _, _, pid, _ in row.live:   # resident resp pages + prompt refs
            self.alloc.release([pid])
        row.live.clear()
        self._outstanding -= row.credit
        row.credit = 0
        self.sched.evict(slot)
        self._ptab[slot] = 0
        if len(g.done_rows) == g.G:
            resp = np.full((g.G, self.T), self.pad_id, np.int32)
            lens = np.zeros((g.G,), np.int32)
            lps = np.zeros((g.G, self.T), np.float32)
            for i, r in g.done_rows.items():
                resp[i, : len(r)] = r
                lens[i] = len(r)
                if self.capture_logprobs:
                    lps[i, : len(r)] = g.done_lps[i]
            h = self._handles.pop(g.gid)
            h._result = RolloutBatch(
                response_ids=jnp.asarray(resp),
                response_len=jnp.asarray(lens),
                response_logprobs=(jnp.asarray(lps)
                                   if self.capture_logprobs else None))
            h._event.set()

    def step(self) -> bool:
        """One admission pass + one fused D-step decode block for every
        slot (spec engines verify a k+1-token block instead —
        §Spec-decode). Returns False (and does nothing) when the engine is
        idle and no block is in flight.

        ``drain_interval == 1`` dispatches and drains synchronously — the
        legacy admission/eviction cadence, one drain per token step.
        ``drain_interval > 1`` runs the one-deep pipeline: block n+1 is
        built and dispatched BEFORE block n's buffers are read, so block
        n's device->host transfer (started asynchronously at dispatch)
        overlaps block n+1's host-side build and device compute. The
        optimistic dispatch assumes no row stopped inside the in-flight
        block; the device-resident ``done`` flags make that exact (a
        stopped row's extra steps are masked to the trash page), and the
        drain simply skips plan entries whose slot was re-assigned.

        Each phase is a span (``paged.admit``, then ``paged.build``/
        ``paged.launch`` in ``_dispatch_block`` and ``paged.wait``/
        ``paged.commit`` in ``_drain_block``) and feeds the always-on
        phase clocks that ``stats_snapshot`` reads (``decode_host_s``,
        ``decode_launch_s``, ``decode_wait_s``); the time from one step's
        return to the next step's start is ``decode_gap_s`` while the
        engine still holds rows, so an idle engine between iterations
        counts nothing."""
        with self._mutex:
            t0 = time.perf_counter()
            if self._step_end is not None:
                self.decode_gap_s += t0 - self._step_end
            prefill_s = 0.0
            with otrace.span("paged.admit"):
                # admit one row at a time: _admit_row consumes pages, and
                # the gate must see the freelist as it actually is for the
                # NEXT row
                while True:
                    admitted = self.sched.admit(self._admission_gate,
                                                limit=1)
                    if not admitted:
                        break
                    prefill_s += self._admit_row(*admitted[0])
            self.decode_host_s += time.perf_counter() - t0 - prefill_s
            act = self.sched.active_slots()
            if self.spec_k:
                ran = self._spec_step(act) if act else False
            elif self.drain == 1:
                nxt = self._dispatch_block(act) if act else None
                if nxt is not None:
                    self._drain_block(nxt)
                ran = nxt is not None
            else:
                nxt = self._dispatch_block(act) if act else None
                prev, self._pending = self._pending, nxt
                if prev is not None:
                    self._drain_block(prev)
                if self._pending is not None and self.sched.idle:
                    # every row stopped inside ``prev``: the block behind it
                    # holds only device-masked steps. Drain it now, so the
                    # engine is quiescent (and the gap clock stops) when the
                    # last handle resolves, ahead of the weight sync
                    self._drain_block(self._pending)
                    self._pending = None
                ran = nxt is not None or prev is not None
            self._step_end = (None if self.sched.idle
                              and self._pending is None
                              else time.perf_counter())
            return ran

    def _dispatch_block(self, act: List[int]) -> Optional[_Block]:
        """Build one fused decode block for the active slots and dispatch
        it: per slot, schedule up to D steps from its frontier
        (``row.sched_t`` — NOT the committed length, which lags while a
        block is in flight), allocating the response pages those steps
        write and reclaiming out-of-window pages at the block's first
        query position. All page bookkeeping stays host-side; the device
        receives the per-step keys/write-slots/valid masks as (D, B)
        arrays and runs free."""
        B, D, page = self.B, self.drain, self.page
        t_build = time.perf_counter()
        with otrace.span("paged.build"):
            keys = np.zeros((D, B, 2), np.uint32)
            wsl = np.full((D, B), TRASH_PAGE * page, np.int32)
            valid = np.zeros((D, B), bool)
            rows = np.zeros((B,), np.int32)
            pos0 = np.full((B,), INVALID_POS, np.int32)
            active = np.zeros((B,), bool)
            # fixed worst-case shape: each slot crosses at most D//page + 1
            # page boundaries per block (trash-padding keeps the jit cache
            # at one trace)
            fresh = np.full((B * (D // page + 2),), TRASH_PAGE, np.int32)
            n_fresh = 0
            plan = []
            for s in act:
                row = self.sched.slot_req[s]
                g = row.group
                t0 = row.sched_t
                if t0 >= g.max_new:      # fully scheduled; awaiting drain
                    continue
                q0 = len(g.prompt) + t0
                if self.window is not None:
                    self._reclaim_row(s, row, q0)
                n_row = min(D, g.max_new - t0)
                for t in range(t0, t0 + n_row):
                    k = t // page
                    if k == len(row.pages):       # crossed a page boundary
                        fresh[n_fresh] = self._alloc_resp_page(s, row, k)
                        n_fresh += 1
                    keys[t - t0, s] = g.keys[t]
                    wsl[t - t0, s] = row.pages[k] * page + t % page
                    valid[t - t0, s] = True
                rows[s] = row.idx
                pos0[s] = q0
                active[s] = True
                row.sched_t = t0 + n_row
                plan.append((s, row, t0, n_row))
        t_launch = time.perf_counter()
        self.decode_host_s += t_launch - t_build
        if not plan:
            return None
        with otrace.span("paged.launch", slots=len(plan), steps=D):
            if n_fresh:
                # one fixed-shape invalidation for every page freshly
                # allocated this block — stale (pos, kv) from a previous
                # occupant would otherwise pass the causal mask
                self.caches = self._invalidate(self.caches,
                                               jnp.asarray(fresh))
            base = self.sched.step
            self.sched.step += D
            # the page table goes up as a snapshot: the host edits _ptab in
            # place (drain, admission, the next build) while this block
            # may still run, and a backend that wraps host memory without
            # a copy (the CPU's) would let the block read those edits
            toks, lps, self.caches, self.logits, self._done = self._decode(
                self.params, self.caches, self.logits, self._done,
                jnp.asarray(keys), jnp.asarray(wsl), jnp.asarray(valid),
                jnp.asarray(rows), jnp.asarray(pos0), jnp.asarray(active),
                jnp.asarray(self._ptab.copy()))
            self.decode_steps += D
            # start the device->host transfer NOW so it overlaps the next
            # block's build + compute; the drain then finds it landed
            for buf in (toks, lps):
                if hasattr(buf, "copy_to_host_async"):
                    buf.copy_to_host_async()
        self.decode_launch_s += time.perf_counter() - t_launch
        return _Block(plan=plan, base=base, toks=toks, lps=lps)

    def _drain_block(self, blk: _Block) -> None:
        """Commit one drained block into host bookkeeping — the ONLY
        device->host touch of the non-spec decode path, once per D steps
        (or per row completion) instead of per token."""
        t_wait = time.perf_counter()
        with otrace.span("paged.wait"):
            # repro: allow(host-sync): one buffered readback per drained
            # D-step block (transfer started async at dispatch), not per
            # token — DESIGN.md §Device-resident-decode drain protocol
            toks, lps = jax.device_get((blk.toks, blk.lps))
        t_commit = time.perf_counter()
        self.decode_wait_s += t_commit - t_wait
        with otrace.span("paged.commit", slots=len(blk.plan)):
            for s, row, t0, n_row in blk.plan:
                if self.sched.slot_req[s] is not row:
                    # row finished inside an EARLIER block; these
                    # optimistic steps ran device-masked (done flag) and
                    # wrote nothing
                    continue
                g = row.group
                assert len(row.toks) == t0, "drain out of order"
                for j in range(n_row):
                    tv = int(toks[j, s])
                    row.toks.append(tv)
                    if self.capture_logprobs:
                        row.lps.append(float(lps[j, s]))
                    self.generated_tokens += 1
                    if g.on_token is not None:
                        g.on_token(row.idx, tv)
                    if tv == self.eos_id or len(row.toks) >= g.max_new:
                        self._finish_row(s, row, blk.base + j + 1)
                        break
            self._push_block_metrics()
        self.decode_host_s += time.perf_counter() - t_commit

    def _push_block_metrics(self) -> None:
        """Flush block-granularity deltas into the metrics registry (one
        counter add per drained block, not per page event)."""
        self._m_drain_blocks.add(1)
        self._m_pages_live.set(self.alloc.num_live)
        d = self.reclaimed_pages - self._pushed_reclaimed
        if d:
            self._m_reclaimed.add(d)
            self._pushed_reclaimed = self.reclaimed_pages
        otrace.counter("paged.pages_live", self.alloc.num_live)

    def _drain_verify(self, ctoks, clps, count):
        """Drain one fused verify block's commit buffers (the spec plane's
        analogue of ``_drain_block``): the accept/commit walk already ran
        on device (``spec/verify.py commit_block``), so the host reads one
        right-padded buffer per block."""
        for buf in (ctoks, clps, count):
            if hasattr(buf, "copy_to_host_async"):
                buf.copy_to_host_async()
        # repro: allow(host-sync): one buffered readback per verify block
        # (device-side commit walk) — DESIGN.md §Device-resident-decode
        return jax.device_get((ctoks, clps, count))

    def _spec_step(self, act: List[int]) -> bool:
        """One spec-decode engine step (DESIGN.md §Spec-decode), called
        under the mutex with ``act`` the live slots: draft k tokens per
        row, pre-allocate the block's speculative pages against the row
        credits, run ONE k+1-token verify forward whose device-side commit
        walk yields 1..k+1 committed tokens per row, drain the commit
        buffers, and roll rejected speculative pages back to the
        freelist."""
        B, k, page = self.B, self.spec_k, self.page
        t_draft = time.perf_counter()
        drafts = self._draft.propose(act, k)
        otrace.complete("spec.draft", t_draft, time.perf_counter(),
                        slots=len(act), k=k)
        tokens = np.full((B, k + 1), self.pad_id, np.int32)
        positions = np.full((B, k + 1), INVALID_POS, np.int32)
        segs = np.full((B, k + 1), -1, np.int32)
        wslots = np.full((B, k + 1), TRASH_PAGE * page, np.int32)
        keys = np.zeros((B, 2), np.uint32)
        folds = np.zeros((B,), np.int32)
        fresh_m = np.zeros((B,), bool)
        fresh_pages = np.full((B * (k + 1),), TRASH_PAGE, np.int32)
        n_fresh = 0
        for s in act:
            row = self.sched.slot_req[s]
            g = row.group
            rc = len(row.toks)
            start_rp = rc if row.fresh else rc - 1
            if self.window is not None:
                self._reclaim_row(s, row, len(g.prompt) + start_rp)
            if row.fresh:
                blk = [(int(drafts[s, j]), rc + j) for j in range(k)] \
                    + [(self.pad_id, None)]
            else:
                blk = [(row.toks[-1], rc - 1)] \
                    + [(int(drafts[s, j]), rc + j) for j in range(k)]
            for j, (tv, rp) in enumerate(blk):
                if rp is None or rp >= g.max_new:
                    continue                    # masked slot: trash page
                pidx = rp // page
                while pidx >= len(row.pages):
                    fresh_pages[n_fresh] = self._alloc_resp_page(
                        s, row, len(row.pages))
                    n_fresh += 1
                tokens[s, j] = tv
                positions[s, j] = len(g.prompt) + rp
                segs[s, j] = 0
                wslots[s, j] = row.pages[pidx] * page + rp % page
            keys[s] = g.keys[rc]
            folds[s] = row.idx
            fresh_m[s] = row.fresh
        t_verify = time.perf_counter()
        if n_fresh:
            self.caches = self._invalidate(self.caches,
                                           jnp.asarray(fresh_pages))
        ctoks, clps, count, self.caches = self._verify(
            self.params, self.caches, self.logits, jnp.asarray(tokens),
            jnp.asarray(positions), jnp.asarray(segs), jnp.asarray(wslots),
            jnp.asarray(self._ptab), jnp.asarray(keys), jnp.asarray(folds),
            jnp.asarray(fresh_m), jnp.asarray(drafts))
        t_launched = time.perf_counter()
        self.decode_host_s += t_verify - t_draft
        self.decode_launch_s += t_launched - t_verify
        # host-side dispatch only — the verify block's device time (and
        # its one buffered readback) lands inside the spec.commit span
        otrace.complete("spec.verify", t_verify, t_launched, slots=len(act))
        self._commit_spec_rows(act, ctoks, clps, count)
        return True

    def _commit_spec_rows(self, act, ctoks, clps, count) -> None:
        """Drain one verify block and commit its rows -- the host half
        of the spec step, one frame below the hot entry point so the hot
        tier itself stays sync-free (DESIGN.md §Device-resident-decode).
        After the buffered drain the walk touches only host numpy."""
        from repro.spec.sampler import truncate_commit
        k = self.spec_k
        t_commit = time.perf_counter()
        g0 = self.generated_tokens
        ctoks, clps, count = self._drain_verify(ctoks, clps, count)
        t_drained = time.perf_counter()
        self.decode_wait_s += t_drained - t_commit
        step = self.sched.tick()
        self.decode_steps += 1
        for s in act:
            row = self.sched.slot_req[s]
            g = row.group
            rc = len(row.toks)
            n = int(count[s])
            ct = [int(t) for t in ctoks[s, :n]]
            cl = [float(x) for x in clps[s, :n]]
            self.spec_steps += 1
            self.drafted_tokens += k
            self.accepted_tokens += n - 1
            ct, cl, row_done = truncate_commit(ct, cl, g.max_new - rc,
                                               self.eos_id)
            row.toks.extend(ct)
            if self.capture_logprobs:
                row.lps.extend(cl)
            if g.on_token is not None:
                for tv in ct:
                    g.on_token(row.idx, int(tv))
            self._draft.commit(s, ct)
            self.generated_tokens += len(ct)
            row.fresh = False
            if row_done:
                self._finish_row(s, row, step)
                self._draft.stop(s)
            else:
                # speculative pages past the committed-and-fed frontier
                # hold only rejected drafts — roll them back
                self._rollback_row(s, row, len(row.toks) - 2)
        committed = self.generated_tokens - g0
        self._m_drafted.add(k * len(act))
        self._m_accepted.add(max(0, committed - len(act)))
        self._push_block_metrics()
        t_end = time.perf_counter()
        self.decode_host_s += t_end - t_drained
        otrace.complete("spec.commit", t_commit, t_end, slots=len(act),
                        tokens=committed)

    # -- standalone serving -------------------------------------------------

    def serve(self, params, prompts: List[np.ndarray], key
              ) -> List[Completed]:
        """Serve independent requests (engine built with group_size=1; each
        prompt is its own group). Returns completions in completion order,
        mirroring ``ContinuousBatchingSampler.run``."""
        assert self.G == 1, "serve() treats each request as a 1-row group"
        self.set_params(params)
        keys = jax.random.split(key, len(prompts))
        handles = [self.submit(p, k) for p, k in zip(prompts, keys)]
        while self.step():
            pass
        done = []
        for rid, h in enumerate(handles):
            h.result(timeout=0)       # completion check (raises if not)
            g = h._group
            # the committed tokens already live host-side in done_rows —
            # no device readback needed to assemble completions
            done.append(Completed(
                request_id=rid,
                response_ids=g.done_rows[0],
                finish_step=g.finish_step))
        done.sort(key=lambda c: c.finish_step)
        return done
