"""Attention: GQA and MLA (DeepSeek-V2 latent attention), with a pure-JAX
chunked flash implementation (lax.scan over KV chunks + online softmax) so
activation memory stays bounded at 32k-500k contexts in the compiled HLO.

Masking is driven by (position, segment) arrays, which uniformly express:
  * causal:            kv_pos <= q_pos
  * sliding window:    q_pos - kv_pos < window
  * shared-prompt:     kv_seg == 0 (shared prompt)  OR  kv_seg == q_seg
Padding uses seg == -1 (tokens only attend within their own padding run via
the diagonal) and invalid cache slots use pos == INVALID_POS (masked by the
causal rule).

KV caches are built and stepped through the :class:`CacheBackend` layer
(DESIGN.md §Cache-backends): one *layout* policy (dense-contiguous,
ring/sliding-window, paged) over one *content* spec (``cache_streams`` —
per-head K/V rows for GQA, ``(ckv, kr)`` latent rows for MLA), so every
decode engine constructs and advances its cache through the same interface
instead of per-engine ad-hoc dicts.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, dense_init
from repro.sharding.specs import (LOGICAL_TO_MESH, constrain, current_mesh,
                                  profile_has, shard_map, spec_for)

INVALID_POS = jnp.int32(2**30)
NEG_INF = -1e30


def allow_mask(q_pos, kv_pos, q_seg, kv_seg, window: Optional[int]):
    """(B, Sq), (B, Skv) -> (B, Sq, Skv) boolean allow mask."""
    qp = q_pos[:, :, None]
    kp = kv_pos[:, None, :]
    qs = q_seg[:, :, None]
    ks = kv_seg[:, None, :]
    allow = kp <= qp
    allow &= (ks == 0) | (ks == qs)
    if window is not None:
        allow &= (qp - kp) < window
    return allow


def chunked_attention(q, k, v, q_pos, kv_pos, q_seg, kv_seg, *,
                      window: Optional[int] = None, chunk_size: int = 512,
                      scale: Optional[float] = None):
    """Flash-style attention with online softmax over KV chunks.

    q: (B, Sq, H, Dk); k: (B, Skv, Hkv, Dk); v: (B, Skv, Hkv, Dv).
    Returns (B, Sq, H, Dv) in q.dtype.
    """
    B, Sq, H, Dk = q.shape
    _, Skv, Hkv, Dv = v.shape
    G = H // Hkv
    scale = Dk ** -0.5 if scale is None else scale
    C = min(chunk_size, Skv)
    pad = (-Skv) % C
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=2**30)
        kv_seg = jnp.pad(kv_seg, ((0, 0), (0, pad)), constant_values=-2)
    n = k.shape[1] // C

    qr = q.reshape(B, Sq, Hkv, G, Dk)

    if Sq == 1:
        # Decode fast path (SPerf, deepseek-v2-lite decode hillclimb): a
        # single-token query needs no KV-chunk scan -- scanning makes the
        # chunk index the leading dim, and dynamic-slicing that dim forces
        # SPMD to ALL-GATHER the whole seq-sharded cache every layer
        # (measured: 27.6 GiB/step on dsv2-lite decode_32k). The dense
        # single-pass form keeps the contraction over the sharded cache
        # dim local: softmax stats and the PV product decompose into local
        # partials + (B, H)-sized reductions. Score tile is only
        # (B, Hkv, G, 1, L) f32.
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qr, k,
                       preferred_element_type=jnp.float32) * scale
        ok = allow_mask(q_pos, kv_pos, q_seg, kv_seg, window)  # (B, 1, L)
        s = jnp.where(ok[:, None, None, :, :], s, NEG_INF)
        m = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        out = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        out = out / jnp.maximum(p.sum(axis=-1)[..., None], 1e-30)
        out = jnp.moveaxis(out, 3, 1).reshape(B, Sq, H, Dv)
        return out.astype(q.dtype)

    @jax.checkpoint
    def body(carry, xs):
        # checkpointed: backward recomputes the (B, Hkv, G, Sq, C) score /
        # probability tiles per chunk instead of saving every chunk's —
        # the flash-attention memory property in reverse mode.
        acc, m, l = carry
        kc, vc, kpc, ksc = xs
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qr, kc,
                       preferred_element_type=jnp.float32) * scale
        ok = allow_mask(q_pos, kpc, q_seg, ksc, window)        # (B, Sq, C)
        s = jnp.where(ok[:, None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # NOTE (§Perf iter 2, refuted): materialising p in bf16 does NOT cut
        # HBM traffic — the f32 score chain (dot -> mask -> exp) dominates
        # and dots are fusion barriers; only the fused Pallas kernel
        # (kernels/spa_attention.py) removes that traffic structurally.
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vc.dtype), vc,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, Hkv, G, Sq, Dv), jnp.float32)
    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)

    xs = (
        jnp.moveaxis(k.reshape(B, n, C, Hkv, Dk), 1, 0),
        jnp.moveaxis(v.reshape(B, n, C, Hkv, Dv), 1, 0),
        jnp.moveaxis(kv_pos.reshape(B, n, C), 1, 0),
        jnp.moveaxis(kv_seg.reshape(B, n, C), 1, 0),
    )
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0), xs)
    out = acc / jnp.maximum(l, 1e-30)[..., None]               # (B,Hkv,G,Sq,Dv)
    out = jnp.moveaxis(out, 3, 1).reshape(B, Sq, H, Dv)
    return out.astype(q.dtype)


# ==========================================================================
# CacheBackend — the unified KV-cache layer (DESIGN.md §Cache-backends)
# ==========================================================================

def cache_streams(cfg: ModelConfig) -> Tuple[Tuple[str, tuple], ...]:
    """What one cached token consists of, per attention family:
    (name, per-token trailing shape) for each stream. GQA caches per-head
    K/V rows; MLA caches the compressed latent + shared rope key — the
    layout backends below are agnostic to which."""
    if cfg.use_mla:
        return (("ckv", (cfg.kv_lora_rank,)),
                ("kr", (cfg.qk_rope_head_dim,)))
    return (("k", (cfg.num_kv_heads, cfg.head_dim)),
            ("v", (cfg.num_kv_heads, cfg.head_dim)))


def is_paged_cache(cache: dict) -> bool:
    return "pos_pages" in cache


class DenseCacheBackend:
    """Contiguous per-row cache of ``length`` slots; doubles as the
    sliding-window RING buffer when ``cfg.sliding_window`` is set (write
    index ``offset % length``, windowed prefill ring-writes the trailing
    window). Used by the group Sampler and the dense-slot engine."""

    paged = False

    def __init__(self, cfg: ModelConfig, length: int):
        self.cfg = cfg
        self.L = length
        self.ring = cfg.sliding_window is not None

    def init(self, batch: int, dtype) -> dict:
        state = {n: jnp.zeros((batch, self.L) + shp, dtype)
                 for n, shp in cache_streams(self.cfg)}
        state["pos"] = jnp.full((batch, self.L), INVALID_POS, jnp.int32)
        state["seg"] = jnp.full((batch, self.L), -2, jnp.int32)
        return state

    def write_decode(self, state: dict, vals: tuple, positions, segments,
                     cache_offset) -> dict:
        """Decode-time write of S tokens per row (vals are (B, S, *shp);
        S == 1 for plain decode, S == k+1 for the spec-decode verify block
        — DESIGN.md §Spec-decode); ``cache_offset`` is a scalar (lock-step
        engines) or (B,) per-row START offsets (slot engines): row b's
        token j lands at slot ``off[b] + j`` (mod L on ring caches)."""
        L = self.L
        off = jnp.asarray(cache_offset)
        new = {}
        if off.ndim == 1:
            # per-row offsets (continuous batching: each slot is at a
            # different position) -> batched scatter at off[b] + j.
            S = positions.shape[1]
            idx = off[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
            if self.ring:
                idx = idx % L
            b_idx = jnp.arange(off.shape[0], dtype=jnp.int32)[:, None]
            for (n, shp), val in zip(cache_streams(self.cfg), vals):
                new[n] = state[n].at[b_idx, idx].set(val)
            new["pos"] = state["pos"].at[b_idx, idx].set(positions)
            new["seg"] = state["seg"].at[b_idx, idx].set(segments)
        else:
            idx = cache_offset % L if self.ring else cache_offset
            for (n, shp), val in zip(cache_streams(self.cfg), vals):
                new[n] = jax.lax.dynamic_update_slice(
                    state[n], val, (0, idx) + (0,) * len(shp))
            new["pos"] = jax.lax.dynamic_update_slice(
                state["pos"], positions, (0, idx))
            new["seg"] = jax.lax.dynamic_update_slice(
                state["seg"], segments, (0, idx))
        return new

    def write_prefill(self, state: dict, vals: tuple, positions,
                      segments) -> dict:
        """Prompt prefill. S <= L writes at offset 0; S > L (legal only on
        ring caches) ring-writes the trailing window — token i lands in slot
        ``i % L`` so later decode steps (``idx = offset % L``) find it."""
        S = positions.shape[1]
        new = {}
        if S > self.L:
            assert self.ring, "prefill exceeds cache"
            rr = S % self.L
            ring = lambda a: jnp.roll(a[:, -self.L:], rr, axis=1)
            for (n, _), val in zip(cache_streams(self.cfg), vals):
                new[n] = ring(val)
            new["pos"] = ring(positions)
            new["seg"] = ring(segments)
            return new
        for (n, shp), val in zip(cache_streams(self.cfg), vals):
            new[n] = jax.lax.dynamic_update_slice(
                state[n], val, (0, 0) + (0,) * len(shp))
        new["pos"] = jax.lax.dynamic_update_slice(
            state["pos"], positions, (0, 0))
        new["seg"] = jax.lax.dynamic_update_slice(
            state["seg"], segments, (0, 0))
        return new

    def read(self, state: dict) -> tuple:
        """-> (*streams, kv_pos, kv_seg), each full-length."""
        return tuple(state[n] for n, _ in cache_streams(self.cfg)) \
            + (state["pos"], state["seg"])


class PagedCacheBackend:
    """One physical page pool shared by every sequence on the engine
    (DESIGN.md §Continuous-batching). Logical sequences are stitched
    together by a per-slot page table; a GRPO group's rows list the same
    prompt pages, so the shared prompt is stored once per group — the
    cache-level counterpart of SPA's shared-prompt packing. For MLA the
    pages hold ``(ckv, kr)`` latent rows (cache_streams), ~10x smaller than
    a GQA page — absorbed decode gathers latent pages directly."""

    paged = True

    def __init__(self, cfg: ModelConfig, page_size: int):
        self.cfg = cfg
        self.page = page_size

    def init(self, num_pages: int, dtype) -> dict:
        state = {n + "_pages": jnp.zeros((num_pages, self.page) + shp, dtype)
                 for n, shp in cache_streams(self.cfg)}
        state["pos_pages"] = jnp.full((num_pages, self.page), INVALID_POS,
                                      jnp.int32)
        return state

    def write_decode(self, state: dict, vals: tuple, positions,
                     cache_offset) -> dict:
        """cache_offset: (B, S) flat slot indices (page_id * page_size +
        slot) where this step's S tokens per row land (S == 1 for plain
        decode, k+1 for the spec verify block) — engines point inactive
        rows and masked speculative slots at the trash page, so duplicate
        trash indices across rows are harmless garbage."""
        P, page = state["pos_pages"].shape
        flat = lambda a: a.reshape((P * page,) + a.shape[2:])
        idx = jnp.asarray(cache_offset)                        # (B, S)
        new = {}
        for (n, _), val in zip(cache_streams(self.cfg), vals):
            pool = state[n + "_pages"]
            new[n + "_pages"] = flat(pool).at[idx].set(val).reshape(
                pool.shape)
        new["pos_pages"] = flat(state["pos_pages"]).at[idx].set(
            positions).reshape(state["pos_pages"].shape)
        return new

    def gather(self, state: dict, page_table) -> tuple:
        """(B, n_max) page table -> (*streams (B, L, *shp), kv_pos (B, L))
        logical contexts; null page 0 carries pos 2^30 (masked)."""
        B, n_max = page_table.shape
        L = n_max * self.page
        outs = tuple(
            state[n + "_pages"][page_table].reshape((B, L) + shp)
            for n, shp in cache_streams(self.cfg))
        kv_pos = state["pos_pages"][page_table].reshape(B, L)
        return outs + (kv_pos,)


def cache_backend(cfg: ModelConfig, *, length: Optional[int] = None,
                  page_size: Optional[int] = None):
    """The single construction point every decode path goes through:
    ``page_size`` selects the paged pool backend, otherwise a dense /
    ring cache of ``length`` slots."""
    if page_size is not None:
        return PagedCacheBackend(cfg, page_size)
    assert length is not None, "dense cache backend needs a length"
    return DenseCacheBackend(cfg, length)


def backend_of(cfg: ModelConfig, cache: dict):
    """Recover the layout backend from a cache state's leaves."""
    if is_paged_cache(cache):
        return PagedCacheBackend(cfg, cache["pos_pages"].shape[1])
    return DenseCacheBackend(cfg, cache["pos"].shape[1])


# ==========================================================================
# GQA attention block
# ==========================================================================

def init_gqa(key, cfg: ModelConfig, dtype) -> dict:
    d, H, Hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, (d, H * hd), 0, dtype),
        "wk": dense_init(k2, (d, Hkv * hd), 0, dtype),
        "wv": dense_init(k3, (d, Hkv * hd), 0, dtype),
        "wo": dense_init(k4, (H * hd, d), 0, dtype),
    }


def make_paged_kv_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                        dtype) -> dict:
    """Paged pool state (see PagedCacheBackend); GQA or MLA-latent pages
    depending on cfg."""
    return PagedCacheBackend(cfg, page_size).init(num_pages, dtype)


def _is_verify(S: int, cache_offset) -> bool:
    """Multi-token DECODE-side write (the spec plane's k+1-token verify
    block, DESIGN.md §Spec-decode) vs prefill: every prefill call passes a
    scalar offset (0), while verify engines pass per-row start offsets."""
    return S > 1 and cache_offset is not None \
        and jnp.asarray(cache_offset).ndim >= 1


def _paged_offsets(cache_offset):
    """Normalise the paged write offsets to (B, S) flat slot indices —
    single-token engines pass (B,), the spec verify block (B, k+1)."""
    off = jnp.asarray(cache_offset)
    return off[:, None] if off.ndim == 1 else off


def _paged_gqa_decode(params, cfg: ModelConfig, q, k, v, positions, cache,
                      cache_offset, page_table):
    """GQA decode against the paged pool: S == 1 plain decode or S == k+1
    spec-decode verify (DESIGN.md §Spec-decode) — the written block then
    attends over the row's full gathered context, so intra-block causality
    falls out of the position mask. Returns (out (B,S,H,Dv), new_cache)."""
    B, S = q.shape[:2]
    be = backend_of(cfg, cache)
    new_cache = be.write_decode(cache, (k, v), positions,
                                _paged_offsets(cache_offset))
    if cfg.use_pallas_attention:
        # flash-decode Pallas kernel over the page pool (§Perf): the kernel
        # wrapper owns the page-table gather; causal masking comes from kv
        # pos (invalid slots carry 2^30).
        if S == 1:
            from repro.kernels.ops import paged_decode_attention as _flash
            out = _flash(q[:, 0], new_cache["k_pages"],
                         new_cache["v_pages"], new_cache["pos_pages"],
                         page_table, positions[:, 0],
                         window=cfg.sliding_window)[:, None]
        else:
            from repro.kernels.ops import paged_verify_attention as _flash
            out = _flash(q, new_cache["k_pages"], new_cache["v_pages"],
                         new_cache["pos_pages"], page_table, positions,
                         window=cfg.sliding_window)
        return out, new_cache
    # pure-JAX path: gather each row's logical context,
    # (B, n_max, page, ...) -> (B, L, ...), then single-pass decode
    kk, vv, kp = be.gather(new_cache, page_table)
    zeros = jnp.zeros((B, S), jnp.int32)
    out = chunked_attention(q, kk, vv, positions, kp, zeros,
                            jnp.zeros(kp.shape, jnp.int32),
                            window=cfg.sliding_window,
                            chunk_size=cfg.attn_chunk_size)
    return out, new_cache


def _shmap_decode_fit(cfg: ModelConfig, cache: dict, mesh, S: int) -> bool:
    """True when the dense-GQA single-token decode step should run under
    the fully-manual shard_map path (``_shmap_gqa_decode``): a mesh is
    installed whose model axis actually shards the cache's length dim
    (the active profile maps "seq" -> "model" and the axis divides L), so
    the GSPMD single-slot write would pay the residual collective this
    path exists to remove. Everything else (no mesh, unsharded cache,
    MLA, paged pool, verify blocks) keeps the GSPMD branch."""
    if mesh is None or "model" not in mesh.axis_names:
        return False
    n = mesh.shape["model"]
    if n <= 1 or S != 1 or cfg.use_mla or is_paged_cache(cache):
        return False
    if "model" not in LOGICAL_TO_MESH.get("seq", ()):
        return False
    return cache["pos"].shape[1] % n == 0


def _shmap_gqa_decode(cfg: ModelConfig, q, k, v, positions, segments,
                      cache: dict, cache_offset, mesh):
    """shard_map'd dense-GQA decode step over the seq-sharded cache
    (DESIGN.md §Device-resident-decode): each model shard writes the new
    K/V row ONLY when the slot falls inside its local L/n range (a masked
    local in-place update — no collective), computes flash partials over
    its local shard, and the shards merge through one pmax + two psums on
    (B, H)-sized softmax stats (``combine_partial_stats``). This is the
    structural fix for dense-GQA decode's residual SPMD collective: the
    cache never moves, only the stats do.

    Handles both offset conventions ``write_decode`` accepts for S == 1:
    a scalar (lock-step engines) and (B,) per-row starts (slot engines).
    Returns (out (B, 1, H, Dv) pre-``wo``, new_cache)."""
    from repro.kernels.decode_attention import (combine_partial_stats,
                                                decode_partial_stats)
    from jax.sharding import PartitionSpec as P

    B = q.shape[0]
    L = cache["pos"].shape[1]
    n = mesh.shape["model"]
    L_loc = L // n
    ring = cfg.sliding_window is not None
    off = jnp.asarray(cache_offset)

    q_spec = spec_for(mesh, q.shape, ("batch", None, None, None))
    row_spec = spec_for(mesh, positions.shape, ("batch", None))
    off_spec = spec_for(mesh, off.shape, ("batch",)) if off.ndim else P()
    ckv_spec = spec_for(mesh, cache["k"].shape, ("batch", "seq", None, None))
    cpos_spec = spec_for(mesh, cache["pos"].shape, ("batch", "seq"))
    out_spec = spec_for(mesh, (B, 1, q.shape[2], v.shape[-1]),
                        ("batch", None, None, None))

    def body(qb, kb, vb, qp, qs, ob, ck, cv, cp, cs):
        base = jax.lax.axis_index("model") * L_loc
        gidx = ob % L if ring else ob
        loc = gidx - base
        ok = (loc >= 0) & (loc < L_loc)
        idx = jnp.clip(loc, 0, L_loc - 1)
        if ob.ndim == 1:
            # per-row slot offsets (dense-slot engine): gather the current
            # row at the clamped local slot, select, scatter back — rows
            # whose slot lives on another shard write their own old value.
            bi = jnp.arange(qb.shape[0], dtype=jnp.int32)
            sel = lambda cur, new: jnp.where(
                ok.reshape((-1,) + (1,) * (new.ndim - 1)), new, cur)
            ck = ck.at[bi, idx].set(sel(ck[bi, idx], kb[:, 0]))
            cv = cv.at[bi, idx].set(sel(cv[bi, idx], vb[:, 0]))
            cp = cp.at[bi, idx].set(jnp.where(ok, qp[:, 0], cp[bi, idx]))
            cs = cs.at[bi, idx].set(jnp.where(ok, qs[:, 0], cs[bi, idx]))
        else:
            # scalar offset (lock-step engines): masked DUS at the local
            # index — off-shard devices rewrite the slot's current value.
            def upd(buf, new):
                cur = jax.lax.dynamic_slice_in_dim(buf, idx, 1, 1)
                return jax.lax.dynamic_update_slice_in_dim(
                    buf, jnp.where(ok, new, cur), idx, 1)
            ck, cv = upd(ck, kb), upd(cv, vb)
            cp, cs = upd(cp, qp), upd(cs, qs)
        pv, m, l = decode_partial_stats(qb, ck, cv, qp, cp, qs, cs,
                                        window=cfg.sliding_window)
        out = combine_partial_stats(pv, m, l, "model")
        out = jnp.moveaxis(out, 3, 1)                  # (B, 1, Hkv, G, Dv)
        out = out.reshape(qb.shape[0], 1, -1, out.shape[-1])
        return out.astype(qb.dtype), ck, cv, cp, cs

    out, nk, nv, npos, nseg = shard_map(
        body, mesh,
        in_specs=(q_spec, q_spec, q_spec, row_spec, row_spec, off_spec,
                  ckv_spec, ckv_spec, cpos_spec, cpos_spec),
        out_specs=(out_spec, ckv_spec, ckv_spec, cpos_spec, cpos_spec))(
            q, k, v, positions, segments, off,
            cache["k"], cache["v"], cache["pos"], cache["seg"])
    return out, {"k": nk, "v": nv, "pos": npos, "seg": nseg}


def _platform() -> str:
    return jax.default_backend()


def spa_kernel_applies(cfg: ModelConfig, batch: int, length: int) -> bool:
    """Whether GQA's training / scoring forward (no cache, S > 1) of
    ``batch`` rows of ``length`` runs the Pallas SPA kernel (DESIGN.md §3):
    on a TPU, with no multi-device mesh installed (a ``pallas_call`` does
    not partition under GSPMD), with a head dim that fills the kernel's
    128-lane tiles, and with a visit list that fits its scalar memory.
    Read from the platform and the shapes, never from an option."""
    from repro.kernels.spa_attention import fits
    mesh = current_mesh()
    return (_platform() == "tpu" and (mesh is None or mesh.size == 1)
            and cfg.head_dim % 128 == 0 and fits(batch, length, length))


def attn_tile_share(cfg: ModelConfig, positions, segments):
    """Share of attention tiles one training forward computes: the SPA
    kernel's visit map over these positions and segments where GQA takes
    the kernel, else 1 (the chunked scan computes every tile). A scalar
    f32."""
    if (cfg.use_mla or cfg.attention_free
            or not (cfg.use_pallas_attention
                    or spa_kernel_applies(cfg, *positions.shape))):
        return jnp.float32(1.0)
    from repro.kernels.ops import spa_tile_share
    return spa_tile_share(positions, positions, segments, segments,
                          window=cfg.sliding_window)


def gqa_attention(params, cfg: ModelConfig, x, positions, segments, *,
                  cache: Optional[dict] = None, cache_offset=None,
                  page_table=None):
    """x: (B, S, d). Training/prefill when cache is None or being filled;
    decode when S == 1 and cache holds history. A paged cache (leaves
    ``k_pages``/``v_pages``/``pos_pages`` + a ``page_table``) routes decode
    through the shared page pool instead of per-row contiguous caches.

    Returns (out, new_cache)."""
    B, S, d = x.shape
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if profile_has("heads") and S > 1:
        # Megatron-SP: gather seq once per layer; projections below then
        # emit head-sharded q (column parallel) instead of forcing a full
        # weight gather against seq-sharded activations.
        x = constrain(x, "batch", None, None)
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"]).reshape(B, S, H, hd)
    k = jnp.einsum("bsd,dh->bsh", x, params["wk"]).reshape(B, S, Hkv, hd)
    v = jnp.einsum("bsd,dh->bsh", x, params["wv"]).reshape(B, S, Hkv, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None and is_paged_cache(cache):
        # S == 1: plain decode; S > 1: spec-decode verify block
        out, new_cache = _paged_gqa_decode(params, cfg, q, k, v, positions,
                                           cache, cache_offset, page_table)
        out = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, H * hd),
                         params["wo"])
        return out, new_cache
    if cache is None:
        kk, vv, kp, ks = k, v, positions, segments
    else:
        be = backend_of(cfg, cache)
        if S == 1 or _is_verify(S, cache_offset):
            mesh = current_mesh()
            if _shmap_decode_fit(cfg, cache, mesh, S):
                out, new_cache = _shmap_gqa_decode(
                    cfg, q, k, v, positions, segments, cache, cache_offset,
                    mesh)
                out = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, H * hd),
                                 params["wo"])
                return out, new_cache
            # NOTE (SPerf): a mask-based (iota==idx select) write was
            # REFUTED as a fix -- XLA computes the select replicated and
            # the gather just moves to the sharding constraint (measured
            # identical 2.16 s bound on internlm2 decode_32k). The
            # structural fix is the shard_map'd decode step above
            # (_shmap_gqa_decode); this GSPMD branch remains for unsharded
            # caches / no-mesh runs / verify blocks, where the single-slot
            # write pays no collective (or the profile leaves seq
            # unsharded).
            new_cache = be.write_decode(cache, (k, v), positions, segments,
                                        cache_offset)
        elif S > be.L:
            # windowed prefill (S > window): attend against the full fresh
            # K/V (the window mask handles visibility) and ring-write only
            # the trailing L tokens.
            new_cache = be.write_prefill(cache, (k, v), positions, segments)
            out = chunked_attention(q, k, v, positions, positions,
                                    segments, segments,
                                    window=cfg.sliding_window,
                                    chunk_size=cfg.attn_chunk_size)
            out = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, H * hd),
                             params["wo"])
            return out, new_cache
        else:  # prefill into an empty cache (L >= S, offset 0)
            new_cache = be.write_prefill(cache, (k, v), positions, segments)
        kk, vv, kp, ks = be.read(new_cache)

    # Under the "sp_heads" profile (§Perf): reshard once per layer — q to
    # head-sharded, k/v replicated over the model axis — so the KV-chunk
    # scan below is collective-free. No-op when heads don't divide the
    # model axis or under other profiles ("heads" unmapped).
    q = constrain(q, "batch", None, "heads", None)
    kk = constrain(kk, "batch", None, None, None)
    vv = constrain(vv, "batch", None, None, None)
    if S > 1 and ((cache is None and spa_kernel_applies(cfg, B, S))
                  or cfg.use_pallas_attention):
        # fused block-sparse shared-prompt flash kernel, differentiable:
        # scores/probs never leave VMEM (§Perf iter A5) and dead
        # response x response tiles are never visited (DESIGN.md §3).
        from repro.kernels.ops import spa_attention as _spa_kernel
        out = _spa_kernel(q, kk, vv, positions, kp, segments, ks,
                          window=cfg.sliding_window)
    else:
        out = chunked_attention(q, kk, vv, positions, kp, segments, ks,
                                window=cfg.sliding_window,
                                chunk_size=cfg.attn_chunk_size)
    out = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, H * hd), params["wo"])
    return out, new_cache


# ==========================================================================
# MLA (multi-head latent attention, DeepSeek-V2)
# ==========================================================================

def init_mla(key, cfg: ModelConfig, dtype) -> dict:
    d, H = cfg.d_model, cfg.num_heads
    r, nd, rd, vd = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq": dense_init(ks[0], (d, H * (nd + rd)), 0, dtype),
        "w_dkv": dense_init(ks[1], (d, r), 0, dtype),
        "w_kr": dense_init(ks[2], (d, rd), 0, dtype),
        "ckv_norm": jnp.ones((r,), dtype),
        "w_uk": dense_init(ks[3], (r, H * nd), 0, dtype),
        "w_uv": dense_init(ks[4], (r, H * vd), 0, dtype),
        "wo": dense_init(ks[5], (H * vd, d), 0, dtype),
    }


def _mla_qckv(params, cfg: ModelConfig, x, positions):
    from repro.models.layers import rmsnorm
    B, S, _ = x.shape
    H = cfg.num_heads
    nd, rd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = jnp.einsum("bsd,dh->bsh", x, params["wq"]).reshape(B, S, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    ckv = rmsnorm({"scale": params["ckv_norm"]},
                  jnp.einsum("bsd,dr->bsr", x, params["w_dkv"]), cfg.norm_eps)
    kr = jnp.einsum("bsd,dr->bsr", x, params["w_kr"])[:, :, None, :]
    kr = apply_rope(kr, positions, cfg.rope_theta)[:, :, 0, :]
    return q_nope, q_rope, ckv, kr


def _absorbed_q(params, cfg: ModelConfig, q_nope, q_rope):
    """Fold w_uk into q: (B, S, H, nd) -> (B, S, H, r + rd) latent-space
    queries — shared by the contiguous and paged absorbed-decode paths."""
    H = cfg.num_heads
    w_uk = params["w_uk"].reshape(cfg.kv_lora_rank, H, cfg.qk_nope_head_dim)
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk)
    return jnp.concatenate([q_lat, q_rope], axis=-1)


def _paged_mla_decode(params, cfg: ModelConfig, q_nope, q_rope, ckv, kr,
                      positions, cache, cache_offset, page_table, scale):
    """Absorbed MLA decode against the paged latent pool (S == 1 plain
    decode, S == k+1 spec verify): pages hold (ckv, kr) rows; scores and
    values stay in the (rank + rope) latent space. Returns
    (o_lat (B,S,H,r), new_cache)."""
    B, S = ckv.shape[:2]
    be = backend_of(cfg, cache)
    new_cache = be.write_decode(cache, (ckv, kr), positions,
                                _paged_offsets(cache_offset))
    q_cat = _absorbed_q(params, cfg, q_nope, q_rope)           # (B,S,H,r+rd)
    if cfg.use_pallas_attention:
        if S == 1:
            from repro.kernels.ops import (paged_mla_decode_attention
                                           as _flash)
            o_lat = _flash(q_cat[:, 0], new_cache["ckv_pages"],
                           new_cache["kr_pages"], new_cache["pos_pages"],
                           page_table, positions[:, 0], scale=scale,
                           window=cfg.sliding_window)[:, None]
        else:
            from repro.kernels.ops import (paged_mla_verify_attention
                                           as _flash)
            o_lat = _flash(q_cat, new_cache["ckv_pages"],
                           new_cache["kr_pages"], new_cache["pos_pages"],
                           page_table, positions, scale=scale,
                           window=cfg.sliding_window)
        return o_lat, new_cache
    ckv_all, kr_all, kp = be.gather(new_cache, page_table)
    k_cat = jnp.concatenate([ckv_all, kr_all], axis=-1)[:, :, None, :]
    zeros = jnp.zeros((B, S), jnp.int32)
    o_lat = chunked_attention(q_cat, k_cat, ckv_all[:, :, None, :],
                              positions, kp, zeros,
                              jnp.zeros(kp.shape, jnp.int32),
                              window=cfg.sliding_window,
                              chunk_size=cfg.attn_chunk_size, scale=scale)
    return o_lat, new_cache


def mla_attention(params, cfg: ModelConfig, x, positions, segments, *,
                  cache: Optional[dict] = None, cache_offset=None,
                  page_table=None):
    """Expanded path for train/prefill; absorbed path for decode (S == 1):
    scores and values live in the (rank + rope) latent space so the KV cache
    stores only ckv + shared rope key — the MLA memory win. A paged cache
    (``ckv_pages``/``kr_pages``/``pos_pages`` + page table) routes absorbed
    decode through the shared latent page pool."""
    B, S, d = x.shape
    H = cfg.num_heads
    nd, rd, vd, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim, cfg.kv_lora_rank)
    q_nope, q_rope, ckv, kr = _mla_qckv(params, cfg, x, positions)
    scale = (nd + rd) ** -0.5

    if cache is not None and is_paged_cache(cache):
        # S == 1: plain decode; S > 1: spec-decode verify block
        o_lat, new_cache = _paged_mla_decode(
            params, cfg, q_nope, q_rope, ckv, kr, positions, cache,
            cache_offset, page_table, scale)
        w_uv = params["w_uv"].reshape(r, H, vd)
        out = jnp.einsum("bshr,rhv->bshv", o_lat, w_uv)
        out = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, H * vd),
                         params["wo"])
        return out, new_cache

    new_cache = None
    verify = _is_verify(S, cache_offset)
    if cache is not None:
        be = backend_of(cfg, cache)
        if S > 1 and S > be.L and not verify:
            # windowed prefill: ring-write trailing window, attend full
            # (mirrors gqa_attention's windowed-prefill path).
            new_cache = be.write_prefill(cache, (ckv, kr), positions,
                                         segments)
            ckv_all, kr_all = ckv, kr
            kp, ks = positions, segments
        else:
            if S == 1 or verify:
                new_cache = be.write_decode(cache, (ckv, kr), positions,
                                            segments, cache_offset)
            else:
                new_cache = be.write_prefill(cache, (ckv, kr), positions,
                                             segments)
            ckv_all, kr_all, kp, ks = be.read(new_cache)
    else:
        ckv_all, kr_all, kp, ks = ckv, kr, positions, segments

    if (S == 1 or verify) and cache is not None:
        # absorbed decode: fold w_uk into q, attend in latent space.
        q_cat = _absorbed_q(params, cfg, q_nope, q_rope)        # (B,1,H,r+rd)
        k_cat = jnp.concatenate([ckv_all, kr_all], axis=-1)[:, :, None, :]
        o_lat = chunked_attention(q_cat, k_cat,
                                  ckv_all[:, :, None, :],
                                  positions, kp, segments, ks,
                                  window=cfg.sliding_window,
                                  chunk_size=cfg.attn_chunk_size,
                                  scale=scale)                  # (B,1,H,r)
        w_uv = params["w_uv"].reshape(r, H, vd)
        out = jnp.einsum("bshr,rhv->bshv", o_lat, w_uv)
    else:
        # expanded: materialise per-head k/v from the latent (chunk-bounded
        # activations come from scanning layers; S*H*(nd+rd) is one layer's).
        k_nope = jnp.einsum("bsr,rh->bsh", ckv_all, params["w_uk"]).reshape(
            B, -1, H, nd)
        v = jnp.einsum("bsr,rh->bsh", ckv_all, params["w_uv"]).reshape(
            B, -1, H, vd)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr_all[:, :, None, :],
                                      k_nope.shape[:3] + (rd,))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = chunked_attention(q, k, v, positions, kp, segments, ks,
                                window=cfg.sliding_window,
                                chunk_size=cfg.attn_chunk_size, scale=scale)
    out = jnp.einsum("bsh,hd->bsd", out.reshape(B, S, H * vd), params["wo"])
    return out, new_cache


def init_attention(key, cfg: ModelConfig, dtype) -> dict:
    return init_mla(key, cfg, dtype) if cfg.use_mla else init_gqa(key, cfg, dtype)


def attention(params, cfg: ModelConfig, x, positions, segments, **kw):
    fn = mla_attention if cfg.use_mla else gqa_attention
    return fn(params, cfg, x, positions, segments, **kw)


def make_cache(cfg: ModelConfig, batch: int, length: int, dtype) -> dict:
    return cache_backend(cfg, length=length).init(batch, dtype)
