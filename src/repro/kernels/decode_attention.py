"""Flash-decode Pallas kernel: new tokens' GQA attention against a long KV
cache, blocked over cache length with an online-softmax accumulator in VMEM
— the serving-side hot spot of the decoupled deployment (decode_32k /
long_500k shapes).

Two query shapes share one kernel body:

  * q_len = 1 (``decode_attention``): one new token per row — the plain
    continuous-batching decode step;
  * q_len = k+1 (``verify_attention``): the spec-decode verify block
    (DESIGN.md §Spec-decode) — k drafted tokens plus the unfed committed
    token attend in ONE pass, each query row masked by its OWN position, so
    intra-block causality needs no extra machinery.

Layout: grid = (B, Hkv, nR, nL) with the cache-length axis innermost;
queries are flattened to R = q_len * G rows per Hkv head and blocked by up
to ``ROW_BLOCK`` rows, K/V are read head-major in (bL, D) blocks, and each
row block's (bR, Dv) accumulator lives in VMEM scratch.
Invalid cache slots carry pos >= 2**30 and are masked by the causal rule.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30
# query rows per block: a verify block or a paged suffix prefill flattens
# q_len * G rows, which must not all sit in VMEM at once (256 rows of
# MLA's 576-wide queries plus their f32 accumulator fit its 16 MiB budget)
ROW_BLOCK = 256


def _kernel(qpos_ref, q_ref, k_ref, v_ref, kpos_ref,
            o_ref,
            acc_ref, m_ref, l_ref,
            *, scale: float, window: Optional[int], nL: int):
    li = pl.program_id(3)

    @pl.when(li == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (bR, D)
    k = k_ref[0, 0].astype(jnp.float32)            # (bL, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (bR, bL)
    qp = qpos_ref[0]                               # (bR, 1) per-row positions
    kp = kpos_ref[0]                               # (1, bL)
    ok = kp <= qp
    if window is not None:
        ok &= (qp - kp) < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)            # (bL, Dv)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new

    @pl.when(li == nL - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _flash_rows(qr, k, v, kv_pos, q_pos_rows, *, scale: float,
                window: Optional[int], block_l: int, interpret: bool):
    """Blocked online-softmax attention for R query rows per Hkv head.

    qr: (B, Hkv, R, D) flattened query rows; q_pos_rows: (B, R) each row's
    own position (decode broadcasts one position over G rows; verify
    interleaves q_len positions x G). k/v: (B, L, Hkv, Dv); kv_pos: (B, L).
    Returns (B, Hkv, R, Dv) in qr.dtype.

    Every block's last two dims are (8, 128)-divisible or span the array
    (Mosaic's tiling rule): K/V go to the kernel head-major as (B, Hkv, L,
    D) blocks of (bL, D), query positions as a (B, R, 1) column and cache
    positions as a (B, 1, L) row. Rows are blocked by ``ROW_BLOCK``.
    """
    B, Hkv, R, D = qr.shape
    _, L, _, Dv = v.shape

    bR = R if R <= ROW_BLOCK else ROW_BLOCK
    pad_r = (-R) % bR
    if pad_r:       # padded rows attend at position 0 and are cut below
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, pad_r), (0, 0)))
        q_pos_rows = jnp.pad(q_pos_rows, ((0, 0), (0, pad_r)))
    nR = (R + pad_r) // bR

    bL = min(block_l, L)
    pad = (-L) % bL
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=2**30)
    L_p = L + pad
    nL = L_p // bL
    k = jnp.swapaxes(k, 1, 2)                      # (B, Hkv, L_p, D)
    v = jnp.swapaxes(v, 1, 2)

    grid = (B, Hkv, nR, nL)
    kernel = functools.partial(_kernel, scale=scale, window=window, nL=nL)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bR, 1), lambda b, h, r, li: (b, r, 0)),  # q_pos
            pl.BlockSpec((1, 1, bR, D), lambda b, h, r, li: (b, h, r, 0)),
            pl.BlockSpec((1, 1, bL, D), lambda b, h, r, li: (b, h, li, 0)),
            pl.BlockSpec((1, 1, bL, Dv), lambda b, h, r, li: (b, h, li, 0)),
            pl.BlockSpec((1, 1, bL), lambda b, h, r, li: (b, 0, li)),  # kv_pos
        ],
        out_specs=pl.BlockSpec((1, 1, bR, Dv),
                               lambda b, h, r, li: (b, h, r, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R + pad_r, Dv), qr.dtype),
        scratch_shapes=[
            pltpu.VMEM((bR, Dv), jnp.float32),
            pltpu.VMEM((bR, 1), jnp.float32),
            pltpu.VMEM((bR, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q_pos_rows[:, :, None], qr, k, v, kv_pos[:, None, :])
    return out[:, :, :R] if pad_r else out


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "block_l", "interpret"))
def decode_attention(q, k, v, kv_pos, q_pos, *,
                     scale: Optional[float] = None,
                     window: Optional[int] = None,
                     block_l: int = 256, interpret: bool = False):
    """q: (B, H, D) one token per row; k/v: (B, L, Hkv, Dv); kv_pos: (B, L);
    q_pos: (B,). Returns (B, H, Dv) in q.dtype."""
    B, H, D = q.shape
    _, L, Hkv, Dv = v.shape
    G = H // Hkv
    scale = D ** -0.5 if scale is None else scale
    qr = q.reshape(B, Hkv, G, D)
    qp = jnp.broadcast_to(q_pos[:, None], (B, G))
    out = _flash_rows(qr, k, v, kv_pos, qp, scale=scale, window=window,
                      block_l=block_l, interpret=interpret)
    return out.reshape(B, H, Dv)


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "block_l", "interpret"))
def verify_attention(q, k, v, kv_pos, q_pos, *,
                     scale: Optional[float] = None,
                     window: Optional[int] = None,
                     block_l: int = 256, interpret: bool = False):
    """Multi-token verify block (DESIGN.md §Spec-decode): q: (B, S, H, D)
    where S = k+1 drafted-plus-unfed tokens; q_pos: (B, S) each token's own
    position (the cache already holds the block's K/V, so causality within
    the block is the ordinary position mask); k/v: (B, L, Hkv, Dv);
    kv_pos: (B, L). Returns (B, S, H, Dv) in q.dtype."""
    B, S, H, D = q.shape
    _, L, Hkv, Dv = v.shape
    G = H // Hkv
    scale = D ** -0.5 if scale is None else scale
    # flatten to R = S*G query rows per Hkv head, position repeated per G
    qr = q.reshape(B, S, Hkv, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, S * G, D)
    qp = jnp.repeat(q_pos, G, axis=1)                          # (B, S*G)
    out = _flash_rows(qr, k, v, kv_pos, qp, scale=scale, window=window,
                      block_l=block_l, interpret=interpret)
    out = out.reshape(B, Hkv, S, G, Dv).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, S, H, Dv)


def decode_partial_stats(q, k, v, q_pos, kv_pos, q_seg, kv_seg, *,
                         window: Optional[int] = None,
                         scale: Optional[float] = None):
    """Single-pass decode attention PARTIAL stats over one KV shard — the
    per-device half of the shard_map'd dense-GQA decode step
    (models/attention.py ``_shmap_gqa_decode``, DESIGN.md
    §Device-resident-decode). Scores are normalised against the LOCAL max
    only; ``combine_partial_stats`` merges shards exactly (the flash
    online-softmax identity, applied once across devices instead of
    across chunks).

    q: (B, Sq, H, D); k/v: (B, L_loc, Hkv, Dv); q_pos/q_seg: (B, Sq);
    kv_pos/kv_seg: (B, L_loc). Returns f32 (pv, m, l):
    pv (B, Hkv, G, Sq, Dv) exp-weighted values, m (B, Hkv, G, Sq) local
    max, l (B, Hkv, G, Sq) local exp-sum. A shard with zero visible slots
    yields m == NEG_INF and garbage pv/l — the combine's exp(m - m_g)
    factor underflows to exactly 0.0, so the garbage never contributes."""
    B, Sq, H, D = q.shape
    _, L, Hkv, Dv = v.shape
    G = H // Hkv
    scale = D ** -0.5 if scale is None else scale
    qr = q.reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qr, k,
                   preferred_element_type=jnp.float32) * scale
    qp, kp = q_pos[:, :, None], kv_pos[:, None, :]
    qs, ks = q_seg[:, :, None], kv_seg[:, None, :]
    ok = (kp <= qp) & ((ks == 0) | (ks == qs))
    if window is not None:
        ok &= (qp - kp) < window
    s = jnp.where(ok[:, None, None, :, :], s, NEG_INF)
    m = s.max(axis=-1)                                 # (B, Hkv, G, Sq)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    return pv, m, l


def combine_partial_stats(pv, m, l, axis_name: str):
    """Merge per-shard flash partials across ``axis_name`` (inside a
    shard_map): one pmax on the (B, Hkv, G, Sq) max plus two psums on the
    rescaled sum/value partials — the only collectives the shard_map'd
    decode step pays, all of them (B, H)-sized instead of cache-sized.
    Returns the normalised (B, Hkv, G, Sq, Dv) attention output."""
    m_g = jax.lax.pmax(m, axis_name)
    c = jnp.exp(m - m_g)
    l_g = jax.lax.psum(l * c, axis_name)
    pv_g = jax.lax.psum(pv * c[..., None], axis_name)
    return pv_g / jnp.maximum(l_g, 1e-30)[..., None]


def _gather_pages(k_pages, v_pages, pos_pages, page_table):
    """(P, page, Hkv, D) pools + (B, n_max) tables -> each row's logical
    (B, L, Hkv, D) context (null page 0 carries pos 2^30, masked)."""
    B, n_max = page_table.shape
    P, page = pos_pages.shape
    L = n_max * page
    k = k_pages[page_table].reshape(B, L, k_pages.shape[2],
                                    k_pages.shape[-1])
    v = v_pages[page_table].reshape(B, L, v_pages.shape[2],
                                    v_pages.shape[-1])
    kv_pos = pos_pages[page_table].reshape(B, L)
    return k, v, kv_pos


def _gather_latent_pages(ckv_pages, kr_pages, pos_pages, page_table):
    """Latent pools -> MQA-shaped (B, L, 1, r+rd) keys / (B, L, 1, r)
    values (absorbed MLA decode is MQA with Dk = r + rd, Dv = r)."""
    B, n_max = page_table.shape
    P, page, r = ckv_pages.shape
    L = n_max * page
    ckv = ckv_pages[page_table].reshape(B, L, r)
    kr = kr_pages[page_table].reshape(B, L, kr_pages.shape[-1])
    k = jnp.concatenate([ckv, kr], axis=-1)[:, :, None, :]
    v = ckv[:, :, None, :]
    kv_pos = pos_pages[page_table].reshape(B, L)
    return k, v, kv_pos


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "block_l", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, pos_pages, page_table, q_pos,
                           *, scale: Optional[float] = None,
                           window: Optional[int] = None,
                           block_l: int = 256, interpret: bool = False):
    """Flash decode over a paged KV pool (DESIGN.md §Continuous-batching).

    q: (B, H, D); k_pages/v_pages: (P, page, Hkv, Dv); pos_pages: (P, page);
    page_table: (B, n_max) page ids per row (null page 0 carries pos 2^30,
    masked by the causal rule). The gather assembles each row's logical
    context — one shared physical prompt copy per GRPO group — and the
    blocked online-softmax kernel above consumes it unchanged.
    """
    k, v, kv_pos = _gather_pages(k_pages, v_pages, pos_pages, page_table)
    return decode_attention(q, k, v, kv_pos, q_pos, scale=scale,
                            window=window, block_l=block_l,
                            interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "block_l", "interpret"))
def paged_verify_attention(q, k_pages, v_pages, pos_pages, page_table,
                           q_pos, *, scale: Optional[float] = None,
                           window: Optional[int] = None,
                           block_l: int = 256, interpret: bool = False):
    """Spec-decode verify over a paged KV pool: q: (B, S, H, D) with the
    k+1-token block already written into the pool (speculative pages), so
    the gathered context + per-token position mask give exact causality."""
    k, v, kv_pos = _gather_pages(k_pages, v_pages, pos_pages, page_table)
    return verify_attention(q, k, v, kv_pos, q_pos, scale=scale,
                            window=window, block_l=block_l,
                            interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "block_l", "interpret"))
def paged_mla_decode_attention(q, ckv_pages, kr_pages, pos_pages, page_table,
                               q_pos, *, scale: Optional[float] = None,
                               window: Optional[int] = None,
                               block_l: int = 256, interpret: bool = False):
    """Flash decode over a paged MLA LATENT pool (DESIGN.md
    §Cache-backends): pages hold compressed ``(ckv, kr)`` latent rows
    instead of per-head K/V.

    q: (B, H, r + rd) absorbed latent-space queries (w_uk folded in);
    ckv_pages: (P, page, r); kr_pages: (P, page, rd); pos_pages: (P, page);
    page_table: (B, n_max); q_pos: (B,). Returns (B, H, r) latent outputs —
    the caller applies w_uv. Absorbed MLA decode is exactly MQA with
    Dk = r + rd and Dv = r, so after the latent gather the blocked
    online-softmax kernel above consumes it unchanged (Hkv = 1, G = H).
    """
    k, v, kv_pos = _gather_latent_pages(ckv_pages, kr_pages, pos_pages,
                                        page_table)
    return decode_attention(q, k, v, kv_pos, q_pos, scale=scale,
                            window=window, block_l=block_l,
                            interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "block_l", "interpret"))
def paged_mla_verify_attention(q, ckv_pages, kr_pages, pos_pages,
                               page_table, q_pos, *,
                               scale: Optional[float] = None,
                               window: Optional[int] = None,
                               block_l: int = 256, interpret: bool = False):
    """Spec-decode verify over the paged MLA latent pool: q: (B, S, H,
    r + rd) absorbed queries for the k+1-token block; q_pos: (B, S).
    Returns (B, S, H, r) latent outputs."""
    k, v, kv_pos = _gather_latent_pages(ckv_pages, kr_pages, pos_pages,
                                        page_table)
    return verify_attention(q, k, v, kv_pos, q_pos, scale=scale,
                            window=window, block_l=block_l,
                            interpret=interpret)
