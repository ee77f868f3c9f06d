"""Block-sparse shared-prompt flash attention — the TPU-native counterpart of
the paper's ``npu_fusion_attention`` custom-mask kernel (§5, §4.3).

TPU adaptation (see DESIGN.md §3): instead of a dense masked kernel, the
shared-prompt mask is evaluated per 128x128 tile from (position, segment)
arrays, and tiles where *no* query can see *any* key — response_i x
response_j blocks with i != j, and fully-non-causal blocks — are skipped
entirely via a host-precomputed block map. That realises the paper's
O(Lp^2 + K*Lr*Lp + K*Lr^2) complexity *structurally* on the MXU, with the
online-softmax running max/sum held in VMEM scratch.

Layout: q/k/v are head-folded to (BH, S, D); grid = (BH, nq, nk) with the
kv axis innermost so the (bq, D) accumulator lives in VMEM scratch across
kv steps.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _kernel(bmap_ref,                       # scalar prefetch (SMEM)
            qpos_ref, kpos_ref, qseg_ref, kseg_ref,
            q_ref, k_ref, v_ref,            # inputs
            o_ref,                          # output
            acc_ref, m_ref, l_ref,          # VMEM scratch
            *, scale: float, window: Optional[int], heads: int, nq: int,
            nk: int):
    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(bmap_ref[((bh // heads) * nq + qi) * nk + ki] != 0)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # (bq, D)
        k = k_ref[0].astype(jnp.float32)            # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (bq, bk)
        qp = qpos_ref[0]                            # (bq, 1)
        kp = kpos_ref[0]                            # (1, bk)
        qs = qseg_ref[0]
        ks = kseg_ref[0]
        allow = (kp <= qp) & ((ks == 0) | (ks == qs))
        if window is not None:
            allow &= (qp - kp) < window
        s = jnp.where(allow, s, NEG_INF)
        m_prev = m_ref[...]                          # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)               # (bq, 1)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)             # (bk, Dv)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def block_map(q_pos, kv_pos, q_seg, kv_seg, bq: int, bk: int,
              window: Optional[int] = None):
    """Host/jnp-side conservative tile visibility map -> (B, nq, nk) int32.

    A tile is live iff some (q, kv) pair in it could be visible: causal
    (min kv_pos <= max q_pos), window (max kv_pos > min q_pos - window) and
    segment-compatible (kv tile touches segment 0, or the segment ranges
    intersect). Over-approximation is safe — the in-kernel mask is exact."""
    B, Sq = q_pos.shape
    Skv = kv_pos.shape[1]
    nq, nk = Sq // bq, Skv // bk
    qp = q_pos.reshape(B, nq, bq)
    kp = kv_pos.reshape(B, nk, bk)
    qs = q_seg.reshape(B, nq, bq)
    ks = kv_seg.reshape(B, nk, bk)
    causal = kp.min(-1)[:, None, :] <= qp.max(-1)[:, :, None]   # (B, nq, nk)
    if window is not None:
        causal &= kp.max(-1)[:, None, :] > (qp.min(-1)[:, :, None] - window)
    ks_min, ks_max = ks.min(-1), ks.max(-1)
    qs_min, qs_max = qs.min(-1), qs.max(-1)
    seg_ok = (ks_min[:, None, :] <= 0) | (
        (ks_min[:, None, :] <= qs_max[:, :, None])
        & (ks_max[:, None, :] >= qs_min[:, :, None]))
    return (causal & seg_ok).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "block_q", "block_k",
                              "interpret"))
def spa_attention(q, k, v, q_pos, kv_pos, q_seg, kv_seg, *,
                  scale: Optional[float] = None,
                  window: Optional[int] = None,
                  block_q: int = 128, block_k: int = 128,
                  interpret: bool = False):
    """Shared-prompt flash attention.

    q: (B, Sq, H, D); k/v: (B, Skv, Hkv, Dv) with H % Hkv == 0 (GQA: kv heads
    are repeated to H on the host side of the fold). pos/seg: (B, S) int32.
    Returns (B, Sq, H, Dv) in q.dtype.
    """
    B, Sq, H, D = q.shape
    _, Skv, Hkv, Dv = v.shape
    scale = D ** -0.5 if scale is None else scale
    G = H // Hkv
    if G != 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)

    bq, bk = min(block_q, Sq), min(block_k, Skv)
    pad_q, pad_k = (-Sq) % bq, (-Skv) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)))
        q_seg = jnp.pad(q_seg, ((0, 0), (0, pad_q)), constant_values=-1)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad_k)), constant_values=2**30)
        kv_seg = jnp.pad(kv_seg, ((0, 0), (0, pad_k)), constant_values=-2)
    Sq_p, Skv_p = Sq + pad_q, Skv + pad_k
    nq, nk = Sq_p // bq, Skv_p // bk

    bmap = block_map(q_pos, kv_pos, q_seg, kv_seg, bq, bk, window)

    # fold heads into batch: (B, S, H, D) -> (B*H, S, D)
    qf = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq_p, D)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * H, Skv_p, D)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * H, Skv_p, Dv)

    grid = (B * H, nq, nk)
    kernel = functools.partial(_kernel, scale=scale, window=window, heads=H,
                               nq=nq, nk=nk)
    # Every block's last two dims are (8, 128)-divisible or span the array
    # (Mosaic's tiling rule): query positions/segments go in as (B, Sq, 1)
    # columns, kv ones as (B, 1, Skv) rows, and the tile map rides scalar
    # prefetch (SMEM), flattened.
    col = lambda a: a[:, :, None]
    row = lambda a: a[:, None, :]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1), lambda b, qi, ki, bm: (b // H, qi, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, qi, ki, bm: (b // H, 0, ki)),
            pl.BlockSpec((1, bq, 1), lambda b, qi, ki, bm: (b // H, qi, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, qi, ki, bm: (b // H, 0, ki)),
            pl.BlockSpec((1, bq, D), lambda b, qi, ki, bm: (b, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda b, qi, ki, bm: (b, ki, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, qi, ki, bm: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv),
                               lambda b, qi, ki, bm: (b, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, Dv), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running sum
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, Sq_p, Dv), q.dtype),
        interpret=interpret,
    )(bmap.reshape(-1), col(q_pos), row(kv_pos), col(q_seg), row(kv_seg),
      qf, kf, vf)

    out = out.reshape(B, H, Sq_p, Dv)[:, :, :Sq]
    return jnp.moveaxis(out, 1, 2)
