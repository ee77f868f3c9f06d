"""Block-sparse shared-prompt flash attention — the TPU-native counterpart of
the paper's ``npu_fusion_attention`` custom-mask kernel (§5, §4.3) — with a
flash-attention backward, so the trainer's grad step runs through it.

TPU adaptation (see DESIGN.md §3): instead of a dense masked kernel, the
shared-prompt mask is evaluated per tile from (position, segment) arrays,
and tiles where *no* query can see *any* key — response_i x response_j
blocks with i != j, and fully-non-causal blocks — are never visited. That
realises the paper's O(Lp^2 + K*Lr*Lp + K*Lr^2) complexity *structurally*
on the MXU.

Layout and grid:

* q/k/v keep their (B, S, heads * D) layout; a grid step takes one KV head
  and the G query heads that share it (GQA through the index maps, no
  repeat), so the per-pair mask is built once for the group.
* ``block_map`` is exact: a tile is live iff some pair in it is allowed.
  The live tiles are compacted into a list (``_visit_list``) that rides
  scalar prefetch, and the grid is (Hkv, number of live tiles) with the
  second bound dynamic, so dead tiles cost neither a grid step nor a DMA.
* Forward and dQ walk the list query-tile-major (each query tile's output
  stays in VMEM across its key tiles); dK/dV walks the transposed list
  key-tile-major and sums over the group's query heads itself.
* Operands enter the MXU in their own dtype with f32 accumulation; P·V and
  the backward's products take P / dS in the operand dtype, as the
  pure-JAX ``chunked_attention`` does.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30
# tile sizes: the fewest computed pairs at the GRPO cell's layout
# (DESIGN.md §3)
BLOCK_Q = 128
BLOCK_K = 128
# the visit lists ride scalar memory (1 MiB on a v5e), one int32 a tile
MAX_TILES = 1 << 17
# a visit-list entry: flat tile index, and flags for the first / last visit
# of its output tile
_LAST = 1 << 29
_FIRST = 1 << 30
_IDX = _LAST - 1
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_NN = (((1,), (0,)), ((), ()))          # a @ b


def _allow(qp, kp, qs, ks, window: Optional[int]):
    """The shared-prompt pair mask, broadcast over whatever orientation the
    operands come in: causal by position, kv in the shared prompt or in
    the query's own segment, optionally windowed."""
    allow = (kp <= qp) & ((ks == 0) | (ks == qs))
    if window is not None:
        allow &= (qp - kp) < window
    return allow


def block_map(q_pos, kv_pos, q_seg, kv_seg, bq: int, bk: int,
              window: Optional[int] = None):
    """Exact tile visibility map -> (B, nq, nk) int32: a tile is live iff
    some (q, kv) pair in it is allowed. It costs one compare per pair,
    1/(heads * head_dim) of the dense attention it prunes."""
    B, Sq = q_pos.shape
    Skv = kv_pos.shape[1]
    nq, nk = Sq // bq, Skv // bk
    allow = _allow(q_pos[:, :, None], kv_pos[:, None, :], q_seg[:, :, None],
                   kv_seg[:, None, :], window)
    return allow.reshape(B, nq, bq, nk, bk).any(axis=(2, 4)).astype(jnp.int32)


def fits(batch: int, q_len: int, kv_len: int, block_q: int = BLOCK_Q,
         block_k: int = BLOCK_K) -> bool:
    """Whether the visit list of a (batch, q_len, kv_len) call fits the
    kernel's scalar memory (at 128-tiles, up to about 46k tokens a row)."""
    return batch * -(-q_len // block_q) * -(-kv_len // block_k) <= MAX_TILES


def _visit_map(live):
    """Live tiles plus, for any query tile or key tile with none, its tile
    against the first tile of the other axis, so every output tile of the
    forward, dQ and dK/dV is visited (and written) at least once. Forward
    and backward share this map, so the backward is the exact gradient of
    what the forward computed."""
    visit = live.astype(bool)
    visit = visit.at[:, :, 0].set(visit[:, :, 0] | ~visit.any(axis=2))
    return visit.at[:, 0, :].set(visit[:, 0, :] | ~visit.any(axis=1))


def _visit_list(visit):
    """(B, n_out, n_in) bool -> (codes (B*n_out*n_in,) int32, n int32): the
    visited tiles in (b, out, in) order, each coded as its flat index with
    _FIRST / _LAST set on the first / last visit of its (b, out) run;
    entries past ``n`` are never read."""
    B, n_out, n_in = visit.shape
    flat = visit.reshape(-1)
    size = flat.shape[0]
    n = flat.sum(dtype=jnp.int32)
    (idx,) = jnp.nonzero(flat, size=size, fill_value=size - 1)
    idx = idx.astype(jnp.int32)
    run = idx // n_in
    j = jnp.arange(size, dtype=jnp.int32)
    first = (j == 0) | (run != jnp.roll(run, 1))
    last = (j == n - 1) | (run != jnp.roll(run, -1))
    codes = (idx | jnp.where(first, _FIRST, 0) | jnp.where(last, _LAST, 0))
    return codes.astype(jnp.int32), n


@dataclasses.dataclass(frozen=True)
class _Tiling:
    G: int                  # query heads per KV head
    D: int
    Dv: int
    bq: int
    bk: int
    nq: int
    nk: int
    scale: float
    window: Optional[int]
    interpret: bool

    def q_major(self, code):
        """A forward / dQ list entry -> (b, q tile, kv tile)."""
        i = code & _IDX
        return i // (self.nq * self.nk), (i // self.nk) % self.nq, i % self.nk

    def k_major(self, code):
        """A dK/dV list entry -> (b, q tile, kv tile)."""
        i = code & _IDX
        return i // (self.nk * self.nq), i % self.nq, (i // self.nq) % self.nk


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------

def _fwd_kernel(code_ref, qpos_ref, kpos_ref, qseg_ref, kseg_ref,
                q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, t: _Tiling):
    code = code_ref[pl.program_id(1)]

    @pl.when((code & _FIRST) != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    allow = _allow(qpos_ref[0], kpos_ref[0], qseg_ref[0], kseg_ref[0],
                   t.window)                                   # (bq, bk)
    k = k_ref[0]
    v = v_ref[0]
    for g in range(t.G):
        q = q_ref[0, :, g * t.D:(g + 1) * t.D]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32)
        s = jnp.where(allow, s * t.scale, NEG_INF)
        m_prev = m_ref[g]                                      # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[g] = l_ref[g] * corr + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, _NN,
                                 preferred_element_type=jnp.float32)
        sl = slice(g * t.Dv, (g + 1) * t.Dv)
        acc_ref[:, sl] = acc_ref[:, sl] * corr + pv
        m_ref[g] = m_new

    @pl.when((code & _LAST) != 0)
    def _finish():
        for g in range(t.G):
            l = jnp.maximum(l_ref[g], 1e-30)
            sl = slice(g * t.Dv, (g + 1) * t.Dv)
            o_ref[0, :, sl] = (acc_ref[:, sl] / l).astype(o_ref.dtype)
            lse_ref[0, g] = m_ref[g] + jnp.log(l)


def _dq_kernel(code_ref, qpos_ref, kpos_ref, qseg_ref, kseg_ref,
               q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_ref,
               *, t: _Tiling):
    code = code_ref[pl.program_id(1)]

    @pl.when((code & _FIRST) != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    allow = _allow(qpos_ref[0], kpos_ref[0], qseg_ref[0], kseg_ref[0],
                   t.window)                                   # (bq, bk)
    k = k_ref[0]
    v = v_ref[0]
    for g in range(t.G):
        q = q_ref[0, :, g * t.D:(g + 1) * t.D]
        do = do_ref[0, :, g * t.Dv:(g + 1) * t.Dv]
        s = jax.lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32)
        s = jnp.where(allow, s * t.scale, NEG_INF)
        p = jnp.exp(s - lse_ref[0, g])
        dp = jax.lax.dot_general(do, v, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0, g])
        sl = slice(g * t.D, (g + 1) * t.D)
        acc_ref[:, sl] = acc_ref[:, sl] + jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    @pl.when((code & _LAST) != 0)
    def _finish():
        dq_ref[0] = (acc_ref[...] * t.scale).astype(dq_ref.dtype)


def _dkv_kernel(code_ref, kpos_ref, qpos_ref, kseg_ref, qseg_ref,
                q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, t: _Tiling):
    # transposed orientation: scores are (bk, bq), so dV = P^T dO and
    # dK = dS^T Q are plain products and lse / di enter as rows
    code = code_ref[pl.program_id(1)]

    @pl.when((code & _FIRST) != 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    allow = _allow(qpos_ref[0], kpos_ref[0], qseg_ref[0], kseg_ref[0],
                   t.window)                                   # (bk, bq)
    k = k_ref[0]
    v = v_ref[0]
    for g in range(t.G):
        q = q_ref[0, :, g * t.D:(g + 1) * t.D]
        do = do_ref[0, :, g * t.Dv:(g + 1) * t.Dv]
        s = jax.lax.dot_general(k, q, _NT,
                                preferred_element_type=jnp.float32)
        s = jnp.where(allow, s * t.scale, NEG_INF)
        p = jnp.exp(s - lse_ref[0, g])                         # (bk, bq)
        dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0, g])
        dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, _NN, preferred_element_type=jnp.float32)

    @pl.when((code & _LAST) != 0)
    def _finish():
        dk_ref[0] = (dk_acc[...] * t.scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# --------------------------------------------------------------------------
# pallas_calls. Every block's last two dims are (8, 128)-divisible or span
# the array (Mosaic's tiling rule): positions / segments go in as (B, S, 1)
# columns or (B, 1, S) rows, lse / di as (B, H, Sq, 1) columns or
# (B, H, 1, Sq) rows, one block of G heads at a time.
# --------------------------------------------------------------------------

def _index_maps(decode):
    """Wrap f(h, b, q tile, kv tile) -> block index as an index map of the
    (kv head, list position) grid that decodes the list entry."""
    def at(f):
        def index_map(h, j, code_ref):
            b, qi, ki = decode(code_ref[j])
            return f(h, b, qi, ki)
        return index_map
    return at


def _forward(t: _Tiling, q, k, v, qpos, kpos, qseg, kseg, codes, n):
    B, Sq, _ = q.shape
    Hkv = k.shape[2] // t.D
    H = Hkv * t.G
    G, D, Dv, bq, bk = t.G, t.D, t.Dv, t.bq, t.bk

    at = _index_maps(t.q_major)
    col_q = pl.BlockSpec((1, bq, 1), at(lambda h, b, qi, ki: (b, qi, 0)))
    row_k = pl.BlockSpec((1, 1, bk), at(lambda h, b, qi, ki: (b, 0, ki)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Hkv, n),
        in_specs=[
            col_q, row_k, col_q, row_k,
            pl.BlockSpec((1, bq, G * D), at(lambda h, b, qi, ki: (b, qi, h))),
            pl.BlockSpec((1, bk, D), at(lambda h, b, qi, ki: (b, ki, h))),
            pl.BlockSpec((1, bk, Dv), at(lambda h, b, qi, ki: (b, ki, h))),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, G * Dv), at(lambda h, b, qi, ki: (b, qi, h))),
            pl.BlockSpec((1, G, bq, 1), at(lambda h, b, qi, ki: (b, h, qi, 0))),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, G * Dv), jnp.float32),   # acc
            pltpu.VMEM((G, bq, 1), jnp.float32),     # running max
            pltpu.VMEM((G, bq, 1), jnp.float32),     # running sum
        ],
    )
    return pl.pallas_call(
        functools.partial(_fwd_kernel, t=t),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Sq, H * Dv), q.dtype),
                   jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32)],
        interpret=t.interpret,
        name="spa_attention_fwd",
    )(codes, qpos[:, :, None], kpos[:, None, :], qseg[:, :, None],
      kseg[:, None, :], q, k, v)


def _backward_dq(t: _Tiling, q, k, v, qpos, kpos, qseg, kseg, do, lse, di,
                 codes, n):
    B, Sq, _ = q.shape
    Hkv = k.shape[2] // t.D
    G, D, Dv, bq, bk = t.G, t.D, t.Dv, t.bq, t.bk

    at = _index_maps(t.q_major)
    col_q = pl.BlockSpec((1, bq, 1), at(lambda h, b, qi, ki: (b, qi, 0)))
    row_k = pl.BlockSpec((1, 1, bk), at(lambda h, b, qi, ki: (b, 0, ki)))
    stat = pl.BlockSpec((1, G, bq, 1), at(lambda h, b, qi, ki: (b, h, qi, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Hkv, n),
        in_specs=[
            col_q, row_k, col_q, row_k,
            pl.BlockSpec((1, bq, G * D), at(lambda h, b, qi, ki: (b, qi, h))),
            pl.BlockSpec((1, bk, D), at(lambda h, b, qi, ki: (b, ki, h))),
            pl.BlockSpec((1, bk, Dv), at(lambda h, b, qi, ki: (b, ki, h))),
            pl.BlockSpec((1, bq, G * Dv), at(lambda h, b, qi, ki: (b, qi, h))),
            stat, stat,
        ],
        out_specs=pl.BlockSpec((1, bq, G * D),
                               at(lambda h, b, qi, ki: (b, qi, h))),
        scratch_shapes=[pltpu.VMEM((bq, G * D), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_dq_kernel, t=t),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=t.interpret,
        name="spa_attention_dq",
    )(codes, qpos[:, :, None], kpos[:, None, :], qseg[:, :, None],
      kseg[:, None, :], q, k, v, do, lse, di)


def _backward_dkv(t: _Tiling, q, k, v, qpos, kpos, qseg, kseg, do, lse, di,
                  codes, n):
    B, Sq, _ = q.shape
    Hkv = k.shape[2] // t.D
    H = Hkv * t.G
    G, D, Dv, bq, bk = t.G, t.D, t.Dv, t.bq, t.bk

    at = _index_maps(t.k_major)
    col_k = pl.BlockSpec((1, bk, 1), at(lambda h, b, qi, ki: (b, ki, 0)))
    row_q = pl.BlockSpec((1, 1, bq), at(lambda h, b, qi, ki: (b, 0, qi)))
    stat = pl.BlockSpec((1, G, 1, bq), at(lambda h, b, qi, ki: (b, h, 0, qi)))
    kv_out = lambda w: pl.BlockSpec((1, bk, w),
                                    at(lambda h, b, qi, ki: (b, ki, h)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Hkv, n),
        in_specs=[
            col_k, row_q, col_k, row_q,
            pl.BlockSpec((1, bq, G * D), at(lambda h, b, qi, ki: (b, qi, h))),
            kv_out(D), kv_out(Dv),
            pl.BlockSpec((1, bq, G * Dv), at(lambda h, b, qi, ki: (b, qi, h))),
            stat, stat,
        ],
        out_specs=[kv_out(D), kv_out(Dv)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, Dv), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_dkv_kernel, t=t),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        interpret=t.interpret,
        name="spa_attention_dkv",
    )(codes, kpos[:, :, None], qpos[:, None, :], kseg[:, :, None],
      qseg[:, None, :], q, k, v, do, lse.reshape(B, H, 1, Sq),
      di.reshape(B, H, 1, Sq))


# --------------------------------------------------------------------------
# custom VJP: the forward saves the per-row log-sum-exp; the backward
# computes di = rowsum(o * do) once, then runs the dQ and dK/dV kernels over
# the same visit map. Positions, segments and the visit lists get no
# gradient.
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _spa(t, q, k, v, qpos, kpos, qseg, kseg, q_codes, q_n, k_codes, k_n):
    return _forward(t, q, k, v, qpos, kpos, qseg, kseg, q_codes, q_n)[0]


def _spa_fwd(t, q, k, v, qpos, kpos, qseg, kseg, q_codes, q_n, k_codes, k_n):
    o, lse = _forward(t, q, k, v, qpos, kpos, qseg, kseg, q_codes, q_n)
    return o, (q, k, v, qpos, kpos, qseg, kseg, q_codes, q_n, k_codes, k_n,
               o, lse)


def _spa_bwd(t, res, do):
    q, k, v, qpos, kpos, qseg, kseg, q_codes, q_n, k_codes, k_n, o, lse = res
    B, Sq, HDv = o.shape
    H = HDv // t.Dv
    di = (o.astype(jnp.float32) * do.astype(jnp.float32)).reshape(
        B, Sq, H, t.Dv).sum(-1)
    di = jnp.moveaxis(di, 2, 1)[..., None]                     # (B, H, Sq, 1)
    dq = _backward_dq(t, q, k, v, qpos, kpos, qseg, kseg, do, lse, di,
                      q_codes, q_n)
    dk, dv = _backward_dkv(t, q, k, v, qpos, kpos, qseg, kseg, do, lse, di,
                           k_codes, k_n)
    return (dq, dk, dv) + (None,) * 8


_spa.defvjp(_spa_fwd, _spa_bwd)


def _tiles(q_pos, kv_pos, q_seg, kv_seg, bq, bk, window):
    """Pad positions / segments to whole tiles (query padding sees the
    prompt's first tokens at most, key padding is never seen) and return
    them with the visit map."""
    pad_q, pad_k = (-q_pos.shape[1]) % bq, (-kv_pos.shape[1]) % bk
    if pad_q:
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)))
        q_seg = jnp.pad(q_seg, ((0, 0), (0, pad_q)), constant_values=-1)
    if pad_k:
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad_k)), constant_values=2**30)
        kv_seg = jnp.pad(kv_seg, ((0, 0), (0, pad_k)), constant_values=-2)
    live = block_map(q_pos, kv_pos, q_seg, kv_seg, bq, bk, window)
    return (q_pos, kv_pos, q_seg, kv_seg), _visit_map(live)


@functools.partial(jax.jit, static_argnames=("window", "block_q", "block_k"))
def tile_share(q_pos, kv_pos, q_seg, kv_seg, *,
               window: Optional[int] = None, block_q: int = BLOCK_Q,
               block_k: int = BLOCK_K):
    """Share of the (nq x nk) tiles the kernel computes for these
    positions and segments: its visit map's mean, a scalar f32."""
    bq, bk = min(block_q, q_pos.shape[1]), min(block_k, kv_pos.shape[1])
    _, visit = _tiles(q_pos, kv_pos, q_seg, kv_seg, bq, bk, window)
    return visit.mean(dtype=jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("scale", "window", "block_q", "block_k",
                              "interpret"))
def spa_attention(q, k, v, q_pos, kv_pos, q_seg, kv_seg, *,
                  scale: Optional[float] = None,
                  window: Optional[int] = None,
                  block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                  interpret: bool = False):
    """Shared-prompt flash attention, differentiable in q, k and v.

    q: (B, Sq, H, D); k/v: (B, Skv, Hkv, Dv) with H % Hkv == 0 (GQA: each
    grid step serves the H // Hkv query heads of one KV head). pos/seg:
    (B, S) int32. Returns (B, Sq, H, Dv) in q.dtype.
    """
    B, Sq, H, D = q.shape
    _, Skv, Hkv, Dv = v.shape
    scale = D ** -0.5 if scale is None else scale
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    pad_q, pad_k = (-Sq) % bq, (-Skv) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    (q_pos, kv_pos, q_seg, kv_seg), visit = _tiles(q_pos, kv_pos, q_seg,
                                                   kv_seg, bq, bk, window)
    Sq_p, Skv_p = Sq + pad_q, Skv + pad_k
    t = _Tiling(G=H // Hkv, D=D, Dv=Dv, bq=bq, bk=bk, nq=Sq_p // bq,
                nk=Skv_p // bk, scale=scale, window=window,
                interpret=interpret)
    q_codes, q_n = _visit_list(visit)
    k_codes, k_n = _visit_list(jnp.swapaxes(visit, 1, 2))
    out = _spa(t, q.reshape(B, Sq_p, H * D), k.reshape(B, Skv_p, Hkv * D),
               v.reshape(B, Skv_p, Hkv * Dv), q_pos, kv_pos, q_seg, kv_seg,
               q_codes, q_n, k_codes, k_n)
    return out.reshape(B, Sq_p, H, Dv)[:, :Sq]
