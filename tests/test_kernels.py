"""Pallas kernel sweeps: shapes x dtypes x mask variants, interpret mode on
CPU, assert_allclose against the pure-jnp oracles in repro.kernels.ref.

Also checks the structural property that makes spa_attention the paper's
K-fold win: the block map really drops the response_i x response_j tiles.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ref import (decode_attention_ref, spa_attention_ref,
                               verify_attention_ref)
from repro.kernels.spa_attention import block_map, spa_attention
from repro.kernels.decode_attention import (ROW_BLOCK, decode_attention,
                                            verify_attention)

INTERP = dict(interpret=True)


def spa_layout(key, B, Lp, Lr, K, H, Hkv, D, dtype, pad_tail=0):
    """Build a shared-prompt packed row: [prompt, r_1..r_K] + optional pad."""
    S = Lp + K * Lr + pad_tail
    pos = np.zeros((B, S), np.int32)
    seg = np.full((B, S), -1, np.int32)
    pos[:, :Lp] = np.arange(Lp)
    seg[:, :Lp] = 0
    off = Lp
    for k in range(K):
        pos[:, off:off + Lr] = np.arange(Lp, Lp + Lr)
        seg[:, off:off + Lr] = k + 1
        off += Lr
    if pad_tail:
        pos[:, off:] = 2 ** 30 - 1   # invalid-pad: masked by causal rule
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), dtype)
    k = jax.random.normal(kk, (B, S, Hkv, D), dtype)
    v = jax.random.normal(kv, (B, S, Hkv, D), dtype)
    return q, k, v, jnp.asarray(pos), jnp.asarray(seg)


TOL = {jnp.float32: dict(atol=2e-5, rtol=2e-5),
       jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Lp,Lr,K,H,Hkv,D,bq,bk",
    [
        (1, 32, 16, 2, 2, 2, 64, 16, 16),     # MHA, tiny tiles
        (2, 40, 24, 3, 4, 2, 64, 32, 32),     # GQA 2:1, non-divisible -> pad
        (1, 64, 32, 4, 8, 2, 128, 64, 64),    # GQA 4:1, wide head
        (1, 17, 9, 2, 2, 1, 32, 16, 16),      # ragged lengths -> padding path
    ])
def test_spa_kernel_matches_ref(dtype, B, Lp, Lr, K, H, Hkv, D, bq, bk):
    q, k, v, pos, seg = spa_layout(jax.random.PRNGKey(0), B, Lp, Lr, K,
                                   H, Hkv, D, dtype)
    got = spa_attention(q, k, v, pos, pos, seg, seg,
                        block_q=bq, block_k=bk, **INTERP)
    want = spa_attention_ref(q, k, v, pos, pos, seg, seg)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("window", [8, 32, None])
def test_spa_kernel_window(window):
    q, k, v, pos, seg = spa_layout(jax.random.PRNGKey(1), 2, 32, 16, 2,
                                   4, 2, 64, jnp.float32)
    got = spa_attention(q, k, v, pos, pos, seg, seg, window=window,
                        block_q=16, block_k=16, **INTERP)
    want = spa_attention_ref(q, k, v, pos, pos, seg, seg, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_spa_kernel_with_padding_tail():
    """Rows padded past the packed content (seg=-1, huge pos) must not leak
    into real outputs."""
    q, k, v, pos, seg = spa_layout(jax.random.PRNGKey(2), 2, 24, 8, 2,
                                   2, 2, 32, jnp.float32, pad_tail=24)
    got = spa_attention(q, k, v, pos, pos, seg, seg,
                        block_q=16, block_k=16, **INTERP)
    want = spa_attention_ref(q, k, v, pos, pos, seg, seg)
    real = 24 + 2 * 8
    np.testing.assert_allclose(np.asarray(got)[:, :real],
                               np.asarray(want)[:, :real],
                               atol=2e-5, rtol=2e-5)


def _packed_rows(align, H, Hkv, D, dtype):
    """One GRPO group packed by core/spa.py (3 responses of 40 after a
    65-token prompt), with random q/k/v over its length."""
    from repro.core.queue import RolloutGroup
    from repro.core.spa import pack_spa
    rng = np.random.RandomState(5)
    g = RolloutGroup(
        uid=0, prompt_ids=rng.randint(3, 250, size=(65,)).astype(np.int32),
        response_ids=rng.randint(3, 250, size=(3, 40)).astype(np.int32),
        response_len=np.asarray([40, 23, 31], np.int32),
        rewards=np.asarray([1.0, 0.0, 1.0], np.float32), weight_version=0)
    mb = pack_spa(g, np.zeros(3, np.float32), 65, 40, responses_per_row=3,
                  align=align)
    pos, seg = jnp.asarray(mb.positions), jnp.asarray(mb.segments)
    S = pos.shape[1]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(6), 3)
    return (jax.random.normal(kq, (1, S, H, D), dtype),
            jax.random.normal(kk, (1, S, Hkv, D), dtype),
            jax.random.normal(kv, (1, S, Hkv, D), dtype), pos, pos, seg, seg)


def _layout_case(case, dtype):
    """(q, k, v, q_pos, kv_pos, q_seg, kv_seg, window, bq, bk) per case."""
    if case == "mha":
        q, k, v, pos, seg = spa_layout(jax.random.PRNGKey(0), 1, 32, 16, 2,
                                       2, 2, 64, dtype)
        return q, k, v, pos, pos, seg, seg, None, 16, 16
    if case == "gqa":
        q, k, v, pos, seg = spa_layout(jax.random.PRNGKey(1), 2, 40, 24, 3,
                                       4, 2, 64, dtype)
        return q, k, v, pos, pos, seg, seg, None, 32, 32
    if case == "window":
        q, k, v, pos, seg = spa_layout(jax.random.PRNGKey(2), 1, 32, 16, 2,
                                       4, 1, 32, dtype)
        return q, k, v, pos, pos, seg, seg, 12, 16, 16
    if case == "pad_tail":
        q, k, v, pos, seg = spa_layout(jax.random.PRNGKey(3), 1, 17, 9, 2,
                                       2, 1, 32, dtype, pad_tail=21)
        return q, k, v, pos, pos, seg, seg, None, 16, 16
    if case in ("align0", "align128"):
        args = _packed_rows(0 if case == "align0" else 128, 4, 2, 32, dtype)
        return args + ((None, 64, 64) if case == "align0"
                       else (None, 128, 128))
    # prompt_queries: the queries are the prompt alone, so no query tile
    # has a live key tile past it and every response key tile is visited
    # only to write its zero dK / dV
    q, k, v, pos, seg = spa_layout(jax.random.PRNGKey(4), 1, 32, 16, 3,
                                   2, 2, 32, dtype)
    return (q[:, :32], k, v, pos[:, :32], pos, seg[:, :32], seg, None,
            16, 16)


@pytest.mark.parametrize(
    "case,dtype",
    [(c, jnp.float32) for c in ("mha", "gqa", "window", "pad_tail", "align0",
                                "align128", "prompt_queries")]
    + [(c, jnp.bfloat16) for c in ("mha", "gqa", "align128")])
def test_spa_kernel_gradients(case, dtype):
    """jax.grad through the kernel's custom VJP (the Pallas dQ and dK/dV
    kernels) against autodiff of chunked_attention and of the dense f32
    oracle, for dq, dk and dv."""
    from repro.models.attention import chunked_attention
    q, k, v, qp, kp, qs, ks, window, bq, bk = _layout_case(case, dtype)
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape[:3] + v.shape[3:])

    def grads(attn):
        def loss(q, k, v):
            o = attn(q, k, v, qp, kp, qs, ks, window=window)
            return (o.astype(jnp.float32) * w).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    got = grads(lambda *a, **kw: spa_attention(*a, block_q=bq, block_k=bk,
                                               **kw, **INTERP))
    wants = (grads(spa_attention_ref),
             grads(lambda *a, **kw: chunked_attention(*a, chunk_size=16,
                                                      **kw)))
    rel = 2e-5 if dtype == jnp.float32 else 3e-2
    for want in wants:
        for name, g, r in zip("qkv", got, want):
            g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
            scale = np.abs(r).max()
            assert np.abs(g - r).max() <= rel * scale, (name, case)
    if case == "prompt_queries":
        # response keys are seen by no query: their gradient is exactly 0
        assert not np.asarray(got[1], np.float32)[:, 32:].any()
        assert not np.asarray(got[2], np.float32)[:, 32:].any()


def test_spa_equals_per_sample_attention():
    """The packed SPA output at response k's rows equals standard causal
    attention over [prompt; response_k] alone — the paper's exactness claim
    at the kernel level."""
    B, Lp, Lr, K, H, D = 1, 32, 16, 3, 2, 64
    key = jax.random.PRNGKey(3)
    q, k, v, pos, seg = spa_layout(key, B, Lp, Lr, K, H, H, D, jnp.float32)
    packed = spa_attention(q, k, v, pos, pos, seg, seg,
                           block_q=16, block_k=16, **INTERP)
    for j in range(K):
        sl = np.r_[0:Lp, Lp + j * Lr: Lp + (j + 1) * Lr]
        qj, kj, vj = q[:, sl], k[:, sl], v[:, sl]
        pj = pos[:, sl]
        zj = jnp.zeros_like(pj)
        want = spa_attention_ref(qj, kj, vj, pj, pj, zj, zj)  # plain causal
        np.testing.assert_allclose(
            np.asarray(packed[:, Lp + j * Lr: Lp + (j + 1) * Lr]),
            np.asarray(want[:, Lp:]), atol=2e-5, rtol=2e-5)


def test_block_map_sparsity_structure():
    """Tiles fully inside response_i x response_j (i != j) must be dead, and
    the live fraction must approach Eq. 5's rho for Lp >> Lr."""
    B, Lp, Lr, K = 1, 256, 64, 4
    S = Lp + K * Lr
    pos = np.zeros((B, S), np.int32)
    seg = np.zeros((B, S), np.int32)
    pos[:, :Lp] = np.arange(Lp)
    off = Lp
    for k in range(K):
        pos[:, off:off + Lr] = np.arange(Lp, Lp + Lr)
        seg[:, off:off + Lr] = k + 1
        off += Lr
    bq = bk = 64
    bm = np.asarray(block_map(jnp.asarray(pos), jnp.asarray(pos),
                              jnp.asarray(seg), jnp.asarray(seg), bq, bk))
    nq = S // bq
    # response_i x response_j dead tiles: query tile in resp i, kv tile in
    # resp j != i (both fully inside one response since Lr == tile size)
    for i in range(K):
        for j in range(K):
            qt = (Lp + i * Lr) // bq
            kt = (Lp + j * Lr) // bk
            if i == j:
                assert bm[0, qt, kt] == 1
            else:
                assert bm[0, qt, kt] == 0, (i, j)
    # kv tiles in the shared prompt are live for all later query tiles
    assert bm[0, nq - 1, 0] == 1
    live_frac = bm.mean()
    # dense causal would be ~0.56; SPA structure must prune well below it
    dense_causal = np.tril(np.ones((nq, nq))).mean()
    assert live_frac < dense_causal


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,L,H,Hkv,D,bL",
    [
        (2, 64, 4, 4, 64, 32),     # MHA
        (2, 100, 8, 2, 64, 32),    # GQA 4:1, ragged L -> pad
        (1, 256, 8, 1, 128, 64),   # MQA
        (4, 33, 2, 2, 32, 16),     # tiny ragged
    ])
def test_decode_kernel_matches_ref(dtype, B, L, H, Hkv, D, bL):
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, D), dtype)
    k = jax.random.normal(kk, (B, L, Hkv, D), dtype)
    v = jax.random.normal(kv, (B, L, Hkv, D), dtype)
    kv_pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    q_pos = jnp.full((B,), L, jnp.int32)
    got = decode_attention(q, k, v, kv_pos, q_pos, block_l=bL, **INTERP)
    want = decode_attention_ref(q, k, v, kv_pos, q_pos)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("window", [16, 64])
def test_decode_kernel_window_and_invalid_slots(window):
    """Ring-buffer semantics: some slots carry INVALID pos (2**30) and the
    window must exclude old positions."""
    B, L, H, Hkv, D = 2, 96, 4, 2, 64
    key = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, L, Hkv, D), jnp.float32)
    v = jax.random.normal(kv, (B, L, Hkv, D), jnp.float32)
    kv_pos = np.broadcast_to(np.arange(L, dtype=np.int32), (B, L)).copy()
    kv_pos[:, 70:] = 2 ** 30    # unwritten ring slots
    kv_pos = jnp.asarray(kv_pos)
    q_pos = jnp.full((B,), 70, jnp.int32)
    got = decode_attention(q, k, v, kv_pos, q_pos, window=window,
                           block_l=32, **INTERP)
    want = decode_attention_ref(q, k, v, kv_pos, q_pos, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_decode_kernel_matches_spa_kernel_single_token():
    """Cross-kernel consistency: decoding one token with decode_attention
    equals running spa_attention with Sq=1."""
    B, L, H, Hkv, D = 2, 64, 4, 2, 64
    key = jax.random.PRNGKey(9)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, 1, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, L, Hkv, D), jnp.float32)
    v = jax.random.normal(kv, (B, L, Hkv, D), jnp.float32)
    kv_pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    q_pos1 = jnp.full((B, 1), L, jnp.int32)
    zq = jnp.zeros((B, 1), jnp.int32)
    zk = jnp.zeros((B, L), jnp.int32)
    a = spa_attention(q, k, v, q_pos1, kv_pos, zq, zk,
                      block_q=16, block_k=16, **INTERP)
    b = decode_attention(q[:, 0], k, v, kv_pos, q_pos1[:, 0],
                         block_l=32, **INTERP)
    np.testing.assert_allclose(np.asarray(a[:, 0]), np.asarray(b),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("S", [128, 70])
def test_verify_kernel_blocks_long_query_rows(S):
    """A suffix-prefill-sized block (S * G query rows beyond ROW_BLOCK, a
    ragged last row block when S = 70) matches the dense oracle."""
    B, L, H, Hkv, D = 2, 160, 8, 2, 32
    assert S * (H // Hkv) > ROW_BLOCK
    key = jax.random.PRNGKey(11)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, L, Hkv, D), jnp.float32)
    v = jax.random.normal(kv, (B, L, Hkv, D), jnp.float32)
    kv_pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    q_pos = jnp.broadcast_to(jnp.arange(L - S, L, dtype=jnp.int32), (B, S))
    got = verify_attention(q, k, v, kv_pos, q_pos, block_l=32, **INTERP)
    want = verify_attention_ref(q, k, v, kv_pos, q_pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
