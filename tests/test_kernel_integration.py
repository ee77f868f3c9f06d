"""Model-level Pallas kernel integration: cfg.use_pallas_attention swaps the
pure-JAX chunked path for the fused kernel (interpret mode on CPU) — the
full forward must agree, including SPA-packed inputs — and the training
path takes the kernel by the platform rule, forward and backward."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.core.queue import RolloutGroup
from repro.core.spa import pack_spa
from repro.models import forward_hidden, init
from repro.rl.grpo import group_advantages, jaxify


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config(get_config("llama3.2-3b"))
    params = init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_pallas_path_matches_chunked_forward(setup):
    cfg, params = setup
    cfg_k = dataclasses.replace(cfg, use_pallas_attention=True)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 3,
                              cfg.vocab_size)
    h_ref, _, _, _ = forward_hidden(params, cfg, toks)
    h_ker, _, _, _ = forward_hidden(params, cfg_k, toks)
    np.testing.assert_allclose(np.asarray(h_ker), np.asarray(h_ref),
                               atol=2e-4, rtol=2e-4)


def test_pallas_path_matches_on_spa_packed_rows(setup):
    """The kernel's raison d'etre: SPA-packed segment masks."""
    cfg, params = setup
    cfg_k = dataclasses.replace(cfg, use_pallas_attention=True)
    rng = np.random.RandomState(0)
    g = RolloutGroup(
        uid=0, prompt_ids=rng.randint(3, 250, size=(12,)).astype(np.int32),
        response_ids=rng.randint(3, 250, size=(3, 6)).astype(np.int32),
        response_len=np.full((3,), 6, np.int32),
        rewards=np.asarray([1.0, 0.0, 1.0], np.float32), weight_version=0)
    adv = np.asarray(group_advantages(jnp.asarray(g.rewards)))
    mb = pack_spa(g, adv, 12, 6, responses_per_row=3)
    kw = dict(positions=jnp.asarray(mb.positions),
              segments=jnp.asarray(mb.segments))
    toks = jnp.asarray(mb.tokens)
    h_ref, _, _, _ = forward_hidden(params, cfg, toks, **kw)
    h_ker, _, _, _ = forward_hidden(params, cfg_k, toks, **kw)
    np.testing.assert_allclose(np.asarray(h_ker), np.asarray(h_ref),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("arch", ["llama3.2-3b", "deepseek-v2-lite-16b"])
def test_pallas_paged_decode_matches_pure(arch):
    """One paged decode step through the attention block with
    cfg.use_pallas_attention on vs off (GQA K/V pages and MLA latent
    pages): the flash-decode kernel wrapper and the pure-JAX gather path
    must agree on the same page pool."""
    from repro.models.attention import (PagedCacheBackend, attention,
                                        init_attention)
    cfg = reduced_config(get_config(arch))
    cfg_k = dataclasses.replace(cfg, use_pallas_attention=True)
    rng = np.random.RandomState(7)
    params = init_attention(jax.random.PRNGKey(11), cfg, jnp.float32)
    P, page, n_max, B = 6, 4, 3, 2
    be = PagedCacheBackend(cfg, page)
    cache = be.init(P, jnp.float32)
    # fill pages 2..5 with a fake history at positions 0..7 per row
    cache = {k: (jnp.asarray(rng.randn(*v.shape), jnp.float32)
                 if v.dtype != jnp.int32 else v) for k, v in cache.items()}
    pos = np.full((P, page), 2 ** 30, np.int64)
    for j, p0 in ((2, 0), (3, 4), (4, 0), (5, 4)):
        pos[j] = np.arange(p0, p0 + page)
    cache["pos_pages"] = jnp.asarray(pos, jnp.int32)
    table = jnp.asarray([[2, 3, 0], [4, 5, 0]], jnp.int32)
    x = jnp.asarray(rng.randn(B, 1, cfg.d_model), jnp.float32)
    positions = jnp.full((B, 1), 8, jnp.int32)
    segments = jnp.zeros((B, 1), jnp.int32)
    wslot = jnp.asarray([3 * page + 0, 5 * page + 0], jnp.int32)
    o_ref, c_ref = attention(params, cfg, x, positions, segments,
                             cache=cache, cache_offset=wslot,
                             page_table=table)
    o_ker, c_ker = attention(params, cfg_k, x, positions, segments,
                             cache=cache, cache_offset=wslot,
                             page_table=table)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               atol=2e-4, rtol=2e-4)
    for k in c_ref:
        np.testing.assert_allclose(np.asarray(c_ker[k]),
                                   np.asarray(c_ref[k]), atol=1e-6)


# --------------------------------------------------------------------------
# training through the kernel: the dispatch rule (models/attention.py
# spa_kernel_applies) is forced by steering its platform check to "tpu";
# the kernel itself then runs in interpret mode on the CPU
# --------------------------------------------------------------------------

def _spa_group(prompt_len=40, resp=24, G=3):
    rng = np.random.RandomState(1)
    lens = np.asarray([resp, resp - 7, resp - 3][:G], np.int32)
    return RolloutGroup(
        uid=0, prompt_ids=rng.randint(3, 250, size=(prompt_len,)).astype(
            np.int32),
        response_ids=rng.randint(3, 250, size=(G, resp)).astype(np.int32),
        response_len=lens,
        response_logprobs=-rng.rand(G, resp).astype(np.float32),
        rewards=np.asarray([1.0, 0.0, 1.0][:G], np.float32),
        weight_version=0)


@pytest.fixture(scope="module")
def train_setup():
    from repro.configs.base import RLConfig
    # head_dim 128 fills the kernel's lanes, so the rule can pick it
    cfg = dataclasses.replace(reduced_config(get_config("llama3.2-3b")),
                              head_dim=128, attn_chunk_size=16)
    params = init(jax.random.PRNGKey(0), cfg)
    rl = RLConfig(max_prompt_len=40, max_response_len=24, group_size=3)
    g = _spa_group()
    adv = np.asarray(group_advantages(jnp.asarray(g.rewards)))
    mb = jaxify(pack_spa(g, adv, 40, 24, responses_per_row=3))
    return cfg, rl, params, mb


def _on_tpu(monkeypatch):
    from repro.models import attention as attn_mod
    monkeypatch.setattr(attn_mod, "_platform", lambda: "tpu")


def _close(a, b, rel):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
        assert np.abs(x - y).max() <= rel * max(np.abs(y).max(), 1e-6)


@pytest.mark.parametrize("step", ["captured", "trimodel"])
def test_grad_step_through_kernel_matches_chunked(train_setup, monkeypatch,
                                                  step):
    """The captured grad step (one reference forward) and the tri-model
    step (old + ref under vmap) give the chunked path's loss and gradients
    on an SPA-packed group when the rule sends attention to the kernel."""
    from repro.models.attention import spa_kernel_applies
    from repro.rl.grpo import make_grad_step, make_grad_step_captured
    cfg, rl, params, mb = train_setup
    make = make_grad_step_captured if step == "captured" else make_grad_step
    old = jax.tree.map(lambda a: a * 0.999, params)
    g_ref, m_ref = make(cfg, rl)(params, old, params, mb)
    rows = mb.positions.shape
    assert not spa_kernel_applies(cfg, *rows)   # the CPU picks the scan
    assert float(m_ref["attn_tile_share"]) == 1.0
    _on_tpu(monkeypatch)
    assert spa_kernel_applies(cfg, *rows)
    calls = []
    import repro.kernels.ops as kops
    real = kops.spa_attention
    monkeypatch.setattr(kops, "spa_attention",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    g_ker, m_ker = make(cfg, rl)(params, old, params, mb)
    assert calls, "the dispatch rule did not take the kernel"
    np.testing.assert_allclose(float(m_ker["loss"]), float(m_ref["loss"]),
                               rtol=1e-5, atol=1e-6)
    _close(g_ker, g_ref, 2e-4)
    from repro.kernels.ops import spa_tile_share
    share = float(spa_tile_share(mb.positions, mb.positions, mb.segments,
                                 mb.segments))
    assert float(m_ker["attn_tile_share"]) == share


def test_tile_share_reads_the_visit_map():
    """attn_tile_share is the kernel's visit map's mean: one tile per
    128-token block pair, dead response x response tiles left out."""
    from repro.kernels.ops import spa_tile_share
    Lp, Lr, K = 128, 128, 3
    pos = np.concatenate([np.arange(Lp)] + [np.arange(Lp, Lp + Lr)] * K)
    seg = np.concatenate([np.zeros(Lp)] + [np.full(Lr, k + 1)
                                           for k in range(K)])
    pos, seg = (jnp.asarray(a[None], jnp.int32) for a in (pos, seg))
    # prompt: 1 tile; each response: the prompt's tile and its own
    assert float(spa_tile_share(pos, pos, seg, seg)) == (1 + 2 * K) / 16


def test_dispatch_rule_keeps_chunked_off_tpu_and_under_a_mesh(monkeypatch):
    """Off the TPU, under a multi-device mesh, with a head dim the kernel's
    lanes do not fit, or with a visit list too long for its scalar memory,
    GQA keeps chunked_attention."""
    from jax.sharding import AbstractMesh
    from repro.models import attention as attn_mod
    from repro.sharding.specs import current_mesh, set_mesh
    cfg = dataclasses.replace(reduced_config(get_config("llama3.2-3b")),
                              head_dim=128)
    applies = attn_mod.spa_kernel_applies
    assert not applies(cfg, 1, 4096)                       # CPU backend
    _on_tpu(monkeypatch)
    assert applies(cfg, 1, 4096)
    assert not applies(dataclasses.replace(cfg, head_dim=64), 1, 4096)
    assert applies(cfg, 1, 32768) and not applies(cfg, 1, 65536)
    prev = current_mesh()
    set_mesh(AbstractMesh((4,), ("data",)))
    try:
        assert not applies(cfg, 1, 4096)
    finally:
        set_mesh(prev)
    # and off the TPU a training forward runs the scan, not the kernel
    monkeypatch.setattr(attn_mod, "_platform", lambda: "cpu")
    import repro.kernels.ops as kops
    monkeypatch.setattr(kops, "spa_attention",
                        lambda *a, **kw: pytest.fail("kernel taken on CPU"))
    params = init(jax.random.PRNGKey(0), cfg)
    toks = jnp.full((1, 16), 5, jnp.int32)
    forward_hidden(params, cfg, toks)
