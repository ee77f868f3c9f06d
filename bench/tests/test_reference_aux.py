"""The reference's hook for an architecture's auxiliary loss, on a dense
model of CPU size: a module with ``aux_loss`` adds ``aux_coef * aux / N``
for each of a micro-batch's samples, loss and gradient, as the program adds
the term to a micro-batch's loss and weighs its gradient by its samples;
``dense_gqa``, which has no hook, gives the loss and gradient of the plain
GRPO loss bit for bit."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.reference import dense_gqa
from bench.reference import grpo as ref_grpo
from bench.reference.numerics import F32

M = dict(d=16, H=2, Hkv=1, hd=8, ff=32, V=40, L=1, eps=1e-5, theta=1e4,
         dtype="float32")
TRAINER = dict(clip_eps_low=0.2, clip_eps_high=0.2, kl_coef=0.02)
LP, LR, N = 5, 4, 4
AUX_COEF = 0.25


def _aux(num, m, params, tokens):
    """A term that depends on the weights, so that it moves the gradient."""
    e = params["embed"]["embedding"][tokens].astype(jnp.float32)
    return jnp.sum(e * e) * jnp.sum(params["final_norm"]["scale"])


# a module of its own, as a configuration's reference is (and hashable, as
# the reference's compiled programs take it as a static argument)
WITH_AUX = types.ModuleType("with_aux")
WITH_AUX.next_token_logprobs = dense_gqa.next_token_logprobs
WITH_AUX.aux_loss = _aux


def _inputs(seed):
    rng = np.random.default_rng(seed)
    policy = weights.make(dense_gqa.layout(M), seed)
    ref = weights.make(dense_gqa.layout(M), seed + 1)
    tokens = rng.integers(3, M["V"], size=(2, LP + LR)).astype(np.int32)
    lens = np.asarray([LR, LR - 1], np.int32)
    adv = rng.normal(size=2).astype(np.float32)
    return policy, ref, jnp.asarray(tokens), jnp.asarray(lens), jnp.asarray(
        adv)


def _micro(model, m, policy, ref, tokens, lens, adv, same_ref=False):
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), policy)
    loss, lp, g = ref_grpo._micro(
        model, F32, tuple(sorted(m.items())), tuple(sorted(TRAINER.items())),
        LP, LR, same_ref, policy, ref, tokens, lens, adv, jnp.float32(N),
        zeros)
    return float(loss), g


def _plain_loss(p, ref, tokens, lens, adv, same_ref):
    """The GRPO loss share of a micro-batch as the reference computed it
    before it took an auxiliary term."""
    resp = lambda q: jax.vmap(lambda t: dense_gqa.next_token_logprobs(
        F32, M, q, t))(tokens)[:, LP - 1:LP - 1 + LR]
    mask = (jnp.arange(LR)[None, :] < lens[:, None]).astype(jnp.float32)
    w = mask / jnp.maximum(lens[:, None], 1).astype(jnp.float32)
    lp_ref = None if same_ref else jax.lax.stop_gradient(resp(ref))
    lp = resp(p)
    rho = jnp.exp(lp - jax.lax.stop_gradient(lp))
    a = adv[:, None]
    surr = jnp.minimum(rho * a, jnp.clip(rho, 0.8, 1.2) * a)
    d = (jax.lax.stop_gradient(lp) if same_ref else lp_ref) - lp
    kl = jnp.exp(d) - d - 1.0
    return -jnp.sum((surr - TRAINER["kl_coef"] * kl) * w) / N


@pytest.mark.parametrize("same_ref", [True, False])
def test_dense_without_hook_is_the_plain_loss(same_ref):
    policy, ref, tokens, lens, adv = _inputs(3)
    loss, g = _micro(dense_gqa, M, policy, ref, tokens, lens, adv, same_ref)
    want, g_want = jax.jit(jax.value_and_grad(_plain_loss),
                           static_argnums=5)(policy, ref, tokens, lens, adv,
                                             same_ref)
    assert loss == float(want)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_aux_term_is_added_per_sample_over_the_iteration():
    policy, ref, tokens, lens, adv = _inputs(4)
    m = dict(M, aux_coef=AUX_COEF)
    base, g0 = _micro(dense_gqa, M, policy, ref, tokens, lens, adv)
    loss, g1 = _micro(WITH_AUX, m, policy, ref, tokens, lens, adv)

    def share(p):
        aux = jax.vmap(lambda t: _aux(F32, m, p, t))(tokens)
        return AUX_COEF * jnp.sum(aux) / N
    want, g_aux = jax.value_and_grad(share)(policy)
    assert float(want) > 1e-3
    np.testing.assert_allclose(loss - base, float(want), rtol=1e-5)
    moved = 0
    for a, b, c in zip(jax.tree.leaves(g1), jax.tree.leaves(g0),
                       jax.tree.leaves(g_aux)):
        np.testing.assert_allclose(np.asarray(a) - np.asarray(b),
                                   np.asarray(c), rtol=1e-4, atol=1e-7)
        moved += bool(np.any(np.asarray(c) != 0))
    assert moved == 2                   # the embedding and the final norm


def test_iteration_loss_shifts_by_the_mean_aux():
    """Over a whole iteration (two micro-batches of two samples) the loss
    moves by ``aux_coef`` times the mean of the samples' terms, as it does
    in the program's N micro-batches of one sample, each weighed 1/N."""
    policy, _, _, _, _ = _inputs(5)
    rng = np.random.default_rng(5)
    group = dict(prompt_ids=rng.integers(3, M["V"], LP).astype(np.int32),
                 response_ids=rng.integers(3, M["V"], (N, LR)).astype(
                     np.int32),
                 response_len=np.asarray([LR, LR, LR - 1, 2]))
    follow = lambda model, m: ref_grpo.follow(
        model, F32, m, dict(TRAINER, adv_eps=1e-4, grad_clip=1.0,
                            adam_b1=0.9, adam_b2=0.95, adam_eps=1e-8,
                            weight_decay=0.0, learning_rate=1e-3),
        policy, [[group]], prompt_len=LP, resp_len=LR)
    base = follow(dense_gqa, M)["loss"][0]
    loss = follow(WITH_AUX, dict(M, aux_coef=AUX_COEF))["loss"][0]
    toks, _, _, _ = ref_grpo.iteration_batch([group], LP, LR, 1e-4)
    aux = [float(_aux(F32, M, policy, jnp.asarray(t))) for t in toks]
    np.testing.assert_allclose(loss - base, AUX_COEF * np.mean(aux),
                               rtol=1e-5)
