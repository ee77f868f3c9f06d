"""Plain reference of GRPO training on recorded rollouts (paper Eq. 1):

* rewards: the benchmark's stand-in, the parity of each response's token
  sum (a random-weight policy never solves a task, and tied rewards give a
  zero gradient);
* advantages: per group, (r - mean) / (std + eps);
* loss: per sample the token mean over its response of
  min(rho A, clip(rho, 1-eps_l, 1+eps_h) A) - beta KL_k3(ref || policy),
  averaged over the iteration's samples and negated. The run is on-policy
  (the old policy is the policy), so rho = exp(logp - stop_gradient(logp)).
  Where the architecture's module has ``aux_loss``, each sample adds
  ``aux_coef * aux_loss / N``: the program adds the term to each
  micro-batch's loss and weighs the micro-batch's gradient by its samples
  over the iteration's N, and a micro-batch of one sample weighs 1/N;
* update: AdamW with global-norm clipping, moments in float32, parameters
  stored in the configuration's dtype.

Each sample is its own unpacked sequence, prompt then response, with no
packing, no cache and no captured values.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def parity_rewards(responses) -> np.ndarray:
    return np.asarray([float(int(np.sum(r.astype(np.int64))) % 2)
                       for r in responses], np.float32)


def advantages(rewards: np.ndarray, eps: float) -> np.ndarray:
    r = rewards.astype(np.float64)
    return ((r - r.mean()) / (r.std() + eps)).astype(np.float32)


def iteration_batch(groups, prompt_len: int, resp_len: int, adv_eps: float):
    """Rollout groups -> per-sample arrays: tokens (N, Lp+Lr) right-padded,
    response lengths, advantages, and the response of each sample."""
    toks, lens, advs, resps = [], [], [], []
    for g in groups:
        rs = [np.asarray(g["response_ids"][j, :int(g["response_len"][j])])
              for j in range(len(g["response_len"]))]
        a = advantages(parity_rewards(rs), adv_eps)
        for r, aj in zip(rs, a):
            t = np.zeros((prompt_len + resp_len,), np.int32)
            t[:prompt_len] = g["prompt_ids"]
            t[prompt_len:prompt_len + len(r)] = r
            toks.append(t)
            lens.append(len(r))
            advs.append(aj)
            resps.append(r)
    return (np.stack(toks), np.asarray(lens, np.int32),
            np.asarray(advs, np.float32), resps)


def _response_logprobs(model, num, m, params, tokens, Lp, Lr):
    """(B, Lp+Lr) -> (B, Lr) log p(response token j)."""
    lp = jax.vmap(lambda t: model.next_token_logprobs(num, m, params, t))(
        tokens)
    return lp[:, Lp - 1:Lp - 1 + Lr]


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6), donate_argnums=(13,))
def _micro(model, num, mk, tk, Lp, Lr, same_ref, policy, ref, tokens, lens,
           adv, n_total, gsum):
    """Add the float32 gradient of this micro-batch's share of -J (and of
    the auxiliary term, where the model has one) to ``gsum``; return the
    loss share, the response logprobs and the sum. The gradient is taken at
    the float32 values of the stored weights."""
    m, t = dict(mk), dict(tk)
    aux_loss = getattr(model, "aux_loss", None)
    mask = (jnp.arange(Lr)[None, :] < lens[:, None]).astype(jnp.float32)
    w = mask / jnp.maximum(lens[:, None], 1).astype(jnp.float32)
    # the policy of the first iteration is the reference model itself
    lp_ref = None if same_ref else jax.lax.stop_gradient(
        _response_logprobs(model, num, m, ref, tokens, Lp, Lr))

    def loss(p):
        lp = _response_logprobs(model, num, m, p, tokens, Lp, Lr)
        rho = jnp.exp(lp - jax.lax.stop_gradient(lp))
        a = adv[:, None]
        surr = jnp.minimum(rho * a, jnp.clip(rho, 1 - t["clip_eps_low"],
                                             1 + t["clip_eps_high"]) * a)
        d = (jax.lax.stop_gradient(lp) if same_ref else lp_ref) - lp
        kl = jnp.exp(d) - d - 1.0
        j = jnp.sum((surr - t["kl_coef"] * kl) * w) / n_total
        if aux_loss is None:
            return -j, lp
        aux = jax.vmap(lambda s: aux_loss(num, m, p, s))(tokens)
        return -j + m["aux_coef"] * jnp.sum(aux) / n_total, lp

    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), policy)
    (l, lp), g = jax.value_and_grad(loss, has_aux=True)(p32)
    return l, lp, jax.tree.map(jnp.add, gsum, g)


@partial(jax.jit, static_argnums=(0,), donate_argnums=(3, 4))
def _adam(tk, params, grads, mu, nu, step):
    """One AdamW step with global-norm clipping; the moments are consumed.
    Returns the new parameters and moments, the
    pre-clip norm and the leaf norms of the clipped gradient."""
    t = dict(tk)
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.minimum(1.0, t["grad_clip"] / jnp.maximum(gnorm, 1e-12))
    step = step + 1
    bc1 = 1.0 - t["adam_b1"] ** step
    bc2 = 1.0 - t["adam_b2"] ** step

    def upd(p, g, m, v):
        g = g * scale
        m = t["adam_b1"] * m + (1 - t["adam_b1"]) * g
        v = t["adam_b2"] * v + (1 - t["adam_b2"]) * g * g
        p32 = p.astype(jnp.float32)
        delta = (m / bc1) / (jnp.sqrt(v / bc2) + t["adam_eps"]) \
            + t["weight_decay"] * p32
        return (p32 - t["learning_rate"] * delta).astype(p.dtype), m, v

    out = jax.tree.map(upd, params, grads, mu, nu)
    is3 = lambda x: isinstance(x, tuple) and len(x) == 3
    new_p = jax.tree.map(lambda o: o[0], out, is_leaf=is3)
    new_m = jax.tree.map(lambda o: o[1], out, is_leaf=is3)
    new_v = jax.tree.map(lambda o: o[2], out, is_leaf=is3)
    clipped = [jnp.sqrt(jnp.sum(jnp.square(g * scale))) for g in leaves]
    return new_p, new_m, new_v, gnorm, clipped


@jax.jit
def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in jax.tree.leaves(tree)]


@jax.jit
def leaf_diff_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def follow(model, num, m, trainer, params, iterations, *, prompt_len,
           resp_len, micro: int = 2):
    """Follow the program's first iterations from the same weights.

    ``params``: the seed's weights (the reference model and the initial
    policy); ``iterations``: per iteration, its rollout groups (dicts with
    prompt_ids, response_ids, response_len). Returns per iteration the
    loss, the pre-clip gradient norm and the logprob of every response
    token, plus the clipped first gradient's leaf norms, the first
    gradient's unclipped leaf norms, and the leaf norms of the parameters'
    change after all iterations."""
    mk = tuple(sorted(m.items()))
    tk = tuple(sorted((k, v) for k, v in trainer.items()
                      if isinstance(v, (int, float))))
    zeros = jax.jit(lambda t: jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), t))
    policy = params
    mu, nu = zeros(params), zeros(params)
    step = jnp.zeros((), jnp.int32)
    out = {"loss": [], "grad_norm": [], "logprobs": []}
    for it, groups in enumerate(iterations):
        toks, lens, adv, resps = iteration_batch(
            groups, prompt_len, resp_len, trainer["adv_eps"])
        N = len(lens)
        gsum, lsum, lps = zeros(params), 0.0, []
        for lo in range(0, N, micro):
            sl = slice(lo, lo + micro)
            l, lp, gsum = _micro(model, num, mk, tk, prompt_len, resp_len,
                                 it == 0, policy, params,
                                 jnp.asarray(toks[sl]), jnp.asarray(lens[sl]),
                                 jnp.asarray(adv[sl]), jnp.float32(N), gsum)
            lsum += float(l)
            lp = np.asarray(lp)
            lps.extend(lp[i, :lens[lo + i]] for i in range(lp.shape[0]))
        if it == 0:
            out["grad_leaf_norms"] = [float(x) for x in leaf_norms(gsum)]
        policy, mu, nu, gnorm, clipped = _adam(tk, policy, gsum, mu, nu,
                                               step)
        del gsum
        step = step + 1
        if it == 0:
            out["clipped_leaf_norms"] = [float(x) for x in clipped]
        out["loss"].append(lsum)
        out["grad_norm"].append(float(gnorm))
        out["logprobs"].append(lps)
    out["change_leaf_norms"] = [float(x)
                                for x in leaf_diff_norms(policy, params)]
    return out
