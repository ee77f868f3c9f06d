"""GRPO with the unified tri-model forward (paper §4.2.1, Figure 2).

A micro-step computes THREE per-token log-probs — policy (with grad),
old-policy and reference — inside one jitted program. The no-grad pair is
evaluated by a *stacked vmap* over the two parameter pytrees: the JAX
analogue of the paper's shared-parallel-layout tri-model, fusing both
forwards into a single XLA computation with identical sharding.

Loss (PPO-clip + k3 KL penalty, paper Eq. 1 / Table 8):
    J = E_t[ min(r_t A, clip(r_t, 1-eps_l, 1+eps_h) A) - beta * KL_t ]
    KL_t = exp(ref - pol) - (ref - pol) - 1        (k3 estimator, >= 0)
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RLConfig
from repro.models import forward_hidden, token_logprobs
from repro.models.attention import attn_tile_share
from repro.optim.adam import adam_update


class MicroBatch(NamedTuple):
    """One micro-batch of packed samples (SPA-packed or plain).

    ``loss_mask`` carries per-token loss WEIGHTS (1/len(sample) on that
    sample's label positions, 0 elsewhere) so the loss is the exact
    per-sample token-mean regardless of row packing; the micro-batch loss is
    sum(per_token * weight) / n_samples."""
    tokens: jax.Array        # (m, S) int32
    labels: jax.Array        # (m, S) int32 — next-token ids
    positions: jax.Array     # (m, S) int32
    segments: jax.Array      # (m, S) int32 — 0 = prompt/shared, k = response k
    loss_mask: jax.Array     # (m, S) f32 — per-token loss weights (see above)
    advantages: jax.Array    # (m, S) f32 — group-normalised, broadcast per token
    n_samples: jax.Array = 1.0  # scalar f32 — number of packed samples
    extras: dict = {}        # modality-frontend stubs: vision_embeds / enc_embeds
    # (m, S) f32 rollout-captured behavior logprobs scattered onto label
    # positions (0 elsewhere), or None when the rollouts carried no capture
    # — see DESIGN.md §Tri-model-capture. Under Proposition 1 these ARE the
    # old-policy logprobs, so the grad step can skip the old recompute.
    logp_behavior: Optional[jax.Array] = None


def jaxify(mb: MicroBatch) -> MicroBatch:
    """Host-packed (numpy) micro-batch -> device arrays; ``None`` fields
    (absent captured logprobs) and empty extras pass through untouched."""
    return jax.tree.map(jnp.asarray, mb)


def group_advantages(rewards: jax.Array, eps: float = 1e-4) -> jax.Array:
    """GRPO advantages: per-group standardised rewards. rewards: (G,)."""
    mu = rewards.mean()
    sd = rewards.std()
    return (rewards - mu) / (sd + eps)


def _model_logprobs(params, cfg: ModelConfig, mb: MicroBatch) -> jax.Array:
    h, _, aux, _ = forward_hidden(params, cfg, mb.tokens,
                                  positions=mb.positions,
                                  segments=mb.segments,
                                  **(mb.extras or {}))
    if cfg.vision_prefix_len:       # hidden rows of the image prefix carry no loss
        h = h[:, cfg.vision_prefix_len:]
    return token_logprobs(params, cfg, h, mb.labels), aux


def trimodel_ref_old_logprobs(old_params, ref_params, cfg: ModelConfig,
                              mb: MicroBatch):
    """Fused old+ref forward: stack the two pytrees and vmap once."""
    stacked = jax.tree.map(lambda a, b: jnp.stack([a, b]), old_params, ref_params)
    lp, _ = jax.vmap(lambda p: _model_logprobs(p, cfg, mb))(stacked)
    return lp[0], lp[1]      # old, ref


def grpo_loss(policy_params, cfg: ModelConfig, rl: RLConfig, mb: MicroBatch,
              logp_old: jax.Array, logp_ref: jax.Array):
    logp, aux = _model_logprobs(policy_params, cfg, mb)
    ratio = jnp.exp(logp - logp_old)
    clipped = jnp.clip(ratio, 1.0 - rl.clip_eps_low, 1.0 + rl.clip_eps_high)
    adv = mb.advantages
    surr = jnp.minimum(ratio * adv, clipped * adv)
    d = logp_ref - logp
    kl = jnp.exp(d) - d - 1.0
    per_tok = surr - rl.kl_coef * kl
    n = jnp.asarray(mb.n_samples, jnp.float32)
    j = (per_tok * mb.loss_mask).sum() / jnp.maximum(n, 1.0)
    loss = -j + aux
    hard_mask = (mb.loss_mask > 0).astype(jnp.float32)
    denom = jnp.maximum(hard_mask.sum(), 1.0)
    metrics = {
        "loss": loss,
        "kl": (kl * hard_mask).sum() / denom,
        "ratio_mean": (ratio * hard_mask).sum() / denom,
        "aux": aux,
        "n_tokens": hard_mask.sum(),
        "attn_tile_share": attn_tile_share(cfg, mb.positions, mb.segments),
    }
    return loss, metrics


def make_grad_step(cfg: ModelConfig, rl: RLConfig):
    """grad_step(policy, old, ref, mb) -> (grads, metrics). The consumer
    accumulates these over the B rollouts of an iteration (Algorithm 1,
    lines 7-9)."""

    @jax.jit
    def grad_step(policy_params, old_params, ref_params, mb: MicroBatch):
        logp_old, logp_ref = trimodel_ref_old_logprobs(
            old_params, ref_params, cfg, mb)
        logp_old = jax.lax.stop_gradient(logp_old)
        logp_ref = jax.lax.stop_gradient(logp_ref)
        (loss, metrics), grads = jax.value_and_grad(
            grpo_loss, has_aux=True)(policy_params, cfg, rl, mb,
                                     logp_old, logp_ref)
        return grads, metrics

    return grad_step


def make_grad_step_captured(cfg: ModelConfig, rl: RLConfig):
    """Capture-path grad step (DESIGN.md §Tri-model-capture): the ratio's
    denominator is ``mb.logp_behavior`` — the logprobs the inference engine
    evaluated while sampling — so the no-grad pass shrinks from the stacked
    old+ref vmap to a SINGLE reference forward (~1/3 of the tri-model's
    training forward FLOPs deleted). Same signature as ``make_grad_step``
    so the scheduler can dispatch per micro-batch; ``old_params`` is
    accepted and unused. In strict on-policy modes the captured values
    equal the old-policy recompute up to fp reduction order; in
    ``async_offpolicy`` they are evaluated under the BEHAVIOR weights
    (the weights that actually sampled the rollout) rather than the
    current old weights, removing the old~behavior weights approximation.
    Both paths use raw-distribution logprobs — rollout temperature/top-p
    filtering sits outside the ratio convention either way."""

    @jax.jit
    def grad_step(policy_params, old_params, ref_params, mb: MicroBatch):
        del old_params                   # behavior logprobs ride the batch
        logp_ref, _ = _model_logprobs(ref_params, cfg, mb)
        logp_ref = jax.lax.stop_gradient(logp_ref)
        (loss, metrics), grads = jax.value_and_grad(
            grpo_loss, has_aux=True)(policy_params, cfg, rl, mb,
                                     mb.logp_behavior, logp_ref)
        return grads, metrics

    return grad_step


def make_apply_update(cfg: ModelConfig, rl: RLConfig):
    # the optimizer state and the averaged gradients are dead after the
    # update: donating them lets the new moments reuse their HBM, which a
    # published-width model on one chip needs (the policy is not donated —
    # the old-policy slot still aliases it)
    @partial(jax.jit, donate_argnums=(1, 2))
    def apply_update(policy_params, opt_state, grads):
        return adam_update(policy_params, grads, opt_state,
                           lr=rl.learning_rate, b1=rl.adam_b1, b2=rl.adam_b2,
                           weight_decay=rl.weight_decay,
                           grad_clip=rl.grad_clip)
    return apply_update


def make_train_step(cfg: ModelConfig, rl: RLConfig,
                    num_microbatches: int = 1):
    """Fused step (tri-model logits -> loss -> grad -> Adam) — the step
    lowered by the multi-pod dry-run for the train_4k shape.

    ``num_microbatches > 1`` applies the paper's Eq. 1 micro-batching INSIDE
    the compiled step: a lax.scan over M row-slices accumulates fp32
    gradients (Table 7: gradient dtype fp32) and applies one Adam update —
    mathematically identical to the monolithic step, with activation memory
    bounded by one micro-batch. Needed for the largest configs, whose
    tri-model + fp32-Adam resident state alone fills most of HBM."""

    def grad_micro(policy_params, old_params, ref_params, mb: MicroBatch):
        logp_old, logp_ref = trimodel_ref_old_logprobs(
            old_params, ref_params, cfg, mb)
        logp_old = jax.lax.stop_gradient(logp_old)
        logp_ref = jax.lax.stop_gradient(logp_ref)
        return jax.value_and_grad(grpo_loss, has_aux=True)(
            policy_params, cfg, rl, mb, logp_old, logp_ref)

    def train_step(policy_params, old_params, ref_params, opt_state,
                   mb: MicroBatch):
        M = num_microbatches
        if M == 1:
            (_, metrics), grads = grad_micro(
                policy_params, old_params, ref_params, mb)
        else:
            def split(a):
                return a.reshape((M, a.shape[0] // M) + a.shape[1:])

            n_micro = jnp.asarray(mb.n_samples, jnp.float32) / M
            xs = (split(mb.tokens), split(mb.labels), split(mb.positions),
                  split(mb.segments), split(mb.loss_mask),
                  split(mb.advantages), jax.tree.map(split, mb.extras or {}))

            def body(acc, xs_i):
                t, y, p, s, w, a, ex = xs_i
                mb_i = MicroBatch(t, y, p, s, w, a,
                                  n_samples=n_micro, extras=ex)
                (_, metrics), grads = grad_micro(
                    policy_params, old_params, ref_params, mb_i)
                acc = jax.tree.map(
                    lambda c, g: c + g.astype(jnp.float32), acc, grads)
                return acc, metrics

            acc0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), policy_params)
            acc, metrics_stack = jax.lax.scan(body, acc0, xs)
            grads = jax.tree.map(lambda a: a / M, acc)
            metrics = jax.tree.map(lambda m: m.mean(), metrics_stack)

        new_params, new_opt, opt_metrics = adam_update(
            policy_params, grads, opt_state,
            lr=rl.learning_rate, b1=rl.adam_b1, b2=rl.adam_b2,
            weight_decay=rl.weight_decay, grad_clip=rl.grad_clip)
        metrics.update(opt_metrics)
        return new_params, new_opt, metrics

    return train_step
