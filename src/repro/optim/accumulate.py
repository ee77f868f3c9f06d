"""Micro-batch gradient accumulation (paper §3, Eq. 1).

J_batch = (1/M) sum_i (1/m) sum_j (...) — the consumer accumulates
micro-batch gradients as rollouts arrive from the queue and applies one
parameter update per iteration. Commutativity of the finite sum is what
makes completion-order consumption gradient-equivalent (Remark 1);
``tests/test_onpolicy.py`` asserts this numerically.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, donate_argnums=(0,))
def _accumulate(total, grads, weight):
    # the running sum is donated: the new sum reuses its buffers instead of
    # holding two f32 copies of the model at once
    return jax.tree.map(lambda a, g: a + g.astype(jnp.float32) * weight,
                        total, grads)


class GradAccumulator:
    """Host-side accumulator: O <- O + grad(micro_batch), then mean."""

    def __init__(self):
        self._sum = None
        self._weight = 0.0
        self._count = 0

    def add(self, grads, weight: float = 1.0) -> None:
        """weight = number of samples in the micro-batch, so unequal
        micro-batches still average to the exact full-batch mean."""
        if self._sum is None:
            self._sum = jax.tree.map(
                lambda g: g.astype(jnp.float32) * weight, grads)
        else:
            self._sum = _accumulate(self._sum, grads, jnp.float32(weight))
        self._weight += float(weight)
        self._count += 1

    @property
    def count(self) -> int:
        return self._count

    def mean(self):
        if self._sum is None:
            raise ValueError("no gradients accumulated")
        w = self._weight
        return jax.tree.map(lambda a: a / w, self._sum)

    def reset(self) -> None:
        self._sum = None
        self._weight = 0.0
        self._count = 0
