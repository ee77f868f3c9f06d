"""Weights from the seed, on the device, in one jitted call, in the dtype
each leaf is served in.

The layout (paths, shapes, dtypes) is the plain reference's
(``bench/reference/<family>.py layout``); the program is handed the same
tree, and ``check_layout`` refuses a program whose parameter tree differs.
Leaf i is drawn from ``fold_in(seed_key, i)`` in flattening order.

Initialisation: norm scales 1; the embedding N(0, 0.02^2); every other
matrix N(0, 1/fan_in), fan_in being the second-to-last axis (matrices are
stored (in, out), stacked on leading layer or expert axes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.common import seed_key


def _is_spec(x) -> bool:
    return (isinstance(x, tuple) and len(x) == 3
            and isinstance(x[0], tuple) and isinstance(x[1], str))


def flat_specs(layout):
    leaves, treedef = jax.tree.flatten(layout, is_leaf=_is_spec)
    return leaves, treedef


def _draw(key, shape, dtype, init):
    dt = jnp.dtype(dtype)
    if init == "ones":
        return jnp.ones(shape, dt)
    std = 0.02 if init == "embed" else shape[-2] ** -0.5
    # drawn in the served dtype: no float32 copy of a large leaf exists
    return jax.random.normal(key, shape, dt) * jnp.asarray(std, dt)


def leaf_names(layout) -> list:
    """Each leaf's path, in flattening order."""
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(layout, is_leaf=_is_spec)[0]]


def make(layout, seed: int):
    """The whole tree, drawn on the default device in one call."""
    specs, treedef = flat_specs(layout)
    base = seed_key(seed)

    @jax.jit
    def build(base):
        return [_draw(jax.random.fold_in(base, i), s, d, init)
                for i, (s, d, init) in enumerate(specs)]

    return jax.tree.unflatten(treedef, build(base))


def check_layout(layout, program_shapes) -> None:
    """``program_shapes``: ``jax.eval_shape`` of the program's own init.
    Paths, shapes and dtypes must all agree."""
    ours = {jax.tree_util.keystr(p): (tuple(s), str(jnp.dtype(d)))
            for p, (s, d, _) in jax.tree_util.tree_flatten_with_path(
                layout, is_leaf=_is_spec)[0]}
    theirs = {jax.tree_util.keystr(p): (tuple(a.shape), str(a.dtype))
              for p, a in jax.tree_util.tree_flatten_with_path(
                  program_shapes)[0]}
    if ours != theirs:
        diff = sorted(set(ours.items()) ^ set(theirs.items()))
        raise ValueError(f"the program's parameter tree differs from the "
                         f"reference layout: {diff[:6]}")
