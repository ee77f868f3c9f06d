"""Matrix products of the plain references, in one place so that the same
forward runs at two precisions:

* ``F32``  — float32 under ``precision=HIGHEST``: the reference;
* ``FP8``  — the control: both operands of every product rounded to
  float8 e4m3 with a per-tensor scale (max |x| -> 448), products summed in
  float32. It is the step below the configuration's bfloat16. Under
  differentiation the rounding passes cotangents straight through, so a
  gradient is that of the rounded forward.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(x):
    x = x.astype(jnp.float32)
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


class Numerics:
    def __init__(self, name: str):
        assert name in ("f32", "fp8"), name
        self.name = name

    def cast(self, x):
        x = x.astype(jnp.float32)
        return _fp8(x) if self.name == "fp8" else x

    def einsum(self, spec: str, a, b):
        return jnp.einsum(spec, self.cast(a), self.cast(b), precision=HIGHEST,
                          preferred_element_type=jnp.float32)

    def __repr__(self):
        return f"Numerics({self.name})"


F32 = Numerics("f32")
FP8 = Numerics("fp8")


def rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, positions, theta):
    """Rotary embedding on the last axis, taken as two halves.
    x: (S, H, D); positions: (S,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def causal_softmax_attention(num, q, k, v, scale):
    """q: (S, H, Dk); k: (S, H, Dk); v: (S, H, Dv) -> (S, H, Dv)."""
    S = q.shape[0]
    s = num.einsum("shd,thd->hst", q, k) * scale
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return num.einsum("hst,thd->shd", p, v)
