"""Plain float32 reference of a dense decoder with grouped-query attention
(InternLM2 / Llama layout): RMSNorm, RoPE on two halves, causal softmax
attention with H query heads over Hkv shared key/value heads, SwiGLU MLP,
untied output head. No cache, no packing, no kernels.

Parameters follow the layout of ``layout`` (matrices stored (in, out),
layers stacked on a leading axis); they are the bfloat16 values the
benchmark drew from the seed, read as float32. The module implements the
interface of ``bench/reference/__init__.py``; it has no auxiliary loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import flops
from bench.reference.numerics import (causal_softmax_attention, rmsnorm,
                                      rope)


def dims_from_spec(spec: dict) -> dict:
    d, H = spec["hidden_size"], spec["num_attention_heads"]
    return dict(d=d, H=H, Hkv=spec["num_key_value_heads"], hd=d // H,
                ff=spec["intermediate_size"], V=spec["vocab_size"],
                L=spec["num_hidden_layers"], eps=spec["rms_norm_eps"],
                theta=float(spec["rope_theta"]),
                dtype=spec.get("torch_dtype", "bfloat16"))


def dims_from_program(cfg) -> dict:
    return dict(d=cfg.d_model, H=cfg.num_heads, Hkv=cfg.num_kv_heads,
                hd=cfg.head_dim, ff=cfg.d_ff, V=cfg.vocab_size,
                L=cfg.num_layers, eps=cfg.norm_eps,
                theta=float(cfg.rope_theta), dtype=cfg.param_dtype)


def layout(m: dict) -> dict:
    """{path: (shape, dtype, init)}; init is "ones", "embed" or "fan_in"."""
    d, H, Hkv, hd, ff, V, L = (m["d"], m["H"], m["Hkv"], m["hd"], m["ff"],
                               m["V"], m["L"])
    dt = m["dtype"]
    return {
        "embed": {"embedding": ((V, d), dt, "embed"),
                  "lm_head": ((d, V), dt, "fan_in")},
        "final_norm": {"scale": ((d,), dt, "ones")},
        "layers": {
            "ln1": {"scale": ((L, d), dt, "ones")},
            "attn": {"wq": ((L, d, H * hd), dt, "fan_in"),
                     "wk": ((L, d, Hkv * hd), dt, "fan_in"),
                     "wv": ((L, d, Hkv * hd), dt, "fan_in"),
                     "wo": ((L, H * hd, d), dt, "fan_in")},
            "ln2": {"scale": ((L, d), dt, "ones")},
            "mlp": {"w_gate": ((L, d, ff), dt, "fan_in"),
                    "w_up": ((L, d, ff), dt, "fan_in"),
                    "w_down": ((L, ff, d), dt, "fan_in")},
        },
    }


def _layer(num, m, x, lp, pos):
    S = x.shape[0]
    H, Hkv, hd = m["H"], m["Hkv"], m["hd"]
    h = rmsnorm(x, lp["ln1"]["scale"], m["eps"])
    a = lp["attn"]
    q = num.einsum("sd,dh->sh", h, a["wq"]).reshape(S, H, hd)
    k = num.einsum("sd,dh->sh", h, a["wk"]).reshape(S, Hkv, hd)
    v = num.einsum("sd,dh->sh", h, a["wv"]).reshape(S, Hkv, hd)
    q, k = rope(q, pos, m["theta"]), rope(k, pos, m["theta"])
    G = H // Hkv                       # query head j reads kv head j // G
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    o = causal_softmax_attention(num, q, k, v, hd ** -0.5)
    x = x + num.einsum("sh,hd->sd", o.reshape(S, H * hd), a["wo"])
    h2 = rmsnorm(x, lp["ln2"]["scale"], m["eps"])
    f = lp["mlp"]
    g = num.einsum("sd,df->sf", h2, f["w_gate"])
    u = num.einsum("sd,df->sf", h2, f["w_up"])
    return x + num.einsum("sf,fd->sd", jax.nn.silu(g) * u, f["w_down"])


def hidden(num, m, params, tokens):
    """tokens: (S,) -> final normed hidden states (S, d), float32."""
    S = tokens.shape[0]
    pos = jnp.arange(S, dtype=jnp.int32)
    x = params["embed"]["embedding"][tokens].astype(jnp.float32)

    def body(x, lp):
        return _layer(num, m, x, lp, pos), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["final_norm"]["scale"], m["eps"])


def logits(num, m, params, tokens):
    """(S,) -> (S, V) float32 next-token logits."""
    return num.einsum("sd,dv->sv", hidden(num, m, params, tokens),
                      params["embed"]["lm_head"])


def next_token_logprobs(num, m, params, tokens):
    """(S,) -> (S-1,) log p(tokens[t+1] | tokens[:t+1])."""
    lg = logits(num, m, params, tokens)[:-1]
    lp = jax.nn.log_softmax(lg, axis=-1)
    return jnp.take_along_axis(lp, tokens[1:, None], axis=-1)[:, 0]


def window_work(m, sizes, wv, t, counters) -> dict:
    """Required FLOPs of the window's iterations: training counts policy
    forward and backward (6N) and the reference forward (2N) over each
    group's prompt once and every response token, rollout counts 2N per
    prompt token per group and per generated token, each with attention
    over the token's context. A dense model needs no counter of the run."""
    del t, counters
    per_tok = 2 * flops.dense_matmul_params(m)
    att = lambda ctx_sum: flops.dense_attention_flops(m, ctx_sum)
    train = roll = 0
    for v, grp in sizes.items():
        if v not in wv:
            continue
        for lp_, rs in grp:
            p_ctx = flops.causal_sum(lp_)
            r_ctx = sum(flops.causal_sum(r, lp_) for r in rs)
            n_tok = lp_ + sum(rs)
            train += 4 * (per_tok * n_tok + att(p_ctx + r_ctx))
            roll += per_tok * n_tok + att(p_ctx + r_ctx)
    return {"train_flops": train, "rollout_flops": roll}
