"""Compile the main path's Pallas kernels for a described TPU v5e at the
published widths of the models they serve (llama3.2-3b GQA, deepseek-v2-lite
MLA). Nothing runs: the chip's compiler (Mosaic) checks block shapes, VMEM
use and lowering, which interpret mode cannot. The topology is described
inside a fixture, never at import, so pytest-xdist workers all collect the
same tests and only the worker given this file loads the TPU library.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (paged_decode_attention,
                                            paged_mla_decode_attention,
                                            paged_mla_verify_attention,
                                            paged_verify_attention)
from repro.kernels.spa_attention import spa_attention
from repro.kernels.transfer_cast import transfer_cast

# llama3.2-3b: 24 query heads over 8 KV heads of 128
H, HKV, D = 24, 8, 128
# deepseek-v2-lite: 16 heads, latent rank 512 + rope 64
MLA_H, RANK, ROPE = 16, 512, 64
B, PAGE, N_MAX = 8, 16, 64            # 8 decode rows, 1024-token contexts
PAGES = 1 + B * N_MAX
SPEC_K = 4
SUFFIX = 256                          # a prefix-cache suffix prefill block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described device cannot be read back from the
    # persistent cache without the chip: keep them out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "the kernel was not lowered to a Mosaic custom call"
    return compiled


def _pool(*tail, dtype=jnp.bfloat16):
    return ((PAGES, PAGE) + tail, dtype)


_TABLE = ((B, N_MAX), jnp.int32)
_POS_PAGES = ((PAGES, PAGE), jnp.int32)


# the multi-token paged kernels serve both the spec verify block (k+1
# tokens) and the radix cache's suffix prefill (hundreds of tokens)
_BLOCKS = {"verify": SPEC_K + 1, "suffix": SUFFIX}


@pytest.mark.parametrize("kind", ["decode", "verify", "suffix"])
def test_paged_gqa_compiles(one_chip, kind):
    if kind == "decode":
        fn, q, qp = paged_decode_attention, (B, H, D), (B,)
    else:
        S = _BLOCKS[kind]
        fn, q, qp = paged_verify_attention, (B, S, H, D), (B, S)
    _compile(partial(fn, interpret=False), one_chip,
             (q, jnp.bfloat16), _pool(HKV, D), _pool(HKV, D), _POS_PAGES,
             _TABLE, (qp, jnp.int32))


@pytest.mark.parametrize("kind", ["decode", "verify", "suffix"])
def test_paged_mla_compiles(one_chip, kind):
    if kind == "decode":
        fn, q, qp = paged_mla_decode_attention, (B, MLA_H, RANK + ROPE), (B,)
    else:
        S = _BLOCKS[kind]
        fn, q, qp = (paged_mla_verify_attention, (B, S, MLA_H, RANK + ROPE),
                     (B, S))
    _compile(partial(fn, interpret=False), one_chip,
             (q, jnp.bfloat16), _pool(RANK), _pool(ROPE), _POS_PAGES,
             _TABLE, (qp, jnp.int32))


def test_spa_attention_compiles(one_chip):
    b, s = 2, 1024
    _compile(partial(spa_attention, interpret=False), one_chip,
             ((b, s, H, D), jnp.bfloat16), ((b, s, HKV, D), jnp.bfloat16),
             ((b, s, HKV, D), jnp.bfloat16), ((b, s), jnp.int32),
             ((b, s), jnp.int32), ((b, s), jnp.int32), ((b, s), jnp.int32))


# the GRPO cell's grad step: InternLM2-20B widths, one SPA-packed group of
# a 512-token prompt and 8 x 384 responses in one 3,591-token row
SPA_H, SPA_HKV, SPA_S = 48, 8, 511 + 8 * 385
RESIDENT = 12_897_302_528       # the cell's peak bytes in use, beside which
HBM = 16 * 2**30                # the grad step's temporaries must fit


def _spa_parts():
    from repro.kernels import spa_attention as spa
    bq, bk = spa.BLOCK_Q, spa.BLOCK_K
    nq, nk = -(-SPA_S // bq), -(-SPA_S // bk)
    sq, sk = nq * bq, nk * bk
    t = spa._Tiling(G=SPA_H // SPA_HKV, D=D, Dv=D, bq=bq, bk=bk, nq=nq,
                    nk=nk, scale=D ** -0.5, window=None, interpret=False)
    bf = lambda *s: (s, jnp.bfloat16)
    i32 = lambda *s: (s, jnp.int32)
    f32 = lambda *s: (s, jnp.float32)
    qkv = (bf(1, sq, SPA_H * D), bf(1, sk, SPA_HKV * D), bf(1, sk, SPA_HKV * D))
    pos = (i32(1, sq), i32(1, sk), i32(1, sq), i32(1, sk))
    lists = (i32(nq * nk), i32())
    stats = (bf(1, sq, SPA_H * D), f32(1, SPA_H, sq, 1), f32(1, SPA_H, sq, 1))
    return spa, t, qkv, pos, lists, stats


@pytest.mark.parametrize("kernel", ["forward", "dq", "dkv"])
def test_spa_training_kernels_compile(one_chip, kernel):
    """The forward with its log-sum-exp residual, and the dQ and dK/dV
    kernels, at the cell's widths, over a dynamic live-tile grid."""
    spa, t, qkv, pos, lists, stats = _spa_parts()
    if kernel == "forward":
        fn, shapes = partial(spa._forward, t), qkv + pos + lists
    else:
        fn = partial(spa._backward_dq if kernel == "dq"
                     else spa._backward_dkv, t)
        shapes = qkv + pos + stats + lists
    _compile(fn, one_chip, *shapes)


def test_spa_attention_grad_compiles_beside_the_resident_state(one_chip):
    """jax.grad of the kernel at the cell's widths: all three Mosaic
    kernels, and temporaries that fit beside the cell's resident bytes."""
    def loss(q, k, v, qp, kp, qs, ks):
        o = spa_attention(q, k, v, qp, kp, qs, ks, interpret=False)
        return o.astype(jnp.float32).sum()

    s = SPA_S
    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                        ((1, s, SPA_H, D), jnp.bfloat16),
                        ((1, s, SPA_HKV, D), jnp.bfloat16),
                        ((1, s, SPA_HKV, D), jnp.bfloat16),
                        *(((1, s), jnp.int32),) * 4)
    assert compiled.as_text().count("tpu_custom_call") >= 3
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes \
        < HBM - RESIDENT


def test_transfer_cast_compiles(one_chip):
    _compile(partial(transfer_cast, dtype=jnp.bfloat16, interpret=False),
             one_chip, ((3072, 8192), jnp.float32))
