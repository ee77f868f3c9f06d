"""End-to-end behaviour tests for the periodic-asynchrony system.

These run the REAL pipeline (jitted sampler inference + tri-model GRPO
training) at CPU scale, plus integration tests of the pieces the paper's
Figure 1 composes: engine pool, generator, scheduler modes, SPA end-to-end,
checkpointing, and the serving driver.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.configs.base import RLConfig
from repro.checkpoint.ckpt import load_checkpoint, save_checkpoint
from repro.core.engine import InferenceInstance, InferencePool
from repro.data.tokenizer import Tokenizer
from repro.launch.serve import serve_batch
from repro.launch.train import build_pipeline
from repro.models import init
from repro.rl.rollout import Sampler


@pytest.fixture(scope="module")
def cfg():
    return reduced_config(get_config("llama3.2-3b"))


def _rl(**kw) -> RLConfig:
    base = dict(mode="async", batch_prompts=2, group_size=4, micro_batch=2,
                num_inference_instances=2, max_prompt_len=32,
                max_response_len=12, learning_rate=1e-3, seed=0)
    base.update(kw)
    return RLConfig(**base)


# =========================================================================
# full pipeline with REAL jitted inference
# =========================================================================

def test_e2e_async_real_inference(cfg):
    sched, parts, = build_pipeline(cfg, _rl())[0:2]
    hist = sched.run(2)
    assert len(hist) == 2
    for s in hist:
        assert s.trained_tokens > 0
        assert s.max_staleness == 0
        assert s.tpspd > 0
    assert parts["tri"].version == 2
    # queue fully drained
    assert parts["queue"].outstanding == 0


def test_e2e_spa_mode_real_inference(cfg):
    """SPA packing end-to-end: the whole group trains as one packed row."""
    sched, parts = build_pipeline(cfg, _rl(shared_prompt_attention=True,
                                           micro_batch=4))[0:2]
    hist = sched.run(1)
    assert hist[0].trained_tokens > 0
    assert parts["tri"].version == 1


def test_e2e_training_descends(cfg):
    """The optimizer actually consumes rollouts and steps every iteration."""
    rl = _rl(batch_prompts=3, learning_rate=5e-3)
    sched, parts = build_pipeline(cfg, rl)[0:2]
    sched.run(3)
    assert parts["tri"].version == 3
    assert sched.history[-1].trained_tokens > 0


# =========================================================================
# engine / pool integration
# =========================================================================

def test_pool_round_robin_distribution(cfg):
    params = init(jax.random.PRNGKey(0), cfg)
    sampler = Sampler(cfg, 16, 4)
    insts = [InferenceInstance(i, cfg, sampler) for i in range(3)]
    pool = InferencePool(insts)
    pool.sync_weights(params, version=7)
    assert all(i.version == 7 for i in insts)
    picks = [pool.pick().inst_id for _ in range(6)]
    assert picks == [0, 1, 2, 0, 1, 2]


def test_instance_version_tags_rollouts(cfg):
    params = init(jax.random.PRNGKey(0), cfg)
    sampler = Sampler(cfg, 16, 4)
    inst = InferenceInstance(0, cfg, sampler)
    inst.sync_weights(params, version=3)
    prompts = [np.asarray([1, 5, 9], np.int32)] * 2
    out, version = inst.generate_group(prompts, jax.random.PRNGKey(0))
    assert version == 3
    assert out.response_ids.shape == (2, 4)


# =========================================================================
# sampler behaviour
# =========================================================================

def test_sampler_eos_stops_row(cfg):
    """After EOS, a row must emit only PAD."""
    params = init(jax.random.PRNGKey(0), cfg)
    sampler = Sampler(cfg, 16, 16, temperature=1.0)
    prompts = [np.asarray([1, 7, 7], np.int32)] * 4
    out = sampler.generate(params, prompts, jax.random.PRNGKey(1))
    resp = np.asarray(out.response_ids)
    lens = np.asarray(out.response_len)
    for i in range(4):
        if lens[i] < 16:  # EOS observed
            assert resp[i, lens[i] - 1] == Tokenizer.EOS
            assert (resp[i, lens[i]:] == Tokenizer.PAD).all()


def test_sampler_greedy_is_deterministic(cfg):
    params = init(jax.random.PRNGKey(0), cfg)
    s = Sampler(cfg, 16, 8, temperature=0.0)
    prompts = [np.asarray([1, 4, 2, 9], np.int32)]
    a = s.generate(params, prompts, jax.random.PRNGKey(0))
    b = s.generate(params, prompts, jax.random.PRNGKey(42))
    np.testing.assert_array_equal(np.asarray(a.response_ids),
                                  np.asarray(b.response_ids))


def test_sampler_variable_prompt_lengths(cfg):
    """Left-padding: rows with different prompt lengths decode correctly."""
    params = init(jax.random.PRNGKey(0), cfg)
    s = Sampler(cfg, 16, 6)
    prompts = [np.asarray([1, 4], np.int32),
               np.asarray([1, 4, 9, 11, 13, 2, 8], np.int32)]
    out = s.generate(params, prompts, jax.random.PRNGKey(3))
    assert out.response_ids.shape == (2, 6)
    assert np.isfinite(np.asarray(out.response_len)).all()


# =========================================================================
# serving driver
# =========================================================================

def test_serve_batch_driver(cfg):
    prompts = [np.asarray([1, 5, 6, 7], np.int32)] * 3
    out, stats = serve_batch(cfg, prompts, max_prompt_len=16, max_new=8)
    assert out.response_ids.shape == (3, 8)
    assert stats["generated_tokens"] > 0
    assert stats["tok_per_s"] > 0


# =========================================================================
# checkpointing
# =========================================================================

def test_checkpoint_roundtrip(tmp_path, cfg):
    params = init(jax.random.PRNGKey(0), cfg)
    path = os.path.join(tmp_path, "ckpt")
    save_checkpoint(path, params, step=5)
    restored, step = load_checkpoint(path, params)
    assert step == 5
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_checkpoint_bf16_roundtrip(tmp_path):
    tree = {"w": jnp.arange(8, dtype=jnp.bfloat16) / 3,
            "opt": {"step": jnp.int32(3)}}
    path = os.path.join(tmp_path, "ckpt")
    save_checkpoint(path, tree)
    restored, _ = load_checkpoint(path, tree)
    assert restored["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(restored["w"], np.float32),
                                  np.asarray(tree["w"], np.float32))


# =========================================================================
# pipeline overlap: async must beat sync under simulated inference latency
# =========================================================================

def test_async_overlaps_inference_and_training(cfg):
    """With a simulated remote inference service (constant latency) the
    async scheduler's wall time per iteration must be well below the sync
    scheduler's (T_infer + T_train vs max(T_infer, T_train) — §4.2.2).

    The latency per group is set to the host's measured training time per
    group, so T_infer ~ T_train whatever the host's speed: a fixed latency
    far above the training time leaves almost nothing to hide on a fast,
    idle host, and the comparison would measure the host, not the
    scheduler. Each mode keeps its fastest of three iterations, so one
    iteration slowed by a busy host decides nothing."""
    from repro.rl.rollout import RolloutBatch

    def scripted(prompts, key):
        G, T = len(prompts), 8
        resp = np.random.RandomState(0).randint(3, 200, size=(G, T)).astype(np.int32)
        return RolloutBatch(response_ids=jnp.asarray(resp),
                            response_len=jnp.full((G,), T, jnp.int32))

    n_groups = 8
    latency = [0.0]

    def run(mode):
        rl = _rl(mode=mode, batch_prompts=n_groups, num_inference_instances=1,
                 micro_batch=4)
        sched, _ = build_pipeline(cfg, rl, scripted_fn=scripted,
                                  latency_fn=lambda out: latency[0])[0:2]
        sched.run(1)          # warm the jit caches
        if mode == "sync":    # calibrate: steady training time per group
            latency[0] = float(np.median(
                [s.train_time for s in sched.run(3)])) / n_groups
        return min(s.wall_time for s in sched.run(3))

    t_sync = run("sync")
    t_async = run("async")
    # sync pays the groups' serial inference latency on top of training;
    # async hides most of it behind the training of earlier groups
    assert t_async < t_sync * 0.85, (t_sync, t_async, latency[0])


# =========================================================================
# architecture-agnosticism: the SAME pipeline runs attention-free (SSM) and
# MoE+MLA families end-to-end (paper claim: algorithm- and architecture-
# agnostic periodic asynchrony)
# =========================================================================

@pytest.mark.parametrize("arch", ["mamba2-2.7b", "deepseek-v2-lite-16b"])
def test_e2e_nondense_families(arch):
    cfg_a = reduced_config(get_config(arch))
    rl = _rl(batch_prompts=2, group_size=2, micro_batch=2,
             max_prompt_len=24, max_response_len=8)
    sched, parts = build_pipeline(cfg_a, rl)[0:2]
    hist = sched.run(1)
    assert hist[0].trained_tokens > 0
    assert hist[0].max_staleness == 0
    assert parts["tri"].version == 1


def test_spa_rejected_for_attention_free_archs():
    """SPA packing on an SSM would leak across responses through the
    recurrence — the scheduler must refuse and point at prefix sharing."""
    cfg_ssm = reduced_config(get_config("mamba2-2.7b"))
    rl = _rl(shared_prompt_attention=True, batch_prompts=1, group_size=2)
    sched, _ = build_pipeline(cfg_ssm, rl)[0:2]
    with pytest.raises(ValueError, match="prefix-state sharing"):
        sched.run(1)
