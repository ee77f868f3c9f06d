"""GRPO rollouts at ``RLConfig``'s default fused decode-block length D
(DESIGN.md §Device-resident-decode) against the one-token-per-drain
cadence (D=1), through ``build_pipeline`` as the trainer runs them: the
same tokens, the same captured logprobs, a quiescent engine at every
weight-sync boundary, and D decode steps in every drained block of a
full-length response."""
import numpy as np

from repro.configs import get_config, reduced_config
from repro.configs.base import RLConfig
from repro.core.queue import RolloutGroup
from repro.launch.train import build_pipeline

D = RLConfig().decode_drain_interval


def _sync_rollouts(drain):
    """Three synchronous iterations on the paged engine at drain interval
    ``drain``. Returns the queued groups by (version, uid), the engine's
    state at each weight flip, and the decode steps of each drained
    block."""
    cfg = reduced_config(get_config("llama3.2-3b"))
    rl = RLConfig(mode="sync", batch_prompts=2, group_size=4, micro_batch=4,
                  num_inference_instances=1, max_prompt_len=24,
                  max_response_len=2 * D, learning_rate=1e-3,
                  rollout_engine="paged", cbatch_slots=8, kv_page_size=8,
                  decode_drain_interval=drain, seed=3)
    sched, parts = build_pipeline(cfg, rl, seed=3)
    inst = parts["pool"].instances[0]
    eng = inst.paged_engine
    assert eng.drain == drain
    # an id never sampled: every response runs to its cap
    eng.eos_id = -1

    boundaries, block_steps, groups = [], [], []
    flip, drain_block = inst.store.on_flip, eng._drain_block
    put = parts["queue"].put

    def flip_recorded(params):
        boundaries.append((eng._pending is None, eng.sched.idle))
        flip(params)

    def drain_recorded(blk):
        block_steps.append(max(n for _, _, _, n in blk.plan))
        drain_block(blk)

    def put_recorded(item):
        if isinstance(item, RolloutGroup):
            groups.append(item)
        put(item)

    inst.store.on_flip = flip_recorded
    eng._drain_block = drain_recorded
    parts["queue"].put = put_recorded
    sched.run(3)
    groups.sort(key=lambda g: (g.weight_version, g.uid))
    return groups, boundaries, block_steps


def test_default_drain_matches_per_token_drain():
    assert D > 1, "GRPO rollouts default to fused decode blocks"
    fused, fused_bounds, fused_steps = _sync_rollouts(D)
    legacy, legacy_bounds, legacy_steps = _sync_rollouts(1)

    assert len(fused) == len(legacy) == 3 * 2
    for a, b in zip(fused, legacy):
        assert (a.weight_version, a.uid) == (b.weight_version, b.uid)
        np.testing.assert_array_equal(a.prompt_ids, b.prompt_ids)
        np.testing.assert_array_equal(np.asarray(a.response_len),
                                      np.asarray(b.response_len))
        np.testing.assert_array_equal(np.asarray(a.response_ids),
                                      np.asarray(b.response_ids))
        np.testing.assert_allclose(np.asarray(a.response_logprobs),
                                   np.asarray(b.response_logprobs),
                                   rtol=0, atol=1e-5)
        assert np.all(np.asarray(a.response_len) == 2 * D)

    # every weight flip finds no block in flight and no row admitted or
    # queued
    for bounds in (fused_bounds, legacy_bounds):
        assert len(bounds) >= 3
        assert all(pending_none and idle for pending_none, idle in bounds)

    # full-length responses of 2D tokens: every block carries D steps (two
    # groups a sync iteration, each admitted at a block's start)
    assert len(fused_steps) >= 2 * 3 and set(fused_steps) == {D}
    assert len(legacy_steps) >= 2 * D * 3 and set(legacy_steps) == {1}
