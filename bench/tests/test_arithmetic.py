"""Window arithmetic on scripted numbers, the page pool's size, and the
generator's promise that every seed gets the same sizes."""
import numpy as np

from bench import common
from bench.kinds import grpo


def test_grpo_prompts_fixed_size_from_seed():
    t = common.traffic_spec("grpo_async_g8")
    a = next(grpo.Loader(t, 11568, 5).batches(1))
    b = next(grpo.Loader(t, 11568, 5).batches(1))
    c = next(grpo.Loader(t, 11568, 6).batches(1))
    assert len(a) == t["prompts_per_iteration"]
    assert all(len(ids) == t["prompt_len"] for _, ids in a + c)
    assert all(np.array_equal(x[1], y[1]) for x, y in zip(a, b))
    assert not np.array_equal(a[0][1], c[0][1])
    assert all(3 <= ids.min() and ids.max() < 11568 for _, ids in a)


def test_tpspd_counts_generated_samples_of_window_iterations():
    # iteration 3 and 4 are in the window; 2 prompts of 10 tokens, 2
    # responses each
    sizes = {2: [(10, [5, 5])], 3: [(10, [1, 2]), (10, [3, 4])],
             4: [(10, [7, 8])]}
    wv = {3, 4}
    tokens = sum(lp + r for v, grp in sizes.items() if v in wv
                 for lp, rs in grp for r in rs)
    assert tokens == 6 * 10 + 1 + 2 + 3 + 4 + 7 + 8


def test_pool_holds_every_group_whole():
    common.add_program_to_path()
    t = common.traffic_spec("grpo_async_g8")
    # 512-token prompts are 32 pages, 384-token responses 24 pages a row
    assert t["resident_groups"] * t["group_size"] == t["rollout_slots"]
    assert grpo.pool_pages(t) == 2 + 4 * (32 + 8 * 24)


def test_seed_key_keeps_high_bits():
    import jax
    k1 = common.seed_key(5)
    k2 = common.seed_key(5 + 2 ** 33)
    assert not np.array_equal(jax.random.key_data(k1),
                              jax.random.key_data(k2))
