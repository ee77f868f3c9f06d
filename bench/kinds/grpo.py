"""GRPO training traffic through the program's own pipeline:
``launch/train.py build_pipeline`` and ``PeriodicAsyncScheduler.run``,
with real rollouts on the paged engine.

Generator: each iteration draws ``prompts_per_iteration`` prompts of
exactly ``prompt_len`` token ids, uniform over the configuration's
vocabulary (specials excluded), from the seed; every seed has the same
sizes. Rewards are the benchmark's stand-in (parity of each response's
token sum), responses are sampled by the policy up to
``max_response_len``; with ``stop_at_eos`` false a sampled EOS does not
end a response, so every response runs to the cap and every seed trains
on the same sizes (the ``ignore_eos`` of serving benchmarks). The engine's
page pool holds ``resident_groups`` whole groups at their longest, so that
many groups decode at once.

Set-up builds the pipeline with the seed's weights and drives it through
``checked_iterations`` iterations with the window's own call; those
iterations compile every program, and the plain reference follows them
after the window. The window runs whole iterations until ``--seconds``
have passed; a traced run then traces ``TRACE_ITERS`` more.

Whatever depends on the architecture (the sizes, the weights' layout, the
reference's loss terms and the required work) comes from the module that
the configuration's ``reference`` names (``bench/reference/__init__.py``).
"""
from __future__ import annotations

import dataclasses
import time
import types

import numpy as np

from bench import common, weights

REHEARSE = dict(prompt_len=24, max_response_len=16, group_size=4,
                rollout_slots=8)
# a traced run traces this many iterations after the window
TRACE_ITERS = 2


def parity_reward(response_ids, answer=None) -> float:
    del answer
    return float(int(np.sum(np.asarray(response_ids, np.int64))) % 2)


class Loader:
    """The program's PromptLoader surface, fed from the seed."""

    def __init__(self, t: dict, vocab: int, seed: int):
        self.t = t
        self.vocab = vocab
        self.rng = common.seed_rng(seed, "grpo.prompts")
        self.uid = 0

    def batches(self, n: int):
        for _ in range(n):
            batch = []
            for _ in range(self.t["prompts_per_iteration"]):
                self.uid += 1
                ids = self.rng.integers(3, self.vocab,
                                        size=self.t["prompt_len"],
                                        dtype=np.int64).astype(np.int32)
                batch.append((types.SimpleNamespace(uid=self.uid,
                                                    answer=None), ids))
            yield batch


def _install_faults(ctx, sched, comp) -> None:
    """Break the timed path on purpose (tests of the check only)."""
    import jax
    if "state_unchanged" in ctx.faults:
        upd = sched.apply_update

        def unchanged(policy, opt, grads):
            # the update donates the optimizer state: keep a copy to return
            keep = jax.tree.map(lambda a: a.copy(), opt)
            _, _, m = upd(policy, opt, grads)
            return policy, keep, m
        sched.apply_update = unchanged
    if "half_batch" in ctx.faults:
        sched_mb = sched._micro_batches

        def half(group):
            g = dataclasses.replace(
                group, response_ids=group.response_ids[: len(
                    group.response_len) // 2],
                response_len=group.response_len[: len(
                    group.response_len) // 2],
                rewards=group.rewards[: len(group.rewards) // 2],
                response_logprobs=None if group.response_logprobs is None
                else group.response_logprobs[: len(group.response_len)
                                             // 2])
            yield from sched_mb(g)
        sched._micro_batches = half
    if "token_altered" in ctx.faults:
        eng = comp["pool"].instances[0].paged_engine
        drain = eng._drain_block

        def altered(blk):
            toks = jax.device_get(blk.toks).copy()
            toks[0] = (toks[0] + 1) % ctx.cfg_vocab
            return drain(dataclasses.replace(blk, toks=toks))
        eng._drain_block = altered


def run(ctx) -> dict:
    import jax
    common.add_program_to_path()
    from repro.models import init
    from bench.reference import grpo as ref_grpo
    from bench.reference.numerics import F32
    from bench.harness import free_device_memory

    spec, t = ctx.spec, dict(ctx.traffic)
    if t["reward"] != "parity":
        raise ValueError(f"unknown reward {t['reward']!r}; this generator "
                         f"knows 'parity'")
    ref_model = common.reference_module(spec["reference"])
    cfg = common.model_config(spec, rehearse=ctx.rehearse)
    if ctx.rehearse:
        t.update(REHEARSE)
        dims = ref_model.dims_from_program(cfg)
    else:
        dims = ref_model.dims_from_spec(spec)
        prog = ref_model.dims_from_program(cfg)
        if dims != prog:
            raise ValueError(f"the configuration file's sizes {dims} differ "
                             f"from the program's {prog}")
    ctx.cfg_vocab = dims["V"]
    trainer = dict(spec["trainer"])
    layout = ref_model.layout(dims)
    weights.check_layout(layout, jax.eval_shape(
        lambda k: init(k, cfg), jax.random.PRNGKey(0)))
    # every object of the program lives inside _program and is gone when it
    # returns, so the reference has the chip to itself
    out = _program(ctx, cfg, t, trainer, layout)
    live = free_device_memory()
    rollouts, sizes, wv = out["rollouts"], out["sizes"], out["wv"]

    # -- the reference follows the checked iterations ---------------------
    t_ref = time.perf_counter()
    params = weights.make(layout, ctx.seed)
    follow = lambda num, its: ref_grpo.follow(
        ref_model, num, dims, trainer, params, its,
        prompt_len=t["prompt_len"], resp_len=t["max_response_len"])
    ref = follow(F32, rollouts)
    prog_lps = [[g["logprobs"][j, :int(n)] for g in groups
                 for j, n in enumerate(g["response_len"])]
                for groups in rollouts]
    numbers = compare(ref, prog_lps, out["prog_loss"], out["prog_gnorm"],
                      out["first_grad"], out["change"], out["staleness"])
    ref_s = time.perf_counter() - t_ref
    names = weights.leaf_names(layout)
    keep = kept_leaves(ref)
    by_leaf = lambda prog, want: dict(zip(names, (
        float(x) for x in leaf_gaps(prog, want, keep))))
    controls = {c: control_numbers(c, follow, ref, rollouts, V=dims["V"])
                for c in ctx.controls}
    work = ref_model.window_work(dims, sizes, wv, t, out["counters"])

    hist = out["hist"]
    tokens = sum(lp_ + r for v, grp in sizes.items() if v in wv
                 for lp_, rs in grp for r in rs)
    groups_done = sum(len(grp) for v, grp in sizes.items() if v in wv)
    n_prompts = len(hist) * t["prompts_per_iteration"]
    window_s = out["window_s"]
    return {
        "setup_s": out["setup_s"], "window_s": window_s,
        "attempted": n_prompts, "failed": n_prompts - groups_done,
        "e2e": {"tpspd": tokens / window_s / ctx.chips,
                "setup_s": out["setup_s"]},
        "numbers": numbers, "memory_peak": out["memory_peak"],
        "hist": hist, "decode_steps_traced": out["decode_steps_traced"],
        "work": work,
        "chips": ctx.chips,
        "info": {"iterations": len(hist), "tokens": tokens,
                 "setup_compiles": out["setup_compiles"],
                 "setup_compile_s": out["setup_compile_s"],
                 "window_compiles": out["window_compiles"],
                 "reference_s": ref_s, "live_bytes_before_reference": live,
                 "ref_loss": ref["loss"], "prog_loss": out["prog_loss"],
                 "ref_grad_norm": ref["grad_norm"][0],
                 "prog_grad_norm": out["prog_gnorm"],
                 "decode_steps": out["decode_steps"], "work": work,
                 "first_grad_leaf_gaps": by_leaf(out["first_grad"],
                                                 ref["clipped_leaf_norms"]),
                 "groups": group_summary(rollouts),
                 "controls": controls},
    }


def group_summary(rollouts) -> list:
    """Per checked iteration and group: how many samples were rewarded,
    and the shortest response."""
    from bench.reference.grpo import parity_rewards
    return [[[int(parity_rewards([g["response_ids"][j, :int(n)] for j, n in
                                  enumerate(g["response_len"])]).sum()),
              int(np.min(g["response_len"]))] for g in its]
            for its in rollouts]


def pool_pages(t: dict) -> int:
    """Pages that hold ``resident_groups`` groups whole: each group's
    prompt pages once and every row's response pages, beside the engine's
    reserved pages (its own auto-size holds two groups)."""
    from repro.core.paged import FIRST_PAGE
    page = t["kv_page_size"]
    per_group = (-(-t["prompt_len"] // page)
                 + t["group_size"] * -(-t["max_response_len"] // page))
    return FIRST_PAGE + t["resident_groups"] * per_group


def _program(ctx, cfg, t, trainer, layout) -> dict:
    """Build the pipeline with the seed's weights, drive it through the
    checked iterations (set-up) and the window, and return plain data."""
    import jax
    from repro.configs.base import RLConfig
    from repro.core.queue import RolloutGroup
    import repro.launch.train as train_mod
    from bench.harness import memory_peak
    from bench.reference import grpo as ref_grpo

    G, Lp, Lr = t["group_size"], t["prompt_len"], t["max_response_len"]
    params = weights.make(layout, ctx.seed)
    rl = RLConfig(
        mode=t["mode"], rollout_engine="paged", group_size=G,
        batch_prompts=t["prompts_per_iteration"], micro_batch=t["micro_batch"],
        num_inference_instances=t["rollout_instances"], max_prompt_len=Lp,
        max_response_len=Lr, shared_prompt_attention=t[
            "shared_prompt_attention"], cbatch_slots=t["rollout_slots"],
        kv_page_size=t["kv_page_size"], kv_pages=pool_pages(t),
        temperature=t["temperature"],
        capture_logprobs=True, learning_rate=trainer["learning_rate"],
        adam_b1=trainer["adam_b1"], adam_b2=trainer["adam_b2"],
        weight_decay=trainer["weight_decay"], grad_clip=trainer["grad_clip"],
        kl_coef=trainer["kl_coef"], clip_eps_low=trainer["clip_eps_low"],
        clip_eps_high=trainer["clip_eps_high"],
        seed=ctx.seed & 0x7FFFFFFF)
    made = train_mod.init
    train_mod.init = lambda key, c: params      # the seed's weights
    try:
        sched, comp = train_mod.build_pipeline(cfg, rl, seed=rl.seed)
    finally:
        train_mod.init = made
    del params
    sched.loader = Loader(t, ctx.cfg_vocab, ctx.seed)
    if not t["stop_at_eos"]:
        # an id that is never sampled: the engine reads it when its decode
        # block is first traced, in set-up. A random policy samples EOS at
        # a random step, and a response cut short weighs its few tokens
        # far above the rest in the token-mean loss, so the first gradient
        # would hinge on how soon a seed happens to draw it.
        for inst in comp["pool"].instances:
            inst.paged_engine.eos_id = -1
    comp["generator"].reward_fn = parity_reward
    pool, tri = comp["pool"], comp["tri"]
    checked = t["checked_iterations"]

    # every finished group passes the queue: keep the checked iterations'
    # rollouts whole and the sizes of the rest
    kept, sizes = [], {}
    put = comp["queue"].put

    def record(item):
        if isinstance(item, RolloutGroup):
            v = item.weight_version
            sizes.setdefault(v, []).append(
                (len(item.prompt_ids), [int(x) for x in item.response_len]))
            if v < checked:
                kept.append({"version": v, "uid": item.uid,
                             "prompt_ids": np.asarray(item.prompt_ids),
                             "response_ids": np.asarray(item.response_ids),
                             "response_len": np.asarray(item.response_len),
                             "logprobs": np.asarray(
                                 item.response_logprobs)})
        put(item)
    comp["queue"].put = record
    _install_faults(ctx, sched, comp)
    eng = pool.instances[0].paged_engine
    for obj, attr, label in (
            (eng, "step", "rollout.engine_step"),
            (sched, "grad_step_captured", "trainer.grad_step"),
            (sched, "apply_update", "trainer.update"),
            (comp["queue"], "get", "trainer.wait_for_rollouts"),
            (comp["transfer"], "ensure", "weights.ensure"),
            (comp["transfer"], "_stream", "weights.stream")):
        ctx.annotate(obj, attr, label)

    # -- set-up: the first iterations, through the window's own call ----
    sched.run(1)
    b1 = trainer["adam_b1"]
    first_grad = [float(n) / (1 - b1)
                  for n in ref_grpo.leaf_norms(tri.opt.mu)]
    sched.run(checked - 1)
    change = [float(n) for n in ref_grpo.leaf_diff_norms(tri.policy,
                                                           tri.ref)]
    setup_hist = list(sched.history)
    setup_compiles = ctx.clock.count
    setup_compile_s = ctx.clock.seconds
    setup_s = time.perf_counter() - ctx.t_start

    # -- window -----------------------------------------------------------
    ctx.clock.reset()
    k0 = len(sched.history)
    eng0 = pool.engine_stats()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < ctx.seconds:
        sched.run(1)
    window_s = time.perf_counter() - t0
    window_compiles = ctx.clock.count
    hist = sched.history[k0:]
    counters = window_counters(eng0, pool.engine_stats(), hist)
    steps_traced = None
    if ctx.trace:
        # traced iterations follow the window, so tracing slows none of the
        # iterations that the window's numbers are taken from
        s0 = pool.engine_stats()["decode_steps"]
        ctx.trace_start()
        sched.run(TRACE_ITERS)
        ctx.trace_stop()
        steps_traced = pool.engine_stats()["decode_steps"] - s0
    kept.sort(key=lambda g: (g["version"], g["uid"]))
    return {
        "setup_s": setup_s, "window_s": window_s,
        "setup_compiles": setup_compiles, "setup_compile_s": setup_compile_s,
        "window_compiles": window_compiles,
        "memory_peak": memory_peak(jax.devices()),
        "decode_steps": counters["engine"]["decode_steps"],
        "decode_steps_traced": steps_traced, "counters": counters,
        "hist": [dataclasses.asdict(s) for s in hist],
        "sizes": sizes, "wv": {s.iteration for s in hist},
        "staleness": max(s.max_staleness for s in setup_hist + hist),
        "prog_loss": [s.metrics["loss"] for s in setup_hist[:checked]],
        "prog_gnorm": setup_hist[0].metrics["grad_norm"],
        "first_grad": first_grad, "change": change,
        "rollouts": [[g for g in kept if g["version"] == it]
                     for it in range(checked)],
    }


def window_counters(eng0, eng1, hist) -> dict:
    """The program's counters over the window: the engine's, as the change
    of ``engine_stats`` across it, and the trainer's, as its iterations'
    ``IterationStats.metrics`` summed."""
    trainer = {}
    for s in hist:
        for k, v in s.metrics.items():
            trainer[k] = trainer.get(k, 0) + v
    return {"engine": {k: eng1[k] - eng0[k] for k in eng1},
            "trainer": trainer}


def kept_leaves(ref) -> np.ndarray:
    """Leaves whose reference gradient is not nought to rounding: at least
    a thousandth of the median leaf's."""
    unclipped = np.asarray(ref["grad_leaf_norms"])
    return unclipped >= 1e-3 * np.median(unclipped)


def leaf_gaps(prog, want, keep) -> np.ndarray:
    """Per leaf, the gap between the program's norm and the reference's
    over the larger of that leaf's and the median kept leaf's; 0 where the
    leaf is left out."""
    prog, want = np.asarray(prog), np.asarray(want)
    den = np.maximum(want, np.median(want[keep]))
    return np.where(keep, np.abs(prog - want) / den, 0.0)


def compare(ref, prog_lps, prog_loss, prog_gnorm, first_grad, change,
            staleness) -> dict:
    """The numbers that decide ``correct`` (limits in
    ``bench/limits/<workload>.json``). ``prog_lps``: per iteration, the
    logprob of every response token, samples in the reference's order."""
    gap = 0.0
    for ours_it, ref_it in zip(prog_lps, ref["logprobs"]):
        for ours, want in zip(ours_it, ref_it):
            gap = max(gap, float(np.max(np.abs(np.asarray(ours) - want))))
    keep = kept_leaves(ref)
    worst = lambda prog, want: float(np.max(leaf_gaps(prog, want, keep)))

    return {
        "max_staleness": float(staleness),
        "rollout_logprob_gap": gap,
        "loss_gap": float(max(abs(a - b) for a, b in zip(prog_loss,
                                                         ref["loss"]))),
        "grad_norm_gap": abs(prog_gnorm - ref["grad_norm"][0])
        / ref["grad_norm"][0],
        "first_grad_leaf_gap": worst(first_grad, ref["clipped_leaf_norms"]),
        "change_leaf_gap": worst(change, ref["change_leaf_norms"]),
    }


def control_numbers(name, follow, ref, rollouts, *, V) -> dict:
    """The numbers a control or a planted fault reads, put in the
    program's place (``bench/tools/control.py``):

    * ``fp8``: the reference in float8 (the step below bfloat16);
    * ``half_batch``: each group's gradient from half its samples;
    * ``token_altered``: every response token of each group's first
      sample replaced by the next id where it is produced, with the
      logprob the sampler captured for the original token.
    """
    from bench.reference.numerics import F32, FP8
    if name == "fp8":
        r = follow(FP8, rollouts)
        return compare(ref, r["logprobs"], r["loss"], r["grad_norm"][0],
                       r["clipped_leaf_norms"], r["change_leaf_norms"], 0)
    if name == "half_batch":
        half = [[dict(g, response_ids=g["response_ids"][:len(
            g["response_len"]) // 2], response_len=g["response_len"][:len(
                g["response_len"]) // 2]) for g in its] for its in rollouts]
        r = follow(F32, half)
        out = compare(ref, [[] for _ in rollouts], r["loss"],
                      r["grad_norm"][0], r["clipped_leaf_norms"],
                      r["change_leaf_norms"], 0)
        del out["rollout_logprob_gap"]
        return out
    if name == "token_altered":
        bad = [[dict(g, response_ids=np.where(
            np.arange(len(g["response_len"]))[:, None] == 0,
            (g["response_ids"] + 1) % V, g["response_ids"]))
            for g in its] for its in rollouts]
        r = follow(F32, bad)
        lps = [[g["logprobs"][j, :int(n)] for g in its
                for j, n in enumerate(g["response_len"])] for its in bad]
        return compare(r, lps, ref["loss"], ref["grad_norm"][0],
                       ref["clipped_leaf_norms"], ref["change_leaf_norms"],
                       0)
    raise KeyError(name)
