"""Periodic-asynchrony scheduler — Algorithm 1 of the paper.

Modes:
  * ``sync``            — paper's synchronous decoupled baseline: dispatch all
                          rollouts, wait for the full batch, then train in the
                          original prompt order (Figure 3a).
  * ``async``           — periodic asynchrony (ours): the consumer trains on
                          rollouts in completion-time order while the producer
                          is still generating; weights sync only at iteration
                          boundaries (Figure 3b). Strictly on-policy —
                          asserted at runtime per group.
  * ``async_offpolicy`` — AReaL-like fully-asynchronous baseline with
                          staleness threshold eta: the producer runs ahead of
                          the trainer by up to eta iterations, so consumed
                          rollouts may be stale (off-policy).

TPSPD (tokens trained per second per device) is the paper's primary metric.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import jax
import numpy as np

from repro.configs.base import ModelConfig, RLConfig
from repro.core.generator import TemporaryDataGenerator
from repro.core.onpolicy import OnPolicyMonitor
from repro.core.queue import RolloutGroup, RolloutQueue
from repro.core.spa import PAD, pack_plain, pack_spa
from repro.core.trimodel import TriModelState
from repro.obs import trace as otrace
from repro.obs.metrics import metrics
from repro.optim.accumulate import GradAccumulator
from repro.rl.grpo import (MicroBatch, group_advantages, make_apply_update,
                           make_grad_step, make_grad_step_captured)
from repro.transfer.service import WeightTransferService


@dataclasses.dataclass
class IterationStats:
    iteration: int
    wall_time: float
    infer_time: float   # producer busy-time aggregated over pool instances
    # consumer BUSY-time: grad micro-steps + the iteration-boundary update
    # only. Time the consumer spends blocked on queue.get() waiting for the
    # producer is excluded — that wait is precisely what the async/sync
    # TPSPD comparison must not fold into training cost.
    train_time: float
    trained_tokens: int
    reward_mean: float
    tpspd: float
    max_staleness: int
    metrics: dict


def _pad_rows(mb: MicroBatch, m: int) -> MicroBatch:
    """Pad a micro-batch to exactly m rows (dummy rows carry zero weight) so
    jitted step shapes stay static."""
    have = mb.tokens.shape[0]
    if have == m:
        return mb
    pad_n = m - have
    S = mb.tokens.shape[1]
    z_i = np.zeros((pad_n, S), np.int32)
    z_f = np.zeros((pad_n, S), np.float32)
    return MicroBatch(
        tokens=np.concatenate([mb.tokens, np.full((pad_n, S), PAD, np.int32)]),
        labels=np.concatenate([mb.labels, z_i]),
        positions=np.concatenate([mb.positions, z_i]),
        segments=np.concatenate([mb.segments, np.full((pad_n, S), -1, np.int32)]),
        loss_mask=np.concatenate([mb.loss_mask, z_f]),
        advantages=np.concatenate([mb.advantages, z_f]),
        n_samples=mb.n_samples,
        logp_behavior=(None if mb.logp_behavior is None
                       else np.concatenate([mb.logp_behavior, z_f])),
    )


class PeriodicAsyncScheduler:
    def __init__(self, cfg: ModelConfig, rl: RLConfig, tri: TriModelState,
                 generator: TemporaryDataGenerator, queue: RolloutQueue,
                 loader, *, num_devices: int = 1,
                 transfer: Optional[WeightTransferService] = None):
        self.cfg = cfg
        self.rl = rl
        self.tri = tri
        self.generator = generator
        self.queue = queue
        self.loader = loader
        self.num_devices = num_devices
        # the weight-plane (DESIGN.md §Weight-plane): versioned bucket
        # streaming trainer -> pool, replacing the old serial per-instance
        # whole-tree pool.sync_weights at the boundary
        self.transfer = transfer if transfer is not None else \
            WeightTransferService(
                generator.pool,
                bucket_bytes=rl.transfer_bucket_bytes,
                wire_dtype=rl.transfer_wire_dtype or None,
                use_pallas_cast=rl.transfer_pallas_cast,
                overlap=rl.transfer_overlap)
        self.grad_step = make_grad_step(cfg, rl)
        self.grad_step_captured = make_grad_step_captured(cfg, rl)
        # micro-step accounting: captured = ratio from rollout-time behavior
        # logprobs (single ref no-grad forward); recomputed = stacked
        # old+ref tri-model forward (capture off, or rollouts without
        # captured logprobs, e.g. scripted/simulated instances)
        self.captured_micro_steps = 0
        self.recomputed_micro_steps = 0
        self.apply_update = make_apply_update(cfg, rl)
        self.monitor = OnPolicyMonitor(strict=(rl.mode != "async_offpolicy"))
        self.history: List[IterationStats] = []
        # submitted-but-unconsumed batches carried across run() calls — the
        # async_offpolicy producer runs up to eta iterations ahead, so a
        # run() boundary is NOT a drained pipeline; re-fetching and
        # re-submitting from scratch would double-submit and train leftover
        # groups against mismatched counts.
        self._inflight: List = []
        self._key = None
        self._train_busy = 0.0
        # per-iteration device scalars: micro-step losses and attention
        # tile shares, and the update's pre-clip gradient norm, read once
        # when the iteration's stats land
        self._losses: List = []
        self._tile_shares: List = []
        self._grad_norm = None
        # registry metrics for the live ops plane (/metrics): cached
        # handles, pushed once per iteration at the boundary
        _m = metrics()
        self._m_iteration = _m.gauge("scheduler.iteration")
        self._m_trained_tokens = _m.counter("scheduler.trained_tokens")
        self._m_tpspd = _m.gauge("scheduler.tpspd")
        # set when a run() unwound mid-iteration: gradients were half-
        # accumulated and the failed iteration's groups are partially
        # consumed, so re-entering run() cannot resume soundly — it would
        # deadlock on wait_empty (strict modes) or train shifted batch
        # boundaries (off-policy). Subsequent run() calls refuse loudly.
        self._failed = False

    # ------------------------------------------------------------------
    def _micro_batches(self, group: RolloutGroup):
        adv = np.asarray(group_advantages(group.rewards))
        rl = self.rl
        if rl.shared_prompt_attention:
            # repro: allow(support-matrix): SPA packing is a training-side
            # attention-mask feature, not a decode engine — its SSM
            # exclusion is not an engine-matrix row (DESIGN.md §SPA)
            if self.cfg.attention_free:
                # SPA is an attention-MASK optimisation: packed responses
                # would leak into each other through an SSM's recurrence.
                # The state-space analogue is prefix-state sharing
                # (core/prefix.py) — see DESIGN.md §Arch-applicability.
                raise ValueError(
                    f"{self.cfg.name} is attention-free; shared-prompt "
                    "attention packing does not apply — use prefix-state "
                    "sharing (repro.core.prefix) instead")
            mb = pack_spa(group, adv, rl.max_prompt_len, rl.max_response_len,
                          responses_per_row=rl.group_size,
                          align=rl.spa_align)
            if not rl.capture_logprobs:
                mb = mb._replace(logp_behavior=None)
            yield _pad_rows(mb, mb.tokens.shape[0]), float(mb.n_samples)
        else:
            mb = pack_plain([group], [adv], rl.max_prompt_len,
                            rl.max_response_len)
            if not rl.capture_logprobs:
                mb = mb._replace(logp_behavior=None)
            m = rl.micro_batch
            rows = mb.tokens.shape[0]
            for lo in range(0, rows, m):
                hi = min(lo + m, rows)
                sub = MicroBatch(
                    tokens=mb.tokens[lo:hi], labels=mb.labels[lo:hi],
                    positions=mb.positions[lo:hi],
                    segments=mb.segments[lo:hi],
                    loss_mask=mb.loss_mask[lo:hi],
                    advantages=mb.advantages[lo:hi],
                    n_samples=np.float32(hi - lo),
                    logp_behavior=(None if mb.logp_behavior is None
                                   else mb.logp_behavior[lo:hi]))
                yield _pad_rows(sub, m), float(hi - lo)

    def _train_group(self, group: RolloutGroup, acc: GradAccumulator) -> int:
        """Consumer busy work for one group — timed into ``_train_busy``
        (the quantity ``IterationStats.train_time`` reports)."""
        tokens = 0
        t0 = time.perf_counter()
        for mb, weight in self._micro_batches(group):
            if mb.logp_behavior is not None:
                self.captured_micro_steps += 1
                step = self.grad_step_captured
            else:
                self.recomputed_micro_steps += 1
                step = self.grad_step
            with otrace.span("train.grad_step",
                             captured=mb.logp_behavior is not None):
                grads, step_metrics = step(self.tri.policy, self.tri.old,
                                           self.tri.ref, mb)
                self._losses.append(step_metrics["loss"])
                self._tile_shares.append(step_metrics["attn_tile_share"])
                # repro: allow(host-sync): trainer-side busy-time measurement
                # barrier (paper Table 7 timing); not a decode path
                jax.block_until_ready(jax.tree.leaves(grads)[0])
                acc.add(grads, weight)
            tokens += int((np.asarray(mb.tokens) != PAD).sum())
        t1 = time.perf_counter()
        self._train_busy += t1 - t0
        # the span reuses the busy stopwatch's own endpoints, so the
        # analyzer's train_time reproduces IterationStats.train_time
        otrace.complete("train.group", t0, t1, uid=group.uid, tokens=tokens)
        return tokens

    def _finish_iteration(self, acc: GradAccumulator) -> None:
        t0 = time.perf_counter()
        grads = acc.mean()
        acc.reset()          # free the f32 sum before the update allocates
        new_params, new_opt, opt_metrics = self.apply_update(
            self.tri.policy, self.tri.opt, grads)
        self._grad_norm = opt_metrics["grad_norm"]
        # repro: allow(host-sync): update must materialise before the
        # version flip (Proposition 1 boundary); trainer-side, once per
        # iteration
        jax.block_until_ready(jax.tree.leaves(new_params)[0])
        self.tri.apply_update(new_params, new_opt)   # line 11
        t1 = time.perf_counter()
        self._train_busy += t1 - t0
        otrace.complete("train.update", t0, t1, version=self.tri.version)
        # overlap: start streaming the NEW version's buckets to the pool's
        # back buffers the moment the update materialises — the wire time
        # hides under the iteration tail instead of extending the next
        # boundary; flips stay version-gated (no-op when overlap is off)
        self.transfer.publish_async(self.tri.policy, self.tri.version)

    def _sync_boundary(self, submit) -> None:
        """THE iteration boundary (Algorithm 1 lines 3 + 10) — the one
        place the Proposition-1 invariant 'rollout weights == old-policy
        weights' is established: drain (strict modes), dispatch the
        iteration's submissions, flip every instance to the policy's
        version via the weight-plane barrier, then old <- policy. The
        residual block time is the pool's sync-gap
        (``IterationStats.metrics['sync_gap']``).

        ``submit`` runs BETWEEN the drain and the flip barrier: every
        request it dispatches version-gates on ``tri.version``, so
        correctness never depends on flip-before-submit ordering — and the
        stream tail overlaps the generator's worker spin-up instead of
        extending the boundary. Paged engines stay quiescent through their
        deferred flip because the gates hold every new request back until
        the flip lands."""
        if self.rl.mode in ("sync", "async"):
            # Algorithm 1 line 3: wait until Q empty BEFORE submitting
            # (a new submission registers pending groups) and before the
            # weights move — also guarantees paged engines are quiescent
            # for their deferred flips
            with otrace.span("boundary.drain"):
                self.queue.wait_empty()
        with otrace.span("boundary.submit"):
            submit()
        flipped = self.transfer.ensure(self.tri.policy, self.tri.version)
        # Algorithm 1 line 10 at the BOUNDARY, before training: old <-
        # policy == the weights just flipped to the pool, so old-policy
        # weights equal rollout weights at consumption (Proposition 1's
        # equality — refreshing at iteration END left old one optimizer
        # step stale during iteration t's grad steps; see DESIGN.md
        # §Tri-model-capture). The flipped version is passed through so
        # the equality is asserted, not assumed.
        self.tri.refresh_old(expected_rollout_version=flipped)

    # ------------------------------------------------------------------
    def status(self) -> dict:
        """Live pipeline introspection for the ops plane (``/status`` via
        ``launch/train.py --metrics-port``): iteration progress, policy
        version, micro-step mix, and the pool's per-instance rows (each
        snapshotted atomically by its owner). Safe to call from a scrape
        thread mid-``run()`` — every field is a single read or delegated
        to a lock-holding snapshot."""
        out = {
            "mode": self.rl.mode,
            "iterations_completed": len(self.history),
            "policy_version": self.tri.version,
            "failed": self._failed,
            "captured_micro_steps": self.captured_micro_steps,
            "recomputed_micro_steps": self.recomputed_micro_steps,
            "pool": self.generator.pool.status(),
        }
        if self.history:
            out["last_iteration"] = dataclasses.asdict(self.history[-1])
        return out

    def run(self, num_iterations: int, *, key=None) -> List[IterationStats]:
        """Run ``num_iterations`` and return THEIR stats (self.history keeps
        the full cumulative record across calls).

        Safe to call repeatedly: in ``async_offpolicy`` mode up to
        ``staleness_eta`` submitted-but-unconsumed batches from the previous
        call are still in flight at a run() boundary — they carry over in
        ``self._inflight`` and are consumed FIRST, and only the shortfall is
        fetched from the loader (no double-submit).

        NOT safe to call again after a previous run() raised mid-iteration:
        the pipeline state is unrecoverable by re-entry (half-accumulated
        gradients, partially consumed batches) and this method refuses with
        a RuntimeError instead of deadlocking or double-submitting —
        rebuild the pipeline to recover."""
        if self._failed:
            raise RuntimeError(
                "scheduler state is inconsistent: a previous run() raised "
                "mid-iteration (groups from the failed iteration may still "
                "be queued and gradients were discarded half-accumulated). "
                "Rebuild the pipeline instead of retrying run().")
        start = len(self.history)
        if key is None:
            key = (self._key if self._key is not None
                   else jax.random.PRNGKey(self.rl.seed))
        mode = self.rl.mode
        pool = self.generator.pool
        eta = self.rl.staleness_eta if mode == "async_offpolicy" else 0
        # consume-order batch list: in-flight leftovers first, then exactly
        # enough fresh batches for this call's consumption + eta lookahead
        need = num_iterations + eta - len(self._inflight)
        batches = self._inflight + list(self.loader.batches(max(need, 0)))
        next_submit = len(self._inflight)
        consumed_upto = 0   # first batch index NOT fully consumed yet

        try:
            for t in range(num_iterations):
                it_start = time.perf_counter()
                busy0 = pool.busy_time
                engine0 = pool.engine_stats()
                self._train_busy = 0.0
                self._losses = []
                self._tile_shares = []
                acc = GradAccumulator()
                rewards_seen: List[float] = []
                trained_tokens = 0
                self.monitor.max_staleness_seen = 0

                if mode in ("sync", "async"):
                    def submit():
                        nonlocal key, next_submit
                        key, k_t = jax.random.split(key)
                        self.generator.submit_batch(batches[t], k_t,
                                                    self.tri.version)
                        next_submit = t + 1

                    self._sync_boundary(submit)
                    n_expect = len(batches[t])
                    if mode == "sync":
                        self.generator.join()        # full-batch barrier
                    groups = []
                    for _ in range(n_expect):
                        groups.append(self.queue.get())
                        if mode == "async":
                            g = groups[-1]
                            self.monitor.check(g, self.tri.version)
                            rewards_seen.extend(g.rewards.tolist())
                            trained_tokens += self._train_group(g, acc)
                    if mode == "sync":
                        groups.sort(key=lambda g: g.uid)  # prompt order
                        for g in groups:
                            self.monitor.check(g, self.tri.version)
                            rewards_seen.extend(g.rewards.tolist())
                            trained_tokens += self._train_group(g, acc)
                else:  # async_offpolicy (AReaL-like, staleness <= eta)
                    def submit():
                        nonlocal key, next_submit
                        while (next_submit <= t + eta
                               and next_submit < len(batches)):
                            key, k_t = jax.random.split(key)
                            self.generator.submit_batch(
                                batches[next_submit], k_t, self.tri.version)
                            next_submit += 1

                    self._sync_boundary(submit)
                    for _ in range(len(batches[t])):
                        g = self.queue.get()
                        self.monitor.check(g, self.tri.version)
                        rewards_seen.extend(g.rewards.tolist())
                        trained_tokens += self._train_group(g, acc)

                self._finish_iteration(acc)
                wall = time.perf_counter() - it_start
                otrace.complete("iteration", it_start, it_start + wall,
                                iteration=start + t, mode=mode)
                # per-iteration engine-stat deltas (spec acceptance, prefix
                # hit rate, page reclamation, decode steps and the host
                # seconds of their phases) surfaced through the same
                # metrics path as sync_gap — zero when no paged engine runs
                engine1 = pool.engine_stats()
                d = {k: engine1[k] - engine0[k] for k in engine1}
                spec_acceptance = (d["accepted_tokens"] / d["drafted_tokens"]
                                   if d["drafted_tokens"] else 0.0)
                prefix_probes = d["prefix_hit_pages"] + d["prefix_miss_pages"]
                prefix_hit_rate = (d["prefix_hit_pages"] / prefix_probes
                                   if prefix_probes else 0.0)
                # repro: allow(host-sync): once per iteration, after the
                # update has materialised
                losses, tile_shares = jax.device_get(
                    (self._losses, self._tile_shares))
                stats = IterationStats(
                    iteration=start + t, wall_time=wall,
                    # producer busy-time delta over this iteration — in
                    # async modes the wall clock overlaps inference with
                    # training, so only the instances' own occupancy
                    # measures inference cost
                    infer_time=pool.busy_time - busy0,
                    # consumer busy-time only (grad steps + boundary
                    # update) — NOT wall-since-first-get, which in async
                    # mode counts time spent blocked on the producer
                    # inside queue.get()
                    train_time=self._train_busy,
                    trained_tokens=trained_tokens,
                    reward_mean=(float(np.mean(rewards_seen))
                                 if rewards_seen else 0.0),
                    tpspd=trained_tokens / wall / self.num_devices,
                    max_staleness=self.monitor.max_staleness_seen,
                    # boundary sync-gap: time the pool sat idle waiting for
                    # this iteration's weight flip (weight-plane barrier)
                    metrics={"sync_gap": self.transfer.last_gap,
                             "spec_acceptance": spec_acceptance,
                             "prefix_hit_rate": prefix_hit_rate,
                             "pages_reclaimed": d["reclaimed_pages"],
                             "decode_steps": d["decode_steps"],
                             "decode_host_s": d["decode_host_s"],
                             "decode_launch_s": d["decode_launch_s"],
                             "decode_wait_s": d["decode_wait_s"],
                             "decode_gap_s": d["decode_gap_s"],
                             "loss": float(np.mean(losses)),
                             # share of attention tiles the grad steps
                             # computed (1.0 on the chunked path)
                             "attn_tile_share": float(np.mean(tile_shares)),
                             "grad_norm": float(self._grad_norm)})
                self.history.append(stats)
                self._m_iteration.set(start + t + 1)
                self._m_trained_tokens.add(trained_tokens)
                self._m_tpspd.set(stats.tpspd)
                consumed_upto = t + 1
        except BaseException:
            # mid-iteration unwind (producer put_error surfaced by
            # queue.get, staleness assert, ...): the pipeline cannot be
            # resumed by another run() — poison re-entry (see __init__)
            self._failed = True
            raise
        finally:
            # record submitted-but-unconsumed batches: on the happy path
            # this is the eta-lookahead tail the next call consumes first;
            # after an error it is diagnostic only (run() refuses re-entry)
            self._inflight = batches[consumed_upto:next_submit]
            self._key = key
            # join any background bucket stream BEFORE unwinding — a
            # daemon thread mid-device_put at interpreter teardown aborts
            # the runtime. On the happy path a failed stream's error
            # surfaces here AND poisons re-entry (groups of in-flight
            # eta-lookahead batches may already be queued — resubmitting
            # them would double-train); when already unwinding, the
            # original exception wins.
            try:
                self.transfer.drain()
            except Exception:
                if not self._failed:
                    self._failed = True
                    raise
        self.generator.join()
        return self.history[start:]
