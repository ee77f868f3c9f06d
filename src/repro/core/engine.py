"""Inference service: a pool of independent engine instances with
iteration-boundary weight synchronisation (the decoupled deployment of
paper §4.1 — 'vLLM for inference, Megatron for training').

Three execution modes per instance:
  * real / group  — the jitted Sampler generates a whole group at a time
             (JAX releases the GIL during compute, so producer threads
             overlap with the consumer's training compute);
  * real / paged  — token-level continuous batching over a paged KV cache
             (core/paged.py): concurrent group requests from the generator's
             workers decode together one token per step, short rollouts
             free their slots early, and the GRPO group's prompt is stored
             once. Worker threads drive the engine convoy-style: whoever
             waits on a group steps the engine under the instance lock, so
             no dedicated decode thread exists and the engine goes quiet
             exactly when no requests are in flight (weight sync stays an
             iteration-boundary event — Proposition 1 intact);
  * simulated — the instance sleeps according to a latency model and returns
             scripted responses. This is the trainer's-eye view of a REMOTE
             inference deployment (inference on separate devices), and is
             what the throughput benchmarks use so results reflect pipeline
             structure rather than this container's single CPU core.

Weights live in a :class:`~repro.transfer.service.VersionedParamStore` per
instance: readers take an atomic (params, version) snapshot, and the
weight-plane service streams versioned buckets into the store's back
buffer (DESIGN.md §Weight-plane). ``sync_weights`` remains as the eager
whole-tree path (tests / serving), built on the same store so the
(params, version) pair can never tear.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, List, Optional

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.paged import PagedGroupEngine
from repro.obs import trace as otrace
from repro.rl.rollout import RolloutBatch, Sampler
from repro.transfer.service import VersionedParamStore


class InferenceInstance:
    def __init__(self, inst_id: int, cfg: ModelConfig, sampler: Optional[Sampler],
                 latency_fn: Optional[Callable] = None,
                 scripted_fn: Optional[Callable] = None,
                 paged_engine: Optional[PagedGroupEngine] = None):
        self.inst_id = inst_id
        self.cfg = cfg
        self.sampler = sampler
        self.latency_fn = latency_fn
        self.scripted_fn = scripted_fn
        self.paged_engine = paged_engine
        assert paged_engine is None or scripted_fn is None, \
            "paged engine runs real decode; simulated instances script it"
        # the paged engine's set_params asserts decode quiescence, so its
        # flips are DEFERRED to the scheduler's boundary (after the queue
        # drain) instead of landing from the stream thread
        self.store = VersionedParamStore(
            name=f"inst{inst_id}",
            on_flip=(None if paged_engine is None else paged_engine.set_params),
            defer_flip=paged_engine is not None)
        self._lock = threading.Lock()  # one request in flight per instance
        self.busy_time = 0.0
        # deferred busy clock (DESIGN.md §Device-resident-decode): the
        # generation call dispatches asynchronously, and a settle thread
        # charges the exact dispatch->ready interval later, so the hot
        # path never fences the dispatch stream on a device barrier
        self._busy_lock = threading.Lock()
        self._settles: List[threading.Thread] = []
        # settle threads that a boundary read actually had to block on —
        # completed settles deregister themselves, so repeated busy_time
        # reads between boundaries join nothing (O(1); regression-tested)
        self.settle_joins = 0

    def sync_weights(self, params, version: int) -> None:
        """Eager whole-tree publish (legacy path; the RL scheduler streams
        buckets through the weight-plane service instead)."""
        self.store.install(params, version)

    @property
    def version(self) -> int:
        return self.store.version

    def generate_group(self, prompts: List[np.ndarray], key,
                       min_version: Optional[int] = None) -> tuple:
        """Returns (RolloutBatch, weight_version).

        ``min_version`` is the rollout-side half of the weight-plane's
        version gate: the request blocks until the store's ACTIVE buffer
        holds at least that version, so overlapped bucket streaming can
        never hand an iteration-t request pre-flip weights. The (params,
        version) pair is one atomic snapshot — the version returned is
        provably the version sampled from."""
        if self.paged_engine is not None:
            return self._generate_group_paged(prompts, key, min_version)
        # group-at-a-time: serialised per instance — models single-instance
        # occupancy / continuous batching slot limits.
        with self._lock:
            # gate BEFORE the busy clock starts: time blocked waiting for
            # the weight flip is the boundary's sync-gap, not inference
            # occupancy — folding it into busy_time would contaminate
            # IterationStats.infer_time exactly the way producer waits
            # were once folded into train_time
            params, version = self.store.wait_version(min_version)
            t0 = time.perf_counter()
            if self.scripted_fn is not None:
                out = self.scripted_fn(prompts, key)
                if self.latency_fn is not None:
                    time.sleep(self.latency_fn(out))
                t1 = time.perf_counter()
                with self._busy_lock:
                    self.busy_time += t1 - t0
                otrace.complete("producer.busy", t0, t1, busy=t1 - t0,
                                inst=self.inst_id,
                                track=f"producer/inst{self.inst_id}")
            else:
                assert self.sampler is not None and params is not None
                out = self.sampler.generate(params, prompts, key)
                # busy-clock charge is DEFERRED: the settle thread blocks
                # on the arrays so this hot path doesn't serialize the
                # dispatch stream; the boundary read (pool.busy_time)
                # flushes pending settles first
                self._defer_busy(t0, out.response_ids)
            return out, version

    def _defer_busy(self, t0: float, arrays) -> None:
        """Charge the busy clock off the dispatch path: a daemon settle
        thread waits for ``arrays`` and adds the exact dispatch->ready
        interval under the busy lock. A completed settle deregisters
        itself, so only genuinely in-flight settles remain for
        ``flush_busy`` to join at the iteration boundary (where the queue
        is already drained, so those joins return immediately)."""
        def settle():
            # repro: allow(host-sync): busy-clock barrier DELIBERATELY
            # moved off the dispatch path into this settle thread — the
            # hot path no longer blocks (§Device-resident-decode)
            jax.block_until_ready(arrays)
            t1 = time.perf_counter()
            with self._busy_lock:
                self.busy_time += t1 - t0
                self._settles.remove(th)  # deregister: nothing to rejoin
            # producer busy span from the deferred clock's own endpoints —
            # no new barrier, no timestamp invented on the dispatch path
            otrace.complete("producer.busy", t0, t1, busy=t1 - t0,
                            inst=self.inst_id,
                            track=f"producer/inst{self.inst_id}")
        th = threading.Thread(target=settle, daemon=True,
                              name=f"busy-settle-{self.inst_id}")
        with self._busy_lock:
            self._settles.append(th)
        th.start()

    def flush_busy(self) -> None:
        """Join pending busy-clock settles (boundary accounting barrier —
        NOT on the per-request path). Settles that already completed have
        deregistered themselves, so between boundaries this is a single
        lock acquisition and an empty-list check."""
        while True:
            with self._busy_lock:
                if not self._settles:
                    return
                th = self._settles[-1]
                self.settle_joins += 1
            th.join()

    def status(self) -> dict:
        """Live introspection for the ops plane: identity, weight-plane
        version (atomic via the store), and the busy clock read under
        its own lock — one consistent row of ``/status``'s per-instance
        table."""
        with self._busy_lock:
            busy = self.busy_time
            in_flight_settles = len(self._settles)
        out = {"inst_id": self.inst_id,
               "weight_version": self.store.version,
               "busy_s": busy,
               "in_flight_settles": in_flight_settles,
               "mode": ("paged" if self.paged_engine is not None else
                        "simulated" if self.scripted_fn is not None
                        else "group")}
        if self.paged_engine is not None:
            out["engine"] = self.paged_engine.status_snapshot()
        return out

    def _generate_group_paged(self, prompts: List[np.ndarray], key,
                              min_version: Optional[int] = None) -> tuple:
        """Token-level path: submit the group, then help drive the shared
        engine until it completes. Concurrent callers' groups share decode
        steps — the engine lock serialises single steps, not whole groups.
        Each step is a ``producer.step`` span and the wait for the lock
        before it a ``producer.lock_wait`` span, on the driving thread."""
        eng = self.paged_engine
        assert len(prompts) == eng.G, \
            f"group size {len(prompts)} != engine group_size {eng.G}"
        # the paged engine stores ONE physical prompt per group — a GRPO
        # group is G rollouts of the same prompt, so reject anything else
        # rather than silently decoding G copies of prompts[0]
        assert all(np.array_equal(p, prompts[0]) for p in prompts[1:]), \
            "paged engine serves GRPO groups: all prompts in a group must " \
            "be identical (heterogeneous requests go through separate groups)"
        # the engine holds the flipped params; set_params asserts quiescence,
        # so the version cannot change while this group is in flight
        _, version = self.store.wait_version(min_version)
        handle = eng.submit(prompts[0], key)
        drive0 = None   # first step this caller took; busy = its step time
        busy = 0.0
        t1 = 0.0
        while not handle.done():
            # entered by hand so that it closes as the lock is taken,
            # inside the `with` that holds it across the step
            waiting = otrace.span("producer.lock_wait")
            waiting.__enter__()
            with self._lock:
                waiting.__exit__(None, None, None)
                if handle.done():
                    break
                t0 = time.perf_counter()
                with otrace.span("producer.step"):
                    eng.step()
                t1 = time.perf_counter()
                if drive0 is None:
                    drive0 = t0
                busy += t1 - t0
                self.busy_time += t1 - t0
        if drive0 is not None:
            # convoy driving interleaves callers, so the span's wall extent
            # includes lock waits — the charged occupancy rides in `busy`
            # (what the analyzer sums to reproduce infer_time)
            otrace.complete("producer.busy", drive0, t1, busy=busy,
                            inst=self.inst_id,
                            track=f"producer/inst{self.inst_id}")
        return handle.result(), version


class InferencePool:
    """Evenly distributes incoming prompt groups across instances
    (paper §4.2.1: 'evenly distributes incoming prompts across available
    instances')."""

    def __init__(self, instances: List[InferenceInstance]):
        self.instances = instances
        self._rr = 0
        self._rr_lock = threading.Lock()

    def __len__(self) -> int:
        return len(self.instances)

    @property
    def token_level(self) -> bool:
        """True when instances batch at token level (paged engines) — the
        generator then benefits from more concurrent groups per instance."""
        return any(i.paged_engine is not None for i in self.instances)

    def pick(self) -> InferenceInstance:
        with self._rr_lock:
            inst = self.instances[self._rr % len(self.instances)]
            self._rr += 1
            return inst

    def sync_weights(self, params, version: int) -> None:
        """Eager per-instance publish (legacy/tests; the scheduler's
        boundary goes through ``WeightTransferService.ensure``)."""
        for inst in self.instances:
            inst.sync_weights(params, version)

    def generate_group(self, prompts, key, min_version: Optional[int] = None):
        return self.pick().generate_group(prompts, key, min_version)

    def reset_stats(self) -> None:
        for inst in self.instances:
            inst.flush_busy()    # a late settle must not leak into the
            inst.busy_time = 0.0  # next accounting window
            if inst.paged_engine is not None:
                inst.paged_engine.reset_stats()

    def engine_stats(self) -> dict:
        """Aggregated paged-engine counters across instances (atomic per
        engine). Zeros when no instance runs a paged engine, so callers
        can diff snapshots unconditionally."""
        agg = {"decode_steps": 0, "generated_tokens": 0,
               "reclaimed_pages": 0, "decode_host_s": 0.0,
               "decode_launch_s": 0.0, "decode_wait_s": 0.0,
               "decode_gap_s": 0.0, "spec_steps": 0, "drafted_tokens": 0,
               "accepted_tokens": 0, "prefix_hit_pages": 0,
               "prefix_miss_pages": 0, "prefix_evicted_pages": 0}
        for inst in self.instances:
            if inst.paged_engine is not None:
                for k, v in inst.paged_engine.stats_snapshot().items():
                    agg[k] += v
        return agg

    def status(self) -> dict:
        """Per-instance status rows + pool aggregate for ``/status``.
        Does NOT flush the deferred busy clocks (that is a boundary
        barrier) — a mid-iteration scrape reads the busy time charged so
        far, which is exactly what "live" means here."""
        rows = [inst.status() for inst in self.instances]
        return {"num_instances": len(rows),
                "token_level": self.token_level,
                "instances": rows,
                "busy_s": sum(r["busy_s"] for r in rows)}

    @property
    def busy_time(self) -> float:
        """Aggregate producer busy-time across instances (the quantity
        ``IterationStats.infer_time`` reports). Flushes the deferred busy
        clocks first — this is the boundary read, after the queue drain,
        so pending settles resolve immediately; settles that already
        completed have deregistered themselves, making repeated reads
        between boundaries O(1)."""
        for inst in self.instances:
            inst.flush_busy()
        return sum(inst.busy_time for inst in self.instances)
