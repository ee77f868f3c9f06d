"""Request-driven serving tier: the inference half of the decoupled
deployment, runnable standalone.

Engines (DESIGN.md §Continuous-batching):
  * fixed  — the jitted group-at-a-time Sampler (every row decodes max_new
             steps; finished rows ride along as PAD);
  * paged  — token-level continuous batching over the paged KV pool: slots
             free at EOS and admit the next request the same step;
             ``--prefix-cache`` layers the radix prefix cache on top
             (DESIGN.md §Radix-prefix-cache), ``--spec`` the draft/verify
             plane (§Spec-decode).

The ``RequestDriver`` closes the gap between "drive a fixed batch" and the
workload the serving tier exists for: requests ARRIVE over time (Poisson or
an explicit trace), stream their tokens as the engine commits them, and are
measured by the latency metrics serving systems quote — time-to-first-token
(TTFT) and time-per-output-token (TPOT), p50/p99
(``benchmarks/table9_serving.py``).

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \
        --num-requests 8 --max-new 24 [--engine paged --slots 4] \
        [--prefix-cache] [--rate 4.0]
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, reduced_config
from repro.data.tasks import ArithmeticTask
from repro.data.tokenizer import Tokenizer
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init
from repro.obs import trace as otrace
from repro.rl.rollout import Sampler


# ---------------------------------------------------------------------
# request stream + latency metrics
# ---------------------------------------------------------------------


@dataclasses.dataclass
class ServedRequest:
    """One request through the driver: its schedule, its streamed tokens,
    and the timestamps the latency metrics are computed from (all times
    are seconds on the driver's clock, origin at ``run`` start)."""
    rid: int
    prompt: np.ndarray
    arrival: float                     # scheduled arrival offset
    max_new: Optional[int] = None
    submit_t: Optional[float] = None   # when the engine accepted it
    tokens: List[int] = dataclasses.field(default_factory=list)
    token_t: List[float] = dataclasses.field(default_factory=list)
    done_t: Optional[float] = None

    @property
    def ttft(self) -> Optional[float]:
        """Time to first token, measured from ARRIVAL (queueing included —
        that is the latency the client observes)."""
        return self.token_t[0] - self.arrival if self.token_t else None

    @property
    def tpot(self) -> Optional[float]:
        """Mean inter-token time after the first token."""
        if len(self.token_t) < 2:
            return None
        return (self.token_t[-1] - self.token_t[0]) / (len(self.token_t) - 1)


def poisson_arrivals(n: int, rate: float, seed: int = 0) -> np.ndarray:
    """Arrival offsets (seconds) for an open-loop Poisson process of
    ``rate`` requests/second; ``rate <= 0`` means all arrive at t=0."""
    if rate <= 0:
        return np.zeros(n)
    rng = np.random.RandomState(seed)
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def compute_latency_metrics(reqs: List[ServedRequest]) -> Dict[str, float]:
    """p50/p99 TTFT and TPOT + throughput over a finished request set.
    Pure numpy over the recorded timestamps — tests/test_serving.py checks
    it against an independent recomputation on a scripted trace."""
    ttft = np.asarray([r.ttft for r in reqs if r.ttft is not None])
    tpot = np.asarray([r.tpot for r in reqs if r.tpot is not None])

    def pct(xs, q):
        return float(np.percentile(xs, q)) if len(xs) else 0.0

    done = [r.done_t for r in reqs if r.done_t is not None]
    toks = sum(len(r.tokens) for r in reqs)
    makespan = max(done) if done else 0.0
    return {
        "n_requests": len(reqs),
        "generated_tokens": toks,
        "makespan_s": makespan,
        "tok_per_s": toks / makespan if makespan > 0 else 0.0,
        "ttft_mean_s": float(ttft.mean()) if len(ttft) else 0.0,
        "ttft_p50_s": pct(ttft, 50), "ttft_p99_s": pct(ttft, 99),
        "tpot_mean_s": float(tpot.mean()) if len(tpot) else 0.0,
        "tpot_p50_s": pct(tpot, 50), "tpot_p99_s": pct(tpot, 99),
    }


class _WallClock:
    def time(self) -> float:
        return time.time()

    def sleep(self, seconds: float) -> None:
        time.sleep(seconds)


class RequestDriver:
    """Open-loop request-queue driver over a paged engine built with
    ``group_size=1``: submits each request when its arrival time comes due,
    steps the engine (continuous batching admits into free slots), and
    records per-token delivery times through the engine's ``on_token``
    streaming hook — tokens arrive in commit order, so TTFT/TPOT read
    straight off the timestamp lists.

    ``clock`` is injectable (``time``/``sleep``) so tests drive a virtual
    clock over a scripted trace; the default is the wall clock. Per-request
    sampling keys are ``fold_in(key, rid)`` — scheduling-order-invariant,
    like every engine here (DESIGN.md §Exactness)."""

    def __init__(self, engine, *, clock=None):
        assert engine.G == 1, "RequestDriver serves 1-row groups"
        self.eng = engine
        self.clock = clock if clock is not None else _WallClock()

    def run(self, requests: List[ServedRequest], key) -> List[ServedRequest]:
        reqs = sorted(requests, key=lambda r: (r.arrival, r.rid))
        pending = deque(reqs)
        handles: Dict[int, object] = {}
        t0 = self.clock.time()

        def now() -> float:
            return self.clock.time() - t0

        def sink(r: ServedRequest):
            def deliver(row_idx: int, token_id: int) -> None:
                r.tokens.append(int(token_id))
                r.token_t.append(now())
                # lifecycle instant per committed token (serving only —
                # fires from the engine's drain, already off the hot tier)
                otrace.instant("request.token", rid=r.rid)
            return deliver

        while pending or not self.eng.idle:
            while pending and pending[0].arrival <= now():
                r = pending.popleft()
                r.submit_t = now()
                # async span: opens at submit, closes (possibly from the
                # completion sweep below) when the request finishes; the
                # driver-clock offsets let the analyzer walk TTFT back to
                # the open-loop arrival, queueing included
                otrace.begin("request", uid=r.rid, rid=r.rid,
                             arrival=r.arrival, submit=r.submit_t)
                handles[r.rid] = self.eng.submit(
                    r.prompt, jax.random.fold_in(key, r.rid),
                    max_new=r.max_new, on_token=sink(r))
            if not self.eng.step() and pending:
                # engine drained before the next arrival: sleep up to it
                self.clock.sleep(max(0.0, pending[0].arrival - now()))
        t_end = now()
        for r in reqs:
            h = handles[r.rid]
            h.result(timeout=0)       # completion check (raises if not)
            r.done_t = r.token_t[-1] if r.token_t else t_end
            otrace.end("request", uid=r.rid, rid=r.rid, done=r.done_t,
                       tokens=len(r.tokens))
            # the committed tokens are already host-side (the same arrays
            # the RolloutBatch was assembled from) — no device readback
            # needed for the streamed==final identity check
            final = list(map(int, h.host_rows()[0]))
            assert final == r.tokens, \
                f"streaming delivery diverged from the final response " \
                f"for request {r.rid}"
        return reqs


# ---------------------------------------------------------------------
# batch entry points
# ---------------------------------------------------------------------


def _settle_batch(out, t0: float):
    """Boundary settle for one served batch: barrier on the responses and
    read the token count — the syncs live HERE, one call frame below the
    serving entry point, so the hot tier itself stays sync-free
    (DESIGN.md §Device-resident-decode)."""
    # repro: allow(host-sync): wall-clock measurement barrier — tok/s is
    # meaningless unless the batch actually finished
    jax.block_until_ready(out.response_ids)
    wall = time.time() - t0
    # repro: allow(host-sync): once per served batch, for the stats dict
    toks = int(np.asarray(out.response_len).sum())
    return wall, toks


def serve_batch(cfg, prompts, *, max_prompt_len: int, max_new: int,
                temperature: float = 0.7, seed: int = 0):
    """Serve a batch of requests; returns (responses, stats)."""
    params = init(jax.random.PRNGKey(seed), cfg)
    # serving has no trainer consuming behavior logprobs — skip capture
    sampler = Sampler(cfg, max_prompt_len, max_new, temperature=temperature,
                      capture_logprobs=False)
    t0 = time.time()
    out = sampler.generate(params, prompts, jax.random.PRNGKey(seed + 1))
    wall, toks = _settle_batch(out, t0)
    return out, {"wall_s": wall, "generated_tokens": toks,
                 "tok_per_s": toks / wall}


def build_paged_engine(cfg, *, max_prompt_len: int, max_new: int,
                       num_slots: int = 4, page_size: int = 16,
                       temperature: float = 0.7, seed: int = 0,
                       spec_k: int = 0, spec_draft: str = "prompt_lookup",
                       prefix_cache: bool = False, extra_pages: int = 0,
                       drain_interval: int = 1):
    """One serving-shaped paged engine (group_size=1, no capture): enough
    pages for every slot to hold a full prompt + response, plus headroom
    for the radix tree to keep cached prompt pages resident (idle cached
    pages are LRU-evicted on a deficit either way)."""
    from repro.core.paged import FIRST_PAGE, PagedGroupEngine
    if num_slots < 1 or page_size < 1:
        raise ValueError(f"serving needs num_slots >= 1 and "
                         f"page_size >= 1, got {num_slots}/{page_size}")
    n_pp = -(-max_prompt_len // page_size)
    n_rp = -(-max_new // page_size)
    pages = FIRST_PAGE + num_slots * (n_pp + n_rp) + extra_pages
    if prefix_cache:
        pages += n_pp            # headroom: one cached prompt stays resident
    return PagedGroupEngine(cfg, num_slots=num_slots, page_size=page_size,
                            num_pages=pages, max_prompt_len=max_prompt_len,
                            max_new_tokens=max_new, group_size=1,
                            temperature=temperature,
                            capture_logprobs=False,   # serving: no consumer
                            spec_k=spec_k, spec_draft=spec_draft,
                            prefix_cache=prefix_cache,
                            drain_interval=drain_interval, seed=seed)


def serve_paged(cfg, prompts, *, max_prompt_len: int, max_new: int,
                num_slots: int = 4, page_size: int = 16,
                temperature: float = 0.7, seed: int = 0,
                spec_k: int = 0, spec_draft: str = "prompt_lookup",
                prefix_cache: bool = False):
    """Serve independent requests through the token-level paged engine
    (each request is its own group of size 1); returns (completions in
    completion order, stats). ``spec_k`` > 0 turns on speculative decode
    (DESIGN.md §Spec-decode); ``prefix_cache`` the radix prefix cache
    (§Radix-prefix-cache) — stats then report hit rate."""
    params = init(jax.random.PRNGKey(seed), cfg)
    eng = build_paged_engine(
        cfg, max_prompt_len=max_prompt_len, max_new=max_new,
        num_slots=num_slots, page_size=page_size, temperature=temperature,
        seed=seed, spec_k=spec_k, spec_draft=spec_draft,
        prefix_cache=prefix_cache)
    t0 = time.time()
    done = eng.serve(params, prompts, jax.random.PRNGKey(seed + 1))
    wall = time.time() - t0
    toks = sum(len(c.response_ids) for c in done)
    stats = {"wall_s": wall, "generated_tokens": toks,
             "tok_per_s": toks / wall, "decode_steps": eng.decode_steps}
    if spec_k:
        # tokens committed per PER-ROW verify forward (1.0 = no spec win;
        # up to k+1 on a clean sweep) — engine steps batch many rows, so
        # decode_steps alone would conflate batching with speculation
        stats.update(spec_k=spec_k, acceptance_rate=eng.acceptance_rate,
                     tokens_per_forward=(toks / eng.spec_steps
                                         if eng.spec_steps else 0.0))
    if prefix_cache:
        stats.update(prefix_hit_rate=eng.prefix_hit_rate,
                     prefix_hit_pages=eng.prefix_hit_pages,
                     prefix_evicted_pages=eng.prefix_evicted_pages)
    return done, stats


def serve_shared(cfg, system_prompt, suffixes, *, max_prompt_len: int,
                 max_new: int, page_size: int = 16,
                 temperature: float = 0.7, seed: int = 0,
                 spec_k: int = 0, spec_draft: str = "prompt_lookup"):
    """Serve N requests that share one system prompt through the RADIX
    PREFIX CACHE (DESIGN.md §Radix-prefix-cache): each request submits its
    FULL prompt (system + its own suffix) as an independent 1-row group;
    the first admission prefills the system pages cold and inserts them
    into the tree, every later request retains those cached pages with a
    refcount bump and prefills only its own suffix into private pages — a
    real suffix prefill, replacing the old teacher-forced-token workaround
    (the suffix is prompt, not forced "response"; tests/test_radix.py
    keeps the regression proof that both emit identical tokens greedily).

    Returns (completions, stats incl. the prompt pages the cache saved vs
    N private prompt copies)."""
    from repro.core.cbatch import Completed
    N = len(suffixes)
    params = init(jax.random.PRNGKey(seed), cfg)
    full_mpl = max(len(system_prompt) + len(s) for s in suffixes)
    eng = build_paged_engine(
        cfg, max_prompt_len=max(max_prompt_len, full_mpl), max_new=max_new,
        num_slots=N, page_size=page_size, temperature=temperature,
        seed=seed, spec_k=spec_k, spec_draft=spec_draft, prefix_cache=True)
    eng.set_params(params)
    system = np.asarray(system_prompt, np.int32)
    t0 = time.time()
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), N)
    handles = [eng.submit(np.concatenate([system,
                                          np.asarray(suf, np.int32)]), k)
               for suf, k in zip(suffixes, keys)]
    while eng.step():
        pass
    wall = time.time() - t0
    done = []
    for i, h in enumerate(handles):
        h.result(timeout=0)           # completion check (raises if not)
        # committed tokens are already host-side in host_rows — no device
        # readback needed to assemble completions
        done.append(Completed(request_id=i,
                              response_ids=h.host_rows()[0],
                              finish_step=h.finish_step))
    toks = sum(len(c.response_ids) for c in done)
    stats = {"wall_s": wall, "generated_tokens": toks,
             "tok_per_s": toks / wall, "decode_steps": eng.decode_steps,
             "prefix_hit_rate": eng.prefix_hit_rate,
             # pages served from the tree = prompt pages NOT re-prefilled
             # (the analogue of the old forced path's pages-saved stat)
             "prompt_pages_saved": eng.prefix_hit_pages,
             "peak_pages": eng.peak_pages_used}
    if spec_k:
        stats.update(spec_k=spec_k, acceptance_rate=eng.acceptance_rate)
    return done, stats


def serve_requests(cfg, prompts, *, max_prompt_len: int, max_new: int,
                   num_slots: int = 4, page_size: int = 16,
                   temperature: float = 0.7, seed: int = 0,
                   spec_k: int = 0, spec_draft: str = "prompt_lookup",
                   prefix_cache: bool = False, rate: float = 0.0,
                   arrivals: Optional[np.ndarray] = None,
                   params=None, engine=None):
    """Serve ``prompts`` as a TIMED request stream through the
    ``RequestDriver`` (Poisson arrivals at ``rate`` req/s, or an explicit
    ``arrivals`` offset trace); returns (requests with per-token
    timestamps, latency metrics, engine stats). The workload
    ``benchmarks/table9_serving.py`` measures."""
    if params is None:
        params = init(jax.random.PRNGKey(seed), cfg)
    if engine is None:
        engine = build_paged_engine(
            cfg, max_prompt_len=max_prompt_len, max_new=max_new,
            num_slots=num_slots, page_size=page_size,
            temperature=temperature, seed=seed, spec_k=spec_k,
            spec_draft=spec_draft, prefix_cache=prefix_cache)
    engine.set_params(params)
    if arrivals is None:
        arrivals = poisson_arrivals(len(prompts), rate, seed=seed)
    reqs = [ServedRequest(rid=i, prompt=np.asarray(p, np.int32),
                          arrival=float(t))
            for i, (p, t) in enumerate(zip(prompts, arrivals))]
    driver = RequestDriver(engine)
    driver.run(reqs, jax.random.PRNGKey(seed + 1))
    metrics = compute_latency_metrics(reqs)
    stats = {"decode_steps": engine.decode_steps,
             "peak_pages": engine.peak_pages_used}
    if engine.radix is not None:
        stats.update(prefix_hit_rate=engine.prefix_hit_rate,
                     prefix_hit_pages=engine.prefix_hit_pages,
                     prefix_evicted_pages=engine.prefix_evicted_pages)
    if spec_k:
        stats.update(spec_k=spec_k, acceptance_rate=engine.acceptance_rate)
    return reqs, metrics, stats


def main(argv: Optional[list] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b", choices=ARCH_IDS)
    ap.add_argument("--engine", default="fixed", choices=["fixed", "paged"])
    ap.add_argument("--num-requests", type=int, default=8)
    ap.add_argument("--max-prompt-len", type=int, default=48)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots (paged engine)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--spec", action="store_true",
                    help="speculative decode (paged engine; DESIGN.md "
                         "§Spec-decode) — stats report acceptance rate")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="drafted tokens per verify step")
    ap.add_argument("--spec-draft", default="prompt_lookup",
                    choices=["prompt_lookup", "model"])
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache over the paged pool "
                         "(DESIGN.md §Radix-prefix-cache) — requests "
                         "sharing a token prefix share its pages")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate (req/s) — run the request "
                         "driver and report TTFT/TPOT p50/p99 (paged "
                         "engine; 0 = all requests arrive at once, "
                         "batch mode)")
    ap.add_argument("--shared-system", type=int, default=0, metavar="N",
                    help="serve N requests sharing one system prompt "
                         "through the radix prefix cache (suffix-only "
                         "prefill into private pages)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default="",
                    help="write a Chrome/Perfetto trace of the run to this "
                         "path (request lifecycle, drain blocks, spec "
                         "steps); inspect with `repro-trace report`")
    ap.add_argument("--trace-dir", default="",
                    help="streaming trace export: rotate JSONL segments "
                         "into this directory (bounded tracer memory); "
                         "analyze with `repro-trace report <dir>`")
    ap.add_argument("--serve-port", type=int, default=None,
                    help="boot the live ops front-end on this port "
                         "(0 = ephemeral) and serve real socket requests: "
                         "POST /v1/generate streams tokens via SSE, "
                         "GET /metrics is Prometheus-scrapeable, "
                         "/healthz + /status introspect the engine "
                         "(obs/server.py; ctrl-C to stop)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if not (args.trace or args.trace_dir):
        _cli_run(args)
        return
    # tracing wraps the whole run so every early-return (and crash) path
    # still exports — flush-on-crash for serving runs
    if args.trace_dir:
        otrace.install(process_name="repro-serve", stream_dir=args.trace_dir)
    else:
        otrace.install(process_name="repro-serve")
    try:
        _cli_run(args)
    finally:
        if args.trace_dir:
            otrace.export()
            print(f"trace segments written to {args.trace_dir}")
        else:
            otrace.export(args.trace)
            print(f"trace written to {args.trace}")
        otrace.uninstall()


def _cli_run(args) -> None:
    cfg = reduced_config(get_config(args.arch))
    tok = Tokenizer(cfg.vocab_size)
    task = ArithmeticTask(seed=args.seed)
    spec_k = args.spec_k if args.spec else 0
    if spec_k and args.engine != "paged" and not args.shared_system:
        raise SystemExit("--spec rides the paged engine here; add "
                         "--engine paged (or --shared-system N)")
    if (args.prefix_cache or args.rate) and args.engine != "paged" \
            and not args.shared_system:
        raise SystemExit("--prefix-cache/--rate ride the paged engine; "
                         "add --engine paged (or --shared-system N)")

    if args.serve_port is not None:
        # the live ops front-end (DESIGN.md §Observability): real socket
        # requests stream through the same paged engine + fold_in(key,
        # rid) derivation as the in-process RequestDriver, so a served
        # request is bitwise-identical to the driver path
        from repro.obs.server import OpsServer
        params = init(jax.random.PRNGKey(args.seed), cfg)
        eng = build_paged_engine(
            cfg, max_prompt_len=args.max_prompt_len, max_new=args.max_new,
            num_slots=args.slots, page_size=args.page_size, seed=args.seed,
            spec_k=spec_k, spec_draft=args.spec_draft,
            prefix_cache=args.prefix_cache)
        eng.set_params(params)
        srv = OpsServer(engine=eng, key=jax.random.PRNGKey(args.seed + 1),
                        port=args.serve_port)
        srv.start()
        print(f"serving {args.arch} on {srv.url}\n"
              f"  POST {srv.url}/v1/generate "
              f'{{"prompt": [1,2,3], "max_new": {args.max_new}}} (SSE)\n'
              f"  GET  {srv.url}/metrics /healthz /status\n"
              f"ctrl-C to stop")
        try:
            while True:
                time.sleep(1.0)
        except KeyboardInterrupt:
            pass
        finally:
            srv.stop()
        return

    if args.shared_system:
        # shared-system-prompt scenario: the radix tree serves every
        # request's system pages from cache after the first admission
        system = np.asarray(
            tok.encode("You are a terse arithmetic solver. ")[
                : args.max_prompt_len], np.int32)
        problems = task.batch(args.shared_system)
        suffixes = [np.asarray(tok.encode(p.prompt)[: args.max_new // 2],
                               np.int32) for p in problems]
        done, stats = serve_shared(
            cfg, system, suffixes, max_prompt_len=args.max_prompt_len,
            max_new=args.max_new, page_size=args.page_size, seed=args.seed,
            spec_k=spec_k, spec_draft=args.spec_draft)
        extra = (f", accept={stats['acceptance_rate']:.2f}"
                 if spec_k else "")
        print(f"{args.arch} (shared-system x{args.shared_system}): "
              f"{stats['generated_tokens']} tokens in "
              f"{stats['wall_s']:.2f}s ({stats['tok_per_s']:.1f} tok/s, "
              f"{stats['decode_steps']} decode steps, "
              f"prefix hit rate {stats['prefix_hit_rate']:.2f}, "
              f"{stats['prompt_pages_saved']} prompt pages saved by "
              f"the cache{extra})")
        for c in done[:4]:
            print(f"  req {c.request_id}: "
                  f"{tok.decode(c.response_ids.tolist())!r}")
        return

    problems = task.batch(args.num_requests)
    prompts = [np.asarray(tok.encode(p.prompt)[: args.max_prompt_len],
                          np.int32) for p in problems]

    if args.engine == "paged" and args.rate > 0:
        reqs, metrics, stats = serve_requests(
            cfg, prompts, max_prompt_len=args.max_prompt_len,
            max_new=args.max_new, num_slots=args.slots,
            page_size=args.page_size, seed=args.seed, spec_k=spec_k,
            spec_draft=args.spec_draft, prefix_cache=args.prefix_cache,
            rate=args.rate)
        hit = (f", prefix hit rate {stats['prefix_hit_rate']:.2f}"
               if args.prefix_cache else "")
        print(f"{args.arch} (driver x{args.slots} @ {args.rate} req/s): "
              f"{metrics['generated_tokens']} tokens, "
              f"TTFT p50={metrics['ttft_p50_s'] * 1e3:.0f}ms "
              f"p99={metrics['ttft_p99_s'] * 1e3:.0f}ms, "
              f"TPOT p50={metrics['tpot_p50_s'] * 1e3:.1f}ms "
              f"p99={metrics['tpot_p99_s'] * 1e3:.1f}ms, "
              f"{metrics['tok_per_s']:.1f} tok/s{hit}")
        for r in reqs[:4]:
            print(f"  req {r.rid} arrived {r.arrival:.2f}s "
                  f"ttft {r.ttft:.2f}s: {tok.decode(r.tokens)!r}")
        return

    if args.engine == "paged":
        done, stats = serve_paged(
            cfg, prompts, max_prompt_len=args.max_prompt_len,
            max_new=args.max_new, num_slots=args.slots,
            page_size=args.page_size, seed=args.seed,
            spec_k=spec_k, spec_draft=args.spec_draft,
            prefix_cache=args.prefix_cache)
        extra = (f", accept={stats['acceptance_rate']:.2f}, "
                 f"{stats['tokens_per_forward']:.2f} tok/forward"
                 if spec_k else "")
        if args.prefix_cache:
            extra += f", prefix hit rate {stats['prefix_hit_rate']:.2f}"
        print(f"{args.arch} (paged x{args.slots}"
              f"{f' spec k={spec_k}' if spec_k else ''}): {len(done)} "
              f"requests in completion order, "
              f"{stats['generated_tokens']} tokens in "
              f"{stats['wall_s']:.2f}s ({stats['tok_per_s']:.1f} tok/s, "
              f"{stats['decode_steps']} decode steps{extra})")
        for c in done[:4]:
            print(f"  req {c.request_id} finished at step {c.finish_step}: "
                  f"{tok.decode(c.response_ids.tolist())!r}")
        return

    out, stats = serve_batch(cfg, prompts, max_prompt_len=args.max_prompt_len,
                             max_new=args.max_new, seed=args.seed)
    print(f"{args.arch}: served {args.num_requests} requests, "
          f"{stats['generated_tokens']} tokens in {stats['wall_s']:.2f}s "
          f"({stats['tok_per_s']:.1f} tok/s)")
    # repro: allow(host-sync): final result printing after the run
    resp = np.asarray(out.response_ids)
    # repro: allow(host-sync): final result printing after the run
    lens = np.asarray(out.response_len)
    for i in range(min(4, len(problems))):
        text = tok.decode(resp[i, : lens[i]])
        print(f"  [{problems[i].prompt!r}] -> {text!r}")


if __name__ == "__main__":
    main()
