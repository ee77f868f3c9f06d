"""Device-resident decode loop (DESIGN.md §Device-resident-decode).

The fused D-step decode block must be bitwise TOKEN-IDENTICAL to the
legacy one-drain-per-token cadence, which is itself token-identical to
the group Sampler — so every ``drain_interval`` is proven against the
same oracle, across the cache families (GQA / MLA latent / sliding
window), under greedy and sampled decode, with spec and the radix
prefix cache riding along. Drain edge cases get targeted tests: a row
hitting EOS in the middle of an in-flight block (the optimistic extra
steps run device-masked and must write nothing), EOS landing exactly on
a block's last buffered token, blocks that don't divide the response
budget, and slot re-assignment while a stale block drains.

The satellite contracts live here too: the deferred busy clock
(``InferenceInstance._defer_busy`` charges off the dispatch path,
``flush_busy`` joins at the boundary), the ``commit_block`` device walk
vs the host ``assemble_commit`` oracle, the ``repro-check --forbid-hot``
severity gate, and the shard_map'd dense-GQA decode step (subprocess,
like test_moe_ep.py, so forced fake devices never leak into the suite).
"""
import dataclasses
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.core.cbatch import ContinuousBatchingSampler
from repro.core.engine import InferenceInstance, InferencePool
from repro.core.paged import PagedGroupEngine
from repro.models import init
from repro.rl.rollout import Sampler
from repro.spec import SpecSampler, assemble_commit
from repro.spec.verify import commit_block

G, T, LP = 4, 8, 16


def _gqa():
    return reduced_config(get_config("llama3.2-3b"))


def _mla_nomoe():
    c = reduced_config(get_config("deepseek-v2-lite-16b"))
    return dataclasses.replace(c, num_experts=0, num_experts_per_tok=0,
                               num_shared_experts=0, moe_d_ff=0,
                               first_k_dense=0, dense_d_ff=0)


def _swa():
    return dataclasses.replace(_gqa(), sliding_window=8)


VARIANTS = {"gqa": _gqa, "mla": _mla_nomoe, "swa": _swa}


@pytest.fixture(scope="module")
def setups():
    out = {}
    for name, mk in VARIANTS.items():
        cfg = mk()
        out[name] = (cfg, init(jax.random.PRNGKey(0), cfg))
    return out


PROMPT = np.asarray([1, 9, 4, 7, 3], np.int32)


def _assert_group_identical(out, ref):
    pr, pl = np.asarray(out.response_ids), np.asarray(out.response_len)
    rr, rl = np.asarray(ref.response_ids), np.asarray(ref.response_len)
    np.testing.assert_array_equal(pl, rl)
    for i in range(rr.shape[0]):
        np.testing.assert_array_equal(pr[i, : pl[i]], rr[i, : rl[i]])


def _engine(cfg, **kw):
    base = dict(num_slots=3, page_size=4, num_pages=0, max_prompt_len=LP,
                max_new_tokens=T, group_size=G)
    base.update(kw)
    return PagedGroupEngine(cfg, **base)


def _run_group(eng, params, prompt, key):
    eng.set_params(params)
    h = eng.submit(prompt, key)
    while eng.step():
        pass
    return h.result(1)


# =========================================================================
# fused == legacy == Sampler, across families / drains / temperatures
# =========================================================================

@pytest.mark.parametrize("drain,temperature", [(2, 0.0), (3, 1.0),
                                               (8, 0.0), (8, 1.0)])
def test_paged_fused_drain_token_identical_gqa(setups, drain, temperature):
    """Every drain interval reproduces the Sampler's tokens exactly under
    the same key: D=3 doesn't divide T=8 (the last block is short), D=8
    fuses the whole budget into one block, and slots < group size force
    rows of one group into different block phases. Paged sampling draws
    per-token keys, so this holds sampled, not just greedy."""
    cfg, params = setups["gqa"]
    key = jax.random.PRNGKey(5)
    ref = Sampler(cfg, LP, T, temperature=temperature)
    eng = _engine(cfg, temperature=temperature, drain_interval=drain)
    _assert_group_identical(_run_group(eng, params, PROMPT, key),
                            ref.generate(params, [PROMPT] * G, key))


@pytest.mark.parametrize("variant", ["mla", "swa"])
@pytest.mark.parametrize("drain", [3, 8])
def test_paged_fused_drain_token_identical_backends(setups, variant, drain):
    """The cache backends the fused block must not disturb: MLA latent
    pages (absorbed-decode gather) and sliding-window reclamation, which
    the fused dispatcher performs once per block at the block's first
    query position."""
    cfg, params = setups[variant]
    key = jax.random.PRNGKey(13)
    ref = Sampler(cfg, LP, T, temperature=1.0)
    eng = _engine(cfg, temperature=1.0, drain_interval=drain)
    free0 = eng.alloc.num_free
    _assert_group_identical(_run_group(eng, params, PROMPT, key),
                            ref.generate(params, [PROMPT] * G, key))
    assert eng.alloc.num_free == free0 and eng.idle


def test_paged_spec_fused_drain_greedy_identical(setups):
    """Spec verify blocks drain per k+1-token block on their own cadence;
    a drain_interval > 1 must ride along without disturbing the spec
    path's exactness (guards against future coupling of the two knobs)."""
    cfg, params = setups["gqa"]
    key = jax.random.PRNGKey(7)
    ref = Sampler(cfg, LP, T, temperature=0.0)
    eng = _engine(cfg, temperature=0.0, spec_k=2, drain_interval=8)
    _assert_group_identical(_run_group(eng, params, PROMPT, key),
                            ref.generate(params, [PROMPT] * G, key))


def test_paged_prefix_cache_fused_identical(setups):
    """Radix prefix cache + fused blocks: warm fused serving must be
    token-identical to cold legacy serving (a cached page is bitwise the
    page a cold prefill would write; the fused block never reads one)."""
    cfg, params = setups["gqa"]
    rng = np.random.RandomState(3)
    sys_p = list(rng.randint(3, 200, size=12))
    prompts = [np.asarray(sys_p + list(rng.randint(3, 200, size=3)),
                          np.int32) for _ in range(4)]
    key = jax.random.PRNGKey(11)

    def serve(**kw):
        eng = _engine(cfg, group_size=1, num_slots=2, temperature=0.0, **kw)
        done = eng.serve(params, prompts, key)
        return {c.request_id: list(c.response_ids) for c in done}, eng

    cold, _ = serve(drain_interval=1)
    warm_eng = _engine(cfg, group_size=1, num_slots=2, temperature=0.0,
                       prefix_cache=True, drain_interval=8)
    warm_eng.serve(params, prompts, key)          # populates the tree
    done = warm_eng.serve(params, prompts, key)   # served warm
    warm = {c.request_id: list(c.response_ids) for c in done}
    assert warm == cold
    assert warm_eng.prefix_hit_pages > 0


@pytest.mark.parametrize("drain", [2, 3, 8])
def test_cbatch_fused_drain_greedy_identical(setups, drain):
    """Slot engine: the fused loop under greedy decode is token-identical
    for every D (a sampled chain legitimately realigns at D>1 — the
    per-slot key stream is consumed at different steps). Per-request caps
    force rows to stop mid-block."""
    cfg, params = setups["gqa"]
    prompts = [np.asarray([1, 9, 4, 7, 3][: 2 + i % 4], np.int32)
               for i in range(6)]
    targets = [3, 8, 5, 1, 7, 4]       # rows stop inside fused blocks
    key = jax.random.PRNGKey(2)

    def run(d):
        eng = ContinuousBatchingSampler(cfg, num_slots=2, max_prompt_len=LP,
                                        max_new_tokens=T, temperature=0.0,
                                        drain_interval=d)
        done = eng.run(params, prompts, key, max_new_per_request=targets)
        return {c.request_id: list(c.response_ids) for c in done}

    legacy = run(1)
    assert all(len(v) <= t for v, t in
               zip((legacy[i] for i in range(6)), targets))
    assert run(drain) == legacy


# =========================================================================
# drain edge cases: EOS inside / at the edge of an in-flight block
# =========================================================================

def test_paged_eos_mid_block_and_block_boundary(setups):
    """Pin EOS to exact steps by re-running with eos_id set to a token the
    no-EOS greedy stream emits: mid-block (the optimistic trailing steps
    of the in-flight block run device-masked and must contribute
    nothing), the last buffered token of a block (drain must not read
    past it), and the final budgeted step."""
    cfg, params = setups["gqa"]
    key = jax.random.PRNGKey(4)
    D = 3

    def serve_one(eos_id, drain):
        eng = _engine(cfg, group_size=1, num_slots=1, temperature=0.0,
                      eos_id=eos_id, drain_interval=drain)
        done = eng.serve(params, [PROMPT], key)
        return list(done[0].response_ids)

    stream = serve_one(-1, 1)          # eos never fires: full budget
    assert len(stream) == T
    for t_star in (D + 1, 2 * D - 1, T - 1):   # mid-block, block-last, end
        tok = stream[t_star]
        want = stream.index(tok) + 1   # first occurrence stops the row
        legacy = serve_one(tok, 1)
        fused = serve_one(tok, D)
        assert fused == legacy
        assert len(fused) == want and fused[-1] == tok


@pytest.mark.parametrize("t_star", [4, 5])
def test_paged_last_row_eos_leaves_no_block_in_flight(setups, t_star):
    """The last row stops by EOS inside a block (mid-block, and on its
    last step) while the next block is already dispatched: when its handle
    resolves, as a driving instance sees it, that masked block has been
    drained too, so the engine is quiescent for the weight sync and a
    further step finds nothing to do."""
    cfg, params = setups["gqa"]
    key = jax.random.PRNGKey(4)

    def engine(eos_id, drain):
        eng = _engine(cfg, group_size=1, num_slots=1, temperature=0.0,
                      eos_id=eos_id, drain_interval=drain)
        eng.set_params(params)
        return eng

    stream = list(engine(-1, 1).serve(params, [PROMPT], key)[0]
                  .response_ids)
    tok = stream[t_star]
    want = stream[: stream.index(tok) + 1]
    assert len(want) > 3          # the stop falls in the second block
    eng = engine(tok, 3)
    h = eng.submit(PROMPT, key)
    while not h.done():
        eng.step()
    assert list(np.asarray(h.host_rows()[0])) == want
    assert eng._pending is None and eng.idle
    assert not eng.step()


def test_paged_block_page_table_is_a_snapshot(setups):
    """The host edits its page table (new pages, evictions, admissions)
    while a pipelined block is still in flight; the table the block was
    given must keep the rows it was dispatched with. The CPU backend
    wraps a 64-byte-aligned host array without a copy, so the engine's
    table is moved to such an address first: an upload of the live table
    would then change under the block."""
    cfg, params = setups["gqa"]
    eng = _engine(cfg, temperature=1.0, drain_interval=3)
    buf = np.zeros(eng._ptab.nbytes + 64, np.uint8)
    off = -buf.ctypes.data % 64
    eng._ptab = buf[off:off + eng._ptab.nbytes].view(np.int32).reshape(
        eng._ptab.shape)
    tables = []
    decode = eng._decode

    def recorded(*args):
        tables.append((args[-1], np.array(args[-1])))
        return decode(*args)

    eng._decode = recorded
    _run_group(eng, params, PROMPT, jax.random.PRNGKey(8))
    assert len(tables) > 2
    for sent, at_dispatch in tables:
        np.testing.assert_array_equal(np.asarray(sent), at_dispatch)


def test_paged_slot_reassignment_during_stale_drain(setups):
    """More requests than slots with a large D: a row finishing inside an
    earlier block frees its slot while a later optimistic block for that
    slot is still in flight; the drain must skip the stale plan entries
    (slot re-assigned) and the admitted successor must decode exactly as
    under the legacy cadence."""
    cfg, params = setups["gqa"]
    rng = np.random.RandomState(9)
    prompts = [rng.randint(3, 200, size=(2 + i,)).astype(np.int32)
               for i in range(6)]
    key = jax.random.PRNGKey(6)

    def serve(d):
        eng = _engine(cfg, group_size=1, num_slots=2, temperature=1.0,
                      drain_interval=d)
        done = eng.serve(params, prompts, key)
        assert eng.idle
        return {c.request_id: list(c.response_ids) for c in done}

    assert serve(5) == serve(1)


# =========================================================================
# commit_block (device walk) == assemble_commit (host oracle)
# =========================================================================

def test_commit_block_matches_assemble_commit():
    rng = np.random.RandomState(0)
    B, k = 16, 4
    for trial in range(25):
        accept = rng.randint(0, 2, size=(B, k)).astype(bool)
        alt = rng.randint(0, 50, size=(B, k + 1)).astype(np.int32)
        draft = rng.randint(0, 50, size=(B, k)).astype(np.int32)
        lp_d = rng.randn(B, k).astype(np.float32)
        lp_a = rng.randn(B, k + 1).astype(np.float32)
        toks, lps, count = jax.jit(commit_block)(
            jnp.asarray(accept), jnp.asarray(alt), jnp.asarray(draft),
            jnp.asarray(lp_d), jnp.asarray(lp_a))
        toks, lps, count = jax.device_get((toks, lps, count))
        for b in range(B):
            ref_t, ref_l = assemble_commit(accept[b], alt[b], draft[b],
                                           lp_d[b], lp_a[b])
            n = int(count[b])
            assert n == len(ref_t)
            assert [int(t) for t in toks[b, :n]] == ref_t
            np.testing.assert_array_equal(lps[b, :n],
                                          np.asarray(ref_l, np.float32))
            assert not toks[b, n:].any() and not lps[b, n:].any()


# =========================================================================
# deferred busy clock
# =========================================================================

def test_busy_clock_defers_and_flushes(setups):
    """_defer_busy must not charge on the dispatch path; flush_busy (and
    the pool's boundary reads) join the settle threads and land the exact
    dispatch->ready interval."""
    cfg, _ = setups["gqa"]
    inst = InferenceInstance(0, cfg, sampler=None)
    t0 = time.perf_counter() - 0.25          # pretend dispatch was 250ms ago
    inst._defer_busy(t0, jnp.zeros((4,)))
    inst.flush_busy()
    assert not inst._settles
    assert inst.busy_time >= 0.25

    pool = InferencePool([inst])
    inst._defer_busy(time.perf_counter() - 0.5, jnp.zeros((4,)))
    # the boundary read flushes pending settles itself
    assert pool.busy_time >= 0.75
    inst._defer_busy(time.perf_counter() - 0.1, jnp.zeros((4,)))
    pool.reset_stats()                       # flush-then-zero: no leak
    assert inst.busy_time == 0.0 and not inst._settles


# =========================================================================
# repro-check --forbid-hot severity gate
# =========================================================================

HOT_SUPPRESSED = """\
import jax


class PagedGroupEngine:
    def __init__(self):
        self._decode = jax.jit(self._decode_fn)

    def step(self):
        tok = self._decode(1)
        # repro: allow(host-sync): justified, but hot tier
        return float(tok)
"""

WARM_SUPPRESSED = """\
import jax


class PagedGroupEngine:
    def __init__(self):
        self._decode = jax.jit(self._decode_fn)

    def step(self):
        tok = self._decode(1)
        return self._drain(tok)

    def _drain(self, tok):
        # repro: allow(host-sync): one buffered readback per block
        return jax.device_get(tok)
"""


def test_cli_forbid_hot_gate(tmp_path, capsys):
    """A justified pragma exempts a warm sync but NOT a hot-tier one:
    --forbid-hot fails (exit 2) on any error-severity host-sync finding,
    suppressed or not — the device-resident-decode CI gate."""
    from repro.analysis.cli import main as cli_main
    core = tmp_path / "core"
    core.mkdir()
    (core / "paged.py").write_text(HOT_SUPPRESSED)
    base = [str(core), "--root", str(tmp_path), "--checker", "host-sync"]
    assert cli_main(base) == 0                    # suppression holds...
    rc = cli_main(base + ["--forbid-hot"])        # ...but not on hot tier
    assert rc == 2
    assert "hot-tier host-sync" in capsys.readouterr().out

    (core / "paged.py").write_text(WARM_SUPPRESSED)
    assert cli_main(base + ["--forbid-hot"]) == 0


# =========================================================================
# shard_map'd dense-GQA decode (subprocess: forced fake devices)
# =========================================================================

def _usable_cpus() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:
        return os.cpu_count() or 1


SHMAP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp
from repro.models.attention import (DenseCacheBackend, gqa_attention,
                                    init_gqa, _shmap_decode_fit)
from repro.configs.base import ModelConfig
from repro.sharding.specs import use_mesh, current_mesh

cfg = ModelConfig(name="t", d_model=32, num_heads=4, num_kv_heads=2,
                  head_dim=8, num_layers=1, d_ff=64, vocab_size=64)
params = init_gqa(jax.random.PRNGKey(0), cfg, jnp.float32)
B, L = 2, 16
st = DenseCacheBackend(cfg, L).init(B, jnp.float32)
x_pre = jax.random.normal(jax.random.PRNGKey(1), (B, 4, 32))
pos = jnp.broadcast_to(jnp.arange(4), (B, 4)).astype(jnp.int32)
seg = jnp.zeros((B, 4), jnp.int32)
_, st = gqa_attention(params, cfg, x_pre, pos, seg, cache=st,
                      cache_offset=0)

xd = jax.random.normal(jax.random.PRNGKey(2), (B, 1, 32))
posd = jnp.full((B, 1), 4, jnp.int32)
segd = jnp.zeros((B, 1), jnp.int32)

# single-program reference, jitted WITHOUT a mesh -> plain GSPMD branch
ref_out, ref_st = jax.jit(lambda c: gqa_attention(
    params, cfg, xd, posd, segd, cache=c, cache_offset=4))(st)

mesh = jax.make_mesh((1, 2), ("data", "model"))
with use_mesh(mesh):
    assert _shmap_decode_fit(cfg, st, current_mesh(), 1), \
        "seq-sharded dense GQA decode must take the shard_map branch"
    for off in (4, jnp.full((B,), 4, jnp.int32)):   # both offset forms
        out, new = jax.jit(lambda c, o: gqa_attention(
            params, cfg, xd, posd, segd, cache=c, cache_offset=o))(st, off)
        err = float(jnp.abs(ref_out - out).max())
        print("out err", err)
        assert err < 1e-5, err
        for kk in ("k", "v", "pos", "seg"):
            d = float(jnp.abs(jnp.asarray(ref_st[kk], jnp.float32)
                              - jnp.asarray(new[kk], jnp.float32)).max())
            assert d == 0.0, (kk, d)    # cache write: bitwise
print("OK")
"""


@pytest.mark.skipif(
    _usable_cpus() < 2 and not os.environ.get("FORCE_SHMAP_DECODE"),
    reason="host has <2 usable cores for the forced-2-device shard_map "
           "decode check (FORCE_SHMAP_DECODE=1 overrides)")
def test_shmap_decode_matches_gspmd_reference():
    """The shard_map'd decode step (seq-sharded cache, masked local write,
    flash partial-stat combine over the mesh) must reproduce the plain
    GSPMD branch: output to fp tolerance, cache writes bitwise."""
    r = subprocess.run([sys.executable, "-c", SHMAP_SCRIPT],
                       capture_output=True, text=True, timeout=420,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert r.returncode == 0, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "OK" in r.stdout
