"""Sharding rules: logical-axis -> mesh-axis resolution.

Logical axes used throughout the model code:
  "batch"  -> (pod, data)   activations' leading batch dim
  "model"  -> model         head / ffn-hidden / vocab dims of weights
  "expert" -> model         MoE expert dim (expert parallelism)

``constrain(x, *logical)`` applies a with_sharding_constraint when (a) a mesh
has been installed via :func:`set_mesh`/:func:`use_mesh` and (b) the dim is
divisible by the mesh-axis size — otherwise it is a transparent no-op, so
model code runs unmodified in single-device tests.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

LOGICAL_TO_MESH = {
    "batch": ("pod", "data"),
    # FSDP storage axis for weights: crosses the pod boundary on the
    # multi-pod mesh (DCN all-gathers) — without it the 235B tri-model +
    # fp32 Adam state cannot fit 16 GB/chip (see EXPERIMENTS.md).
    "data": ("pod", "data"),
    "model": ("model",),
    # expert parallelism lives on the data axis (all-to-all from the
    # batch-sharded token buffer); per-expert ffn dim is on "model".
    "expert": ("data",),
    "expert_data": ("data",),
    # Megatron-style sequence parallelism: activations' seq dim lives on the
    # model axis between tensor-parallel regions, so per-layer residuals
    # saved by the remat scan are 1/TP the size.
    "seq": ("model",),
}

# --------------------------------------------------------------------------
# sharding profiles (§Perf hillclimb) — switch the logical-axis mapping.
#
#   baseline  — paper-faithful Megatron-flavoured mapping: activations
#               batch x seq sharded (sequence parallelism on the model
#               axis), weights 2D FSDP. The KV-chunk scan then pays
#               per-chunk activation collectives (measured: the dominant
#               roofline term for dense train_4k).
#   dp2       — beyond-paper: activations sharded on batch over BOTH mesh
#               axes (("pod","data","model")), seq unsharded. All attention
#               and FFN compute is device-local; the only collectives left
#               are the FSDP weight gathers + gradient reductions.
# --------------------------------------------------------------------------

_PROFILES = {
    "baseline": dict(LOGICAL_TO_MESH),
    "dp2": {
        "batch": ("pod", "data", "model"),
        "data": ("pod", "data"),
        "model": ("model",),
        "expert": ("data",),
        "expert_data": ("data",),
        "seq": (),        # unresolvable -> no constraint
    },
    # dp2 + Megatron-style weight storage: weights/opt state sharded ONLY on
    # the model axis (replicated across data) -> zero FSDP gathers; grads
    # all-reduce across data once per step. Fits models whose bf16 tri-model
    # + fp32 Adam state / TP-degree stays under HBM (~<= 20B at TP16).
    # baseline + head-sharded attention (Megatron SP<->TP transition): the
    # seq-sharded activations are resharded to head-sharded q (+ replicated
    # k/v) ONCE per layer instead of paying per-KV-chunk collectives inside
    # the attention scan. Applies only when num_heads divides the model
    # axis (64-head archs); the constraint is a no-op otherwise.
    "sp_heads": {
        "batch": ("pod", "data"),
        "data": ("pod", "data"),
        "model": ("model",),
        "expert": ("data",),
        "expert_data": ("data",),
        "seq": ("model",),
        "heads": ("model",),
        "ffn": ("model",),
    },
    "dp2_zero1": {
        "batch": ("pod", "data", "model"),
        "data": (),
        "model": ("model",),
        "expert": ("data",),
        "expert_data": ("data",),
        "seq": (),
    },
    # inference-pool profile (the weight-plane's destination layout —
    # DESIGN.md §Weight-plane): weights TP-sharded on the model axis and
    # REPLICATED across data (data -> ()), so decode pays zero FSDP
    # gathers; activations batch-sharded. The trainer keeps its FSDP
    # profile — repro.transfer reshards leaf-by-leaf in flight.
    "infer_tp": {
        "batch": ("pod", "data"),
        "data": (),
        "model": ("model",),
        "expert": ("data",),
        "expert_data": ("data",),
        "seq": (),
    },
}


def profile_has(axis: str) -> bool:
    """True if the active profile maps this logical axis to mesh axes —
    used to gate Megatron-SP constraint groups (see models/attention.py,
    models/layers.py)."""
    return bool(LOGICAL_TO_MESH.get(axis))


def set_profile(name: str) -> None:
    """Install a sharding profile (mutates the live mapping)."""
    LOGICAL_TO_MESH.clear()
    LOGICAL_TO_MESH.update(_PROFILES[name])


def current_profile_map() -> dict:
    return dict(LOGICAL_TO_MESH)


@contextlib.contextmanager
def use_profile(name: str):
    """Temporarily install a sharding profile (restores the previous live
    mapping on exit) — used to resolve param specs under a profile other
    than the active one, e.g. the weight-plane computing its destination
    (inference) layout while the trainer profile stays installed."""
    prev = dict(LOGICAL_TO_MESH)
    set_profile(name)
    try:
        yield
    finally:
        LOGICAL_TO_MESH.clear()
        LOGICAL_TO_MESH.update(prev)


def param_specs_for_profile(params, mesh: Mesh, profile: str):
    """NamedSharding pytree for ``params`` as profile ``profile`` would
    place it — the src/dst spec trees a reshard plan is built from."""
    with use_profile(profile):
        return param_specs(params, mesh)


def set_mesh(mesh: Optional[Mesh]) -> None:
    _state.mesh = mesh


def current_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    prev = current_mesh()
    set_mesh(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        set_mesh(prev)


def _resolve(mesh: Mesh, dim: int, logical) -> Optional[tuple]:
    if logical is None:
        return None
    axes = tuple(a for a in LOGICAL_TO_MESH.get(logical, (logical,))
                 if a in mesh.axis_names)
    if not axes:
        return None
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    if size == 0 or dim % size:
        return None
    return axes if len(axes) > 1 else axes[0]


def spec_for(mesh: Mesh, shape: tuple, logical: tuple) -> P:
    return P(*(_resolve(mesh, d, l) for d, l in zip(shape, logical)))


def shard_map(f, mesh: Mesh, in_specs, out_specs):
    """Fully-manual ``jax.shard_map`` with replication checking off (the
    callers' out_specs deliberately leave collectively-reduced /
    replicated axes unmentioned)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def constrain(x: jax.Array, *logical):
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = spec_for(mesh, x.shape, logical)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# --------------------------------------------------------------------------
# parameter partition rules (matched by trailing path names)
# --------------------------------------------------------------------------

_PARAM_RULES = [
    # (name match, logical axes for the trailing dims).
    # 2D sharding: the tensor-parallel dim goes to "model", the other big dim
    # to "data" (FSDP/ZeRO-3 style) so 34B-235B params + fp32 Adam state fit
    # 16 GB HBM. Weights sharded on "data" are all-gathered at use and grads
    # reduce-scattered — the standard GSPMD FSDP pattern.
    ("embedding", ("model", "data")),
    ("lm_head", ("data", "model")),
    ("router", (None, None)),
    ("w_gate", ("data", "model")),   # (d, ff)
    ("w_up", ("data", "model")),
    ("w_down", ("model", "data")),   # (ff, d)
    ("wq", ("data", "model")),
    ("wk", ("data", "model")),
    ("wv", ("data", "model")),
    ("wo", ("model", "data")),
    ("w_dkv", ("data", "model")),
    ("w_kr", ("data", None)),
    ("w_uk", ("data", "model")),
    ("w_uv", ("data", "model")),
    ("in_proj", ("data", "model")),
    ("out_proj", ("model", "data")),
    ("conv_w", (None, "model")),
    ("conv_b", ("model",)),
    ("gate_norm", ("model",)),
]

# MoE expert weights: experts over "data" (expert parallelism, all-to-all at
# dispatch); a weight dim over "model" for ZeRO-3-style storage, all-gathered
# just-in-time inside the expert-parallel shard_map (see models/moe.py).
_EXPERT_RULES = {
    "w_gate": ("expert_data", "model", None),   # (E, d, ff)
    "w_up": ("expert_data", "model", None),
    "w_down": ("expert_data", None, "model"),   # (E, ff, d)
}


def _rule_for(path_str: str, name: str, ndim: int) -> tuple:
    is_expert = "moe" in path_str and name in _EXPERT_RULES and ndim >= 3
    if is_expert:
        base = _EXPERT_RULES[name]
        return (None,) * (ndim - 3) + base
    for key, axes in _PARAM_RULES:
        if name == key:
            axes_full = (None,) * (ndim - len(axes)) + axes
            return axes_full if len(axes_full) == ndim else (None,) * ndim
    return (None,) * ndim


# decode-cache partition rules: batch over ("pod","data"), cache length over
# "model" (sequence-sharded KV — heads are usually < 16 so the length dim is
# the shardable one); SSM state / conv tails shard on batch only.
_CACHE_RULES = {
    "k": ("batch", "seq", None, None),
    "v": ("batch", "seq", None, None),
    "pos": ("batch", "seq"),
    "seg": ("batch", "seq"),
    "ckv": ("batch", "seq", None),
    "kr": ("batch", "seq", None),
    "state": ("batch", None, None, None),
    "conv": ("batch", None, None),
}


def cache_specs(caches, mesh: Mesh):
    """NamedSharding pytree for a decode-cache pytree (leading stacked-layer
    dims padded with None)."""
    def one(path, leaf):
        keys = [getattr(e, "key", getattr(e, "name", None)) for e in path]
        keys = [k for k in keys if isinstance(k, str)]
        name = keys[-1] if keys else ""
        axes = _CACHE_RULES.get(name, ())
        logical = (None,) * (leaf.ndim - len(axes)) + axes
        logical = logical[-leaf.ndim:] if leaf.ndim else ()
        return NamedSharding(mesh, spec_for(mesh, leaf.shape, logical))

    return jax.tree_util.tree_map_with_path(one, caches)


def param_specs(params, mesh: Mesh):
    """Build a pytree of NamedSharding for a params pytree.

    With scan-over-layers, stacked layer params carry a leading layer dim
    which is handled by the (None,)*(ndim-len) padding in the rules."""
    def one(path, leaf):
        keys = [getattr(e, "key", getattr(e, "name", None)) for e in path]
        keys = [k for k in keys if isinstance(k, str)]
        name = keys[-1] if keys else ""
        logical = _rule_for("/".join(keys), name, leaf.ndim)
        return NamedSharding(mesh, spec_for(mesh, leaf.shape, logical))

    return jax.tree_util.tree_map_with_path(one, params)
