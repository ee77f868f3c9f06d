"""Pallas fused cast+copy for the weight-plane wire path.

When the transfer service streams an fp32-mastered tree as a bf16 payload
(``RLConfig.transfer_wire_dtype``), the naive path materialises an fp32
copy in HBM and then a second pass casts it. This kernel fuses the two:
one read of the source tile, one write of the down-cast tile — the copy IS
the cast, so the wire staging buffer is written exactly once at the
payload dtype.

Layout: the leaf is viewed as a (rows, 128) lane grid. When the element
count is lane-aligned and the row count tiles evenly (every power-of-two
weight matrix — the weight-plane's common case), the source is fed to the
kernel AS IS: no padding copy, total traffic = one source read + one
payload write (half the HBM traffic of copy-then-cast for fp32->bf16).
Ragged leaves (norm vectors, odd tails) fall back to a zero-padded
staging copy first — strictly worse than ``astype`` for them, but they
are a rounding error of the tree's bytes. Rounding is XLA's convert
(round to nearest even), so the result is bitwise-identical to
``x.astype(dtype)`` — asserted in tests/test_transfer.py against the
pure-JAX path.

On the chip the call compiles to Mosaic; interpret mode is the CPU test
path (``kernels/ops.py`` picks per backend).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANES = 128
_MIN_SUBLANES = 8


def _cast_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].astype(o_ref.dtype)


def _cast_call(x2d, dtype, bm: int, interpret: bool):
    rows = x2d.shape[0]
    return pl.pallas_call(
        _cast_kernel,
        grid=(rows // bm,),
        in_specs=[pl.BlockSpec((bm, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), dtype),
        interpret=interpret,
    )(x2d)


@partial(jax.jit, static_argnames=("dtype", "block_rows", "interpret"))
def transfer_cast(x, dtype, *, block_rows: int = 256,
                  interpret: bool = False):
    """Fused cast+copy of one pytree leaf: ``x`` -> ``dtype``.

    Any shape/dtype in; value-equal to ``x.astype(dtype)`` out. No-op
    dtypes and 0-element leaves short-circuit.
    """
    dtype = jnp.dtype(dtype)
    if x.dtype == dtype:
        return x
    n = x.size
    if n == 0:
        return x.astype(dtype)
    flat = x.reshape(-1)
    if n % _LANES == 0:
        rows = n // _LANES
        bm = math.gcd(rows, block_rows)
        if bm >= _MIN_SUBLANES:
            # aligned fast path: the source IS the kernel input — no
            # staging copy, no output slice
            out = _cast_call(flat.reshape(rows, _LANES), dtype, bm,
                             interpret)
            return out.reshape(x.shape)
    rows = -(-n // _LANES)
    rows = -(-rows // block_rows) * block_rows
    padded = jnp.zeros((rows * _LANES,), x.dtype).at[:n].set(flat)
    out = _cast_call(padded.reshape(rows, _LANES), dtype, block_rows,
                     interpret)
    return out.reshape(-1)[:n].reshape(x.shape)
