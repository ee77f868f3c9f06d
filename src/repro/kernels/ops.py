"""Jit'd public wrappers for the Pallas kernels.

On the TPU the calls compile to Mosaic (the chip path); elsewhere they run
in Pallas interpret mode, the CPU test path. ``auto_interpret()`` picks per
backend.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels.transfer_cast import transfer_cast as _transfer_cast
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.decode_attention import verify_attention as _verify
from repro.kernels.decode_attention import paged_decode_attention as _paged
from repro.kernels.decode_attention import (paged_mla_decode_attention
                                            as _paged_mla)
from repro.kernels.decode_attention import (paged_verify_attention
                                            as _paged_verify)
from repro.kernels.decode_attention import (paged_mla_verify_attention
                                            as _paged_mla_verify)
from repro.kernels.spa_attention import BLOCK_K, BLOCK_Q, block_map
from repro.kernels.spa_attention import spa_attention as _spa
# share of tiles ``spa_attention`` computes at the same tile sizes
from repro.kernels.spa_attention import tile_share as spa_tile_share


def auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def spa_attention(q, k, v, q_pos, kv_pos, q_seg, kv_seg, *,
                  scale: Optional[float] = None,
                  window: Optional[int] = None,
                  block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                  interpret: Optional[bool] = None):
    """Block-sparse shared-prompt flash attention, differentiable in q, k
    and v (see spa_attention.py)."""
    itp = auto_interpret() if interpret is None else interpret
    return _spa(q, k, v, q_pos, kv_pos, q_seg, kv_seg, scale=scale,
                window=window, block_q=block_q, block_k=block_k,
                interpret=itp)


def decode_attention(q, k, v, kv_pos, q_pos, *,
                     scale: Optional[float] = None,
                     window: Optional[int] = None,
                     block_l: int = 256,
                     interpret: Optional[bool] = None):
    """Flash-decode GQA attention (see decode_attention.py)."""
    itp = auto_interpret() if interpret is None else interpret
    return _decode(q, k, v, kv_pos, q_pos, scale=scale, window=window,
                   block_l=block_l, interpret=itp)


def paged_decode_attention(q, k_pages, v_pages, pos_pages, page_table, q_pos,
                           *, scale: Optional[float] = None,
                           window: Optional[int] = None,
                           block_l: int = 256,
                           interpret: Optional[bool] = None):
    """Flash-decode over a paged KV pool (see decode_attention.py)."""
    itp = auto_interpret() if interpret is None else interpret
    return _paged(q, k_pages, v_pages, pos_pages, page_table, q_pos,
                  scale=scale, window=window, block_l=block_l, interpret=itp)


def paged_mla_decode_attention(q, ckv_pages, kr_pages, pos_pages, page_table,
                               q_pos, *, scale: Optional[float] = None,
                               window: Optional[int] = None,
                               block_l: int = 256,
                               interpret: Optional[bool] = None):
    """Flash-decode over a paged MLA latent pool (see decode_attention.py)."""
    itp = auto_interpret() if interpret is None else interpret
    return _paged_mla(q, ckv_pages, kr_pages, pos_pages, page_table, q_pos,
                      scale=scale, window=window, block_l=block_l,
                      interpret=itp)


def verify_attention(q, k, v, kv_pos, q_pos, *,
                     scale: Optional[float] = None,
                     window: Optional[int] = None,
                     block_l: int = 256,
                     interpret: Optional[bool] = None):
    """Multi-token spec-decode verify attention (decode_attention.py)."""
    itp = auto_interpret() if interpret is None else interpret
    return _verify(q, k, v, kv_pos, q_pos, scale=scale, window=window,
                   block_l=block_l, interpret=itp)


def paged_verify_attention(q, k_pages, v_pages, pos_pages, page_table, q_pos,
                           *, scale: Optional[float] = None,
                           window: Optional[int] = None,
                           block_l: int = 256,
                           interpret: Optional[bool] = None):
    """Spec-decode verify over a paged KV pool (decode_attention.py)."""
    itp = auto_interpret() if interpret is None else interpret
    return _paged_verify(q, k_pages, v_pages, pos_pages, page_table, q_pos,
                         scale=scale, window=window, block_l=block_l,
                         interpret=itp)


def paged_mla_verify_attention(q, ckv_pages, kr_pages, pos_pages, page_table,
                               q_pos, *, scale: Optional[float] = None,
                               window: Optional[int] = None,
                               block_l: int = 256,
                               interpret: Optional[bool] = None):
    """Spec-decode verify over a paged MLA latent pool
    (decode_attention.py)."""
    itp = auto_interpret() if interpret is None else interpret
    return _paged_mla_verify(q, ckv_pages, kr_pages, pos_pages, page_table,
                             q_pos, scale=scale, window=window,
                             block_l=block_l, interpret=itp)


def transfer_cast(x, dtype, *, block_rows: int = 256,
                  interpret: Optional[bool] = None):
    """Fused cast+copy for the weight-plane wire path (transfer_cast.py)."""
    itp = auto_interpret() if interpret is None else interpret
    return _transfer_cast(x, dtype, block_rows=block_rows, interpret=itp)


__all__ = ["spa_attention", "spa_tile_share", "decode_attention",
           "verify_attention", "paged_decode_attention",
           "paged_mla_decode_attention", "paged_verify_attention",
           "paged_mla_verify_attention",
           "block_map", "auto_interpret", "transfer_cast"]
