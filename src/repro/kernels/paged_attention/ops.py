"""Public ops: paged attention through block tables, for single-token
decode (:func:`paged_attention`) and for one prefill chunk
(:func:`paged_prefill_attention`), and their latent-mode twins over an
MLA pool of latent rows (:func:`paged_latent_attention`,
:func:`paged_latent_prefill_attention`).

The Pallas kernel is compiled for the device unless the caller passes
``interpret=True`` (how the CPU tests run it).  Nothing here looks at the
platform: the model chooses between these kernels and the block-table
gather at lowering (``repro.models.transformer``), and :func:`supports`
is the shape test it applies.

:func:`copied_positions` counts the context positions the kernel copies
for given row lengths, so a caller's counter can follow what is read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention.paged_attention import (
    mla_chunk_attention, mla_decode_attention, paged_chunk_attention,
    paged_decode_attention)

__all__ = ["paged_attention", "paged_prefill_attention", "supports",
           "supports_latent", "copied_positions", "paged_decode_attention",
           "paged_chunk_attention", "paged_latent_attention",
           "paged_latent_prefill_attention"]

# positions each step of the kernel's loop computes, and the most
# consecutive blocks one copy moves: on one v5e at the benchmark's serving
# shapes, 1024 and 8 came within 3% of the best of spans 256-2048 and
# runs of 1-64
SPAN = 1024
RUN = 8
# a prefill chunk's query rows (queries x the heads of a KV group) in one
# step of its kernel's grid, and the positions each step of its loop
# computes: a (1024, 512) f32 score tile is 2 MiB, and the kernel's VMEM
# (ring, tiles, accumulators, a few score tiles) stays under CHUNK_VMEM
CHUNK_ROWS = 1024
CHUNK_SPAN = 512
# the second-minor tile of a pool dtype: a block must fill whole tiles
_SUBLANES = {jnp.dtype(jnp.bfloat16): 16, jnp.dtype(jnp.float32): 8}


def supports(head_dim: int, block_size: int, dtype) -> bool:
    """Whether the kernel takes this shape: whole 128-lane heads, and pool
    blocks of whole ``(sublanes, 128)`` tiles of a bf16 or f32 pool."""
    sub = _SUBLANES.get(jnp.dtype(dtype))
    return sub is not None and head_dim % 128 == 0 and block_size % sub == 0


def supports_latent(width: int, value_dim: int, block_size: int,
                    dtype) -> bool:
    """Whether the latent mode takes this pool: rows of whole 128-lane
    tiles whose values fill whole tiles too, in blocks of whole tiles of
    a bf16 or f32 pool."""
    return (width % 128 == 0 and value_dim % 128 == 0
            and supports(128, block_size, dtype))


def copied_positions(lengths, block_size: int) -> int:
    """Context positions the kernel copies for rows of these lengths:
    each row's blocks below its length, whole."""
    lengths = np.asarray(lengths, np.int64)
    return int((-(-lengths // block_size)).sum() * block_size)


def _ring(positions: int, block_size: int, pages: int,
          pages_per_copy: int | None = None):
    """(pages_per_copy, run_pages): unless given, the blocks of
    ``positions`` per loop step (at most ``pages``), and the longest run
    of at most ``RUN`` that divides them."""
    if pages_per_copy is None:
        pages_per_copy = max(1, min(positions // block_size, pages))
    return pages_per_copy, max(r for r in range(1, RUN + 1)
                               if pages_per_copy % r == 0)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    layer: jax.Array, tables: jax.Array, lengths: jax.Array,
                    *, pages_per_copy: int | None = None,
                    interpret: bool = False) -> jax.Array:
    """q ``(B, H, hd)`` against layer ``layer`` of the layer-stacked pools
    ``(L, NB, BS, KV*hd)`` through ``tables (B, W)``; row ``b`` attends its
    first ``lengths[b]`` positions and a row of length 0 gets zeros.
    Returns ``(B, H, hd)`` in q's dtype.  ``pages_per_copy`` (default:
    ``SPAN`` positions' worth, at most ``W``) is the number of blocks each
    step of the kernel's loop copies and computes; runs of up to ``RUN``
    of them (the largest divisor of ``pages_per_copy``) move in one copy
    where they are consecutive in the pool."""
    B, H, hd = q.shape
    BS = k_pool.shape[2]
    kv = k_pool.shape[3] // hd
    pages_per_copy, run_pages = _ring(SPAN, BS, tables.shape[1],
                                      pages_per_copy)
    out = paged_decode_attention(
        q.astype(jnp.float32).reshape(B, kv, H // kv, hd), k_pool, v_pool,
        layer, tables, lengths, pages_per_copy=pages_per_copy,
        run_pages=run_pages, interpret=interpret)
    return out.reshape(B, H, hd).astype(q.dtype)


def _chunk_tile(Cb: int, groups: int) -> int:
    """Queries per grid step: the most that divide ``Cb`` and give at
    most ``CHUNK_ROWS`` rows of whole bf16 tiles (or the whole chunk)."""
    fits = [t for t in range(1, Cb + 1)
            if Cb % t == 0 and t * groups <= CHUNK_ROWS
            and (t == Cb or t * groups % 16 == 0)]
    return max(fits, default=Cb)


def paged_prefill_attention(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, layer: jax.Array,
                            table: jax.Array, start: jax.Array,
                            length: jax.Array, *,
                            interpret: bool = False) -> jax.Array:
    """One chunk's queries q ``(Cb, H, hd)``, at positions ``start ..
    start+Cb-1``, against layer ``layer`` of the layer-stacked pools
    ``(L, NB, BS, KV*hd)`` through the request's ``table (W,)``; the
    pools already hold the chunk's own keys and values.  Query ``i``
    attends causally the positions below ``start + length``; a pad query
    (``i >= length``) gets a meaningless row.  Returns ``(Cb, H, hd)`` in
    q's dtype.  Each step of the kernel's loop computes ``CHUNK_SPAN``
    positions' worth of blocks (at most ``W``)."""
    Cb, H, hd = q.shape
    BS = k_pool.shape[2]
    kv = k_pool.shape[3] // hd
    G = H // kv
    W = table.shape[0]
    pages_per_copy, run_pages = _ring(CHUNK_SPAN, BS, W)
    grouped = q.reshape(Cb, kv, G, hd).transpose(1, 0, 2, 3).reshape(
        kv, Cb * G, hd)
    out = paged_chunk_attention(
        grouped, k_pool, v_pool, layer, table, start, length, groups=G,
        q_tile=_chunk_tile(Cb, G), pages_per_copy=pages_per_copy,
        run_pages=run_pages, interpret=interpret)
    return out.reshape(kv, Cb, G, hd).transpose(1, 0, 2, 3).reshape(
        Cb, H, hd)


def paged_latent_attention(q: jax.Array, pool: jax.Array, layer: jax.Array,
                           tables: jax.Array, lengths: jax.Array, *,
                           value_dim: int, scale: float,
                           interpret: bool = False) -> jax.Array:
    """Latent-mode decode: queries ``q (B, H, W)`` (``[q_lat | q_pe |
    0]``) against layer ``layer`` of the stacked latent pool ``(L, NB,
    BS, W)`` through ``tables (B, W_t)``; row ``b`` attends its first
    ``lengths[b]`` positions.  Returns ``o_lat (B, H, value_dim)``
    float32 (zeros for a row of length 0).  The loop steps over ``SPAN``
    positions as :func:`paged_attention` does."""
    ppc, run = _ring(SPAN, pool.shape[2], tables.shape[1])
    return mla_decode_attention(
        q.astype(jnp.float32), pool, layer, tables, lengths,
        value_dim=value_dim, scale=scale, pages_per_copy=ppc, run_pages=run,
        interpret=interpret)


def paged_latent_prefill_attention(q: jax.Array, pool: jax.Array,
                                   layer: jax.Array, table: jax.Array,
                                   start: jax.Array, length: jax.Array, *,
                                   value_dim: int, scale: float,
                                   interpret: bool = False) -> jax.Array:
    """Latent-mode prefill chunk: queries ``q (Cb, H, W)`` at positions
    ``start ..`` against layer ``layer`` of the stacked latent pool,
    which already holds the chunk's rows, through ``table (W_t,)``;
    causal as :func:`paged_prefill_attention`.  Returns ``o_lat (Cb, H,
    value_dim)`` in q's dtype."""
    Cb, H, W = q.shape
    ppc, run = _ring(CHUNK_SPAN, pool.shape[2], table.shape[0])
    out = mla_chunk_attention(
        q.reshape(Cb * H, W), pool, layer, table, start, length, groups=H,
        value_dim=value_dim, scale=scale, q_tile=_chunk_tile(Cb, H),
        pages_per_copy=ppc, run_pages=run, interpret=interpret)
    return out.reshape(Cb, H, value_dim)
