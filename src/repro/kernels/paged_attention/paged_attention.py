"""Paged attention — Pallas TPU kernels: single-token decode, and one
prefill chunk of queries.

Each request's keys and values live in fixed-size blocks of one layer of
the layer-stacked pool ``(L, NB, BS, KV*hd)``, found through the
request's block table.  Both kernels read them straight from the pool, at
the layer they are given, and only the blocks they need:

  * the block tables, lengths and layer are scalar-prefetched into SMEM
    and the stacked pools stay in HBM (``memory_space=pl.ANY``), so no
    layer's pool is sliced out of them;
  * a table's live blocks are copied ``pages_per_copy`` at a time into a
    ring of ``DEPTH`` VMEM tiles ``(pages_per_copy, BS, KV*hd)``
    (:func:`_stream_pages`): the copies of the next ``DEPTH - 1`` groups
    are in flight while a group is computed, and a block past what is
    needed is never copied;
  * a run of ``run_pages`` pages that the table holds on consecutive
    pool blocks (a prompt's blocks, allocated together) moves in one
    copy; any other page moves alone;
  * GQA without repeating K/V: the queries come grouped by KV head and
    each KV head's queries meet that head's lane slice of the tile, one
    matmul pair per head;
  * bf16 (the pool's dtype) into the MXU, f32 scores, online softmax and
    accumulator, so no score tensor and no gathered view is ever
    materialized.

Decode (:func:`paged_decode_attention`): grid = (rows,); row ``b``
attends its first ``lengths[b]`` positions, and a row of length 0 issues
no copy and writes zeros.

Prefill (:func:`paged_chunk_attention`): grid = (query tiles,) over one
chunk of one request whose own keys and values are already in the pool.
A tile of ``q_tile`` queries at positions ``[first, first + q_tile)``
copies only the blocks below ``min(first + q_tile, start + length)``:
nothing past the request's fill, nothing above the tile's causal
diagonal.  Only groups that reach the diagonal apply the mask; a tile of
pad queries alone (at or past ``length``) copies nothing and writes
zeros.

Oracle: ref.py (the block-table gather and the dense masked attention).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_attention", "paged_chunk_attention"]

NEG_INF = -1e30
# tiles in the copy ring: the copies of DEPTH - 1 groups are in flight
# while one is computed (4 measured within 3% of the best of 2-8 on one
# v5e at the benchmark's serving shapes)
DEPTH = 4
# VMEM the chunk kernel may use: its ring, query and output tiles,
# accumulators and a few (rows, span) f32 score tiles
CHUNK_VMEM = 64 * 2 ** 20


def _stream_pages(tables_ref, base, n_pages, layer, k_hbm, v_hbm, k_buf,
                  v_buf, sem, whole_ref, compute, *, pages_per_copy: int,
                  run_pages: int, pages_per_row: int, num_blocks: int):
    """Copy the first ``n_pages`` pages of the table at ``tables_ref[base
    :]`` (layer ``layer`` of the stacked pools) through the ring,
    ``pages_per_copy`` at a time, and call ``compute(g, slot)`` on group
    ``g`` once its copies have landed in ring tile ``slot``."""
    n_groups = (n_pages + pages_per_copy - 1) // pages_per_copy
    runs = pages_per_copy // run_pages

    def block(page):
        """Pool block of the table's ``page``-th page (clamped in range)."""
        page = jnp.minimum(page, pages_per_row - 1)
        return tables_ref[base + page]

    def copies(src, dst, slot):
        return (pltpu.make_async_copy(k_hbm.at[layer, src],
                                      k_buf.at[slot, dst], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, src],
                                      v_buf.at[slot, dst], sem.at[1, slot]))

    def page_by_page(g, slot, lo, hi, action):
        """One copy per live page ``lo <= i < hi`` of group ``g``."""
        def one(i, carry):
            blk = jnp.clip(block(g * pages_per_copy + i), 0, num_blocks - 1)
            for copy in copies(blk, i, slot):
                action(copy)
            return carry
        jax.lax.fori_loop(lo, jnp.minimum(hi, n_pages - g * pages_per_copy),
                          one, 0)

    def run_is_whole(g, r):
        """Whether the ``r``-th run of group ``g`` is ``run_pages`` live
        pages on consecutive pool blocks: one copy then moves them all."""
        first = g * pages_per_copy + r * run_pages
        blk0 = block(first)
        whole = ((first + run_pages <= n_pages) & (blk0 >= 0)
                 & (blk0 + run_pages <= num_blocks))
        for i in range(1, run_pages):
            whole = whole & (block(first + i) == blk0 + i)
        return whole

    def for_live_pages(g, slot, action, plan):
        """Apply ``action`` to each copy of group ``g``'s live pages: a
        run on consecutive blocks in one copy, any other page alone.
        ``plan`` decides the runs (as the copies start) and records the
        decision for the waits, which read it back."""
        if run_pages == 1:
            page_by_page(g, slot, 0, pages_per_copy, action)
            return

        def run(r, carry):
            flag = slot * runs + r
            if plan:
                whole_ref[flag] = run_is_whole(g, r).astype(jnp.int32)
            whole = whole_ref[flag] == 1

            @pl.when(whole)
            def _():
                blk0 = block(g * pages_per_copy + r * run_pages)
                for copy in copies(pl.ds(blk0, run_pages),
                                   pl.ds(r * run_pages, run_pages), slot):
                    action(copy)

            @pl.when(jnp.logical_not(whole))
            def _():
                page_by_page(g, slot, r * run_pages, (r + 1) * run_pages,
                             action)
            return carry

        live = n_pages - g * pages_per_copy
        jax.lax.fori_loop(0, jnp.minimum(runs, (live + run_pages - 1)
                                         // run_pages), run, 0)

    # group g lands in tile g % DEPTH of the ring
    def prologue(ahead, carry):
        for_live_pages(ahead, ahead, lambda c: c.start(), True)
        return carry

    jax.lax.fori_loop(0, jnp.minimum(DEPTH - 1, n_groups), prologue, 0)

    def body(g, carry):
        ahead = g + DEPTH - 1

        @pl.when(ahead < n_groups)
        def _():
            for_live_pages(ahead, jax.lax.rem(ahead, DEPTH),
                           lambda c: c.start(), True)

        slot = jax.lax.rem(g, DEPTH)
        for_live_pages(g, slot, lambda c: c.wait(), False)
        compute(g, slot)
        return carry

    jax.lax.fori_loop(0, n_groups, body, 0)


def _online_softmax_step(h, q, k, v, s_ok, v_ok, m_ref, l_ref, acc_ref,
                         scale: float):
    """Fold one tile of KV head ``h``'s keys ``k``/values ``v`` into its
    queries' running max, sum and accumulator.  ``s_ok`` masks scores
    (None: every key counts); ``v_ok`` zeroes values a tile may hold
    stale, so that no stale value meets a zero weight."""
    s = jax.lax.dot_general(
        q.astype(k.dtype), k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if s_ok is not None:
        s = jnp.where(s_ok, s, NEG_INF)
    m_prev = m_ref[h]                                     # (rows, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[h] = alpha * l_ref[h] + p.sum(axis=1, keepdims=True)
    if v_ok is not None:
        v = jnp.where(v_ok, v, jnp.zeros_like(v))
    acc_ref[h] = alpha * acc_ref[h] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[h] = m_new


def _head_tiles(k_buf, v_buf, slot, h, span: int, head_dim: int):
    """KV head ``h``'s lane slice of ring tile ``slot``: ``(span, hd)``."""
    cols = pl.ds(h * head_dim, head_dim)
    return (k_buf[slot, :, :, cols].reshape(span, head_dim),
            v_buf[slot, :, :, cols].reshape(span, head_dim))


def _kernel(tables_ref, lengths_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sem, whole_ref, m_ref, l_ref, acc_ref, *,
            block_size: int, pages_per_copy: int, run_pages: int,
            pages_per_row: int, num_blocks: int, kv_heads: int,
            head_dim: int, scale: float):
    b = pl.program_id(0)
    length = lengths_ref[b]
    n_pages = jnp.minimum((length + block_size - 1) // block_size,
                          pages_per_row)
    span = pages_per_copy * block_size

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def compute(g, slot):
        g_len = length - g * span
        groups = q_ref.shape[2]
        live_s = jax.lax.broadcasted_iota(jnp.int32, (groups, span), 1) < g_len
        live_v = jax.lax.broadcasted_iota(jnp.int32, (span, head_dim), 0) < g_len
        for h in range(kv_heads):
            k, v = _head_tiles(k_buf, v_buf, slot, h, span, head_dim)
            # a page's tail past the length holds whatever the tile held
            # before: it is masked, and its values zeroed
            _online_softmax_step(h, q_ref[0, h], k, v, live_s, live_v,
                                 m_ref, l_ref, acc_ref, scale)

    _stream_pages(tables_ref, b * pages_per_row, n_pages, layer_ref[0],
                  k_hbm, v_hbm, k_buf, v_buf, sem, whole_ref, compute,
                  pages_per_copy=pages_per_copy, run_pages=run_pages,
                  pages_per_row=pages_per_row, num_blocks=num_blocks)
    for h in range(kv_heads):
        o_ref[0, h] = (acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
                       ).astype(o_ref.dtype)


def _ring_scratch(pages_per_copy: int, run_pages: int, BS: int, C: int,
                  dtype) -> list:
    """The copy ring's k/v tiles, its DMA semaphores and its run flags."""
    return [pltpu.VMEM((DEPTH, pages_per_copy, BS, C), dtype),
            pltpu.VMEM((DEPTH, pages_per_copy, BS, C), dtype),
            pltpu.SemaphoreType.DMA((2, DEPTH)),
            pltpu.SMEM((DEPTH * (pages_per_copy // run_pages),), jnp.int32)]


@functools.partial(jax.jit, static_argnames=("pages_per_copy", "run_pages",
                                             "interpret"))
def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, layer: jax.Array,
                           tables: jax.Array, lengths: jax.Array, *,
                           pages_per_copy: int, run_pages: int,
                           interpret: bool = False) -> jax.Array:
    """q ``(B, KV, G, hd)`` float32 (grouped by KV head); layer-stacked
    pools ``(L, NB, BS, KV*hd)``, read at ``layer`` (an int32 scalar);
    tables ``(B, W)`` int32 (``-1`` past a row's blocks); lengths ``(B,)``
    int32, each at most ``W*BS``.  Returns ``(B, KV, G, hd)`` float32: row
    ``b`` attends its first ``lengths[b]`` positions.
    Each step of the kernel's loop computes ``pages_per_copy`` blocks;
    ``run_pages`` consecutive blocks (dividing ``pages_per_copy``) move
    in one copy where the table holds them on consecutive pool blocks.
    """
    B, KV, G, hd = q.shape
    _, NB, BS, C = k_pool.shape
    W = tables.shape[1]
    assert C == KV * hd and v_pool.shape == k_pool.shape, (q.shape,
                                                          k_pool.shape)
    assert pages_per_copy % run_pages == 0, (pages_per_copy, run_pages)
    body = functools.partial(
        _kernel, block_size=BS, pages_per_copy=pages_per_copy,
        run_pages=run_pages, pages_per_row=W, num_blocks=NB, kv_heads=KV, head_dim=hd,
        scale=1.0 / math.sqrt(hd))
    row = pl.BlockSpec((1, KV, G, hd),
                       lambda b, tables, lengths, layer: (b, 0, 0, 0))
    kernel = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[row, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row,
            scratch_shapes=_ring_scratch(pages_per_copy, run_pages, BS, C,
                                         k_pool.dtype) + [
                pltpu.VMEM((KV, G, 1), jnp.float32),
                pltpu.VMEM((KV, G, 1), jnp.float32),
                pltpu.VMEM((KV, G, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )
    return kernel(jnp.asarray(tables, jnp.int32).reshape(-1),
                  jnp.asarray(lengths, jnp.int32),
                  jnp.asarray(layer, jnp.int32).reshape(1), q, k_pool, v_pool)


def _chunk_kernel(table_ref, scalars_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sem, whole_ref, m_ref, l_ref, acc_ref, *,
                  block_size: int, pages_per_copy: int, run_pages: int,
                  pages_per_row: int, num_blocks: int, kv_heads: int,
                  head_dim: int, q_tile: int, groups: int, scale: float):
    t = pl.program_id(0)
    start, length, layer = scalars_ref[0], scalars_ref[1], scalars_ref[2]
    end = start + length                    # the request's fill after it
    first = start + t * q_tile              # the tile's first query
    last = jnp.minimum(first + q_tile, end)  # one past the keys it reads
    n_pages = jnp.where(
        t * q_tile < length,
        jnp.minimum((last + block_size - 1) // block_size, pages_per_row), 0)
    span = pages_per_copy * block_size
    rows = q_tile * groups                  # query row r is query r // G

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(g, slot, on_diagonal: bool):
        s_ok = v_ok = None
        if on_diagonal:
            key = g * span + jax.lax.broadcasted_iota(jnp.int32,
                                                      (rows, span), 1)
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, span), 0)
            # key <= first + row // G, without a vector division
            s_ok = ((key - first) * groups <= row) & (key < end)
            # keys at or past ``last`` were not copied for this tile
            v_ok = g * span + jax.lax.broadcasted_iota(
                jnp.int32, (span, head_dim), 0) < last
        for h in range(kv_heads):
            k, v = _head_tiles(k_buf, v_buf, slot, h, span, head_dim)
            _online_softmax_step(h, q_ref[h], k, v, s_ok, v_ok, m_ref, l_ref,
                                 acc_ref, scale)

    def compute(g, slot):
        # a group wholly below the tile's first query needs no mask
        on_diagonal = (g + 1) * span > first

        @pl.when(on_diagonal)
        def _():
            attend(g, slot, True)

        @pl.when(jnp.logical_not(on_diagonal))
        def _():
            attend(g, slot, False)

    _stream_pages(table_ref, 0, n_pages, layer, k_hbm, v_hbm, k_buf, v_buf,
                  sem, whole_ref, compute, pages_per_copy=pages_per_copy,
                  run_pages=run_pages, pages_per_row=pages_per_row,
                  num_blocks=num_blocks)
    for h in range(kv_heads):
        o_ref[h] = (acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("groups", "q_tile",
                                             "pages_per_copy", "run_pages",
                                             "interpret"))
def paged_chunk_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                          layer: jax.Array, table: jax.Array,
                          start: jax.Array, length: jax.Array, *,
                          groups: int, q_tile: int, pages_per_copy: int,
                          run_pages: int,
                          interpret: bool = False) -> jax.Array:
    """q ``(KV, Cb*G, hd)`` with ``G = groups`` (grouped by KV head: row
    ``i*G + j`` is chunk query ``i``'s ``j``-th head of the group);
    layer-stacked pools ``(L, NB, BS, KV*hd)`` that already hold the
    chunk's keys and values at positions ``start .. start+length-1``,
    read at ``layer`` through ``table (W,)``.  Query ``i`` (position
    ``start + i``) attends the positions ``p <= start + i`` below ``start
    + length``.  Returns ``(KV, Cb*G, hd)`` in q's dtype; a tile of pad
    queries alone (``i >= length``) gets zeros.  ``q_tile`` queries
    (dividing ``Cb``) make one step of the grid; ``pages_per_copy`` and
    ``run_pages`` as in :func:`paged_decode_attention`."""
    KV, R, hd = q.shape
    _, NB, BS, C = k_pool.shape
    W = table.shape[0]
    Cb = R // groups
    assert C == KV * hd and v_pool.shape == k_pool.shape, (q.shape,
                                                          k_pool.shape)
    assert Cb * groups == R and Cb % q_tile == 0, (R, groups, q_tile)
    assert pages_per_copy % run_pages == 0, (pages_per_copy, run_pages)
    rows = q_tile * groups
    body = functools.partial(
        _chunk_kernel, block_size=BS, pages_per_copy=pages_per_copy,
        run_pages=run_pages, pages_per_row=W, num_blocks=NB, kv_heads=KV,
        head_dim=hd, q_tile=q_tile, groups=groups, scale=1.0 / math.sqrt(hd))
    tile = pl.BlockSpec((KV, rows, hd), lambda t, table, scalars: (0, t, 0))
    kernel = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Cb // q_tile,),
            in_specs=[tile, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=tile,
            scratch_shapes=_ring_scratch(pages_per_copy, run_pages, BS, C,
                                         k_pool.dtype) + [
                pltpu.VMEM((KV, rows, 1), jnp.float32),
                pltpu.VMEM((KV, rows, 1), jnp.float32),
                pltpu.VMEM((KV, rows, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=CHUNK_VMEM),
        interpret=interpret,
    )
    scalars = jnp.stack([jnp.asarray(start, jnp.int32),
                         jnp.asarray(length, jnp.int32),
                         jnp.asarray(layer, jnp.int32)])
    return kernel(jnp.asarray(table, jnp.int32), scalars, q, k_pool, v_pool)
