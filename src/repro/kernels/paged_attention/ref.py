"""Oracles for the paged attention kernels: gather the whole block-table
view of the pool, then the dense attention with its mask — the model's
own paged read before the kernels."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import _repeat_kv, decode_attention, gather_kv_paged


def paged_attention_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        layer: jax.Array, tables: jax.Array,
                        lengths: jax.Array) -> jax.Array:
    """q ``(B, H, hd)``; layer-stacked pools ``(L, NB, BS, KV*hd)``, read
    at ``layer``; tables ``(B, W)``; lengths ``(B,)``.  Returns ``(B, H,
    hd)`` in q's dtype.  A row of length 0 attends nothing; its output is
    meaningless (uniform weights over masked positions), where the kernel
    writes zeros."""
    B, H, hd = q.shape
    S = tables.shape[1] * k_pool.shape[2]
    kv = k_pool.shape[3] // hd
    k = gather_kv_paged(k_pool, layer, tables).reshape(B, S, kv, hd)
    v = gather_kv_paged(v_pool, layer, tables).reshape(B, S, kv, hd)
    return decode_attention(q[:, None], k.astype(q.dtype), v.astype(q.dtype),
                            lengths)[:, 0]


def paged_prefill_attention_ref(q: jax.Array, k_pool: jax.Array,
                                v_pool: jax.Array, layer: jax.Array,
                                table: jax.Array, start: jax.Array,
                                length: jax.Array) -> jax.Array:
    """q ``(Cb, H, hd)`` at positions ``start ..``; layer-stacked pools
    ``(L, NB, BS, KV*hd)`` holding the chunk's own keys and values, read
    at ``layer`` through ``table (W,)``.  Query ``i`` attends the
    positions ``p <= start + i`` below ``start + length``, in float32.
    Returns ``(Cb, H, hd)`` in q's dtype."""
    Cb, H, hd = q.shape
    S = table.shape[0] * k_pool.shape[2]
    kv = k_pool.shape[3] // hd
    k = gather_kv_paged(k_pool, layer, table[None]).reshape(1, S, kv, hd)
    v = gather_kv_paged(v_pool, layer, table[None]).reshape(1, S, kv, hd)
    k = _repeat_kv(k, H // kv)[0].astype(jnp.float32)     # (S, H, hd)
    v = _repeat_kv(v, H // kv)[0].astype(jnp.float32)
    s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32), k) / jnp.sqrt(
        jnp.float32(hd))
    key = jnp.arange(S)[None, :]
    query = start + jnp.arange(Cb)[:, None]
    s = jnp.where((key <= query) & (key < start + length), s, -1e30)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return out.astype(q.dtype)
