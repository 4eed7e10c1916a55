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


def paged_latent_attention_ref(q: jax.Array, pool: jax.Array,
                               layer: jax.Array, tables: jax.Array,
                               lengths: jax.Array, *, value_dim: int,
                               scale: float) -> jax.Array:
    """Latent mode: q ``(B, H, W)``; the layer-stacked latent pool ``(L,
    NB, BS, W)``, read at ``layer``; row ``b`` scores its first
    ``lengths[b]`` rows whole and sums their first ``value_dim`` lanes,
    in float32.  Returns ``(B, H, value_dim)`` float32."""
    rows = gather_kv_paged(pool, layer, tables).astype(jnp.float32)
    s = jnp.einsum("bhw,btw->bht", q.astype(jnp.float32), rows) * scale
    live = jnp.arange(rows.shape[1])[None, None, :] < lengths[:, None, None]
    p = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
    return jnp.einsum("bht,btc->bhc", p, rows[..., :value_dim])


def paged_latent_prefill_attention_ref(q: jax.Array, pool: jax.Array,
                                       layer: jax.Array, table: jax.Array,
                                       start: jax.Array, length: jax.Array,
                                       *, value_dim: int,
                                       scale: float) -> jax.Array:
    """Latent mode: q ``(Cb, H, W)`` at positions ``start ..``; the pool
    holds the chunk's own rows.  Query ``i`` attends the positions ``p <=
    start + i`` below ``start + length``, in float32.  Returns ``(Cb, H,
    value_dim)`` in q's dtype."""
    Cb = q.shape[0]
    rows = gather_kv_paged(pool, layer, table[None])[0].astype(jnp.float32)
    s = jnp.einsum("qhw,tw->hqt", q.astype(jnp.float32), rows) * scale
    key = jnp.arange(rows.shape[0])[None, :]
    query = start + jnp.arange(Cb)[:, None]
    s = jnp.where((key <= query) & (key < start + length), s, -1e30)
    out = jnp.einsum("hqt,tc->qhc", jax.nn.softmax(s, axis=-1),
                     rows[:, :value_dim])
    return out.astype(q.dtype)
