"""Oracle for the paged decode attention kernel: gather each row's whole
block-table view of the pool, then the dense single-token attention with
per-row length masking — the model's own paged read before the kernel."""

from __future__ import annotations

import jax

from repro.models.common import decode_attention, gather_kv_paged


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
