"""Shared model components: norms, positional embeddings, attention, MLPs.

Conventions
-----------
* Parameters are nested dicts of ``jnp`` arrays.  Every init function returns
  ``(params, specs)`` where ``specs`` mirrors the tree with tuples of
  *logical axis names* — the sharding layer maps logical axes to mesh axes
  through a rule table (MaxText-style), which is the hillclimb lever.
* Layer-stacked params carry a leading ``layers`` axis and are consumed with
  ``jax.lax.scan`` (keeps HLO size O(1) in depth — essential for compiling
  94-layer configs on the dry-run host).
* Attention switches to a blockwise (flash) implementation above
  ``cfg.flash_threshold`` so 32k-token prefill fits compile-time memory;
  the Pallas kernel in ``repro/kernels/flash_attention`` is the TPU-optimized
  twin of the same algorithm (same oracle).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding import constrain

__all__ = [
    "ParamBuilder", "rms_norm", "make_rope", "apply_rope", "apply_mrope",
    "sinusoidal_positions", "attention", "blockwise_attention", "mlp_swiglu",
    "mlp_gelu", "decode_attention", "gather_kv_paged", "scatter_kv_paged",
]

Tree = Dict[str, Any]


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _scaled_normal(key: jax.Array, scale: float, *, shape: Tuple[int, ...],
                   dtype: jnp.dtype) -> jax.Array:
    """normal(0, scale) drawn in float32 and cast, as one compiled program:
    the float32 draw is fused away, so building a tensor needs no more
    memory than the tensor itself."""
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


class ParamBuilder:
    """Builds a (params, specs) pair with matching structure.

    ``abstract=True`` emits ShapeDtypeStructs instead of arrays — the
    allocation-free init used by the multi-pod dry-run (full configs are
    never materialized on the CPU host).
    """

    def __init__(self, key: jax.Array, dtype: jnp.dtype = jnp.float32,
                 abstract: bool = False):
        self.key = key
        self.dtype = dtype
        self.abstract = abstract
        self.params: Tree = {}
        self.specs: Tree = {}

    def _next(self) -> jax.Array:
        self.key, sub = jax.random.split(self.key)
        return sub

    def dense(self, path: str, shape: Tuple[int, ...], axes: Tuple[Optional[str], ...],
              scale: Optional[float] = None, zeros: bool = False) -> None:
        """He/Glorot-ish init: normal(0, scale), scale defaults 1/sqrt(fan_in)."""
        if len(shape) != len(axes):
            raise ValueError(f"{path}: shape {shape} vs axes {axes}")
        if self.abstract:
            arr = jax.ShapeDtypeStruct(shape, self.dtype)
        elif zeros:
            arr = jnp.zeros(shape, self.dtype)
        else:
            if scale is None:
                fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
                scale = 1.0 / math.sqrt(max(fan_in, 1))
            arr = _scaled_normal(self._next(), scale, shape=tuple(shape),
                                 dtype=self.dtype)
        self._set(path, arr, tuple(axes))

    def ones(self, path: str, shape: Tuple[int, ...],
             axes: Tuple[Optional[str], ...]) -> None:
        if self.abstract:
            self._set(path, jax.ShapeDtypeStruct(shape, self.dtype), tuple(axes))
            return
        self._set(path, jnp.ones(shape, self.dtype), tuple(axes))

    def zeros(self, path: str, shape: Tuple[int, ...],
              axes: Tuple[Optional[str], ...]) -> None:
        if self.abstract:
            self._set(path, jax.ShapeDtypeStruct(shape, self.dtype), tuple(axes))
            return
        self._set(path, jnp.zeros(shape, self.dtype), tuple(axes))

    def const(self, path: str, arr: jax.Array,
              axes: Tuple[Optional[str], ...]) -> None:
        if self.abstract:
            self._set(path, jax.ShapeDtypeStruct(arr.shape, self.dtype),
                      tuple(axes))
            return
        self._set(path, arr.astype(self.dtype), tuple(axes))

    def _set(self, path: str, arr: jax.Array, spec: Tuple) -> None:
        parts = path.split("/")
        p, s = self.params, self.specs
        for part in parts[:-1]:
            p = p.setdefault(part, {})
            s = s.setdefault(part, {})
        p[parts[-1]] = arr
        s[parts[-1]] = spec

    def build(self) -> Tuple[Tree, Tree]:
        return self.params, self.specs


# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


# ----------------------------------------------------------------- positions
def make_rope(positions: jax.Array, head_dim: int, theta: float
              ) -> Tuple[jax.Array, jax.Array]:
    """positions: (..., S) int -> cos/sin of shape (..., S, head_dim//2)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, hd); cos/sin: (B, S, hd//2) or (S, hd//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def apply_mrope(x: jax.Array, positions_3d: jax.Array, head_dim: int,
                theta: float, sections: Tuple[int, int, int]) -> jax.Array:
    """Qwen2-VL M-RoPE: the rotary half-dim is split into (t, h, w) sections,
    each rotated by its own position stream.  positions_3d: (3, B, S)."""
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang_tbw = positions_3d.astype(jnp.float32)[..., None] * freqs  # (3,B,S,half)
    sec_ids = np.repeat(np.arange(3), sections)                    # (half,)
    # select, per rotary dim j, the position stream sections[j] belongs to
    sel = jax.nn.one_hot(jnp.asarray(sec_ids), 3, dtype=jnp.float32)  # (half,3)
    ang = jnp.einsum("tbsh,ht->bsh", ang_tbw, sel)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return apply_rope(x, cos, sin)


def sinusoidal_positions(positions: jax.Array, d_model: int) -> jax.Array:
    """MusicGen-style sinusoidal embeddings. positions: (S,) or (B,S)."""
    half = d_model // 2
    freqs = 1.0 / (10000.0 ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ----------------------------------------------------------------- attention
def _repeat_kv(k: jax.Array, groups: int) -> jax.Array:
    """(B, S, KV, hd) -> (B, S, KV*groups, hd) by repeat (GQA)."""
    if groups == 1:
        return k
    b, s, kv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, groups, hd)
                            ).reshape(b, s, kv * groups, hd)


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              *, causal: bool = True,
              segment_ids: Optional[jax.Array] = None,
              block_q: int = 512, block_kv: int = 1024,
              flash_threshold: int = 8192) -> jax.Array:
    """Multi-head attention, GQA-aware.

    q: (B, S, H, hd); k/v: (B, T, KV, hd).  Dispatches to the blockwise
    (flash) path for long sequences; both paths share the same semantics and
    are cross-checked in tests (and against kernels/flash_attention/ref.py).
    """
    groups = q.shape[2] // k.shape[2]
    k = _repeat_kv(k, groups)
    v = _repeat_kv(v, groups)
    if q.shape[1] >= flash_threshold:
        return blockwise_attention(q, k, v, causal=causal,
                                   segment_ids=segment_ids,
                                   block_q=block_q, block_kv=block_kv)
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = constrain(logits, "batch", "act_heads", None, None)
    mask = None
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        offs = sk - sq  # allow cached prefixes
        mask = (jnp.arange(sq)[:, None] + offs) >= jnp.arange(sk)[None, :]
    if segment_ids is not None:
        seg_mask = (segment_ids[:, :, None] == segment_ids[:, None, :])[:, None]
        mask = seg_mask if mask is None else (mask[None, None] & seg_mask)
    elif mask is not None:
        mask = mask[None, None]
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        *, causal: bool = True,
                        segment_ids: Optional[jax.Array] = None,
                        block_q: int = 512, block_kv: int = 1024) -> jax.Array:
    """Memory-O(S·block) flash attention in pure JAX (online softmax over KV
    blocks, scanned over Q blocks).  This is the compile-memory-safe path for
    prefill_32k and the oracle for the Pallas kernel."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    nq = -(-sq // block_q)
    pad_q = nq * block_q - sq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    nk = -(-sk // block_kv)
    pad_k = nk * block_kv - sk
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    if segment_ids is not None:
        seg_q = jnp.pad(segment_ids, ((0, 0), (0, pad_q)), constant_values=-1)
        seg_k = jnp.pad(segment_ids, ((0, 0), (0, pad_k)), constant_values=-2)
        seg_qb = seg_q.reshape(b, nq, block_q)
        seg_kb = seg_k.reshape(b, nk, block_kv)

    qb = q.reshape(b, nq, block_q, h, hd)
    kb = k.reshape(b, nk, block_kv, h, hd)
    vb = v.reshape(b, nk, block_kv, h, hd)
    offs = sk - sq  # query i attends keys <= i + offs

    def q_step(_, qi):
        qblk, qidx = qi  # (b, block_q, h, hd), scalar block index
        q_pos = qidx * block_q + jnp.arange(block_q) + offs

        def kv_step(carry, ki):
            acc, m, l = carry
            kblk, vblk, kidx = ki
            k_pos = kidx * block_kv + jnp.arange(block_kv)
            s = jnp.einsum("bqhd,bkhd->bhqk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            s = constrain(s, "batch", "act_heads", None, None)
            mask = jnp.ones((block_q, block_kv), bool)
            if causal:
                mask = q_pos[:, None] >= k_pos[None, :]
            mask = jnp.broadcast_to(mask[None, None], s.shape)
            if segment_ids is not None:
                sm = (seg_qb[:, qidx][:, :, None] == seg_kb[:, kidx][:, None, :])
                mask = mask & sm[:, None]
            s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vblk.astype(jnp.float32))
            return (acc, m_new, l), None

        acc0 = jnp.zeros((b, h, block_q, hd), jnp.float32)
        m0 = jnp.full((b, h, block_q), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, block_q), jnp.float32)
        if causal:
            # skip fully-masked KV blocks: last kv block index needed
            last = jnp.minimum(
                (qidx * block_q + block_q - 1 + offs) // block_kv, nk - 1)
        else:
            last = nk - 1
        # lax.scan over all nk blocks; masked blocks contribute exp(-inf)=0,
        # which is exact.  (The Pallas kernel *skips* them — perf only.)
        (acc, m, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nk)))
        l = jnp.maximum(l, 1e-30)
        out = (acc / l[..., None]).astype(q.dtype)  # (b, h, block_q, hd)
        return None, jnp.einsum("bhqd->bqhd", out)

    _, out = jax.lax.scan(q_step, None,
                          (jnp.moveaxis(qb, 1, 0), jnp.arange(nq)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, nq * block_q, h, hd)
    return out[:, :sq]


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cur_len: jax.Array) -> jax.Array:
    """Single-token decode: q (B, 1, H, hd) vs cache (B, S, KV, hd); positions
    >= cur_len are masked out.  ``cur_len`` is a scalar shared by every row
    or a (B,) vector of per-row lengths (the paged decode's gathered
    views, where each row's sequence has its own fill)."""
    groups = q.shape[2] // k_cache.shape[2]
    k = _repeat_kv(k_cache, groups)
    v = _repeat_kv(v_cache, groups)
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    cur_len = jnp.reshape(cur_len, (-1, 1, 1, 1))   # () -> (1,..); (B,) -> (B,..)
    valid = jnp.arange(k.shape[1])[None, None, None, :] < cur_len
    logits = jnp.where(valid, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ------------------------------------------------------------- paged KV
def gather_kv_paged(pool: jax.Array, layer: jax.Array,
                    tables: jax.Array) -> jax.Array:
    """Materialize per-request KV views of one layer of a paged pool.

    ``pool`` is the layer-stacked block store ``(L, NB, BS, C)`` — per
    layer, ``NB`` blocks of ``BS`` token positions each; ``tables (B, W)``
    int32 maps request ``b``'s logical block ``w`` (token positions
    ``[w*BS, (w+1)*BS)``) onto a pool block, ``-1`` padding unassigned
    entries.  Returns layer ``layer``'s dense view ``(B, W*BS, C)`` —
    identical in content (at every position below the request's fill) to
    a per-slot cache's row, so the attention math downstream is the same
    function.  One gather over (layer, block): the layer's whole ``(NB,
    BS, C)`` pool is never sliced out.  Unassigned/garbage entries are
    gathered from block 0 and must be masked by the caller's length
    masking, exactly like a dense cache's unwritten tail.
    """
    B, W = tables.shape
    _, _, BS, C = pool.shape
    # every row's whole W*BS view is read, whatever its fill: where paged
    # decode takes this gather, the serve loop's dispatch_log counts
    # "read_positions" as rows * W * BS * decode steps, and that count must
    # follow any change in what is read (PagedServeLoop._read_positions)
    got = pool[layer, jnp.clip(tables, 0)]               # (B, W, BS, C)
    return got.reshape(B, W * BS, C)


def scatter_kv_paged(pool: jax.Array, layer: jax.Array, new: jax.Array,
                     cur: jax.Array, active: jax.Array,
                     tables: jax.Array) -> jax.Array:
    """Masked per-request KV append into one layer of a paged pool.

    Write ``new (B, 1, C)`` into the layer-stacked pool ``(L, NB, BS,
    C)`` at layer ``layer``, request ``b``'s logical position ``cur[b]``
    — pool block ``tables[b, cur[b] // BS]``, offset ``cur[b] % BS`` —
    for every row with ``active[b]``, in place.  Inactive rows, rows
    whose position falls on an unassigned (``-1``) table entry, and rows
    past their table's width are dropped via an out-of-bounds block index
    (XLA ``mode="drop"``), so a frozen or unallocated slot can never
    corrupt a live block.
    """
    _, NB, BS, _ = pool.shape
    B, W = tables.shape
    cur = jnp.asarray(cur, jnp.int32)
    widx = jnp.clip(cur // BS, 0, W - 1)
    blk = jnp.take_along_axis(tables, widx[:, None], axis=1)[:, 0]
    ok = (jnp.asarray(active).astype(bool) & (blk >= 0)
          & (cur < W * BS))
    blk = jnp.where(ok, blk, NB)                 # OOB -> dropped write
    return pool.at[layer, blk, cur % BS].set(new[:, 0].astype(pool.dtype),
                                             mode="drop")


# ----------------------------------------------------------------- MLPs
def mlp_swiglu(x: jax.Array, wi_gate: jax.Array, wi_up: jax.Array,
               wo: jax.Array) -> jax.Array:
    g = jnp.einsum("bsd,df->bsf", x, wi_gate)
    u = jnp.einsum("bsd,df->bsf", x, wi_up)
    g = constrain(g, "batch", None, "act_mlp")
    u = constrain(u, "batch", None, "act_mlp")
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, wo)


def mlp_gelu(x: jax.Array, wi: jax.Array, bi: jax.Array,
             wo: jax.Array, bo: jax.Array) -> jax.Array:
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, wi) + bi)
    h = constrain(h, "batch", None, "act_mlp")
    return jnp.einsum("bsf,fd->bsd", h, wo) + bo
