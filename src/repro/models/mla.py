"""Multi-head latent attention (MLA), as DeepSeek-V2/V3 publish it.

Per token ``x`` (hidden ``d``), with ``H`` heads:

  * ``c_q = RMSNorm(x W_qa)`` (``q_lora_rank`` wide), and per head
    ``[q_nope | q_pe] = c_q W_qb`` (``qk_nope_head_dim`` + ``qk_rope_head_dim``);
  * ``[c_kv | k_pe] = x W_kva`` (``kv_lora_rank`` + ``qk_rope_head_dim``),
    ``c_kv = RMSNorm(c_kv)``; ``k_pe`` is ONE rope key that every head
    shares;
  * per head ``k_nope = c_kv W_uk`` and ``v = c_kv W_uv``;
  * score ``(q_nope . k_nope + rope(q_pe) . rope(k_pe)) * s`` with
    ``s = (nope + rope)^-0.5 * m^2`` (``m`` the YaRN attention factor),
    ``o = softmax . v``, then ``W_o``.

Rope rotates interleaved pairs ``(2i, 2i+1)`` of the rope part by YaRN's
frequencies (:func:`yarn_inv_freq`); :func:`rope` returns the rotated
pairs as two halves, a fixed permutation of the lanes that leaves every
dot product as the interleaved rotation gives it.

Two forms compute the same attention:

  * **expanded** (:func:`expanded_attention`): K and V of every head from
    ``c_kv``, then ordinary multi-head attention.  The training forward
    uses it, and the CPU tests check the absorbed form against it.
  * **absorbed** (:func:`absorb_queries`, :func:`latent_attention`,
    :func:`absorb_outputs`): ``q_lat = q_nope W_uk^T`` is scored against
    ``c_kv`` and ``q_pe`` against ``k_pe``, the softmax weights sum
    ``c_kv`` in latent space, and ``W_uv`` maps the result out.  Nothing
    per head is ever built from the cache, so a position costs the cache
    only ``c_kv`` and ``k_pe``.  Both paged programs use it.

The paged pool holds one row per position and layer (:func:`latent_row`):
``[c_kv | k_pe | 0]``, padded to whole 128-lane tiles
(:func:`latent_width`: 640 lanes for 512 + 64).  Scores take the whole
row against ``[q_lat | q_pe | 0]``, and values are its first
``kv_lora_rank`` lanes.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import ParamBuilder, attention, rms_norm
from repro.models.config import ModelConfig

__all__ = ["init_attention", "yarn_inv_freq", "softmax_scale", "rope",
           "latent_width", "project", "absorb_queries", "absorb_outputs",
           "latent_row", "latent_queries", "latent_attention",
           "expanded_attention", "attention_forward"]

LANES = 128


def init_attention(pb: ParamBuilder, cfg: ModelConfig, prefix: str,
                   L: int) -> None:
    """The attention weights of ``L`` stacked layers under ``prefix``."""
    d, H = cfg.d_model, cfg.num_heads
    ql, cl, r = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    n, v = cfg.qk_nope_head_dim, cfg.v_head_dim
    pb.dense(f"{prefix}/wq_a", (L, d, ql), ("layers", "embed", None))
    pb.ones(f"{prefix}/q_norm", (L, ql), ("layers", None))
    pb.dense(f"{prefix}/wq_b", (L, ql, H * (n + r)), ("layers", None, "heads"))
    pb.dense(f"{prefix}/wkv_a", (L, d, cl + r), ("layers", "embed", None))
    pb.ones(f"{prefix}/kv_norm", (L, cl), ("layers", None))
    pb.dense(f"{prefix}/wk_b", (L, cl, H * n), ("layers", None, "heads"))
    pb.dense(f"{prefix}/wv_b", (L, cl, H * v), ("layers", None, "heads"))
    pb.dense(f"{prefix}/wo", (L, H * v, d), ("layers", "heads", "embed"))


# ------------------------------------------------------------------ YaRN
def _yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _correction_dim(rotations: float, dim: int, base: float,
                    max_pos: int) -> float:
    """The rope dimension whose wavelength makes ``rotations`` turns over
    ``max_pos`` positions."""
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))
            / (2 * math.log(base)))


def yarn_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """``(rope/2,)`` float32 inverse frequencies of the rope part: plain
    rope where ``rope_factor`` is 1; else YaRN's blend, the original
    frequencies above ``beta_fast`` rotations over the original context,
    those divided by the factor below ``beta_slow``, a linear ramp
    between."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_factor <= 1:
        return extra.astype(np.float32)
    inter = extra / cfg.rope_factor
    lo = max(math.floor(_correction_dim(cfg.yarn_beta_fast, dim, base,
                                        cfg.rope_original_max)), 0)
    hi = min(math.ceil(_correction_dim(cfg.yarn_beta_slow, dim, base,
                                       cfg.rope_original_max)), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp              # 1: the original frequency
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def _rope_mscale(cfg: ModelConfig) -> float:
    """YaRN's factor on cos and sin (1 where mscale == mscale_all_dim)."""
    return (_yarn_mscale(cfg.rope_factor, cfg.yarn_mscale)
            / _yarn_mscale(cfg.rope_factor, cfg.yarn_mscale_all_dim))


def softmax_scale(cfg: ModelConfig) -> float:
    """``(nope + rope)^-0.5``, times YaRN's ``m^2`` where rope is
    scaled."""
    m = _yarn_mscale(cfg.rope_factor, cfg.yarn_mscale_all_dim)
    return cfg.head_dim ** -0.5 * m * m


def rope(x: jax.Array, positions: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Rotate the interleaved pairs of ``x (B, S, ..., rope)`` at
    ``positions (B, S)`` (or ``(S,)``); returns the rotated pairs as two
    halves (evens, then odds), in float32."""
    inv = jnp.asarray(yarn_inv_freq(cfg))
    pos = jnp.broadcast_to(positions, x.shape[:2]).astype(jnp.float32)
    ang = pos[..., None] * inv                                # (B, S, r/2)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:])
    cos, sin = jnp.cos(ang) * _rope_mscale(cfg), jnp.sin(ang) * _rope_mscale(cfg)
    x = x.astype(jnp.float32)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([even * cos - odd * sin, odd * cos + even * sin],
                           axis=-1)


# ------------------------------------------------------------ projections
def latent_width(cfg: ModelConfig) -> int:
    """Lanes of a pool row: ``c_kv`` and ``k_pe`` in whole 128-lane
    tiles."""
    w = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    return -(-w // LANES) * LANES


def project(cfg: ModelConfig, ap, h: jax.Array, positions: jax.Array
            ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """``h (B, S, d)`` -> ``q_nope (B, S, H, nope)``, rotated ``q_pe (B,
    S, H, rope)``, normed ``c_kv (B, S, kv_lora)`` and rotated ``k_pe
    (B, S, rope)``, in ``h``'s dtype."""
    B, S, _ = h.shape
    H, n = cfg.num_heads, cfg.qk_nope_head_dim
    eps = cfg.rms_norm_eps
    c_q = rms_norm(jnp.einsum("bsd,dr->bsr", h, ap["wq_a"]), ap["q_norm"], eps)
    q = jnp.einsum("bsr,rq->bsq", c_q, ap["wq_b"]).reshape(
        B, S, H, cfg.head_dim)
    kv = jnp.einsum("bsd,dr->bsr", h, ap["wkv_a"])
    c_kv = rms_norm(kv[..., :cfg.kv_lora_rank], ap["kv_norm"], eps)
    q_pe = rope(q[..., n:], positions, cfg).astype(h.dtype)
    k_pe = rope(kv[..., cfg.kv_lora_rank:], positions, cfg).astype(h.dtype)
    return q[..., :n], q_pe, c_kv, k_pe


def absorb_queries(cfg: ModelConfig, ap, q_nope: jax.Array) -> jax.Array:
    """``q_lat = q_nope W_uk^T`` per head: ``(B, S, H, kv_lora)`` f32."""
    w_uk = ap["wk_b"].reshape(cfg.kv_lora_rank, cfg.num_heads,
                              cfg.qk_nope_head_dim)
    return jnp.einsum("bshn,chn->bshc", q_nope, w_uk,
                      preferred_element_type=jnp.float32)


def absorb_outputs(cfg: ModelConfig, ap, o_lat: jax.Array,
                   dtype) -> jax.Array:
    """``o = o_lat W_uv`` per head: ``(B, S, H, kv_lora)`` -> ``(B, S,
    H * v)`` in ``dtype``."""
    B, S = o_lat.shape[:2]
    w_uv = ap["wv_b"].reshape(cfg.kv_lora_rank, cfg.num_heads,
                              cfg.v_head_dim)
    o = jnp.einsum("bshc,chv->bshv", o_lat.astype(dtype), w_uv)
    return o.reshape(B, S, cfg.num_heads * cfg.v_head_dim)


def latent_row(cfg: ModelConfig, c_kv: jax.Array, k_pe: jax.Array
               ) -> jax.Array:
    """The pool rows ``[c_kv | k_pe | 0]``: ``(B, S, latent_width)``."""
    pad = latent_width(cfg) - cfg.kv_lora_rank - cfg.qk_rope_head_dim
    return jnp.concatenate(
        [c_kv, k_pe.astype(c_kv.dtype),
         jnp.zeros(c_kv.shape[:-1] + (pad,), c_kv.dtype)], axis=-1)


def latent_queries(cfg: ModelConfig, q_lat: jax.Array,
                   q_pe: jax.Array) -> jax.Array:
    """The queries the pool rows are scored against, ``[q_lat | q_pe |
    0]``: ``(B, S, H, latent_width)`` float32."""
    pad = latent_width(cfg) - cfg.kv_lora_rank - cfg.qk_rope_head_dim
    return jnp.concatenate(
        [q_lat, q_pe.astype(jnp.float32),
         jnp.zeros(q_lat.shape[:-1] + (pad,), jnp.float32)], axis=-1)


def latent_attention(cfg: ModelConfig, q: jax.Array, rows: jax.Array,
                     mask: jax.Array) -> jax.Array:
    """Absorbed attention over gathered pool rows: ``q (B, S, H, W)``
    (:func:`latent_queries`) against ``rows (B, T, W)``, keys where
    ``mask`` (broadcast to ``(B, H, S, T)``) holds.  Returns ``o_lat (B,
    S, H, kv_lora)`` float32."""
    rows = rows.astype(jnp.float32)
    s = jnp.einsum("bshw,btw->bhst", q, rows) * softmax_scale(cfg)
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    return jnp.einsum("bhst,btc->bshc", p, rows[..., :cfg.kv_lora_rank])


def expanded_attention(cfg: ModelConfig, ap, q_nope: jax.Array,
                       q_pe: jax.Array, c_kv: jax.Array, k_pe: jax.Array,
                       **kw) -> jax.Array:
    """Causal multi-head attention with every head's K and V built from
    ``c_kv`` and the shared ``k_pe`` (``common.attention``; ``kw`` its
    options).  Returns ``(B, S, H * v)``."""
    B, S, H, _ = q_nope.shape
    dt = q_nope.dtype
    k_nope = jnp.einsum("bsc,cq->bsq", c_kv, ap["wk_b"]).reshape(
        B, -1, H, cfg.qk_nope_head_dim)
    v = jnp.einsum("bsc,cq->bsq", c_kv, ap["wv_b"]).reshape(
        B, -1, H, cfg.v_head_dim)
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :].astype(dt),
                            k_nope.shape[:3] + (cfg.qk_rope_head_dim,))
    k = jnp.concatenate([k_nope, k_pe], axis=-1)
    # common.attention scales by head_dim^-0.5: YaRN's m^2 rides on q
    m2 = softmax_scale(cfg) * math.sqrt(cfg.head_dim)
    q = (jnp.concatenate([q_nope, q_pe.astype(dt)], axis=-1)
         * jnp.asarray(m2, dt))
    vd = cfg.v_head_dim
    if S >= kw.get("flash_threshold", 8192) and vd != cfg.head_dim:
        # the blockwise path keeps one head width for q, k and v
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, cfg.head_dim - vd),))
    o = attention(q, k, v, causal=True, **kw)[..., :vd]
    return o.reshape(B, S, H * vd)


def attention_forward(cfg: ModelConfig, ap, h: jax.Array,
                      positions: jax.Array, **kw) -> jax.Array:
    """The full causal forward's attention, expanded: ``h (B, S, d)`` ->
    ``(B, S, d)`` after ``W_o``."""
    q_nope, q_pe, c_kv, k_pe = project(cfg, ap, h, positions)
    o = expanded_attention(cfg, ap, q_nope, q_pe, c_kv, k_pe, **kw)
    return jnp.einsum("bsq,qd->bsd", o, ap["wo"])
