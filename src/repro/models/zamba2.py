"""Zamba2 — Mamba2 backbone with a shared attention block (arXiv:2411.15242).

54 Mamba2 (SSD) layers; after every 6th layer, ONE shared transformer block
(weights reused across all 9 applications) runs on concat(hidden, embedding)
(2·d_model wide), projecting back to d_model — the Zamba "shared attention"
design that amortizes attention parameters over an SSM backbone.
Simplification noted in DESIGN.md: the per-application LoRA deltas on the
shared block are omitted; one shared block instead of Zamba2's two.

Mamba2/SSD per layer: in_proj -> (z, x, B, C, dt); causal depthwise conv on
(x,B,C); h_t = exp(-exp(A)·dt_t)·h_{t-1} + dt_t·x_t⊗B_t; y = C_t·h_t + D·x_t;
out = out_proj(RMSNorm(y)·silu(z)).  The scan is the shared chunked
linear-attention (scalar-per-head decay path) — same oracle as the Pallas
kernel.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.common import (
    ParamBuilder, apply_rope, attention, decode_attention, make_rope,
    mlp_swiglu, rms_norm,
)
from repro.models.linear_scan import chunked_linear_attention, linear_attention_step
from repro.sharding import constrain

__all__ = ["init_params", "forward", "init_state", "decode_step",
           "prefill", "mamba_dims"]

Tree = Dict[str, Any]
EXPAND = 2
MAMBA_HEAD = 64


def mamba_dims(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(d_inner, n_heads, d_state)."""
    din = EXPAND * cfg.d_model
    return din, din // MAMBA_HEAD, cfg.ssm_state


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype: jnp.dtype = jnp.bfloat16,
                abstract: bool = False) -> Tuple[Tree, Tree]:
    pb = ParamBuilder(key, dtype, abstract=abstract)
    d, v, L = cfg.d_model, cfg.vocab_size, cfg.num_layers
    din, nh, N = mamba_dims(cfg)
    K = cfg.conv_kernel
    every = cfg.shared_attention_every
    assert every and L % every == 0, "num_layers must divide shared_attention_every"

    pb.dense("embed/tok", (v, d), ("vocab", "embed"), scale=1.0)

    # Mamba2 layers (stacked over all L)
    proj_out = 2 * din + 2 * N + nh                  # z, x, B, C, dt
    pb.dense("layers/m/in_proj", (L, d, proj_out), ("layers", "embed", "heads"))
    pb.dense("layers/m/conv_w", (L, K, din + 2 * N), ("layers", None, "heads"),
             scale=1.0 / math.sqrt(K))
    pb.zeros("layers/m/conv_b", (L, din + 2 * N), ("layers", "heads"))
    pb.const("layers/m/A_log",
             jnp.tile(jnp.log(jnp.linspace(1.0, 16.0, nh))[None], (L, 1)),
             ("layers", "heads"))
    pb.ones("layers/m/D", (L, nh), ("layers", "heads"))
    pb.zeros("layers/m/dt_bias", (L, nh), ("layers", "heads"))
    pb.dense("layers/m/out_proj", (L, din, d), ("layers", "heads", "embed"))
    pb.ones("layers/m/norm", (L, din), ("layers", "heads"))
    pb.ones("layers/ln", (L, d), ("layers", "embed"))

    # ONE shared attention+MLP block on concat(hidden, embed) (2d wide)
    pb.dense("shared/wq", (2 * d, cfg.q_dim), ("embed", "heads"))
    pb.dense("shared/wk", (2 * d, cfg.kv_dim), ("embed", "kv"))
    pb.dense("shared/wv", (2 * d, cfg.kv_dim), ("embed", "kv"))
    pb.dense("shared/wo", (cfg.q_dim, d), ("heads", "embed"))
    pb.dense("shared/wi_gate", (2 * d, cfg.d_ff), ("embed", "mlp"))
    pb.dense("shared/wi_up", (2 * d, cfg.d_ff), ("embed", "mlp"))
    pb.dense("shared/wo_mlp", (cfg.d_ff, d), ("mlp", "embed"))
    pb.ones("shared/ln1", (2 * d,), ("embed",))
    pb.ones("shared/ln2", (2 * d,), ("embed",))

    pb.ones("final_norm", (d,), ("embed",))
    pb.dense("lm_head", (d, v), ("embed", "vocab"))
    return pb.build()


# --------------------------------------------------------------- mamba layer
def _mamba_split(cfg: ModelConfig, proj: jax.Array):
    din, nh, N = mamba_dims(cfg)
    z, xin, Bc, Cc, dt = jnp.split(
        proj, [din, 2 * din, 2 * din + N, 2 * din + 2 * N], axis=-1)
    return z, xin, Bc, Cc, dt


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 prev: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv. x: (B,S,C); w: (K,C); returns (y, last K-1 x)."""
    K = w.shape[0]
    if prev is None:
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([prev.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(K)) + b
    return jax.nn.silu(y.astype(jnp.float32)).astype(x.dtype), xp[:, -(K - 1):]


def _mamba_layer(cfg: ModelConfig, x: jax.Array, lp: Tree,
                 ssm_state: Optional[jax.Array] = None,
                 conv_state: Optional[jax.Array] = None):
    """x: (B,S,D) -> (out, new_ssm_state, new_conv_state)."""
    B, S, D = x.shape
    din, nh, N = mamba_dims(cfg)
    x = constrain(x, "batch", None, "act_embed")
    h = rms_norm(x, lp["ln"], cfg.rms_norm_eps)
    proj = constrain(jnp.einsum("bsd,dp->bsp", h, lp["m"]["in_proj"]),
                     "batch", None, "act_heads")
    z, xin, Bc, Cc, dt = _mamba_split(cfg, proj)
    conv_in = jnp.concatenate([xin, Bc, Cc], axis=-1)
    conv_out, conv_new = _causal_conv(conv_in, lp["m"]["conv_w"],
                                      lp["m"]["conv_b"], conv_state)
    xin, Bc, Cc = jnp.split(conv_out, [din, din + N], axis=-1)

    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + lp["m"]["dt_bias"].astype(jnp.float32))  # (B,S,nh)
    a = -jnp.exp(lp["m"]["A_log"].astype(jnp.float32))              # (nh,)
    log_w = dt * a[None, None, :]                                   # (B,S,nh) ≤0

    xh = xin.reshape(B, S, nh, MAMBA_HEAD)
    v = (xh.astype(jnp.float32) * dt[..., None]).astype(x.dtype)
    v = v.transpose(0, 2, 1, 3)                             # (B,nh,S,hd)
    k = jnp.broadcast_to(Bc[:, None], (B, nh, S, N))        # shared across heads
    q = jnp.broadcast_to(Cc[:, None], (B, nh, S, N))
    lw = log_w.transpose(0, 2, 1)                           # (B,nh,S) scalar decay

    if S == 1:
        # decode fast path: one recurrent step, no chunk padding
        S0 = (ssm_state if ssm_state is not None
              else jnp.zeros((B, nh, N, MAMBA_HEAD), jnp.float32))
        y1, S_fin = linear_attention_step(
            q[:, :, 0], k[:, :, 0], v[:, :, 0], lw[:, :, 0], S0,
            inclusive=True)
        y = y1[:, None]                                     # (B,1,nh,hd)
    else:
        y, S_fin = chunked_linear_attention(
            q, k, v, lw, inclusive=True, chunk=cfg.scan_chunk,
            initial_state=ssm_state)
        y = y.transpose(0, 2, 1, 3)                         # (B,S,nh,hd)
    y = y + xh * lp["m"]["D"].astype(x.dtype)[None, None, :, None]
    y = y.reshape(B, S, din)
    y = rms_norm(y, lp["m"]["norm"], cfg.rms_norm_eps)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    y = constrain(y, "batch", None, "act_heads")
    out = jnp.einsum("bsp,pd->bsd", y, lp["m"]["out_proj"])
    return constrain(x + out, "batch", None, "act_embed"), S_fin, conv_new


# --------------------------------------------------------------- shared attn
def _shared_block(cfg: ModelConfig, x: jax.Array, x0: jax.Array, sp: Tree,
                  positions: jax.Array,
                  kv_cache: Optional[Tuple[jax.Array, jax.Array]] = None,
                  cur_len: Optional[jax.Array] = None):
    """Shared attention+MLP on concat(x, x0). Returns (x + delta, (k,v))."""
    B, S, D = x.shape
    cat = jnp.concatenate([x, x0], axis=-1)
    h = rms_norm(cat, sp["ln1"], cfg.rms_norm_eps)
    q = constrain(jnp.einsum("bsd,dq->bsq", h, sp["wq"]).reshape(
        B, S, cfg.num_heads, cfg.head_dim), "batch", None, "act_heads", None)
    k = constrain(jnp.einsum("bsd,dq->bsq", h, sp["wk"]).reshape(
        B, S, cfg.num_kv_heads, cfg.head_dim), "batch", None, "act_kv", None)
    v = constrain(jnp.einsum("bsd,dq->bsq", h, sp["wv"]).reshape(
        B, S, cfg.num_kv_heads, cfg.head_dim), "batch", None, "act_kv", None)
    cos, sin = make_rope(positions, cfg.head_dim, cfg.rope_theta)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    if kv_cache is None:
        a = attention(q, k, v, causal=True,
                      block_q=cfg.attn_block_q, block_kv=cfg.attn_block_kv,
                      flash_threshold=cfg.flash_threshold)
        new_kv = (k, v)
    else:
        kc, vc = kv_cache                          # (B, S_max, KV*hd) flat
        kc = jax.lax.dynamic_update_slice_in_dim(
            kc, k.reshape(B, S, cfg.kv_dim).astype(kc.dtype), cur_len, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(
            vc, v.reshape(B, S, cfg.kv_dim).astype(vc.dtype), cur_len, axis=1)
        S_max = kc.shape[1]
        a = decode_attention(
            q,
            kc.reshape(B, S_max, cfg.num_kv_heads, cfg.head_dim),
            vc.reshape(B, S_max, cfg.num_kv_heads, cfg.head_dim),
            cur_len + 1)
        new_kv = (kc, vc)
    x = x + jnp.einsum("bsq,qd->bsd", a.reshape(B, S, cfg.q_dim), sp["wo"])
    h2 = rms_norm(jnp.concatenate([x, x0], axis=-1), sp["ln2"],
                  cfg.rms_norm_eps)
    x = x + mlp_swiglu(h2, sp["wi_gate"], sp["wi_up"], sp["wo_mlp"])
    return x, new_kv


# ------------------------------------------------------------------ forward
def _group_params(cfg: ModelConfig, params: Tree) -> Tree:
    """Reshape stacked (L, ...) mamba params to (G, every, ...) for the
    two-level scan (outer groups, inner mamba layers)."""
    every = cfg.shared_attention_every
    G = cfg.num_layers // every
    return jax.tree.map(lambda a: a.reshape(G, every, *a.shape[1:]),
                        params["layers"])


def forward(params: Tree, cfg: ModelConfig, inputs: Dict[str, jax.Array],
            *, remat: str = "full", return_hidden: bool = False,
            cap_e=None) -> Tuple[jax.Array, jax.Array]:
    tokens = inputs["tokens"]
    x0 = params["embed"]["tok"][tokens]
    B, S = tokens.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    grouped = _group_params(cfg, params)

    def inner(x, lp):
        y, _, _ = _mamba_layer(cfg, x, lp)
        return y, None

    if remat == "full":
        inner = jax.checkpoint(inner)

    def outer(x, glp):
        x, _ = jax.lax.scan(inner, x, glp)
        x, _ = _shared_block(cfg, x, x0, params["shared"], positions)
        return x, jnp.zeros((1,), jnp.float32)

    x, loads = jax.lax.scan(outer, x0, grouped)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    if return_hidden:
        return x, loads
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return logits, loads


def prefill(params: Tree, cfg: ModelConfig, inputs: Dict[str, jax.Array],
            max_len=None, *, remat: str = "full",
            cap_e=None) -> Tuple[jax.Array, Tree]:
    """Process a prompt: (last-token logits, hybrid state) — O(1) SSM state
    per layer + KV cache for the shared attention blocks."""
    tokens = inputs["tokens"]
    x0 = params["embed"]["tok"][tokens]
    B, S = tokens.shape
    max_len = max_len or S
    positions = jnp.arange(S, dtype=jnp.int32)
    grouped = _group_params(cfg, params)

    def inner(x, lp):
        y, ssm_fin, conv_fin = _mamba_layer(cfg, x, lp)
        return y, (ssm_fin, conv_fin)

    if remat == "full":
        inner = jax.checkpoint(inner)

    def outer(x, glp):
        x, (ssm_fin, conv_fin) = jax.lax.scan(inner, x, glp)
        x, (k, v) = _shared_block(cfg, x, x0, params["shared"], positions)
        pad = max_len - S
        kf = k.reshape(B, S, cfg.kv_dim)
        vf = v.reshape(B, S, cfg.kv_dim)
        if pad > 0:
            kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0)))
            vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0)))
        return x, (ssm_fin, conv_fin, kf.astype(x.dtype), vf.astype(x.dtype))

    x, (ssm, conv, ks, vs) = jax.lax.scan(outer, x0, grouped)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = jnp.einsum("bd,dv->bv", x[:, -1], params["lm_head"])
    L = cfg.num_layers
    state = {
        "ssm": ssm.reshape(L, *ssm.shape[2:]),
        "conv": conv.reshape(L, *conv.shape[2:]),
        "shared_k": ks, "shared_v": vs,
        "len": jnp.asarray(S, jnp.int32),
    }
    return logits, state


# ------------------------------------------------------------------- decode
def init_state(cfg: ModelConfig, batch: int, max_len: int,
               dtype: jnp.dtype = jnp.bfloat16,
               abstract: bool = False) -> Tuple[Tree, Tree]:
    din, nh, N = mamba_dims(cfg)
    L, K = cfg.num_layers, cfg.conv_kernel
    G = L // cfg.shared_attention_every
    z = (jax.ShapeDtypeStruct if abstract
         else (lambda s, d: jnp.zeros(s, d)))
    state = {
        "ssm": z((L, batch, nh, N, MAMBA_HEAD), jnp.float32),
        "conv": z((L, batch, K - 1, din + 2 * N), dtype),
        "shared_k": z((G, batch, max_len, cfg.kv_dim), dtype),  # flat KV
        "shared_v": z((G, batch, max_len, cfg.kv_dim), dtype),
        "len": z((), jnp.int32),
    }
    specs = {
        "ssm": ("layers", "batch", "heads", None, None),
        "conv": ("layers", "batch", None, "heads"),
        "shared_k": ("layers", "batch", "seq_cache", "kv"),
        "shared_v": ("layers", "batch", "seq_cache", "kv"),
        "len": (),
    }
    return state, specs


def decode_step(params: Tree, cfg: ModelConfig, inputs: Dict[str, jax.Array],
                state: Tree, *, cap_e=None) -> Tuple[jax.Array, Tree]:
    tokens = inputs["tokens"]                       # (B,1)
    x0 = params["embed"]["tok"][tokens]             # (B,1,D)
    B = tokens.shape[0]
    cur = state["len"]
    positions = jnp.full((B, 1), cur, jnp.int32)
    every = cfg.shared_attention_every
    G = cfg.num_layers // every
    grouped = _group_params(cfg, params)
    ssm_g = jax.tree.map(
        lambda a: a.reshape(G, every, *a.shape[1:]), state["ssm"])
    conv_g = state["conv"].reshape(G, every, *state["conv"].shape[1:])

    def inner(x, layer):
        lp, ssm, conv = layer
        y, ssm_new, conv_new = _mamba_layer(cfg, x, lp, ssm, conv)
        return y, (ssm_new, conv_new)

    def outer(x, glayer):
        glp, gssm, gconv, kc, vc = glayer
        x, (ssm_new, conv_new) = jax.lax.scan(inner, x, (glp, gssm, gconv))
        x, (kc_new, vc_new) = _shared_block(
            cfg, x, x0, params["shared"], positions, (kc, vc), cur)
        return x, (ssm_new, conv_new, kc_new, vc_new)

    x, (ssm_new, conv_new, k_new, v_new) = jax.lax.scan(
        outer, x0, (grouped, ssm_g, conv_g,
                    state["shared_k"], state["shared_v"]))
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])[:, 0]
    new_state = {
        "ssm": ssm_new.reshape(cfg.num_layers, *ssm_new.shape[2:]),
        "conv": conv_new.reshape(cfg.num_layers, *conv_new.shape[2:]),
        "shared_k": k_new, "shared_v": v_new,
        "len": cur + 1,
    }
    return logits, new_state
