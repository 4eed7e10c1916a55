"""RWKV6 "Finch" — attention-free RNN LM with data-dependent decay.

Faithful core (arXiv:2404.05892): per layer a time-mix block (the wkv linear
recurrence with **data-dependent per-channel decay** w_t = exp(-exp(w0 +
tanh(x W_a) W_b)) and bonus u) and a channel-mix block (squared-ReLU FFN with
receptance gate).  Simplifications, documented in DESIGN.md: static token-
shift mixing coefficients (the low-rank *dynamic* mixing of the five streams
is omitted; the *decay* — the headline Finch feature — keeps its full
data-dependent low-rank form), and RMSNorm in place of LayerNorm/GroupNorm.

Shapes: d_model=2560, wkv head dim 64 -> H=40 heads; state (B,H,64,64).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.common import ParamBuilder, rms_norm
from repro.sharding import constrain
from repro.models.linear_scan import chunked_linear_attention, linear_attention_step

__all__ = ["init_params", "forward", "init_state", "decode_step", "prefill"]

Tree = Dict[str, Any]
LORA = 64  # low-rank dim of the data-dependent decay


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype: jnp.dtype = jnp.bfloat16,
                abstract: bool = False) -> Tuple[Tree, Tree]:
    pb = ParamBuilder(key, dtype, abstract=abstract)
    d, f, v, L = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.num_layers
    n = cfg.wkv_head_dim
    H = d // n

    pb.dense("embed/tok", (v, d), ("vocab", "embed"), scale=1.0)

    # ---- time-mix ----
    for name in ("wr", "wk", "wv", "wg", "wo"):
        pb.dense(f"layers/tm/{name}", (L, d, d), ("layers", "embed", "heads"))
    # static token-shift mix coefficients for r,k,v,g,w streams
    pb.dense("layers/tm/mix", (L, 5, d), ("layers", None, "embed"), scale=0.02)
    # data-dependent decay: w = exp(-exp(w0 + tanh(x A) B))
    pb.dense("layers/tm/decay_w0", (L, d), ("layers", "embed"), scale=0.1)
    pb.dense("layers/tm/decay_a", (L, d, LORA), ("layers", "embed", None))
    pb.dense("layers/tm/decay_b", (L, LORA, d), ("layers", None, "embed"))
    pb.dense("layers/tm/bonus_u", (L, H, n), ("layers", "heads", None), scale=0.5)
    pb.ones("layers/tm/out_norm", (L, d), ("layers", "embed"))

    # ---- channel-mix ----
    pb.dense("layers/cm/wk", (L, d, f), ("layers", "embed", "mlp"))
    pb.dense("layers/cm/wv", (L, f, d), ("layers", "mlp", "embed"))
    pb.dense("layers/cm/wr", (L, d, d), ("layers", "embed", "heads"))
    pb.dense("layers/cm/mix", (L, 2, d), ("layers", None, "embed"), scale=0.02)

    pb.ones("layers/ln1", (L, d), ("layers", "embed"))
    pb.ones("layers/ln2", (L, d), ("layers", "embed"))
    pb.ones("final_norm", (d,), ("embed",))
    pb.dense("lm_head", (d, v), ("embed", "vocab"))
    return pb.build()


def _shift(x: jax.Array, prev: Optional[jax.Array] = None) -> jax.Array:
    """Token shift: x_{t-1} (first position gets `prev` or zeros)."""
    shifted = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    if prev is not None:
        shifted = shifted.at[:, 0].set(prev)
    return shifted


def _decay_logw(h_w: jax.Array, lp: Tree) -> jax.Array:
    """log w_t = -exp(w0 + tanh(h W_a) W_b)  (≤ 0, data-dependent)."""
    z = jnp.tanh(jnp.einsum("bsd,dr->bsr", h_w, lp["tm"]["decay_a"]))
    raw = lp["tm"]["decay_w0"].astype(jnp.float32) + jnp.einsum(
        "bsr,rd->bsd", z, lp["tm"]["decay_b"]).astype(jnp.float32)
    return -jnp.exp(raw)


def _time_mix(cfg: ModelConfig, x: jax.Array, lp: Tree,
              prev_x: Optional[jax.Array] = None,
              state: Optional[jax.Array] = None,
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (out, final_state, last_x). x: (B,S,D)."""
    B, S, D = x.shape
    n = cfg.wkv_head_dim
    H = D // n
    xs = _shift(x, prev_x)
    mix = lp["tm"]["mix"]                                   # (5, D)
    streams = [x + (xs - x) * mix[i] for i in range(5)]     # r,k,v,g,w
    hr, hk, hv, hg, hw = streams
    r = constrain(jnp.einsum("bsd,de->bse", hr, lp["tm"]["wr"]),
                  "batch", None, "act_heads")
    k = constrain(jnp.einsum("bsd,de->bse", hk, lp["tm"]["wk"]),
                  "batch", None, "act_heads")
    v = constrain(jnp.einsum("bsd,de->bse", hv, lp["tm"]["wv"]),
                  "batch", None, "act_heads")
    g = constrain(jnp.einsum("bsd,de->bse", hg, lp["tm"]["wg"]),
                  "batch", None, "act_heads")
    log_w = constrain(_decay_logw(hw, lp), "batch", None, "act_heads")

    def heads(t):  # (B,S,D) -> (B,H,S,n)
        return constrain(t.reshape(B, S, H, n).transpose(0, 2, 1, 3),
                         "batch", "act_heads", None, None)

    y, S_fin = chunked_linear_attention(
        heads(r), heads(k), heads(v), heads(log_w),
        u=lp["tm"]["bonus_u"].astype(jnp.float32),
        inclusive=False, chunk=cfg.scan_chunk, initial_state=state)
    y = y.transpose(0, 2, 1, 3).reshape(B, S, D)
    y = rms_norm(y, lp["tm"]["out_norm"], cfg.rms_norm_eps)
    y = y * jax.nn.silu(g.astype(jnp.float32)).astype(y.dtype)
    out = jnp.einsum("bsd,de->bse", y, lp["tm"]["wo"])
    return out, S_fin, x[:, -1]


def _channel_mix(x: jax.Array, lp: Tree,
                 prev_x: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    xs = _shift(x, prev_x)
    mix = lp["cm"]["mix"]
    hk = x + (xs - x) * mix[0]
    hr = x + (xs - x) * mix[1]
    kk = constrain(jnp.einsum("bsd,df->bsf", hk, lp["cm"]["wk"]),
                   "batch", None, "act_mlp")
    kk = jnp.square(jax.nn.relu(kk.astype(jnp.float32))).astype(x.dtype)
    vv = jnp.einsum("bsf,fd->bsd", kk, lp["cm"]["wv"])
    rr = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", hr, lp["cm"]["wr"]
                                   ).astype(jnp.float32)).astype(x.dtype)
    return rr * vv, x[:, -1]


def forward(params: Tree, cfg: ModelConfig, inputs: Dict[str, jax.Array],
            *, remat: str = "full", return_hidden: bool = False,
            cap_e=None) -> Tuple[jax.Array, jax.Array]:
    """Training/prefill forward. Returns (logits (B,S,V), dummy loads)."""
    x = params["embed"]["tok"][inputs["tokens"]]

    def body(x, lp):
        x = constrain(x, "batch", None, "act_embed")
        h = rms_norm(x, lp["ln1"], cfg.rms_norm_eps)
        tm, _, _ = _time_mix(cfg, h, lp)
        x = x + tm
        h = rms_norm(x, lp["ln2"], cfg.rms_norm_eps)
        cm, _ = _channel_mix(h, lp)
        return constrain(x + cm, "batch", None, "act_embed"),             jnp.zeros((1,), jnp.float32)

    if remat == "full":
        body = jax.checkpoint(body)
    x, loads = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    if return_hidden:
        return x, loads
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return logits, loads


def prefill(params: Tree, cfg: ModelConfig, inputs: Dict[str, jax.Array],
            max_len=None, *, remat: str = "full",
            cap_e=None) -> Tuple[jax.Array, Tree]:
    """Process a prompt, producing (last-token logits, recurrent state).
    For an RNN the "KV cache" is O(1): per-layer wkv state + token shifts."""
    del max_len  # state is O(1) in context length
    x = params["embed"]["tok"][inputs["tokens"]]

    def body(x, lp):
        x = constrain(x, "batch", None, "act_embed")
        h = rms_norm(x, lp["ln1"], cfg.rms_norm_eps)
        tm, S_fin, _ = _time_mix(cfg, h, lp)
        sh_tm = h[:, -1]
        x = x + tm
        h2 = rms_norm(x, lp["ln2"], cfg.rms_norm_eps)
        cm, _ = _channel_mix(h2, lp)
        sh_cm = h2[:, -1]
        return (constrain(x + cm, "batch", None, "act_embed"),
                (S_fin, sh_tm, sh_cm))

    if remat == "full":
        body = jax.checkpoint(body)
    x, (wkv, sh_tm, sh_cm) = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = jnp.einsum("bd,dv->bv", x[:, -1], params["lm_head"])
    state = {"wkv": wkv, "shift_tm": sh_tm.astype(x.dtype),
             "shift_cm": sh_cm.astype(x.dtype),
             "len": jnp.asarray(inputs["tokens"].shape[1], jnp.int32)}
    return logits, state


def init_state(cfg: ModelConfig, batch: int,
               dtype: jnp.dtype = jnp.float32,
               abstract: bool = False) -> Tuple[Tree, Tree]:
    """Recurrent decode state (takes the place of a KV cache)."""
    n = cfg.wkv_head_dim
    H = cfg.d_model // n
    L = cfg.num_layers
    z = (jax.ShapeDtypeStruct if abstract
         else (lambda s, d: jnp.zeros(s, d)))
    state = {
        "wkv": z((L, batch, H, n, n), jnp.float32),
        "shift_tm": z((L, batch, cfg.d_model), dtype),
        "shift_cm": z((L, batch, cfg.d_model), dtype),
        "len": z((), jnp.int32),
    }
    specs = {
        "wkv": ("layers", "batch", "heads", None, None),
        "shift_tm": ("layers", "batch", "embed"),
        "shift_cm": ("layers", "batch", "embed"),
        "len": (),
    }
    return state, specs


def decode_step(params: Tree, cfg: ModelConfig, inputs: Dict[str, jax.Array],
                state: Tree, *, cap_e=None) -> Tuple[jax.Array, Tree]:
    """One-token decode. inputs tokens (B,1). O(1) in context length —
    this is why rwkv6 runs the long_500k cell."""
    x = params["embed"]["tok"][inputs["tokens"]][:, 0]    # (B,D)
    B, D = x.shape
    n = cfg.wkv_head_dim
    H = D // n

    def body(x, layer):
        lp, wkv, sh_tm, sh_cm = layer
        h = rms_norm(x, lp["ln1"], cfg.rms_norm_eps)
        mix = lp["tm"]["mix"]
        streams = [h + (sh_tm.astype(h.dtype) - h) * mix[i] for i in range(5)]
        hr, hk, hv, hg, hw = streams
        r = jnp.einsum("bd,de->be", hr, lp["tm"]["wr"])
        k = jnp.einsum("bd,de->be", hk, lp["tm"]["wk"])
        v = jnp.einsum("bd,de->be", hv, lp["tm"]["wv"])
        g = jnp.einsum("bd,de->be", hg, lp["tm"]["wg"])
        z = jnp.tanh(jnp.einsum("bd,dr->br", hw, lp["tm"]["decay_a"]))
        raw = lp["tm"]["decay_w0"].astype(jnp.float32) + jnp.einsum(
            "br,rd->bd", z, lp["tm"]["decay_b"]).astype(jnp.float32)
        log_w = -jnp.exp(raw)

        def hshape(t):
            return t.reshape(B, H, n)

        y, wkv_new = linear_attention_step(
            hshape(r), hshape(k), hshape(v), hshape(log_w), wkv,
            u=lp["tm"]["bonus_u"].astype(jnp.float32), inclusive=False)
        y = y.reshape(B, D)
        y = rms_norm(y, lp["tm"]["out_norm"], cfg.rms_norm_eps)
        y = y * jax.nn.silu(g.astype(jnp.float32)).astype(y.dtype)
        x = x + jnp.einsum("bd,de->be", y, lp["tm"]["wo"])

        h2 = rms_norm(x, lp["ln2"], cfg.rms_norm_eps)
        cmix = lp["cm"]["mix"]
        hk2 = h2 + (sh_cm.astype(h2.dtype) - h2) * cmix[0]
        hr2 = h2 + (sh_cm.astype(h2.dtype) - h2) * cmix[1]
        kk = jnp.einsum("bd,df->bf", hk2, lp["cm"]["wk"])
        kk = jnp.square(jax.nn.relu(kk.astype(jnp.float32))).astype(x.dtype)
        vv = jnp.einsum("bf,fd->bd", kk, lp["cm"]["wv"])
        rr = jax.nn.sigmoid(jnp.einsum("bd,de->be", hr2, lp["cm"]["wr"]
                                       ).astype(jnp.float32)).astype(x.dtype)
        x = x + rr * vv
        return x, (wkv_new, h.astype(sh_tm.dtype), h2.astype(sh_cm.dtype))

    x, (wkv_new, sh_tm_new, sh_cm_new) = jax.lax.scan(
        body, x, (params["layers"], state["wkv"],
                  state["shift_tm"], state["shift_cm"]))
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = jnp.einsum("bd,dv->bv", x, params["lm_head"])
    new_state = {"wkv": wkv_new, "shift_tm": sh_tm_new,
                 "shift_cm": sh_cm_new, "len": state["len"] + 1}
    return logits, new_state
