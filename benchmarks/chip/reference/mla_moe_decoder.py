"""Plain float32 reference of one chip's share of a DeepSeek-V3 decoder.

The block is DeepSeek-V3's: RMSNorm before attention and the feed-forward
layer; multi-head latent attention (MLA) in its EXPANDED form — the
queries through a ``q_lora_rank`` bottleneck with its RMSNorm, keys and
values of every head built from the normed ``kv_lora_rank`` latent, the
rope part of each key shared by all heads, YaRN rope on interleaved
pairs, softmax scale ``(nope + rope)^-0.5 * m^2`` with ``m = 0.1 ln(factor)
+ 1``; the first ``first_k_dense_replace`` layers with a dense SwiGLU of
width ``intermediate_size``, the others with ``n_shared_experts`` shared
SwiGLU experts and routed ones of width ``moe_intermediate_size``; a
final RMSNorm and its own head.

The router (``scoring_func`` sigmoid, ``topk_method`` noaux_tc) scores
every published expert by a sigmoid; a correction bias is added for the
ranking only; the experts fall in ``n_group`` groups, each ranked by the
sum of its two best biased scores, and the ``topk_group`` best groups are
eligible; each token picks the ``num_experts_per_tok`` best eligible
experts and weights them by their unbiased scores, renormalised
(``norm_topk_prob``) and times ``routed_scaling_factor``.

The configuration is one chip's share of an expert-parallel deployment:
it holds ``n_routed_experts`` experts of each expert layer, from
``expert_offset`` on, out of the ``published["n_routed_experts"]`` that
the router scores.  Every token goes through every held expert, weighted
by its gate where its picks name that expert and by zero elsewhere; the
experts held on other chips add nothing, as in the program.  The shared
experts and the dense layers are every chip's alike.

Nothing here comes from the program under test.  The weights are drawn
in one jitted call from the seed, in the serving engine's layout, normal
with std 0.02 (the router's correction bias too) and norms one.
:func:`logits_at` upcasts them to float32 a layer at a time
(``lax.scan``) at ``Precision.HIGHEST``, and keeps what it builds small
enough to fit beside them on one chip: attention runs a group of heads at
a time and, within it, a block of queries at a time; the feed-forward
layers a block of positions at a time; the head a block of the
vocabulary at a time.  Sequences are padded to a few lengths
(:func:`_padded_length`), so that few programs are compiled.

``quant="fp8"`` is the control: every linear layer, the router, every
expert and the head take their inputs and weights rounded to float8 e4m3
with one absmax scale per tensor, and attention its queries, keys and
values (per group of heads); the comparison must reject it.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import manifest

dense = manifest.module("reference", "dense_decoder")

Tree = Dict[str, Any]
HIGHEST = dense.HIGHEST
INIT_STD = dense.INIT_STD

HEAD_GROUP = 16        # heads attended together
QUERY_BLOCK = 512      # queries scored together
ROW_BLOCK = 1024       # positions through a feed-forward layer together
VOCAB_BLOCKS = 10      # the head in this many slices of the vocabulary


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The shapes of a configuration file, under short names."""
    rs = cfg["rope_scaling"]
    return {"L": int(cfg["num_hidden_layers"]),
            "L0": int(cfg["first_k_dense_replace"]),
            "d": int(cfg["hidden_size"]), "H": int(cfg["num_attention_heads"]),
            "ql": int(cfg["q_lora_rank"]), "kl": int(cfg["kv_lora_rank"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope": int(cfg["qk_rope_head_dim"]),
            "vd": int(cfg["v_head_dim"]), "F": int(cfg["intermediate_size"]),
            "f": int(cfg["moe_intermediate_size"]),
            "ns": int(cfg["n_shared_experts"]), "V": int(cfg["vocab_size"]),
            "E": int(cfg["published"]["n_routed_experts"]),
            "G": int(cfg["n_routed_experts"]), "off": int(cfg["expert_offset"]),
            "k": int(cfg["num_experts_per_tok"]), "ng": int(cfg["n_group"]),
            "kg": int(cfg["topk_group"]),
            "scaling": float(cfg["routed_scaling_factor"]),
            "theta": float(cfg["rope_theta"]), "factor": float(rs["factor"]),
            "orig": int(rs["original_max_position_embeddings"]),
            "beta_fast": float(rs["beta_fast"]),
            "beta_slow": float(rs["beta_slow"]),
            "mscale": float(rs["mscale"]),
            "mscale_all": float(rs["mscale_all_dim"]),
            "eps": float(cfg["rms_norm_eps"])}


def _attention_specs(p: str, L: int, m: Dict[str, Any]) -> Dict[str, Any]:
    d, H, ql, kl = m["d"], m["H"], m["ql"], m["kl"]
    return {f"{p}/attn/wq_a": ((L, d, ql), "normal"),
            f"{p}/attn/q_norm": ((L, ql), "one"),
            f"{p}/attn/wq_b": ((L, ql, H * (m["nope"] + m["rope"])), "normal"),
            f"{p}/attn/wkv_a": ((L, d, kl + m["rope"]), "normal"),
            f"{p}/attn/kv_norm": ((L, kl), "one"),
            f"{p}/attn/wk_b": ((L, kl, H * m["nope"]), "normal"),
            f"{p}/attn/wv_b": ((L, kl, H * m["vd"]), "normal"),
            f"{p}/attn/wo": ((L, H * m["vd"], d), "normal"),
            f"{p}/ln1": ((L, d), "one"), f"{p}/ln2": ((L, d), "one")}


def weight_specs(cfg: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """``path -> (shape, init)`` in the serving engine's parameter layout
    (the leading dense layers under ``dense_layers``, the expert layers
    under ``layers``); ``init`` is ``normal`` or ``one``."""
    m = dims(cfg)
    L0, L1, d, V = m["L0"], m["L"] - m["L0"], m["d"], m["V"]
    F, f, G, fs = m["F"], m["f"], m["G"], m["ns"] * m["f"]
    spec = {"embed/tok": ((V, d), "normal"), "final_norm": ((d,), "one"),
            "lm_head": ((d, V), "normal")}
    spec.update(_attention_specs("dense_layers", L0, m))
    spec.update({"dense_layers/mlp/wi_gate": ((L0, d, F), "normal"),
                 "dense_layers/mlp/wi_up": ((L0, d, F), "normal"),
                 "dense_layers/mlp/wo": ((L0, F, d), "normal")})
    spec.update(_attention_specs("layers", L1, m))
    spec.update({"layers/moe/router": ((L1, d, m["E"]), "normal"),
                 "layers/moe/router_bias": ((L1, m["E"]), "normal"),
                 "layers/moe/w_gate": ((L1, G, d, f), "normal"),
                 "layers/moe/w_up": ((L1, G, d, f), "normal"),
                 "layers/moe/w_down": ((L1, G, f, d), "normal"),
                 "layers/moe/shared/wi_gate": ((L1, d, fs), "normal"),
                 "layers/moe/shared/wi_up": ((L1, d, fs), "normal"),
                 "layers/moe/shared/wo": ((L1, fs, d), "normal")})
    return spec


def make_weights(cfg: Dict[str, Any], seed: int,
                 dtype=jnp.bfloat16) -> Tree:
    """Every weight of the share, drawn on the device in ONE jitted call
    from ``seed``, in ``dtype`` (the type the engine serves in)."""
    spec = weight_specs(cfg)
    names = sorted(spec)

    def draw(key):
        out = {}
        for i, name in enumerate(names):
            shape, init = spec[name]
            if init == "one":
                out[name] = jnp.ones(shape, dtype)
                continue
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out[name] = (z * INIT_STD).astype(dtype)
        return out

    return dense._nest(jax.jit(draw)(dense.seed_key(seed)))


# ------------------------------------------------------------------ rope
def _yarn_m(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(m: Dict[str, Any]) -> np.ndarray:
    """YaRN's inverse frequencies of the rope part, from its formula:
    ``theta^(-2i/dim)`` where a dimension makes more than ``beta_fast``
    turns over the original context, that divided by ``factor`` where it
    makes fewer than ``beta_slow``, linear in ``i`` between."""
    dim, base = m["rope"], m["theta"]
    i = np.arange(dim // 2, dtype=np.float64)
    plain = base ** (-2 * i / dim)

    def dim_of(turns):     # the dimension making ``turns`` turns
        return dim * math.log(m["orig"] / (turns * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(dim_of(m["beta_fast"])), 0)
    hi = min(math.ceil(dim_of(m["beta_slow"])), dim - 1)
    t = np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)  # 0 plain, 1 scaled
    return (plain * (1 - t) + plain / m["factor"] * t).astype(np.float32)


def _rope(x: jax.Array, pos: jax.Array, m: Dict[str, Any]) -> jax.Array:
    """Rotate each interleaved pair ``(x[2i], x[2i+1])`` of ``x (S, ...,
    rope)`` by ``pos * inv_freq[i]``, in place (the pairs stay
    interleaved)."""
    scale = _yarn_m(m["factor"], m["mscale"]) / _yarn_m(m["factor"], m["mscale_all"])
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq(m))
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + ang.shape[-1:])
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    pairs = x.reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


# --------------------------------------------------------------- forward
def _q(a: jax.Array, quant: Optional[str]) -> jax.Array:
    a = a.astype(jnp.float32)
    return dense._q8(a) if quant == "fp8" else a


def _by_rows(fn, h: jax.Array) -> jax.Array:
    """``fn`` on ``h (S, d)`` a block of ``ROW_BLOCK`` positions at a
    time."""
    S = h.shape[0]
    out = jax.lax.map(fn, h.reshape(S // ROW_BLOCK, ROW_BLOCK, -1))
    return out.reshape(S, -1)


def _swiglu(h, wg, wu, wd, quant):
    g = dense._linear(h, wg, quant)
    u = dense._linear(h, wu, quant)
    return dense._linear(jax.nn.silu(g) * u, wd, quant)


def _attention(h: jax.Array, a: Tree, pos: jax.Array, m: Dict[str, Any],
               quant: Optional[str]) -> jax.Array:
    """Expanded MLA over ``h (S, d)`` (normed), causal: each group of
    ``HEAD_GROUP`` heads builds its queries, keys and values, attends a
    block of queries at a time, and adds its share of the output
    projection.  Returns ``(S, d)``."""
    S = h.shape[0]
    H, nope, r, vd, kl = m["H"], m["nope"], m["rope"], m["vd"], m["kl"]
    hg = math.gcd(HEAD_GROUP, H)
    ng = H // hg
    scale = (nope + r) ** -0.5 * _yarn_m(m["factor"], m["mscale_all"]) ** 2
    c_q = dense._rms(dense._linear(h, a["wq_a"], quant), a["q_norm"], m["eps"])
    kv = dense._linear(h, a["wkv_a"], quant)
    c_kv = dense._rms(kv[:, :kl], a["kv_norm"], m["eps"])
    k_pe = _rope(kv[:, kl:], pos, m)                               # (S, r)

    def groups(w, width):       # (rows, H*width) -> (ng, rows, hg*width)
        return w.reshape(w.shape[0], ng, hg * width).swapaxes(0, 1)

    def group(out, ws):
        wq, wk, wv, wo = ws
        q = dense._linear(c_q, wq, quant).reshape(S, hg, nope + r)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, m)], -1)
        k = dense._linear(c_kv, wk, quant).reshape(S, hg, nope)
        k = jnp.concatenate([k, jnp.broadcast_to(k_pe[:, None], (S, hg, r))], -1)
        v = dense._linear(c_kv, wv, quant).reshape(S, hg, vd)
        if quant == "fp8":
            q, k, v = dense._q8(q), dense._q8(k), dense._q8(v)

        def block(i):
            qb = jax.lax.dynamic_slice_in_dim(q, i * QUERY_BLOCK, QUERY_BLOCK)
            s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * scale
            qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
            s = jnp.where(qpos[None, :, None] >= jnp.arange(S)[None, None, :],
                          s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                              precision=HIGHEST)

        o = jax.lax.map(block, jnp.arange(S // QUERY_BLOCK))
        o = o.reshape(S, hg * vd)
        return out + dense._linear(o, wo, quant), None

    wo = a["wo"].reshape(ng, hg * vd, -1)
    out, _ = jax.lax.scan(group, jnp.zeros(h.shape, jnp.float32),
                          (groups(a["wq_b"], nope + r), groups(a["wk_b"], nope),
                           groups(a["wv_b"], vd), wo))
    return out


def route(h: jax.Array, router: jax.Array, bias: jax.Array,
          m: Dict[str, Any], quant: Optional[str]) -> jax.Array:
    """``(S, E)`` gates: each token's picks weighted by their unbiased
    sigmoid scores, renormalised and scaled; zero elsewhere."""
    S, E, ng = h.shape[0], m["E"], m["ng"]
    scores = jax.nn.sigmoid(dense._linear(h, router, quant))      # (S, E)
    ranked = scores + bias.astype(jnp.float32)
    best2 = jnp.sort(ranked.reshape(S, ng, E // ng), axis=-1)[..., -2:].sum(-1)
    keep = jnp.argsort(-best2, axis=-1, stable=True)[:, :m["kg"]]    # groups
    eligible = jax.nn.one_hot(keep, ng).sum(axis=1) > 0           # (S, ng)
    ranked = jnp.where(jnp.repeat(eligible, E // ng, axis=-1), ranked, -jnp.inf)
    picks = jnp.argsort(-ranked, axis=-1, stable=True)[:, :m["k"]]  # (S, k)
    w = jnp.take_along_axis(scores, picks, axis=-1)
    w = w / w.sum(axis=-1, keepdims=True) * m["scaling"]
    return jnp.einsum("sk,ske->se", w, jax.nn.one_hot(picks, E))


def _experts(h: jax.Array, mp: Tree, m: Dict[str, Any],
             quant: Optional[str]) -> jax.Array:
    """The shared experts and the held share of the routed ones for ``h
    (rows, d)``: every row through every held expert, weighted by its
    gate for that expert."""
    gates = route(h, mp["router"], mp["router_bias"], m, quant)
    weight = jax.lax.dynamic_slice_in_dim(gates, m["off"], m["G"], axis=1)
    x = _q(h, quant)
    g = jnp.einsum("sd,gdf->gsf", x, _q(mp["w_gate"], quant), precision=HIGHEST)
    u = jnp.einsum("sd,gdf->gsf", x, _q(mp["w_up"], quant), precision=HIGHEST)
    y = jnp.einsum("gsf,gfd->gsd", _q(jax.nn.silu(g) * u, quant),
                   _q(mp["w_down"], quant), precision=HIGHEST)
    out = jnp.einsum("gsd,sg->sd", y, weight, precision=HIGHEST)
    sp = mp["shared"]
    return out + _swiglu(h, sp["wi_gate"], sp["wi_up"], sp["wo"], quant)


def _hidden(weights: Tree, tokens: jax.Array, m: Dict[str, Any],
            quant: Optional[str]) -> jax.Array:
    """Final hidden states ``(S, d)`` before the norm."""
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = weights["embed"]["tok"][tokens].astype(jnp.float32)

    def layer(moe):
        def body(x, lp):
            x = x + _attention(dense._rms(x, lp["ln1"], m["eps"]), lp["attn"],
                               pos, m, quant)
            h = dense._rms(x, lp["ln2"], m["eps"])
            if moe:
                return x + _by_rows(lambda r: _experts(r, lp["moe"], m, quant), h), None
            mp = lp["mlp"]
            return x + _by_rows(lambda r: _swiglu(
                r, mp["wi_gate"], mp["wi_up"], mp["wo"], quant), h), None
        return body

    x, _ = jax.lax.scan(layer(False), x, weights["dense_layers"])
    x, _ = jax.lax.scan(layer(True), x, weights["layers"])
    return x


@partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _logits_at(weights: Tree, tokens: jax.Array, at: jax.Array, *,
               cfg_items: Tuple, quant: Optional[str]) -> jax.Array:
    m = dims(_unflat(cfg_items))
    x = _hidden(weights, tokens, m, quant)
    xs = dense._rms(x[at], weights["final_norm"], m["eps"])    # (len(at), d)
    head = weights["lm_head"]
    nb = max(b for b in range(1, VOCAB_BLOCKS + 1) if head.shape[1] % b == 0)
    width = head.shape[1] // nb

    def block(i):
        w = jax.lax.dynamic_slice_in_dim(head, i * width, width, axis=1)
        return dense._linear(xs, w, quant)

    out = jax.lax.map(block, jnp.arange(nb))                  # (nb, n, width)
    return out.swapaxes(0, 1).reshape(xs.shape[0], -1)[:, :m["V"]]


def _flat(cfg: Dict[str, Any]) -> Tuple:
    """The configuration's numbers and strings as a hashable static
    argument (nested groups under ``<group>.<key>``)."""
    out = {k: v for k, v in cfg.items()
           if isinstance(v, (int, float, bool, str))}
    for group in ("published", "rope_scaling"):
        out.update({f"{group}.{k}": v for k, v in cfg[group].items()
                    if isinstance(v, (int, float, bool, str))})
    return tuple(sorted(out.items()))


def _unflat(items: Tuple) -> Dict[str, Any]:
    cfg: Dict[str, Any] = {"published": {}, "rope_scaling": {}}
    for k, v in items:
        group, _, key = k.partition(".")
        if key:
            cfg[group][key] = v
        else:
            cfg[k] = v
    return cfg


def _padded_length(n: int) -> int:
    """The length a sequence of ``n`` tokens is computed at, so that a few
    programs serve every length: a power of two from 1024 to 4096, then a
    multiple of 4096 up to 12288, then of 2048."""
    if n <= 4096:
        return max(ROW_BLOCK, 1 << (n - 1).bit_length())
    step = 4096 if n <= 12288 else 2048
    return -(-n // step) * step


def logits_at(weights: Tree, cfg: Dict[str, Any], tokens: np.ndarray,
              at: np.ndarray, quant: Optional[str] = None) -> np.ndarray:
    """float32 logits ``(len(at), vocab)``: row ``i`` predicts the token
    after position ``at[i]`` of ``tokens``.  ``tokens`` may be padded at
    the end (causal attention never reads ahead); ``at`` may be padded
    with any valid position and the extra rows ignored."""
    tokens = np.asarray(tokens, np.int32)
    padded = np.zeros(_padded_length(int(tokens.size)), np.int32)
    padded[:tokens.size] = tokens
    out = _logits_at(weights, jnp.asarray(padded), jnp.asarray(at, jnp.int32),
                     cfg_items=_flat(cfg), quant=quant)
    return np.asarray(out, np.float32)
