"""Plain float32 reference of a dense decoder-only transformer.

Covers the configurations of the Qwen2 / Phi-3 kind: RMSNorm before
attention and MLP, rotary positions (half-split rotation), grouped-query
or multi-head attention with optional q/k/v bias, a SwiGLU MLP, a final
RMSNorm and a head that is either its own matrix or the tied embedding.

Nothing here comes from the program under test.  The benchmark draws the
weights itself (:func:`make_weights`), in one jitted call from the seed,
in the layout that the serving engine takes, and hands the same arrays to
the engine and to :func:`logits_at`.  The reference upcasts them to
float32, runs one layer at a time (``lax.scan``) at
``Precision.HIGHEST`` and returns logits only at the positions asked
for, so a 4k-token sequence fits beside the weights on one chip.

``quant="fp8"`` is the control: every linear layer and the head take
their inputs and weights, and attention its queries, keys and values (an
fp8 KV cache), rounded to float8 e4m3 with one absmax scale per tensor,
accumulating in float32.  It is the precision step below the
configuration's bfloat16, and the comparison must reject it.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Tree = Dict[str, Any]
HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                      # largest finite float8_e4m3fn

# the random weights follow the published configs' initializer_range:
# every matrix, embedding and bias is normal(0, 0.02^2), norm weights are
# one.  (Unit-normal rows in a tied embedding would make every logit
# favour the current token by several standard deviations, and no
# rounding could ever change a served token.)
INIT_STD = 0.02


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The shapes of a configuration file, under short names."""
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    hd = int(cfg.get("head_dim") or d // h)
    kv = int(cfg["num_key_value_heads"])
    return {"L": int(cfg["num_hidden_layers"]), "d": d, "H": h, "KV": kv,
            "hd": hd, "q": h * hd, "kv": kv * hd,
            "f": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"]),
            "tied": bool(cfg["tie_word_embeddings"]),
            "bias": bool(cfg["attention_bias"]),
            "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"])}


def weight_specs(cfg: Dict[str, Any]) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """``path -> (shape, init)`` in the serving engine's parameter layout;
    ``init`` is ``normal`` or ``one``."""
    m = dims(cfg)
    L, d, q, kv, f, V = m["L"], m["d"], m["q"], m["kv"], m["f"], m["V"]
    spec = {
        "embed/tok": ((V, d), "normal"),
        "layers/attn/wq": ((L, d, q), "normal"),
        "layers/attn/wk": ((L, d, kv), "normal"),
        "layers/attn/wv": ((L, d, kv), "normal"),
        "layers/attn/wo": ((L, q, d), "normal"),
        "layers/ln1": ((L, d), "one"),
        "layers/ln2": ((L, d), "one"),
        "layers/mlp/wi_gate": ((L, d, f), "normal"),
        "layers/mlp/wi_up": ((L, d, f), "normal"),
        "layers/mlp/wo": ((L, f, d), "normal"),
        "final_norm": ((d,), "one"),
    }
    if m["bias"]:
        spec.update({"layers/attn/bq": ((L, q), "normal"),
                     "layers/attn/bk": ((L, kv), "normal"),
                     "layers/attn/bv": ((L, kv), "normal")})
    if not m["tied"]:
        spec["lm_head"] = ((d, V), "normal")
    return spec


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 64 bits and more included."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _nest(flat: Dict[str, Any]) -> Tree:
    tree: Tree = {}
    for path, leaf in flat.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return tree


def make_weights(cfg: Dict[str, Any], seed: int,
                 dtype=jnp.bfloat16) -> Tree:
    """Every weight of the model, drawn on the device in ONE jitted call
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

    return _nest(jax.jit(draw)(seed_key(seed)))


# --------------------------------------------------------------- forward
def _q8(a: jax.Array) -> jax.Array:
    """float8 e4m3 round trip with one absmax scale for the tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _linear(x: jax.Array, w: jax.Array, quant: Optional[str]) -> jax.Array:
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _q8(x), _q8(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x (S, heads, hd); rotate the two halves of each head."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv            # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _logits_at(weights: Tree, tokens: jax.Array, at: jax.Array, *,
               cfg_items: Tuple, quant: Optional[str]) -> jax.Array:
    m = dims(dict(cfg_items))
    S = tokens.shape[0]
    H, KV, hd = m["H"], m["KV"], m["hd"]
    pos = jnp.arange(S, dtype=jnp.int32)
    causal = pos[:, None] >= pos[None, :]
    x = weights["embed"]["tok"][tokens].astype(jnp.float32)  # (S, d)

    def layer(x, lp):
        a = lp["attn"]
        h = _rms(x, lp["ln1"], m["eps"])
        q = _linear(h, a["wq"], quant)
        k = _linear(h, a["wk"], quant)
        v = _linear(h, a["wv"], quant)
        if m["bias"]:
            q = q + a["bq"].astype(jnp.float32)
            k = k + a["bk"].astype(jnp.float32)
            v = v + a["bv"].astype(jnp.float32)
        q = _rope(q.reshape(S, H, hd), pos, m["theta"])
        k = _rope(k.reshape(S, KV, hd), pos, m["theta"])
        v = v.reshape(S, KV, hd)
        if quant == "fp8":
            q, k, v = _q8(q), _q8(k), _q8(v)
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
        x = x + _linear(o.reshape(S, H * hd), a["wo"], quant)
        h = _rms(x, lp["ln2"], m["eps"])
        mp = lp["mlp"]
        g = _linear(h, mp["wi_gate"], quant)
        u = _linear(h, mp["wi_up"], quant)
        x = x + _linear(jax.nn.silu(g) * u, mp["wo"], quant)
        return x, None

    x, _ = jax.lax.scan(layer, x, weights["layers"])
    xs = _rms(x[at], weights["final_norm"], m["eps"])        # (G, d)
    head = (weights["embed"]["tok"].T if m["tied"] else weights["lm_head"])
    return _linear(xs, head, quant)[:, :m["V"]]


def logits_at(weights: Tree, cfg: Dict[str, Any], tokens: np.ndarray,
              at: np.ndarray, quant: Optional[str] = None) -> np.ndarray:
    """float32 logits ``(len(at), vocab)``: row ``i`` predicts the token
    after position ``at[i]`` of ``tokens``.  ``tokens`` may be padded at
    the end (causal attention never reads ahead); ``at`` may be padded
    with any valid position and the extra rows ignored."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, bool, str))))
    out = _logits_at(weights, jnp.asarray(tokens, jnp.int32),
                     jnp.asarray(at, jnp.int32), cfg_items=items,
                     quant=quant)
    return np.asarray(out, np.float32)
