"""Decoder-only transformer covering the dense / MoE / audio / VLM archs.

One implementation, feature-flagged by ``ModelConfig``:
  * GQA attention with optional qk-norm (qwen3), qkv-bias (qwen2 family),
    RoPE / M-RoPE (qwen2-vl) / sinusoidal (musicgen) positions;
  * SwiGLU or GELU MLP, or MoE FFN with UDS-planned capacities;
  * token or stub-frontend (precomputed embeddings) inputs;
  * scan-over-layers with configurable remat for O(1) HLO depth;
  * full train forward, 32k prefill (blockwise attention), KV-cache decode.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.ops import (paged_attention,
                                               paged_prefill_attention)
from repro.kernels.paged_attention.ops import supports as paged_kernel_supports
from repro.models.config import ModelConfig
from repro.models.common import (ParamBuilder, _repeat_kv, apply_mrope,
                                 apply_rope, decode_attention,
                                 gather_kv_paged, make_rope, mlp_gelu,
                                 mlp_swiglu, rms_norm, scatter_kv_paged,
                                 sinusoidal_positions)
from repro.models.moe import moe_ffn, moe_held
from repro.sharding import constrain, current_rules

__all__ = ["init_params", "forward", "init_cache", "decode_step", "prefill",
           "init_paged_cache", "paged_decode_step", "fused_paged_decode_steps",
           "prefill_paged_chunk", "paged_kernel_engages"]

Tree = Dict[str, Any]


# ---------------------------------------------------------------------- init
def init_params(cfg: ModelConfig, key: jax.Array,
                dtype: jnp.dtype = jnp.bfloat16,
                abstract: bool = False) -> Tuple[Tree, Tree]:
    pb = ParamBuilder(key, dtype, abstract=abstract)
    d, hd = cfg.d_model, cfg.head_dim
    L, f = cfg.num_layers, cfg.d_ff
    v = cfg.padded_vocab      # pad so the vocab axis shards evenly (minicpm)

    pb.dense("embed/tok", (v, d), ("vocab", "embed"), scale=1.0)

    # --- per-layer stacked params (leading `layers` axis, consumed by scan)
    pb.dense("layers/attn/wq", (L, d, cfg.q_dim), ("layers", "embed", "heads"))
    pb.dense("layers/attn/wk", (L, d, cfg.kv_dim), ("layers", "embed", "kv"))
    pb.dense("layers/attn/wv", (L, d, cfg.kv_dim), ("layers", "embed", "kv"))
    pb.dense("layers/attn/wo", (L, cfg.q_dim, d), ("layers", "heads", "embed"))
    if cfg.qkv_bias:
        pb.zeros("layers/attn/bq", (L, cfg.q_dim), ("layers", "heads"))
        pb.zeros("layers/attn/bk", (L, cfg.kv_dim), ("layers", "kv"))
        pb.zeros("layers/attn/bv", (L, cfg.kv_dim), ("layers", "kv"))
    if cfg.qk_norm:
        pb.ones("layers/attn/q_norm", (L, hd), ("layers", None))
        pb.ones("layers/attn/k_norm", (L, hd), ("layers", None))
    pb.ones("layers/ln1", (L, d), ("layers", "embed"))
    pb.ones("layers/ln2", (L, d), ("layers", "embed"))

    if cfg.is_moe:
        E, G = cfg.num_experts, cfg.held_experts  # router width, experts held
        pb.dense("layers/moe/router", (L, d, E), ("layers", "embed", None))
        pb.dense("layers/moe/w_gate", (L, G, d, f),
                 ("layers", "experts", "embed", "mlp"))
        pb.dense("layers/moe/w_up", (L, G, d, f),
                 ("layers", "experts", "embed", "mlp"))
        pb.dense("layers/moe/w_down", (L, G, f, d),
                 ("layers", "experts", "mlp", "embed"))
    elif cfg.mlp == "swiglu":
        pb.dense("layers/mlp/wi_gate", (L, d, f), ("layers", "embed", "mlp"))
        pb.dense("layers/mlp/wi_up", (L, d, f), ("layers", "embed", "mlp"))
        pb.dense("layers/mlp/wo", (L, f, d), ("layers", "mlp", "embed"))
    else:  # gelu (musicgen)
        pb.dense("layers/mlp/wi", (L, d, f), ("layers", "embed", "mlp"))
        pb.zeros("layers/mlp/bi", (L, f), ("layers", "mlp"))
        pb.dense("layers/mlp/wo", (L, f, d), ("layers", "mlp", "embed"))
        pb.zeros("layers/mlp/bo", (L, d), ("layers", "embed"))

    pb.ones("final_norm", (d,), ("embed",))
    if not cfg.tie_embeddings:
        pb.dense("lm_head", (d, v), ("embed", "vocab"))
    return pb.build()


# ------------------------------------------------------------------- layers
def _head_shards(cfg: ModelConfig) -> int:
    """Product of mesh-axis sizes the act_heads rule maps to (1 if none)."""
    ctx = current_rules()
    if ctx is None:
        return 1
    _, rules, sizes = ctx
    ax = rules.get("act_heads")
    axes = (ax,) if isinstance(ax, str) else tuple(ax or ())
    n = 1
    for a in axes:
        n *= sizes.get(a, 1)
    return max(n, 1)


def _padded_attention(cfg: ModelConfig, q, k, v, **kw):
    """Attention with the head dim padded to a shardable multiple.

    Archs whose head count doesn't divide the model axis (minicpm 36H,
    qwen2-vl 28H on a 16-way axis) otherwise force GSPMD to replicate the
    per-head score tensors — measured 12.4 TB/chip of block-wise
    all-gathers on minicpm prefill_32k.  Zero-padded heads produce uniform
    softmax outputs that are sliced off before the output projection
    (48/36 = 1.33x attention FLOPs for a ~60x collective reduction).
    """
    from repro.models.common import attention as _attn
    H = q.shape[2]
    n = _head_shards(cfg)
    if n <= 1 or H % n == 0:
        return _attn(q, k, v, **kw)
    Hp = -(-H // n) * n
    kv = k.shape[2]
    while Hp % kv and (Hp // kv) * kv != Hp:   # keep GQA groups integral
        Hp += n
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, Hp - H), (0, 0)))
    if kv == H:                                 # MHA: pad k/v alongside
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Hp - H), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Hp - H), (0, 0)))
    qp = constrain(qp, "batch", None, "act_heads", None)
    out = _attn(qp, k, v, **kw)
    return out[:, :, :H]


def _attn_qkv(lp: Tree, cfg: ModelConfig, h: jax.Array
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = h.shape
    q = jnp.einsum("bsd,dq->bsq", h, lp["attn"]["wq"])
    k = jnp.einsum("bsd,dq->bsq", h, lp["attn"]["wk"])
    v = jnp.einsum("bsd,dq->bsq", h, lp["attn"]["wv"])
    if cfg.qkv_bias:
        q = q + lp["attn"]["bq"]
        k = k + lp["attn"]["bk"]
        v = v + lp["attn"]["bv"]
    q = constrain(q.reshape(B, S, cfg.num_heads, cfg.head_dim),
                  "batch", None, "act_heads", None)
    k = constrain(k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim),
                  "batch", None, "act_kv", None)
    v = constrain(v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim),
                  "batch", None, "act_kv", None)
    if cfg.qk_norm:
        q = rms_norm(q, lp["attn"]["q_norm"])
        k = rms_norm(k, lp["attn"]["k_norm"])
    return q, k, v


def _position_rotate(cfg: ModelConfig, q: jax.Array, k: jax.Array,
                     positions: jax.Array,
                     positions_3d: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    if cfg.positional != "rope":
        return q, k
    if cfg.mrope_sections is not None:
        assert positions_3d is not None, "qwen2-vl requires positions_3d (3,B,S)"
        q = apply_mrope(q, positions_3d, cfg.head_dim, cfg.rope_theta,
                        cfg.mrope_sections)
        k = apply_mrope(k, positions_3d, cfg.head_dim, cfg.rope_theta,
                        cfg.mrope_sections)
        return q, k
    cos, sin = make_rope(positions, cfg.head_dim, cfg.rope_theta)
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin)


def _layer(cfg: ModelConfig, x: jax.Array, lp: Tree,
           positions: jax.Array, positions_3d: Optional[jax.Array],
           segment_ids: Optional[jax.Array],
           cap_e: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """One decoder block. Returns (x, expert_load or zeros)."""
    x = constrain(x, "batch", None, "act_embed")
    h = rms_norm(x, lp["ln1"])
    q, k, v = _attn_qkv(lp, cfg, h)
    q, k = _position_rotate(cfg, q, k, positions, positions_3d)
    a = _padded_attention(cfg, q, k, v, causal=True, segment_ids=segment_ids,
                          block_q=cfg.attn_block_q,
                          block_kv=cfg.attn_block_kv,
                          flash_threshold=cfg.flash_threshold)
    B, S = x.shape[:2]
    a = constrain(a.reshape(B, S, cfg.q_dim), "batch", None, "act_heads")
    x = x + jnp.einsum("bsq,qd->bsd", a, lp["attn"]["wo"])
    x = constrain(x, "batch", None, "act_embed")

    h = rms_norm(x, lp["ln2"])
    if cfg.is_moe:
        out, load = moe_ffn(h, lp["moe"]["router"], lp["moe"]["w_gate"],
                            lp["moe"]["w_up"], lp["moe"]["w_down"], cfg, cap_e)
    elif cfg.mlp == "swiglu":
        out = mlp_swiglu(h, lp["mlp"]["wi_gate"], lp["mlp"]["wi_up"],
                         lp["mlp"]["wo"])
        load = jnp.zeros((1,), jnp.float32)
    else:
        out = mlp_gelu(h, lp["mlp"]["wi"], lp["mlp"]["bi"],
                       lp["mlp"]["wo"], lp["mlp"]["bo"])
        load = jnp.zeros((1,), jnp.float32)
    return constrain(x + out, "batch", None, "act_embed"), load


# ------------------------------------------------------------------ forward
def _embed_inputs(cfg: ModelConfig, params: Tree, inputs: Dict[str, jax.Array]
                  ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """Returns (x (B,S,D), positions (B,S) or (S,), positions_3d or None)."""
    if cfg.frontend != "none":
        x = inputs["embeds"].astype(params["embed"]["tok"].dtype)
    else:
        x = params["embed"]["tok"][inputs["tokens"]]
    x = constrain(x, "batch", None, "act_embed")
    B, S = x.shape[:2]
    positions = inputs.get("positions")
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    if cfg.positional == "sinusoidal":
        x = x + sinusoidal_positions(positions, cfg.d_model).astype(x.dtype)
    return x, positions, inputs.get("positions_3d")


def forward(params: Tree, cfg: ModelConfig, inputs: Dict[str, jax.Array],
            *, remat: str = "full", return_hidden: bool = False,
            cap_e: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Full causal forward. Returns (logits (B,S,V), expert_loads (L,E)|(L,1)).

    ``inputs``: tokens (B,S) int32 | embeds (B,S,D), optional positions,
    positions_3d (3,B,S), segment_ids (B,S) for packed sequences.
    ``remat``: "full" | "none" — activation checkpointing policy of the scan.
    ``return_hidden``: return final-norm hidden states instead of logits
    (the chunked-CE loss path never materializes (B,S,V) logits).
    """
    x, positions, pos3d = _embed_inputs(cfg, params, inputs)
    segment_ids = inputs.get("segment_ids")

    def body(x, lp):
        y, load = _layer(cfg, x, lp, positions, pos3d, segment_ids, cap_e)
        return y, load

    if remat == "full":
        body = jax.checkpoint(body)
    x, loads = jax.lax.scan(body, x, params["layers"])

    x = rms_norm(x, params["final_norm"])
    if return_hidden:
        return x, loads
    head = (params["embed"]["tok"].T if cfg.tie_embeddings
            else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", x, head)[..., :cfg.vocab_size]
    return logits, loads


# -------------------------------------------------------------------- decode
def cache_dtype(cfg: ModelConfig, default=jnp.bfloat16):
    if cfg.kv_cache_dtype == "fp8":
        return jnp.float8_e4m3fn
    return default


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype: jnp.dtype = jnp.bfloat16,
               abstract: bool = False) -> Tuple[Tree, Tree]:
    """KV cache: (L, B, max_len, KV*hd) per k/v + current length scalar.

    The kv-heads dim is stored *flattened* with head_dim so the "kv" logical
    axis shards evenly even when num_kv_heads < model-axis size (grok: 8 kv
    heads on a 16-way axis shard as 1024 = 8·128 columns / 64 per chip).
    ``cfg.kv_cache_dtype="fp8"`` stores the cache in f8e4m3 (half the HBM;
    attention math upcasts on read — the standard serving memory lever).
    """
    dtype = cache_dtype(cfg, dtype)
    shape = (cfg.num_layers, batch, max_len, cfg.kv_dim)
    z = (jax.ShapeDtypeStruct if abstract
         else (lambda s, d: jnp.zeros(s, d)))
    cache = {
        "k": z(shape, dtype),
        "v": z(shape, dtype),
        "len": z((), jnp.int32),
    }
    specs = {
        "k": ("layers", "batch", "seq_cache", "kv"),
        "v": ("layers", "batch", "seq_cache", "kv"),
        "len": (),
    }
    return cache, specs


def _held_ffn(cfg: ModelConfig, lp: Tree, h: jax.Array, valid: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """The paged programs' expert layer: :func:`moe_held` on one layer's
    weights, over the tokens ``valid (B, S)`` marks."""
    m = lp["moe"]
    return moe_held(h, m["router"], m["w_gate"], m["w_up"], m["w_down"], cfg,
                    valid)


def _expert_counters(sizes: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The paged programs' counters of one pass over the layers, from
    ``sizes (L, G)``, the slots each held expert took in each layer:
    ``expert_slots``, every slot a held expert computed, and
    ``experts_used``, the held experts that took at least one slot (each
    one read of an expert's weights)."""
    return sizes.sum(dtype=jnp.int32), (sizes > 0).sum(dtype=jnp.int32)


def _dense_attend(cfg: ModelConfig, q: jax.Array, kc: jax.Array,
                  vc: jax.Array, attend_len: jax.Array) -> jax.Array:
    """:func:`decode_attention` over flat ``(B, S, KV*hd)`` cache rows (one
    layer's)."""
    shape = kc.shape[:2] + (cfg.num_kv_heads, cfg.head_dim)
    return decode_attention(q, kc.reshape(shape).astype(q.dtype),
                            vc.reshape(shape).astype(q.dtype), attend_len)


def _decode_forward(params: Tree, cfg: ModelConfig,
                    inputs: Dict[str, jax.Array], cache: Tree,
                    positions: jax.Array, kv_append, attend_len: jax.Array,
                    cap_e: Optional[jax.Array],
                    attend=None, moe_valid: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, jax.Array, jax.Array,
                               Optional[jax.Array]]:
    """The one-token decode body of :func:`decode_step` (per-slot) and
    :func:`paged_decode_step` (paged).

    The two differ ONLY in how a layer's new K/V row lands in the cache
    (``kv_append(cache, l, new_(B,1,kv))``: ``dynamic_update_slice`` at a
    scalar length vs a block-table paged scatter), in how attention reads
    the cache back (``attend(q, kc, vc, l)``: by default
    :func:`decode_attention` over layer ``l``'s dense rows with
    ``attend_len`` masking; the paged pool supplies its own reader, see
    :func:`paged_decode_step`), and in the position/length values fed to
    rotary and attention masking — everything else (qkv, residual,
    MLP/MoE, final norm, head) is this one function, so the engines cannot
    drift apart.

    The layer-stacked caches ``(L, ...)`` are the layer scan's carry, not
    scanned inputs: each layer writes its K/V into them in place at its
    layer index ``l`` and reads them back there, so no call slices a
    layer's cache out of the stack or copies the stack whole.

    Each stage runs in a named scope (``embed``, ``qkv``, ``kv_append``,
    ``attention``, ``attn_out``, ``mlp``, ``head``; the paged gather adds
    ``kv_gather``, the held-share expert layer ``router`` and ``experts``
    inside ``mlp``), so a device trace can tell the program's operations
    apart.

    ``moe_valid (B,)``, given for an MoE config (the paged engine), runs
    the dropless held-share expert layer (:func:`moe_held`) over the rows
    it marks and counts their expert slots; otherwise (the per-slot
    :func:`decode_step`) an MoE config runs the capacity layer
    (:func:`moe_ffn`, ``cap_e``).

    Returns (logits (B, V), new_k, new_v, the held experts' slots per
    layer ``(L, G)``, or None where the held-share layer did not run).
    """
    if attend is None:
        def attend(q, kc, vc, l):
            with jax.named_scope("attention"):
                return _dense_attend(cfg, q, kc[l], vc[l], attend_len)
    with jax.named_scope("embed"):
        if cfg.frontend != "none":
            x = inputs["embeds"].astype(params["embed"]["tok"].dtype)
        else:
            x = params["embed"]["tok"][inputs["tokens"]]
        if cfg.positional == "sinusoidal":
            x = x + sinusoidal_positions(positions, cfg.d_model).astype(x.dtype)
    B = x.shape[0]
    pos3d = inputs.get("positions_3d")  # (3,B,1) for qwen2-vl
    held = cfg.is_moe and moe_valid is not None

    def body(carry, layer):
        x, kc, vc = carry                       # kc/vc: (L, ...) stacked
        lp, l = layer
        with jax.named_scope("qkv"):
            h = rms_norm(x, lp["ln1"])
            q, k, v = _attn_qkv(lp, cfg, h)
            q, k = _position_rotate(cfg, q, k, positions, pos3d)
        with jax.named_scope("kv_append"):
            kc = kv_append(kc, l, k.reshape(B, 1, cfg.kv_dim))
            vc = kv_append(vc, l, v.reshape(B, 1, cfg.kv_dim))
        a = attend(q, kc, vc, l)
        with jax.named_scope("attn_out"):
            a = a.reshape(B, 1, cfg.q_dim)
            x = x + jnp.einsum("bsq,qd->bsd", a, lp["attn"]["wo"])
        sizes = ()
        with jax.named_scope("mlp"):
            h = rms_norm(x, lp["ln2"])
            if held:
                out, sizes = _held_ffn(cfg, lp, h, moe_valid[:, None])
            elif cfg.is_moe:
                out, _ = moe_ffn(h, lp["moe"]["router"], lp["moe"]["w_gate"],
                                 lp["moe"]["w_up"], lp["moe"]["w_down"], cfg,
                                 cap_e)
            elif cfg.mlp == "swiglu":
                out = mlp_swiglu(h, lp["mlp"]["wi_gate"], lp["mlp"]["wi_up"],
                                 lp["mlp"]["wo"])
            else:
                out = mlp_gelu(h, lp["mlp"]["wi"], lp["mlp"]["bi"],
                               lp["mlp"]["wo"], lp["mlp"]["bo"])
            x = x + out
        return (x, kc, vc), sizes

    (x, new_k, new_v), sizes = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"])
        head = (params["embed"]["tok"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = jnp.einsum("bsd,dv->bsv", x, head)[:, 0, :cfg.vocab_size]
    return logits, new_k, new_v, (sizes if held else None)


# -------------------------------------------------------------- paged KV
def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     dtype: jnp.dtype = jnp.bfloat16,
                     abstract: bool = False) -> Tuple[Tree, Tree]:
    """Paged serving cache: one ``(L, num_blocks, block_size, KV*hd)``
    block pool per k/v, shared by every in-flight request.  There is no
    per-slot ``len`` here: fills and block tables belong to the host-side
    manager (``repro.serve_mem``), which renders tables per dispatch —
    cache *memory* is the scheduled resource, so its bookkeeping lives
    with the scheduler, not the device state."""
    dtype = cache_dtype(cfg, dtype)
    shape = (cfg.num_layers, num_blocks, block_size, cfg.kv_dim)
    z = (jax.ShapeDtypeStruct if abstract
         else (lambda s, d: jnp.zeros(s, d)))
    cache = {"k": z(shape, dtype), "v": z(shape, dtype)}
    specs = {"k": ("layers", None, "seq_cache", "kv"),
             "v": ("layers", None, "seq_cache", "kv")}
    return cache, specs


def paged_kernel_engages(cfg: ModelConfig, block_size: int, dtype,
                         platform: str = "tpu") -> bool:
    """Whether the paged decode program, lowered for ``platform``, reads
    K/V through the Pallas kernel: on a TPU, for the shapes the kernel
    takes (whole 128-lane heads, blocks of whole tiles of a bf16 or f32
    pool).  Everywhere else it gathers every row's whole view."""
    return platform == "tpu" and paged_kernel_supports(cfg.head_dim,
                                                       block_size, dtype)


def _paged_attend(cfg: ModelConfig, pool: jax.Array, tables: jax.Array,
                  cur: jax.Array, active: jax.Array, interpret: bool):
    """The paged decode's attention reader, ``attend(q, kc, vc, l)`` over
    layer ``l`` of the stacked pools ``(L, NB, BS, C)`` (``pool`` is one
    of them: its block size and dtype choose the path).

    The gather materializes every row's whole ``(B, W*BS, C)`` view and
    runs the dense :func:`decode_attention` on it.  The kernel
    (``repro.kernels.paged_attention``) copies only each active row's
    blocks below its length, and is chosen at lowering where
    :func:`paged_kernel_engages` says so (``interpret=True`` runs it in
    interpret mode on any platform).  Both give an active row the same
    attention; an inactive row's result is discarded by the caller."""
    def gather(q, kc, vc, l):
        with jax.named_scope("kv_gather"):
            k = gather_kv_paged(kc, l, tables)       # (B, W*BS, C)
            v = gather_kv_paged(vc, l, tables)
        with jax.named_scope("attention"):
            return _dense_attend(cfg, q, k, v, cur + 1)

    live = jnp.where(active, cur + 1, 0)

    def kernel(q, kc, vc, l):
        with jax.named_scope("attention"):
            return paged_attention(q[:, 0], kc, vc, l, tables, live,
                                   interpret=interpret)[:, None]

    if interpret:
        return kernel
    if not paged_kernel_engages(cfg, pool.shape[2], pool.dtype):
        return gather
    return lambda q, kc, vc, l: jax.lax.platform_dependent(
        q, kc, vc, l, tpu=kernel, default=gather)


def _paged_chunk_attend(cfg: ModelConfig, pool: jax.Array,
                        tables: jax.Array, start: jax.Array,
                        length: jax.Array, Cb: int, interpret: bool):
    """The paged prefill's attention and KV append for one chunk (``Cb``
    queries at positions ``start ..``, ``length`` of them real),
    ``attend(q, k, v, kc, vc, l) -> (a, kc, vc)`` over layer ``l`` of the
    stacked pools ``(L, NB, BS, C)`` (``pool`` is one of them: its block
    size and dtype choose the path, as in :func:`_paged_attend`).

    The gather materializes the request's whole ``(1, W*BS, C)`` view,
    appends the chunk's own K/V to it and runs a dense attention masked
    to the past below ``start`` and causally within the chunk; then it
    scatters the chunk's K/V into the pool.  The kernel
    (``repro.kernels.paged_attention``) scatters first, and then reads
    from the pool only the blocks below each query tile's diagonal and
    the request's fill.  Both write the same pool and give every real
    query the same attention, to rounding."""
    B = 1
    W = tables.shape[-1]
    BS = pool.shape[2]
    S_past = W * BS
    tab_b = jnp.broadcast_to(jnp.asarray(tables, jnp.int32)[None, :],
                             (1, W))
    # key validity: past pool positions are real iff below the fill at
    # chunk start; chunk positions attend causally within the chunk
    past_ok = jnp.arange(S_past)[None, :] < start            # (1, S_past)
    tri = (jnp.arange(Cb)[:, None] >= jnp.arange(Cb)[None, :])
    mask = jnp.concatenate(
        [jnp.broadcast_to(past_ok, (Cb, S_past)), tri], axis=1)
    mask = mask[None, None]                                  # (1,1,Cb,S)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    chunk_pos = start + jnp.arange(Cb, dtype=jnp.int32)      # (Cb,)

    def append(k, v, kc, vc, l):
        # scatter the chunk's ROTATED keys (decode appends rotated keys
        # too) into the request's blocks; positions >= length are pad and
        # dropped.  Each chunk position is its own "row" of the scatter.
        with jax.named_scope("kv_append"):
            write_ok = jnp.arange(Cb) < length
            kc = scatter_kv_paged(
                kc, l, k.reshape(Cb, 1, cfg.kv_dim), chunk_pos, write_ok,
                jnp.broadcast_to(tables, (Cb, W)))
            vc = scatter_kv_paged(
                vc, l, v.reshape(Cb, 1, cfg.kv_dim), chunk_pos, write_ok,
                jnp.broadcast_to(tables, (Cb, W)))
        return kc, vc

    def gather(q, k, v, kc, vc, l):
        with jax.named_scope("kv_gather"):
            past_k = gather_kv_paged(kc, l, tab_b)  # (1, S_past, C)
            past_v = gather_kv_paged(vc, l, tab_b)
        with jax.named_scope("attention"):
            keys = jnp.concatenate(
                [past_k.reshape(B, S_past, cfg.num_kv_heads, cfg.head_dim
                                ).astype(q.dtype), k], axis=1)
            vals = jnp.concatenate(
                [past_v.reshape(B, S_past, cfg.num_kv_heads, cfg.head_dim
                                ).astype(q.dtype), v], axis=1)
            groups = q.shape[2] // keys.shape[2]
            kk = _repeat_kv(keys, groups)
            vv = _repeat_kv(vals, groups)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(mask, s, -1e30)
            probs = jax.nn.softmax(s, axis=-1).astype(q.dtype)
            a = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
        return (a,) + append(k, v, kc, vc, l)

    def kernel(q, k, v, kc, vc, l):
        kc, vc = append(k, v, kc, vc, l)
        with jax.named_scope("attention"):
            a = paged_prefill_attention(q[0], kc, vc, l, tables, start,
                                        length, interpret=interpret)
        return a[None], kc, vc

    if interpret:
        return kernel
    if not paged_kernel_engages(cfg, BS, pool.dtype):
        return gather
    return lambda *args: jax.lax.platform_dependent(
        *args, tpu=kernel, default=gather)


def paged_decode_step(params: Tree, cfg: ModelConfig,
                      inputs: Dict[str, jax.Array], cache: Tree, *,
                      tables: jax.Array, lengths: jax.Array,
                      active: Optional[jax.Array] = None,
                      kernel_interpret: bool = False
                      ) -> Tuple[jax.Array, ...]:
    """One-token decode across every row of a paged-KV pool.

    ``tables (B, W)`` int32 maps each row's logical blocks onto pool
    blocks (``-1`` = unassigned); ``lengths (B,)`` is each row's fill.
    The body is the same :func:`_decode_forward` as the per-slot
    :func:`decode_step` — only the append (block-table scatter) and the
    attention's read of the cache differ (:func:`_paged_attend`: on a TPU
    the Pallas kernel reads each active row's live blocks; elsewhere a
    block-table gather to a ``(B, W*BS, C)`` view feeds the dense
    attention), so an active row's math is that of :func:`decode_step`
    over a dense cache holding the same sequence — the paged-vs-per-slot
    equivalence guarantee, exact on the gather and to rounding (the
    kernel's online softmax) on the kernel.  ``kernel_interpret=True``
    runs the kernel in interpret mode, on any platform and shape.

    An MoE config runs the dropless held-share expert layer over the
    active rows (:func:`moe_held`) and returns, last, the step's
    ``expert_slots`` and ``experts_used`` (:func:`_expert_counters`).

    Returns (logits (B, V), updated cache, updated lengths[,
    expert_slots, experts_used]).
    """
    cur = jnp.asarray(lengths, jnp.int32)
    B = cur.shape[0]
    active = (jnp.ones((B,), bool) if active is None
              else jnp.asarray(active).astype(bool))
    logits, new_k, new_v, sizes = _decode_forward(
        params, cfg, inputs, cache,
        positions=cur[:, None],
        kv_append=lambda c, l, new: scatter_kv_paged(c, l, new, cur, active,
                                                     tables),
        attend_len=cur + 1,
        cap_e=None,
        attend=_paged_attend(cfg, cache["k"], tables, cur, active,
                             kernel_interpret),
        moe_valid=active if cfg.is_moe else None)
    out = (logits, {"k": new_k, "v": new_v}, cur + active.astype(jnp.int32))
    return out + (_expert_counters(sizes) if cfg.is_moe else ())


def fused_paged_decode_steps(params: Tree, cfg: ModelConfig,
                             inputs: Dict[str, jax.Array], cache: Tree, *,
                             num_steps: int, tables: jax.Array,
                             lengths: jax.Array, limits: jax.Array,
                             active: Optional[jax.Array] = None,
                             remaining: Optional[jax.Array] = None,
                             eos_id: Optional[jax.Array] = None,
                             kernel_interpret: bool = False
                             ) -> Tuple[jax.Array, ...]:
    """Run up to ``num_steps`` greedy tokens per row through the paged
    pool ON DEVICE: one ``lax.scan`` over :func:`paged_decode_step`, ONE
    dispatch per ``num_steps`` tokens instead of one per token.  The
    dispatch quantum ``num_steps`` is a schedule parameter
    (``PagedServeLoop(decode_steps=T)``); greedy decode is deterministic,
    so every T gives the same tokens.

    Per-row stop handling lives in the loop carry, and a stopped row
    freezes in place (no KV append, no length bump, no further tokens):

    * ``remaining (B,) int32`` — tokens the row still wants; it freezes
      the step its count hits zero;
    * ``eos_id`` — optional scalar; a row that emits it freezes on the
      next step (the EOS token itself is emitted and counted);
    * ``limits (B,)`` — each row's currently-covered capacity in tokens
      (``allocated_blocks * BS``; block tables are fixed for the duration
      of a dispatch, the host allocates before dispatching).  A row whose
      fill reaches its limit freezes rather than scattering into a block
      it does not own, which is the memory-pressure edge the serve loop
      turns into a preemption decision.

    ``kernel_interpret`` as in :func:`paged_decode_step`.

    Returns ``(tokens (B, num_steps), cache, lengths, active,
    remaining)``; frozen steps emit -1.  An MoE config also returns,
    last, ``expert_slots`` and ``experts_used`` (int32, see
    :func:`_expert_counters`) summed over the dispatch's steps: what its
    active rows asked of the held experts.
    """
    tok = inputs["tokens"]                          # (B, 1) int32
    ln = jnp.asarray(lengths, jnp.int32)
    B = ln.shape[0]
    limits = jnp.asarray(limits, jnp.int32)
    act = (jnp.ones((B,), bool) if active is None
           else jnp.asarray(active).astype(bool))
    rem = (jnp.full((B,), num_steps, jnp.int32) if remaining is None
           else jnp.asarray(remaining).astype(jnp.int32))
    act = act & (rem > 0) & (ln < limits)
    eos = (jnp.asarray(-1, jnp.int32) if eos_id is None
           else jnp.asarray(eos_id).astype(jnp.int32))

    def body(carry, _):
        tok, k, v, ln, act, rem = carry[:6]
        logits, new_cache, new_ln, *counts = paged_decode_step(
            params, cfg, {"tokens": tok}, {"k": k, "v": v},
            tables=tables, lengths=ln, active=act,
            kernel_interpret=kernel_interpret)
        with jax.named_scope("sample"):
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # (B,)
            emit = jnp.where(act, nxt, -1)
            rem = rem - act.astype(jnp.int32)
            act = act & (rem > 0) & (nxt != eos) & (new_ln < limits)
            tok = jnp.where(act, nxt, tok[:, 0])[:, None]
        counted = tuple(c + n for c, n in zip(carry[6:], counts))
        return (tok, new_cache["k"], new_cache["v"], new_ln, act, rem
                ) + counted, emit

    counters = (jnp.zeros((), jnp.int32),) * 2 if cfg.is_moe else ()
    (tok, k, v, ln, act, rem, *counted), toks = jax.lax.scan(
        body, (tok, cache["k"], cache["v"], ln, act, rem) + counters,
        None, length=num_steps)
    return (toks.T, {"k": k, "v": v}, ln, act, rem) + tuple(counted)


def prefill_paged_chunk(params: Tree, cfg: ModelConfig,
                        inputs: Dict[str, jax.Array], cache: Tree, *,
                        tables: jax.Array, start: jax.Array,
                        length: jax.Array, kernel_interpret: bool = False
                        ) -> Tuple[jax.Array, ...]:
    """Process ONE chunk of one request's prompt through the paged cache.

    This is what makes prefill schedulable: instead of one monolithic
    prompt pass, the serve loop feeds bucket-padded chunks —
    ``inputs["tokens"] (1, Cb)`` holding ``length`` real tokens — and
    interleaves them with decode dispatches.  The chunk's queries attend
    the request's already-cached prefix (``start`` tokens, gathered from
    the pool through ``tables (W,)``) plus themselves causally, exactly
    the keys the full prefill would have seen, and the chunk's rotated
    K/V are scattered into the request's blocks at positions
    ``start .. start+length-1`` (pad positions are dropped, never
    written).  ``length``/``start`` are traced scalars, so one compile
    serves every chunk of a given padded width ``Cb`` — the
    one-compile-per-bucket guarantee carries over from dense prefill.
    How the attention reads the prefix is :func:`_paged_chunk_attend`'s
    choice: on a TPU the Pallas kernel reads the request's live blocks,
    elsewhere a gather of its whole view feeds a dense masked attention;
    ``kernel_interpret=True`` runs the kernel in interpret mode, on any
    platform and shape.

    Each stage runs in a named scope (``embed``, ``qkv``, ``kv_gather``,
    ``attention``, ``attn_out``, ``mlp``, ``kv_append``, ``head``), as in
    the decode body.  An MoE config runs the dropless held-share expert
    layer over the chunk's real positions (:func:`moe_held`, scopes
    ``router`` and ``experts`` inside ``mlp``).

    Returns (logits (1, V) at the chunk's last real position, updated
    cache) — the logits only matter for the final chunk of a prompt,
    where they produce the request's first generated token.  An MoE
    config also returns, last, ``expert_slots`` and ``experts_used``
    (int32, see :func:`_expert_counters`): what the chunk's real
    positions asked of the held experts.
    """
    start = jnp.asarray(start, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    Cb = inputs["tokens"].shape[1]
    positions = start + jnp.arange(Cb, dtype=jnp.int32)[None, :]  # (1, Cb)
    with jax.named_scope("embed"):
        x, positions, pos3d = _embed_inputs(
            cfg, params, dict(inputs, positions=positions))
    B = x.shape[0]
    attend = _paged_chunk_attend(cfg, cache["k"], tables, start, length,
                                 Cb, kernel_interpret)

    def body(carry, layer):
        x, kc, vc = carry                       # kc/vc: (L, NB, BS, C)
        lp, l = layer
        with jax.named_scope("qkv"):
            h = rms_norm(x, lp["ln1"])
            q, k, v = _attn_qkv(lp, cfg, h)
            q, k = _position_rotate(cfg, q, k, positions, pos3d)
        a, kc, vc = attend(q, k, v, kc, vc, l)
        with jax.named_scope("attn_out"):
            a = a.reshape(B, Cb, cfg.q_dim)
            x = x + jnp.einsum("bsq,qd->bsd", a, lp["attn"]["wo"])
        counted = ()
        with jax.named_scope("mlp"):
            h = rms_norm(x, lp["ln2"])
            if cfg.is_moe:
                real = jnp.arange(Cb)[None, :] < length      # not the pad
                out, counted = _held_ffn(cfg, lp, h, real)
            elif cfg.mlp == "swiglu":
                out = mlp_swiglu(h, lp["mlp"]["wi_gate"], lp["mlp"]["wi_up"],
                                 lp["mlp"]["wo"])
            else:
                out = mlp_gelu(h, lp["mlp"]["wi"], lp["mlp"]["bi"],
                               lp["mlp"]["wo"], lp["mlp"]["bo"])
            x = x + out
        return (x, kc, vc), counted

    (x, ks, vs), counted = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"])
        head = (params["embed"]["tok"].T if cfg.tie_embeddings
                else params["lm_head"])
        x_last = jax.lax.dynamic_index_in_dim(x, length - 1, axis=1,
                                              keepdims=False)
        logits = jnp.einsum("bd,dv->bv", x_last, head)[:, :cfg.vocab_size]
    return ((logits, {"k": ks, "v": vs})
            + (_expert_counters(counted) if cfg.is_moe else ()))


def decode_step(params: Tree, cfg: ModelConfig, inputs: Dict[str, jax.Array],
                cache: Tree, *, cap_e: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Tree]:
    """One-token decode: inputs token (B,1) (or embeds (B,1,D)); returns
    (logits (B,V), updated cache).  ``cache["len"]`` is a scalar shared by
    every row (see :func:`init_cache`)."""
    cur = cache["len"]
    B = (inputs["embeds"] if cfg.frontend != "none"
         else inputs["tokens"]).shape[0]
    logits, new_k, new_v, _ = _decode_forward(
        params, cfg, inputs, cache,
        positions=jnp.full((B, 1), cur, dtype=jnp.int32),
        kv_append=lambda c, l, new: jax.lax.dynamic_update_slice(
            c, new[None].astype(c.dtype), (l, 0, cur, 0)),
        attend_len=cur + 1,
        cap_e=cap_e)
    new_cache = {"k": new_k, "v": new_v, "len": cur + 1}
    return logits, new_cache


def prefill(params: Tree, cfg: ModelConfig, inputs: Dict[str, jax.Array],
            max_len: Optional[int] = None,
            *, remat: str = "full",
            length: Optional[jax.Array] = None,
            cap_e: Optional[jax.Array] = None) -> Tuple[jax.Array, Tree]:
    """Process a full prompt, building the KV cache; returns
    (last-position logits (B,V), cache).

    ``length`` (scalar, traced) marks the REAL prompt length inside a
    right-padded ``tokens`` buffer: logits are read at position
    ``length - 1`` and the cache fill is set to ``length``, not the padded
    width.  Under causal masking positions < length never attend the pad
    tail, so a prompt padded to a shared length bucket produces the same
    prefix math — the lever that lets serving compile ONE prefill per
    bucket instead of one per distinct prompt length.  Pad positions do
    land garbage K/V in the cache, but decode overwrites them in order
    (appends happen exactly at ``len``, ``len+1``, …) and attention masks
    everything at or beyond the current fill, so they are never read."""
    x, positions, pos3d = _embed_inputs(cfg, params, inputs)
    B, S = x.shape[:2]
    max_len = max_len or S
    segment_ids = inputs.get("segment_ids")

    def body(x, lp):
        h = rms_norm(x, lp["ln1"])
        q, k, v = _attn_qkv(lp, cfg, h)
        qr, kr = _position_rotate(cfg, q, k, positions, pos3d)
        a = _padded_attention(cfg, qr, kr, v, causal=True,
                              segment_ids=segment_ids,
                              block_q=cfg.attn_block_q,
                              block_kv=cfg.attn_block_kv,
                              flash_threshold=cfg.flash_threshold)
        a = a.reshape(B, S, cfg.q_dim)
        x = x + jnp.einsum("bsq,qd->bsd", a, lp["attn"]["wo"])
        h = rms_norm(x, lp["ln2"])
        if cfg.is_moe:
            out, _ = moe_ffn(h, lp["moe"]["router"], lp["moe"]["w_gate"],
                             lp["moe"]["w_up"], lp["moe"]["w_down"], cfg, cap_e)
        elif cfg.mlp == "swiglu":
            out = mlp_swiglu(h, lp["mlp"]["wi_gate"], lp["mlp"]["wi_up"],
                             lp["mlp"]["wo"])
        else:
            out = mlp_gelu(h, lp["mlp"]["wi"], lp["mlp"]["bi"],
                           lp["mlp"]["wo"], lp["mlp"]["bo"])
        # cache stores *rotated* keys (decode appends rotated keys too),
        # flattened to (B, S, KV*hd) — see init_cache
        return x + out, (kr.reshape(B, S, cfg.kv_dim),
                         v.reshape(B, S, cfg.kv_dim))

    if remat == "full":
        body = jax.checkpoint(body)
    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])

    pad = max_len - S
    if pad > 0:
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0)))
    ks = ks.astype(cache_dtype(cfg, ks.dtype))
    vs = vs.astype(cache_dtype(cfg, vs.dtype))
    x = rms_norm(x, params["final_norm"])
    head = (params["embed"]["tok"].T if cfg.tie_embeddings
            else params["lm_head"])
    if length is None:
        x_last = x[:, -1]
        fill = jnp.asarray(S, jnp.int32)
    else:
        fill = jnp.asarray(length, jnp.int32)
        x_last = jax.lax.dynamic_index_in_dim(x, fill - 1, axis=1,
                                              keepdims=False)
    logits = jnp.einsum("bd,dv->bv", x_last, head)[:, :cfg.vocab_size]
    cache = {"k": ks, "v": vs, "len": fill}
    return logits, cache
