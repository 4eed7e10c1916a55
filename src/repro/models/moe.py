"""Mixture-of-Experts layer with UDS-planned expert capacities.

Scheduling view (the paper's adaptation): experts are *units of processing*,
tokens are *units of work*.  The capacity vector ``cap_e`` — how many tokens
each expert may accept this step — is planned host-side by a UDS (weighted
factoring over measured expert loads, see ``repro/sched/moe_capacity.py``)
and passed in as a dynamic (traced) argument: buffer shapes stay static,
capacity *contents* change step to step without recompilation.

Dispatch is scatter-based (per top-k slot), not mask-einsum based: the
classic (tokens × experts × capacity) dispatch mask is O(10^13) elements at
our shapes; scatters keep the dispatch buffer at (B, E, C, D) which shards
cleanly as batch→data, experts→model (the all-to-all falls out of GSPMD
sharding propagation on the dispatch/combine scatter-gathers).

Serving runs :func:`moe_held` instead: the dropless layer of one chip's
share under expert parallelism (``ModelConfig.experts_held`` and
``expert_offset``), with no capacity, which the paged programs call for
every MoE config.  Training and the per-slot engine keep :func:`moe_ffn`.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.sharding import constrain

__all__ = ["moe_capacity", "moe_buffer_capacity", "moe_ffn", "moe_held",
           "router_topk"]


def moe_capacity(cfg: ModelConfig, seq_len: int) -> int:
    """Uniform per-sequence, per-expert capacity C (the slot *budget* is
    E*C; the buffer adds headroom so the WF2 planner can raise hot experts
    above C while staying within the budget)."""
    tokens = seq_len * cfg.experts_per_token
    return max(1, math.ceil(tokens / cfg.num_experts * cfg.moe_capacity_factor))


def moe_buffer_capacity(cfg: ModelConfig, seq_len: int) -> int:
    """Static dispatch-buffer capacity: C x headroom."""
    return max(1, math.ceil(moe_capacity(cfg, seq_len) * cfg.moe_cap_headroom))


def router_topk(x: jax.Array, w_router: jax.Array, k: int
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (gates (B,S,k) f32 renormalized, expert_ids (B,S,k) int32,
    probs (B,S,E) f32 — for aux losses / load stats)."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    gates = topv / jnp.maximum(topv.sum(axis=-1, keepdims=True), 1e-9)
    return gates, topi.astype(jnp.int32), probs


def moe_ffn(x: jax.Array,
            w_router: jax.Array,
            w_gate: jax.Array,   # (E, D, F)
            w_up: jax.Array,     # (E, D, F)
            w_down: jax.Array,   # (E, F, D)
            cfg: ModelConfig,
            cap_e: Optional[jax.Array] = None,
            ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out (B, S, D), expert_load (E,) f32 fraction).

    ``cap_e``: optional (E,) int32 — UDS-planned per-expert capacity
    (≤ static buffer capacity C); tokens over capacity are dropped
    (contribute zero), the standard capacity-based MoE semantics.

    Under an active mesh (axis_rules context) the shard_map fast path runs:
    dispatch scatters are *local per shard* (the GSPMD partitioner cannot
    shard this scatter pattern and falls back to global replication —
    measured 243 TB/chip of all-reduce on qwen3-moe train_4k; see
    EXPERIMENTS.md §Perf iteration 2), each model shard computes only its
    expert slice, and a single psum combines — the same collective cost
    as one TP layer.
    """
    import os
    from repro.sharding import current_rules
    if cfg.held_experts != cfg.num_experts:
        raise ValueError(
            f"{cfg.name} holds {cfg.held_experts} of {cfg.num_experts} "
            f"experts: only the paged serving programs run a share (moe_held)")
    ctx = current_rules()
    if (ctx is not None and ctx[0].size > 1
            and not os.environ.get("REPRO_MOE_LOCAL")):  # baseline knob
        return _moe_ffn_shardmap(x, w_router, w_gate, w_up, w_down, cfg,
                                 cap_e, ctx)
    return _moe_ffn_local(x, w_router, w_gate, w_up, w_down, cfg, cap_e)


def _moe_ffn_local(x, w_router, w_gate, w_up, w_down, cfg, cap_e
                   ) -> Tuple[jax.Array, jax.Array]:
    """Single-device reference path (also the shard_map oracle in tests)."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    C = moe_buffer_capacity(cfg, S)

    gates, topi, probs = router_topk(x, w_router, k)

    # position of each slot within its expert, per batch row (so the cumsum
    # never crosses data shards: batch is the data-parallel axis)
    e_flat = topi.reshape(B, S * k)                                # (B, S*k)
    oh = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)                # (B, S*k, E)
    pos_flat = (jnp.cumsum(oh, axis=1) - 1)
    pos_flat = jnp.take_along_axis(pos_flat, e_flat[..., None],
                                   axis=-1)[..., 0]                # (B, S*k)
    pos = pos_flat.reshape(B, S, k)

    if cap_e is not None:
        cap = jnp.minimum(cap_e.astype(jnp.int32), C)              # (E,)
        lim = cap[topi]                                            # (B, S, k)
    else:
        # no plan: uniform budget C/headroom (same total slots as planned)
        lim = jnp.full_like(pos, moe_capacity(cfg, S))
    # send over-capacity slots out of bounds -> dropped by scatter mode
    pos = jnp.where(pos < lim, pos, C)

    # ONE fused scatter for all k slots: a per-slot loop makes GSPMD
    # replicate + all-reduce the (B,E,C,D) dest across the model axis k
    # times — measured 8x collective/memory blow-up on qwen3-moe
    # (EXPERIMENTS.md §Perf, iteration 1)
    b_idx = jnp.arange(B, dtype=jnp.int32)[:, None]                # (B, 1)
    p_flat = pos.reshape(B, S * k)
    upd = jnp.broadcast_to(x[:, :, None, :], (B, S, k, D)
                           ).reshape(B, S * k, D)
    dispatched = constrain(jnp.zeros((B, E, C, D), x.dtype),
                           "batch", "act_experts", None, "act_embed")
    dispatched = dispatched.at[b_idx, e_flat, p_flat].set(upd, mode="drop")
    dispatched = constrain(dispatched,
                           "batch", "act_experts", None, "act_embed")

    # expert FFN (SwiGLU), batched over experts
    g = jnp.einsum("becd,edf->becf", dispatched, w_gate)
    u = jnp.einsum("becd,edf->becf", dispatched, w_up)
    g = constrain(g, "batch", "act_experts", None, "act_mlp")
    u = constrain(u, "batch", "act_experts", None, "act_mlp")
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
         ).astype(x.dtype)
    eout = jnp.einsum("becf,efd->becd", h, w_down)                 # (B,E,C,D)
    eout = constrain(eout, "batch", "act_experts", None, "act_embed")

    # ONE fused gather for the combine (same argument as the scatter)
    got = eout.at[b_idx, e_flat, p_flat].get(
        mode="fill", fill_value=0).reshape(B, S, k, D)
    out = jnp.einsum("bskd,bsk->bsd", got, gates.astype(x.dtype))

    # expert load (fraction of routed slots per expert) — the measurement the
    # WF2/AWF capacity scheduler consumes (end-loop-body analogue)
    load = oh.astype(jnp.float32).sum(axis=(0, 1)) / float(B * S * k)
    return out, load


def moe_held(x: jax.Array,
             w_router: jax.Array,
             w_gate: jax.Array,   # (G, D, F): the G held experts
             w_up: jax.Array,     # (G, D, F)
             w_down: jax.Array,   # (G, F, D)
             cfg: ModelConfig,
             valid: Optional[jax.Array] = None,
             ) -> Tuple[jax.Array, jax.Array]:
    """Dropless expert layer over the experts this config holds:
    x (B, S, D) -> (out (B, S, D), sizes (G,) int32).

    The router scores all ``num_experts`` in float32 and keeps each
    token's renormalised top-k (:func:`router_topk`).  The slots whose
    expert is held here (``expert_offset`` .. ``+ held_experts - 1``)
    are sorted by local expert, run through the held experts' SwiGLU as
    one grouped matmul (``lax.ragged_dot``), weighted by their gates and
    summed back onto their tokens; a slot whose expert is held elsewhere
    gives zero.  There is no capacity: every held slot is computed, even
    when all of them name one expert, since the sorted buffer has room
    for ``k`` held slots of every token.  With every expert held it is
    the whole layer.

    ``valid (B, S)`` marks the tokens that count (a prefill chunk's real
    positions, a decode dispatch's live rows): the slots of the others
    are neither computed nor counted, and those tokens get zero.
    ``sizes`` holds the counted slots that each held expert took.

    Named scopes: ``router`` (scores, top-k, sort) and ``experts``
    (gather, grouped matmuls, combine).
    """
    B, S, D = x.shape
    k, G = cfg.experts_per_token, cfg.held_experts
    T = B * S
    with jax.named_scope("router"):
        gates, topi, _ = router_topk(x, w_router, k)
        local = topi.reshape(T * k) - cfg.expert_offset
        held = (local >= 0) & (local < G)
        if valid is not None:
            held = held & jnp.repeat(jnp.broadcast_to(valid, (B, S)).reshape(T), k)
        group = jnp.where(held, local, G)           # G: not computed here
        order = jnp.argsort(group, stable=True)     # held slots first
        sizes = (group[:, None] == jnp.arange(G)[None, :]).sum(
            axis=0, dtype=jnp.int32)                # (G,) slots per expert
    with jax.named_scope("experts"):
        R = T * min(k, G)             # the most held slots k picks can make
        xs = x.reshape(T, D)[order[:R] // k]        # (R, D), expert order
        g = jax.lax.ragged_dot(xs, w_gate, sizes)
        u = jax.lax.ragged_dot(xs, w_up, sizes)
        h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
             ).astype(x.dtype)
        y = jax.lax.ragged_dot(h, w_down, sizes)    # (R, D)
        # back to slot order: slot i sits at row rank[i] of the sorted buffer
        rank = jnp.zeros((T * k,), jnp.int32).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32))
        got = jnp.take(y, rank, axis=0, mode="fill", fill_value=0)
        w = jnp.where(held, gates.reshape(T * k), 0.0)
        got = jnp.where(held[:, None], got.astype(jnp.float32), 0.0)
        out = jnp.einsum("tkd,tk->td", got.reshape(T, k, D), w.reshape(T, k))
    return out.astype(x.dtype).reshape(B, S, D), sizes


# ---------------------------------------------------------------------------
def _axis_tuple(v):
    if v is None:
        return ()
    return (v,) if isinstance(v, str) else tuple(v)


def _moe_ffn_shardmap(x, w_router, w_gate, w_up, w_down, cfg, cap_e, ctx
                      ) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE via shard_map.

    Layout (from the rule table):
      x        : batch over data axes, (S, D) full per shard
      router   : replicated (tiny)
      w_gate/up: experts over `experts` axis (if any), D over `embed`
                 (FSDP) axis — gathered per layer inside the shard
      w_down   : experts over `experts`, F over `mlp`, D-out full
    Each model shard scatters only the tokens routed to ITS experts
    (locally — no cross-shard scatter semantics), computes its slice, and
    one psum over the model axis assembles the output (row-parallel
    pattern: same collective cost as a TP MLP layer).
    """
    from jax.sharding import PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    mesh, rules, sizes = ctx
    batch_axes = _axis_tuple(rules.get("batch"))
    fsdp_axes = _axis_tuple(rules.get("embed"))
    expert_axes = _axis_tuple(rules.get("experts"))
    mlp_axes = _axis_tuple(rules.get("mlp"))
    # drop axes not in this mesh / sized 1
    def live(axes):
        return tuple(a for a in axes if sizes.get(a, 1) > 1)
    batch_axes, fsdp_axes = live(batch_axes), live(fsdp_axes)
    expert_axes, mlp_axes = live(expert_axes), live(mlp_axes)
    # an axis may shard at most one dim of the expert weights
    # (priority: experts > embed/fsdp > mlp — mirrors spec_for's dedup)
    fsdp_axes = tuple(a for a in fsdp_axes if a not in expert_axes)
    mlp_axes = tuple(a for a in mlp_axes
                     if a not in expert_axes and a not in fsdp_axes)

    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    C = moe_buffer_capacity(cfg, S)
    e_shards = 1
    for a in expert_axes:
        e_shards *= sizes[a]
    if E % max(e_shards, 1):
        e_shards = 1
        expert_axes = ()
    E_loc = E // max(e_shards, 1)

    x_spec = P(batch_axes if batch_axes else None, None, None)
    wg_spec = P(expert_axes or None, fsdp_axes or None, mlp_axes or None)
    wd_spec = P(expert_axes or None, mlp_axes or None, fsdp_axes or None)
    cap_spec = P(None)

    def local(x_l, router, wg_l, wu_l, wd_l, cap):
        # gather the FSDP-sharded dims of this layer's expert weights
        for ax in fsdp_axes:
            wg_l = jax.lax.all_gather(wg_l, ax, axis=1, tiled=True)
            wu_l = jax.lax.all_gather(wu_l, ax, axis=1, tiled=True)
            wd_l = jax.lax.all_gather(wd_l, ax, axis=2, tiled=True)
        Bl = x_l.shape[0]
        gates, topi, _ = router_topk(x_l, router, k)
        e_flat = topi.reshape(Bl, S * k)
        oh = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)
        pos_flat = jnp.cumsum(oh, axis=1) - 1
        pos_flat = jnp.take_along_axis(pos_flat, e_flat[..., None],
                                       axis=-1)[..., 0]
        if cap is not None:
            lim = jnp.minimum(cap.astype(jnp.int32), C)[e_flat]
        else:
            lim = jnp.full_like(pos_flat, moe_capacity(cfg, S))
        pos_eff = jnp.where(pos_flat < lim, pos_flat, C)

        # restrict to THIS shard's expert slice
        if expert_axes:
            m = jax.lax.axis_index(expert_axes[0])
            for ax in expert_axes[1:]:
                m = m * sizes[ax] + jax.lax.axis_index(ax)
            e_lo = m * E_loc
        else:
            e_lo = 0
        e_local = e_flat - e_lo
        in_range = (e_local >= 0) & (e_local < E_loc)
        e_local = jnp.clip(e_local, 0, E_loc - 1)
        pos_eff = jnp.where(in_range, pos_eff, C)     # out-of-range -> drop

        b_idx = jnp.arange(Bl, dtype=jnp.int32)[:, None]
        # gather-based dispatch: scatter only the int32 slot->token map,
        # then gather rows of x — avoids materializing k copies of x as
        # scatter updates (8x the residual bytes on qwen3-moe; §Perf iter 3)
        tok_of_slot = (jnp.arange(S * k, dtype=jnp.int32) // k)[None, :]
        src = jnp.full((Bl, E_loc, C), S, jnp.int32)
        src = src.at[b_idx, e_local, pos_eff].set(
            jnp.broadcast_to(tok_of_slot, (Bl, S * k)), mode="drop")
        x_pad = jnp.pad(x_l, ((0, 0), (0, 1), (0, 0)))   # row S = zeros
        dest = jax.vmap(lambda xp, s: xp[s])(x_pad, src)  # (Bl,E_loc,C,D)

        g = jnp.einsum("becd,edf->becf", dest, wg_l)
        u = jnp.einsum("becd,edf->becf", dest, wu_l)
        h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
             ).astype(x_l.dtype)
        eout = jnp.einsum("becf,efd->becd", h, wd_l)

        got = eout.at[b_idx, e_local, pos_eff].get(
            mode="fill", fill_value=0).reshape(Bl, S, k, D)
        out = jnp.einsum("bskd,bsk->bsd", got, gates.astype(x_l.dtype))
        reduce_axes = tuple(expert_axes) + tuple(mlp_axes)
        if reduce_axes:
            out = jax.lax.psum(out, reduce_axes)
        load = oh.astype(jnp.float32).sum(axis=(0, 1)) / float(Bl * S * k)
        if batch_axes:
            load = jax.lax.pmean(load, batch_axes)
        return out, load

    if cap_e is None:
        cap_e = jnp.full((E,), moe_capacity(cfg, S), jnp.int32)
    fn = shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, P(None, None), wg_spec, wg_spec, wd_spec, cap_spec),
        out_specs=(x_spec, P(None)),
        check_rep=False)
    return fn(x, w_router, w_gate, w_up, w_down, cap_e)


def load_balancing_loss(probs: jax.Array, topi: jax.Array, E: int) -> jax.Array:
    """Switch-style auxiliary loss: E * sum_e f_e * p_e."""
    f = jax.nn.one_hot(topi, E, dtype=jnp.float32).sum(axis=(-2)).mean(
        axis=tuple(range(probs.ndim - 1)))  # fraction routed per expert
    p = probs.mean(axis=tuple(range(probs.ndim - 1)))
    return E * jnp.sum(f * p)
