"""Compiled step functions: train_step / prefill_step / serve_step.

These are THE artifacts the multi-pod dry-run lowers and compiles, and what
``train.py`` / ``serve.py`` drive for real.  All architecture dispatch goes
through the model facade; all sharding through the logical-rule tables.

Memory-critical design choices (each is a §Perf lever):
  * chunked softmax cross-entropy — the (B,S,V) logits tensor is never
    materialized; the LM head matmul runs inside a sequence-chunk scan
    (e.g. grok-1 train_4k: 318 GB of logits+grad avoided globally);
  * scan-over-layers + jax.checkpoint (remat policy configurable);
  * donated params/optimizer/cache buffers (in-place update at XLA level).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import Model, ModelConfig
from repro.optim.specs import opt_state_specs  # noqa: F401  (re-export)
from repro.configs.base import ShapeSpec
from repro.core.spec import SpecLike
from repro.sharding import constrain

__all__ = ["chunked_softmax_ce", "make_train_step", "make_fused_train_step",
           "make_prefill_step", "make_serve_step", "apply_microbatch_plan",
           "plan_microbatches", "split_batch_by_shares", "input_specs",
           "head_weights"]

Tree = Any


# batch-dict keys with a leading batch dim (mirrors input_specs); keys not
# listed here (e.g. the per-expert cap_e vector) pass through unpermuted
_BATCH_MAJOR_KEYS = frozenset({"tokens", "embeds", "labels", "segment_ids"})


def apply_microbatch_plan(batch: Dict[str, jax.Array], perm,
                          extra_batch_keys: Sequence[str] = ()
                          ) -> Dict[str, jax.Array]:
    """Apply a UDS microbatch permutation (``sched.microbatch``, planned
    through the engine) to a host-side batch: rows are reordered so the
    compiled step's *static* equal split sees cost-balanced microbatches.
    Permutes by explicit key (``_BATCH_MAJOR_KEYS`` + ``extra_batch_keys``;
    ``positions_3d`` is (3, B, S) and permuted on its second axis) — never
    by shape inference, so same-length non-batch vectors can't be
    scrambled."""
    perm = jnp.asarray(perm)
    keys = _BATCH_MAJOR_KEYS | set(extra_batch_keys)
    out: Dict[str, jax.Array] = {}
    for k, v in batch.items():
        if k == "positions_3d":
            out[k] = v[:, perm]
        elif k in keys:
            out[k] = v[perm]
        else:
            out[k] = v
    return out


def plan_microbatches(batch: Dict[str, jax.Array], costs, num_microbatches: int,
                      scheduler: SpecLike = "dynamic,1",
                      history=None,
                      extra_batch_keys: Sequence[str] = ()
                      ) -> Dict[str, jax.Array]:
    """Plan and apply the UDS microbatch assignment in one step.

    ``scheduler`` is a schedule clause (spec / string / instance) resolved
    through the unified registry; the permutation it plans over
    ``costs`` (per-row work estimates) is applied so the compiled step's
    *static* equal split sees cost-balanced microbatches.
    """
    from repro.sched.microbatch import plan_microbatch_permutation
    perm = plan_microbatch_permutation(scheduler, costs, num_microbatches,
                                       history=history)
    return apply_microbatch_plan(batch, perm,
                                 extra_batch_keys=extra_batch_keys)


def split_batch_by_shares(batch: Dict[str, jax.Array], shares,
                          num_hosts: int,
                          labels_np: Optional[np.ndarray] = None):
    """Apply AWF token shares as an UNEVEN data-parallel batch split.

    The jitted train step needs ONE static shape, so the split is
    pad/mask-based: the global ``(B, S)`` batch is viewed as ``num_hosts``
    contiguous row blocks (the "host"-axis sharding layout), and host ``h``
    keeps only the first ``shares[h]`` token positions of its block
    (row-major), the rest becoming padding (tokens 0, labels -100,
    segment_ids 0, embeds zeroed) — exactly how a real uneven input
    pipeline underfills a slow host's feed while the compiled step keeps
    one shape.  Shares above a host's physical capacity
    (``B/num_hosts * S``) are clamped: a fast host can keep everything it
    was packed but cannot absorb another host's rows.

    Uniform shares at (or above) capacity are an exact no-op — the batch
    is returned UNTOUCHED (same arrays), the identity the multi-host
    loss-equivalence guarantee rests on.

    Returns ``(batch, host_tokens)`` where ``host_tokens[h]`` counts the
    real (label-carrying) tokens host ``h`` still owns — the per-host
    work estimate the straggler telemetry attributes step time by.
    Pass the packer's host-resident labels as ``labels_np`` to count them
    with zero device traffic; without it ``batch["labels"]`` is copied to
    host once (a device sync on a committed array).
    """
    labels = batch["labels"]
    B, S = labels.shape
    if B % num_hosts != 0:
        raise ValueError(f"global batch {B} not divisible by "
                         f"{num_hosts} hosts")
    shares = np.asarray(shares, np.int64)
    if shares.shape != (num_hosts,):
        raise ValueError(f"expected {num_hosts} shares, got shape "
                         f"{shares.shape}")
    rows_per_host = B // num_hosts
    cap = rows_per_host * S
    budget = np.clip(shares, 0, cap)
    # real-token counting works on a host-side labels array + the numpy
    # keep mask — never on the masked device output
    if labels_np is None:
        labels_np = np.asarray(labels)
    elif labels_np.shape != (B, S):
        raise ValueError(f"labels_np shape {labels_np.shape} != {(B, S)}")
    real = labels_np >= 0

    if bool((budget >= cap).all()):          # uniform/full shares: no-op
        return batch, real.reshape(num_hosts, -1).sum(axis=1,
                                                      dtype=np.int64)
    # token position within its host's block, row-major: row b col s ->
    # (b % rows_per_host) * S + s; kept iff below the host's budget
    pos = np.arange(B * S, dtype=np.int64).reshape(B, S) % cap
    keep_np = pos < budget[np.arange(B) // rows_per_host, None]
    host_tokens = (real & keep_np).reshape(num_hosts, -1).sum(
        axis=1, dtype=np.int64)
    keep = jnp.asarray(keep_np)
    out: Dict[str, jax.Array] = {}
    for k, v in batch.items():
        if k == "tokens":
            out[k] = jnp.where(keep, v, 0)
        elif k == "labels":
            out[k] = jnp.where(keep, v, -100)
        elif k == "segment_ids":
            out[k] = jnp.where(keep, v, 0)
        elif k == "embeds":
            out[k] = jnp.where(keep[..., None], v, 0)
        else:                                # positions_3d, cap_e, ...
            out[k] = v
    return out, host_tokens


def head_weights(params: Tree, cfg: ModelConfig) -> jax.Array:
    return (params["embed"]["tok"].T if cfg.tie_embeddings
            else params["lm_head"])


def chunked_softmax_ce(x: jax.Array, head: jax.Array, labels: jax.Array,
                       mask: Optional[jax.Array] = None,
                       chunk: int = 512,
                       valid_vocab: Optional[int] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """Cross-entropy over a huge vocab without materializing full logits.

    x: (B, S, D) final hidden; head: (D, V); labels: (B, S) int32.
    ``valid_vocab``: mask head columns >= this out of the logsumexp
    (padded-vocab support).  Returns (sum_loss, sum_count).
    """
    B, S, D = x.shape
    V = head.shape[-1]
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nc = (S + pad) // chunk
    xc = x.reshape(B, nc, chunk, D).swapaxes(0, 1)          # (nc,B,c,D)
    lc = labels.reshape(B, nc, chunk).swapaxes(0, 1)
    mc = mask.reshape(B, nc, chunk).swapaxes(0, 1)

    @jax.checkpoint   # recompute chunk logits in bwd: never store (B,c,V) f32
    def step(carry, inp):
        loss_sum, cnt = carry
        xb, lb, mb = inp
        logits = jnp.einsum("bcd,dv->bcv", xb, head,
                            preferred_element_type=jnp.float32)
        logits = constrain(logits, "batch", None, "act_vocab")
        if valid_vocab is not None and valid_vocab < V:
            pad_mask = jnp.arange(V) < valid_vocab
            logits = jnp.where(pad_mask, logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)             # (B,c)
        onehot = jax.nn.one_hot(lb, V, dtype=logits.dtype)
        ll = jnp.einsum("bcv,bcv->bc", logits, onehot)
        loss_sum = loss_sum + jnp.sum((lse - ll) * mb)
        cnt = cnt + jnp.sum(mb)
        return (loss_sum, cnt), None

    (loss_sum, cnt), _ = jax.lax.scan(
        step, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xc, lc, mc))
    return loss_sum, cnt


def _make_microbatch_grads(model: Model, *, remat: str, ce_chunk: int,
                           aux_loss_weight: float,
                           num_microbatches: int) -> Callable:
    """The shared loss/grad core of the train-step factories: full-batch
    gradients, or a ``lax.scan`` gradient accumulation over
    ``num_microbatches`` equal splits (one compiled shape).

    The accumulation is GROUPING-INVARIANT: every microbatch's objective
    is its CE *sum* normalized by the GLOBAL real-token count (computed
    before the scan) plus its 1/M share of the aux loss, so summing the
    per-microbatch gradients reproduces the full-batch gradient exactly
    (in exact arithmetic) no matter which rows land in which microbatch.
    This is the invariant that lets a hierarchical host-block-aligned
    microbatch grouping match the flat single-host grouping's loss
    trajectory — and makes ``num_microbatches`` itself loss-neutral."""
    cfg = model.cfg

    def _losses(params, batch):
        inputs = {k: v for k, v in batch.items()
                  if k in ("tokens", "embeds", "positions_3d", "segment_ids")}
        hidden, loads = model.forward(params, inputs, remat=remat,
                                      return_hidden=True,
                                      cap_e=batch.get("cap_e"))
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        loss_sum, cnt = chunked_softmax_ce(
            hidden, head_weights(params, cfg), jnp.maximum(labels, 0),
            mask, chunk=ce_chunk,
            valid_vocab=(cfg.vocab_size
                         if cfg.padded_vocab != cfg.vocab_size else None))
        aux = jnp.zeros((), jnp.float32)
        if cfg.is_moe:
            # switch-style balance loss from measured hard loads
            # (aux = E * sum_e f_e^2; f = p approximation documented)
            f = loads.mean(axis=0)
            aux = cfg.num_experts * jnp.sum(f * f)
        return loss_sum, cnt, aux

    def loss_fn(params, batch):
        loss_sum, cnt, aux = _losses(params, batch)
        ce = loss_sum / jnp.maximum(cnt, 1.0)
        return ce + aux_loss_weight * aux, (ce, aux, cnt)

    def mb_loss_fn(params, batch, denom):
        # one microbatch's share of the GLOBAL objective
        loss_sum, cnt, aux = _losses(params, batch)
        obj = loss_sum / denom + aux_loss_weight * aux / num_microbatches
        return obj, (loss_sum, aux, cnt)

    def microbatch_grads(params, batch):
        if num_microbatches == 1:
            grads, (ce, aux, cnt) = jax.grad(
                loss_fn, has_aux=True)(params, batch)
            return grads, ce, aux, cnt
        # static equal split (UDS plans sizes host-side by permuting work
        # into the microbatches; compiled shapes stay uniform)
        def split(v):
            if v.ndim >= 2 and v.shape[0] % num_microbatches == 0:
                return v.reshape(num_microbatches,
                                 v.shape[0] // num_microbatches, *v.shape[1:])
            return jnp.broadcast_to(v, (num_microbatches,) + v.shape)
        mb = {k: (split(v) if k != "positions_3d" else
                  v.reshape(3, num_microbatches, -1, v.shape[-1])
                  .swapaxes(0, 1))
              for k, v in batch.items()}
        denom = jnp.maximum(
            (batch["labels"] >= 0).sum().astype(jnp.float32), 1.0)

        def one(carry, mbi):
            g_acc, ls_acc, aux_acc, cnt_acc = carry
            grads, (ls, aux, cnt) = jax.grad(
                mb_loss_fn, has_aux=True)(params, mbi, denom)
            g_acc = jax.tree.map(jnp.add, g_acc, grads)
            return (g_acc, ls_acc + ls, aux_acc + aux, cnt_acc + cnt), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (g, ls, aux, cnt), _ = jax.lax.scan(
            one, (zeros, jnp.zeros(()), jnp.zeros(()), jnp.zeros(())), mb)
        # reported loss = global token mean (what the full batch reports)
        return g, ls / denom, aux / num_microbatches, cnt

    return microbatch_grads


def _apply_update(opt_update: Callable, params, opt_state, step,
                  grads, ce, aux, cnt):
    updates, opt_state, om = opt_update(grads, opt_state, params, step)
    params = jax.tree.map(
        lambda p, u: (p.astype(jnp.float32) + u.astype(jnp.float32)
                      ).astype(p.dtype), params, updates)
    # "tokens": labelled (non-masked) tokens this step — the measure
    # stage's tok/s numerator, threaded out for the telemetry loop
    metrics = {"loss": ce, "aux_loss": aux, "step": step + 1,
               "tokens": cnt, **om}
    return params, opt_state, metrics


def make_train_step(model: Model, opt_update: Callable,
                    *, remat: str = "full", ce_chunk: int = 512,
                    aux_loss_weight: float = 0.01,
                    num_microbatches: int = 1) -> Callable:
    """Returns train_step(params, opt_state, step, batch) ->
    (params, opt_state, metrics).

    ``batch``: tokens/embeds, labels, optional segment_ids / positions_3d /
    cap_e (engine-planned expert capacities).  ``num_microbatches`` > 1 runs
    UDS-sized gradient accumulation: ``sched/microbatch.py`` plans the row
    permutation host-side and ``apply_microbatch_plan`` applies it; the
    equal split here keeps the compiled shape static.
    """
    microbatch_grads = _make_microbatch_grads(
        model, remat=remat, ce_chunk=ce_chunk,
        aux_loss_weight=aux_loss_weight,
        num_microbatches=num_microbatches)

    def train_step(params, opt_state, step, batch):
        grads, ce, aux, cnt = microbatch_grads(params, batch)
        return _apply_update(opt_update, params, opt_state, step,
                             grads, ce, aux, cnt)

    return train_step


def make_fused_train_step(model: Model, opt_update: Callable,
                          *, remat: str = "full", ce_chunk: int = 512,
                          aux_loss_weight: float = 0.01,
                          num_microbatches: int = 1,
                          extra_batch_keys: Sequence[str] = ()) -> Callable:
    """Returns train_step(params, opt_state, step, batch, perm) ->
    (params, opt_state, metrics): the FUSED K-microbatch dispatch.

    One jitted call per optimizer step does everything the per-microbatch
    path spread over host round-trips: the UDS microbatch assignment
    (``perm``, the plan's chunk table as a device int32 array — the
    schedule still decides which rows land in which microbatch) is applied
    ON DEVICE, then the ``lax.scan`` gradient accumulation runs all
    ``num_microbatches`` microbatches, then the optimizer update — no
    host-side eager permutation dispatches between them.  Numerically
    identical to ``make_train_step`` fed a host-permuted batch (the
    permutation is the same gather, just lowered into the program).
    """
    microbatch_grads = _make_microbatch_grads(
        model, remat=remat, ce_chunk=ce_chunk,
        aux_loss_weight=aux_loss_weight,
        num_microbatches=num_microbatches)
    keys = tuple(extra_batch_keys)

    def train_step(params, opt_state, step, batch, perm):
        batch = apply_microbatch_plan(batch, perm, extra_batch_keys=keys)
        grads, ce, aux, cnt = microbatch_grads(params, batch)
        return _apply_update(opt_update, params, opt_state, step,
                             grads, ce, aux, cnt)

    return train_step


def make_prefill_step(model: Model, *, max_len: Optional[int] = None
                      ) -> Callable:
    """``length`` (optional traced scalar) marks the real prompt length
    inside a right-padded token buffer — the bucketed-prefill form that
    compiles once per length BUCKET instead of once per distinct prompt
    length (attention families only; SSM prefills absorb pad tokens into
    their state and must keep exact lengths)."""
    def prefill_step(params, batch, length=None):
        if length is None:
            return model.prefill(params, batch, max_len)
        return model.prefill(params, batch, max_len, length=length)
    return prefill_step


def make_serve_step(model: Model) -> Callable:
    """One decode step: greedy token + updated cache/state."""
    def serve_step(params, batch, cache):
        logits, cache = model.decode(params, batch, cache,
                                     cap_e=batch.get("cap_e"))
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return token, cache
    return serve_step


def make_paged_prefill_step(model: Model) -> Callable:
    """One CHUNK of one prompt through the paged-KV pool — the
    continuous-batching prefill unit.  ``batch["tokens"]`` is a
    bucket-padded ``(1, Cb)`` chunk; ``start``/``length`` are traced
    scalars, so each padded width ``Cb`` compiles exactly once (the paged
    analogue of the per-slot engine's one-compile-per-bucket prefill).

    Returns prefill_chunk(params, batch, cache, tables, start, length)
    -> (logits (1, V) at the chunk's last real token, cache[,
    expert_slots, experts_used]); an MoE model adds the two counters."""
    if model.paged_prefill_chunk is None:
        raise ValueError(
            f"{model.name}: model family has no paged-KV path "
            f"(use ServeLoop's per-slot engine)")

    def prefill_chunk(params, batch, cache, tables, start, length):
        return model.paged_prefill_chunk(params, batch, cache,
                                         tables=tables, start=start,
                                         length=length)
    return prefill_chunk


def make_paged_serve_step(model: Model, num_steps: int) -> Callable:
    """``num_steps`` greedy tokens per dispatch across every row of a
    paged-KV block pool — ONE jitted call runs a ``lax.scan`` of
    ``num_steps`` decode steps with per-row stop/EOS/limit handling on
    device (``transformer.fused_paged_decode_steps``), so the Python→XLA
    round-trip is paid once per ``num_steps`` tokens.
    ``tables (B, W)`` / ``lengths (B,)`` come from the host-side block
    manager; ``limits (B,)`` is per-row allocated capacity in tokens, so a
    row that outgrows its blocks freezes mid-dispatch instead of writing
    into memory it does not own (the serve loop's preemption signal).

    Returns serve_step(params, batch, cache, tables, lengths, limits,
    active, remaining, eos_id) -> (tokens (B, num_steps), cache, lengths,
    active, remaining[, expert_slots, experts_used]); an MoE model adds
    the two counters."""
    if model.fused_paged_decode is None:
        raise ValueError(
            f"{model.name}: model family has no paged-KV path "
            f"(use ServeLoop's per-slot engine)")
    if num_steps < 1:
        raise ValueError(f"num_steps must be >= 1, got {num_steps}")

    def serve_step(params, batch, cache, tables, lengths, limits,
                   active, remaining, eos_id):
        return model.fused_paged_decode(params, batch, cache,
                                        num_steps=num_steps, tables=tables,
                                        lengths=lengths, limits=limits,
                                        active=active, remaining=remaining,
                                        eos_id=eos_id)
    return serve_step


# --------------------------------------------------------------------------
def input_specs(cfg: ModelConfig, shape: ShapeSpec,
                dtype: jnp.dtype = jnp.bfloat16) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of a cell —
    weak-type-correct, shardable, no device allocation (dry-run contract)."""
    B = shape.global_batch
    S = shape.seq_len if shape.kind != "decode" else 1
    sds = jax.ShapeDtypeStruct
    out: Dict[str, Any] = {}
    if cfg.frontend != "none":
        out["embeds"] = sds((B, S, cfg.d_model), dtype)
    else:
        out["tokens"] = sds((B, S), jnp.int32)
    if cfg.mrope_sections is not None:
        out["positions_3d"] = sds((3, B, S), jnp.int32)
    if shape.kind == "train":
        out["labels"] = sds((B, S), jnp.int32)
    if cfg.is_moe:
        out["cap_e"] = sds((cfg.num_experts,), jnp.int32)
    return out


def input_shardings(cfg: ModelConfig, shape: ShapeSpec, rules, mesh):
    """NamedShardings matching input_specs (divisibility-checked)."""
    from repro.launch.mesh import input_sharding
    from repro.sharding import BATCH_AXES
    specs = input_specs(cfg, shape)
    return {k: input_sharding(mesh, rules, *BATCH_AXES[k], shape=v.shape)
            for k, v in specs.items()}
