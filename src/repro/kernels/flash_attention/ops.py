"""Public op: flash attention with GQA and padding.

The Pallas kernel is compiled for the device unless the caller passes
``interpret=True`` (how the CPU tests run it); ``use_kernel=False``
computes the jnp oracle instead.  Nothing here looks at the platform.

The Q-block visit order is a UDS scheduling decision: under causal masking
Q block i attends to O(i) KV blocks, so a decreasing-cost schedule
(GSS/TSS) balances a multi-kernel megacore split.  ``mha(schedule=...)``
plans the order through the PlanEngine (cached across identically-shaped
calls) and scalar-prefetches it into the kernel.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.engine import plan_worker_order
from repro.core.spec import SpecLike
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref

__all__ = ["mha", "plan_q_block_order", "flash_attention", "attention_ref"]


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def plan_q_block_order(sched: SpecLike,
                       q_blocks: int, num_workers: int = 2,
                       device: bool = False,
                       **sched_params):
    """Worker-major Q-block visit order for a schedule clause (spec,
    string like ``"tss"`` / ``"guided,4"``, or scheduler instance),
    planned (and cached) by the engine: each of the ``num_workers``
    kernel lanes (default 2 = megacore) gets its worker's contiguous
    block run, so the lanes inherit the schedule's load balance.  A
    hierarchical clause (``"hier(host=static, tile=tss)"``) yields a
    host-block-major leaf order — each outer block's Q-blocks visited in
    its own child plan's order (``ComposedPlan.tile_order``).
    ``device=True`` returns the plan's cached device array (one upload
    per plan, reused across launches)."""
    return plan_worker_order(sched, q_blocks, num_workers=num_workers,
                             loop_id=f"flash_attention/{q_blocks}",
                             device=device, **sched_params)


def mha(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
        block_q: int = 512, block_kv: int = 1024,
        schedule: Optional[SpecLike] = None,
        use_kernel: bool = True, interpret: bool = False) -> jax.Array:
    """q: (B, S, H, d); k/v: (B, S, KV, d) (GQA repeated here).
    Returns (B, S, H, d).  ``schedule`` is the schedule clause that orders
    the kernel's Q-block visits — a ScheduleSpec, a clause string, or a
    scheduler instance (None = identity / static block order)."""
    b, s, hq, d = q.shape
    kv = k.shape[2]
    if hq != kv:
        reps = hq // kv
        k = jnp.repeat(k, reps, axis=2)
        v = jnp.repeat(v, reps, axis=2)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if not use_kernel:
        out = attention_ref(qt, kt, vt, causal=causal)
        return out.transpose(0, 2, 1, 3)
    bq = min(block_q, max(8, s))
    bkv = min(block_kv, max(8, s))
    qp = _pad_to(qt, 2, bq)
    kp = _pad_to(kt, 2, bkv)
    vp = _pad_to(vt, 2, bkv)
    order = None
    if schedule is not None:
        # the plan's cached device table: a plan-cache hit reuses the
        # buffer uploaded for a previous identically-shaped launch
        order = plan_q_block_order(schedule, qp.shape[2] // bq, device=True)
    out = flash_attention(qp, kp, vp, order, causal=causal, block_q=bq,
                          block_kv=bkv, interpret=interpret)
    return out[:, :, :s].transpose(0, 2, 1, 3)
