"""Chunked linear-attention scan — Pallas TPU kernel (RWKV6 wkv / Mamba2 SSD).

Recurrence: S_t = diag(w_t)·S_{t-1} + k_t v_tᵀ;  y_t = q_t·S (see ref.py).

TPU mapping:
  * grid = (B·H, n_chunks); chunk index innermost, so the running state
    S (dk × dv, f32) persists in VMEM scratch across the chunk loop —
    HBM→VMEM traffic is one (C × d) tile set per chunk, state never
    leaves VMEM (the CUDA versions bounce state through shared memory
    per thread-block; on TPU it simply stays resident);
  * scalar-per-head decay (Mamba2): full MXU chunked form — intra-chunk
    (C×C) score matmul masked by the decay-gap matrix, inter-chunk one
    (C×dk)@(dk×dv) matmul;
  * vector decay (RWKV6): numerically-safe sequential inner loop over the
    chunk (VPU outer products) with chunked I/O.  The common factored
    q̃·k̃ form overflows for data-dependent per-channel decay
    (exp(−Σlog w) is unbounded); the paper-faithful safe form is kept —
    see models/linear_scan.py for the same choice in the jnp path.

The chunk size is the UDS-schedulable parameter (cfg.scan_chunk).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["linear_scan_scalar", "linear_scan_vector"]


# ----------------------------------------------------------- scalar decay
def _scalar_kernel(q_ref, k_ref, v_ref, lw_ref, y_ref, s_out_ref, s_ref,
                   *, chunk: int, n_chunks: int, inclusive: bool):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    q = q_ref[0].astype(jnp.float32)          # (C, dk)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)          # (C, dv)
    # this chunk's log-decay: row ci of the (n_chunks, C) block
    lw_row = lw_ref[0, pl.ds(ci, 1), :].astype(jnp.float32)   # (1, C)

    # prefix sums of the log-decay as masked reductions (the TPU lowering
    # has no cumsum and no small transposes): the same sums, laid out as a
    # column (C, 1) and as a row (1, C)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lw = jnp.sum(jnp.where(row == col, lw_row, 0.0), axis=1, keepdims=True)
    ai = jnp.sum(jnp.where(col <= row, lw_row, 0.0), axis=1,
                 keepdims=True)               # (C, 1) inclusive log-decay
    ai_row = jnp.sum(jnp.where(row <= col, lw, 0.0), axis=0, keepdims=True)
    q_dec = ai if inclusive else ai - lw
    # inter-chunk: (q ⊙ exp(dec)) @ S
    y = jax.lax.dot_general(q * jnp.exp(q_dec), s_ref[...],
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # intra-chunk
    gap = q_dec - ai_row                      # (C, C), masked entries <= 0
    mask = (col <= row) if inclusive else (col < row)
    m = jnp.where(mask, jnp.exp(jnp.where(mask, gap, 0.0)), 0.0)
    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * m
    y = y + jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)
    # state update
    alast = jnp.sum(lw_row, axis=1, keepdims=True)             # (1, 1)
    kdec = k * jnp.exp(alast - ai)
    s_ref[...] = s_ref[...] * jnp.exp(alast) + jax.lax.dot_general(
        kdec, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ci == n_chunks - 1)
    def _out():
        s_out_ref[0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("inclusive", "chunk", "interpret"))
def linear_scan_scalar(q: jax.Array, k: jax.Array, v: jax.Array,
                       log_w: jax.Array, *, inclusive: bool = True,
                       chunk: int = 32, interpret: bool = False
                       ) -> Tuple[jax.Array, jax.Array]:
    """Mamba2/SSD form. q/k: (B,H,T,dk); v: (B,H,T,dv); log_w: (B,H,T).
    Returns (y (B,H,T,dv), final_state (B,H,dk,dv) f32).  T % chunk == 0."""
    B, H, T, dk = q.shape
    dv = v.shape[-1]
    assert T % chunk == 0, (T, chunk)
    nc = T // chunk
    bh = B * H
    qr = q.reshape(bh, T, dk)
    kr = k.reshape(bh, T, dk)
    vr = v.reshape(bh, T, dv)
    # (n_chunks, C) per head: the block's last two dims are the array's
    lwr = log_w.reshape(bh, nc, chunk)

    y, s = pl.pallas_call(
        functools.partial(_scalar_kernel, chunk=chunk, n_chunks=nc,
                          inclusive=inclusive),
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, nc, chunk), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, T, dv), v.dtype),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, lwr)
    return y.reshape(B, H, T, dv), s.reshape(B, H, dk, dv)


# ----------------------------------------------------------- vector decay
def _vector_kernel(q_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, s_out_ref,
                   s_ref, q_s, k_s, v_s, w_s, y_acc_ref,
                   *, chunk: int, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    # stage the chunk in f32 scratch: the token loop reads one row of each
    q_s[...] = q_ref[0].astype(jnp.float32)   # (C, dk)
    k_s[...] = k_ref[0].astype(jnp.float32)
    v_s[...] = v_ref[0].astype(jnp.float32)   # (C, dv)
    w_s[...] = jnp.exp(lw_ref[0].astype(jnp.float32))
    u = u_ref[0].astype(jnp.float32)          # (1, dk)
    n = s_ref.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))

    def column(x):
        """(1, n) -> (n, 1) as a masked reduction (no small transposes
        in the TPU lowering)."""
        return jnp.sum(jnp.where(eye, x, 0.0), axis=1, keepdims=True)

    def step(t, _):
        qt = q_s[pl.ds(t, 1), :]                            # (1, dk)
        kt = k_s[pl.ds(t, 1), :]
        vt = v_s[pl.ds(t, 1), :]                            # (1, dv)
        wt = w_s[pl.ds(t, 1), :]                            # (1, dk)
        # exclusive + bonus-u (RWKV6): y = q·S_prev + (q·(u⊙k)) v
        y_hist = jax.lax.dot_general(qt, s_ref[...],
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        bonus = jnp.sum(qt * u * kt, axis=-1, keepdims=True)
        y_acc_ref[pl.ds(t, 1), :] = y_hist + bonus * vt
        # S = diag(w)·S + kᵀ v
        s_ref[...] = s_ref[...] * column(wt) + column(kt) * vt
        return ()

    jax.lax.fori_loop(0, chunk, step, ())
    y_ref[0] = y_acc_ref[...].astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _out():
        s_out_ref[0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def linear_scan_vector(q: jax.Array, k: jax.Array, v: jax.Array,
                       log_w: jax.Array, u: jax.Array, *,
                       chunk: int = 32, interpret: bool = False
                       ) -> Tuple[jax.Array, jax.Array]:
    """RWKV6 wkv form (exclusive + bonus u).  q/k/v/log_w: (B,H,T,n);
    u: (H, n).  Returns (y (B,H,T,n), final_state (B,H,n,n) f32)."""
    B, H, T, n = q.shape
    assert T % chunk == 0, (T, chunk)
    nc = T // chunk
    bh = B * H
    qr = q.reshape(bh, T, n)
    kr = k.reshape(bh, T, n)
    vr = v.reshape(bh, T, n)
    lwr = log_w.reshape(bh, T, n)
    ur = jnp.broadcast_to(u[None], (B, H, n)).reshape(bh, 1, n)

    y, s = pl.pallas_call(
        functools.partial(_vector_kernel, chunk=chunk, n_chunks=nc),
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, n), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, n, n), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, T, n), v.dtype),
            jax.ShapeDtypeStruct((bh, n, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)]
        + [pltpu.VMEM((chunk, n), jnp.float32) for _ in range(5)],
        interpret=interpret,
    )(qr, kr, vr, lwr, ur)
    return y.reshape(B, H, T, n), s.reshape(B, H, n, n)
