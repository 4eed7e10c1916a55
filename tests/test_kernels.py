"""Pallas kernels vs pure-jnp oracles (interpret=True on the CPU host;
TPU is the compile target).  Shape/dtype sweeps via hypothesis."""

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="dev dependency "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import make_scheduler, plan_schedule
from repro.kernels.sched_matmul.ops import (scheduled_matmul,
                                            tile_order_from_plan)
from repro.kernels.sched_matmul.ref import sched_matmul_ref
from repro.kernels.flash_attention.ops import mha
from repro.kernels.linear_scan.ops import ssd, wkv
from repro.kernels.linear_scan.ref import linear_attention_ref
from repro.kernels.paged_attention.ops import (copied_positions,
                                               paged_attention,
                                               paged_prefill_attention,
                                               supports)
from repro.kernels.paged_attention.ref import (paged_attention_ref,
                                               paged_prefill_attention_ref)

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------ sched_matmul
@given(mt=st.integers(1, 4), k=st.sampled_from([64, 128, 192]),
       n=st.sampled_from([128, 256]),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
       seed=st.integers(0, 99))
@settings(max_examples=12, deadline=None)
def test_sched_matmul_sweep(mt, k, n, dtype, seed):
    rng = np.random.default_rng(seed)
    m = mt * 128
    a = jnp.asarray(rng.normal(size=(m, k)), dtype)
    b = jnp.asarray(rng.normal(size=(k, n)), dtype)
    order = jnp.asarray(rng.permutation(mt), jnp.int32)
    out = scheduled_matmul(a, b, order, block_k=64, interpret=True)
    ref = sched_matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("sched", ["guided", "fac2", "tss"])
def test_sched_matmul_with_uds_plans(sched):
    """Tile orders straight from UDS plans — the integration the kernel
    exists for."""
    m_tiles = 8
    plan = plan_schedule(make_scheduler(sched), m_tiles, 2)
    order = tile_order_from_plan(plan, m_tiles)
    a = jnp.asarray(RNG.normal(size=(m_tiles * 128, 64)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(64, 128)), jnp.float32)
    out = scheduled_matmul(a, b, jnp.asarray(order), block_k=64,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(sched_matmul_ref(a, b)),
                               rtol=2e-5, atol=2e-5)


def test_sched_matmul_padding_path():
    a = jnp.asarray(RNG.normal(size=(200, 96)), jnp.float32)   # non-multiples
    b = jnp.asarray(RNG.normal(size=(96, 130)), jnp.float32)
    out = scheduled_matmul(a, b, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(a @ b), rtol=2e-4, atol=2e-4)


# --------------------------------------------------------- flash attention
@given(b=st.integers(1, 2), s=st.sampled_from([32, 64, 96, 128]),
       h=st.sampled_from([1, 2, 4]), kv=st.sampled_from([1, 2]),
       d=st.sampled_from([16, 32, 64]), causal=st.booleans(),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
       seed=st.integers(0, 99))
@settings(max_examples=16, deadline=None)
def test_flash_attention_sweep(b, s, h, kv, d, causal, dtype, seed):
    if h % kv:
        kv = 1
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, kv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, kv, d)), dtype)
    out = mha(q, k, v, causal=causal, block_q=32, block_kv=32,
              interpret=True)
    ref = mha(q, k, v, causal=causal, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_attention_matches_model_blockwise():
    """Kernel == model's pure-jnp blockwise path == naive reference."""
    from repro.models.common import blockwise_attention
    q = jnp.asarray(RNG.normal(size=(2, 128, 4, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 128, 4, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 128, 4, 32)), jnp.float32)
    kern = mha(q, k, v, causal=True, block_q=32, block_kv=64, interpret=True)
    blockwise = blockwise_attention(q, k, v, causal=True, block_q=32,
                                    block_kv=64)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(blockwise),
                               rtol=2e-4, atol=2e-4)


# -------------------------------------------------------------- linear scan
@given(b=st.integers(1, 2), h=st.integers(1, 3),
       t=st.sampled_from([16, 32, 48, 64]),
       n=st.sampled_from([8, 16]), hd=st.sampled_from([8, 16]),
       chunk=st.sampled_from([8, 16]), seed=st.integers(0, 99))
@settings(max_examples=12, deadline=None)
def test_ssd_kernel_sweep(b, h, t, n, hd, chunk, seed):
    rng = np.random.default_rng(seed)
    c = jnp.asarray(rng.normal(size=(b, h, t, n)), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(b, h, t, n)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(b, h, t, hd)), jnp.float32)
    la = jnp.asarray(-rng.uniform(0.01, 3.0, size=(b, h, t)), jnp.float32)
    y, s = ssd(c, bb, x, la, chunk=chunk, interpret=True)
    yr, sr = linear_attention_ref(c, bb, x, la, inclusive=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=3e-4, atol=3e-4)


@given(b=st.integers(1, 2), h=st.integers(1, 2),
       t=st.sampled_from([16, 32, 48]), n=st.sampled_from([8, 16]),
       chunk=st.sampled_from([8, 16]), seed=st.integers(0, 99))
@settings(max_examples=10, deadline=None)
def test_wkv_kernel_sweep(b, h, t, n, chunk, seed):
    rng = np.random.default_rng(seed)
    r = jnp.asarray(rng.normal(size=(b, h, t, n)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, t, n)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, t, n)), jnp.float32)
    lw = jnp.asarray(-rng.uniform(0.01, 5.0, size=(b, h, t, n)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, n)), jnp.float32)
    y, s = wkv(r, k, v, lw, u, chunk=chunk, interpret=True)
    yr, sr = linear_attention_ref(r, k, v, lw, u=u, inclusive=False)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=3e-4, atol=3e-4)


def test_wkv_strong_decay_no_overflow():
    """The factored GLA form overflows for strong data-dependent decay; the
    safe formulation must not (this is the kernel's raison d'être)."""
    b, h, t, n = 1, 1, 64, 16
    rng = np.random.default_rng(7)
    r = jnp.asarray(rng.normal(size=(b, h, t, n)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, t, n)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, t, n)), jnp.float32)
    lw = jnp.full((b, h, t, n), -7.0, jnp.float32)   # w = e^-7 per step
    u = jnp.zeros((h, n), jnp.float32)
    y, s = wkv(r, k, v, lw, u, chunk=32, interpret=True)
    assert np.isfinite(np.asarray(y)).all()
    assert np.isfinite(np.asarray(s)).all()
    yr, _ = linear_attention_ref(r, k, v, lw, u=u, inclusive=False)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=3e-4, atol=3e-4)


# --------------------------------------------------- paged decode attention
PAGED_LAYERS = 4


@pytest.mark.parametrize("layer", [0, 2, PAGED_LAYERS - 1],
                         ids=["first", "middle", "last"])
@pytest.mark.parametrize(
    "heads,kv,block_size,pages_per_copy,consecutive,dtype", [
        pytest.param(16, 2, 16, 1, False, jnp.float32, id="gqa8-copy1"),
        pytest.param(16, 2, 16, 4, False, jnp.float32, id="gqa8-copy4"),
        pytest.param(16, 2, 16, 4, True, jnp.float32,
                     id="gqa8-copy4-consecutive"),
        pytest.param(16, 2, 16, None, True, jnp.bfloat16,
                     id="gqa8-bf16-default"),
        pytest.param(64, 4, 16, 4, False, jnp.float32, id="gqa16-copy4"),
        pytest.param(64, 4, 16, None, True, jnp.bfloat16,
                     id="gqa16-bf16-default"),
        pytest.param(4, 4, 8, 2, False, jnp.float32, id="gqa1-copy2"),
        pytest.param(4, 4, 8, 3, True, jnp.bfloat16,
                     id="gqa1-bf16-copy3-consecutive"),
    ])
def test_paged_attention_matches_gather_oracle(heads, kv, block_size,
                                               pages_per_copy, consecutive,
                                               dtype, layer):
    """The kernel reads each row's live blocks through its table, at one
    layer of a layer-stacked pool, and matches the gather + dense decode
    attention oracle: ragged lengths (0, 1, BS-1, BS, BS+1, the whole
    W*BS, one short), ``-1`` past each row's blocks, and an inactive row
    (length 0) that still holds blocks; a row of length 0 gets zeros.
    qwen2.5-3b's GQA (16 heads on 2, hd 128), qwen3-moe-235b-a22b's (64
    heads on 4) and one query head per KV head; blocks scattered over the
    pool, or handed out in order as a fresh pool does (runs of
    consecutive blocks, which move in one copy), broken once and
    reversed once.  Every layer of the pool holds different data, and the
    first, a middle and the last layer are read, so a kernel that reads
    any other layer fails."""
    hd, W, BS, L = 128, 6, block_size, PAGED_LAYERS
    lengths = [0, 1, BS - 1, BS, BS + 1, W * BS, W * BS - 1, 0]
    B = len(lengths)
    nb = B * W + 3
    rng = np.random.default_rng(heads + BS)
    q = jnp.asarray(rng.normal(size=(B, heads, hd)), dtype)
    k_pool = jnp.asarray(rng.normal(size=(L, nb, BS, kv * hd)), dtype)
    v_pool = jnp.asarray(rng.normal(size=(L, nb, BS, kv * hd)), dtype)
    tables = np.full((B, W), -1, np.int32)
    order = np.arange(nb) if consecutive else rng.permutation(nb)
    blocks = iter(order.tolist())
    for b, n in enumerate(lengths):
        held = W if b == B - 1 else -(-n // BS)   # the last row is inactive
        tables[b, :held] = [next(blocks) for _ in range(held)]
    if consecutive:
        tables[5, 2] = nb - 1                     # a run broken
        tables[4, :2] = tables[4, 1::-1]          # a run reversed
    lens = jnp.asarray(lengths, jnp.int32)
    at = jnp.int32(layer)
    out = paged_attention(q, k_pool, v_pool, at, jnp.asarray(tables), lens,
                          pages_per_copy=pages_per_copy, interpret=True)
    ref = paged_attention_ref(q, k_pool, v_pool, at, jnp.asarray(tables),
                              lens)
    # the oracle reads the layer it is given, and only that layer
    alone = paged_attention_ref(q, k_pool[layer][None], v_pool[layer][None],
                                jnp.int32(0), jnp.asarray(tables), lens)
    np.testing.assert_array_equal(np.asarray(ref, np.float32),
                                  np.asarray(alone, np.float32))
    assert out.shape == q.shape and out.dtype == q.dtype
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(ref, np.float32)[live],
                               **_tol(dtype))
    assert not np.asarray(out, np.float32)[~live].any()
    for other in set(range(L)) - {layer}:
        elsewhere = paged_attention_ref(q, k_pool, v_pool, jnp.int32(other),
                                        jnp.asarray(tables), lens)
        assert not np.allclose(np.asarray(out, np.float32)[live],
                               np.asarray(elsewhere, np.float32)[live],
                               **_tol(dtype))


def test_paged_attention_shape_test_and_copy_count():
    """The shape test takes whole 128-lane heads and blocks of whole bf16
    (16-row) or f32 (8-row) tiles; the copy count is each row's blocks
    below its length, whole."""
    assert supports(128, 16, jnp.bfloat16) and supports(256, 32, jnp.bfloat16)
    assert supports(128, 8, jnp.float32)
    assert not supports(96, 16, jnp.bfloat16)          # phi3-mini's heads
    assert not supports(128, 8, jnp.bfloat16)
    assert not supports(128, 16, jnp.float8_e4m3fn)
    assert copied_positions([0, 1, 15, 16, 17, 96], 16) == (
        0 + 16 + 16 + 16 + 32 + 96)
    assert copied_positions([], 16) == 0


# ------------------------------------------------- paged prefill attention
CHUNK = 512


@pytest.mark.parametrize("length", [1, 300, CHUNK])
@pytest.mark.parametrize("start", [0, 17, 500, 1040])
@pytest.mark.parametrize("heads,kv", [(16, 2), (64, 4), (8, 8)],
                         ids=["gqa8", "gqa16", "gqa1"])
def test_paged_prefill_attention_matches_gather_oracle(heads, kv, start,
                                                       length):
    """A 512-query chunk at ``start`` holding ``length`` real queries,
    against the middle layer of a layer-stacked pool that already holds
    its keys, through a table whose blocks lie scattered over the pool:
    each real query matches the gather + dense causal mask oracle.
    qwen2.5-3b's GQA (16 heads on 2, hd 128), qwen3-moe-235b-a22b's (64
    on 4) and one query head per KV head.  The kernel gets a pool that is
    NaN everywhere but at the request's positions below ``start +
    length`` in the layer it reads, so a copy, score or value of any
    other position, layer or block would show; the oracle gets the clean
    pool.  Pad queries get finite rows."""
    hd, BS, L, layer = 128, 16, 3, 1
    W = -(-(1040 + CHUNK) // BS) + 3
    end = start + length
    rng = np.random.default_rng(heads + start + length)
    nb = W + 9
    q = jnp.asarray(rng.normal(size=(CHUNK, heads, hd)), jnp.bfloat16)
    clean = [rng.normal(size=(L, nb, BS, kv * hd)).astype(np.float32)
             for _ in range(2)]
    need = -(-end // BS)
    table = np.full((W,), -1, np.int32)
    table[:need] = rng.permutation(nb)[:need]
    poisoned = [np.full_like(p, np.nan) for p in clean]
    for pos in range(end):
        blk, off = table[pos // BS], pos % BS
        for dirty, p in zip(poisoned, clean):
            dirty[layer, blk, off] = p[layer, blk, off]
    args = (jnp.int32(layer), jnp.asarray(table), jnp.int32(start),
            jnp.int32(length))
    out = paged_prefill_attention(
        q, *(jnp.asarray(p, jnp.bfloat16) for p in poisoned), *args,
        interpret=True)
    ref = paged_prefill_attention_ref(
        q, *(jnp.asarray(p, jnp.bfloat16) for p in clean), *args)
    assert out.shape == q.shape and out.dtype == q.dtype
    out = np.asarray(out, np.float32)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[:length],
                               np.asarray(ref, np.float32)[:length],
                               **_tol(jnp.bfloat16))


@pytest.mark.parametrize("start,length", [(0, 1), (0, 512), (17, 300),
                                          (500, 12), (1040, 512)])
def test_paged_prefill_copy_count_and_shape_test(start, length):
    """A chunk's attention copies, per layer, the request's blocks below
    ``start + length``, whole: what ``copied_positions`` counts for the
    serve loop's ``read_positions``.  The kernel takes no head of 96
    lanes (phi3-mini's), so such a model keeps the gather."""
    BS = 16
    blocks = len(range(0, start + length, BS))
    assert copied_positions([start + length], BS) == blocks * BS
    assert blocks * BS - BS < start + length <= blocks * BS
    assert supports(128, BS, jnp.bfloat16)
    assert not supports(96, BS, jnp.bfloat16)
