"""Paged-KV serving tests: allocator invariants + paged-vs-per-slot equivalence.

The block-table KV subsystem makes cache memory the scheduled resource, so
its correctness splits into two layers, each locked here:

* **Allocator invariants** (host side, ``repro.serve_mem``): a live block
  is owned by exactly one table and never on the free list (no aliasing),
  releasing everything returns the pool to full, used/free watermarks
  never go negative, and a refused allocation changes nothing
  (all-or-nothing).  Checked over long seeded-random op sequences always,
  and via hypothesis when the dev dependency is installed.

* **Engine equivalence** (device side): the paged engine — chunked
  prefill through block tables, fused paged decode, preemption with
  evict→readmit — serves token-for-token the SAME generations as the
  per-slot :class:`ServeLoop` for every schedule family and every
  dispatch quantum T, including runs where memory pressure forces at
  least one preemption (greedy decode is deterministic, so a readmitted
  request must resume exactly where an uninterrupted run would be), and
  a row that stops inside a fused dispatch (budget, EOS, the context
  ceiling) freezes there on device.

Plus the chunked-prefill bucketing regression: prefill chunks are
bucket-padded, so compile count is bounded by the BUCKET count no matter
how many distinct prompt lengths (or UDS chunk sizes) the trace produces.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.launch.serve import (PagedServeLoop, Request, ServeLoop,
                                bucket_length, plan_prefill_chunks)
from repro.launch import serve as serve_module
from repro.launch.steps import make_paged_prefill_step, make_paged_serve_step
from repro.models import get_model
from repro.models.transformer import paged_kernel_engages
from repro.serve_mem import BlockPool, BlockTables, make_mixed_trace
from repro.serve_mem.blocks import blocks_for_tokens

MAX_LEN = 64
BLOCK_SIZE = 8
N_REQUESTS = 6


def make_requests(seed: int, n: int = N_REQUESTS, lo: int = 4, hi: int = 12,
                  max_new: int = 3):
    rng = np.random.default_rng(seed)
    cfg = get_smoke_config("qwen2.5-3b")
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=int(rng.integers(lo, hi))
                                        ).astype(np.int32),
                    max_new=max_new)
            for i in range(n)]


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------
def check_invariants(pool: BlockPool, tables: BlockTables, mirror) -> None:
    """The subsystem's safety net, checked after every op:

    no aliasing (every live block in exactly one table, none on the free
    list), conservation (used + free == pool size), non-negative
    watermarks, and table/mirror agreement."""
    held = [b for tab in mirror.values() for b in tab]
    assert len(held) == len(set(held)), "block aliased across tables"
    free = set(pool._free)
    assert not (set(held) & free), "live block on the free list"
    assert pool.used + pool.num_free == pool.num_blocks
    assert pool.used == len(held)
    assert 0 <= pool.used <= pool.num_blocks
    assert 0 <= pool.num_free <= pool.num_blocks
    assert 0 <= pool.peak_used <= pool.num_blocks
    assert pool.peak_used >= pool.used
    for rid, tab in mirror.items():
        assert list(tables.row(rid)[:len(tab)]) == tab
        assert all(b == -1 for b in tables.row(rid)[len(tab):])


def run_ops(ops, num_blocks: int, block_size: int, max_blocks: int) -> None:
    """Drive ensure/release ops against a pool while mirroring the
    expected table contents in plain python."""
    pool = BlockPool(num_blocks, block_size)
    tables = BlockTables(pool, max_blocks=max_blocks)
    mirror = {}
    for kind, rid, n_tokens in ops:
        if kind == "ensure":
            need = blocks_for_tokens(n_tokens, block_size)
            if need > max_blocks:
                with pytest.raises(ValueError):
                    tables.ensure(rid, n_tokens)
            else:
                before = pool.num_free
                have = len(mirror.get(rid, []))
                ok = tables.ensure(rid, n_tokens)
                grow = max(need - have, 0)
                if ok:
                    mirror.setdefault(rid, [])
                    got = tables.row(rid)[have:have + grow]
                    mirror[rid].extend(int(b) for b in got)
                    assert pool.num_free == before - grow
                else:   # all-or-nothing: refusal changes NOTHING
                    assert grow > before
                    assert pool.num_free == before
                    assert tables.num_blocks_of(rid) == have
        else:           # release
            freed = tables.release(rid)
            assert freed == len(mirror.pop(rid, []))
        check_invariants(pool, tables, mirror)
    for rid in list(mirror):
        tables.release(rid)
        mirror.pop(rid)
        check_invariants(pool, tables, mirror)
    assert pool.num_free == pool.num_blocks, "release did not drain pool"


def random_ops(rng, n_ops: int, n_rids: int, max_tokens: int):
    ops = []
    for _ in range(n_ops):
        rid = int(rng.integers(0, n_rids))
        if rng.random() < 0.7:
            ops.append(("ensure", rid, int(rng.integers(0, max_tokens))))
        else:
            ops.append(("release", rid, 0))
    return ops


@pytest.mark.parametrize("seed", range(8))
def test_allocator_random_sequences(seed):
    """Long random ensure/release sequences keep every invariant, with
    pools small enough that refusals and over-capacity asks both occur."""
    rng = np.random.default_rng(seed)
    num_blocks = int(rng.integers(1, 24))
    block_size = int(rng.integers(1, 16))
    max_blocks = int(rng.integers(1, 12))
    ops = random_ops(rng, 120, n_rids=6,
                     max_tokens=(max_blocks + 2) * block_size)
    run_ops(ops, num_blocks, block_size, max_blocks)


def test_allocator_hypothesis():
    """The same invariant checker under hypothesis-generated op
    sequences (dev dependency; the seeded suite above always runs)."""
    pytest.importorskip("hypothesis", reason="dev dependency "
                        "(pip install -r requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st

    op = st.tuples(st.sampled_from(["ensure", "release"]),
                   st.integers(0, 5), st.integers(0, 40))

    @settings(max_examples=60, deadline=None)
    @given(num_blocks=st.integers(1, 20), block_size=st.integers(1, 8),
           max_blocks=st.integers(1, 8), ops=st.lists(op, max_size=60))
    def inner(num_blocks, block_size, max_blocks, ops):
        run_ops(ops, num_blocks, block_size, max_blocks)

    inner()


def test_alloc_all_or_nothing_and_counters():
    pool = BlockPool(4, 8)
    got = pool.alloc(3)
    assert got is not None and len(got) == 3
    assert pool.alloc(2) is None            # only 1 free: refused whole
    assert pool.num_free == 1 and pool.failed_allocs == 1
    assert pool.peak_used == 3
    pool.free(got)
    assert pool.num_free == 4 and pool.peak_used == 3


def test_double_free_and_alien_free_refused():
    pool = BlockPool(4, 8)
    got = pool.alloc(2)
    pool.free(got)
    with pytest.raises(ValueError):
        pool.free([got[0]])                 # already free
    with pytest.raises(ValueError):
        pool.free([99])                     # not a pool block


def test_ensure_beyond_table_capacity_raises():
    pool = BlockPool(16, 8)
    tables = BlockTables(pool, max_blocks=2)
    assert tables.max_context == 16
    with pytest.raises(ValueError):
        tables.ensure(0, 17)
    assert pool.num_free == 16              # nothing leaked


def test_blocks_for_tokens():
    assert blocks_for_tokens(0, 8) == 0
    assert blocks_for_tokens(1, 8) == 1
    assert blocks_for_tokens(8, 8) == 1
    assert blocks_for_tokens(9, 8) == 2


# ---------------------------------------------------------------------------
# prefill chunk planning
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("clause", ["static", "dynamic", "guided,2"])
@pytest.mark.parametrize("n", [1, 7, 16, 53])
def test_plan_prefill_chunks_tiles_the_prompt(clause, n):
    sizes = plan_prefill_chunks(clause, n, max_chunk=16)
    assert sum(sizes) == n
    assert all(1 <= s <= 16 for s in sizes)


def test_plan_prefill_chunks_follows_the_clause():
    # static: one burst, capped at max_chunk -> equal-ish large chunks
    assert plan_prefill_chunks("static", 48, max_chunk=16) == [16, 16, 16]
    # dynamic,1: minimal chunks
    assert plan_prefill_chunks("dynamic,1", 5, max_chunk=16) == [1] * 5
    assert plan_prefill_chunks("static", 0, max_chunk=16) == []


# ---------------------------------------------------------------------------
# the KV helpers on a layer-stacked pool
# ---------------------------------------------------------------------------
HELPER_LAYERS = 3


def _stacked_pool(rng, nb=12, bs=4, c=8):
    """A (L, NB, BS, C) pool whose every layer holds different data."""
    return jnp.asarray(rng.normal(size=(HELPER_LAYERS, nb, bs, c)),
                       jnp.bfloat16)


@pytest.mark.parametrize("layer", range(HELPER_LAYERS))
def test_scatter_kv_paged_writes_only_its_layer(layer):
    """An append at layer ``l`` changes exactly the ``(l, block, offset)``
    entry of each active row whose position lands on an assigned block
    inside its table, and every other entry of every layer stays
    bit-identical: an inactive row, a ``-1`` table entry and a position
    past ``W*BS`` are dropped."""
    from repro.models.common import scatter_kv_paged
    rng = np.random.default_rng(10 + layer)
    pool = _stacked_pool(rng)
    C = pool.shape[-1]
    tables = np.array([[3, 7, -1], [5, 0, 9], [1, -1, -1], [2, 4, 6],
                       [8, 10, 11]], np.int32)
    cur = np.array([5, 0, 6, 9, 12], np.int32)   # last: past W*BS = 12
    active = np.array([True, True, True, False, True])
    new = jnp.asarray(rng.normal(size=(5, 1, C)), jnp.bfloat16)
    got = np.asarray(scatter_kv_paged(pool, jnp.int32(layer), new,
                                      jnp.asarray(cur), jnp.asarray(active),
                                      jnp.asarray(tables)), np.float32)
    want = np.asarray(pool, np.float32).copy()
    # row 0: block 7 offset 1; row 1: block 5 offset 0; row 2 lands on -1,
    # row 3 is inactive, row 4 is past its table: dropped
    for row, blk, off in [(0, 7, 1), (1, 5, 0)]:
        want[layer, blk, off] = np.asarray(new[row, 0], np.float32)
    np.testing.assert_array_equal(got, want)
    other = [i for i in range(HELPER_LAYERS) if i != layer]
    np.testing.assert_array_equal(got[other],
                                  np.asarray(pool, np.float32)[other])


@pytest.mark.parametrize("layer", range(HELPER_LAYERS))
def test_gather_kv_paged_reads_its_layer(layer):
    """The gather at layer ``l`` equals the per-layer gather of ``pool[l]``
    through the clipped tables (``-1`` reads block 0), and differs from
    every other layer's."""
    from repro.models.common import gather_kv_paged
    rng = np.random.default_rng(30 + layer)
    pool = _stacked_pool(rng)
    _, _, BS, C = pool.shape
    tables = jnp.asarray([[3, 7, -1], [11, 0, 9]], jnp.int32)
    got = np.asarray(gather_kv_paged(pool, jnp.int32(layer), tables))
    one = jnp.take(pool[layer], jnp.clip(tables, 0), axis=0)
    np.testing.assert_array_equal(got, np.asarray(one).reshape(2, 3 * BS, C))
    for other in set(range(HELPER_LAYERS)) - {layer}:
        assert not np.array_equal(
            got, np.asarray(gather_kv_paged(pool, jnp.int32(other), tables)))


# ---------------------------------------------------------------------------
# engine equivalence (module-scoped loops: compile once, swap schedulers)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("qwen2.5-3b")


@pytest.fixture(scope="module")
def per_slot_loop(cfg):
    """The token reference: the per-slot engine, one batch-1 dense cache
    per slot."""
    return ServeLoop(cfg, slots=3, max_len=MAX_LEN)


@pytest.fixture(scope="module")
def paged_loops(cfg):
    """Paged loops by dispatch quantum T, built on first use (one
    compile each) and shared across the module's tests.  The pool holds
    N_REQUESTS * max_context: no pressure, pure equivalence."""
    loops = {}

    def get(decode_steps: int) -> PagedServeLoop:
        if decode_steps not in loops:
            loops[decode_steps] = PagedServeLoop(
                cfg, num_blocks=64, block_size=BLOCK_SIZE,
                max_context=MAX_LEN, concurrency=8,
                decode_steps=decode_steps, prefill_chunk=16)
        return loops[decode_steps]
    return get


@pytest.fixture(scope="module")
def paged_loop(paged_loops):
    return paged_loops(2)


@pytest.fixture(scope="module")
def tight_loop(cfg):
    # pool far below the working set: decode growth MUST preempt
    return PagedServeLoop(cfg, num_blocks=10, block_size=BLOCK_SIZE,
                          max_context=MAX_LEN, concurrency=8,
                          decode_steps=2, prefill_chunk=16)


def run_loop(loop, scheduler, requests):
    from repro.core import LoopHistory
    loop.scheduler = scheduler
    loop.history = LoopHistory()
    return loop.run(requests)


@pytest.mark.parametrize("decode_steps", [1, 2, 4, 16])
@pytest.mark.parametrize("clause", ["static", "guided,2", "awf"])
def test_paged_token_equivalence(clause, decode_steps, per_slot_loop,
                                 paged_loops):
    """The tentpole guarantee: where both engines fit the working set,
    the paged engine serves token-for-token the same generations as the
    per-slot engine, under every schedule family and at every dispatch
    quantum T; each run flushes one measured epoch with full token
    credit, and T > 1 takes fewer decode dispatches than T = 1."""
    out_d = run_loop(per_slot_loop, clause, make_requests(42))
    loop = paged_loops(decode_steps)
    out_p = run_loop(loop, clause, make_requests(42))
    assert loop.decode_steps == decode_steps
    assert sorted(out_p) == list(range(N_REQUESTS))
    assert out_p == out_d
    assert loop.measured_epoch() == per_slot_loop.measured_epoch() == 1
    assert (loop.last_stats["decoded_tokens"]
            == per_slot_loop.last_stats["decoded_tokens"])
    assert loop.last_stats["preemptions"] == 0
    assert loop.pool.used == 0              # every block returned
    if decode_steps > 1:
        stepwise = paged_loops(1)
        run_loop(stepwise, clause, make_requests(42))
        assert (loop.last_stats["decode_dispatches"]
                < stepwise.last_stats["decode_dispatches"])


@pytest.mark.parametrize("decode_steps", [1, 8])
def test_max_len_mid_dispatch_truncation(cfg, per_slot_loop, paged_loops,
                                         decode_steps):
    """A row whose context fills MID-fused-dispatch (prompt near
    max_context, quantum spanning the cap) freezes at capacity and
    reports the truncation — the per-slot engine's tokens at every
    dispatch quantum."""
    prompt = (np.arange(MAX_LEN - 3, dtype=np.int32) % 16)     # capacity 4
    ref = run_loop(per_slot_loop, "static",
                   [Request(rid=0, prompt=prompt.copy(), max_new=10)])
    loop = paged_loops(decode_steps)
    reqs = [Request(rid=0, prompt=prompt, max_new=10)]
    out = run_loop(loop, "static", reqs)
    assert len(out[0]) == 4                    # capacity, not max_new
    assert out == ref
    assert reqs[0].truncated
    assert loop.last_stats["truncated"] == [0]
    assert loop.pool.used == 0


@pytest.mark.parametrize("decode_steps", [1, 8])
def test_paged_eos_freezes_row_mid_dispatch(cfg, per_slot_loop, paged_loops,
                                            decode_steps):
    """A row that emits EOS inside a fused dispatch freezes in place (no
    tokens past EOS) where the per-slot engine stops."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
    ref = run_loop(per_slot_loop, "static",
                   [Request(rid=0, prompt=prompt.copy(), max_new=8)])[0]
    eos = ref[3]                    # force a stop 4 tokens in
    loop = paged_loops(decode_steps)
    loop.eos_id = eos               # an argument of the program: no compile
    try:
        out = run_loop(loop, "static",
                       [Request(rid=0, prompt=prompt.copy(), max_new=8)])
    finally:
        loop.eos_id = None
    assert out[0] == ref[:4]
    assert out[0][-1] == eos
    assert loop.pool.used == 0


def test_preemption_preserves_tokens(per_slot_loop, tight_loop):
    """Memory pressure forces eviction; the evicted request re-prefills
    its generated prefix on readmission and must resume EXACTLY where an
    uninterrupted (per-slot) run would be — token-for-token."""
    reqs = make_requests(7, lo=8, hi=32, max_new=12)
    out_d = run_loop(per_slot_loop, "dynamic", make_requests(7, lo=8, hi=32,
                                                          max_new=12))
    out_p = run_loop(tight_loop, "dynamic", reqs)
    assert tight_loop.last_stats["preemptions"] >= 1
    assert out_p == out_d
    assert any(r.preemptions > 0 for r in reqs)
    # preemption inflates the victim's e2e latency, never its tokens
    assert tight_loop.pool.used == 0


def test_prefill_compiles_bounded_by_buckets(cfg):
    """Chunked-prefill bucketing regression: a trace of many DISTINCT
    prompt lengths (and UDS chunk sizes) compiles one prefill program per
    bucket, not per length.  With max_chunk=16 the only padded widths are
    8 and 16."""
    loop = PagedServeLoop(cfg, num_blocks=64, block_size=BLOCK_SIZE,
                          max_context=MAX_LEN, concurrency=8,
                          decode_steps=4, prefill_chunk=16)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=1 + i * 3).astype(np.int32),
                    max_new=2)
            for i in range(12)]            # lengths 1, 4, 7, ..., 34
    out = loop.run(reqs)
    assert len(out) == 12
    buckets = {bucket_length(s, 16) for s in range(1, 17)}
    assert loop.prefill_compiles <= len(buckets)
    assert loop.prefill_compiles <= 2


def test_paged_observability(cfg):
    """Every request carries its lifecycle stamps and last_stats carries
    the latency percentiles, pool watermarks and preemption count."""
    loop = PagedServeLoop(cfg, num_blocks=64, block_size=BLOCK_SIZE,
                          max_context=MAX_LEN, concurrency=8,
                          decode_steps=2, prefill_chunk=16)
    reqs = make_requests(11)
    loop.run(reqs)
    for r in reqs:
        assert r.t_arrive is not None
        assert r.t_arrive <= r.t_admit <= r.t_first <= r.t_finish
    s = loop.last_stats
    for key in ("queue_p50_s", "queue_p99_s", "admission_p50_s",
                "admission_p99_s", "e2e_p99_s"):
        assert s[key] is not None and s[key] >= 0.0
    assert 0.0 <= s["kv_util_mean"] <= 1.0
    assert s["requests_finished"] == N_REQUESTS
    assert s["preemptions"] == 0
    assert 0 < s["peak_blocks_used"] <= 64
    assert s["peak_concurrency"] >= 1
    assert s["prefill_compiles"] >= 1
    # the serve_paged loop telemetry flushed into the history
    assert loop.measured_epoch() >= 1


def test_dense_loop_gains_meter(per_slot_loop):
    out = run_loop(per_slot_loop, "static", make_requests(13))
    assert len(out) == N_REQUESTS
    meter = per_slot_loop.last_stats["serve_meter"]
    assert meter["requests_finished"] == N_REQUESTS
    assert meter["queue_p99_s"] is not None
    assert meter["admission_p99_s"] is not None
    assert meter["preemptions"] == 0


def test_truncation_matches_dense(cfg, per_slot_loop, paged_loop):
    """A request whose prompt + max_new overflows max_context is admitted
    with its budget clamped and REPORTED truncated — same rule, same
    tokens as the per-slot engine."""
    def mk():
        rng = np.random.default_rng(5)
        return [Request(rid=0,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            size=60).astype(np.int32),
                        max_new=20)]
    out_d = run_loop(per_slot_loop, "static", mk())
    reqs = mk()
    out_p = run_loop(paged_loop, "static", reqs)
    assert out_p == out_d
    assert reqs[0].truncated and reqs[0].budget == MAX_LEN - 60 + 1
    assert paged_loop.last_stats["truncated"] == [0]


def test_prompt_exceeding_max_context_refused(cfg, paged_loop):
    rng = np.random.default_rng(5)
    req = Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, size=MAX_LEN + 1).astype(np.int32), max_new=2)
    with pytest.raises(ValueError, match="exceeds max_context"):
        run_loop(paged_loop, "static", [req])


def test_pool_smaller_than_one_prompt_refused(cfg):
    loop = PagedServeLoop(cfg, num_blocks=2, block_size=BLOCK_SIZE,
                          max_context=MAX_LEN, concurrency=4,
                          decode_steps=1, prefill_chunk=16)
    rng = np.random.default_rng(5)
    req = Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, size=40).astype(np.int32), max_new=2)
    with pytest.raises(ValueError, match="raise num_blocks"):
        loop.run([req])


def test_ssm_family_has_no_paged_path():
    from repro.models import get_model
    cfg = get_smoke_config("rwkv6-3b")
    assert get_model(cfg).fused_paged_decode is None
    with pytest.raises(ValueError, match="no paged-KV path"):
        PagedServeLoop(cfg, num_blocks=8, block_size=8, max_context=64)


# ---------------------------------------------------------------------------
# shared trace generator (tests and benchmarks must agree on the workload)
# ---------------------------------------------------------------------------
def test_mixed_trace_deterministic_and_mixed():
    a = make_mixed_trace(40, vocab_size=256, seed=9)
    b = make_mixed_trace(40, vocab_size=256, seed=9)
    assert len(a) == 40
    assert all(np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
               for x, y in zip(a, b))
    longs = [t for i, t in enumerate(a) if i % 4 == 0]
    shorts = [t for i, t in enumerate(a) if i % 4 != 0]
    assert min(t.prompt.size for t in longs) > max(
        t.prompt.size for t in shorts)
    c = make_mixed_trace(40, vocab_size=256, seed=10)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def _kernel_loop(cfg, **kw):
    """A paged loop whose two programs take the Pallas kernels, in
    interpret mode: the CPU's stand-in for the TPU lowering."""
    loop = PagedServeLoop(cfg, **kw)
    model = dataclasses.replace(
        loop.model, fused_paged_decode=functools.partial(
            loop.model.fused_paged_decode, kernel_interpret=True),
        paged_prefill_chunk=functools.partial(
            loop.model.paged_prefill_chunk, kernel_interpret=True))
    loop._decode = jax.jit(make_paged_serve_step(model, loop.decode_steps),
                           donate_argnums=(2,))
    loop._prefill_step = jax.jit(make_paged_prefill_step(model),
                                 donate_argnums=(2,))
    loop.kernel_reads = True
    return loop


def _prefill_reads(monkeypatch):
    """Record the ``read_positions`` of every ``serve.prefill`` span the
    loop opens, in order: a stand-in for the profiler's annotation."""
    reads = []

    class Span:
        def __init__(self, name, **meta):
            if name == "serve.prefill":
                reads.append(meta["read_positions"])

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **meta):
            pass

    monkeypatch.setattr(serve_module, "TraceAnnotation", Span)
    return reads


def test_prefill_and_dispatch_logs_count_the_work(cfg, monkeypatch):
    """``prefill_log`` holds one {rid, start, length, bucket} per chunk,
    tiling each admission's tokens; its padding equals an independent
    ``bucket_length`` count over the planned chunks.  Each
    ``dispatch_log`` entry's ``fills``/``made`` are its rows' cached
    positions and tokens.  ``read_positions`` (of each dispatch's entry,
    and of each chunk's ``serve.prefill`` span) is what the program's
    attention reads: on the gather path (the CPU's) a chunk's whole view
    and its own bucket per layer, and every row's whole view each decode
    step; on the kernel path a chunk's blocks below its fill, and each
    running row's blocks below its length, each step it runs — the same
    tokens, fewer positions."""
    kw = dict(num_blocks=64, block_size=BLOCK_SIZE, max_context=MAX_LEN,
              concurrency=8, decode_steps=2, prefill_chunk=16,
              scheduler="static")
    loop = PagedServeLoop(cfg, **kw)
    assert not loop.kernel_reads
    reqs = make_requests(5, lo=4, hi=40, max_new=5)
    reads = _prefill_reads(monkeypatch)
    loop.run(reqs)
    assert len(reads) == len(loop.prefill_log)
    by_rid = {}
    W = MAX_LEN // BLOCK_SIZE
    for e, read in zip(loop.prefill_log, reads):
        assert set(e) == {"rid", "start", "length", "bucket"}
        assert e["bucket"] == bucket_length(e["length"], 16)
        assert read == W * BLOCK_SIZE + e["bucket"]
        assert e["start"] == sum(c["length"] for c in by_rid.get(e["rid"], []))
        by_rid.setdefault(e["rid"], []).append(e)
    assert {r: sum(c["length"] for c in cs) for r, cs in by_rid.items()} == {
        r.rid: r.prompt.size for r in reqs}
    pad = sum(bucket_length(n, 16) - n for r in reqs
              for n in plan_prefill_chunks("static", int(r.prompt.size), max_chunk=16))
    assert sum(e["bucket"] - e["length"] for e in loop.prefill_log) == pad

    for d in loop.dispatch_log:
        assert len(d["fills"]) == len(d["made"]) == d["rows"]
        assert sum(d["made"]) == d["tokens"]
        assert d["read_positions"] == 8 * W * BLOCK_SIZE * 2
    assert sum(sum(d["made"]) for d in loop.dispatch_log) == sum(
        len(r.generated) - 1 for r in reqs)
    # a row's fill advances by the tokens it made, dispatch to dispatch
    first = loop.dispatch_log[0]
    assert sorted(first["fills"]) == sorted(int(r.prompt.size) for r in reqs)

    kernel = _kernel_loop(cfg, **kw)
    kreqs = make_requests(5, lo=4, hi=40, max_new=5)
    gather_reads = list(reads)
    reads.clear()
    kernel.run(kreqs)
    assert [r.generated for r in kreqs] == [r.generated for r in reqs]
    assert kernel.prefill_log == loop.prefill_log
    for e, read, g in zip(kernel.prefill_log, reads, gather_reads):
        blocks = -(-(e["start"] + e["length"]) // BLOCK_SIZE)
        assert read == blocks * BLOCK_SIZE
        assert read < g
    assert len(kernel.dispatch_log) == len(loop.dispatch_log)
    for d, g in zip(kernel.dispatch_log, loop.dispatch_log):
        assert (d["fills"], d["made"]) == (g["fills"], g["made"])
        blocks = sum(-(-(f + j + 1) // BLOCK_SIZE)
                     for f, m in zip(d["fills"], d["made"])
                     for j in range(m))
        assert d["read_positions"] == blocks * BLOCK_SIZE
        assert d["read_positions"] < g["read_positions"]


def test_fused_paged_decode_kernel_matches_gather(cfg):
    """The tiny config's fused paged decode, with the kernel forced in
    interpret mode, makes the same greedy tokens as the gather path over
    the same pool: rows of ragged fills, a frozen row and an empty one,
    ``-1`` past every row's blocks, budgets that run out mid-dispatch."""
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(3), jnp.bfloat16)
    W, BS = 4, BLOCK_SIZE
    pool, _ = model.init_paged_decode(24, BS)
    rng = np.random.default_rng(3)
    pool = {n: jnp.asarray(rng.normal(size=p.shape) * 0.5, p.dtype)
            for n, p in pool.items()}
    fills = np.array([3, BS, 2 * BS + 1, 9, 0], np.int32)
    tables = np.full((5, W), -1, np.int32)
    blocks = iter(rng.permutation(24).tolist())
    for b, f in enumerate(fills):
        held = -(-(f + 4) // BS)                 # room for the dispatch
        tables[b, :held] = [next(blocks) for _ in range(held)]
    args = dict(num_steps=4, tables=jnp.asarray(tables),
                lengths=jnp.asarray(fills),
                limits=jnp.asarray(-(-(fills + 4) // BS) * BS),
                active=jnp.asarray([True, True, True, False, False]),
                remaining=jnp.asarray([4, 2, 4, 4, 0], jnp.int32))
    tokens = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (5, 1)),
                                    jnp.int32)}
    out = {}
    for interpret in (False, True):
        step = jax.jit(functools.partial(model.fused_paged_decode,
                                         kernel_interpret=interpret, **args))
        toks, _, ln, act, rem = step(params, tokens, pool)
        out[interpret] = [np.asarray(x) for x in (toks, ln, act, rem)]
    for got, want in zip(out[True], out[False]):
        np.testing.assert_array_equal(got, want)
    assert (out[True][0][:2, :2] >= 0).all() and (out[True][0][3:] == -1).all()


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "qwen3-moe-235b-a22b"],
                         ids=["dense", "moe"])
def test_prefill_chunk_kernel_matches_gather(arch):
    """A 90-token prompt in three chunks (32, 32 and 26 in a 32 bucket)
    through ``prefill_paged_chunk`` with the kernel forced in interpret
    mode, and through the gather path, from the same empty pool and a
    table scattered over it: each chunk's last-position logits agree to
    rounding and pick the same token, the written pool blocks agree to
    rounding, nothing is written past the prompt or outside its blocks,
    and an MoE model's expert counters are equal."""
    cfg = get_smoke_config(arch)
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(1), jnp.bfloat16)
    W, NB, Cb, P = 12, 40, 32, 90
    rng = np.random.default_rng(0)
    table = rng.permutation(NB)[:W].astype(np.int32)
    prompt = rng.integers(0, cfg.vocab_size, P)
    runs = {}
    for interpret in (False, True):
        pool, _ = model.init_paged_decode(NB, BLOCK_SIZE)
        step = jax.jit(functools.partial(model.paged_prefill_chunk,
                                         kernel_interpret=interpret))
        start, logits, counted = 0, [], []
        for n in (32, 32, 26):
            buf = np.zeros((1, Cb), np.int32)
            buf[0, :n] = prompt[start:start + n]
            out = step(params, {"tokens": jnp.asarray(buf)}, pool,
                       tables=jnp.asarray(table), start=jnp.int32(start),
                       length=jnp.int32(n))
            pool = out[1]
            logits.append(np.asarray(out[0], np.float32))
            counted.append([int(c) for c in out[2:]])
            start += n
        runs[interpret] = (logits, pool, counted)
    (g_logits, g_pool, g_counted), (k_logits, k_pool, k_counted) = (
        runs[False], runs[True])
    for got, want in zip(k_logits, g_logits):
        np.testing.assert_allclose(got, want,
                                   atol=0.03 * np.abs(want).max())
        assert got.argmax() == want.argmax()
    assert k_counted == g_counted and len(k_counted[0]) == (
        2 if cfg.is_moe else 0)
    held = table[:-(-P // BLOCK_SIZE)]
    for name in ("k", "v"):
        got = np.asarray(k_pool[name], np.float32)
        want = np.asarray(g_pool[name], np.float32)
        np.testing.assert_allclose(got, want,
                                   atol=0.02 * np.abs(want).max())
        written = np.zeros(got.shape[1:3], bool)       # (NB, BS)
        for pos in range(P):
            written[table[pos // BLOCK_SIZE], pos % BLOCK_SIZE] = True
        for pool in (got, want):
            assert np.abs(pool[:, written]).min(axis=-1).max() > 0
            assert not pool[:, ~written].any()
        assert written[held].sum() == P


def test_paged_kernel_engages_by_platform_and_shape():
    """The decode program takes the kernel only when lowered for a TPU,
    and only for the shapes the kernel takes: qwen2.5-3b's 128-wide heads
    in bf16 blocks of 16, not phi3-mini's 96, not an fp8 pool."""
    qwen, phi3 = get_config("qwen2.5-3b"), get_config("phi3-mini-3.8b")
    assert paged_kernel_engages(qwen, 16, jnp.bfloat16)
    assert not paged_kernel_engages(qwen, 16, jnp.bfloat16, "cpu")
    assert not paged_kernel_engages(qwen, 8, jnp.bfloat16)
    assert not paged_kernel_engages(qwen, 16, jnp.float8_e4m3fn)
    assert not paged_kernel_engages(phi3, 16, jnp.bfloat16)


def test_logs_reset_per_run(paged_loop):
    run_loop(paged_loop, "static", make_requests(21))
    n = len(paged_loop.prefill_log)
    run_loop(paged_loop, "static", make_requests(21))
    assert len(paged_loop.prefill_log) == n
    assert paged_loop.dispatch_log[0]["dispatch"] == 0
