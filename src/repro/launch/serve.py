"""Serving driver: continuous batching with a UDS request scheduler.

Every model family serves through one engine, chosen by what the family
implements (:func:`engine_for`; the CLI needs no flag for it):

* :class:`PagedServeLoop` serves every attention family (dense, MoE,
  audio, vision; GQA or MLA's latent rows) over a shared KV block pool
  (``repro.serve_mem``): cache
  MEMORY is the scheduled resource.  A request is admitted when blocks
  for its prompt are free; its prompt prefills in UDS-planned chunks
  (:func:`plan_prefill_chunks`) that interleave with decode dispatches;
  its sequence grows block by block as it generates; and under memory
  pressure the most recently admitted request is preempted — blocks
  freed, requeued at the front, later re-prefilled with its generated
  prefix (greedy decode makes the resumed request token-for-token
  identical to an uninterrupted run).  Each decode dispatch is ONE jitted
  call that runs ``decode_steps`` tokens for every active row through an
  on-device ``lax.scan`` (``make_paged_serve_step``); a row freezes in
  place on device when its budget is spent, when it emits EOS, or when
  its fill reaches the blocks it holds (the host then grows its table,
  or preempts).  The dispatch quantum T = ``decode_steps`` trades
  admission latency (new requests join only at dispatch boundaries) for
  fewer Python→XLA round-trips; every T gives the same tokens.  See
  docs/SCHEDULING.md, "Paged KV and continuous batching".
* :class:`ServeLoop` is the per-slot engine: a fixed set of decode slots,
  each with its own batch-1 cache or recurrent state, one jitted call per
  active slot per token.  It serves the ``ssm``/``hybrid`` families,
  whose state has no place in the block pool yet, and is the token
  reference the paged engine is tested against.  Slots are workers and
  requests iterations: a slot that finishes (EOS / budget) dequeues the
  next request chunk through the UDS, i.e. ``schedule(dynamic, 1)``;
  guided/factoring variants admit several requests per dequeue when the
  queue is deep.  Attention families' prompts are right-padded to
  power-of-two length *buckets* before the jitted prefill — causal masking
  makes the padded prefix math identical, so a long tail of distinct
  prompt lengths compiles one program per bucket instead of one per
  length.

In both engines a request whose ``prompt + max_new`` exceeds the context
ceiling is admitted but **truncated**: its generation budget is clamped to
the capacity and the truncation is reported per request
(``Request.truncated``, ``last_stats["truncated"]``) — never silently
padded or dropped.  A prompt that alone exceeds the ceiling is refused
loudly.

Both engines are instrumented with
:class:`~repro.core.telemetry.LoopTelemetry`.  In :class:`ServeLoop` every
chunk's **full wall time** — the prefill of each of its requests plus
every decode call of their generations — is attributed to the slot that
served it, fed back through ``stream.next`` (so within-invocation
adaptive strategies like AWF-B rebalance admission mid-run), and flushed
into the loop's ``LoopHistory`` when the stream closes.  The flush bumps
the history's measured epoch, so a cached adaptive plan for this loop is
invalidated and the *next* ``run()`` replans admission from the measured
slot speeds (AWF timestep).  ``history`` persists across calls — pass one
in to persist across processes (it serializes with checkpoints).

    python -m repro.launch.serve --arch qwen2.5-3b --smoke --requests 32 \
        --num-blocks 48 --block-size 8 --max-concurrency 16 --decode-steps 4
    python -m repro.launch.serve --arch rwkv6-3b --smoke --requests 8
"""

from __future__ import annotations

import argparse
import dataclasses
import inspect
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs import get_config, get_smoke_config
from repro.core import (LoopHistory, LoopSpec, LoopTelemetry,
                        MembershipEvent, SchedulerContext, ServeMeter,
                        get_engine)
from repro.core.spec import SpecLike, describe, resolve
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import (make_paged_prefill_step, make_paged_serve_step,
                                make_prefill_step, make_serve_step)
from repro.kernels.paged_attention.ops import copied_positions
from repro.models import get_model
from repro.models.transformer import paged_kernel_engages
from repro.serve_mem import BlockPool, BlockTables
from repro.serve_mem.blocks import blocks_for_tokens

__all__ = ["ServeLoop", "PagedServeLoop", "Request", "bucket_length",
           "plan_prefill_chunks", "engine_for", "main"]

# smallest prefill bucket: tiny prompts share one program instead of
# compiling at 1, 2, 3, ... tokens
MIN_PREFILL_BUCKET = 8

# what an MoE model's paged programs count, last among their outputs, and
# the loop logs per call: slots its held experts computed, and held
# experts that took a slot (per layer and step)
EXPERT_COUNTERS = ("expert_slots", "experts_used")


def bucket_length(n: int, max_len: int) -> int:
    """Prompt-length bucket: next power of two >= n (floored at
    ``MIN_PREFILL_BUCKET``), capped at ``max_len``.  One jitted prefill
    compilation per bucket serves every prompt length inside it."""
    b = MIN_PREFILL_BUCKET
    while b < n:
        b *= 2
    return min(b, max_len)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (P,) int32
    max_new: int = 16
    generated: Optional[List[int]] = None
    # generation budget = min(max_new, cache capacity), set at admission;
    # truncated=True when the cache clamped the request below max_new
    budget: int = 0
    truncated: bool = False
    # lifecycle stamps (perf_counter clock, set by the serve loops):
    # arrival -> admission is queue latency, admission -> first token is
    # admission latency, arrival -> finish is e2e.  Preemption does NOT
    # reset stamps — the wait is part of the request's latency.
    t_arrive: Optional[float] = None
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    t_finish: Optional[float] = None
    # paged engine bookkeeping: admission sequence (LIFO preemption
    # victim order) and how many times this request was evicted
    admit_seq: int = -1
    preemptions: int = 0


class ServeLoop:
    """Continuous batching over a fixed decode-slot count, per slot.

    Each slot owns a batch-1 cache (or recurrent state) of ``max_len``;
    every decode call advances one slot by one token.  ``history`` carries
    measured per-slot chunk times across ``run()`` invocations — the
    serving steady state's feedback channel.  After each run,
    ``last_stats`` holds the telemetry summary (per-slot busy time,
    tokens, tok/s, decode call counts, truncations, measured epoch).
    Parameters and caches are bfloat16, as in training.
    """

    def __init__(self, cfg, *, slots: int = 4, max_len: int = 256,
                 scheduler: SpecLike = "dynamic", seed: int = 0,
                 history: Optional[LoopHistory] = None,
                 eos_id: Optional[int] = None):
        self.cfg = cfg
        self.model = get_model(cfg)
        self.slots = slots
        self.max_len = max_len
        key = jax.random.PRNGKey(seed)
        self.params, _ = self.model.init(key, jnp.bfloat16)
        # any schedule-clause form: spec, "guided,4", "uds:name", "runtime",
        # or a scheduler instance
        self.scheduler = scheduler
        self.sched_name = describe(scheduler)
        self.loop_id = "serve"
        self.history = history if history is not None else LoopHistory()
        self.last_stats: Dict[str, Any] = {}
        self.eos_id = eos_id
        # jitted prefill, compiled once per prompt-length BUCKET: prompts
        # are right-padded to power-of-two buckets and the real length is
        # passed as a traced scalar (causal masking makes the padded math
        # identical), so a long tail of distinct lengths stops triggering
        # ~0.8s recompiles mid-serve.  SSM/hybrid prefills absorb pad
        # tokens into their recurrent state and take no length, so only
        # attention families bucket.
        self._prefill = jax.jit(make_prefill_step(self.model,
                                                  max_len=max_len))
        self._bucketed = ("length"
                          in inspect.signature(self.model.prefill).parameters)
        # one cache per slot (batch=1); the cache argument is donated, so
        # each call updates it in place
        self._decode = jax.jit(make_serve_step(self.model),
                               donate_argnums=(2,))
        self.caches = [self.model.init_decode(1, max_len)[0]
                       for _ in range(slots)]
        self.active: Dict[int, Request] = {}
        self._decoded = 0
        # last-position logits (V,) of the most recent prefill, kept for
        # comparison against a reference forward pass
        self.last_prefill_logits: Optional[jax.Array] = None

    @property
    def prefill_compiles(self) -> int:
        """Distinct compiled prefill programs (the bucketing regression
        metric: mixed prompt lengths must not grow this per-length)."""
        return self._prefill._cache_size()

    def _prefill_into(self, slot: int, req: Request) -> None:
        P = int(req.prompt.size)
        # the cache holds the prompt plus one KV per decode step; capacity
        # is how many tokens can be generated before the fill hits max_len
        # (the first token comes from the prefill logits and appends
        # nothing).  A prompt that alone overflows the cache is refused
        # loudly; a generation that would overflow is admitted with its
        # budget clamped and the truncation REPORTED per request.
        capacity = self.max_len - P + 1
        if capacity < 1:
            raise ValueError(
                f"request {req.rid}: prompt ({P} tokens) exceeds the "
                f"cache (max_len={self.max_len}); raise ServeLoop max_len "
                f"or shorten the request")
        req.budget = min(req.max_new, capacity)
        req.truncated = req.budget < req.max_new
        tokens = req.prompt
        if self._bucketed:
            pb = bucket_length(P, self.max_len)
            if pb > P:
                tokens = np.concatenate(
                    [tokens, np.zeros(pb - P, tokens.dtype)])
            inputs = {"tokens": jnp.asarray(tokens[None, :])}
            logits, cache = self._prefill(self.params, inputs,
                                          jnp.asarray(P, jnp.int32))
        else:
            inputs = {"tokens": jnp.asarray(tokens[None, :])}
            logits, cache = self._prefill(self.params, inputs)
        self.caches[slot] = cache
        self.last_prefill_logits = logits[0]
        req.generated = [int(jnp.argmax(logits, -1)[0])]

    def _finished(self, req: Request) -> bool:
        """Budget spent, or the newest token is EOS."""
        if len(req.generated) >= req.budget:
            return True
        return self.eos_id is not None and req.generated[-1] == self.eos_id

    def run(self, requests: List[Request]) -> Dict[int, List[int]]:
        """Schedule + serve all requests to completion."""
        sched = resolve(self.scheduler)
        loop = LoopSpec(lb=0, ub=len(requests), num_workers=self.slots,
                        loop_id=self.loop_id)
        telemetry = LoopTelemetry(self.history, loop_id=self.loop_id,
                                  num_workers=self.slots)
        stream = get_engine().open_stream(
            sched, SchedulerContext(loop=loop, history=self.history),
            telemetry=telemetry)
        meter = ServeMeter()
        now = time.perf_counter()
        for req in requests:
            if req.t_arrive is None:
                req.t_arrive = now
        pending: Dict[int, Deque[Request]] = {s: deque()
                                              for s in range(self.slots)}
        # per-chunk wall time of the slot's *previous* chunk (prefill +
        # all decode calls), consumed by the next dequeue and then
        # cleared — never a stale prefill-only value
        elapsed: Dict[int, Optional[float]] = {s: None
                                               for s in range(self.slots)}
        results: Dict[int, List[int]] = {}
        truncated: List[int] = []
        exhausted = set()
        self._decoded = 0

        def finish(req: Request) -> None:
            results[req.rid] = req.generated
            req.t_finish = time.perf_counter()
            if req.truncated:
                truncated.append(req.rid)

        while len(results) < len(requests):
            # admission: idle slots dequeue request chunks via the UDS,
            # reporting the measured wall time of their previous chunk
            for s in range(self.slots):
                if s in self.active or pending[s] or s in exhausted:
                    continue
                chunk = stream.next(s, elapsed[s])
                elapsed[s] = None              # consumed by this dequeue
                if chunk is None:
                    exhausted.add(s)
                    continue
                telemetry.begin(s, chunk)
                for i in range(chunk.start, chunk.stop):
                    pending[s].append(requests[i])
            progressed = False
            for s in range(self.slots):
                if s not in self.active and pending[s]:
                    req = pending[s].popleft()
                    t0 = time.perf_counter()
                    if req.t_admit is None:
                        req.t_admit = t0
                    self._prefill_into(s, req)
                    t1 = time.perf_counter()
                    if req.t_first is None:
                        req.t_first = t1
                    telemetry.add_time(s, t1 - t0, tokens=1)
                    progressed = True
                    if self._finished(req):    # budget of 1, or EOS first
                        finish(req)
                        if not pending[s]:
                            elapsed[s] = telemetry.end(s)
                    else:
                        self.active[s] = req
            # one decode call per active slot
            done_slots = []
            for s, req in list(self.active.items()):
                t0 = time.perf_counter()
                tok, self.caches[s] = self._decode(
                    self.params, {"tokens": jnp.asarray([[req.generated[-1]]])},
                    self.caches[s])
                req.generated.append(int(tok[0]))
                telemetry.add_time(s, time.perf_counter() - t0, tokens=1)
                self._decoded += 1
                progressed = True
                if self._finished(req):
                    finish(req)
                    done_slots.append(s)
            for s in done_slots:
                del self.active[s]
                if not pending[s]:
                    # the chunk is fully served: close its ledger and hand
                    # its wall time to the slot's next dequeue
                    elapsed[s] = telemetry.end(s)
            if not progressed:
                break
        stream.close()        # flushes telemetry -> history epoch bump
        self.last_stats = telemetry.summary()
        # one decode call per token
        self.last_stats["decode_dispatches"] = self._decoded
        self.last_stats["decoded_tokens"] = self._decoded
        self.last_stats["truncated"] = sorted(truncated)
        self.last_stats["prefill_compiles"] = self.prefill_compiles
        self.last_stats["serve_meter"] = meter.summary(requests)
        return results

    def measured_epoch(self) -> int:
        """Measured-invocation count for the serve loop — the plan-cache
        epoch adaptive admission schedules key on."""
        return self.history.measured_invocations(self.loop_id)


def plan_prefill_chunks(scheduler: SpecLike, n_tokens: int, *,
                        max_chunk: int,
                        history: Optional[LoopHistory] = None) -> List[int]:
    """Split one prompt's prefill into chunk sizes via the UDS spine.

    The prompt's token range ``[0, n_tokens)`` is planned as a
    single-worker loop under the serve scheduler clause, so the SAME
    ``--scheduler`` string that the loop serves under also governs how
    coarsely prefill interleaves with decode: ``schedule(static)``
    prefills in bursts of ``max_chunk``, ``schedule(dynamic,1)`` yields
    minimal chunks (lowest head-of-line blocking for in-flight decodes,
    most dispatches), ``guided`` starts coarse and refines toward the
    prompt's tail, and ``auto`` picks online from ``serve_prefill``
    telemetry.  Planned sizes are capped at ``max_chunk``; the caller
    bucket-pads each chunk at dispatch (:func:`bucket_length`), so the
    compile count is bounded by the bucket count, never by chunk-size
    variety.
    """
    if n_tokens <= 0:
        return []
    if max_chunk < 1:
        raise ValueError(f"max_chunk must be >= 1, got {max_chunk}")
    sched = resolve(scheduler)
    loop = LoopSpec(lb=0, ub=n_tokens, num_workers=1,
                    loop_id="serve_prefill")
    plan = get_engine().plan(sched, loop, history=history)
    order = np.argsort(np.asarray(plan.starts, np.int64), kind="stable")
    sizes: List[int] = []
    for i in order:
        rem = int(plan.sizes[i])
        while rem > 0:
            c = min(rem, max_chunk)
            sizes.append(c)
            rem -= c
    if sum(sizes) != n_tokens:
        raise AssertionError(
            f"prefill plan does not tile [0, {n_tokens}): {sizes}")
    return sizes


@dataclasses.dataclass
class _Prefill:
    """One in-flight chunked prefill (batch=1) through the paged pool."""

    req: Request
    tokens: np.ndarray            # prompt (+ generated prefix on readmit)
    sizes: List[int]              # UDS-planned chunk sizes, in order
    idx: int = 0                  # next chunk
    start: int = 0                # tokens already cached


class PagedServeLoop:
    """Continuous batching over a paged KV block pool.

    Where :class:`ServeLoop` schedules a fixed set of ``slots`` (each
    owning a batch-1 ``max_len`` cache), this engine schedules cache
    MEMORY: every request draws fixed-size KV blocks from one shared
    :class:`~repro.serve_mem.BlockPool` as its sequence grows, so
    concurrency is bounded by total cache tokens, not by a slot count.
    The loop interleaves three kinds of work:

    * **admission** — the next queued request is admitted when blocks for
      its prompt are free; its prefill is split into UDS-planned chunks
      (:func:`plan_prefill_chunks`) so long prompts never block in-flight
      decodes for more than one chunk.
    * **decode** — ONE fused dispatch advances every active request
      ``decode_steps`` tokens (``make_paged_serve_step``).  Before each
      dispatch, rows grow their block tables to cover the dispatch's
      appends; a row that cannot grow triggers **preemption**: the most
      recently admitted victim's blocks are freed and it is requeued at
      the FRONT with its generated prefix.  Readmission prefills
      ``prompt + generated`` — greedy decode is deterministic, so the
      resumed request is token-for-token identical to an uninterrupted
      run (locked in ``tests/test_paged.py``).
    * **finish** — completed requests release every block immediately.

    ``max_context`` is the per-request ceiling (:class:`ServeLoop`'s
    ``max_len``); budgets clamp/truncate against it exactly as in
    :class:`ServeLoop`.  ``concurrency`` is only the fused dispatch's
    batch width (compiled once) — memory admission happens first.
    Parameters and the KV pool are bfloat16.
    """

    def __init__(self, cfg, *, num_blocks: int = 64, block_size: int = 8,
                 max_context: int = 256, concurrency: int = 8,
                 scheduler: SpecLike = "dynamic", seed: int = 0,
                 history: Optional[LoopHistory] = None,
                 decode_steps: int = 1, eos_id: Optional[int] = None,
                 prefill_chunk: int = 32,
                 kill_rows: int = 0,
                 kill_at_dispatch: Optional[int] = None):
        self.cfg = cfg
        self.model = get_model(cfg)
        if self.model.fused_paged_decode is None:
            raise ValueError(
                f"{cfg.name}: model family has no paged-KV path "
                f"(use ServeLoop's per-slot engine)")
        if max_context % block_size:
            raise ValueError(
                f"max_context ({max_context}) must be a multiple of "
                f"block_size ({block_size})")
        if decode_steps < 1:
            raise ValueError(f"decode_steps must be >= 1, got {decode_steps}")
        if prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if concurrency < 1:
            raise ValueError(f"concurrency must be >= 1, got {concurrency}")
        if kill_rows < 0 or kill_rows >= concurrency:
            raise ValueError(
                f"kill_rows must leave at least one live dispatch row "
                f"(got kill_rows={kill_rows}, concurrency={concurrency})")
        if (kill_rows > 0) != (kill_at_dispatch is not None):
            raise ValueError(
                "kill_rows and kill_at_dispatch must be given together")
        self.params, _ = self.model.init(jax.random.PRNGKey(seed),
                                         jnp.bfloat16)
        self.scheduler = scheduler
        self.sched_name = describe(scheduler)
        self.loop_id = "serve_paged"
        self.history = history if history is not None else LoopHistory()
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.max_context = max_context
        self.max_blocks_per_seq = max_context // block_size
        self.concurrency = concurrency
        self.decode_steps = decode_steps
        self.prefill_chunk = prefill_chunk
        self.eos_id = eos_id
        self.pool = BlockPool(num_blocks, block_size)
        self.tables = BlockTables(self.pool,
                                  max_blocks=self.max_blocks_per_seq)
        self.cache = self.model.init_paged_decode(num_blocks,
                                                  block_size)[0]
        # which read both programs' attention lowers to on the pool's
        # platform: the Pallas kernels over live blocks, or the gather of
        # every row's whole view (``read_positions`` follows it; for MLA
        # it counts latent rows)
        pool = jax.tree.leaves(self.cache)[0]
        self.kernel_reads = paged_kernel_engages(
            cfg, block_size, pool.dtype, next(iter(pool.devices())).platform)
        # one compile per prefill BUCKET (chunks are bucket-padded) and
        # ONE decode program (fixed (concurrency, W) dispatch shape); both
        # donate the pool, so it is updated in place
        self._prefill_step = jax.jit(make_paged_prefill_step(self.model),
                                     donate_argnums=(2,))
        self._decode = jax.jit(make_paged_serve_step(self.model,
                                                     decode_steps),
                               donate_argnums=(2,))
        self.active: Dict[int, Request] = {}        # dispatch row -> req
        self.last_stats: Dict[str, Any] = {}
        self.last_prefill_logits: Optional[np.ndarray] = None
        self._dispatches = 0
        self._decoded = 0
        self._pf_dispatches = 0
        # elastic slot-set shrink: an injected worker kill marks the top
        # kill_rows dispatch rows dead at the kill_at_dispatch-th decode
        # dispatch — their in-flight requests drain through the normal
        # evict-requeue machinery and readmit on surviving rows
        self._kill_rows = kill_rows
        self._kill_at = kill_at_dispatch
        self._kill_fired = False
        self._dead_rows: set = set()
        self.membership_events: List[MembershipEvent] = []
        # per-dispatch measurement log (elastic_recovery bench splits it
        # at the kill dispatch): wall time, produced tokens, live rows,
        # each row's cached fill and tokens made, and the context
        # positions the program's attention read; an MoE model adds its
        # programs' EXPERT_COUNTERS
        self.dispatch_log: List[Dict[str, Any]] = []
        # its prefill twin: one {rid, start, length, bucket} per chunk
        # (and an MoE model's EXPERT_COUNTERS); the chunk's serve.prefill
        # span adds its read_positions
        self.prefill_log: List[Dict[str, int]] = []

    @property
    def prefill_compiles(self) -> int:
        """Distinct compiled prefill-chunk programs (bounded by the
        bucket count — the chunked-prefill bucketing regression metric)."""
        return self._prefill_step._cache_size()

    def measured_epoch(self) -> int:
        """Measured-invocation count for the paged serve loop."""
        return self.history.measured_invocations(self.loop_id)

    # ----------------------------------------------------------- internals
    def _read_positions(self, fills: np.ndarray, made: np.ndarray) -> int:
        """Context positions one decode dispatch's attention read.  The
        Pallas kernel copies, at each step a row runs, that row's blocks
        below its length (its fill, the step's earlier tokens and the
        step's own); a frozen or empty row reads nothing.  The gather
        reads every row's whole ``max_context`` view at every step."""
        if not self.kernel_reads:
            return self.concurrency * self.max_context * self.decode_steps
        steps = [f + j + 1 for f, m in zip(fills.tolist(), made.tolist())
                 for j in range(m)]
        return copied_positions(steps, self.block_size)

    def _chunk_read_positions(self, end: int, bucket: int) -> int:
        """K/V positions one prefill chunk's attention read per layer.  The
        Pallas kernel copies the request's blocks below its fill after
        the chunk (``end``); the gather reads the request's whole view
        and the chunk's own ``bucket`` positions beside it."""
        if not self.kernel_reads:
            return self.max_blocks_per_seq * self.block_size + bucket
        return copied_positions([end], self.block_size)

    def _fill_of(self, req: Request) -> int:
        """Cached KV positions: the prompt plus one per generated token
        except the newest (its KV lands at the next dispatch)."""
        return int(req.prompt.size) + len(req.generated) - 1

    def run(self, requests: List[Request]) -> Dict[int, List[int]]:
        """Admit, prefill, decode, preempt as needed — to completion.

        Each phase runs inside a ``serve.*`` profiler span (see
        docs/SCHEDULING.md, "Tracing the paged loop"); every prefill chunk
        appends to ``prefill_log`` and every decode dispatch to
        ``dispatch_log``."""
        with TraceAnnotation("serve.run", requests=len(requests)):
            return self._run(requests)

    def _run(self, requests: List[Request]) -> Dict[int, List[int]]:
        meter = ServeMeter()
        telemetry = LoopTelemetry(self.history, loop_id=self.loop_id,
                                  num_workers=1)
        pf_tel = LoopTelemetry(self.history, loop_id="serve_prefill",
                               num_workers=1)
        now = time.perf_counter()
        for req in requests:
            if req.t_arrive is None:
                req.t_arrive = now
        meter.blocks(self.pool.used, self.pool.num_blocks, now)
        queue: Deque[Request] = deque(requests)
        requeue: Deque[Request] = deque()     # preempted; front of the line
        results: Dict[int, List[int]] = {}
        truncated: List[int] = []
        pf: Optional[_Prefill] = None
        admit_seq = 0
        peak_conc = 0
        turn = 0
        self._dispatches = 0
        self._decoded = 0
        self._pf_dispatches = 0
        self.dispatch_log = []
        self.prefill_log = []
        C, W = self.concurrency, self.max_blocks_per_seq
        eos_arr = jnp.asarray(-1 if self.eos_id is None else self.eos_id,
                              jnp.int32)

        def finish(req: Request) -> None:
            results[req.rid] = req.generated
            req.t_finish = time.perf_counter()
            if req.truncated:
                truncated.append(req.rid)
            self.tables.release(req.rid)
            meter.blocks(self.pool.used, self.pool.num_blocks, req.t_finish)

        def preempt_one(exclude_rid: int) -> bool:
            """Evict the most recently admitted active request (LIFO:
            the oldest request keeps its memory — FIFO completion order
            survives pressure) and requeue it at the front."""
            rows = [r for r, rq in self.active.items()
                    if rq.rid != exclude_rid]
            if not rows:
                return False
            victim = max(rows, key=lambda r: self.active[r].admit_seq)
            rq = self.active.pop(victim)
            with TraceAnnotation("serve.preempt", rid=rq.rid):
                self.tables.release(rq.rid)
                rq.preemptions += 1
                meter.preempt(rq.rid)
                meter.blocks(self.pool.used, self.pool.num_blocks,
                             time.perf_counter())
                requeue.appendleft(rq)
            return True

        while len(results) < len(requests):
            with TraceAnnotation("serve.turn", turn=turn):
                turn += 1
                progressed = False
                ran_prefill = False

                # ---- injected worker kill: a slot-set shrink is a membership
                # event.  The doomed rows' in-flight requests drain through
                # the evict-requeue machinery (blocks freed, front of the
                # line) and readmit on surviving rows; greedy decode makes
                # every resumed request token-for-token identical to an
                # unkilled run.  The fused dispatch keeps its compiled
                # (C, W) shape — dead rows just stay mask-gated off.
                if (self._kill_at is not None and not self._kill_fired
                        and self._dispatches >= self._kill_at):
                    self._kill_fired = True
                    doomed = set(range(C - self._kill_rows, C))
                    self._dead_rows |= doomed
                    # evict newest-first so appendleft leaves the requeue in
                    # admit order (oldest victim readmits first)
                    for r in sorted((r for r in doomed if r in self.active),
                                    key=lambda r: self.active[r].admit_seq,
                                    reverse=True):
                        rq = self.active.pop(r)
                        self.tables.release(rq.rid)
                        rq.preemptions += 1
                        meter.preempt(rq.rid)
                        requeue.appendleft(rq)
                    meter.blocks(self.pool.used, self.pool.num_blocks,
                                 time.perf_counter())
                    event = MembershipEvent(
                        kind="loss", old_size=C,
                        new_size=C - len(self._dead_rows),
                        lost=tuple(sorted(doomed)), step=self._dispatches)
                    telemetry.record_membership(event)
                    # the serve loop's telemetry worker is the fused
                    # dispatcher, not a row — keep the summary single-worker
                    telemetry.num_workers = 1
                    self.membership_events.append(event)

                # ---- admission: memory first (blocks for the prompt), then a
                # dispatch row; preempted requests readmit ahead of the queue
                if (pf is None and (requeue or queue)
                        and len(self.active) < C - len(self._dead_rows)):
                    src = requeue if requeue else queue
                    req = src[0]
                    # readmission replays the generated prefix
                    n_all = int(req.prompt.size) + len(req.generated or ())
                    with TraceAnnotation("serve.admit", rid=req.rid,
                                         tokens=n_all):
                        if req.budget == 0:    # first admission: fix the budget
                            P = int(req.prompt.size)
                            capacity = self.max_context - P + 1
                            if capacity < 1:
                                raise ValueError(
                                    f"request {req.rid}: prompt ({P} tokens) "
                                    f"exceeds max_context={self.max_context}; "
                                    f"raise PagedServeLoop max_context or "
                                    f"shorten the request")
                            req.budget = min(req.max_new, capacity)
                            req.truncated = req.budget < req.max_new
                        if self.tables.ensure(req.rid, n_all):
                            src.popleft()
                            req.admit_seq = admit_seq
                            admit_seq += 1
                            t = time.perf_counter()
                            if req.t_admit is None:
                                req.t_admit = t
                            meter.blocks(self.pool.used, self.pool.num_blocks, t)
                            tokens = req.prompt
                            if req.generated:
                                tokens = np.concatenate(
                                    [tokens, np.asarray(req.generated, np.int32)])
                            with TraceAnnotation("serve.plan",
                                                 rid=req.rid) as span:
                                sizes = plan_prefill_chunks(
                                    self.scheduler, n_all,
                                    max_chunk=self.prefill_chunk,
                                    history=self.history)
                                span.set_metadata(chunks=len(sizes))
                            pf = _Prefill(req=req, tokens=tokens, sizes=sizes)
                            progressed = True
                        elif not self.active:
                            # every block is free and the prompt still doesn't
                            # fit: the pool itself is too small for this request
                            raise ValueError(
                                f"request {req.rid}: {n_all} tokens need "
                                f"{blocks_for_tokens(n_all, self.block_size)} "
                                f"blocks but the pool has "
                                f"{self.pool.num_blocks}; raise num_blocks")

                # ---- one prefill chunk per turn while admission can progress
                if pf is not None:
                    ran_prefill = True
                    n = pf.sizes[pf.idx]
                    pb = bucket_length(n, self.prefill_chunk)
                    entry = {"rid": pf.req.rid, "start": pf.start, "length": n,
                             "bucket": pb}
                    self.prefill_log.append(entry)
                    with TraceAnnotation(
                            "serve.prefill", **entry,
                            read_positions=self._chunk_read_positions(
                                pf.start + n, pb)):
                        buf = np.zeros((1, pb), np.int32)
                        buf[0, :n] = pf.tokens[pf.start:pf.start + n]
                        t0 = time.perf_counter()
                        args = (self.params, {"tokens": jnp.asarray(buf)},
                                self.cache,
                                jnp.asarray(self.tables.row(pf.req.rid)),
                                jnp.asarray(pf.start, jnp.int32),
                                jnp.asarray(n, jnp.int32))
                        # the call and the read-back: the host holds no
                        # result until the program has run
                        with TraceAnnotation("serve.wait",
                                             program="prefill_chunk"):
                            logits, self.cache, *counted = self._prefill_step(*args)
                            logits = np.asarray(logits)   # sync: true chunk time
                            counted = [int(c) for c in counted]
                        entry.update(zip(EXPERT_COUNTERS, counted))
                        dt = time.perf_counter() - t0
                        pf_tel.record_chunk(0, pf.start, pf.start + n, dt,
                                            tokens=n)
                        self._pf_dispatches += 1
                        pf.start += n
                        pf.idx += 1
                        progressed = True
                        if pf.idx == len(pf.sizes):     # prompt fully cached
                            req = pf.req
                            pf = None
                            self.last_prefill_logits = logits[0]
                            tok = int(np.argmax(logits[0]))
                            if req.generated is None:
                                req.generated = []
                            req.generated.append(tok)
                            if req.t_first is None:
                                req.t_first = time.perf_counter()
                            done = len(req.generated) >= req.budget
                            if self.eos_id is not None and tok == self.eos_id:
                                done = True
                            if done:
                                finish(req)
                            else:
                                row = min(r for r in range(C)
                                          if r not in self.active
                                          and r not in self._dead_rows)
                                self.active[row] = req
                                peak_conc = max(peak_conc, len(self.active))

                # ---- one fused decode dispatch across every active row.
                # Admission has priority: decode runs when prefill could NOT
                # progress this turn (queue empty, pool full, or concurrency
                # cap) — occupancy builds while blocks are free, and under
                # memory pressure the loop alternates admission attempts with
                # decode dispatches at chunk granularity, which is exactly the
                # prefill/decode interleave the scheduler clause governs.
                if self.active and not ran_prefill:
                    # grow tables oldest-first so the head of the line wins
                    # under pressure; LIFO victims free blocks as needed
                    with TraceAnnotation("serve.grow", rows=len(self.active)):
                        for r in sorted(self.active,
                                        key=lambda r: self.active[r].admit_seq):
                            if r not in self.active:    # preempted this turn
                                continue
                            rq = self.active[r]
                            total_need = int(rq.prompt.size) + rq.budget - 1
                            need = min(self._fill_of(rq) + self.decode_steps,
                                       total_need)
                            while not self.tables.ensure(rq.rid, need):
                                if not preempt_one(exclude_rid=rq.rid):
                                    raise ValueError(
                                        f"request {rq.rid}: cannot grow to "
                                        f"{need} tokens with every other "
                                        f"request evicted — the pool "
                                        f"({self.num_blocks} blocks) is "
                                        f"smaller than one request's context; "
                                        f"raise num_blocks")
                        meter.blocks(self.pool.used, self.pool.num_blocks,
                                     time.perf_counter())
                    with TraceAnnotation("serve.decode",
                                         dispatch=self._dispatches,
                                         rows=len(self.active)):
                        rows = sorted(self.active)
                        last = np.zeros((C, 1), np.int32)
                        mask = np.zeros((C,), bool)
                        rem = np.zeros((C,), np.int32)
                        lens = np.zeros((C,), np.int32)
                        lims = np.zeros((C,), np.int32)
                        tab = np.full((C, W), -1, np.int32)
                        for r in rows:
                            rq = self.active[r]
                            last[r, 0] = rq.generated[-1]
                            mask[r] = True
                            rem[r] = rq.budget - len(rq.generated)
                            lens[r] = self._fill_of(rq)
                            lims[r] = self.tables.capacity(rq.rid)
                            tab[r] = self.tables.row(rq.rid)
                        t0 = time.perf_counter()
                        args = (self.params, {"tokens": jnp.asarray(last)},
                                self.cache, jnp.asarray(tab), jnp.asarray(lens),
                                jnp.asarray(lims), jnp.asarray(mask),
                                jnp.asarray(rem), eos_arr)
                        with TraceAnnotation("serve.wait", program="serve_step"):
                            (toks, self.cache, _, act_out, rem_out,
                             *counted) = self._decode(*args)
                            toks = np.asarray(toks)   # sync: true dispatch time
                            rem_out = np.asarray(rem_out)
                            counted = [int(c) for c in counted]
                        dt = time.perf_counter() - t0
                        made = rem[rows] - rem_out[rows]
                        produced_total = int(made.sum())
                        telemetry.record_chunk(0, self._dispatches,
                                               self._dispatches + 1, dt,
                                               tokens=produced_total)
                        self.dispatch_log.append(
                            {"dispatch": self._dispatches, "dt_s": dt,
                             "tokens": produced_total, "rows": len(rows),
                             "live_rows": C - len(self._dead_rows),
                             "fills": lens[rows].tolist(),
                             "made": made.tolist(),
                             "read_positions": self._read_positions(
                                 lens[rows], made)})
                        self.dispatch_log[-1].update(zip(EXPERT_COUNTERS, counted))
                        self._dispatches += 1
                        progressed = True
                        for r, produced in zip(rows, made.tolist()):
                            rq = self.active[r]
                            rq.generated.extend(int(t) for t in toks[r, :produced])
                            self._decoded += produced
                            done = len(rq.generated) >= rq.budget
                            if (self.eos_id is not None
                                    and rq.generated[-1] == self.eos_id):
                                done = True
                            if done:
                                del self.active[r]
                                finish(rq)
                            # a capacity-frozen row just stays active: the next
                            # turn's growth phase gets it more blocks (or
                            # preempts someone to)

                if not progressed:
                    break
        telemetry.flush()
        pf_tel.flush()
        self.last_stats = telemetry.summary()
        self.last_stats.update(meter.summary(requests))
        self.last_stats["decode_steps"] = self.decode_steps
        self.last_stats["decode_dispatches"] = self._dispatches
        self.last_stats["decoded_tokens"] = self._decoded
        self.last_stats["prefill_dispatches"] = self._pf_dispatches
        self.last_stats["prefill_compiles"] = self.prefill_compiles
        self.last_stats["truncated"] = sorted(truncated)
        self.last_stats["peak_concurrency"] = peak_conc
        self.last_stats["num_blocks"] = self.num_blocks
        self.last_stats["block_size"] = self.block_size
        self.last_stats["peak_blocks_used"] = self.pool.peak_used
        self.last_stats["failed_allocs"] = self.pool.failed_allocs
        self.last_stats["dead_rows"] = sorted(self._dead_rows)
        self.last_stats["live_rows"] = C - len(self._dead_rows)
        self.last_stats["membership_events"] = [
            {"kind": e.kind, "old_size": e.old_size, "new_size": e.new_size,
             "lost": list(e.lost), "at_dispatch": e.step}
            for e in self.membership_events]
        return results


def engine_for(cfg) -> type:
    """The serving engine for ``cfg``'s family: :class:`PagedServeLoop`
    where the family has a paged-KV decode, the per-slot
    :class:`ServeLoop` otherwise (the ``ssm``/``hybrid`` families)."""
    return PagedServeLoop if get_model(cfg).paged_decode is not None \
        else ServeLoop


def main(argv: Optional[List[str]] = None):
    """Serve a batch of random requests through ``engine_for(cfg)``;
    returns the loop."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--scheduler", default="dynamic",
                    help='schedule clause: "dynamic", "guided,4", '
                         '"uds:name(args)", "runtime" (late-bound from '
                         '$REPRO_SCHEDULE), or "auto" (selected online '
                         "from serve telemetry; see docs/SCHEDULING.md)")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-concurrency", type=int, default=8,
                    help="requests in flight: the paged engine's decode "
                         "dispatch width (compiled once; memory admission "
                         "happens first), the per-slot engine's slots")
    ap.add_argument("--max-context", type=int, default=64,
                    help="per-request context ceiling (prompt + "
                         "generated); paged: a multiple of --block-size")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop token id; default: generate to the token "
                         "budget")
    ap.add_argument("--decode-steps", type=int, default=1,
                    help="paged engine: tokens per fused decode dispatch; "
                         "1 = stepwise, 8 amortizes the Python->XLA "
                         "round-trip over 8 tokens")
    ap.add_argument("--num-blocks", type=int, default=64,
                    help="paged engine: KV blocks in the shared pool")
    ap.add_argument("--block-size", type=int, default=8,
                    help="paged engine: token positions per KV block")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="paged engine: max tokens per prefill chunk (the "
                         "UDS plans the chunking under --scheduler)")
    ap.add_argument("--kill-rows", type=int, default=0,
                    help="paged engine: injected worker kill — mark this "
                         "many dispatch rows dead mid-run (drain-and-"
                         "readmit; requires --kill-at-dispatch)")
    ap.add_argument("--kill-at-dispatch", type=int, default=None,
                    help="paged engine: decode dispatch count at which the "
                         "injected kill fires")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=rng.integers(4, 24)
                                        ).astype(np.int32),
                    max_new=args.max_new)
            for i in range(args.requests)]
    paged = engine_for(cfg) is PagedServeLoop
    if paged:
        loop = PagedServeLoop(cfg, num_blocks=args.num_blocks,
                              block_size=args.block_size,
                              max_context=args.max_context,
                              concurrency=args.max_concurrency,
                              scheduler=args.scheduler,
                              decode_steps=args.decode_steps,
                              eos_id=args.eos_id,
                              prefill_chunk=args.prefill_chunk,
                              kill_rows=args.kill_rows,
                              kill_at_dispatch=args.kill_at_dispatch)
    else:
        if args.kill_rows:
            ap.error(f"{cfg.name}: --kill-rows needs the paged engine, "
                     f"which this model family does not have")
        loop = ServeLoop(cfg, slots=args.max_concurrency,
                         max_len=args.max_context, scheduler=args.scheduler,
                         eos_id=args.eos_id)
    t0 = time.perf_counter()
    out = loop.run(reqs)
    dt = time.perf_counter() - t0
    toks = sum(len(v) for v in out.values())
    s = loop.last_stats
    head = (f"{type(loop).__name__}: served {len(out)} requests, {toks} "
            f"tokens in {dt:.2f}s ({toks/dt:.1f} tok/s) under "
            f"schedule({loop.sched_name}); ")
    if paged:
        print(head + f"decode x{loop.decode_steps}, "
              f"peak concurrency {s.get('peak_concurrency')}, "
              f"{s.get('peak_blocks_used')}/{loop.num_blocks} blocks peak "
              f"(mean util {s.get('kv_util_mean')}), "
              f"{s.get('preemptions')} preemptions, "
              f"{s.get('prefill_compiles')} prefill compiles, "
              f"measured epoch {loop.measured_epoch()}")
        for ev in loop.membership_events:
            print(f"membership: {ev.kind} at dispatch {ev.step} — "
                  f"{ev.old_size} -> {ev.new_size} rows "
                  f"(lost {list(ev.lost)}); in-flight requests drained "
                  f"and readmitted on the survivors")
    else:
        print(head + f"{s.get('decode_dispatches')} decode calls, "
              f"measured epoch {loop.measured_epoch()}, "
              f"imbalance {s.get('imbalance')}")
    return loop


if __name__ == "__main__":
    main()
