"""LoopTelemetry — the *measure* stage of plan/execute/measure, closed.

The plan engine's history-epoch cache invalidation (PR 1) was wired but
starved: adaptive strategies key their cached plans on
``LoopHistory.measured_invocations``, yet nothing in the serving or
training steady state actually recorded measurements, so AWF*/AF plans
never replanned from real data.  This module is the missing recorder.

A :class:`LoopTelemetry` aggregates per-chunk / per-worker measured times
from any substrate —

* **serving**: per-chunk wall time (prefill + every decode step of the
  chunk's requests), accumulated across the interleaved slot loop via the
  stopwatch ledger API (``begin`` / ``add_time`` / ``end``),
* **training**: per-step wall times and token counts
  (``record_chunk`` once per step),
* **plan replay**: ``core.executor.execute_plan`` records each replayed
  chunk's modelled elapsed time,
* **straggler mitigation**: per-host step-time deltas,

— buffers them as :class:`~repro.core.history.ChunkRecord` entries, and
``flush()``-es them into a :class:`~repro.core.history.LoopHistory`.  The
flush is what bumps the history's *measured epoch*, which invalidates the
engine's cached adaptive plans: the next ``PlanEngine.plan()`` misses the
cache and replans against the new measurements.  That is the whole
telemetry → history → replan loop.

Recording discipline (no double counting):

* When a telemetry object is attached to a :class:`SchedulerContext`, the
  scheduler measurement hook (``SixOpBase.end_loop_body``) routes chunk
  records *through the telemetry buffer* instead of writing the history
  directly, and the engine's :class:`ScheduleStream` flushes on ``close``
  — one epoch bump per completed invocation.
* The ledger API buffers a chunk exactly once even when its elapsed time
  is also fed back through ``stream.next`` (the hook recognizes
  ledger-recorded chunks and skips them), so within-invocation adaptive
  strategies (AWF-B/C/D/E, AF) still see every measurement.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.core.history import ChunkRecord, LoopHistory
from repro.core.interface import Chunk

__all__ = ["ChunkLedger", "LoopTelemetry", "MembershipEvent", "ServeMeter"]


@dataclasses.dataclass(frozen=True)
class MembershipEvent:
    """A team-size change — worker loss or join — as a scheduling event.

    The paper's contract (start = init + enqueue for the *current* team)
    makes membership change just another replan trigger: the event is
    recorded into the loop's history as a measured sentinel invocation
    (:meth:`LoopTelemetry.record_membership`), which bumps the measured
    epoch that cached adaptive plans key on, so the next ``plan()`` for
    the loop re-runs ``init`` over the new team size.  ``lost`` /
    ``joined`` carry OLD-team worker ids; after a loss the surviving
    team is renumbered densely ``0..new_size-1``.
    """

    kind: str                       # "loss" | "join"
    old_size: int
    new_size: int
    lost: Tuple[int, ...] = ()      # old-team ids that left
    joined: Tuple[int, ...] = ()    # new-team ids that joined
    step: Optional[int] = None      # loop step/dispatch the event landed on

    def __post_init__(self):
        if self.kind not in ("loss", "join"):
            raise ValueError(f"kind must be 'loss' or 'join', "
                             f"got {self.kind!r}")
        if self.old_size < 1 or self.new_size < 1:
            raise ValueError(f"team sizes must be >= 1, got "
                             f"{self.old_size}->{self.new_size}")

    @property
    def tag(self) -> str:
        """Invocation provenance string.  Deliberately NOT a schedule
        clause: ``schedule(auto)`` scores only invocations tagged with
        candidate clauses, so membership sentinels never pollute its
        portfolio statistics."""
        return f"membership({self.old_size}->{self.new_size})"


def _percentile(xs: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile of a small host-side sample (no numpy —
    this module stays dependency-free)."""
    if not xs:
        return None
    s = sorted(xs)
    idx = min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))
    return s[idx]


class ServeMeter:
    """Per-request serving observability: latency percentiles + KV residency.

    The continuous-batching engine makes admission a *scheduling* decision
    (blocks free? chunk budget?), so the interesting latencies live
    between lifecycle edges the loop controls:

    * ``queue``      — arrival → admission (first blocks granted),
    * ``admission``  — admission → first generated token (chunked
      prefill time as the request experiences it),
    * ``e2e``        — arrival → finish.

    The stamps are the requests' own (``t_arrive`` / ``t_admit`` /
    ``t_first`` / ``t_finish``, set by the serve loops);
    :meth:`summary` reduces them to the p50/p99 dictionary that
    ``last_stats`` and BENCH_serve.json carry.  The loop calls
    :meth:`preempt` on every eviction and :meth:`blocks` whenever pool
    occupancy changes (pass ``time.perf_counter()``).  A preempted request
    keeps its original arrival/admission stamps — preemption inflates its
    e2e latency, which is exactly what the percentiles should see.
    """

    def __init__(self) -> None:
        self.preemptions = 0
        self.preempted_rids: List[int] = []
        # time-weighted pool utilization: integral of used/total dt
        self._blk_t: Optional[float] = None
        self._blk_used = 0
        self._blk_total = 0
        self._blk_area = 0.0
        self._blk_span = 0.0

    def preempt(self, rid: int) -> None:
        self.preemptions += 1
        self.preempted_rids.append(rid)

    # --------------------------------------------------------- pool gauge
    def blocks(self, used: int, total: int, t: float) -> None:
        """Record pool occupancy at time ``t``; utilization is the
        time-weighted mean of ``used/total`` between samples."""
        if self._blk_t is not None and total > 0:
            dt = max(t - self._blk_t, 0.0)
            self._blk_area += dt * (self._blk_used / max(self._blk_total, 1))
            self._blk_span += dt
        self._blk_t = t
        self._blk_used = int(used)
        self._blk_total = int(total)

    # ------------------------------------------------------------ summary
    @staticmethod
    def _lat(requests: Sequence[Any], a: str, b: str) -> List[float]:
        pairs = ((getattr(r, a), getattr(r, b)) for r in requests)
        return [tb - ta for ta, tb in pairs if ta is not None and tb is not None]

    def summary(self, requests: Sequence[Any]) -> Dict[str, Any]:
        """Percentiles over ``requests``' lifecycle stamps, and the pool
        gauge and preemption count this meter recorded."""
        queue = self._lat(requests, "t_arrive", "t_admit")
        admission = self._lat(requests, "t_admit", "t_first")
        e2e = self._lat(requests, "t_arrive", "t_finish")
        util = (self._blk_area / self._blk_span
                if self._blk_span > 0 else None)
        return {
            "requests_seen": sum(r.t_arrive is not None for r in requests),
            "requests_finished": sum(r.t_finish is not None
                                     for r in requests),
            "queue_p50_s": _percentile(queue, 50),
            "queue_p99_s": _percentile(queue, 99),
            "admission_p50_s": _percentile(admission, 50),
            "admission_p99_s": _percentile(admission, 99),
            "e2e_p50_s": _percentile(e2e, 50),
            "e2e_p99_s": _percentile(e2e, 99),
            "kv_util_mean": round(util, 4) if util is not None else None,
            "preemptions": self.preemptions,
        }


@dataclasses.dataclass
class ChunkLedger:
    """An open stopwatch for one in-flight chunk on one worker."""

    worker: int
    start: int
    stop: int
    elapsed: float = 0.0
    tokens: int = 0

    @property
    def size(self) -> int:
        return self.stop - self.start


class LoopTelemetry:
    """Aggregate measured chunk times and flush them into a LoopHistory.

    Parameters
    ----------
    history:
        The cross-invocation store to flush into (may be None: telemetry
        then only aggregates statistics — useful for pure reporting).
    loop_id:
        History key; must match the ``LoopSpec.loop_id`` the adaptive
        scheduler plans against, or the epoch bump invalidates nothing.
        Left as None it is bound by ``PlanEngine.open_stream`` /
        ``execute_plan`` from the loop being measured.
    num_workers:
        Team size, for the summary's per-worker tables (optional).
    """

    def __init__(self, history: Optional[LoopHistory] = None,
                 loop_id: Optional[str] = None,
                 num_workers: Optional[int] = None) -> None:
        self.history = history
        self.loop_id = loop_id
        self.num_workers = num_workers
        self._open: Dict[int, ChunkLedger] = {}
        self._buffer: List[ChunkRecord] = []
        # chunks recorded via the ledger API; the scheduler hook skips
        # these so stream-fed elapsed values are not double counted
        self._ledgered: set = set()
        self.records_flushed = 0
        self.flushes = 0
        # aggregates (survive flushes)
        self._time: Dict[int, float] = {}
        self._iters: Dict[int, int] = {}
        self._chunks: Dict[int, int] = {}
        self._tokens: Dict[int, int] = {}
        self._t_first: Optional[float] = None
        self._t_last: Optional[float] = None

    # ------------------------------------------------------------- epochs
    def epoch(self) -> int:
        """The measured-invocation epoch adaptive plan caches key on."""
        if self.history is None or self.loop_id is None:
            return 0
        return self.history.measured_invocations(self.loop_id)

    def record_membership(self, event: MembershipEvent) -> int:
        """Record a team-size change and return the new measured epoch.

        Writes one *measured* zero-size sentinel invocation (worker -1,
        elapsed 0.0) tagged with the event directly into the history —
        the same cache-invalidation edge as :meth:`flush`, so every
        cached adaptive plan for this loop misses on the next ``plan()``
        and replans over the new team.  The sentinel is invisible to the
        rate statistics (``worker_rates`` and the straggler mitigator
        both skip size-0 chunks) and survives history serialization
        (``from_json`` re-derives ``measured`` from the elapsed field).
        Also resizes the summary's team width to the new size.
        """
        self.num_workers = event.new_size
        if self.history is not None and self.loop_id is not None:
            self.history.open_invocation(self.loop_id, scheduler=event.tag)
            self.history.record(self.loop_id,
                                ChunkRecord(worker=-1, start=0, stop=0,
                                            elapsed=0.0))
            # close the sentinel invocation: ``history.record`` appends to
            # the LAST open invocation, so without a fresh boundary the
            # next flush would dump real chunks into the membership-tagged
            # invocation (polluting its provenance and eating the epoch
            # bump those chunks should have produced)
            self.history.open_invocation(self.loop_id)
        return self.epoch()

    # ------------------------------------------------- ledger (stopwatch)
    def begin(self, worker: int, chunk: Chunk) -> ChunkLedger:
        """Open a ledger for a freshly dequeued chunk.  An unclosed ledger
        for the same worker is ended (and buffered) first, so measurements
        are never silently dropped."""
        if worker in self._open:
            self.end(worker)
        led = ChunkLedger(worker=int(worker), start=int(chunk.start),
                          stop=int(chunk.stop))
        self._open[worker] = led
        return led

    def add_time(self, worker: int, dt: float, tokens: int = 0) -> None:
        """Attribute ``dt`` seconds (and optionally generated tokens) to
        the worker's open chunk — e.g. one prefill or one decode step."""
        led = self._open.get(worker)
        if led is None:
            return
        led.elapsed += float(dt)
        led.tokens += int(tokens)
        now = time.perf_counter()
        if self._t_first is None:
            self._t_first = now - dt
        self._t_last = now

    def add_time_weighted(self, dt: float, weights: Dict[int, float],
                          tokens: Optional[Dict[int, int]] = None) -> None:
        """Split one measured wall time across the open ledgers
        proportionally to ``weights`` — per-host attribution for a
        lockstep data-parallel train step: ONE jitted step advances every
        host, so host ``h`` is charged ``dt * w_h / sum(w)``, its modelled
        share of the step's compute, and credited its own token count.  In an
        emulated-host run the weights ARE the measurement model (token
        count x injected skew); a real multi-host deployment feeds
        genuine per-host clocks instead.  Hosts without an open ledger
        are skipped; a non-positive weight total falls back to an equal
        split so a measurement is never silently dropped."""
        ws = {w: max(float(weights.get(w, 0.0)), 0.0)
              for w in self._open}
        if not ws:
            return
        total = sum(ws.values())
        if total <= 0.0:
            ws = {w: 1.0 for w in ws}
            total = float(len(ws))
        for w, wt in ws.items():
            self.add_time(w, float(dt) * wt / total,
                          tokens=(tokens or {}).get(w, 0))

    def end(self, worker: int) -> Optional[float]:
        """Close the worker's ledger, buffer its record, and return the
        chunk's total elapsed time (the value to feed ``stream.next`` so
        within-invocation adaptive strategies see it)."""
        led = self._open.pop(worker, None)
        if led is None:
            return None
        self._buffer.append(ChunkRecord(worker=led.worker, start=led.start,
                                        stop=led.stop, elapsed=led.elapsed))
        self._ledgered.add((led.worker, led.start, led.stop))
        self._aggregate(led.worker, led.size, led.elapsed, led.tokens)
        return led.elapsed

    # ------------------------------------------------------ direct record
    def record_chunk(self, worker: int, start: int, stop: int,
                     elapsed: Optional[float], tokens: int = 0) -> None:
        """Buffer one measured chunk directly (train steps, plan replay,
        straggler deltas)."""
        self._buffer.append(ChunkRecord(worker=int(worker), start=int(start),
                                        stop=int(stop), elapsed=elapsed))
        if elapsed is not None:
            self._aggregate(int(worker), int(stop) - int(start),
                            float(elapsed), int(tokens))
            now = time.perf_counter()
            if self._t_first is None:
                self._t_first = now - elapsed
            self._t_last = now

    def record_chunks(self, workers, starts, stops, elapsed) -> None:
        """Bulk form of :meth:`record_chunk` over parallel sequences
        (``execute_plan``'s replay path — plain lists, one pass)."""
        append = self._buffer.append
        agg = self._aggregate
        for w, s, e, dt in zip(workers, starts, stops, elapsed):
            append(ChunkRecord(worker=w, start=s, stop=e, elapsed=dt))
            if dt is not None:
                agg(w, e - s, dt, 0)

    def observe_chunk(self, worker: int, chunk: Chunk,
                      elapsed: Optional[float]) -> None:
        """Scheduler measurement hook entry point
        (``SixOpBase.end_loop_body`` routes here when a telemetry object is
        attached to the context).  Chunks already buffered by the ledger
        API are skipped — their stream-fed elapsed is the same
        measurement."""
        key = (int(worker), int(chunk.start), int(chunk.stop))
        if key in self._ledgered:
            return
        self.record_chunk(worker, chunk.start, chunk.stop, elapsed)

    # --------------------------------------------------------------- flush
    def flush(self) -> int:
        """Write all buffered records (closing any open ledgers) into the
        history and return the resulting measured epoch.

        This is the cache-invalidation edge: the first record carrying a
        real elapsed time marks the current invocation *measured*, so the
        engine's next ``plan()`` for an adaptive scheduler misses its
        cached plan and replans from the new data.
        """
        for worker in list(self._open):
            self.end(worker)
        if self.history is not None and self._buffer:
            if self.loop_id is None:
                # refusing is better than recording under a wrong key the
                # adaptive scheduler will never look at (silent non-replan)
                raise ValueError(
                    "LoopTelemetry has a history but no loop_id; pass "
                    "loop_id= at construction or bind it via "
                    "PlanEngine.open_stream / execute_plan")
            for rec in self._buffer:
                self.history.record(self.loop_id, rec)
            self.records_flushed += len(self._buffer)
            self.flushes += 1
        self._buffer.clear()
        self._ledgered.clear()
        return self.epoch()

    @property
    def pending(self) -> int:
        """Buffered records not yet flushed (open ledgers excluded)."""
        return len(self._buffer)

    # ------------------------------------------------------------- summary
    def _aggregate(self, worker: int, iters: int, elapsed: float,
                   tokens: int) -> None:
        self._time[worker] = self._time.get(worker, 0.0) + elapsed
        self._iters[worker] = self._iters.get(worker, 0) + iters
        self._chunks[worker] = self._chunks.get(worker, 0) + 1
        self._tokens[worker] = self._tokens.get(worker, 0) + tokens

    def summary(self) -> Dict[str, Any]:
        """Machine-readable aggregate (what the bench harness serializes):
        per-worker busy time / iterations / rate, totals, and tok/s."""
        workers = sorted(self._time)
        if self.num_workers is not None:
            workers = list(range(self.num_workers))
        per_worker = {}
        for w in workers:
            t = self._time.get(w, 0.0)
            it = self._iters.get(w, 0)
            per_worker[w] = {
                "time_s": round(t, 6),
                "iters": it,
                "chunks": self._chunks.get(w, 0),
                "tokens": self._tokens.get(w, 0),
                "rate_s_per_iter": round(t / it, 9) if it else None,
            }
        total_time = sum(self._time.values())
        total_tokens = sum(self._tokens.values())
        wall = None
        if self._t_first is not None and self._t_last is not None:
            wall = max(self._t_last - self._t_first, 1e-12)
        times = [self._time.get(w, 0.0) for w in workers]
        mx = max(times, default=0.0)
        imbalance = (mx - sum(times) / len(times)) / mx if mx > 0 else 0.0
        return {
            "loop_id": self.loop_id,
            "per_worker": per_worker,
            "total_time_s": round(total_time, 6),
            "total_iters": sum(self._iters.values()),
            "total_tokens": total_tokens,
            "tok_s": (round(total_tokens / wall, 2)
                      if wall and total_tokens else None),
            "imbalance": round(imbalance, 4),
            "flushes": self.flushes,
            "records_flushed": self.records_flushed,
            "epoch": self.epoch(),
        }

    # ------------------------------------------------------------- helpers
    def worker_times(self) -> Dict[int, float]:
        return dict(self._time)

    def worker_iters(self) -> Dict[int, int]:
        return dict(self._iters)
