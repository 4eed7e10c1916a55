"""The telemetry -> history -> replan loop: LoopTelemetry recording,
stream flush-on-close, execute_plan measured replay, and the end-to-end
adaptive rebalance under skewed worker speeds."""

import numpy as np
import pytest

from repro.core import (Chunk, LoopHistory, LoopSpec, LoopTelemetry,
                        SchedulerContext, execute_plan, get_engine,
                        make_scheduler, simulate_loop)
from repro.core.engine import PlanEngine


# ----------------------------------------------------------- unit: recorder
def test_serve_meter_reads_the_requests_stamps():
    """ServeMeter's percentiles come from the requests' own lifecycle
    stamps; a request without a stamp is left out of that latency."""
    from types import SimpleNamespace
    from repro.core import ServeMeter
    reqs = [SimpleNamespace(t_arrive=0.0, t_admit=0.5 * i, t_first=0.5 * i + 1.0,
                            t_finish=0.5 * i + 2.0) for i in range(4)]
    reqs.append(SimpleNamespace(t_arrive=0.0, t_admit=None, t_first=None,
                                t_finish=None))
    meter = ServeMeter()
    meter.preempt(3)
    s = meter.summary(reqs)
    assert s["requests_seen"] == 5 and s["requests_finished"] == 4
    assert s["queue_p50_s"] == pytest.approx(1.0)
    assert s["queue_p99_s"] == pytest.approx(1.5)
    assert s["admission_p99_s"] == pytest.approx(1.0)
    assert s["e2e_p99_s"] == pytest.approx(3.5)
    assert s["preemptions"] == 1 and s["kv_util_mean"] is None


def test_ledger_accumulates_interleaved_chunk_time():
    tel = LoopTelemetry(LoopHistory(), loop_id="serve", num_workers=2)
    tel.begin(0, Chunk(0, 3, 0))
    tel.begin(1, Chunk(3, 4, 1))
    tel.add_time(0, 0.5, tokens=1)      # prefill
    tel.add_time(1, 0.2, tokens=1)
    tel.add_time(0, 0.25, tokens=1)     # decode steps, interleaved
    tel.add_time(0, 0.25, tokens=1)
    assert tel.end(0) == pytest.approx(1.0)
    assert tel.end(1) == pytest.approx(0.2)
    epoch = tel.flush()
    assert epoch == 1
    inv = tel.history.invocations("serve")[-1]
    assert [(c.worker, c.elapsed) for c in inv.chunks] == [
        (0, pytest.approx(1.0)), (1, pytest.approx(0.2))]
    assert tel.summary()["total_tokens"] == 4


def test_add_time_weighted_splits_step_time_proportionally():
    """The multi-host train attribution: ONE wall time split over the open
    per-host ledgers proportionally to the given weights."""
    tel = LoopTelemetry(LoopHistory(), loop_id="train_step", num_workers=3)
    for h, size in enumerate((4, 2, 2)):
        tel.begin(h, Chunk(h * 4, h * 4 + size, h))
    tel.add_time_weighted(1.0, {0: 2.0, 1: 1.0, 2: 1.0},
                          tokens={0: 4, 1: 2, 2: 2})
    assert tel.end(0) == pytest.approx(0.5)
    assert tel.end(1) == pytest.approx(0.25)
    assert tel.end(2) == pytest.approx(0.25)
    tel.flush()
    assert tel.summary()["total_tokens"] == 8
    # hosts without an open ledger are skipped; negative weights clamp
    tel.begin(0, Chunk(0, 1, 0))
    tel.add_time_weighted(0.3, {0: 1.0, 7: 5.0, 1: -2.0})
    assert tel.end(0) == pytest.approx(0.3)
    # all-zero weights fall back to an equal split (never drop a sample)
    tel.begin(0, Chunk(0, 1, 0))
    tel.begin(1, Chunk(1, 2, 1))
    tel.add_time_weighted(0.4, {0: 0.0, 1: 0.0})
    assert tel.end(0) == pytest.approx(0.2)
    assert tel.end(1) == pytest.approx(0.2)
    # no open ledgers at all: a silent no-op
    tel.add_time_weighted(1.0, {0: 1.0})


def test_flush_closes_open_ledgers_and_bumps_epoch_once():
    hist = LoopHistory()
    tel = LoopTelemetry(hist, loop_id="x", num_workers=1)
    tel.begin(0, Chunk(0, 2, 0))
    tel.add_time(0, 0.1)
    assert hist.measured_invocations("x") == 0
    assert tel.flush() == 1             # open ledger ended + recorded
    assert tel.pending == 0
    assert tel.flush() == 1             # empty flush does not bump again


def test_record_chunk_direct_api_feeds_worker_rates():
    hist = LoopHistory()
    tel = LoopTelemetry(hist, loop_id="train_step", num_workers=2)
    tel.record_chunk(0, 0, 100, 1.0, tokens=100)
    tel.record_chunk(1, 0, 100, 4.0, tokens=100)
    tel.flush()
    rates = hist.worker_rates("train_step")
    assert rates[1] == pytest.approx(4 * rates[0])
    # direct records carry the wall-clock bounds too, so the train-loop
    # path reports a throughput instead of tok_s=None
    assert tel.summary()["tok_s"] is not None


def test_flush_with_history_but_no_loop_id_raises():
    tel = LoopTelemetry(LoopHistory())        # never bound to a loop
    tel.record_chunk(0, 0, 10, 0.1)
    with pytest.raises(ValueError, match="loop_id"):
        tel.flush()


# ------------------------------------------- stream: flush on close, no dupes
def test_stream_with_telemetry_flushes_on_close_only():
    hist = LoopHistory()
    tel = LoopTelemetry(num_workers=2)   # history inherited from ctx
    loop = LoopSpec(0, 40, num_workers=2, loop_id="s")
    stream = get_engine().open_stream(
        make_scheduler("dynamic", chunk=10),
        SchedulerContext(loop=loop, history=hist), telemetry=tel)
    active = {0, 1}
    while active:                     # each worker drains to its terminal
        for w in list(active):        # None-dequeue, reporting elapsed
            if stream.next(w, 0.01) is None:
                active.discard(w)
    assert hist.measured_invocations("s") == 0   # buffered, not yet flushed
    stream.close()
    assert hist.measured_invocations("s") == 1
    inv = hist.invocations("s")[-1]
    # every dequeued chunk recorded exactly once (4 chunks of 10)
    assert sorted((c.start, c.stop) for c in inv.chunks
                  if c.elapsed is not None) == [
        (0, 10), (10, 20), (20, 30), (30, 40)]


def test_ledger_fed_elapsed_not_double_counted():
    """A chunk measured via the ledger AND fed back through stream.next must
    appear once in the history."""
    hist = LoopHistory()
    tel = LoopTelemetry(num_workers=1)
    loop = LoopSpec(0, 6, num_workers=1, loop_id="d")
    stream = get_engine().open_stream(
        make_scheduler("dynamic", chunk=3),
        SchedulerContext(loop=loop, history=hist), telemetry=tel)
    elapsed = None
    while True:
        chunk = stream.next(0, elapsed)
        if chunk is None:
            break
        tel.begin(0, chunk)
        tel.add_time(0, 0.5)
        elapsed = tel.end(0)
    stream.close()
    chunks = hist.invocations("d")[-1].chunks
    assert sorted((c.start, c.stop) for c in chunks) == [(0, 3), (3, 6)]
    assert all(c.elapsed == pytest.approx(0.5) for c in chunks)


# ----------------------------------------------- execute_plan measured replay
def test_execute_plan_records_and_invalidates_adaptive_cache():
    eng = PlanEngine()
    hist = LoopHistory()
    loop = LoopSpec(0, 800, num_workers=2, loop_id="replay")
    sched = make_scheduler("awf")
    p1 = eng.plan(sched, loop, history=hist)
    res = execute_plan(p1, np.ones(800), speeds=[2.0, 1.0], history=hist)
    assert hist.measured_invocations("replay") == 1
    assert res.wave_times is not None and len(res.wave_times) == p1.num_waves
    assert sum(res.wave_times) >= res.makespan - 1e-9
    p2 = eng.plan(sched, loop, history=hist)
    assert p2 is not p1                                 # epoch bump -> replan
    assert int(p2.worker_iters()[0]) > int(p1.worker_iters()[0])


def test_execute_plan_telemetry_object_aggregates():
    plan = PlanEngine().plan(make_scheduler("static_block"),
                             LoopSpec(0, 100, num_workers=4, loop_id="agg"))
    tel = LoopTelemetry(LoopHistory())
    execute_plan(plan, np.ones(100), telemetry=tel)
    assert tel.loop_id == "agg"                  # bound from the plan's loop
    assert sum(tel.worker_iters().values()) == 100
    assert tel.epoch() == 1


def test_execute_plan_binds_history_onto_bare_telemetry():
    """history= and an unbound telemetry= together: the telemetry inherits
    the history (mirrors open_stream) so the epoch still advances."""
    hist = LoopHistory()
    plan = PlanEngine().plan(make_scheduler("static_block"),
                             LoopSpec(0, 60, num_workers=2, loop_id="bind"))
    tel = LoopTelemetry()
    execute_plan(plan, np.ones(60), history=hist, telemetry=tel)
    assert tel.history is hist
    assert hist.measured_invocations("bind") == 1


# ------------------------------------------------- end-to-end: the issue gate
def test_adaptive_replan_shifts_work_off_slow_worker():
    """Acceptance: an executor steady-state loop under AWF with skewed
    synthetic worker speeds replans (>= 1 history-epoch cache invalidation
    from measured data) and the rebalanced plan gives the slow worker
    less."""
    eng = PlanEngine()
    hist = LoopHistory()
    n, p = 2048, 4
    loop = LoopSpec(0, n, num_workers=p, loop_id="e2e/awf")
    sched = make_scheduler("awf")
    speeds = [1.0, 1.0, 1.0, 0.25]

    shares, makespans = [], []
    for _ in range(4):
        tel = LoopTelemetry(hist, loop_id=loop.loop_id, num_workers=p)
        plan = eng.plan(sched, loop, history=hist)
        res = execute_plan(plan, np.ones(n), speeds=speeds, telemetry=tel)
        shares.append(int(plan.worker_iters()[3]))
        makespans.append(res.makespan)

    assert hist.measured_invocations(loop.loop_id) >= 1
    assert eng.cache_info().misses >= 2   # >=1 invalidation beyond first plan
    assert shares[-1] < shares[0]         # slow worker's share shrank
    assert makespans[-1] < makespans[0]   # and the step got faster
    # learned share should approach the speed ratio (0.25 / 3.25 of work)
    assert shares[-1] < n // p * 0.7


def test_awf_b_rebalances_within_invocation_and_bumps_cache_epoch():
    """AWF-B (batch-boundary adaptation): the streamed schedule itself
    shifts work off the slow worker, and the measured invocation
    invalidates the cached plan for the next step."""
    eng = PlanEngine()
    hist = LoopHistory()
    n, p = 2048, 4
    loop = LoopSpec(0, n, num_workers=p, loop_id="e2e/awf_b")
    speeds = [1.0, 1.0, 1.0, 0.25]

    p1 = eng.plan(make_scheduler("awf_b"), loop, history=hist)
    res = simulate_loop(make_scheduler("awf_b"), loop, np.ones(n),
                        speeds=speeds, history=hist)
    iters = np.zeros(p, np.int64)
    for c in res.chunks:
        iters[c.worker] += c.size
    assert iters[3] < n // p              # rebalanced away from the slow one
    assert hist.measured_invocations(loop.loop_id) == 1
    p2 = eng.plan(make_scheduler("awf_b"), loop, history=hist)
    assert p2 is not p1                   # epoch advanced -> cache invalidated
    assert eng.cache_info().misses == 2


def test_streaming_and_replay_epochs_compose():
    """Mixed feedback: a measured streaming run (simulate_loop) followed by
    measured replays keeps advancing one epoch per invocation."""
    hist = LoopHistory()
    eng = PlanEngine()
    loop = LoopSpec(0, 600, num_workers=3, loop_id="mix")
    simulate_loop(make_scheduler("awf"), loop, np.ones(600),
                  speeds=[1.0, 1.0, 0.5], history=hist)
    assert hist.measured_invocations("mix") == 1
    plan = eng.plan(make_scheduler("awf"), loop, history=hist)
    execute_plan(plan, np.ones(600), speeds=[1.0, 1.0, 0.5], history=hist)
    assert hist.measured_invocations("mix") == 2


# ----------------------------------------------------------- serve loop unit
def test_serve_loop_reports_per_chunk_wall_time():
    """The fixed feedback bug: a slot's second dequeue must report the wall
    time of its whole previous chunk (prefill + decode tokens), not a stale
    prefill-only value.  Exercised via the ledger discipline serve uses."""
    tel = LoopTelemetry(LoopHistory(), loop_id="serve", num_workers=1)
    tel.begin(0, Chunk(0, 1, 0))
    tel.add_time(0, 0.3, tokens=1)                     # prefill
    for _ in range(3):
        tel.add_time(0, 0.1, tokens=1)                 # decode steps
    first = tel.end(0)
    assert first == pytest.approx(0.6)                 # not 0.3 (prefill-only)
    tel.begin(0, Chunk(1, 2, 0))
    tel.add_time(0, 0.05, tokens=1)
    second = tel.end(0)
    assert second == pytest.approx(0.05)               # not stale 0.6
    tel.flush()
    rates = tel.history.worker_rates("serve")
    assert rates[0] == pytest.approx((0.6 + 0.05) / 2)


def test_straggler_mitigator_epoch_advances_per_step():
    from repro.sched import StragglerMitigator
    m = StragglerMitigator(num_hosts=4)
    for step in range(5):
        m.observe_step({h: 1.0 + (0.5 if h == 2 else 0.0) for h in range(4)})
    assert m.epoch() == 5
    assert 2 in m.stragglers()
    w = m.weights()
    assert w[2] < min(w[0], w[1], w[3])
