"""Adaptive-replan benchmark: the telemetry -> history -> replan loop.

Four stages, all serialized machine-readably (CI: ``--json
BENCH_serve.json`` uploaded as an artifact, ``--gate`` as the exit code):

1. **Executor steady state** (pure host, no JAX): an AWF loop over a team
   with one deliberately slow worker.  Each step plans through the
   ``PlanEngine`` (cached), replays via ``execute_plan`` with telemetry
   attached, and flushes — the flush bumps the history's measured epoch,
   the next ``plan()`` misses the adaptive cache and replans from the
   measured rates.  Reported: the slow worker's share trajectory, makespan
   improvement, epoch advances, and cache invalidations — the acceptance
   criterion "an AWF run demonstrably replans from measured data" as
   numbers.

2. **Serve smoke** (real model, CPU-runnable smoke config): two
   ``ServeLoop.run()`` invocations of the per-slot engine under AWF
   admission.  The first run's per-chunk wall times (prefill + decode)
   flush at stream close; the second run plans admission from the
   learned slot rates.  Reported: tok/s, measured epoch, per-slot
   telemetry.

3. **Auto selection** (pure host, no JAX): the same skewed-worker loop
   driven by ``schedule(auto)`` and by every fixed candidate clause.
   Reported: each clause's steady-state makespan, auto's selection
   trajectory, and ``auto_vs_best_fixed_ratio`` (best fixed steady
   makespan / auto's).  The gate enforces ``>= 0.9`` — the acceptance
   criterion that auto converges within 10% of the best hand-picked
   clause without being told which.

4. **Paged concurrency** (real model): the paged-KV continuous-batching
   engine over the deterministic long/short mixed trace that
   ``tests/test_paged.py`` also exercises (``serve_mem.make_mixed_trace``
   — tests and bench gate the same workload).  Two sub-runs: an *open*
   pool serving O(100) concurrent requests (gates: every request
   completes, ``peak_concurrency >= 100``, a warm tok/s floor, and a p99
   admission-latency ceiling) and a *pressured* pool far below the
   working set (gates: every request still completes, ``preemptions >=
   1`` — eviction/readmission demonstrably exercised end to end).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

RESULTS = Path(__file__).parent / "results"

N_ITER = 8192
WORKERS = 8
STEPS = 10
SLOW_WORKER = WORKERS - 1
SLOW_SPEED = 0.25
AUTO_RATIO_GATE = 0.9  # auto must reach >= 90% of the best fixed clause
PAGED_REQUESTS = 120
PAGED_CONCURRENCY_GATE = 100   # paged engine must hold O(100) in flight
PAGED_TOKS_GATE = 10.0         # warm tok/s floor (conservative: CI CPU)
PAGED_ADM_P99_GATE = 5.0       # p99 admission latency ceiling, seconds


def executor_steady_state(n_iter: int = N_ITER, workers: int = WORKERS,
                          steps: int = STEPS) -> dict:
    """plan -> execute_plan -> flush, ``steps`` times, under skewed speeds."""
    import numpy as np
    from repro.core import (LoopHistory, LoopSpec, LoopTelemetry,
                            execute_plan, resolve)
    from repro.core.engine import PlanEngine

    eng = PlanEngine()
    hist = LoopHistory()
    loop = LoopSpec(0, n_iter, num_workers=workers, loop_id="serve_adapt")
    sched = resolve("awf")
    speeds = [1.0] * workers
    speeds[SLOW_WORKER] = SLOW_SPEED
    costs = np.ones(n_iter)

    epochs = [hist.measured_invocations(loop.loop_id)]
    slow_share = []
    makespans = []
    t0 = time.perf_counter()
    for _ in range(steps):
        tel = LoopTelemetry(hist, loop_id=loop.loop_id, num_workers=workers)
        plan = eng.plan(sched, loop, history=hist)
        res = execute_plan(plan, costs, speeds=speeds, telemetry=tel)
        slow_share.append(int(plan.worker_iters()[SLOW_WORKER]))
        makespans.append(round(res.makespan, 2))
        epochs.append(hist.measured_invocations(loop.loop_id))
    wall = time.perf_counter() - t0

    info = eng.cache_info()
    return {
        "n_iter": n_iter,
        "workers": workers,
        "steps": steps,
        "slow_worker": SLOW_WORKER,
        "slow_speed": SLOW_SPEED,
        "slow_share": slow_share,            # iterations given to slow host
        "makespan": makespans,               # virtual seconds per step
        "epochs": epochs,                    # measured-invocation trajectory
        "epoch_advances": epochs[-1] - epochs[0],
        "cache_invalidations": info.misses - 1,   # replans beyond the first
        "cache_hits": info.hits,
        "makespan_improvement": round(makespans[0] / makespans[-1], 3),
        "rebalanced": bool(slow_share[-1] < slow_share[0]),
        "wall_s": round(wall, 3),
    }


def auto_selection(n_iter: int = N_ITER, workers: int = WORKERS,
                   steps: int = STEPS, steady_k: int = 3) -> dict:
    """schedule(auto) vs every fixed candidate on the skewed executor.

    Each clause runs the same plan -> execute -> measure loop as the
    executor stage (fresh ``resolve()`` per step: selection state lives
    in the history, not the object); the figure of merit is the ratio of
    the best fixed clause's steady-state makespan to auto's."""
    import numpy as np
    from repro.core import (LoopHistory, LoopSpec, LoopTelemetry,
                            execute_plan, resolve)
    from repro.core.auto import DEFAULT_CANDIDATES
    from repro.core.engine import PlanEngine

    speeds = [1.0] * workers
    speeds[SLOW_WORKER] = SLOW_SPEED
    costs = np.ones(n_iter)
    loop = LoopSpec(0, n_iter, num_workers=workers, loop_id="auto_select")

    def drive(clause: str) -> dict:
        eng = PlanEngine()
        hist = LoopHistory()
        tel = LoopTelemetry(hist, loop_id=loop.loop_id, num_workers=workers)
        makespans, tags = [], []
        for _ in range(steps):
            sched = resolve(clause)
            plan = eng.plan(sched, loop, history=hist)
            res = execute_plan(plan, costs, speeds=speeds,
                               history=hist, telemetry=tel)
            makespans.append(round(res.makespan, 2))
            tags.append(getattr(sched, "history_tag", clause))
        return {"makespan": makespans, "selected": tags,
                "steady_makespan": round(
                    sum(makespans[-steady_k:]) / steady_k, 2)}

    fixed = {c: drive(c) for c in DEFAULT_CANDIDATES}
    auto = drive("auto")
    best_clause = min(fixed, key=lambda c: fixed[c]["steady_makespan"])
    best = fixed[best_clause]["steady_makespan"]
    ratio = round(best / max(auto["steady_makespan"], 1e-9), 3)
    return {
        "n_iter": n_iter,
        "workers": workers,
        "steps": steps,
        "slow_worker": SLOW_WORKER,
        "slow_speed": SLOW_SPEED,
        "fixed_steady": {c: fixed[c]["steady_makespan"] for c in fixed},
        "best_fixed": best_clause,
        "auto": auto,
        "auto_vs_best_fixed_ratio": ratio,
        "auto_ratio_gate": AUTO_RATIO_GATE,
    }


def serve_smoke(arch: str = "qwen2.5-3b", requests: int = 8,
                slots: int = 2, max_new: int = 4) -> dict:
    """Two real serve runs; the second plans from the first's telemetry."""
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.launch.serve import Request, ServeLoop

    cfg = get_smoke_config(arch)
    rng = np.random.default_rng(0)

    def make_requests():
        return [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            size=int(rng.integers(4, 12))
                                            ).astype(np.int32),
                        max_new=max_new)
                for i in range(requests)]

    loop = ServeLoop(cfg, slots=slots, scheduler="awf")
    t0 = time.perf_counter()
    out1 = loop.run(make_requests())
    cold_s = time.perf_counter() - t0
    epoch1 = loop.measured_epoch()
    t0 = time.perf_counter()
    out2 = loop.run(make_requests())
    warm_s = time.perf_counter() - t0
    toks = sum(len(v) for v in out2.values())
    return {
        "arch": arch,
        "slots": slots,
        "requests": requests,
        "completed": [len(out1), len(out2)],
        "cold_s": round(cold_s, 3),
        "warm_s": round(warm_s, 3),
        "tok_s": round(toks / warm_s, 2),
        "epochs": [epoch1, loop.measured_epoch()],
        "telemetry": loop.last_stats,
    }


def paged_concurrency(arch: str = "qwen2.5-3b",
                      requests: int = PAGED_REQUESTS) -> dict:
    """O(100)-way continuous batching through the paged-KV block pool.

    The *open* run sizes the pool above the trace's working set, so every
    request admits while blocks are free and occupancy climbs to the full
    trace — the concurrency a slot-count engine of the same memory could
    never reach.  The *pressured* run shrinks the pool far below the
    working set: decode growth must evict (LIFO) and readmit, and every
    request must STILL complete with its exact tokens (equivalence is
    locked in tests; the bench locks that the machinery engages under a
    realistic mixed trace).  tok/s and p99 admission latency come from
    the warm open run.
    """
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.launch.serve import PagedServeLoop, Request
    from repro.serve_mem import make_mixed_trace

    cfg = get_smoke_config(arch)
    trace = make_mixed_trace(requests, vocab_size=cfg.vocab_size, seed=3)

    def mk(n=None):
        return [Request(rid=t.rid, prompt=t.prompt.copy(),
                        max_new=t.max_new) for t in trace[:n]]

    open_loop = PagedServeLoop(cfg, num_blocks=512, block_size=8,
                               max_context=64, concurrency=128,
                               scheduler="guided,2", decode_steps=4,
                               prefill_chunk=16)
    open_loop.run(mk())                        # compile + warm
    t0 = time.perf_counter()
    out = open_loop.run(mk())
    wall = time.perf_counter() - t0
    toks = sum(len(v) for v in out.values())
    s = dict(open_loop.last_stats)
    open_rec = {
        "completed": len(out), "tokens": toks, "wall_s": round(wall, 3),
        "tok_s": round(toks / wall, 2),
        "peak_concurrency": s["peak_concurrency"],
        "peak_blocks_used": s["peak_blocks_used"],
        "kv_util_mean": s["kv_util_mean"],
        "preemptions": s["preemptions"],
        "prefill_compiles": s["prefill_compiles"],
        "queue_p50_s": round(s["queue_p50_s"], 4),
        "queue_p99_s": round(s["queue_p99_s"], 4),
        "admission_p50_s": round(s["admission_p50_s"], 4),
        "admission_p99_s": round(s["admission_p99_s"], 4),
    }

    tight_loop = PagedServeLoop(cfg, num_blocks=12, block_size=8,
                                max_context=64, concurrency=16,
                                scheduler="guided,2", decode_steps=4,
                                prefill_chunk=16)
    t0 = time.perf_counter()
    out_t = tight_loop.run(mk(16))
    wall_t = time.perf_counter() - t0
    st = dict(tight_loop.last_stats)
    pressured_rec = {
        "requests": 16, "num_blocks": 12, "completed": len(out_t),
        "wall_s": round(wall_t, 3),
        "preemptions": st["preemptions"],
        "failed_allocs": st["failed_allocs"],
        "kv_util_mean": st["kv_util_mean"],
        "peak_blocks_used": st["peak_blocks_used"],
    }
    return {
        "arch": arch,
        "requests": requests,
        "num_blocks": 512,
        "block_size": 8,
        "open": open_rec,
        "pressured": pressured_rec,
        "concurrency_gate": PAGED_CONCURRENCY_GATE,
        "tok_s_gate": PAGED_TOKS_GATE,
        "admission_p99_gate_s": PAGED_ADM_P99_GATE,
    }


def collect(skip_serve: bool = False) -> dict:
    record: dict = {"bench": "serve_adapt",
                    "executor": executor_steady_state(),
                    "auto": auto_selection()}
    if not skip_serve:
        record["serve"] = serve_smoke()
        record["paged"] = paged_concurrency()
    ex = record["executor"]
    au = record["auto"]
    checks = {
        "epoch_advanced": ex["epoch_advances"] >= 1,
        "replanned_from_measurements": ex["cache_invalidations"] >= 1,
        "rebalanced_off_slow_worker": ex["rebalanced"],
        "makespan_improved": ex["makespan_improvement"] > 1.0,
        "auto_ratio_gate": au["auto_vs_best_fixed_ratio"] >= AUTO_RATIO_GATE,
    }
    if not skip_serve:
        sv = record["serve"]
        checks["serve_measured_epochs"] = sv["epochs"][-1] >= 2
        checks["serve_completed_all"] = (sv["completed"]
                                         == [sv["requests"]] * 2)
        pg = record["paged"]
        checks["paged_completed_all"] = (
            pg["open"]["completed"] == pg["requests"]
            and pg["pressured"]["completed"] == pg["pressured"]["requests"])
        checks["paged_concurrency_gate"] = (
            pg["open"]["peak_concurrency"] >= PAGED_CONCURRENCY_GATE)
        checks["paged_tok_s_gate"] = pg["open"]["tok_s"] >= PAGED_TOKS_GATE
        checks["paged_admission_p99_gate"] = (
            pg["open"]["admission_p99_s"] <= PAGED_ADM_P99_GATE)
        checks["paged_preempted"] = pg["pressured"]["preemptions"] >= 1
    record["gate"] = {"checks": checks, "pass": all(checks.values())}
    return record


def rows(skip_serve: bool = True) -> list:
    """Harness contract: ``name,us_per_call,derived`` rows for run.py."""
    rec = collect(skip_serve=skip_serve)
    ex = rec["executor"]
    out = [("serve_adapt/executor", 0.0,
            f"epochs={ex['epoch_advances']};"
            f"share_slow={ex['slow_share'][0]}->{ex['slow_share'][-1]};"
            f"makespan_x={ex['makespan_improvement']}")]
    au = rec["auto"]
    out.append(("serve_adapt/auto", 0.0,
                f"ratio={au['auto_vs_best_fixed_ratio']};"
                f"best={au['best_fixed']};"
                f"selected={au['auto']['selected'][-1]}"))
    if "serve" in rec:
        sv = rec["serve"]
        out.append(("serve_adapt/serve", 0.0,
                    f"tok_s={sv['tok_s']};epochs={sv['epochs'][-1]}"))
    if "paged" in rec:
        pg = rec["paged"]
        out.append(("serve_adapt/paged", 0.0,
                    f"tok_s={pg['open']['tok_s']};"
                    f"peak_conc={pg['open']['peak_concurrency']};"
                    f"adm_p99_s={pg['open']['admission_p99_s']};"
                    f"preemptions={pg['pressured']['preemptions']}"))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", type=Path, default=None, metavar="PATH",
                    help="write the machine-readable record here "
                         "(CI: BENCH_serve.json)")
    ap.add_argument("--gate", action="store_true",
                    help="exit 1 unless the adaptive loop demonstrably "
                         "replanned from measured data")
    ap.add_argument("--skip-serve", action="store_true",
                    help="executor stage only (no JAX model)")
    args = ap.parse_args(argv)

    record = collect(skip_serve=args.skip_serve)
    ex = record["executor"]
    print(f"executor: slow-worker share {ex['slow_share'][0]} -> "
          f"{ex['slow_share'][-1]} iters, makespan "
          f"{ex['makespan'][0]} -> {ex['makespan'][-1]} "
          f"({ex['makespan_improvement']}x), "
          f"{ex['epoch_advances']} epoch advances, "
          f"{ex['cache_invalidations']} cache invalidations")
    au = record["auto"]
    print(f"auto: steady {au['auto']['steady_makespan']} vs best fixed "
          f"'{au['best_fixed']}' {au['fixed_steady'][au['best_fixed']]} -> "
          f"ratio {au['auto_vs_best_fixed_ratio']} "
          f"(gate >= {AUTO_RATIO_GATE}), selected "
          f"{au['auto']['selected'][0]} -> {au['auto']['selected'][-1]}")
    if "serve" in record:
        sv = record["serve"]
        print(f"serve: {sv['tok_s']} tok/s warm, epochs {sv['epochs']}, "
              f"imbalance {sv['telemetry'].get('imbalance')}")
    if "paged" in record:
        pg = record["paged"]
        op, pr = pg["open"], pg["pressured"]
        print(f"paged: {pg['requests']} requests, {op['tok_s']} tok/s warm, "
              f"peak concurrency {op['peak_concurrency']} "
              f"(gate >= {PAGED_CONCURRENCY_GATE}), admission p99 "
              f"{op['admission_p99_s']}s (gate <= {PAGED_ADM_P99_GATE}s), "
              f"kv util {op['kv_util_mean']}; pressured pool "
              f"({pr['num_blocks']} blocks): {pr['preemptions']} preemptions, "
              f"{pr['failed_allocs']} failed allocs, "
              f"{pr['completed']}/{pr['requests']} completed")
    status = "PASS" if record["gate"]["pass"] else "FAIL"
    print(f"# gate: {record['gate']['checks']} -> {status}")
    RESULTS.mkdir(exist_ok=True)
    (RESULTS / "serve_adapt.json").write_text(json.dumps(record, indent=1))
    if args.json is not None:
        args.json.write_text(json.dumps(record, indent=1))
        print(f"# wrote {args.json}")
    return 0 if (record["gate"]["pass"] or not args.gate) else 1


if __name__ == "__main__":
    sys.exit(main())
