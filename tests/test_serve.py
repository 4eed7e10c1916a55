"""Serving-engine tests: the per-slot engine, its telemetry, and which
engine each model family serves through.

The per-slot ``ServeLoop`` (one jitted call per active slot per token over
batch-1 caches) is the engine of the ``ssm``/``hybrid`` families and the
token reference the paged engine is tested against (``tests/test_paged.py``).
Its admission semantics are the paper's: slots are workers, requests are
iterations, and the UDS decides the chunk→slot assignment.  Locked here:
truncation and refusal at the cache ceiling, partial-team drain, prefill
bucketing (prompts right-padded to power-of-two buckets must not change
tokens and must bound compile count by buckets, not distinct prompt
lengths), the measure stage (per-slot busy times flushed into the
LoopHistory so AWF admission replans per slot), and the serve CLI's choice
of engine by what the family implements.
"""

import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import LoopHistory, LoopSpec, get_engine
from repro.core.spec import resolve
from repro.launch.serve import PagedServeLoop, Request, ServeLoop, engine_for

SLOTS = 3
MAX_LEN = 64
MAX_NEW = 3
N_REQUESTS = 6


def make_requests(seed: int, n: int = N_REQUESTS, max_new: int = MAX_NEW):
    rng = np.random.default_rng(seed)
    cfg = get_smoke_config("qwen2.5-3b")
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=int(rng.integers(4, 12))
                                        ).astype(np.int32),
                    max_new=max_new)
            for i in range(n)]


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("qwen2.5-3b")


# one loop shared across schedule families (compile once); scheduler and
# history are swapped per run
@pytest.fixture(scope="module")
def per_slot_loop(cfg):
    return ServeLoop(cfg, slots=SLOTS, max_len=MAX_LEN)


def run_with(loop: ServeLoop, scheduler, seed: int):
    """Run one isolated invocation: fresh history (no adaptive carry-over
    between parametrized cases), returning (results, chunk assignments)."""
    loop.scheduler = scheduler
    loop.history = LoopHistory()
    out = loop.run(make_requests(seed))
    chunks = sorted((c.worker, c.start, c.stop)
                    for c in loop.history.invocations(loop.loop_id)[-1].chunks)
    return out, chunks


# ------------------------------------------------------------ engine choice
def test_ssm_family_falls_back_to_per_slot():
    """rwkv6 has no paged-KV decode (its state is recurrent, not KV
    blocks): the SSM family serves per-slot, unbucketed, to completion."""
    from repro.models import get_model
    cfg = get_smoke_config("rwkv6-3b")
    assert get_model(cfg).paged_decode is None
    assert engine_for(cfg) is ServeLoop
    loop = ServeLoop(cfg, slots=2, max_len=MAX_LEN)
    assert not loop._bucketed           # pad tokens would enter its state
    rng = np.random.default_rng(4)
    out = loop.run([Request(rid=i, max_new=MAX_NEW,
                            prompt=rng.integers(0, cfg.vocab_size, size=6
                                                ).astype(np.int32))
                    for i in range(3)])
    assert sorted(out) == [0, 1, 2]
    assert all(len(v) == MAX_NEW for v in out.values())


ENGINES = {"qwen2.5-3b": PagedServeLoop, "qwen3-moe-235b-a22b": PagedServeLoop,
           "rwkv6-3b": ServeLoop, "zamba2-2.7b": ServeLoop}


@pytest.mark.parametrize("arch", ENGINES)
def test_serve_cli_picks_engine_by_family(arch, monkeypatch):
    """With no flag, the serve CLI serves attention families through the
    paged engine and the recurrent ones per slot, and every request of
    the run gets its budget."""
    from repro.launch import serve
    # the CLI's persistent compile cache would stay on for later tests
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    engine = ENGINES[arch]
    assert engine_for(get_smoke_config(arch)) is engine
    loop = serve.main(["--arch", arch, "--smoke", "--requests", "2",
                       "--max-new", "2", "--max-concurrency", "2"])
    assert type(loop) is engine
    assert loop.last_stats["decoded_tokens"] == 2 * (2 - 1)


def test_over_capacity_request_is_truncated_and_reported(per_slot_loop):
    """prompt + max_new beyond max_len is admitted with the generation
    budget clamped to cache capacity, and the truncation is REPORTED per
    request — never silently padded (dropped KV appends would corrupt the
    generation) and never refused (the request is serveable)."""
    prompt = np.arange(MAX_LEN - 2, dtype=np.int32) % 16      # capacity 3
    per_slot_loop.scheduler = "dynamic"
    per_slot_loop.history = LoopHistory()
    reqs = [Request(rid=0, prompt=prompt, max_new=8)]
    out = per_slot_loop.run(reqs)
    assert len(out[0]) == MAX_LEN - len(prompt) + 1            # clamped
    assert reqs[0].truncated
    assert per_slot_loop.last_stats["truncated"] == [0]
    assert -1 not in out[0]                    # no frozen-step padding


def test_prompt_alone_over_max_len_is_refused(per_slot_loop):
    """A prompt that cannot even fit the cache is not serveable at any
    budget: refuse loudly instead of truncating the PROMPT."""
    prompt = np.arange(MAX_LEN + 1, dtype=np.int32) % 16
    per_slot_loop.scheduler = "dynamic"
    per_slot_loop.history = LoopHistory()
    with pytest.raises(ValueError, match="max_len"):
        per_slot_loop.run([Request(rid=0, prompt=prompt, max_new=2)])


# ------------------------------------------------------- prefill bucketing
def test_bucket_length():
    from repro.launch.serve import MIN_PREFILL_BUCKET, bucket_length
    assert bucket_length(1, 64) == MIN_PREFILL_BUCKET
    assert bucket_length(8, 64) == 8
    assert bucket_length(9, 64) == 16
    assert bucket_length(33, 64) == 64
    assert bucket_length(60, 64) == 64        # capped at max_len


def test_prefill_compiles_once_per_bucket(cfg):
    """Mixed prompt lengths must not recompile prefill per length: one
    compiled program per power-of-two bucket (the admission-latency fix).
    Lengths 4..12 span buckets {8, 16} -> exactly 2 compilations."""
    loop = ServeLoop(cfg, slots=SLOTS, max_len=MAX_LEN)
    reqs = make_requests(11, n=8)              # lengths in [4, 12)
    lengths = {int(r.prompt.size) for r in reqs}
    assert len(lengths) > 2                    # the test needs mixed lengths
    out = loop.run(reqs)
    assert sorted(out) == list(range(8))
    from repro.launch.serve import bucket_length
    buckets = {bucket_length(n, MAX_LEN) for n in lengths}
    assert loop.prefill_compiles == len(buckets) < len(lengths)


def test_partial_team_drain(per_slot_loop):
    """More slots than requests at the tail: a partially-filled team
    drains without corrupting idle slots."""
    out, _ = run_with(per_slot_loop, "dynamic", seed=7)
    assert sorted(out) == list(range(N_REQUESTS))
    assert all(len(v) == MAX_NEW for v in out.values())


# --------------------------------------------------------------- telemetry
def test_batched_busy_times_bump_epoch_and_replan(cfg):
    """The measure stage: each run flushes per-slot busy times into the
    history (epoch bump), and the bumped epoch invalidates the engine's
    cached adaptive plan, so AWF admission replans from the measured
    data."""
    loop = ServeLoop(cfg, slots=2, max_len=MAX_LEN, scheduler="awf")
    assert loop.measured_epoch() == 0
    out1 = loop.run(make_requests(0))
    assert sorted(out1) == list(range(N_REQUESTS))
    assert loop.measured_epoch() == 1

    # per-slot attribution is intact: every slot that served a chunk has
    # positive measured busy time and generated-token credit
    per_worker = loop.last_stats["per_worker"]
    served = [w for w, st in per_worker.items() if st["chunks"] > 0]
    assert served
    assert all(per_worker[w]["time_s"] > 0 for w in served)
    assert all(per_worker[w]["tokens"] > 0 for w in served)
    rates = loop.history.worker_rates(loop.loop_id)
    assert rates and all(r > 0 for r in rates.values())

    # epoch is the adaptive plan-cache key: the same (scheduler, loop)
    # query before and after the next flush must be a fresh plan object
    spec = LoopSpec(0, N_REQUESTS, num_workers=2, loop_id=loop.loop_id)
    plan1 = get_engine().plan(resolve("awf"), spec, history=loop.history)
    out2 = loop.run(make_requests(1))
    assert sorted(out2) == list(range(N_REQUESTS))
    assert loop.measured_epoch() == 2
    plan2 = get_engine().plan(resolve("awf"), spec, history=loop.history)
    assert plan1 is not plan2          # cache invalidated -> replanned


def test_serve_cli_refuses_a_kill_without_the_paged_engine(monkeypatch):
    """An injected row kill is a paged-engine fault; asked of a family
    that serves per slot, the CLI refuses it instead of ignoring it."""
    from repro.launch import serve
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    with pytest.raises(SystemExit):
        serve.main(["--arch", "rwkv6-3b", "--smoke", "--kill-rows", "1",
                    "--kill-at-dispatch", "1"])
