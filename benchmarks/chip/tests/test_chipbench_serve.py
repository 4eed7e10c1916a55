"""One wave of each serving mix through ``PagedServeLoop`` on the smoke
twins, driven by the harness on the CPU (the chip check is skipped)."""

import numpy as np
import pytest

from chipbench_testing import SMOKE_CELLS, run_smoke, smoke_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return smoke_root(tmp_path_factory.mktemp("serve"))


@pytest.mark.parametrize("cell", sorted(SMOKE_CELLS))
def test_one_wave_is_served_and_correct(root, cell):
    out, rec = run_smoke(root, cell, seed=2**40 + 17)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 32 and out["failed"] == 0
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert set(out["metrics"]) == {"serve_tok_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert rec.window_compiles == 0
    assert all(len(r["generated"]) == r["max_new"] for r in rec.requests)
    assert out["checks"]["checked_tokens"]["value"] >= 300


def test_kv_pressure_preempts_and_the_check_covers_it(root):
    out, rec = run_smoke(root, "phi3-smoke.longanswer", seed=3)
    assert sum(r["preemptions"] for r in rec.requests) > 0
    assert rec.check["checked_preempted"] >= 1
    assert out["correct"], out["checks"]


def test_per_layer_metrics_from_counters(root):
    """--trace 1 on the CPU: counter metrics are read; no TPU plane, so
    the device-trace metrics are left out rather than reported as 0."""
    out, _ = run_smoke(root, "phi3-smoke.longanswer", seed=4, trace=True)
    m = out["metrics"]
    assert {"decode_rows.serve", "preempt_per_req.serve", "mfu.serve"} <= set(m)
    assert not {"prefill_roofline", "decode_roofline", "idle_share.serve"} & set(m)
    assert 1 <= m["decode_rows.serve"]["value"] <= 32
    assert "breakdown" not in out and np.isfinite(m["mfu.serve"]["value"])


def test_trace_covers_the_whole_first_wave(root):
    """The traced calls are every dispatch of the first wave: each served
    token but the one that ends a (re-)prefill was made by a traced
    decode call, and every prompt token was prefilled in a traced call."""
    out, rec = run_smoke(root, "phi3-smoke.longanswer", seed=6, trace=True)
    calls = [c for c in rec.traced_calls if c["program"] == "serve_step"]
    first = [r for r in rec.requests if r["wave"] == 0]
    made = sum(k for c in calls for _, k in c["rows"])
    assert len(calls) == sum(d["wave"] == 0 for d in rec.dispatches)
    assert made == sum(len(r["generated"]) - 1 - r["preemptions"] for r in first)
    assert sum(c["length"] for c in rec.traced_calls
               if c["program"] == "prefill_chunk") >= sum(r["prompt"].size for r in first)


def test_traced_calls_record_work_and_end_the_trace_on_time():
    """The proxy records each call's asked-for work while the trace is on,
    stops the trace at the first call past its cap once both programs
    have run, and then hands the loop its own programs back."""
    from types import SimpleNamespace
    from chipbench import manifest
    engine = manifest.module("engines", "paged_serve")
    prefill = lambda p, b, c, t, start, length: ("logits", c)  # noqa: E731
    decode = lambda p, b, c, t, fill, lim, mask, rem, eos: (  # noqa: E731
        None, c, None, None, np.maximum(rem - 4, 0))
    loop = SimpleNamespace(_prefill_step=prefill, _decode=decode)
    stops = []
    watch = engine.TracedCalls(loop, 0.0, lambda: stops.append(1))
    # past the deadline, the trace runs on until both programs have run
    loop._prefill_step(0, {}, "pool", None, np.int32(512), np.int32(100))
    loop._decode(0, {}, "pool", None, np.array([612, 0, 700]), None,
                 np.array([True, False, True]), np.array([9, 0, 2]), -1)
    assert watch.calls == [
        {"program": "prefill_chunk", "start": 512, "length": 100},
        {"program": "serve_step", "rows": [(612, 4), (700, 2)]}]
    loop._decode(0, {}, "pool", None, np.array([1]), None, np.array([True]),
                 np.array([3]), -1)
    assert stops == [1] and len(watch.calls) == 2
    assert loop._decode is decode and loop._prefill_step is prefill
    watch.close()
    assert stops == [1]
