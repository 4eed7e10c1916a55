"""The reduction from a device trace to busy time, program time, top ops
and idle gaps."""

import json

import pytest

from chipbench_testing import HERE
from chipbench import trace as tr

MS = 1_000_000


def _synthetic():
    """One device: prefill 0-10 ms, decode 14-20 ms (a while loop around
    two fusions), decode 30-34 ms with a 1 ms stall inside, in a window
    of 0-40 ms; the host plans during the first gap."""
    mods = [("jit_prefill_chunk(7)", 0, 10 * MS), ("jit_serve_step(9)", 14 * MS, 6 * MS),
            ("jit_serve_step(9)", 30 * MS, 4 * MS)]
    ops = [("%fusion.1 = bf16[8] fusion()", 0, 6 * MS), ("fusion.2", 6 * MS, 4 * MS),
           ("%while.5 = (s32[]) while()", 14 * MS, 6 * MS),
           ("fusion.1", 14 * MS, 2 * MS), ("fusion.1", 17 * MS, 3 * MS),
           ("copy.3", 30 * MS, 2 * MS), ("fusion.7", 33 * MS, 1 * MS)]
    host = [(tr.WINDOW, 0, 40 * MS), ("plan_chunks", 10 * MS, 3 * MS),
            ("PjitFunction(serve_step)", 13 * MS, 1 * MS)]
    return {"devices": {"/device:TPU:0": {"XLA Modules": mods, "XLA Ops": ops}},
            "host": host}


def test_synthetic_trace():
    r = tr.reduce(_synthetic())
    assert r["window_s"] == pytest.approx(0.040)
    assert r["busy_s"] == pytest.approx(0.019)
    assert r["program_s"] == pytest.approx({"prefill_chunk": 0.010, "serve_step": 0.010})
    assert r["program_calls"] == {"prefill_chunk": 1, "serve_step": 2}
    assert dict(r["device_ops"]) == pytest.approx({
        "prefill_chunk/fusion.1": 0.006, "prefill_chunk/fusion.2": 0.004,
        "serve_step/while.5": 0.001, "serve_step/fusion.1": 0.005,
        "serve_step/copy.3": 0.002, "serve_step/fusion.7": 0.001})
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    assert gaps["prefill_chunk -> serve_step | host: plan_chunks"] == pytest.approx(0.004)
    assert gaps["serve_step -> serve_step | host: none"] == pytest.approx(0.010)
    assert gaps["inside serve_step"] == pytest.approx(0.001)
    assert gaps["serve_step -> end | host: none"] == pytest.approx(0.006)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_overlapping_ops_count_once_and_window_clips():
    ev = _synthetic()
    ev["devices"]["/device:TPU:0"]["XLA Ops"].append(("fusion.9", 2 * MS, 2 * MS))
    r = tr.reduce(ev, 5 * MS, 35 * MS)
    assert r["window_s"] == pytest.approx(0.030)
    assert r["busy_s"] == pytest.approx(0.005 + 0.006 + 0.003)


def test_no_device_op_means_no_numbers():
    ev = _synthetic()
    ev["devices"] = {}
    assert tr.reduce(ev) is None


def test_program_names():
    assert tr.program_name("jit_serve_step(1234)") == "serve_step"
    assert tr.program_name("jit_prefill_chunk") == "prefill_chunk"


def test_recorded_chip_trace():
    """40 ms of a trace recorded on a TPU v5e while the qwen2.5-3b cell
    prefilled: two chunks, with the host reading back logits between."""
    ev = json.loads((HERE / "data" / "trace_slice.json").read_text())
    r = tr.reduce(ev)
    assert r is not None and r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.040)
    assert r["busy_s"] == pytest.approx(0.036007056)
    assert r["program_calls"]["prefill_chunk"] == 2
    assert r["device_ops"][0][0].startswith("prefill_chunk/fusion")
    assert r["idle_gaps"][0][0] == "prefill_chunk -> convert_element_type | host: XlaDelinearize"
    assert sum(r["program_s"].values()) <= r["window_s"] + 1e-9
    assert sum(v for _, v in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] + 1e-9
