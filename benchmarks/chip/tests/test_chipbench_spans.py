"""The serving program's spans and counters, and what the benchmark reads
from them: the loop's ``serve.*`` spans in a CPU trace, its logs against
the harness's call records, span-labelled idle gaps, and the four readers
on a small slice recorded on a TPU v5e."""

import json
import math
from collections import Counter
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from chipbench_testing import BENCH, HERE
from chipbench import harness, manifest, spans, trace as tr

MS = 1_000_000
SPAN_NAMES = {"serve.run", "serve.turn", "serve.admit", "serve.plan", "serve.prefill",
              "serve.grow", "serve.preempt", "serve.decode", "serve.wait"}


def parents(sp):
    """Index of each span's parent: the innermost span it lies in."""
    out, stack = [], []
    for i, s in enumerate(sp):
        while stack and sp[stack[-1]]["end"] < s["end"]:
            stack.pop()
        out.append(stack[-1] if stack else None)
        stack.append(i)
    return out


def _loop(num_blocks: int):
    from repro.configs import get_smoke_config
    from repro.launch.serve import PagedServeLoop
    return PagedServeLoop(get_smoke_config("qwen2.5-3b"), num_blocks=num_blocks,
                          block_size=8, max_context=64, concurrency=4,
                          decode_steps=2, prefill_chunk=16, scheduler="static")


def _requests(n=6):
    from repro.launch.serve import Request
    rng = np.random.default_rng(7)
    return [Request(rid=100 + i, prompt=rng.integers(0, 500, size=int(rng.integers(8, 40))
                                                     ).astype(np.int32), max_new=12)
            for i in range(n)]


@pytest.fixture(scope="module")
def traced_loop(tmp_path_factory):
    """A pool small enough that decode growth preempts, run once under
    the profiler; the loop, its requests and the trace's events."""
    loop, reqs = _loop(num_blocks=10), _requests()
    loop.run(_requests())                     # compile outside the trace
    d = tmp_path_factory.mktemp("prof")
    with jax.profiler.trace(str(d)):
        loop.run(reqs)
    return loop, reqs, d


def test_loop_spans_nest_and_carry_the_logs(traced_loop):
    loop, reqs, d = traced_loop
    names = {e[0] for e in tr.extract(d)["host"]}
    assert SPAN_NAMES <= names
    ev = spans.extract(d, devices=False)
    sp = spans.spans(ev, 0, 2**62)
    assert {s["name"] for s in sp} == SPAN_NAMES
    par = parents(sp)
    want = {"serve.run": {None}, "serve.turn": {"serve.run"},
            "serve.admit": {"serve.turn"}, "serve.plan": {"serve.admit"},
            "serve.prefill": {"serve.turn"}, "serve.grow": {"serve.turn"},
            "serve.preempt": {"serve.grow"}, "serve.decode": {"serve.turn"},
            "serve.wait": {"serve.prefill", "serve.decode"}}
    for s, p in zip(sp, par):
        assert (sp[p]["name"] if p is not None else None) in want[s["name"]], s
    rids = {r.rid for r in reqs}
    for s in sp:
        if s["name"] in ("serve.admit", "serve.plan", "serve.prefill", "serve.preempt"):
            assert s["meta"]["rid"] in rids
    assert Counter(s["name"] for s in sp)["serve.preempt"] == sum(r.preemptions for r in reqs) > 0
    assert sp[0]["meta"] == {"requests": len(reqs)}
    turns = [s["meta"]["turn"] for s in sp if s["name"] == "serve.turn"]
    assert turns == list(range(len(turns)))
    chunks = [{k: s["meta"][k] for k in ("rid", "start", "length", "bucket")}
              for s in sp if s["name"] == "serve.prefill"]
    assert chunks == loop.prefill_log
    plans = [s["meta"] for s in sp if s["name"] == "serve.plan"]
    assert sum(p["chunks"] for p in plans) == len(loop.prefill_log)
    decodes = [s["meta"] for s in sp if s["name"] == "serve.decode"]
    assert decodes == [{"dispatch": e["dispatch"], "rows": e["rows"]}
                       for e in loop.dispatch_log]
    waits = Counter(s["meta"]["program"] for s in sp if s["name"] == "serve.wait")
    assert waits == {"prefill_chunk": len(loop.prefill_log),
                     "serve_step": len(loop.dispatch_log)}


def test_logs_equal_the_harness_call_records():
    """The loop's own logs hold what the harness's wrapper reads back from
    the device on the same run (``TracedCalls``)."""
    engine = manifest.module("engines", "paged_serve", BENCH)
    loop, reqs = _loop(num_blocks=10), _requests()
    watch = engine.TracedCalls(loop, math.inf, lambda: None)
    loop.run(reqs)
    watch.close()
    assert sum(r.preemptions for r in reqs) > 0
    pre = [c for c in watch.calls if c["program"] == "prefill_chunk"]
    dec = [c for c in watch.calls if c["program"] == "serve_step"]
    assert [(c["start"], c["length"]) for c in pre] == [
        (e["start"], e["length"]) for e in loop.prefill_log]
    assert [c["rows"] for c in dec] == [list(zip(e["fills"], e["made"]))
                                        for e in loop.dispatch_log]


def _gap_case():
    """Prefill 0-10 ms, decode 20-30 ms; the host waits 10-13 ms, then
    plans inside an admission 13-19 ms, all inside one turn."""
    mods = [("jit_prefill_chunk(1)", 0, 10 * MS), ("jit_serve_step(2)", 20 * MS, 10 * MS)]
    sp = [{"name": "serve.run", "meta": {}, "start": 0, "end": 40 * MS},
          {"name": "serve.turn", "meta": {}, "start": 0, "end": 31 * MS},
          {"name": "serve.prefill", "meta": {}, "start": 0, "end": 13 * MS},
          {"name": "serve.wait", "meta": {"program": "prefill_chunk"},
           "start": 1 * MS, "end": 13 * MS},
          {"name": "serve.admit", "meta": {}, "start": 13 * MS, "end": 19 * MS},
          {"name": "serve.plan", "meta": {}, "start": 14 * MS, "end": 18 * MS}]
    host = [("np.asarray(jax.Array)", 10 * MS, 3 * MS)]
    return mods, sp, host


def test_gaps_take_the_innermost_span():
    mods, sp, host = _gap_case()
    got = list(spans.gaps([(10 * MS, 13 * MS), (13 * MS, 20 * MS), (30 * MS, 40 * MS)],
                          mods, host, sp))
    assert got == [
        ("prefill_chunk -> serve_step | span: serve.wait(prefill_chunk)", pytest.approx(0.003)),
        ("prefill_chunk -> serve_step | span: serve.plan", pytest.approx(0.007)),
        ("serve_step -> end | host: none", pytest.approx(0.010))]


def test_gaps_never_take_the_turn_or_the_run():
    mods, sp, host = _gap_case()
    outer = [s for s in sp if s["name"] in spans.OUTER]
    hosted = host + [(s["name"], s["start"], s["end"] - s["start"]) for s in outer]
    got = list(spans.gaps([(13 * MS, 20 * MS)], mods, hosted, outer))
    assert got == [("prefill_chunk -> serve_step | host: none", pytest.approx(0.007))]


def test_gaps_without_spans_keep_the_trace_labels():
    mods, _, host = _gap_case()
    iv = [(10 * MS, 13 * MS), (13 * MS, 20 * MS), (30 * MS, 40 * MS), (4 * MS, 5 * MS)]
    assert list(spans.gaps(iv, mods, host, [])) == list(tr._gaps(iv, mods, host))


XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 7000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 8000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8] fusion()"
    stats { metadata_id: 10 str_value: "jit(serve_step)/while/body/closed_call/kv_gather/gather" }
    stats { metadata_id: 12 int64_value: 7 } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = bf16[8] fusion()"
    stats { metadata_id: 10 ref_value: 11 } } }
  event_metadata { key: 3 value { id: 3 name: "jit_serve_step(9)" } }
  event_metadata { key: 4 value { id: 4 name: "%copy.3 = bf16[8] copy()" } }
  stat_metadata { key: 10 value { id: 10 name: "tf_op" } }
  stat_metadata { key: 11 value { id: 11 name: "jit(serve_step)/while/body/mlp/dot_general" } }
  stat_metadata { key: 12 value { id: 12 name: "flops" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000
             stats { metadata_id: 1 int64_value: 4 } } }
  event_metadata { key: 1 value { id: 1 name: "serve.turn" } }
  stat_metadata { key: 1 value { id: 1 name: "turn" } } }
"""


def test_device_ops_carry_their_scope_path(tmp_path):
    """An operation's op_name path sits in its event metadata, which the
    extraction reads from the ``.xplane.pb`` itself; a span's metadata is
    its own stats."""
    from jax.profiler import ProfileData
    (tmp_path / "t.xplane.pb").write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    ev = spans.extract(tmp_path)
    ops = ev["devices"]["/device:TPU:0"]["XLA Ops"]
    assert [(o[0].split(" ")[0], o[3]) for o in ops] == [
        ("%fusion.1", "jit(serve_step)/while/body/closed_call/kv_gather/gather"),
        ("%fusion.2", "jit(serve_step)/while/body/mlp/dot_general"), ("%copy.3", "")]
    assert [spans.scope_of(o[3]) for o in ops] == ["kv_gather", "mlp", None]
    assert spans.scope_of("jit(prefill_chunk)/while/body/closed_call/attention/sub:") == "attention"
    assert spans.scope_of("jit(serve_step)/head:") == "head"
    assert ev["host"] == [("serve.turn", 1000, 9000, {"turn": 4})]
    assert ev["devices"]["/device:TPU:0"]["XLA Modules"] == [("jit_serve_step(9)", 1000, 8000)]
    r = spans.reduce(ev, 1000, 10000)
    assert r["scope_s"] == {"serve_step": pytest.approx({"kv_gather": 5e-6, "mlp": 1e-6,
                                                         "-": 1e-6})}
    assert r["device_ops"][0] == ["serve_step/kv_gather/fusion.1", pytest.approx(5e-6)]


def test_readers_find_nothing_in_a_program_without_spans(tmp_path, monkeypatch):
    """A program that writes no spans and logs no fills (the parent of
    this instrumentation) leaves the four metrics out."""
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    d = jax.jit(lambda x: x + 1)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            d(np.ones(3)).block_until_ready()
    rec = SimpleNamespace(traced_calls=[{"program": "serve_step", "rows": []}],
                          dispatches=[{"dispatch": 0, "rows": 3, "tokens": 9}])
    for name in ("prefill_pad_share.serve", "decode_ctx_share.serve",
                 "plan_us_per_admit.serve", "host_ms_per_turn.serve"):
        assert manifest.module("metrics", name, BENCH).read(rec) is None


def test_readers_on_a_traced_smoke_wave(tmp_path, monkeypatch):
    """The four readers on one traced wave of the smoke twin: the two
    shares equal counts made from the traffic and the harness's call
    records; the two host times are positive and within the turns."""
    from chipbench_testing import run_smoke, smoke_root
    from repro.launch.serve import bucket_length, plan_prefill_chunks
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    out, rec = run_smoke(smoke_root(tmp_path), "qwen-smoke.longprompt", seed=2**35 + 3,
                         trace=True)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    sv = rec.serve
    chunks = [n for r in rec.requests
              for n in plan_prefill_chunks(sv["scheduler"], int(r["prompt"].size),
                                           max_chunk=sv["prefill_chunk"])]
    buckets = [bucket_length(n, sv["prefill_chunk"]) for n in chunks]
    assert m["prefill_pad_share.serve"] == pytest.approx(
        100 * (sum(buckets) - sum(chunks)) / sum(buckets))
    calls = [c for c in rec.traced_calls if c["program"] == "serve_step"]
    needed = sum(f + j + 1 for c in calls for f, k in c["rows"] for j in range(k))
    read = len(calls) * sv["concurrency"] * sv["max_context"] * sv["decode_steps"]
    assert m["decode_ctx_share.serve"] == pytest.approx(100 * needed / read)
    turns = [s["end"] - s["start"] for s in rec.serve_spans if s["name"] == "serve.turn"]
    assert 0 < m["host_ms_per_turn.serve"] < max(turns) / 1e6
    assert m["plan_us_per_admit.serve"] > 0


def _slice():
    """66 ms of the qwen2.5-3b cell's traced first wave on a TPU v5e: a
    prefill program's end and read-back, the next turn's admission, plan
    and whole prefill program.  Op paths are kept once in ``paths``."""
    raw = json.loads((HERE / "data" / "trace_slice_spans.json").read_text())
    devices = {p: {"XLA Modules": [tuple(m) for m in lines["XLA Modules"]],
                   "XLA Ops": [(n, a, d, raw["paths"][k]) for n, a, d, k in lines["XLA Ops"]]}
               for p, lines in raw["devices"].items()}
    return {"devices": devices, "host": [tuple(h) for h in raw["host"]]}


def test_recorded_spans_share_the_device_clock():
    """Each prefill program of the recorded slice lies inside the
    ``serve.prefill`` span whose call launched it: host spans and device
    operations are on one clock, aligned to about a millisecond (the
    device plane shows the program starting 0.88 ms before the host's
    call to it)."""
    ev = _slice()
    t0, t1 = spans.window(ev)
    sp = spans.spans(ev, t0, t1)
    mods = [m for m in ev["devices"]["/device:TPU:0"]["XLA Modules"]
            if tr.program_name(m[0]) == "prefill_chunk" and t0 <= m[1] and m[1] + m[2] <= t1]
    prefills = [s for s in sp if s["name"] == "serve.prefill"]
    assert len(mods) == 1 and len(prefills) == 2
    launcher = [p for p in prefills if p["start"] < mods[0][1] < p["end"]]
    assert len(launcher) == 1 and mods[0][1] + mods[0][2] < launcher[0]["end"]
    call = next(h for h in ev["host"] if h[0] == "PjitFunction(prefill_chunk)"
                and launcher[0]["start"] < h[1] < launcher[0]["end"])
    assert abs(mods[0][1] - call[1]) < 1_000_000


def test_recorded_slice_reduces_with_span_labels():
    ev = _slice()
    r, base = spans.reduce(ev), tr.reduce(spans.plain(ev))
    for key in ("window_s", "busy_s", "devices", "program_s", "program_calls"):
        assert r[key] == base[key]
    assert r["program_calls"]["prefill_chunk"] == 2
    gaps = dict(spans.reduce(ev, top=100)["idle_gaps"])
    between = sum(v for k, v in gaps.items() if not k.startswith("inside "))
    assert sum(v for k, v in gaps.items() if " | span: " in k) >= 0.9 * between > 0
    assert not any("serve.turn" in k or "serve.run" in k for k in gaps)


def test_recorded_slice_splits_device_time_by_scope():
    """The prefill program's device time falls in its named scopes, except
    what XLA adds around the layer scan (the pool copies, the per-layer
    slices and updates of the stacked pool), which carries none."""
    r = spans.reduce(_slice(), top=50)
    scopes = r["scope_s"]["prefill_chunk"]
    assert set(scopes) <= set(spans.SCOPES) | {"-"}
    assert {"qkv", "attention", "attn_out", "mlp", "kv_append", "head"} <= set(scopes)
    assert sum(scopes.values()) == pytest.approx(r["program_s"]["prefill_chunk"], rel=1e-3)
    ops = dict(r["device_ops"])
    assert ops["prefill_chunk/-/copy.64"] > ops["prefill_chunk/attention/fusion.175"] > 0


def test_readers_on_the_recorded_slice():
    ev = _slice()
    sp = spans.spans(ev, *spans.window(ev))
    rec = SimpleNamespace(serve_spans=sp, traced_calls=[{}], dispatches=[])
    read = {name: manifest.module("metrics", name, BENCH).read(rec)
            for name in ("prefill_pad_share.serve", "plan_us_per_admit.serve",
                         "host_ms_per_turn.serve", "decode_ctx_share.serve")}
    pre = [s["meta"] for s in sp if s["name"] == "serve.prefill"]
    assert read["prefill_pad_share.serve"] == pytest.approx(
        100 * sum(p["bucket"] - p["length"] for p in pre) / sum(p["bucket"] for p in pre))
    plans = [s["end"] - s["start"] for s in sp if s["name"] == "serve.plan"]
    assert read["plan_us_per_admit.serve"] == pytest.approx(sum(plans) / len(plans) / 1e3)
    turn = next(s for s in sp if s["name"] == "serve.turn")
    assert 0 < read["host_ms_per_turn.serve"] < (turn["end"] - turn["start"]) / 1e6
    assert read["decode_ctx_share.serve"] is None
