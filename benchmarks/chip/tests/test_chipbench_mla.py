"""The DeepSeek-V3 share's cell: the work counts by hand, the engine's
mapping of the configuration file and its early stop on a program
without latent attention, the manifest's lists, and the smoke twin's
cell driven by the harness on the CPU: served and judged correct, the
float8 control and a planted fault judged not correct.

The twin (``data/deepseek-v3-smoke.json``) has 1 dense and 2 expert
layers and holds experts 4-7 of 8 in 2 groups of 4 (1 kept), top-2, with
one shared expert.  Its limit (0.02) was set from CPU readings of seeds
1, 2, 3 and 5: the program's widest gap 0.0022-0.0120, the control's
0.029-0.058."""

import json
import time

import jax
import pytest

from chipbench_testing import BENCH, FAKE_PEAK, smoke_root
from chipbench import harness, manifest, work_mla, work_moe

CELL = "mla-smoke.longanswer"
REAL = "deepseek-v3.longdoc"
MAN = json.loads((BENCH.parents[1] / "BENCHMARK.json").read_text())
CFG = manifest.config(MAN, "deepseek-v3", BENCH.parents[1])


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A root naming the smoke cells and the MLA twin's cell (under the
    short-prompt longanswer mix), reported wherever the real cell is."""
    tmp = smoke_root(tmp_path_factory.mktemp("mla"))
    man = json.loads((tmp / "BENCHMARK.json").read_text())
    data = json.loads((BENCH / "tests" / "data" / "deepseek-v3-smoke.json").read_text())
    path = tmp / "deepseek-v3-smoke.json"
    path.write_text(json.dumps(data))
    man["configs"].append({"name": data["name"], "source": data["source"], "file": str(path),
                           "reduced": data["reduced"], "why": "smoke twin"})
    man["workloads"].append({"name": CELL, "config": data["name"], "traffic": "longanswer",
                             "chips": 1, "why": "CPU test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(CELL)
    (tmp / "BENCHMARK.json").write_text(json.dumps(man))
    return tmp


def _run(root, seed, trace=False, control=None):
    return harness.run_cell(CELL, seed=seed, seconds=0, trace=trace,
                            devices=jax.devices()[:1], peak=FAKE_PEAK,
                            t_start=time.perf_counter(), root=root, smoke=True,
                            control=control)


def test_work_counts_by_hand():
    s = work_mla.Shapes.of(CFG)
    assert (s.L0, s.L1, s.G, s.E) == (3, 4, 8, 256)
    assert work_mla.attention_matmul_params(s) == 187_105_280
    assert work_mla.dense_layer_params(s) == 583_483_392
    assert work_mla.moe_layer_params(s) == 585_318_656
    assert work_mla.param_count(s) == 5_945_090_048
    engine = manifest.module("engines", "paged_serve_mla")
    assert engine.program_config(CFG).param_count() == work_mla.param_count(s)
    assert work_mla.pair_flops(s) == 81_920
    assert work_mla.latent_bytes(s) == 1_152
    assert work_mla.query_bytes(s) == 81_920
    t = work_mla.Shapes(L0=1, L1=1, d=2, H=1, ql=1, kl=2, nope=1, rope=1, vd=2,
                        F=3, f=1, E=4, G=2, ns=1, V=5)
    # projections 2 + 2 + 6 + 6 + 4 per layer; dense 18; router 8, shared 6
    assert work_mla.token_matmul_params(t) == 2 * 20 + 18 + 8 + 6
    assert work_mla.pair_flops(t) == 8
    # a 2-token prompt (1 + 2 keys), 2 answer tokens after the first
    # (3 + 4 keys): 4 tokens of 2 * 72, 10 pairs of 8 in 2 layers, 3 heads
    assert work_mla.request_flops(t, 2, 3) == 4 * 144 + 10 * 8 * 2 + 3 * 20
    # rows (fill 3, made 2): 4 + 5 keys; (fill 0, made 1): 1 key
    dec = work_mla.decode_attention(t, [(3, 2), (0, 1)])
    assert dec == {"flops": 10 * 8 * 2, "bytes": 2 * (10 * 6 + 3 * 8)}
    # a chunk of 3 from 4: 5 + 6 + 7 keys, the latents below 7 read once
    assert work_mla.prefill_attention(t, 4, 3) == {
        "flops": 18 * 8 * 2, "bytes": 2 * (7 * 6 + 3 * 8)}
    e = work_mla.expert_shapes(s)
    assert work_moe.expert_params(e) == 3 * 7168 * 2048 and e.G == 8


def test_engine_maps_the_configuration():
    engine = manifest.module("engines", "paged_serve_mla")
    cfg = engine.program_config(CFG)
    assert (cfg.num_layers, cfg.first_dense_layers, cfg.moe_layers) == (7, 3, 4)
    assert (cfg.d_ff, cfg.dense_d_ff, cfg.shared_experts) == (2048, 18432, 1)
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_offset) == (256, 8, 0)
    assert (cfg.router, cfg.router_groups, cfg.router_groups_kept,
            cfg.routed_scaling, cfg.experts_per_token) == ("sigmoid", 8, 4, 2.5, 8)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.head_dim) == (
                1536, 512, 128, 64, 128, 192)
    assert (cfg.rope_theta, cfg.rope_factor, cfg.rope_original_max,
            cfg.yarn_beta_fast, cfg.yarn_beta_slow) == (10000, 40, 4096, 32, 1)
    assert cfg.rms_norm_eps == 1e-6 and not cfg.tie_embeddings
    with pytest.raises(ValueError, match="noaux_tc"):
        engine.wanted(dict(CFG, topk_method="greedy"))


def test_engine_stops_before_drawing_weights_without_mla_fields(monkeypatch):
    """A program whose ModelConfig has no latent-attention fields (the
    parent of this cell) stops in the engine's set-up, before the loop
    is built or any weight is drawn."""
    engine = manifest.module("engines", "paged_serve_mla")
    fields = engine._program_fields() - {"kv_lora_rank", "q_lora_rank", "router"}
    monkeypatch.setattr(engine, "_program_fields", lambda: fields)

    class NoWeights:
        @staticmethod
        def make_weights(cfg, seed):
            raise AssertionError("weights drawn")

    with pytest.raises(RuntimeError, match="cannot serve latent attention"):
        engine.base.build(CFG, 1, NoWeights)


def test_per_layer_lists_resolve_for_the_cell():
    names = {m["name"] for m in manifest.metrics_of(MAN, REAL, "per_layer")}
    assert names == {"decode_rows.serve", "preempt_per_req.serve", "prefill_ms_per_ktok",
                     "decode_step_ms", "idle_share.serve", "prefill_pad_share.serve",
                     "decode_ctx_share.serve", "plan_us_per_admit.serve",
                     "host_ms_per_turn.serve", "expert_roofline.moe",
                     "mla_decode_roofline", "mla_prefill_roofline", "mfu.mla_serve"}
    assert {m["name"] for m in manifest.metrics_of(MAN, REAL, "end_to_end")} == {
        "serve_tok_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    cell = manifest.cell(MAN, REAL)
    assert cell["chips"] == 1 and cell["traffic"] == "longdoc"
    assert CFG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "num_nextn_predict_layers"]


def test_smoke_wave_is_correct_and_the_control_is_not(root):
    """One traced wave of the twin: served and correct; the float8
    control on the same sample is not; every prefill chunk and dispatch
    carries the expert counters, and ``mfu.mla_serve`` is read (the
    kernels' rooflines need a TPU plane)."""
    out, rec = _run(root, seed=3, trace=True, control="fp8")
    assert out["correct"], out["checks"]
    assert out["attempted"] == 32 and out["failed"] == 0
    assert rec.window_compiles == 0
    control = rec.check["control"]
    assert control["correct"] is False
    gap = control["numbers"]["logit_gap"]
    assert gap["value"] > gap["limit"]
    logs = rec.prefills + rec.dispatches
    assert logs and all({"expert_slots", "experts_used"} <= set(e) for e in logs)
    assert out["metrics"]["mfu.mla_serve"]["value"] > 0
    assert not {"mla_decode_roofline", "mla_prefill_roofline"} & set(out["metrics"])
    assert rec.mla == work_mla.Shapes.of(rec.cfg)


def test_planted_fault_is_not_correct(root, monkeypatch):
    """The shared expert left out of the paged programs."""
    from repro.models import transformer
    monkeypatch.setattr(transformer, "shared_expert", lambda h, sp: h * 0)
    out, _ = _run(root, seed=3)
    assert out["correct"] is False
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
