"""Serving cells of a DeepSeek-V3 share: the ``paged_serve_moe`` engine,
with the configuration's latent attention, leading dense layers, shared
expert and group-limited sigmoid router mapped onto the program.

The engine is ``engines/paged_serve_moe.py`` (and through it
``engines/paged_serve.py``), loaded as a module of its own and changed in
two places:

* :func:`program_config` maps the configuration's keys onto the
  program's ``ModelConfig``: ``moe_intermediate_size`` is the expert
  width and ``intermediate_size`` the dense layers'; ``n_routed_experts``
  the experts held here, ``published["n_routed_experts"]`` the router's
  width; the MLA widths, ``rope_scaling`` (YaRN), the router's groups and
  scaling, the shared experts and ``rms_norm_eps``.  A program whose
  ``ModelConfig`` lacks these fields stops here, before any weight is
  drawn;
* the result holds ``mla`` (``work_mla.Shapes``) and ``moe``, the expert
  layers' shapes as ``work_moe`` counts them (``work_moe.Shapes.of``
  needs a ``head_dim``, which MLA does not have), besides every wave's
  ``prefill_log`` (:attr:`prefills`) with the expert counters, as the
  MoE engine keeps them.
"""

from __future__ import annotations

import dataclasses
import sys
from types import SimpleNamespace
from typing import Any, Dict, List

from chipbench import REPO_ROOT, manifest, work_mla

moe = manifest.module("engines", "paged_serve_moe")
base = moe.base

# configuration-file key -> ModelConfig field
FIELDS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
          "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads",
          "moe_intermediate_size": "d_ff", "intermediate_size": "dense_d_ff",
          "first_k_dense_replace": "first_dense_layers",
          "n_routed_experts": "experts_held",
          "num_experts_per_tok": "experts_per_token",
          "n_shared_experts": "shared_experts", "n_group": "router_groups",
          "topk_group": "router_groups_kept",
          "routed_scaling_factor": "routed_scaling",
          "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
          "qk_nope_head_dim": "qk_nope_head_dim",
          "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
          "vocab_size": "vocab_size", "rope_theta": "rope_theta",
          "attention_bias": "qkv_bias",
          "tie_word_embeddings": "tie_embeddings",
          "rms_norm_eps": "rms_norm_eps", "expert_offset": "expert_offset"}
# rope_scaling key -> ModelConfig field
ROPE = {"factor": "rope_factor",
        "original_max_position_embeddings": "rope_original_max",
        "beta_fast": "yarn_beta_fast", "beta_slow": "yarn_beta_slow",
        "mscale": "yarn_mscale", "mscale_all_dim": "yarn_mscale_all_dim"}


def _program_fields() -> set:
    if str(REPO_ROOT / "src") not in sys.path:
        sys.path.insert(0, str(REPO_ROOT / "src"))
    from repro.models.config import ModelConfig
    return {f.name for f in dataclasses.fields(ModelConfig)}


def wanted(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The ``ModelConfig`` fields the configuration file sets."""
    fixed = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
             "norm_topk_prob": True, "moe_layer_freq": 1, "hidden_act": "silu"}
    off = {k: cfg.get(k) for k, v in fixed.items() if cfg.get(k) != v}
    if off or cfg["rope_scaling"].get("type") != "yarn":
        raise ValueError(f"{cfg['name']}: the program serves {fixed} with "
                         f"yarn rope, not {off} / {cfg['rope_scaling']}")
    want = {f: cfg[k] for k, f in FIELDS.items() if k in cfg}
    want.update({f: cfg["rope_scaling"][k] for k, f in ROPE.items()})
    want["num_experts"] = cfg["published"]["n_routed_experts"]
    want["router"] = "sigmoid"
    return want


def program_config(cfg: Dict[str, Any], smoke: bool = False):
    """The program's registered config with the file's values on top;
    every field that differs from the registry is reported on stderr.
    Raises before anything is built where the program has no such
    fields."""
    want = wanted(cfg)
    missing = sorted(set(want) - _program_fields())
    if missing:
        raise RuntimeError(f"{cfg['name']}: the program's ModelConfig has no "
                           f"{missing}: it cannot serve latent attention")
    from repro.configs import get_config, get_smoke_config
    reg = (get_smoke_config if smoke else get_config)(cfg["program_arch"])
    changed = {f: (getattr(reg, f), v) for f, v in want.items()
               if getattr(reg, f) != v}
    if changed:
        print(f"chipbench: {cfg['name']} runs the program's "
              f"{cfg['program_arch']} with {changed} (registry, file)",
              file=sys.stderr)
    return dataclasses.replace(reg, **want)


base.program_config = program_config
_build = base.build


def run(ctx: SimpleNamespace) -> SimpleNamespace:
    """:func:`paged_serve.run` with the changes above; the result also
    holds ``prefills`` (every wave's ``prefill_log``, each entry with its
    ``wave``), ``mla`` and ``moe``."""
    logs: List[List[Dict[str, Any]]] = []

    def build_logged(cfg, seed, reference, smoke=False):
        loop, weights = _build(cfg, seed, reference, smoke)
        run_loop = loop.run

        def run_and_log(requests):
            out = run_loop(requests)
            logs.append(list(loop.prefill_log))
            return out
        loop.run = run_and_log
        return loop, weights

    base.build = build_logged
    rec = base.run(ctx)
    # the first run is the warm-up; every later one is a wave
    rec.prefills = [dict(e, wave=w) for w, log in enumerate(logs[1:]) for e in log]
    rec.mla = work_mla.Shapes.of(ctx.cfg)
    rec.moe = work_mla.expert_shapes(rec.mla)
    return rec
