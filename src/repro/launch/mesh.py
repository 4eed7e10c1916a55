"""Meshes and logical-axis sharding rules.

``make_production_mesh`` builds the target v5e meshes:
  * single-pod: (16, 16)      axes ("data", "model")   — 256 chips
  * multi-pod:  (2, 16, 16)   axes ("pod", "data", "model") — 512 chips

Parameters/activations carry *logical* axis names (see models/common.py
ParamBuilder); ``Rules`` maps logical -> mesh axes.  Changing the rule table
(not the model code) is how the §Perf hillclimb re-shards — exactly the
decoupling the paper demands between a scheduling *strategy* and the code
that uses it.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding

from repro.models.config import ModelConfig

__all__ = ["make_production_mesh", "make_mesh", "make_host_mesh", "Rules",
           "base_rules", "rules_for", "spec_for", "shardings_for",
           "input_sharding", "batch_shardings"]


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(num_hosts: int, model_par: int = 1) -> Mesh:
    """Data-parallel mesh whose leading axis is a HOST: ``("host", "model")``
    of shape ``(num_hosts, model_par)``.

    The "host" axis is the straggler-mitigation unit — per-host step times
    feed :class:`~repro.sched.straggler.StragglerMitigator`, whose AWF token
    shares drive the uneven batch split.  On a real pod each "host" entry is
    one process's device block; on CPU, N hosts are emulated with

        XLA_FLAGS=--xla_force_host_platform_device_count=N

    exported before the first jax import (jax locks the device count on
    first init — the same contract as launch/dryrun.py).
    """
    if num_hosts < 1:
        raise ValueError(f"num_hosts must be >= 1, got {num_hosts}")
    return make_mesh((num_hosts, model_par), ("host", "model"))


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """Elastic-scaling entry point: any (data, model[, pod]) factorization of
    the currently-healthy device count (see runtime/elastic.py).  Uses the
    first prod(shape) devices so a 256-chip pod mesh builds on the 512-device
    dry-run host (and on degraded device sets after failures)."""
    import math
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(f"mesh {tuple(shape)} needs {n} devices, "
                         f"only {len(devs)} available")
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devs[:n])


# A rule maps a logical axis name to a mesh axis (or tuple of axes, or None).
from repro.sharding import Rules, shardings_for, spec_for, _sizes


def base_rules(mesh: Mesh) -> Rules:
    """Baseline rule table (the §Perf starting point).

    2-D weight sharding: feature-ish axes over "model" (TP), the embed axis
    over "data" (FSDP/ZeRO) — optimizer state inherits, so a 314B-param
    model's state spreads over all 256 chips.
    """
    has_pod = "pod" in mesh.axis_names
    if "host" in mesh.axis_names:      # make_host_mesh: hosts ARE the DP axis
        batch_axes = ("host",)
        fsdp_axis = "host"
    else:
        batch_axes = ("pod", "data") if has_pod else ("data",)
        fsdp_axis = "data"
    return {
        "batch": batch_axes,
        "seq": None,             # sequence (activations) — context parallel off
        "seq_cache": None,       # KV-cache length axis
        "vocab": "model",
        "embed": fsdp_axis,      # FSDP axis on weights
        "heads": "model",
        "kv": "model",
        "mlp": "model",
        "experts": "model",
        "layers": None,          # scan axis — never sharded
        # activation axes (with_sharding_constraint inside scanned bodies —
        # without these GSPMD replicates batch inside the layer loop)
        "act_embed": None,       # residual feature dim stays unsharded
        "act_heads": "model",
        "act_kv": "model",
        "act_mlp": "model",
        "act_vocab": "model",
        "act_experts": "model",
    }


def rules_for(cfg: ModelConfig, mesh: Mesh, shape_kind: str,
              global_batch: int = 0,
              overrides: Optional[Rules] = None) -> Rules:
    """Baseline rules + per-arch overrides + shape-driven adjustments."""
    rules = base_rules(mesh)
    for k, v in cfg.sharding_overrides:
        v = tuple(v) if isinstance(v, list) else v
        rules[k] = v
        if f"act_{k}" in rules:     # weight override implies activation twin
            rules[f"act_{k}"] = v
    # long-context decode with batch=1: batch is unshardable -> shard the
    # cache/sequence axis over the data (and pod) axes instead.
    if shape_kind == "decode" and global_batch == 1:
        rules["batch"] = None
        rules["seq_cache"] = (("pod", "data") if "pod" in mesh.axis_names
                              else ("data",))
    if overrides:
        rules.update(overrides)
    return rules


def input_sharding(mesh: Mesh, rules: Rules, *axes: Optional[str],
                   shape: Optional[Tuple[int, ...]] = None) -> NamedSharding:
    return NamedSharding(mesh, spec_for(tuple(axes), rules, shape=shape,
                                        axis_sizes=_sizes(mesh)))


def batch_shardings(mesh: Mesh, rules: Rules, batch) -> dict:
    """Per-host input placement for a LIVE batch dict: each key's batch
    axis shards over whatever the rule table maps "batch" onto ("host" on
    a host mesh), everything else replicates.  Keys outside
    ``sharding.BATCH_AXES`` (per-expert vectors etc.) replicate whole.
    ``jax.device_put(batch, batch_shardings(...))`` is how the train loop
    commits each host's row block to that host's devices before the
    jitted step."""
    from repro.sharding import BATCH_AXES
    return {k: input_sharding(mesh, rules,
                              *BATCH_AXES.get(k, (None,) * v.ndim),
                              shape=v.shape)
            for k, v in batch.items()}
