#!/usr/bin/env python3
"""Bring-up check on a TPU: the main paths at a published model's widths.

This is a bring-up run, not a benchmark: it proves that the system starts
and serves correct output on the chip.  Its timings are single cold/warm
runs, printed for orientation only.

    python chip_smoke.py                # one chip: serve qwen2.5-3b
    python chip_smoke.py --four-chips   # four chips: train qwen2.5-3b

One chip: ``PagedServeLoop`` and then the per-slot ``ServeLoop`` each
serve 8 requests (prompts of 128-1024 tokens drawn from ``--seed``, 32 new
tokens each) through full-width, full-depth qwen2.5-3b in bfloat16, with
random weights.  Every request must get exactly its token budget, every
token id must lie in the vocabulary, and the last-position logits of one
served prefill must match ``model.forward`` on the same prompt and
parameters within ``LOGITS_RTOL``.

Four chips (``--four-chips``, this phase alone): ``TrainLoop(hosts=4)`` on
a 2-layer twin of qwen2.5-3b at published widths must match the losses of
``mesh_shape=(1, 1)`` on one of the same chips, and full-depth qwen2.5-3b
must take a few ``hosts=4`` steps with finite losses.

Everything runs in this one process: a child could not reach the chip that
the parent holds.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
it is printed only when every phase passed.  With no TPU, or when a phase
fails, the script exits nonzero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2.5-3b"

# Served logits vs model.forward, both in bfloat16 from the same
# parameters, as max|served - forward| / max|forward|.  The two differ in
# how the work is split and rounded: a paged prefill chunk takes one
# softmax over the pool's keys and rounds its probabilities to bfloat16,
# where forward runs blockwise (online-softmax) attention on long prompts.
# Each bfloat16 rounding is a relative step of 2**-8; across 36 layers of
# random weights the paged engine lands near 2e-2, the per-slot one (same
# attention as forward) near 2e-3.  A wrong mask, position or cache row
# gives errors of order one.
LOGITS_RTOL = 3e-2

# serving shapes (one v5e chip): 640 blocks of 16 positions are ~0.38 GB
# of bfloat16 KV for qwen2.5-3b, enough for all 8 requests at once
SERVE = dict(num_blocks=640, block_size=16, max_context=2048,
             concurrency=8, prefill_chunk=512, decode_steps=4)
N_REQUESTS, PROMPT_MIN, PROMPT_MAX, MAX_NEW = 8, 128, 1024, 32

# training shapes (four v5e chips): 2 rows of 512 tokens per host
TRAIN = dict(batch=8, seq_len=512, steps=3)
# the multi-host loss tolerance of tests/test_train_multihost.py
LOSS_RTOL, LOSS_ATOL = 1e-3, 2e-3


class CompileClock:
    """Seconds spent in XLA backend compiles while the clock is open."""

    def __init__(self):
        self.seconds = 0.0

    def _listen(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False


def require_tpu(count: int):
    """The chip's devices; exits nonzero unless JAX sees ``count`` TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{devs[0].platform!r} — no result")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, JAX found "
                         f"{len(devs)} — no result")
    return devs


def peak_bytes(devices) -> List[int]:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
            for d in devices]


def make_requests(vocab_size: int, seed: int, n: int = N_REQUESTS,
                  lo: int = PROMPT_MIN, hi: int = PROMPT_MAX,
                  max_new: int = MAX_NEW):
    from repro.launch.serve import Request
    rng = np.random.default_rng(seed)
    lengths = rng.integers(lo, hi + 1, size=n)
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab_size,
                                        size=int(p)).astype(np.int32),
                    max_new=max_new)
            for i, p in enumerate(lengths)]


def check_served(out: Dict[int, List[int]], requests, vocab_size: int):
    """Every request served to exactly its budget, every id in the vocab."""
    if sorted(out) != [r.rid for r in requests]:
        raise AssertionError(f"served {sorted(out)}, asked for "
                             f"{[r.rid for r in requests]}")
    for r in requests:
        toks = out[r.rid]
        if len(toks) != r.max_new or r.truncated:
            raise AssertionError(f"request {r.rid}: {len(toks)} tokens, "
                                 f"budget {r.max_new}")
        if not all(0 <= t < vocab_size for t in toks):
            raise AssertionError(f"request {r.rid}: token id outside "
                                 f"[0, {vocab_size})")


def check_prefill_logits(loop, prompt: np.ndarray,
                         rtol: float = LOGITS_RTOL) -> float:
    """Serve ``prompt`` for one token through ``loop`` and compare the
    served prefill's last-position logits with ``model.forward`` on the
    same parameters; returns the relative error."""
    import jax
    from repro.launch.serve import Request
    loop.run([Request(rid=-1, prompt=prompt, max_new=1)])
    served = np.asarray(loop.last_prefill_logits, np.float32)
    forward = jax.jit(lambda p, t: loop.model.forward(p, {"tokens": t})[0])
    ref = np.asarray(forward(loop.params, prompt[None])[0, -1], np.float32)
    if served.shape != ref.shape:
        raise AssertionError(f"logits shape {served.shape} != {ref.shape}")
    if not np.isfinite(served).all():
        raise AssertionError("served logits are not finite")
    err = float(np.abs(served - ref).max() / np.abs(ref).max())
    if not err <= rtol:
        raise AssertionError(f"served logits differ from model.forward by "
                             f"{err:.3e} of max|logit| (limit {rtol:.0e})")
    return err


def serve_phase(loop, cfg, seed: int) -> Dict[str, float]:
    """A cold run (compiles included) and a warm run of the same requests
    through ``loop``, both checked, then the logits check."""
    stats: Dict[str, float] = {}
    for run in ("cold", "warm"):
        reqs = make_requests(cfg.vocab_size, seed)
        with CompileClock() as clock:
            t0 = time.perf_counter()
            out = loop.run(reqs)
            wall = time.perf_counter() - t0
        check_served(out, reqs, cfg.vocab_size)
        toks = sum(len(v) for v in out.values())
        stats[f"{run}_wall_s"] = wall
        stats[f"{run}_compile_s"] = clock.seconds
        stats[f"{run}_tok_per_s"] = toks / wall
    stats["decode_dispatches"] = loop.last_stats["decode_dispatches"]
    stats["preemptions"] = loop.last_stats.get("preemptions", 0)
    prompt = make_requests(cfg.vocab_size, seed)[0].prompt
    stats["logits_rel_err"] = check_prefill_logits(loop, prompt)
    return stats


def serve_paged(cfg, seed: int, **shape) -> Dict[str, float]:
    from repro.launch.serve import PagedServeLoop
    shape = {**SERVE, **shape}
    loop = PagedServeLoop(cfg, scheduler="static", seed=seed, **shape)
    return serve_phase(loop, cfg, seed)


def serve_per_slot(cfg, seed: int, **shape) -> Dict[str, float]:
    from repro.launch.serve import ServeLoop
    shape = {**SERVE, **shape}
    loop = ServeLoop(cfg, slots=shape["concurrency"],
                     max_len=shape["max_context"], scheduler="static",
                     seed=seed)
    return serve_phase(loop, cfg, seed)


def train_phase(cfg, seed: int, *, hosts: int = 1,
                mesh_shape: Optional[Sequence[int]] = None,
                **shape) -> Dict[str, object]:
    """A few ``TrainLoop`` steps; the mesh must span ``hosts`` devices
    (one when ``mesh_shape`` is given) and every loss must be finite."""
    from repro.launch.train import TrainLoop
    shape = {**TRAIN, **shape}
    loop = TrainLoop(cfg, batch=shape["batch"], seq_len=shape["seq_len"],
                     seed=seed, hosts=hosts, mesh_shape=mesh_shape)
    want = hosts if mesh_shape is None else int(np.prod(mesh_shape))
    mesh_devs = {d.id for d in loop.mesh.devices.flat}
    wi = loop.params["layers"]["mlp"]["wi_gate"]
    if len(mesh_devs) != want or len(wi.sharding.device_set) != want:
        raise AssertionError(f"mesh spans {sorted(mesh_devs)}, weights "
                             f"{len(wi.sharding.device_set)} devices; "
                             f"expected {want}")
    with CompileClock() as clock:
        losses = loop.run(shape["steps"], log_every=10 ** 9)
    if not np.isfinite(losses).all():
        raise AssertionError(f"non-finite losses {losses}")
    steady = [s["dt_s"] for s in loop.step_log[1:]]
    tokens = shape["batch"] * shape["seq_len"]
    return {"losses": [float(x) for x in losses],
            "devices": sorted(mesh_devs), "compile_s": clock.seconds,
            "steady_step_s": float(np.median(steady)) if steady else None,
            "tok_per_s": tokens / float(np.median(steady)) if steady
            else None}


def depth_cut(cfg, layers: int = 2):
    """``cfg`` at its published widths with only ``layers`` layers."""
    return dataclasses.replace(cfg, name=f"{cfg.name}-{layers}l",
                               num_layers=layers)


def four_chip_phase(cfg, seed: int, **shape) -> Dict[str, object]:
    twin = depth_cut(cfg)
    multi = train_phase(twin, seed, hosts=4, **shape)
    gc.collect()
    single = train_phase(twin, seed, mesh_shape=(1, 1), **shape)
    gc.collect()
    np.testing.assert_allclose(multi["losses"], single["losses"],
                               rtol=LOSS_RTOL, atol=LOSS_ATOL)
    full = train_phase(cfg, seed, hosts=4, **shape)
    gc.collect()
    return {"twin_hosts4": multi, "twin_single": single,
            "full_hosts4": full}


def _report(name: str, stats: Dict[str, object]) -> None:
    print(f"[bring-up, not a benchmark] {name}: "
          + json.dumps(stats, default=float), flush=True)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip training phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and request prompts")
    args = ap.parse_args(argv)

    devices = require_tpu(4 if args.four_chips else 1)
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    dev = devices[0]
    print(f"[bring-up, not a benchmark] device {dev.platform} "
          f"{dev.device_kind} x{len(devices)}; compile cache {cache_dir}",
          flush=True)
    cfg = get_config(ARCH)
    if args.four_chips:
        _report(f"train {ARCH} hosts=4 vs one chip",
                four_chip_phase(cfg, args.seed))
        _report("peak_bytes_in_use per device", {
            str(d.id): b for d, b in zip(devices, peak_bytes(devices))})
    else:
        _report(f"serve {ARCH} PagedServeLoop", serve_paged(cfg, args.seed))
        gc.collect()
        _report(f"serve {ARCH} ServeLoop", serve_per_slot(cfg, args.seed))
        gc.collect()
        _report("peak_bytes_in_use", {"0": peak_bytes(devices[:1])[0]})
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
