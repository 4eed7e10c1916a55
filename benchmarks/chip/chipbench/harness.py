"""One run of one cell: look everything up by name, run its engine, read
its metrics, and print the result.

The result is the last line of stdout, one JSON object::

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown"], "checks"}

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, and ``device`` also holds the traced
window's ``busy_s`` and ``window_s``.  The numbers compared for
``correct`` are printed beside their limits as the last lines of stderr
and under ``checks``, the last key of the result.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace
from typing import Any, Dict, List, Optional

from chipbench import BENCH_DIR, REPO_ROOT, device, manifest

# the trace of a --trace 1 run is written here, inside the checkout
TRACE_DIR = REPO_ROOT / ".chipbench_trace"


def compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else the fixed ``<checkout>/.jax_cache``; every program is
    cached, however short its compile."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(REPO_ROOT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_cell(cell_name: str, *, seed: int, seconds: float, trace: bool,
             devices: List[Any], peak: Dict[str, float], t_start: float,
             root: Path = REPO_ROOT, bench_dir: Path = BENCH_DIR,
             smoke: bool = False, control: Optional[str] = None):
    """Everything but the chip check and the printing: the result object
    and the run's records.  ``control`` (``"fp8"``) also reads the
    control's gaps on the same sample."""
    man = manifest.load(root)
    cell = manifest.cell(man, cell_name)
    cfg = manifest.config(man, cell["config"], root)
    engine = manifest.module("engines", cfg["engine"], bench_dir)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    ctx = SimpleNamespace(
        cfg=cfg, traffic=manifest.traffic(cell["traffic"], bench_dir),
        seed=int(seed), seconds=float(seconds), trace=bool(trace),
        devices=devices, t_start=t_start, trace_dir=TRACE_DIR, smoke=smoke,
        control=control,
        reference=manifest.module("reference", cfg["reference"], bench_dir))
    rec = engine.run(ctx)
    rec.peak = peak
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in manifest.metrics_of(man, cell_name, kind):
        value = manifest.module("metrics", m["name"], bench_dir).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = dict(device.describe(devices), memory_peak_bytes=rec.memory_peak_bytes)
    out: Dict[str, Any] = {
        "correct": rec.check["correct"],
        "attempted": len(rec.requests),
        "failed": int(rec.check["numbers"]["failed_requests"]["value"]),
        "metrics": metrics, "device": dev}
    if trace and rec.trace is not None:
        dev["busy_s"] = rec.trace["busy_s"]
        dev["window_s"] = rec.trace["window_s"]
        out["breakdown"] = {"device_ops": rec.trace["device_ops"],
                            "idle_gaps": rec.trace["idle_gaps"]}
    out["checks"] = rec.check["numbers"]
    return out, rec


def main(argv: Optional[List[str]] = None, *, t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    man = manifest.load()
    cell = manifest.cell(man, args.workload)
    devices = device.require_tpu(int(cell["chips"]))
    peak = device.peaks(devices[0].device_kind)
    cache = compile_cache()
    print(f"chipbench: {args.workload} seed {args.seed} on "
          f"{devices[0].device_kind} x{len(devices)}; compile cache {cache}",
          file=sys.stderr, flush=True)
    out, rec = run_cell(args.workload, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace), devices=devices, peak=peak,
                        t_start=t_start)
    print(f"window_compiles: {rec.window_compiles} "
          f"({rec.window_compile_s:.3f} s); waves {len(rec.waves)}; "
          f"interval_s {rec.interval_s}; checked "
          f"{rec.check['checked_requests']} requests "
          f"({rec.check['checked_preempted']} preempted); gaps "
          f"{rec.check['gaps']}", flush=True)
    for name, n in out["checks"].items():
        print(f"check {name}: {n['value']} limit {n['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
