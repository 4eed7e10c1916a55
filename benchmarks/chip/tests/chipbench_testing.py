"""Helpers of the benchmark's CPU tests: a checkout root that names the
smoke twins' cells, and a run of one such cell without a chip."""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

from chipbench import harness  # noqa: E402

# peaks of a made-up device, so that the CPU can drive the arithmetic
FAKE_PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}

SMOKE_CELLS = {
    "qwen-smoke.longprompt": ("qwen2.5-3b-smoke", "longprompt"),
    "phi3-smoke.longanswer": ("phi3-mini-3.8b-smoke", "longanswer"),
}


def smoke_root(tmp: Path) -> Path:
    """A root whose ``BENCHMARK.json`` is the real one plus the smoke
    cells and configurations."""
    man = json.loads((BENCH.parents[1] / "BENCHMARK.json").read_text())
    for name, (cfg, mix) in SMOKE_CELLS.items():
        src = HERE / "data" / f"{cfg}.json"
        path = tmp / f"{cfg}.json"
        data = json.loads(src.read_text())
        path.write_text(json.dumps(data))
        man["configs"].append({"name": cfg, "source": data["source"],
                               "file": str(path), "reduced": data["reduced"],
                               "why": "smoke twin"})
        man["workloads"].append({"name": name, "config": cfg, "traffic": mix,
                                 "chips": 1, "why": "CPU test"})
        for m in man["end_to_end"] + man["per_layer"]:
            if "workloads" in m:
                m["workloads"].append(name)
    (tmp / "BENCHMARK.json").write_text(json.dumps(man))
    return tmp


def run_smoke(root: Path, cell: str, seed: int = 7, seconds: float = 0.0,
              trace: bool = False, bench_dir: Path = BENCH):
    """One run of a smoke cell on the CPU: ``seconds=0`` sends one wave."""
    import jax
    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                            devices=jax.devices()[:1], peak=FAKE_PEAK,
                            t_start=time.perf_counter(), root=root,
                            bench_dir=bench_dir, smoke=True)
