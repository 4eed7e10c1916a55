#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, made on the chip.

    python benchmarks/chip/calibrate.py --workload <cell> --seconds 5 \
        --seeds 11 12 13 [--control] [--look]

Runs the cell once per seed in this one process (a short window at the
cell's own load) and prints, per seed, the logit gaps of the program's
served tokens and, with ``--control``, those of the reference computed in
float8 on the same sample, judged by the cell's own limits.  It exits 1
where the control comes out correct on any seed.  ``--look`` traces the first seed
and writes what the trace holds (planes, lines, event names) and a small
slice of its events to ``chiprun_out/``.  The benchmark's own runs never
run the control.  Each line is also appended to
``chiprun_out/calibrate_<cell>.jsonl``.
"""

import time

T0 = time.perf_counter()

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import REPO_ROOT, device, harness, manifest, trace as tr  # noqa: E402

OUT = REPO_ROOT / "chiprun_out"


def look(path: Path) -> None:
    """Plane and line names, counts and sample events of the trace, and a
    slice of the extracted events for the reduction's test."""
    import glob
    from jax.profiler import ProfileData
    f = sorted(glob.glob(str(harness.TRACE_DIR / "**" / "*.xplane.pb"),
                         recursive=True))[-1]
    data = ProfileData.from_file(f)
    planes = []
    for p in data.planes:
        lines = []
        for ln in p.lines:
            evs = list(ln.events)
            names = sorted({e.name for e in evs})
            lines.append({"line": ln.name, "events": len(evs),
                          "names": names[:40],
                          "stats": [list(map(str, s)) for s in
                                    (evs[0].stats if evs else [])][:12]})
        planes.append({"plane": p.name, "lines": lines})
    (path / "trace_look.json").write_text(json.dumps(planes, indent=1))
    ev = tr.extract(harness.TRACE_DIR)
    w = tr.window_of(ev["host"])
    t0 = w[0] if w else 0
    t1 = t0 + 200_000_000                      # the first 0.2 s
    small = {"devices": {k: {ln: [e for e in es if t0 <= e[1] and e[1] + e[2] < t1]
                             for ln, es in v.items()}
                         for k, v in ev["devices"].items()},
             "host": [e for e in ev["host"] if t0 <= e[1] and e[1] + e[2] < t1]
             + [(tr.WINDOW, t0, t1 - t0)]}
    (path / "trace_slice.json").write_text(json.dumps(small))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--look", action="store_true")
    args = ap.parse_args()
    cell = manifest.cell(manifest.load(), args.workload)
    devices = device.require_tpu(int(cell["chips"]))
    peak = device.peaks(devices[0].device_kind)
    harness.compile_cache()
    OUT.mkdir(exist_ok=True)
    log = OUT / f"calibrate_{args.workload}.jsonl"
    t_start = T0
    control_passed = []
    for i, seed in enumerate(args.seeds):
        traced = args.look and i == 0
        t = time.perf_counter()
        out, rec = harness.run_cell(args.workload, seed=seed,
                                    seconds=args.seconds, trace=traced,
                                    devices=devices, peak=peak,
                                    t_start=t_start,
                                    control="fp8" if args.control else None)
        line = {"cell": args.workload, "seed": seed,
                "gaps": rec.check["gaps"], "control": rec.check.get("control"),
                "checked_tokens": rec.check["numbers"]["checked_tokens"]["value"],
                "checked_requests": rec.check["checked_requests"],
                "checked_preempted": rec.check["checked_preempted"],
                "failed": out["failed"], "attempted": out["attempted"],
                "waves": len(rec.waves), "interval_s": rec.interval_s,
                "setup_s": rec.setup_s, "window_compiles": rec.window_compiles,
                "wall_s": time.perf_counter() - t, "metrics": out["metrics"],
                "device": out["device"], "breakdown": out.get("breakdown")}
        print(json.dumps(line), flush=True)
        with log.open("a") as fh:
            fh.write(json.dumps(line) + "\n")
        if traced:
            look(OUT)
        if args.control and rec.check["control"]["correct"]:
            control_passed.append(seed)
        t_start = time.perf_counter()
    if control_passed:
        print(f"the control came out correct on seeds {control_passed}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
