#!/usr/bin/env python3
"""Read the serving program's spans and scopes out of a traced run.

    python benchmarks/chip/run.py --workload <cell> --seed <n> --seconds 51 --trace 1
    python benchmarks/chip/span_report.py [--trace-dir .chipbench_trace] [--top 15]

Prints one JSON object for the traced window: per ``serve.*`` span name
its count, total and mean seconds; the host's own time per turn (a turn
less its ``serve.wait`` spans); each program's device seconds per named
scope; the top device operations labelled ``<program>/<scope>/<op>``;
and the idle gaps labelled by the innermost span that covers most of
each (``chipbench.spans``).  Needs no chip: it reads the trace on disk.
"""

import argparse
import json
import sys
from collections import defaultdict
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import harness, spans  # noqa: E402


def report(trace_dir: Path, top: int) -> dict:
    ev = spans.extract(trace_dir)
    r = spans.reduce(ev, top=10**9)         # every gap, for the shares
    if r is None:
        return {"error": f"no device operation in the traced window of {trace_dir}"}
    by_name = defaultdict(lambda: [0, 0.0])
    for s in r["spans"]:
        by_name[spans.label(s)][0] += 1
        by_name[spans.label(s)][1] += (s["end"] - s["start"]) / 1e9
    own, turn = [], None
    for s in r["spans"]:
        if s["name"] == "serve.turn":
            turn = len(own)
            own.append((s["end"] - s["start"]) / 1e9)
        elif s["name"] == "serve.wait" and turn is not None:
            own[turn] -= (s["end"] - s["start"]) / 1e9
    idle = r["window_s"] - r["busy_s"]
    between = sum(v for k, v in r["idle_gaps"] if not k.startswith("inside "))
    by_span = sum(v for k, v in r["idle_gaps"] if " | span: " in k)
    return {
        "window_s": r["window_s"], "busy_s": r["busy_s"], "idle_s": idle,
        "program_s": r["program_s"], "program_calls": r["program_calls"],
        "spans": {k: {"count": n, "total_s": t, "mean_s": t / n}
                  for k, (n, t) in sorted(by_name.items())},
        "host_s_per_turn": sum(own) / len(own) if own else None,
        "scope_s": r["scope_s"],
        "device_ops": r["device_ops"][:top],
        "idle_gaps": r["idle_gaps"][:top],
        "idle_between_programs_s": between,
        "idle_labelled_by_span_share": by_span / between if between else None,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace-dir", type=Path, default=harness.TRACE_DIR)
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args()
    print(json.dumps(report(args.trace_dir, args.top), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
