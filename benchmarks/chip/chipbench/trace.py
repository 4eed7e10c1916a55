"""Device trace: capture with the JAX profiler, and reduce to numbers.

Capture writes an ``.xplane.pb`` under a directory of the checkout.
:func:`extract` turns it into plain event lists, and :func:`reduce` turns
those into the device's busy time, each jitted program's device time, the
operations that took most time, and the idle gaps labelled by what the
host was doing around them.  The reduction works on plain lists, so it is
checked on a small recorded trace without a chip.

Programs are found by their jit names (``jit_prefill_chunk``,
``jit_serve_step``) on each device plane's ``XLA Modules`` line; the
program has no named scopes yet.
"""

from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, duration_ns)

WINDOW = "chipbench_traced_window"     # host annotation around the traced part
_SUFFIX = re.compile(r"\(\d+\)$")
_DEVICE = re.compile(r"^/device:TPU:\d+$")


def program_name(module: str) -> str:
    """``jit_serve_step(1234)`` -> ``serve_step``."""
    name = _SUFFIX.sub("", module.strip())
    return name[4:] if name.startswith("jit_") else name


def op_name(op: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return op.split(" = ", 1)[0].strip().lstrip("%")


def _self_times(ops: List[Tuple[str, int, int]]) -> List[Tuple[str, int, int, int]]:
    """(name, start, end, self time): an op's time less that of the ops
    nested in it (a ``while`` holds its body's ops on the same line)."""
    out: List[Tuple[str, int, int, int]] = []
    stack: List[list] = []
    for name, a, b in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            n, sa, sb, child = stack.pop()
            out.append((n, sa, sb, (sb - sa) - child))
        if stack:
            stack[-1][3] += min(b, stack[-1][2]) - a
        stack.append([name, a, b, 0])
    out.extend((n, sa, sb, (sb - sa) - c) for n, sa, sb, c in reversed(stack))
    return out


def start(log_dir: Path) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # host spans come from JAX's TraceMe
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def extract(log_dir: Path) -> Dict[str, Any]:
    """Events of the newest trace under ``log_dir``: per TPU plane its
    ``XLA Modules`` and ``XLA Ops``, and every host event."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if _DEVICE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in ("XLA Modules", "XLA Ops"):
                    lines[line.name] = [(e.name, int(e.start_ns), int(e.duration_ns))
                                        for e in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events)
    return {"devices": devices, "host": host}


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(events: List[Event], t0: int, t1: int) -> List[Tuple[str, int, int]]:
    """(name, start, end) of the events that overlap [t0, t1], clipped."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b))
    return out


def window_of(host: List[Event]) -> Optional[Tuple[int, int]]:
    spans = [(s, s + d) for n, s, d in host if n == WINDOW]
    return (min(a for a, _ in spans), max(b for _, b in spans)) if spans else None


def reduce(ev: Dict[str, Any], t0: Optional[int] = None,
           t1: Optional[int] = None, top: int = 10) -> Optional[Dict[str, Any]]:
    """Numbers of the traced window ``[t0, t1]`` (default: the host's
    :data:`WINDOW` annotation).  ``None`` when no device op ran in it."""
    if t0 is None or t1 is None:
        w = window_of(ev["host"])
        if w is None:
            return None
        t0, t1 = w
    busy_total = 0.0
    programs: Dict[str, float] = defaultdict(float)
    calls: Dict[str, int] = defaultdict(int)
    ops: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    host = sorted(_clip([e for e in ev["host"] if e[0] != WINDOW], t0, t1),
                  key=lambda e: e[1])
    n_dev = 0
    for _plane, lines in sorted(ev["devices"].items()):
        op_ev = _clip(lines.get("XLA Ops", []), t0, t1)
        mod_ev = _clip(lines.get("XLA Modules", []), t0, t1)
        if not op_ev and not mod_ev:
            continue
        n_dev += 1
        busy = _union([(a, b) for _, a, b in (op_ev or mod_ev)])
        busy_total += sum(b - a for a, b in busy) / 1e9
        for name, a, b in mod_ev:
            programs[program_name(name)] += (b - a) / 1e9
            calls[program_name(name)] += 1
        mods = sorted(mod_ev, key=lambda e: e[1])
        starts = [m[1] for m in mods]
        for name, a, _b, own in _self_times(op_ev):
            i = bisect.bisect_right(starts, a) - 1
            prog = program_name(mods[i][0]) if i >= 0 and mods[i][2] > a else "?"
            ops[f"{prog}/{op_name(name)}"] += own / 1e9
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        for label, secs in _gaps(list(zip(edges[0::2], edges[1::2])), mods, host):
            gaps[label] += secs
    if n_dev == 0:
        return None
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy_total / n_dev,
        "devices": n_dev,
        "program_s": {k: v / n_dev for k, v in programs.items()},
        "program_calls": {k: v // n_dev for k, v in calls.items()},
        "device_ops": sorted(([k, v / n_dev] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v / n_dev] for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:top],
    }


HOST_MAX_NS = 1_000_000_000      # host events longer than this label no gap


def _gaps(spans, mods, host):
    """Label each idle gap: inside a program (between its own ops), or
    ``<program before> -> <program after> | host: <the host event that
    covers most of the gap>``.  Yields (label, seconds)."""
    host = [h for h in host if h[2] - h[1] <= HOST_MAX_NS]
    hstarts = [h[1] for h in host]
    starts = [m[1] for m in mods]
    for a, b in spans:
        if b <= a:
            continue
        i = bisect.bisect_right(starts, a) - 1          # last module started <= a
        if i >= 0 and mods[i][2] >= b:
            yield f"inside {program_name(mods[i][0])}", (b - a) / 1e9
            continue
        k = i + 1                                       # first module after a
        prev = program_name(mods[i][0]) if i >= 0 else "start"
        nxt = program_name(mods[k][0]) if k < len(mods) else "end"
        best, best_len = "none", 0
        lo = bisect.bisect_left(hstarts, a - HOST_MAX_NS)
        hi = bisect.bisect_left(hstarts, b)
        for name, hs, he in host[lo:hi]:
            cover = min(he, b) - max(hs, a)
            if cover > best_len:
                best, best_len = name, cover
        yield f"{prev} -> {nxt} | host: {best}", (b - a) / 1e9
