"""The serving program's own marks in a trace: host spans and device scopes.

The paged serving loop wraps each phase of a turn in a ``serve.*``
profiler span (``jax.profiler.TraceAnnotation``) whose metadata are
values the loop already holds (a request's ``rid``, a chunk's ``start``,
``length`` and ``bucket``, ...), and its two jitted programs name their
stages with ``jax.named_scope`` (``qkv``, ``kv_gather``, ``attention``,
...).  The spans land on the profiler's host plane and the device's
operations on its device planes, on one clock, so no alignment is made
here.

:func:`extract` reads a trace like :func:`chipbench.trace.extract`, and
keeps each host event's metadata and each device operation's scope path.
:func:`reduce` adds to :func:`chipbench.trace.reduce` the window's spans,
each program's device time per scope, top operations labelled by scope,
and idle gaps labelled by the innermost span that covers most of each.
:func:`traced_spans` gives a metric reader the spans of its run's traced
window; a program without spans gives it nothing.
"""

from __future__ import annotations

import bisect
import glob
from collections import defaultdict
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from chipbench import trace as tr

PREFIX = "serve."
OUTER = ("serve.run", "serve.turn")        # cover every gap: label none
# the programs' named scopes (models/transformer.py)
SCOPES = ("embed", "qkv", "kv_append", "kv_gather", "attention", "attn_out",
          "mlp", "head", "sample")
# the stat of a device operation's event metadata that holds its op_name
OP_PATH_STAT = "tf_op"

Span = Dict[str, Any]          # {"name", "meta", "start", "end"} in ns


def scope_of(path: str) -> Optional[str]:
    """The first of :data:`SCOPES` on an op_name path
    (``jit(serve_step)/while/body/closed_call/kv_gather/gather:``)."""
    for part in path.split("/"):
        if part.rstrip(":") in SCOPES:
            return part.rstrip(":")
    return None


def _host_events(plane) -> Iterator[Tuple[str, int, int, Dict[str, Any]]]:
    for line in plane.lines:
        for e in line.events:
            meta = ({k: v for k, v in e.stats if not k.startswith("_")}
                    if e.name.startswith(PREFIX) else {})
            yield e.name, int(e.start_ns), int(e.duration_ns), meta


def _varint(b, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one protocol-buffer message: an int for
    a varint, a memoryview of the bytes otherwise."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protocol-buffer wire type {wire}")
        yield key >> 3, v


def op_stats(path: Path) -> Dict[str, Dict[str, Dict[str, Any]]]:
    """Per TPU plane, each operation's name -> the string stats of its
    event metadata (``tf_op`` holds the op_name path).  ``ProfileData``
    shows an event's own stats only, so this reads the ``.xplane.pb``
    (XSpace > XPlane > event_metadata, stat_metadata) itself."""
    data = memoryview(Path(path).read_bytes())
    out: Dict[str, Dict[str, Dict[str, Any]]] = {}
    for f, plane in _fields(data):
        if f != 1:                                  # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:                              # XPlane.name
                name = bytes(v).decode()
                if not tr._DEVICE.match(name):
                    break
            elif g == 4:                            # event_metadata entry
                metas.extend(m for k, m in _fields(v) if k == 2)
            elif g == 5:                            # stat_metadata entry
                for k, m in _fields(v):
                    if k == 2:
                        fm = dict(_fields(m))
                        stat_names[fm.get(1, 0)] = bytes(fm.get(2, b"")).decode()
        if not tr._DEVICE.match(name):
            continue
        ops: Dict[str, Dict[str, Any]] = {}
        for m in metas:
            ev_name, stats = "", {}
            for k, v in _fields(m):
                if k == 2:                          # XEventMetadata.name
                    ev_name = bytes(v).decode()
                elif k == 5:                        # XEventMetadata.stats
                    st = dict(_fields(v))
                    if 5 in st:                     # str_value
                        stats[stat_names.get(st.get(1, 0), "")] = bytes(st[5]).decode()
                    elif 7 in st:                   # ref_value: a stat name
                        stats[stat_names.get(st.get(1, 0), "")] = stat_names.get(st[7], "")
            ops[ev_name] = stats
        out[name] = ops
    return out


def _xplane(log_dir: Path) -> str:
    files = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def extract(log_dir: Path, devices: bool = True) -> Dict[str, Any]:
    """Events of the newest trace under ``log_dir``: per TPU plane its
    ``XLA Modules`` (name, start, duration) and ``XLA Ops`` (name, start,
    duration, op_name path), and every host event (name, start, duration,
    metadata; metadata only on ``serve.*`` spans).  ``devices=False``
    reads the host planes alone, which is quick."""
    from jax.profiler import ProfileData
    path = _xplane(log_dir)
    data = ProfileData.from_file(path)
    paths = op_stats(path) if devices else {}
    devs: Dict[str, Dict[str, list]] = {}
    host: List[Tuple[str, int, int, Dict[str, Any]]] = []
    for plane in data.planes:
        if devices and tr._DEVICE.match(plane.name):
            lines: Dict[str, list] = {}
            stats = paths.get(plane.name, {})
            for line in plane.lines:
                if line.name == "XLA Modules":
                    lines[line.name] = [(e.name, int(e.start_ns), int(e.duration_ns))
                                        for e in line.events]
                elif line.name == "XLA Ops":
                    lines[line.name] = [(e.name, int(e.start_ns), int(e.duration_ns),
                                         stats.get(e.name, {}).get(OP_PATH_STAT, ""))
                                        for e in line.events]
            devs[plane.name] = lines
        elif plane.name.startswith("/host:"):
            host.extend(_host_events(plane))
    return {"devices": devs, "host": host}


def plain(ev: Dict[str, Any]) -> Dict[str, Any]:
    """The events in :func:`chipbench.trace.extract`'s form."""
    return {"devices": {p: {ln: [tuple(e[:3]) for e in es] for ln, es in lines.items()}
                        for p, lines in ev["devices"].items()},
            "host": [tuple(e[:3]) for e in ev["host"]]}


def window(ev: Dict[str, Any]) -> Optional[Tuple[int, int]]:
    """The traced window (:data:`chipbench.trace.WINDOW`) of the events."""
    return tr.window_of([e[:3] for e in ev["host"]])


def spans(ev: Dict[str, Any], t0: int, t1: int) -> List[Span]:
    """The ``serve.*`` spans that overlap ``[t0, t1]``, unclipped, by
    start (an outer span before the spans it holds)."""
    out = [{"name": e[0], "meta": dict(e[3]) if len(e) > 3 else {},
            "start": e[1], "end": e[1] + e[2]}
           for e in ev["host"] if e[0].startswith(PREFIX) and e[1] < t1 and e[1] + e[2] > t0]
    out.sort(key=lambda s: (s["start"], -s["end"]))
    return out


def label(s: Span) -> str:
    """A span's name, with the program a ``serve.wait`` waited on."""
    prog = s["meta"].get("program")
    return f"{s['name']}({prog})" if prog else s["name"]


def gaps(intervals, mods, host, sp: Sequence[Span]) -> Iterator[Tuple[str, float]]:
    """:func:`chipbench.trace._gaps`, except that a gap between programs
    is labelled by the innermost ``serve.*`` span (not one of
    :data:`OUTER`) that covers more than half of it, where there is one:
    ``<program before> -> <program after> | span: <span>``.  Elsewhere
    the label is that of :func:`chipbench.trace._gaps` over the host
    events other than spans."""
    intervals = [(a, b) for a, b in intervals if b > a]
    others = [h for h in host if not h[0].startswith(PREFIX)]
    inner = [s for s in sp if s["name"] not in OUTER]
    starts = [s["start"] for s in inner]
    longest = max((s["end"] - s["start"] for s in inner), default=0)
    for (a, b), (base, secs) in zip(intervals, tr._gaps(intervals, mods, others)):
        if base.startswith("inside "):
            yield base, secs
            continue
        best = None
        lo = bisect.bisect_left(starts, a - longest)
        for s in inner[lo:bisect.bisect_left(starts, b)]:
            cover = min(s["end"], b) - max(s["start"], a)
            if 2 * cover > b - a and (best is None
                                      or s["end"] - s["start"] < best["end"] - best["start"]):
                best = s
        if best is None:
            yield base, secs
        else:
            yield f"{base.split(' | ', 1)[0]} | span: {label(best)}", secs


def reduce(ev: Dict[str, Any], t0: Optional[int] = None, t1: Optional[int] = None,
           top: int = 10) -> Optional[Dict[str, Any]]:
    """:func:`chipbench.trace.reduce` of the window, plus ``spans``,
    ``scope_s`` (per program, device self time per scope; ``-`` for
    operations outside every scope), top ``device_ops`` labelled
    ``<program>/<scope>/<op>``, and ``idle_gaps`` labelled by :func:`gaps`."""
    if t0 is None or t1 is None:
        w = window(ev)
        if w is None:
            return None
        t0, t1 = w
    out = tr.reduce(plain(ev), t0, t1, top)
    if out is None:
        return None
    sp = spans(ev, t0, t1)
    scope_s: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    ops: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    host = sorted(tr._clip([e[:3] for e in ev["host"] if e[0] != tr.WINDOW], t0, t1),
                  key=lambda e: e[1])
    n_dev = 0
    for _plane, lines in sorted(ev["devices"].items()):
        op_ev = [((name, path), max(s, t0), min(s + d, t1))
                 for name, s, d, path in lines.get("XLA Ops", [])
                 if min(s + d, t1) > max(s, t0)]
        mods = sorted(tr._clip(lines.get("XLA Modules", []), t0, t1), key=lambda e: e[1])
        if not op_ev and not mods:
            continue
        n_dev += 1
        starts = [m[1] for m in mods]
        for (name, path), a, _b, own in tr._self_times(op_ev):
            i = bisect.bisect_right(starts, a) - 1
            prog = tr.program_name(mods[i][0]) if i >= 0 and mods[i][2] > a else "?"
            scope = scope_of(path) or "-"
            scope_s[prog][scope] += own / 1e9
            ops[f"{prog}/{scope}/{tr.op_name(name)}"] += own / 1e9
        busy = tr._union([(a, b) for _, a, b in (op_ev or mods)])
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        for lab, secs in gaps(list(zip(edges[0::2], edges[1::2])), mods, host, sp):
            idle[lab] += secs
    out["spans"] = sp
    out["scope_s"] = {p: {k: v / n_dev for k, v in d.items()} for p, d in scope_s.items()}
    out["device_ops"] = sorted(([k, v / n_dev] for k, v in ops.items()),
                               key=lambda kv: -kv[1])[:top]
    out["idle_gaps"] = sorted(([k, v / n_dev] for k, v in idle.items()),
                              key=lambda kv: -kv[1])[:top]
    return out


def traced_spans(rec) -> Optional[List[Span]]:
    """The ``serve.*`` spans of the run's traced window, read once from
    the trace the harness wrote and kept on ``rec``; ``None`` where the
    run was not traced or the program wrote no spans."""
    if not hasattr(rec, "serve_spans"):
        rec.serve_spans = _read_spans(rec) or None
    return rec.serve_spans


def _read_spans(rec) -> List[Span]:
    from chipbench import harness
    if not getattr(rec, "traced_calls", None):
        return []
    try:
        ev = extract(harness.TRACE_DIR, devices=False)
    except FileNotFoundError:
        return []
    w = window(ev)
    return spans(ev, *w) if w else []
