"""Device time of one kernel, found by its name in the traced window."""

from __future__ import annotations

from typing import Any, Dict, Optional

from chipbench import spans, trace as tr


def kernel_seconds(ev: Dict[str, Any], kernel: str) -> Optional[float]:
    """Device self time of the operations whose name starts with
    ``kernel`` in the traced window of the events ``ev``
    (``spans.extract``), per device; None where none ran."""
    w = spans.window(ev)
    if w is None:
        return None
    t0, t1 = w
    total, n_dev = 0, 0
    for lines in ev["devices"].values():
        ops = [(name, max(s, t0), min(s + d, t1))
               for name, s, d, _path in lines.get("XLA Ops", [])
               if min(s + d, t1) > max(s, t0)]
        if not ops:
            continue
        n_dev += 1
        total += sum(own for name, _a, _b, own in tr._self_times(ops)
                     if tr.op_name(name).startswith(kernel))
    return total / n_dev / 1e9 if total else None
