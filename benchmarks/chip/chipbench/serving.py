"""Arithmetic over a serving run's records, shared by the metric readers.

A run's records hold, for every request of the window, its prompt, its
served tokens and its host-clock stamps, and for every decode dispatch the
rows it advanced and the tokens they made (the program's ``dispatch_log``).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from chipbench import work

# the program's jitted functions, as the trace names them
PREFILL, DECODE = "prefill_chunk", "serve_step"


def needed(rec, reqs: List[Dict[str, Any]]) -> Dict[str, int]:
    """Operations that ``reqs`` needed: each prompt once, and every
    served token after the first."""
    tot = {"prefill_flops": 0, "decode_flops": 0}
    for r in reqs:
        w = work.request_work(rec.shapes, int(r["prompt"].size), len(r["generated"]))
        tot["prefill_flops"] += w["prefill_flops"]
        tot["decode_flops"] += w["decode_flops"]
    return tot


def traced_work(rec, program: str) -> Dict[str, int]:
    """Operations and bytes that the traced calls of ``program`` were
    asked for.  A prefill chunk of ``n`` tokens from position ``s`` needs
    its tokens through every layer, causal attention over ``s + i`` keys,
    its KV written and the prefix's KV read, and the weights once where
    it starts a prompt (``s == 0``).  A decode dispatch needs the weights
    once per step that some row made a token in, and for each token its
    layers, head, context read and append."""
    s = rec.shapes
    kv = work.kv_bytes_per_position(s)
    tot = {"flops": 0, "bytes": 0, "tokens": 0, "calls": 0}
    for c in rec.traced_calls:
        if c["program"] != program:
            continue
        tot["calls"] += 1
        if program == PREFILL:
            a, n = c["start"], c["length"]
            tot["flops"] += (2 * s.L * work.layer_matmul_params(s) * n
                             + 2 * s.q_dim * s.L * ((a + n) * (a + n + 1) - a * (a + 1)))
            tot["bytes"] += (a + n) * kv + (work.weight_bytes(s) if a == 0 else 0)
            tot["tokens"] += n
        else:
            made = [k for _, k in c["rows"]]
            tot["bytes"] += max(made, default=0) * work.weight_bytes(s)
            for fill, k in c["rows"]:
                for j in range(k):
                    tot["flops"] += work.decode_token_flops(s, fill + j + 1)
                    tot["bytes"] += work.decode_token_bytes(s, fill + j)
                tot["tokens"] += k
    return tot


def program_seconds(rec, program: str) -> Optional[float]:
    """Device seconds of one jitted program in the trace, if it ran."""
    if rec.trace is None:
        return None
    t = rec.trace["program_s"].get(program)
    return t if t else None
