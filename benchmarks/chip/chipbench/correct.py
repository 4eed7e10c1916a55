"""The comparison that decides ``correct`` for a served model.

A sample of the finished requests, drawn from the seed, goes through the
float32 reference with its prompt and its served tokens.  At each
position whose next token was served, the gap is the reference's best
logit minus the reference's logit of the served token; the number
compared is the widest gap over the sample.  Greedy decoding at the
configuration's precision keeps them near the rounding of bfloat16; a
wrong mask, position, cache block or token makes them the size of the
logits' spread.

The control (``quant="fp8"``) reads the same gaps for the token that the
reference computed in float8 puts first at each position, and is judged
by the same limits: it has to come out not correct.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

BUCKET = 1024        # reference sequence lengths are padded to a multiple


def sample(requests: List[Dict[str, Any]], seed: int,
           tokens: int) -> List[Dict[str, Any]]:
    """Finished requests: the longest, one that was preempted (if any),
    then others in an order drawn from ``seed`` until the served tokens
    reach ``tokens``; never fewer than two requests where two exist."""
    done = [r for r in requests if r["generated"]]
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 0xC0])
    longest = max(done, key=lambda r: (r["prompt"].size + len(r["generated"]),
                                       -r["rid"]))
    rest = [r for r in done if r is not longest]
    rest = [rest[i] for i in rng.permutation(len(rest))]
    rest.sort(key=lambda r: r["preemptions"] == 0)     # stable: preempted first
    out, n = [longest], len(longest["generated"])
    for r in rest:
        if n >= tokens and len(out) >= 2:
            break
        out.append(r)
        n += len(r["generated"])
    return out


def _inputs(req: Dict[str, Any], max_context: int, max_new: int):
    P = int(req["prompt"].size)
    gen = np.asarray(req["generated"], np.int64)
    seq = np.concatenate([req["prompt"].astype(np.int64), gen[:-1]])
    n = int(seq.size)
    L = min(-(-n // BUCKET) * BUCKET, max_context)
    tokens = np.zeros(L, np.int32)
    tokens[:n] = seq
    at = np.full(max_new, P - 1, np.int32)
    at[:gen.size] = P - 1 + np.arange(gen.size)
    return tokens, at, gen


def gaps(ref_logits: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """Per row: best reference logit minus the reference logit of the
    chosen token (inf for a token outside the vocabulary)."""
    V = ref_logits.shape[1]
    ok = (chosen >= 0) & (chosen < V)
    picked = np.take_along_axis(ref_logits, np.clip(chosen, 0, V - 1)[:, None],
                                axis=1)[:, 0]
    return np.where(ok, ref_logits.max(axis=1) - picked, np.inf)


def served_gaps(cfg: Dict[str, Any], reference, weights, reqs,
                max_new: int, quant: Optional[str] = None):
    """(gap of each served token, gap of the control's choice or None)."""
    served, control = [], []
    for r in reqs:
        tokens, at, gen = _inputs(r, cfg["serve"]["max_context"], max_new)
        ref = reference.logits_at(weights, cfg, tokens, at)[:gen.size]
        served.append(gaps(ref, gen))
        if quant is not None:
            low = reference.logits_at(weights, cfg, tokens, at,
                                      quant=quant)[:gen.size]
            control.append(gaps(ref, low.argmax(axis=1)))
    cat = lambda xs: np.concatenate(xs) if xs else np.zeros(0)  # noqa: E731
    return cat(served), (cat(control) if quant is not None else None)


def _stats(x: np.ndarray) -> Dict[str, float]:
    return {"logit_gap": float(x.max()) if x.size else float("inf")}


def judge(limits: Dict[str, float], g: np.ndarray,
          failed: int) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """(verdict, numbers compared beside their limits) for the gaps ``g``
    of the checked tokens and ``failed`` unfinished requests."""
    read = _stats(g)
    numbers = {name: {"value": read[name], "limit": float(limit)}
               for name, limit in limits.items()}
    numbers["failed_requests"] = {"value": failed, "limit": 0}
    numbers["checked_tokens"] = {"value": int(g.size), "limit": 1}
    ok = (all(n["value"] <= n["limit"] for k, n in numbers.items()
              if k != "checked_tokens") and g.size >= 1)
    return bool(ok), numbers


def check_served(cfg: Dict[str, Any], reference, weights,
                 requests: List[Dict[str, Any]], seed: int, traffic,
                 quant: Optional[str] = None) -> Dict[str, Any]:
    """Numbers compared, each with its limit, and the verdict; the
    configuration's ``check.limits`` holds the limit of ``logit_gap``.
    With ``quant`` the control's gaps go through the same :func:`judge`,
    and ``control`` holds its verdict."""
    chk = cfg["check"]
    failed = sum(len(r["generated"]) != r["max_new"] for r in requests)
    reqs = sample(requests, seed, int(chk["sample_tokens"]))
    g, c = served_gaps(cfg, reference, weights, reqs,
                       int(traffic.spec["output_tokens"]["max"]), quant)
    ok, numbers = judge(chk["limits"], g, failed)
    out = {"correct": ok, "numbers": numbers, "gaps": _stats(g),
           "checked_requests": len(reqs),
           "checked_preempted": sum(r["preemptions"] > 0 for r in reqs)}
    if c is not None:
        c_ok, c_numbers = judge(chk["limits"], c, failed)
        out["control"] = {"correct": c_ok, "numbers": c_numbers,
                          "gaps": _stats(c)}
    return out
