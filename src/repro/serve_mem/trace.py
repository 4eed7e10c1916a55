"""Deterministic long/short request-mix traces for serving tests + bench.

The paged engine's acceptance scenario — O(100) concurrent requests with
a long/short prompt mix under memory pressure — needs one trace both the
test suite and ``benchmarks/serve_adapt.py``'s paged-concurrency stage
agree on, or the
bench gates a workload the tests never exercised.  ``make_mixed_trace``
is that single source: seeded, host-only, and returning plain
``(prompt, max_new)`` material the caller wraps into ``Request``s.
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

__all__ = ["TraceItem", "make_mixed_trace"]


@dataclasses.dataclass(frozen=True)
class TraceItem:
    """One request blueprint: a prompt array and its generation budget."""

    rid: int
    prompt: np.ndarray          # (P,) int32
    max_new: int


def make_mixed_trace(n: int, *, vocab_size: int, seed: int = 0,
                     long_frac: float = 0.25,
                     short_len: tuple = (4, 12),
                     long_len: tuple = (40, 57),
                     short_new: tuple = (4, 9),
                     long_new: tuple = (8, 17)) -> List[TraceItem]:
    """``n`` requests, a ``long_frac`` fraction of them long-prompt.

    Long requests are dealt round-robin through the trace (every
    ``1/long_frac``-th position) rather than randomly placed, so every
    window of the trace carries the mix — the "sustained" part of the
    concurrency gate.  Lengths/budgets are drawn uniformly from the
    half-open ranges; everything derives from ``seed`` alone.
    """
    if not 0.0 <= long_frac <= 1.0:
        raise ValueError(f"long_frac must be in [0, 1], got {long_frac}")
    rng = np.random.default_rng(seed)
    stride = int(round(1.0 / long_frac)) if long_frac > 0 else 0
    items: List[TraceItem] = []
    for i in range(n):
        is_long = stride > 0 and i % stride == 0
        lo, hi = long_len if is_long else short_len
        nlo, nhi = long_new if is_long else short_new
        prompt = rng.integers(0, vocab_size,
                              size=int(rng.integers(lo, hi))).astype(np.int32)
        items.append(TraceItem(rid=i, prompt=prompt,
                               max_new=int(rng.integers(nlo, nhi))))
    return items
