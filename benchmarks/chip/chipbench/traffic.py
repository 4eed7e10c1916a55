"""The one traffic generator: reads a mix's data file and yields requests.

A mix is data (``traffic/<name>.json``).  ``arrivals: "waves"`` sends
``wave_size`` requests together; the next wave starts when the last one
has finished (offline batch inference).  Prompt and answer lengths are
the quantiles ``(i + 1/2) / n`` of their distributions, paired and, in
each wave, ordered by fixed permutations: every seed sends the same
lengths in the same order, and the seed draws only the token ids.  So
the seed never changes the amount or the shape of the work.

Distributions: ``lognormal`` (``median``, ``sigma``) and ``uniform``,
both clipped to ``[min, max]`` and rounded to whole tokens.  The
log-normal is the length law of the repo's ``SyntheticCorpus``.
"""

from __future__ import annotations

import statistics
from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np

PAIRING_SEED = 0
ORDER_SEED = 1


def quantile_lengths(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` whole lengths at the quantiles ``(i + 1/2) / n`` of ``spec``."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif spec["dist"] == "uniform":
        x = lo + u * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


@dataclass
class Wanted:
    """One request as the traffic defines it."""

    wave: int
    index: int
    prompt: np.ndarray            # int32 token ids
    max_new: int


class Traffic:
    """Requests of one mix for one seed, wave after wave."""

    def __init__(self, spec: Dict[str, Any], vocab_size: int, seed: int):
        if spec.get("arrivals") != "waves":
            raise ValueError(f"unknown arrivals {spec.get('arrivals')!r}")
        self.spec = spec
        self.vocab_size = int(vocab_size)
        self.seed = int(seed)
        n = int(spec["wave_size"])
        prompts = quantile_lengths(spec["prompt_tokens"], n)
        answers = quantile_lengths(spec["output_tokens"], n)
        pair = np.random.default_rng(PAIRING_SEED).permutation(n)
        self.lengths = [(int(p), int(a)) for p, a in zip(prompts, answers[pair])]

    @property
    def max_total(self) -> int:
        return max(p + a for p, a in self.lengths)

    def wave(self, w: int) -> List[Wanted]:
        order = np.random.default_rng([ORDER_SEED, w]).permutation(len(self.lengths))
        rng = np.random.default_rng([self.seed, w])
        out = []
        for i in order:
            p, a = self.lengths[i]
            toks = rng.integers(0, self.vocab_size, size=p, dtype=np.int64)
            out.append(Wanted(wave=w, index=int(i),
                              prompt=toks.astype(np.int32), max_new=a))
        return out
