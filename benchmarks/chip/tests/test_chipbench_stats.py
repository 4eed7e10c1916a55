"""Tails and rates are taken over all requests of the window."""

import statistics
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench_testing import BENCH
from chipbench import manifest
from chipbench.stats import percentile, spread


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
def test_percentile_matches_numpy_linear(q):
    xs = np.random.default_rng(3).lognormal(0, 1, 257)
    assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_spread_uses_statistics_quartiles():
    xs = [10.0, 11.0, 12.0, 13.0, 20.0, 9.0]
    q1, med, q3 = statistics.quantiles(xs, n=4)
    assert spread(xs) == pytest.approx((q3 - q1) / med)


def _rec():
    """Two waves: tails must cover both, never a median of per-wave tails."""
    reqs = []
    for w, (t0, ttfts) in enumerate([(0.0, [0.1, 0.2, 0.3]),
                                     (10.0, [1.0, 2.0, 9.0])]):
        for i, f in enumerate(ttfts):
            reqs.append({"wave": w, "t_arrive": t0, "t_admit": t0,
                         "t_first": t0 + f, "t_finish": t0 + f + 0.5 * (i + 1),
                         "generated": [1] * (i + 2), "preemptions": 0,
                         "prompt": np.zeros(4, np.int32), "max_new": i + 2})
    return SimpleNamespace(requests=reqs, interval_s=20.0)


def test_tails_cover_every_request_of_every_wave():
    rec = _rec()
    ttft = manifest.module("metrics", "ttft_p95_ms", BENCH).read(rec)
    allv = [(r["t_first"] - r["t_arrive"]) * 1e3 for r in rec.requests]
    assert ttft == pytest.approx(np.percentile(allv, 95))
    tpot = manifest.module("metrics", "tpot_p95_ms", BENCH).read(rec)
    per = [(r["t_finish"] - r["t_first"]) * 1e3 / (len(r["generated"]) - 1)
           for r in rec.requests]
    assert tpot == pytest.approx(np.percentile(per, 95))


def test_rate_is_all_tokens_over_the_whole_interval():
    rec = _rec()
    rate = manifest.module("metrics", "serve_tok_s", BENCH).read(rec)
    assert rate == pytest.approx(sum(len(r["generated"]) for r in rec.requests) / 20.0)


@pytest.mark.parametrize("gaps, failed, want", [
    ([0.1, 0.4], 0, True),          # every number within its limit
    ([0.1, 0.6], 0, False),         # the widest gap over its limit
    ([0.1, 0.2], 1, False),         # a request that never finished
    ([], 0, False),                 # nothing checked
])
def test_judge(gaps, failed, want):
    from chipbench.correct import judge
    ok, numbers = judge({"logit_gap": 0.5}, np.asarray(gaps, float), failed)
    assert ok is want
    assert list(numbers) == ["logit_gap", "failed_requests", "checked_tokens"]
    assert numbers["checked_tokens"]["value"] == len(gaps)
