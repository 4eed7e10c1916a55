"""Traffic mixes: one generator, driven by data, deterministic per seed."""

import json
import statistics

import numpy as np
import pytest

from chipbench_testing import BENCH
from chipbench.traffic import Traffic, quantile_lengths

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


def _spec(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    a = Traffic(_spec(mix), 32000, seed=2**40 + 3)
    b = Traffic(_spec(mix), 32000, seed=2**40 + 3)
    for w in range(3):
        for x, y in zip(a.wave(w), b.wave(w)):
            assert x.max_new == y.max_new
            np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_share_lengths_and_order_not_tokens(mix):
    """Every wave of every seed holds the same lengths; a wave's order is
    the same for every seed; the seed draws the token ids.  So the seed
    does not change the amount or shape of the work."""
    spec = _spec(mix)
    a, b = Traffic(spec, 32000, seed=1), Traffic(spec, 32000, seed=2)
    waves = [t.wave(w) for t in (a, b) for w in (0, 1)]
    sizes = [sorted((q.prompt.size, q.max_new) for q in wv) for wv in waves]
    assert all(s == sizes[0] for s in sizes)
    assert len(sizes[0]) == spec["wave_size"]
    orders = [[q.index for q in wv] for wv in waves]
    assert orders[0] == orders[2] and orders[1] == orders[3]
    assert orders[0] != orders[1]
    assert not np.array_equal(waves[0][0].prompt[:8], waves[2][0].prompt[:8])


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_follow_the_mix(mix):
    spec = _spec(mix)
    for key in ("prompt_tokens", "output_tokens"):
        d = spec[key]
        xs = quantile_lengths(d, spec["wave_size"])
        assert xs.min() >= d["min"] and xs.max() <= d["max"]
        if d["dist"] == "lognormal":
            assert abs(statistics.median(xs) - d["median"]) <= 0.1 * d["median"]
        else:
            assert abs(xs.mean() - (d["min"] + d["max"]) / 2) <= 1


def test_token_ids_lie_in_the_vocabulary():
    for q in Traffic(_spec(MIXES[0]), 100, seed=5).wave(0):
        assert q.prompt.dtype == np.int32
        assert q.prompt.min() >= 0 and q.prompt.max() < 100
