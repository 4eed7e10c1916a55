"""``correct`` comes out false when the timed path is broken underneath,
and for the control: the float8 reference in the program's place, judged
by the same comparison as the program.

The phi3 smoke twin's limit (0.007, in ``data/phi3-mini-3.8b-smoke.json``)
was set from readings on the CPU of ``phi3-smoke.longanswer`` on seeds
1-4, 11, 12 and 2**40 + 17: the program's widest gap 0.0014-0.0030, the
control's 0.0121-0.0420.  (The qwen twin's tied 64-wide embedding makes
the current token win every logit, so its gaps read 0 for program and
control alike: it cannot show the control failing.)"""

import json
from pathlib import Path

import jax.numpy as jnp
import pytest

import repro.launch.serve as serve_mod
from repro.launch import steps
from chipbench_testing import BENCH, run_smoke, smoke_root
from chipbench import harness, manifest

CELL = "phi3-smoke.longanswer"


def _broken(kind):
    """``make_paged_serve_step`` with one fault planted in its output."""
    def make(model, num_steps):
        real = steps.make_paged_serve_step(model, num_steps)
        V = model.cfg.vocab_size

        def step(params, batch, cache, *rest):
            toks, new_cache, ln, act, rem = real(params, batch, cache, *rest)
            if kind == "state_unchanged":          # KV appends dropped
                new_cache = cache
            elif kind == "token_altered":          # off by one where produced
                toks = jnp.where(toks >= 0, (toks + 1) % V, toks)
            elif kind == "half_batch":             # upper half of rows not computed
                B = toks.shape[0]
                keep = (jnp.arange(B) < B // 2)[:, None] | (toks < 0)
                toks = jnp.where(keep, toks, 0)
            return toks, new_cache, ln, act, rem
        return step
    return make


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return smoke_root(tmp_path_factory.mktemp("faults"))


@pytest.mark.parametrize("kind", ["state_unchanged", "token_altered", "half_batch"])
def test_fault_is_not_correct(root, kind, monkeypatch):
    monkeypatch.setattr(serve_mod, "make_paged_serve_step", _broken(kind))
    out, _ = run_smoke(root, CELL, seed=11)
    assert out["correct"] is False
    gap = out["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def _compared_statistics():
    """The gap statistics that the benchmark's cells compare."""
    man = manifest.load(BENCH.parents[1])
    return sorted({k for c in man["configs"]
                   for k in manifest.config(man, c["name"], BENCH.parents[1])
                   ["check"]["limits"]})


def test_control_is_not_correct(root, tmp_path):
    """For each statistic that a cell compares, the smoke twin compares it
    too and the control's verdict, by the harness's own comparison, is
    false while the program's is true."""
    import jax
    import time
    src = json.loads((root / "BENCHMARK.json").read_text())
    twin = next(c for c in src["configs"] if c["name"] == "phi3-mini-3.8b-smoke")
    base = json.loads(Path(twin["file"]).read_text())
    stats = _compared_statistics()
    assert stats
    for stat in stats:
        base["check"]["limits"] = {stat: {"logit_gap": 0.007}[stat]}
        path = tmp_path / f"twin-{stat}.json"
        path.write_text(json.dumps(base))
        man = dict(src, configs=[dict(c, file=str(path)) if c is twin else c
                                 for c in src["configs"]])
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
        out, rec = harness.run_cell(CELL, seed=12, seconds=0, trace=False,
                                    devices=jax.devices()[:1], peak={},
                                    smoke=True, t_start=time.perf_counter(),
                                    root=tmp_path, control="fp8")
        assert out["correct"] is True, out["checks"]                # the program
        control = rec.check["control"]
        assert control["correct"] is False                          # the control
        assert control["numbers"][stat]["value"] > control["numbers"][stat]["limit"]
