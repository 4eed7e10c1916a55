"""chip_smoke.py's phases on the smoke twin, on the CPU.

The script itself refuses to run anywhere but on a TPU; these tests call
its phase functions directly, so its control flow and checks are covered
by every CPU run.  The four-host phase needs 4 emulated devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m pytest -q tests/test_chip_smoke.py
"""

import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]

needs_hosts = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4 "
           "(the multi-host CI leg)")

# small enough for CPU compiles, large enough that requests share the pool
SMOKE_SERVE = dict(num_blocks=160, block_size=16, max_context=1280)


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("qwen2.5-3b")


def test_main_refuses_the_cpu(chip_smoke, capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_requests_are_drawn_from_the_seed(chip_smoke, cfg):
    a = chip_smoke.make_requests(cfg.vocab_size, seed=5)
    b = chip_smoke.make_requests(cfg.vocab_size, seed=5)
    c = chip_smoke.make_requests(cfg.vocab_size, seed=6)
    assert len(a) == chip_smoke.N_REQUESTS
    assert all(chip_smoke.PROMPT_MIN <= r.prompt.size <= chip_smoke.PROMPT_MAX
               for r in a)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert not all(np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a, c))


def test_check_served_rejects_short_and_out_of_vocab_output(chip_smoke, cfg):
    reqs = chip_smoke.make_requests(cfg.vocab_size, seed=0, n=2, max_new=3)
    chip_smoke.check_served({0: [1, 2, 3], 1: [4, 5, 6]}, reqs,
                            cfg.vocab_size)
    with pytest.raises(AssertionError, match="budget"):
        chip_smoke.check_served({0: [1, 2], 1: [4, 5, 6]}, reqs,
                                cfg.vocab_size)
    with pytest.raises(AssertionError, match="outside"):
        chip_smoke.check_served({0: [1, 2, cfg.vocab_size], 1: [4, 5, 6]},
                                reqs, cfg.vocab_size)
    with pytest.raises(AssertionError, match="served"):
        chip_smoke.check_served({0: [1, 2, 3]}, reqs, cfg.vocab_size)


@pytest.mark.parametrize("engine", ["serve_paged", "serve_per_slot"])
def test_serve_phase_on_the_smoke_twin(chip_smoke, cfg, engine):
    stats = getattr(chip_smoke, engine)(cfg, 0, **SMOKE_SERVE)
    assert 0 <= stats["logits_rel_err"] <= chip_smoke.LOGITS_RTOL
    for run in ("cold", "warm"):
        assert stats[f"{run}_tok_per_s"] > 0
        assert stats[f"{run}_compile_s"] >= 0
    # the warm run reuses every program the cold run compiled
    assert stats["warm_compile_s"] == 0


def test_logits_check_catches_shifted_logits(chip_smoke, cfg, monkeypatch):
    """Served logits that are off by one vocabulary position fail the
    check: the tolerance is far below a real mismatch."""
    from repro.launch.serve import ServeLoop
    loop = ServeLoop(cfg, slots=1, max_len=256, seed=0)
    prompt = chip_smoke.make_requests(cfg.vocab_size, 0, n=1,
                                      hi=200)[0].prompt
    assert chip_smoke.check_prefill_logits(loop, prompt) < 1e-2
    run = loop.run

    def run_and_shift(reqs):
        out = run(reqs)
        loop.last_prefill_logits = jnp.roll(loop.last_prefill_logits, 1)
        return out

    monkeypatch.setattr(loop, "run", run_and_shift)
    with pytest.raises(AssertionError, match="differ"):
        chip_smoke.check_prefill_logits(loop, prompt)


def test_single_device_train_phase(chip_smoke, cfg):
    out = chip_smoke.train_phase(cfg, 0, mesh_shape=(1, 1), batch=4,
                                 seq_len=64, steps=3)
    assert len(out["losses"]) == 3 and np.isfinite(out["losses"]).all()
    assert len(out["devices"]) == 1


@needs_hosts
def test_four_host_phase_matches_one_device(chip_smoke, cfg):
    out = chip_smoke.four_chip_phase(cfg, 0, batch=8, seq_len=64, steps=3)
    assert len(out["twin_hosts4"]["devices"]) == 4
    assert len(out["full_hosts4"]["devices"]) == 4
    assert out["twin_single"]["devices"] == [0]


def test_depth_cut_keeps_published_widths(chip_smoke):
    from repro.configs import get_config
    full = get_config("qwen2.5-3b")
    twin = chip_smoke.depth_cut(full)
    assert twin.num_layers == 2
    assert (twin.d_model, twin.vocab_size, twin.d_ff) == (
        full.d_model, full.vocab_size, full.d_ff)


# ------------------------------------------------------ the compile cache
@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    prev = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_compile_cache_follows_the_environment(tmp_path, monkeypatch,
                                               restore_cache_config):
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    # every compile is cached: the entry must land in the given directory
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    jax.jit(lambda x: x * 3 + 1).lower(
        jax.ShapeDtypeStruct((7, 5), jnp.float32)).compile()
    assert any(tmp_path.iterdir())


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_bring_up_report_line_is_json(chip_smoke, capsys):
    chip_smoke._report("x", {"a": np.float32(1.5)})
    line = capsys.readouterr().out.strip()
    assert line.startswith("[bring-up, not a benchmark] x: ")
    assert json.loads(line.split(": ", 1)[1]) == {"a": 1.5}
