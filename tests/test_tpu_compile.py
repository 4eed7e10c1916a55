"""Compiles for a described TPU v5e: the serving programs of a published
config and the Pallas kernels, at real widths, with no chip attached.

The TPU compiler refuses what interpret mode accepts: a block shape not
aligned to the (8, 128) tiling, a primitive the kernel lowering lacks, a
program larger than the chip's memory.  Each compile here takes seconds
and needs no chip.  The topology is described inside a fixture, never at
import: only the worker that runs this file loads the TPU library.
"""

import dataclasses
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.launch.steps import make_paged_prefill_step, make_paged_serve_step
from repro.models import get_model

# one v5e chip: 16 GiB of HBM, of which the compiler lets a program use
# 15.75 GiB (the limit it names when it refuses one)
V5E_HBM_BYTES = 15.75 * 2 ** 30

# the chip_smoke.py serving shapes (qwen2.5-3b at published widths)
BLOCK_SIZE = 16
MAX_CONTEXT = 2048
NUM_BLOCKS = 640
CONCURRENCY = 8
PREFILL_CHUNK = 512
DECODE_STEPS = 4

# the serving shapes of the benchmark's qwen2.5-3b.longprompt cell
# (benchmarks/chip/configs/qwen2.5-3b.json)
CELL_CONCURRENCY = 32
CELL_MAX_CONTEXT = 4192
CELL_NUM_BLOCKS = 8320

# the benchmark's qwen3-moe-235b-a22b.longanswer cell: one chip's share
# (16 layers, experts 0-7 of 128) and its serving shapes; num_blocks was
# chosen to leave this much of the 15.75 GiB spare
MOE_FILE = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
            / "configs" / "qwen3-moe-235b-a22b.json")
MOE_SPARE_BYTES = 256 * 2 ** 20


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip can be written to the persistent
        # cache but never read back without one: keep it out of the cache
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def _assert_pool_updated_in_place(compiled, pool) -> None:
    """The stacked ``(L, NB, BS, C)`` k/v pools cross the layer scan as its
    carry: the optimized program holds no copy of a stacked pool, no array
    of one layer's whole ``(NB, BS, C)`` pool, and less scratch than one
    k pool, so neither pool is ever held twice."""
    L, NB, BS, C = pool["k"].shape
    stacked = f"bf16[{L},{NB},{BS},{C}]"
    hlo = compiled.as_text()
    # "%name = <shape or (tuple of shapes)> <opcode>(operands), ..."
    ops = re.findall(r"= (\(.*?\)|\S+) ([\w-]+)\(", hlo)
    assert not [op for shape, op in ops
                if op in ("copy", "copy-start") and stacked in shape]
    assert f"bf16[{NB},{BS},{C}]" not in hlo
    k_bytes = L * NB * BS * C * pool["k"].dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < k_bytes


@pytest.fixture(scope="module")
def qwen(one_chip):
    """qwen2.5-3b at published widths in bf16, as shapes on one chip."""
    cfg = get_config("qwen2.5-3b")
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0), jnp.bfloat16,
                           abstract=True)
    pool, _ = model.init_paged_decode(NUM_BLOCKS, BLOCK_SIZE, abstract=True)
    return cfg, model, _on(one_chip, params), _on(one_chip, pool)


def test_paged_decode_fits_one_v5e(one_chip, qwen):
    cfg, model, params, pool = qwen
    C, W = CONCURRENCY, MAX_CONTEXT // BLOCK_SIZE
    i32 = jnp.int32
    step = jax.jit(make_paged_serve_step(model, DECODE_STEPS),
                   donate_argnums=(2,))
    compiled = step.lower(
        params, {"tokens": _sds(one_chip, (C, 1), i32)}, pool,
        _sds(one_chip, (C, W), i32), _sds(one_chip, (C,), i32),
        _sds(one_chip, (C,), i32), _sds(one_chip, (C,), jnp.bool_),
        _sds(one_chip, (C,), i32), _sds(one_chip, (), i32)).compile()
    assert cfg.num_layers == 36 and cfg.vocab_size == 151936
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_paged_prefill_chunk_fits_one_v5e(one_chip, qwen):
    _, model, params, pool = qwen
    W = MAX_CONTEXT // BLOCK_SIZE
    i32 = jnp.int32
    step = jax.jit(make_paged_prefill_step(model), donate_argnums=(2,))
    compiled = step.lower(
        params, {"tokens": _sds(one_chip, (1, PREFILL_CHUNK), i32)}, pool,
        _sds(one_chip, (W,), i32), _sds(one_chip, (), i32),
        _sds(one_chip, (), i32)).compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


@pytest.fixture(scope="module")
def qwen_cell(one_chip):
    """qwen2.5-3b tied, as published and as the benchmark serves it, with
    the cell's 8,320-block pool, as shapes on one chip."""
    cfg = dataclasses.replace(get_config("qwen2.5-3b"), tie_embeddings=True)
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0), jnp.bfloat16,
                           abstract=True)
    pool, _ = model.init_paged_decode(CELL_NUM_BLOCKS, BLOCK_SIZE,
                                      abstract=True)
    return model, _on(one_chip, params), _on(one_chip, pool)


def test_paged_decode_reads_the_pool_through_the_kernel(one_chip, qwen_cell):
    """At the benchmark cell's serving shapes, the paged decode program
    lowered for a v5e fits the chip, holds the Pallas paged-attention
    kernel, and no longer materializes any row's whole (32, 4192, ...)
    view, nor the (32, 262, 16, ...) gather that built it; the pool is
    updated in place through the step scan and the layer scan."""
    model, params, pool = qwen_cell
    C, W = CELL_CONCURRENCY, CELL_MAX_CONTEXT // BLOCK_SIZE
    i32 = jnp.int32
    step = jax.jit(make_paged_serve_step(model, DECODE_STEPS),
                   donate_argnums=(2,))
    compiled = step.lower(
        params, {"tokens": _sds(one_chip, (C, 1), i32)}, pool,
        _sds(one_chip, (C, W), i32),
        _sds(one_chip, (C,), i32), _sds(one_chip, (C,), i32),
        _sds(one_chip, (C,), jnp.bool_), _sds(one_chip, (C,), i32),
        _sds(one_chip, (), i32)).compile()
    # the pool is donated: its output aliases its argument
    assert compiled.memory_analysis().peak_memory_in_bytes < V5E_HBM_BYTES
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert f"[{C},{W * BLOCK_SIZE}," not in hlo
    assert f"[{C},{W},{BLOCK_SIZE}," not in hlo
    _assert_pool_updated_in_place(compiled, pool)


@pytest.fixture(scope="module")
def qwen_cell_prefill(one_chip, qwen_cell):
    """The cell's 512-token prefill chunk program, compiled for a v5e."""
    model, params, pool = qwen_cell
    W = CELL_MAX_CONTEXT // BLOCK_SIZE
    i32 = jnp.int32
    step = jax.jit(make_paged_prefill_step(model), donate_argnums=(2,))
    return step.lower(
        params, {"tokens": _sds(one_chip, (1, PREFILL_CHUNK), i32)}, pool,
        _sds(one_chip, (W,), i32), _sds(one_chip, (), i32),
        _sds(one_chip, (), i32)).compile()


def test_paged_prefill_chunk_updates_the_cell_pool_in_place(
        qwen_cell, qwen_cell_prefill):
    """At the benchmark cell's shapes, a 512-token prefill chunk fits the
    chip and writes the stacked pool in place: no copy of it and no one
    layer's whole pool."""
    _, _, pool = qwen_cell
    compiled = qwen_cell_prefill
    assert compiled.memory_analysis().peak_memory_in_bytes < V5E_HBM_BYTES
    _assert_pool_updated_in_place(compiled, pool)


def test_paged_prefill_chunk_reads_the_pool_through_the_kernel(
        qwen_cell, qwen_cell_prefill):
    """At the benchmark cell's shapes, the prefill chunk program lowered
    for a v5e holds the Pallas chunk-attention kernel, reading the
    stacked pool, and neither the (1, 16, 512, 4704) f32 score tensor of
    the dense masked attention nor the request's gathered (1, 4192, ...)
    view; it fits the chip and updates the pool in place."""
    _, _, pool = qwen_cell
    compiled = qwen_cell_prefill
    W = CELL_MAX_CONTEXT // BLOCK_SIZE
    hlo = compiled.as_text()
    pages = f"bf16[{','.join(map(str, pool['k'].shape))}]"
    assert any("tpu_custom_call" in line and pages in line
               for line in hlo.splitlines())
    scores = f"f32[1,16,{PREFILL_CHUNK},{W * BLOCK_SIZE + PREFILL_CHUNK}]"
    assert scores not in hlo
    assert f"[1,{W * BLOCK_SIZE}," not in hlo
    # the pool is donated: its output aliases its argument
    assert compiled.memory_analysis().peak_memory_in_bytes < V5E_HBM_BYTES
    _assert_pool_updated_in_place(compiled, pool)


@pytest.fixture(scope="module")
def moe_share(one_chip):
    """The MoE cell's program config, shapes on one chip, and serving
    sizes."""
    spec = json.loads(MOE_FILE.read_text())
    cfg = dataclasses.replace(
        get_config("qwen3-moe-235b-a22b"),
        num_layers=spec["num_hidden_layers"], experts_held=spec["num_experts"],
        expert_offset=spec["expert_offset"])
    model = get_model(cfg)
    s = spec["serve"]
    params, _ = model.init(jax.random.PRNGKey(0), jnp.bfloat16, abstract=True)
    pool, _ = model.init_paged_decode(s["num_blocks"], s["block_size"],
                                      abstract=True)
    return model, _on(one_chip, params), _on(one_chip, pool), s


@pytest.mark.parametrize("program", ["serve_step", "prefill_chunk"])
def test_moe_share_programs_fit_one_v5e(one_chip, moe_share, program):
    """Both serving programs of the MoE cell compile for a v5e with 256
    MiB to spare; both read the pool through a Pallas paged-attention
    kernel (GQA 16: 64 query heads on 4), the expert layer is the
    compiler's grouped matmul (``ragged-dot``), and both update the
    stacked pool in place."""
    model, params, pool, s = moe_share
    C, W = s["concurrency"], s["max_context"] // s["block_size"]
    i32 = jnp.int32
    if program == "serve_step":
        step = jax.jit(make_paged_serve_step(model, s["decode_steps"]),
                       donate_argnums=(2,))
        compiled = step.lower(
            params, {"tokens": _sds(one_chip, (C, 1), i32)}, pool,
            _sds(one_chip, (C, W), i32), _sds(one_chip, (C,), i32),
            _sds(one_chip, (C,), i32), _sds(one_chip, (C,), jnp.bool_),
            _sds(one_chip, (C,), i32), _sds(one_chip, (), i32)).compile()
    else:
        step = jax.jit(make_paged_prefill_step(model), donate_argnums=(2,))
        compiled = step.lower(
            params, {"tokens": _sds(one_chip, (1, s["prefill_chunk"]), i32)},
            pool, _sds(one_chip, (W,), i32), _sds(one_chip, (), i32),
            _sds(one_chip, (), i32)).compile()
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert peak < V5E_HBM_BYTES - MOE_SPARE_BYTES
    hlo = compiled.as_text()
    assert "ragged-dot" in hlo
    pages = f"bf16[{','.join(map(str, pool['k'].shape))}]"
    assert any("tpu_custom_call" in line and pages in line
               for line in hlo.splitlines())
    _assert_pool_updated_in_place(compiled, pool)


def _kernel_cases():
    from repro.kernels.flash_attention.ops import mha
    from repro.kernels.linear_scan.ops import ssd, wkv
    from repro.kernels.sched_matmul.ops import scheduled_matmul
    bf, f32 = jnp.bfloat16, jnp.float32
    attn = (1, 4096, 16, 128)          # qwen2.5-3b heads at 4k tokens
    rwkv = (1, 40, 4096, 64)           # rwkv6-3b: 40 wkv heads of 64
    mamba = (1, 80, 4096, 64)          # zamba2-2.7b: 80 heads, state 64
    return {
        "sched_matmul": (lambda a, b: scheduled_matmul(a, b),
                         [((2048, 2048), bf), ((2048, 11008), bf)]),
        "flash_attention": (lambda q, k, v: mha(q, k, v, causal=True),
                            [(attn, bf)] * 3),
        "linear_scan_wkv": (lambda r, k, v, lw, u: wkv(r, k, v, lw, u,
                                                       chunk=32),
                            [(rwkv, bf)] * 3 + [(rwkv, f32),
                                                ((40, 64), f32)]),
        "linear_scan_ssd": (lambda c, b, x, la: ssd(c, b, x, la, chunk=32),
                            [(mamba, bf)] * 3 + [(mamba[:3], f32)]),
    }


@pytest.mark.parametrize("name", ["sched_matmul", "flash_attention",
                                  "linear_scan_wkv", "linear_scan_ssd"])
def test_pallas_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_cases()[name]
    args = [_sds(one_chip, s, d) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
