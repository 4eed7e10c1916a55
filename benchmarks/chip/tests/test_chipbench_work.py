"""Operation and byte counts against the program's parameter count and a
count by hand."""

import dataclasses
import json

import pytest

from chipbench_testing import BENCH
from chipbench import work
from repro.configs import get_config

CONFIGS = {"qwen2.5-3b": 3_085_938_688, "phi3-mini-3.8b": 3_821_079_552}


def _shapes(name):
    return work.Shapes.of(json.loads((BENCH / "configs" / f"{name}.json").read_text()))


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_param_count_matches_the_program(name):
    s = _shapes(name)
    cfg = dataclasses.replace(get_config(name), tie_embeddings=s.tied)
    assert work.param_count(s) == cfg.param_count() == CONFIGS[name]


def test_param_count_by_hand():
    # qwen2.5-3b, tied: embedding, 36 layers of q/k/v/o with q/k/v bias,
    # two norms and a SwiGLU MLP, the final norm
    d, L, kv, f, V = 2048, 36, 256, 11008, 151936
    layer = d * d + 2 * d * kv + d * d + d + 2 * kv + 2 * d + 3 * d * f
    assert work.param_count(_shapes("qwen2.5-3b")) == V * d + L * layer + d
    # phi3-mini: MHA, no bias, untied head
    d, L, f, V = 3072, 32, 8192, 32064
    layer = 4 * d * d + 2 * d + 3 * d * f
    assert work.param_count(_shapes("phi3-mini-3.8b")) == 2 * V * d + L * layer + d


def test_prefill_and_decode_counts_by_hand():
    s = _shapes("phi3-mini-3.8b")
    P = 3
    mm = 4 * 3072 * 3072 + 3 * 3072 * 8192
    # layers for every token, causal attention 1 + 2 + 3 keys, head once
    want = 2 * 32 * mm * P + 4 * 3072 * 32 * (1 + 2 + 3) + 2 * 3072 * 32064
    assert work.prefill_flops(s, P) == want
    assert work.decode_token_flops(s, 10) == 2 * (32 * mm + 3072 * 32064) + 4 * 10 * 3072 * 32
    kv_pos = 2 * 2 * 32 * 3072                       # k and v, bf16, 32 layers
    assert work.kv_bytes_per_position(s) == kv_pos == 393_216
    assert work.decode_token_bytes(s, 10) == 11 * kv_pos + 2 * 3072
    assert work.weight_bytes(s) == 2 * (work.param_count(s) - 32064 * 3072)
    q = _shapes("qwen2.5-3b")
    assert work.kv_bytes_per_position(q) == 36_864
    assert work.weight_bytes(q) == 2 * work.param_count(q)


def test_request_work_sums_its_tokens():
    s = _shapes("qwen2.5-3b")
    w = work.request_work(s, 100, 4)
    assert w["decode_tokens"] == 3
    assert w["decode_flops"] == sum(work.decode_token_flops(s, 100 + j) for j in (1, 2, 3))
    assert w["prefill_flops"] == work.prefill_flops(s, 100)


def test_roofline_takes_the_longer_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(1000, 50, peak) == 10.0
    assert work.least_time(100, 500, peak) == 50.0
