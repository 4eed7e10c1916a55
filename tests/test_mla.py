"""Latent attention (MLA), the group-limited sigmoid router, the shared
expert and the leading dense layers of the DeepSeek-V3 share, against
plain references.

The paged programs (chunked prefill, then decode through the latent pool,
by the block-table gather and by the Pallas kernel in interpret mode) are
checked against the benchmark's float32 reference of the share
(``benchmarks/chip/reference/mla_moe_decoder.py``), which computes
attention in its expanded multi-head form and does not call the program.
The absorbed form is checked against the expanded one; the latent kernel
against its oracle; the router against a NumPy transcription; YaRN
against its formula; and the shares of an expert layer against the uncut
layer.
"""

import dataclasses
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.kernels.paged_attention.ops import (paged_latent_attention,
                                               paged_latent_prefill_attention)
from repro.kernels.paged_attention.ref import (
    paged_latent_attention_ref, paged_latent_prefill_attention_ref)
from repro.launch.serve import PagedServeLoop, engine_for
from repro.models import get_model, mla, transformer
from repro.models.moe import moe_held, route, shared_expert

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
sys.path.insert(0, str(BENCH))

from chipbench import manifest  # noqa: E402

REF = manifest.module("reference", "mla_moe_decoder")
ENGINE = manifest.module("engines", "paged_serve_mla")
# the smoke twin's share as the benchmark describes it: experts 4-7 of 8
TWIN = json.loads((BENCH / "tests" / "data" / "deepseek-v3-smoke.json").read_text())
SMOKE = get_smoke_config("deepseek-v3")


def _twin():
    """The twin's program config (experts 4-7 held) and float32
    weights drawn by the reference, ten times the benchmark's scale so
    that at the twin's widths every part of a layer (the shared expert,
    the rope key, a routed expert) moves the logits by far more than
    float32 rounding."""
    cfg = ENGINE.program_config(TWIN, smoke=True)
    w = REF.make_weights(TWIN, 11, dtype=jnp.float32)
    return cfg, jax.tree.map(lambda a: a * 10, w)


@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel"])
def test_paged_prefill_then_decode_matches_the_reference(kernel):
    """Two prefill chunks (16 tokens, then 5 in a bucket of 8) and six
    decode steps through the latent pool give the logits of the
    reference's full forward at every step: within 5e-3 on logits that
    spread by 16 (float32 both, in different orders; leaving out the
    shared expert moves them by 50)."""
    cfg, w = _twin()
    model = get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0), jnp.float32)
    assert (jax.tree.map(lambda a: a.shape, params)
            == jax.tree.map(lambda a: a.shape, w))
    BS, NB, W = 8, 12, 6
    cache, _ = model.init_paged_decode(NB, BS, dtype=jnp.float32)
    table = jnp.asarray([7, 2, 9, 4, 11, 0], jnp.int32)
    seq = np.random.default_rng(3).integers(0, cfg.vocab_size, 27)
    ref = REF.logits_at(w, TWIN, seq, np.arange(27))
    start, P = 0, 21
    for bucket in (16, 8):
        n = min(bucket, P - start)
        chunk = np.zeros((1, bucket), np.int32)
        chunk[0, :n] = seq[start:start + n]
        logits, cache, *_ = transformer.prefill_paged_chunk(
            w, cfg, {"tokens": jnp.asarray(chunk)}, cache, tables=table,
            start=start, length=n, kernel_interpret=kernel)
        start += n
    np.testing.assert_allclose(logits[0], ref[P - 1], atol=5e-3, rtol=1e-4)
    lengths = jnp.asarray([P], jnp.int32)
    for t in range(P, 27):
        logits, cache, lengths, *_ = transformer.paged_decode_step(
            w, cfg, {"tokens": jnp.asarray(seq[None, t:t + 1], jnp.int32)},
            cache, tables=table[None], lengths=lengths,
            kernel_interpret=kernel)
        np.testing.assert_allclose(logits[0], ref[t], atol=5e-3, rtol=1e-4)
    assert int(lengths[0]) == 27 and W * BS >= 27


def test_absorbed_and_expanded_forms_agree():
    """Scoring ``q_lat`` against the latent rows and mapping the latent
    sum through ``W_uv`` equals expanding every head's K and V."""
    cfg = SMOKE
    params, _ = get_model(cfg).init(jax.random.PRNGKey(4), jnp.float32)
    ap = jax.tree.map(lambda a: a[0], params["layers"]["attn"])
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 13, cfg.d_model))
    pos = jnp.arange(13, dtype=jnp.int32)
    q_nope, q_pe, c_kv, k_pe = mla.project(cfg, ap, h, pos)
    expanded = mla.expanded_attention(cfg, ap, q_nope, q_pe, c_kv, k_pe)
    q = mla.latent_queries(cfg, mla.absorb_queries(cfg, ap, q_nope), q_pe)
    causal = pos[:, None] >= pos[None, :]
    o_lat = mla.latent_attention(cfg, q, mla.latent_row(cfg, c_kv, k_pe),
                                 causal[None, None])
    absorbed = mla.absorb_outputs(cfg, ap, o_lat, jnp.float32)
    assert q.shape[-1] == mla.latent_width(cfg) == 128
    np.testing.assert_allclose(absorbed, expanded, atol=2e-5, rtol=2e-4)


def _latent_pool(seed, L=2, NB=24, BS=16, W=256):
    return jax.random.normal(jax.random.PRNGKey(seed), (L, NB, BS, W),
                             jnp.float32).astype(jnp.bfloat16)


def test_latent_decode_kernel_matches_its_oracle():
    """Latent mode of the decode kernel (interpret mode): rows of 0, 1,
    a block and a half and a full table's positions, 8 heads reading one
    256-lane latent row whose first 128 lanes are the values."""
    pool = _latent_pool(0)
    tables = jnp.asarray([[3, 4, 5, 6], [10, 2, -1, -1], [0, -1, -1, -1],
                          [7, 9, 11, 13]], jnp.int32)
    lengths = jnp.asarray([64, 24, 1, 0], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 256), jnp.float32)
    kw = dict(value_dim=128, scale=0.11)
    got = paged_latent_attention(q, pool, jnp.int32(1), tables, lengths,
                                 interpret=True, **kw)
    want = paged_latent_attention_ref(q, pool, jnp.int32(1), tables, lengths,
                                      **kw)
    np.testing.assert_allclose(got[:3], want[:3], atol=2e-2, rtol=2e-2)
    assert not np.asarray(got[3]).any()          # length 0: zeros


@pytest.mark.parametrize("start,length", [(0, 40), (0, 13), (37, 27)])
def test_latent_chunk_kernel_matches_its_oracle(start, length):
    """Latent mode of the chunk kernel (interpret mode) for a chunk of
    64 queries at ``start`` (0 and after), ``length`` of them real; the
    pool holds the chunk's own rows."""
    pool = _latent_pool(2)
    table = jnp.asarray([5, 1, 17, 8, 3, 12], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(3), (64, 8, 256),
                          jnp.float32).astype(jnp.bfloat16)
    kw = dict(value_dim=128, scale=0.11)
    got = paged_latent_prefill_attention(q, pool, jnp.int32(0), table, start,
                                         length, interpret=True, **kw)
    want = paged_latent_prefill_attention_ref(q, pool, jnp.int32(0), table,
                                              start, length, **kw)
    np.testing.assert_allclose(np.asarray(got[:length], np.float32),
                               np.asarray(want[:length], np.float32),
                               atol=3e-2, rtol=3e-2)


def _route_numpy(x, router, bias, cfg):
    """Per token, in float64: sigmoid scores, groups ranked by the sum of
    their two best biased scores, the best groups kept, the top-k of
    their experts by biased score, weighted by the unbiased scores."""
    E, ng = cfg.num_experts, cfg.router_groups
    gates = np.zeros(x.shape[:-1] + (E,))
    for idx in np.ndindex(*x.shape[:-1]):
        s = 1 / (1 + np.exp(-(x[idx] @ router)))
        b = s + bias
        group = [np.sort(b[g * (E // ng):(g + 1) * (E // ng)])[-2:].sum()
                 for g in range(ng)]
        kept = np.argsort(-np.asarray(group), kind="stable")[:cfg.router_groups_kept]
        ok = np.repeat(np.isin(np.arange(ng), kept), E // ng)
        picks = np.argsort(-np.where(ok, b, -np.inf), kind="stable")[:cfg.experts_per_token]
        gates[idx + (picks,)] = s[picks] / s[picks].sum() * cfg.routed_scaling
    return gates


def test_sigmoid_group_router_matches_numpy():
    """The group-limited sigmoid router at DeepSeek-V3's shape (256
    experts in 8 groups, 4 kept, top 8, scaled 2.5)."""
    cfg = dataclasses.replace(get_config("deepseek-v3"), d_model=32)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(3, 5, 32))
    router, bias = rng.normal(size=(32, 256)) * 0.3, rng.normal(size=256) * 0.1
    gates, topi, _ = route(jnp.asarray(x, jnp.float32),
                           jnp.asarray(router, jnp.float32), cfg,
                           jnp.asarray(bias, jnp.float32))
    got = np.zeros((3, 5, 256))
    np.put_along_axis(got, np.asarray(topi), np.asarray(gates), axis=-1)
    want = _route_numpy(x, router, bias, cfg)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert (np.count_nonzero(want, axis=-1) == 8).all()
    # the picks lie in at most 4 of the 8 groups
    assert ((want.reshape(3, 5, 8, 32) > 0).any(-1).sum(-1) <= 4).all()


def test_yarn_frequencies_and_scale_follow_the_formula():
    """DeepSeek-V3's YaRN: dimensions turning more than 32 times over the
    4,096 original positions keep theta^(-2i/64), those turning less than
    once are divided by 40, a linear ramp between; the softmax scale is
    192^-0.5 (0.1 ln 40 + 1)^2."""
    cfg = get_config("deepseek-v3")
    dim, base = 64, 10000.0

    def correction(turns):
        return dim * math.log(4096 / (turns * 2 * math.pi)) / (2 * math.log(base))

    lo, hi = math.floor(correction(32)), math.ceil(correction(1))
    freq = [base ** (-2 * i / dim) for i in range(dim // 2)]
    ramp = [min(max((i - lo) / (hi - lo), 0.0), 1.0) for i in range(dim // 2)]
    want = [f * (1 - r) + f / 40 * r for f, r in zip(freq, ramp)]
    np.testing.assert_allclose(mla.yarn_inv_freq(cfg), want, rtol=1e-6)
    assert (lo, hi) == (10, 23)
    assert mla.softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2)
    plain = dataclasses.replace(cfg, rope_factor=1.0)
    np.testing.assert_allclose(mla.yarn_inv_freq(plain), freq, rtol=1e-6)
    assert mla.softmax_scale(plain) == pytest.approx(192 ** -0.5)


def test_shares_add_up_to_the_uncut_reference_layer():
    """Summed over the 4 shares of 2 experts, with the shared expert
    counted once, the held-share layer equals the reference's uncut
    layer (every expert held) in float32."""
    rng = np.random.default_rng(7)
    cfg = dataclasses.replace(SMOKE, experts_held=0, expert_offset=0)
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    f32 = lambda *s: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)  # noqa: E731
    mp = {"router": f32(d, E), "router_bias": f32(E), "w_gate": f32(E, d, f),
          "w_up": f32(E, d, f), "w_down": f32(E, f, d),
          "shared": {"wi_gate": f32(d, f), "wi_up": f32(d, f),
                     "wo": f32(f, d)}}
    h = f32(2, 9, d) * 5
    total = shared_expert(h, mp["shared"])
    for lo in range(0, E, 2):
        share = dataclasses.replace(cfg, experts_held=2, expert_offset=lo)
        out, _ = moe_held(h, mp["router"], mp["w_gate"][lo:lo + 2],
                          mp["w_up"][lo:lo + 2], mp["w_down"][lo:lo + 2],
                          share, router_bias=mp["router_bias"])
        total = total + out
    uncut = dict(TWIN, n_routed_experts=E, expert_offset=0)
    m = REF.dims(uncut)
    want = REF._experts(h.reshape(-1, d), mp, m, None).reshape(h.shape)
    np.testing.assert_allclose(total, want, atol=1e-4, rtol=1e-4)


def test_engine_for_serves_the_config_paged():
    assert engine_for(SMOKE) is PagedServeLoop
    assert engine_for(get_config("deepseek-v3")) is PagedServeLoop
    with pytest.raises(ValueError, match="paged pool only"):
        get_model(SMOKE).init_decode(1, 16)
