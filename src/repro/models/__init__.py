"""Model zoo facade: family-dispatched init/forward/decode.

``get_model(cfg)`` returns a ``Model`` namespace with a uniform API so the
training/serving steps, dry-run, and tests never branch on architecture:

    model.init(key, dtype)            -> (params, specs)
    model.forward(params, inputs)     -> (logits, aux)     # train/prefill
    model.init_decode(batch, max_len) -> (cache/state, specs)
    model.decode(params, inputs, st)  -> (logits, new st)
    model.prefill(params, inputs, max_len) -> (logits, cache)

Attention (KV-cache) archs also expose the paged-KV serving path
(block-pool cache, host-side block tables — see ``repro.serve_mem``),
which ``PagedServeLoop`` serves them through:

    model.init_paged_decode(num_blocks, block_size) -> (pool, specs)
    model.paged_decode(params, inputs, pool, tables=, lengths=, ...)
                                      -> (logits, pool, lengths)
    model.fused_paged_decode(params, inputs, pool, num_steps=T, tables=,
                             lengths=, limits=, ...)
                -> (tokens (B,T), pool, lengths, active, remaining)
    model.paged_prefill_chunk(params, inputs, pool, tables=, start=,
                              length=) -> (logits (1,V), pool)

They are ``None`` for state-space / hybrid families, which serve through
the per-slot ``ServeLoop`` over ``init_decode``/``decode``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models import transformer, rwkv6, zamba2

__all__ = ["ModelConfig", "Model", "get_model"]


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable
    forward: Callable
    init_decode: Callable
    decode: Callable
    prefill: Optional[Callable] = None
    # paged-KV serving path (block pool + host-side block tables); None
    # when the family has no paged implementation
    init_paged_decode: Optional[Callable] = None
    paged_decode: Optional[Callable] = None
    fused_paged_decode: Optional[Callable] = None
    paged_prefill_chunk: Optional[Callable] = None

    @property
    def name(self) -> str:
        return self.cfg.name


def get_model(cfg: ModelConfig) -> Model:
    if cfg.family == "ssm":          # rwkv6
        return Model(
            cfg=cfg,
            init=partial(rwkv6.init_params, cfg),
            forward=(lambda params, inputs, **kw:
                     rwkv6.forward(params, cfg, inputs, **kw)),
            init_decode=(lambda batch, max_len, **kw:
                         rwkv6.init_state(cfg, batch, **kw)),
            decode=(lambda params, inputs, state, **kw:
                    rwkv6.decode_step(params, cfg, inputs, state, **kw)),
            prefill=(lambda params, inputs, max_len=None, **kw:
                     rwkv6.prefill(params, cfg, inputs, max_len, **kw)),
        )
    if cfg.family == "hybrid":       # zamba2
        return Model(
            cfg=cfg,
            init=partial(zamba2.init_params, cfg),
            forward=(lambda params, inputs, **kw:
                     zamba2.forward(params, cfg, inputs, **kw)),
            init_decode=(lambda batch, max_len, **kw:
                         zamba2.init_state(cfg, batch, max_len, **kw)),
            decode=(lambda params, inputs, state, **kw:
                    zamba2.decode_step(params, cfg, inputs, state, **kw)),
            prefill=(lambda params, inputs, max_len=None, **kw:
                     zamba2.prefill(params, cfg, inputs, max_len, **kw)),
        )
    # dense / moe / audio / vlm all share the transformer implementation
    return Model(
        cfg=cfg,
        init=partial(transformer.init_params, cfg),
        forward=(lambda params, inputs, **kw:
                 transformer.forward(params, cfg, inputs, **kw)),
        init_decode=(lambda batch, max_len, **kw:
                     transformer.init_cache(cfg, batch, max_len, **kw)),
        decode=(lambda params, inputs, cache, **kw:
                transformer.decode_step(params, cfg, inputs, cache, **kw)),
        # ``length`` marks the real prompt inside a right-padded bucket; a
        # family whose prefill takes it serves bucketed prompts
        prefill=(lambda params, inputs, max_len=None, *, length=None, **kw:
                 transformer.prefill(params, cfg, inputs, max_len,
                                     length=length, **kw)),
        init_paged_decode=(lambda num_blocks, block_size, **kw:
                           transformer.init_paged_cache(cfg, num_blocks,
                                                        block_size, **kw)),
        paged_decode=(lambda params, inputs, cache, **kw:
                      transformer.paged_decode_step(params, cfg, inputs,
                                                    cache, **kw)),
        fused_paged_decode=(lambda params, inputs, cache, **kw:
                            transformer.fused_paged_decode_steps(
                                params, cfg, inputs, cache, **kw)),
        paged_prefill_chunk=(lambda params, inputs, cache, **kw:
                             transformer.prefill_paged_chunk(
                                 params, cfg, inputs, cache, **kw)),
    )
