"""Operations and bytes that the work needs, from shapes and lengths.

These count what a request needs, never what an implementation did: no
padded bucket, no gathered view, no recomputation.  A prompt of ``P``
tokens needs every layer's matrices for each of its tokens, causal
attention over its prefix, the head once (for the first answer token),
the weights read once and its keys and values written once.  A decode
step needs the weights read once for all rows it advances; each token it
makes needs the layers and the head, attention over its context, that
context's keys and values read once and its own appended.

``param_count`` is a copy of ``ModelConfig.param_count()`` for dense
SwiGLU models, so that the yardstick does not move with the program.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

BF16 = 2


@dataclass(frozen=True)
class Shapes:
    L: int
    d: int
    H: int
    KV: int
    hd: int
    f: int
    V: int
    tied: bool
    bias: bool

    @classmethod
    def of(cls, cfg: Dict[str, Any]) -> "Shapes":
        d = int(cfg["hidden_size"])
        h = int(cfg["num_attention_heads"])
        return cls(L=int(cfg["num_hidden_layers"]), d=d, H=h,
                   KV=int(cfg["num_key_value_heads"]),
                   hd=int(cfg.get("head_dim") or d // h),
                   f=int(cfg["intermediate_size"]), V=int(cfg["vocab_size"]),
                   tied=bool(cfg["tie_word_embeddings"]),
                   bias=bool(cfg["attention_bias"]))

    @property
    def q_dim(self) -> int:
        return self.H * self.hd

    @property
    def kv_dim(self) -> int:
        return self.KV * self.hd


def param_count(s: Shapes) -> int:
    """Parameters of a dense SwiGLU decoder (copy of the program's
    ``ModelConfig.param_count``)."""
    total = s.V * s.d + (0 if s.tied else s.d * s.V) + s.d
    attn = s.d * s.q_dim + 2 * s.d * s.kv_dim + s.q_dim * s.d
    if s.bias:
        attn += s.q_dim + 2 * s.kv_dim
    return total + s.L * (attn + 2 * s.d + 3 * s.d * s.f)


def layer_matmul_params(s: Shapes) -> int:
    """Matrix entries one token multiplies in one layer."""
    return s.d * s.q_dim + 2 * s.d * s.kv_dim + s.q_dim * s.d + 3 * s.d * s.f


def head_params(s: Shapes) -> int:
    return s.d * s.V


def weight_bytes(s: Shapes) -> int:
    """Bytes of every weight read once (bfloat16).  The embedding table
    is read in full only where it is also the head; an untied table is
    read row by row and counted with the tokens."""
    return BF16 * (param_count(s) - (0 if s.tied else s.V * s.d))


def kv_bytes_per_position(s: Shapes) -> int:
    return BF16 * 2 * s.L * s.kv_dim


def attention_flops(s: Shapes, keys: int) -> int:
    """One query attending ``keys`` positions, over every layer."""
    return 4 * keys * s.q_dim * s.L


def prefill_flops(s: Shapes, P: int) -> int:
    """A prompt of ``P`` tokens, causal, with the head on its last token."""
    layers = 2 * s.L * layer_matmul_params(s) * P
    attn = 4 * s.q_dim * s.L * P * (P + 1) // 2
    return layers + attn + 2 * head_params(s)


def prefill_bytes(s: Shapes, P: int) -> int:
    rows = 0 if s.tied else BF16 * P * s.d
    return weight_bytes(s) + rows + P * kv_bytes_per_position(s)


def decode_token_flops(s: Shapes, context: int) -> int:
    """One generated token whose query attends ``context`` positions."""
    return (2 * (s.L * layer_matmul_params(s) + head_params(s))
            + attention_flops(s, context))


def decode_token_bytes(s: Shapes, context: int) -> int:
    """KV of the context read once and the new position appended; the
    weights are counted per step (:func:`weight_bytes`)."""
    rows = 0 if s.tied else BF16 * s.d
    return (context + 1) * kv_bytes_per_position(s) + rows


def request_work(s: Shapes, P: int, generated: int) -> Dict[str, int]:
    """Needed work of one request served in full: its prefill, and for
    each answer token after the first a decode token that attends the
    prompt and the answer so far (token ``j`` attends ``P + j``)."""
    dec_f = sum(decode_token_flops(s, P + j) for j in range(1, generated))
    dec_b = sum(decode_token_bytes(s, P + j - 1) for j in range(1, generated))
    return {"prefill_flops": prefill_flops(s, P),
            "prefill_bytes": prefill_bytes(s, P),
            "decode_flops": dec_f, "decode_bytes": dec_b,
            "decode_tokens": max(generated - 1, 0)}


def least_time(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
