"""Operations and bytes that one chip's share of a DeepSeek-V3 model needs.

The share (see ``reference/mla_moe_decoder.py``) has ``L0`` leading dense
layers and ``L1`` expert layers, each with multi-head latent attention
(MLA).  Attention is counted in its expanded multi-head form, whatever
form the program computes it in: a query attending a key costs
``2 H (nope + rope + v)`` operations in a layer (scores of ``nope +
rope`` and a weighted sum of ``v`` per head), 81,920 at DeepSeek-V3's
widths.  Its bytes are those that no form can do without: the
``kv_lora + rope`` values of each needed position's latent in bfloat16
(1,152 B), read once per row (decode) or per chunk (prefill) and layer,
and each query's and output's multi-head width.  So the least time of
either form is at least what these counts give, and a share of the
roofline read from them cannot pass 100% for any form of the kernel.

Outside attention a token needs its projections, the dense layers' or
the shared experts' SwiGLU and the router, twice their parameters in
operations, and the head once per logits row.  Of the routed experts it
needs only the slots that a held expert took, counted by the program's
``expert_slots`` (``work_moe.expert_flops``).

Nothing here comes from the program: ``param_count`` is its own copy of
``ModelConfig.param_count()`` for this share.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Iterable, Tuple

from chipbench import work_moe

BF16 = 2


@dataclass(frozen=True)
class Shapes:
    L0: int         # leading dense layers
    L1: int         # expert layers
    d: int
    H: int
    ql: int         # q_lora_rank
    kl: int         # kv_lora_rank
    nope: int
    rope: int
    vd: int         # v_head_dim
    F: int          # dense width
    f: int          # expert width
    E: int          # experts the router scores
    G: int          # experts held
    ns: int         # shared experts
    V: int

    @classmethod
    def of(cls, cfg: Dict[str, Any]) -> "Shapes":
        L0 = int(cfg["first_k_dense_replace"])
        return cls(L0=L0, L1=int(cfg["num_hidden_layers"]) - L0,
                   d=int(cfg["hidden_size"]), H=int(cfg["num_attention_heads"]),
                   ql=int(cfg["q_lora_rank"]), kl=int(cfg["kv_lora_rank"]),
                   nope=int(cfg["qk_nope_head_dim"]),
                   rope=int(cfg["qk_rope_head_dim"]), vd=int(cfg["v_head_dim"]),
                   F=int(cfg["intermediate_size"]),
                   f=int(cfg["moe_intermediate_size"]),
                   E=int(cfg["published"]["n_routed_experts"]),
                   G=int(cfg["n_routed_experts"]),
                   ns=int(cfg["n_shared_experts"]), V=int(cfg["vocab_size"]))

    @property
    def L(self) -> int:
        return self.L0 + self.L1


def attention_matmul_params(s: Shapes) -> int:
    """Matrix entries of one layer's MLA projections."""
    return (s.d * s.ql + s.ql * s.H * (s.nope + s.rope)
            + s.d * (s.kl + s.rope) + s.kl * s.H * (s.nope + s.vd)
            + s.H * s.vd * s.d)


def dense_layer_params(s: Shapes) -> int:
    return attention_matmul_params(s) + s.ql + s.kl + 2 * s.d + 3 * s.d * s.F


def moe_layer_params(s: Shapes) -> int:
    return (attention_matmul_params(s) + s.ql + s.kl + 2 * s.d
            + s.d * s.E + s.E + (s.G + s.ns) * 3 * s.d * s.f)


def param_count(s: Shapes) -> int:
    """Parameters of the share: its layers, the untied embedding and
    head, and the final norm."""
    return (s.L0 * dense_layer_params(s) + s.L1 * moe_layer_params(s)
            + 2 * s.V * s.d + s.d)


def token_matmul_params(s: Shapes) -> int:
    """Matrix entries one token multiplies over every layer, outside
    attention's scores and the routed experts: the projections, the
    dense layers' SwiGLU, the router and the shared experts."""
    return (s.L * attention_matmul_params(s) + s.L0 * 3 * s.d * s.F
            + s.L1 * (s.d * s.E + s.ns * 3 * s.d * s.f))


def pair_flops(s: Shapes) -> int:
    """One query attending one key in one layer, in the multi-head form."""
    return 2 * s.H * (s.nope + s.rope + s.vd)


def latent_bytes(s: Shapes) -> int:
    """One position's latent in one layer (bfloat16)."""
    return BF16 * (s.kl + s.rope)


def query_bytes(s: Shapes) -> int:
    """One query's multi-head width in and its output out, in one layer."""
    return BF16 * s.H * (s.nope + s.rope + s.vd)


def head_flops(s: Shapes) -> int:
    return 2 * s.d * s.V


def request_flops(s: Shapes, P: int, generated: int) -> int:
    """Operations of one request served in full, outside the routed
    experts: its prompt (causal, the head on its last token), and each
    answer token ``j`` after the first attending ``P + j`` positions, with
    the head."""
    n = max(generated - 1, 0)
    pairs = P * (P + 1) // 2 + n * P + n * (n + 1) // 2
    return ((P + n) * 2 * token_matmul_params(s) + pairs * s.L * pair_flops(s)
            + (1 + n) * head_flops(s))


def decode_attention(s: Shapes, rows: Iterable[Tuple[int, int]]) -> Dict[str, int]:
    """Attention work of one decode dispatch whose rows are ``(fill,
    made)``: the ``j``-th token a row makes attends ``fill + j + 1``
    positions and reads their latents."""
    flops = nbytes = 0
    for fill, made in rows:
        keys = made * fill + made * (made + 1) // 2
        flops += keys * s.L * pair_flops(s)
        nbytes += s.L * (keys * latent_bytes(s) + made * query_bytes(s))
    return {"flops": flops, "bytes": nbytes}


def prefill_attention(s: Shapes, start: int, length: int) -> Dict[str, int]:
    """Attention work of one prefill chunk of ``length`` tokens from
    ``start``: query ``i`` attends ``start + i + 1`` positions; the
    chunk reads the latents below its end once."""
    keys = length * start + length * (length + 1) // 2
    return {"flops": keys * s.L * pair_flops(s),
            "bytes": s.L * ((start + length) * latent_bytes(s)
                            + length * query_bytes(s))}


def expert_shapes(s: Shapes) -> work_moe.Shapes:
    """The expert layers' shapes as ``work_moe`` counts them."""
    return work_moe.Shapes(L=s.L1, d=s.d, H=s.H, KV=s.H, hd=s.nope + s.rope,
                           f=s.f, E=s.E, G=s.G, V=s.V)
