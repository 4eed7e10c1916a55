"""Model configuration: one dataclass covers all ten assigned architectures.

Every field is explicit (no HF config loading — the exact dims come from the
assignment table and are pinned in ``repro/configs/<arch>.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

__all__ = ["ModelConfig"]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int                   # 0 for attention-free (rwkv uses wkv heads)
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None   # default d_model // num_heads

    # --- MoE ---------------------------------------------------------------
    num_experts: int = 0             # the router's width: every expert
    experts_per_token: int = 0
    # the chip's share under expert parallelism: it holds experts
    # expert_offset .. expert_offset + experts_held - 1 of each layer
    # (0 = all of them), routes over all num_experts and computes only the
    # part of the result that its own experts give
    experts_held: int = 0
    expert_offset: int = 0
    moe_capacity_factor: float = 1.25
    # the router: "softmax" keeps each token's top-k by softmax; "sigmoid"
    # (DeepSeek-V3's noaux_tc) scores each expert by a sigmoid, ranks them
    # with a learned correction bias, keeps the router_groups_kept best of
    # router_groups equal groups (a group ranked by its two best biased
    # scores), takes the top-k inside them, and weights each pick by its
    # unbiased score, renormalised and times routed_scaling
    router: str = "softmax"
    router_groups: int = 1
    router_groups_kept: int = 1
    routed_scaling: float = 1.0
    # SwiGLU experts of width shared_experts * d_ff that every token goes
    # through beside its routed ones, on every chip alike
    shared_experts: int = 0
    # the first first_dense_layers layers are dense SwiGLU of width
    # dense_d_ff; the experts start after them
    first_dense_layers: int = 0
    dense_d_ff: int = 0
    moe_cap_headroom: float = 1.6   # buffer slack for WF2 capacity planning

    # --- SSM / linear attention --------------------------------------------
    ssm_state: int = 0               # mamba2 N
    wkv_head_dim: int = 64           # rwkv6 head size
    conv_kernel: int = 4             # mamba2 depthwise conv width
    scan_chunk: int = 64             # chunk size of the chunked linear scan

    # --- latent attention (MLA, DeepSeek-V2/V3) -----------------------------
    # kv_lora_rank > 0 selects it: queries go through a q_lora_rank-wide
    # bottleneck, keys and values come from a kv_lora_rank-wide latent
    # c_kv and a qk_rope_head_dim-wide rope key shared by every head; the
    # cache holds only those two (see repro/models/mla.py)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rope scaling (rope_factor 1: none): the frequencies of
    # rope_original_max positions stretched by rope_factor between the
    # yarn_beta_fast and yarn_beta_slow rotations, and the softmax scale
    # times yarn_mscale(rope_factor, yarn_mscale_all_dim)^2
    rope_factor: float = 1.0
    rope_original_max: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 1.0

    # --- attention variants --------------------------------------------------
    qk_norm: bool = False            # qwen3
    qkv_bias: bool = False           # qwen2/2.5, qwen2-vl
    rope_theta: float = 1e6
    mrope_sections: Optional[Tuple[int, int, int]] = None  # qwen2-vl M-RoPE
    attention: str = "full"          # full | none (rwkv) | hybrid (zamba2)
    positional: str = "rope"         # rope | sinusoidal (musicgen) | none
    rms_norm_eps: float = 1e-6       # every RMSNorm of the model

    # --- MLP variants --------------------------------------------------------
    mlp: str = "swiglu"              # swiglu | gelu (musicgen)

    # --- hybrid (zamba2) ------------------------------------------------------
    shared_attention_every: int = 0  # a shared attn block after every k layers

    # --- embeddings / frontends ----------------------------------------------
    pad_vocab_multiple: int = 0      # pad embed/head rows so vocab shards evenly
    tie_embeddings: bool = False
    frontend: str = "none"           # none | audio | vision (stub embeddings in)

    # --- serving ----------------------------------------------------------------
    kv_cache_dtype: str = "bf16"     # bf16 | fp8 (f8e4m3fn; halves decode HBM)

    # --- attention impl for long sequences -------------------------------------
    attn_block_q: int = 512          # query block of blockwise (flash) attention
    attn_block_kv: int = 1024
    flash_threshold: int = 8192      # use blockwise attention for seq >= this

    # --- UDS integration --------------------------------------------------------
    scheduler: str = "fac2"          # default UDS for packing/microbatching
    moe_scheduler: str = "wf2"       # UDS for expert capacity planning

    # --- sharding ---------------------------------------------------------------
    # per-arch overrides of the logical->mesh rule table, e.g. grok-1 keeps
    # experts unsharded (8 experts < 16-way model axis) and TP-shards each
    # expert's huge d_ff instead:  (("experts", None), ("mlp", "model"))
    sharding_overrides: Tuple[Tuple[str, Any], ...] = ()
    # optimizer: "adamw" (<=32B) or "adafactor" (paLM-style, for >=200B)
    optimizer: str = "adamw"

    def __post_init__(self):
        if self.is_mla:      # a query/key head: its nope and rope parts
            object.__setattr__(self, "head_dim", self.qk_nope_head_dim
                               + self.qk_rope_head_dim)
        if self.num_heads and self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)
        if self.router not in ("softmax", "sigmoid") or (
                self.num_experts % max(self.router_groups, 1)
                or not 1 <= self.router_groups_kept <= self.router_groups):
            raise ValueError(
                f"{self.name}: router {self.router!r} over {self.num_experts} "
                f"experts in {self.router_groups} groups, "
                f"{self.router_groups_kept} kept")
        if not (0 <= self.experts_held <= self.num_experts and 0 <= self.expert_offset
                and self.expert_offset + self.held_experts <= self.num_experts):
            raise ValueError(
                f"{self.name}: experts {self.expert_offset}.."
                f"{self.expert_offset + self.held_experts - 1} held, but the "
                f"router has {self.num_experts}")

    # ------------------------------------------------------------------ sizes
    @property
    def q_dim(self) -> int:
        return self.num_heads * (self.head_dim or 0)

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * (self.head_dim or 0)

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def moe_layers(self) -> int:
        """Layers after the dense prefix: the expert layers of an MoE
        config."""
        return self.num_layers - self.first_dense_layers

    @property
    def padded_vocab(self) -> int:
        if self.pad_vocab_multiple:
            m = self.pad_vocab_multiple
            return ((self.vocab_size + m - 1) // m) * m
        return self.vocab_size

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def held_experts(self) -> int:
        """Experts of each layer whose weights this config holds."""
        return self.experts_held or self.num_experts

    @property
    def sub_quadratic(self) -> bool:
        """True if the arch supports O(seq) context (SSM / linear attention);
        gates the long_500k shape per the assignment spec."""
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Analytic parameter count (validated against the real pytree in
        tests); used for MODEL_FLOPS = 6*N*D roofline bookkeeping."""
        d, f, v, L = self.d_model, self.d_ff, self.vocab_size, self.num_layers
        total = v * d                      # embedding
        if not self.tie_embeddings:
            total += d * v                 # lm head
        total += d                         # final norm
        per_layer = 0
        if self.family == "ssm":           # rwkv6
            n_h = d // self.wkv_head_dim
            per_layer += 5 * d * d         # r,k,v,g,o projections
            per_layer += 2 * (d * 64 + 64 * d)  # low-rank decay + mix
            per_layer += d                 # bonus u (per channel)
            per_layer += 2 * d             # ln weights
            per_layer += d * f + f * d + d * d  # channel mix (k, v, r)
            return total + L * per_layer
        # attention (dense/moe/hybrid-shared/audio/vlm)
        attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        if self.is_mla:
            H, r = self.num_heads, self.qk_rope_head_dim
            attn = (d * self.q_lora_rank + self.q_lora_rank
                    + self.q_lora_rank * H * self.head_dim
                    + d * (self.kv_lora_rank + r) + self.kv_lora_rank
                    + self.kv_lora_rank * H * (self.qk_nope_head_dim
                                               + self.v_head_dim)
                    + H * self.v_head_dim * d)
        if self.qkv_bias:
            attn += self.q_dim + 2 * self.kv_dim
        if self.qk_norm:
            attn += 2 * (self.head_dim or 0)
        if self.mlp == "swiglu":
            mlp = 3 * d * f
        else:
            mlp = 2 * d * f + d + f        # gelu w/ biases
        if self.family == "hybrid":        # zamba2: mamba2 layers + shared attn
            H = self.num_heads
            din = 2 * d                    # mamba2 inner dim (expand=2)
            N = self.ssm_state
            nheads = din // 64
            m = d * (2 * din + 2 * N + nheads)     # in_proj (z,x,B,C,dt)
            m += self.conv_kernel * (din + 2 * N)  # depthwise conv
            m += nheads * 2 + nheads               # A, D, dt_bias
            m += din * d                           # out_proj
            m += 2 * d                             # norms
            shared = (2 * d) * self.q_dim + 2 * (2 * d) * self.kv_dim \
                + self.q_dim * d + 3 * (2 * d) * f // 2 + 2 * 2 * d
            n_shared = 1
            return total + L * m + n_shared * shared
        per_layer = attn + 2 * d           # ln1, ln2
        if self.first_dense_layers:
            total += self.first_dense_layers * (per_layer
                                                + 3 * d * self.dense_d_ff)
        if self.is_moe:
            per_layer += d * self.num_experts              # router
            if self.router == "sigmoid":
                per_layer += self.num_experts              # its bias
            per_layer += self.held_experts * 3 * d * f     # expert swiglu
            per_layer += self.shared_experts * 3 * d * f   # shared swiglu
        else:
            per_layer += mlp
        return total + self.moe_layers * per_layer

    def active_param_count(self) -> int:
        """Active params per token (MoE: at most top-k of the held experts
        count)."""
        if not self.is_moe:
            return self.param_count()
        d, f = self.d_model, self.d_ff
        dense_experts = self.held_experts * 3 * d * f
        active_experts = min(self.experts_per_token,
                             self.held_experts) * 3 * d * f
        return self.param_count() - self.moe_layers * (dense_experts - active_experts)
