"""Configuration dataclasses for the FLAD-JAX framework.

Every architecture in ``repro.configs`` instantiates a :class:`ModelConfig`;
input shapes are :class:`ShapeConfig`; mesh/runtime knobs live in
:class:`RunConfig`.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0
    top_k: int = 0
    d_expert: int = 0          # per-expert FFN hidden size
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    router_z_weight: float = 1e-3
    # DeepSeek-V3-style routing (serving's held-expert layer reads these;
    # the defaults are the softmax top-k that ``moe_block`` computes)
    num_shared_experts: int = 0     # one SwiGLU of width n * d_expert
    score_func: str = "softmax"     # softmax | sigmoid
    route_scale: float = 1.0        # routed_scaling_factor
    norm_topk: bool = True          # renormalize the chosen top-k scores
    selection_bias: bool = False    # e_score_correction_bias on selection
    first_dense_layers: int = 0     # leading dense SwiGLU layers (d_ff)
    # expert parallelism: this chip holds experts [expert_offset,
    # expert_offset + experts_held) of num_experts (0 = all of them)
    expert_offset: int = 0
    experts_held: int = 0

    @property
    def held(self) -> int:
        return self.experts_held or self.num_experts


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2/V3). ``kv_lora_rank`` 0
    means the model has none. Keys and values come from a shared latent
    of ``kv_lora_rank`` plus a ``qk_rope_head_dim`` rotary key shared by
    every head; ``q_lora_rank`` 0 projects queries directly."""
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = True    # the published config class's default
    kv_norm_eps: float = 1e-6       # the latent's RMSNorm (its own default)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row(self) -> int:
        """Values one token keeps per layer: the latent and its rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """State for xLSTM / Mamba-style recurrent paths."""
    state_size: int = 16       # per-head recurrent state dim (mamba N)
    conv_kernel: int = 4       # depthwise conv width (mamba)
    slstm_every: int = 0       # xlstm: 1-in-k blocks are sLSTM (0 = none)
    expand: int = 2            # mamba inner expansion


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0           # 0 -> d_model // num_heads
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    moe: MoEConfig = MoEConfig()
    ssm: SSMConfig = SSMConfig()
    mla: MLAConfig = MLAConfig()
    # encoder-decoder split (family == 'encdec'); num_layers = enc + dec
    enc_layers: int = 0
    dec_layers: int = 0
    # sliding-window attention (None = full attention). Set per-run for the
    # long_500k shape; window caches keep decode memory bounded.
    window: Optional[int] = None
    # multimodal stub frontend: number of prefix embedding tokens fed by
    # input_specs() (vlm patch embeddings / audio frame embeddings)
    prefix_tokens: int = 0
    prefix_dim: int = 0
    # extra task heads for the FLAD vision encoder
    num_waypoints: int = 0
    num_light_classes: int = 0
    param_dtype: str = "bfloat16"
    # attention impl: 'auto' picks chunked for long sequences
    q_chunk: int = 512
    kv_chunk: int = 1024
    # Pallas flash-attention VMEM tile sizes (fwd + bwd kernels); callers
    # may override per-call via blocks.attention(block_q=..., block_k=...)
    attn_block_q: int = 128
    attn_block_k: int = 128

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def latent(self) -> bool:
        """True for latent attention (MLA): the KV pool holds one latent
        row per token per layer instead of K and V per KV head."""
        return self.mla.kv_lora_rank > 0

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ---- parameter counting (used by SWIFT's memory model & rooflines) ----
    def param_count(self) -> int:
        d, hd = self.d_model, self.hd
        nq, nkv = self.num_heads, self.num_kv_heads
        V = self.vocab_size
        emb = V * d
        out = 0 if self.tie_embeddings else V * d

        def attn_params() -> int:
            p = d * nq * hd + 2 * d * nkv * hd + nq * hd * d
            if self.qkv_bias:
                p += (nq + 2 * nkv) * hd
            if self.qk_norm:
                p += 2 * hd
            return p + 2 * d  # two RMSNorm vectors per block

        def ffn_params() -> int:
            if self.moe.num_experts:
                e = self.moe.num_experts
                return d * e + e * 3 * d * self.moe.d_expert
            return 3 * d * self.d_ff  # SwiGLU

        def mlstm_params() -> int:
            di = self.ssm.expand * d
            # in-proj (x,z), out-proj, q/k/v projections, gates, conv
            return d * 2 * di + di * d + 3 * di * di + 2 * di + d

        def block_params() -> int:
            if self.family == "ssm":
                return mlstm_params() + ffn_params() + 2 * d
            if self.family == "hybrid":
                return attn_params() + mlstm_params() + ffn_params()
            return attn_params() + ffn_params()

        n = self.num_layers * block_params() + emb + out + d
        if self.family == "encdec":
            # decoder blocks additionally carry cross-attention
            n += self.dec_layers * (d * nq * hd + 2 * d * nkv * hd + nq * hd * d + d)
        if self.prefix_tokens:
            n += self.prefix_dim * d  # projector
        return n

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: top_k of num_experts)."""
        if not self.moe.num_experts:
            return self.param_count()
        e, k = self.moe.num_experts, self.moe.top_k
        full = self.param_count()
        expert_p = self.num_layers * e * 3 * self.d_model * self.moe.d_expert
        return full - expert_p + expert_p * k // e


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                   # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


INPUT_SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

# Sliding window used when a full-attention architecture runs long_500k.
LONG_CONTEXT_WINDOW = 8192


@dataclasses.dataclass(frozen=True)
class RunConfig:
    arch: str = "flad-vision"
    shape: str = "train_4k"
    strategy: str = "tensor"     # tensor | pipeline  (pipeline == FHDP)
    multi_pod: bool = False
    microbatches: int = 8        # pipeline microbatching
    remat: str = "block"         # none | block  (activation checkpointing)
    learning_rate: float = 3e-4
    seed: int = 0


# ---- TPU v5e hardware model (roofline + SWIFT cost model constants) ----
@dataclasses.dataclass(frozen=True)
class HardwareConfig:
    peak_flops: float = 197e12       # bf16 FLOP/s per chip
    hbm_bw: float = 819e9            # bytes/s per chip
    ici_bw: float = 50e9             # bytes/s per link
    hbm_bytes: float = 16 * 2 ** 30  # per chip (v5e: 16 GiB)
    vmem_bytes: float = 128 * 2**20


TPU_V5E = HardwareConfig()
