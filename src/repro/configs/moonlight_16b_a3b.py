"""Moonlight-16B-A3B [hf:moonshotai/Moonlight-16B-A3B, model_type
deepseek_v3]: 27 layers at d_model 2048; latent attention (MLA, 16 heads,
kv_lora_rank 512, no q LoRA, nope/rope/v head dims 128/64/128, RoPE theta
5e4 on interleaved pairs); layer 0 a dense SwiGLU of width 11264, layers
1-26 MoE with 64 routed experts of width 1408, top-6 sigmoid scores with
an aux-free selection bias (noaux_tc, n_group = topk_group = 1, so the
group-limited selection is the plain top-6), norm_topk_prob, routed
scale 2.446, and 2 shared experts; vocab 163840, untied head, RMSNorm
eps 1e-5. Serving only: the paged engine runs it (training keeps the
capacity-routed ``moe_block`` and has no latent attention)."""
from repro.config import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=1,            # one latent row serves every head
    head_dim=192,              # qk_nope + qk_rope: the softmax scale
    d_ff=11264,                # the leading dense layer's width
    vocab_size=163840,
    rope_theta=50000.0,
    norm_eps=1e-5,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, d_expert=1408,
                  num_shared_experts=2, score_func="sigmoid",
                  route_scale=2.446, norm_topk=True, selection_bias=True,
                  first_dense_layers=1),
)
