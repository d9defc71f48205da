"""The program's ``ModelConfig`` for ``moonlight-16b-a3b.json``: the
file's published keys under the program's field names, with this chip's
share of the experts."""


def program_config(cfg: dict):
    from repro.config import MLAConfig, ModelConfig, MoEConfig

    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise NotImplementedError("group-limited expert selection")
    if cfg["q_lora_rank"]:
        raise NotImplementedError("MLA with a query LoRA")
    return ModelConfig(
        name=cfg["name"], family="moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=1,
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], param_dtype=cfg["dtype"],
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"],
                      rope_interleave=cfg["rope_interleave"],
                      kv_norm_eps=cfg["kv_norm_eps"]),
        moe=MoEConfig(num_experts=cfg["router_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_expert=cfg["moe_intermediate_size"],
                      num_shared_experts=cfg["n_shared_experts"],
                      score_func=cfg["scoring_func"],
                      route_scale=cfg["routed_scaling_factor"],
                      norm_topk=cfg["norm_topk_prob"],
                      selection_bias=cfg["topk_method"] == "noaux_tc",
                      first_dense_layers=cfg["first_k_dense_replace"],
                      expert_offset=cfg["expert_offset"],
                      experts_held=cfg["n_routed_experts"]))
