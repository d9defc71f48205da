"""The program's ``ModelConfig`` for ``flad-adllm.json``: the file's
sizes under the program's field names."""


def program_config(cfg: dict):
    from repro.config import ModelConfig

    return ModelConfig(
        name=cfg["name"], family=cfg["family"],
        num_layers=cfg["num_layers"], d_model=cfg["d_model"],
        num_heads=cfg["num_heads"], num_kv_heads=cfg["num_kv_heads"],
        head_dim=cfg["head_dim"], d_ff=cfg["d_ff"],
        vocab_size=cfg["vocab_size"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["norm_eps"], tie_embeddings=cfg["tie_embeddings"],
        param_dtype=cfg["dtype"])
