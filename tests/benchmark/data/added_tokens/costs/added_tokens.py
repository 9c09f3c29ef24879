"""Operations and bytes a frame of the added token configuration."""


def frame_cost(cfg: dict) -> dict:
    width = int(cfg["hidden_size"])
    rows = int(cfg["vocab_size"]) + int(cfg["max_position_embeddings"])
    layers = int(cfg["num_hidden_layers"])
    return {"flops_per_frame": 2 * layers * width * width,
            "in_bytes_per_frame": 8,
            "weight_bytes": (rows + layers * width) * width * 2}
