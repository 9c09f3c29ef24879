"""Application glue for the ViT configuration: registers the program's
``vit_apply`` under a model name and says what a pulled buffer serves."""

from __future__ import annotations

import numpy as np

from benchmark.appglue import fence, served_nbytes, unregister  # noqa: F401


def register(cfg: dict, params, batch: int, name: str) -> None:
    from nnstreamer_tpu.filters.jax_xla import register_model
    from nnstreamer_tpu.models.vit import vit_apply

    heads = int(cfg["num_attention_heads"])
    size = int(cfg["image_size"])
    register_model(name, lambda p, x: vit_apply(p, x, heads=heads),
                   params=params, in_shapes=[(batch, size, size, 3)],
                   in_dtypes=np.float32)


def outputs(buf) -> dict:
    return {"logits": buf.tensors[0].jax()}
