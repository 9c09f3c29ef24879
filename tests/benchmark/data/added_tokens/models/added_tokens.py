"""Application glue of the added token configuration: a model of two
int32 inputs registered under a name, with stages of its own."""

import numpy as np

from benchmark.appglue import fence, served_nbytes, unregister  # noqa: F401


def _apply(params, tokens, positions):
    import jax
    import jax.numpy as jnp

    with jax.named_scope("lookup"):
        x = (params["tokens"][tokens[:, 0]]
             + params["positions"][positions[:, 0]]).astype(jnp.float32)
    for i, w in enumerate(params["mix"]):
        with jax.named_scope(f"layer{i:02d}/mix"):
            x = x + jnp.tanh(jnp.matmul(
                x, w.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
    with jax.named_scope("sum"):
        return jnp.sum(x, axis=-1, keepdims=True)


def register(cfg: dict, params, batch: int, name: str) -> None:
    from nnstreamer_tpu.filters.jax_xla import register_model

    register_model(name, _apply, params=params,
                   in_shapes=[(batch, 1), (batch, 1)], in_dtypes=np.int32)


def outputs(buf) -> dict:
    return {"sum": buf.tensors[0].jax()}
