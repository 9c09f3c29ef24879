"""Seeded tables of the added token configuration: bfloat16 values."""

import jax
import jax.numpy as jnp

from benchmark.frames import seed_key


def make(cfg: dict, seed: int) -> dict:
    k_tok, k_pos, k_mix = jax.random.split(seed_key(seed), 3)
    width = int(cfg["hidden_size"])
    return {
        "mix": (jax.random.normal(
            k_mix, (int(cfg["num_hidden_layers"]), width, width),
            jnp.float32) / width ** 0.5).astype(jnp.bfloat16),
        "tokens": jax.random.normal(
            k_tok, (int(cfg["vocab_size"]), width), jnp.bfloat16),
        "positions": jax.random.normal(
            k_pos, (int(cfg["max_position_embeddings"]), width),
            jnp.bfloat16)}
