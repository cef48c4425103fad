"""A tiny configuration of the compressed-window family (models/eva/) for the
CPU tests: window 32, chunk 4, pages of 8, so a few dozen tokens cross every
boundary the family has (a chunk's edge, a window's edge, a page's edge, a
summary page's edge: 8 chunks = 32 positions = one window)."""

import jax
import jax.numpy as jnp

from llama_pipeline_parallel_tpu.models.eva import model as eva
from llama_pipeline_parallel_tpu.models.eva.config import EvaConfig

WINDOW, CHUNK, PAGE = 32, 4, 8


def tiny_config(dtype=jnp.float32, **kw) -> EvaConfig:
    base = dict(vocab_size=48, hidden_size=32, intermediate_size=64,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=4, max_position_embeddings=512,
                rope_theta=10000.0, window_size=WINDOW, chunk_size=CHUNK,
                num_pred_heads=2, dtype=dtype, param_dtype=dtype)
    base.update(kw)
    return EvaConfig(**base)


def tiny_params(cfg: EvaConfig, seed: int = 0, std: float = 0.3) -> dict:
    """`model.init_params` with spread enough that attention is not flat and
    norm offsets that are not zero (the `1 + g` scale is exercised)."""
    params = eva.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def spread(x):
        if x.ndim >= 2 and x.shape[-1] != cfg.head_dim:
            return (x * (std / 0.02)).astype(x.dtype)
        return x

    params = jax.tree.map(spread, params)
    offset = lambda x: (0.2 * jax.random.normal(next(keys), x.shape)).astype(
        x.dtype)
    params["layers"]["input_norm"] = offset(params["layers"]["input_norm"])
    params["layers"]["post_norm"] = offset(params["layers"]["post_norm"])
    params["norm"] = offset(params["norm"])
    return params
