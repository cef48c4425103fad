"""A tiny dense state-space block (five layers, `mamba mamba attention mamba
mamba`, each a mixer and a SwiGLU half; four multipliers that are not 1, the
head tied, one group of B and C, two KV heads a page row) with the
benchmark's seeded weights on both sides: the program's tree and the plain
reference's layers. Shared by test_granite.py / test_granite_serving.py and
tests/serving_tiny.py."""

import os
import sys

import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import granite_hybrid_weights as weights  # noqa: E402
from benchmark.reference import granite_hybrid_decoder as reference  # noqa: E402
from llama_pipeline_parallel_tpu.models.ssm_moe.config import (  # noqa: E402
    SsmMoEConfig,
)

MODEL = {
    "model_type": "granitemoehybrid",
    # heads of 256 / 4 = 64: two KV heads fill a 128-lane page row, as at the
    # published width (`SsmMoEConfig.kv_pack` follows from the head)
    "hidden_size": 256, "num_hidden_layers": 5, "vocab_size": 128,
    "layer_types": ["mamba", "mamba", "attention", "mamba", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "mamba_n_heads": 8, "mamba_d_head": 64, "mamba_expand": 2,
    "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
    "mamba_chunk_size": 8, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "attention_bias": False, "hidden_act": "silu",
    "normalization_function": "rmsnorm", "position_embedding_type": "nope",
    "shared_intermediate_size": 128, "intermediate_size": 128,
    "num_local_experts": 0, "num_experts_per_tok": 0,
    "embedding_multiplier": 3, "residual_multiplier": 0.6,
    "attention_multiplier": 0.25, "logits_scaling": 2,
    "tie_word_embeddings": True, "rms_norm_eps": 1e-5,
    # a wider draw than the 0.02 of the real widths (hybrid_tiny.py), a
    # matrix product's gain as the other tiny models' 0.15 at a width of 32
    "init_std": 0.05,
}
SEED = 11


def config(model=MODEL, **kw) -> SsmMoEConfig:
    kw = {"dtype": jnp.float32, "param_dtype": jnp.float32, **kw}
    return SsmMoEConfig.from_published(model, **kw)


def both_sides(model=MODEL, seed=SEED):
    """(program params, reference `top`, reference `layer_fn`)."""
    top = weights.make_top(seed, model, jnp.float32)
    return (weights.make_program_weights(seed, model, jnp.float32), top,
            weights.layer_fn(seed, model, jnp.float32))
