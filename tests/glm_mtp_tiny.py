"""A tiny latent-attention decoder that drafts with its multi-token-prediction
module (the dense layer, two expert layers and the module; 16 experts of which
8 are held, `index_topk` 8, values wider than the nope part as published) with
the benchmark's seeded weights on both sides: the program's tree and the plain
reference's layers. Shared by test_latent_mtp.py / test_latent_mtp_serving.py."""

import os
import sys

import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import glm_mtp_weights as weights  # noqa: E402
from benchmark.reference import glm_dsa_mtp_decoder as reference  # noqa: E402
from llama_pipeline_parallel_tpu.models.latent_moe.config import (  # noqa: E402
    LatentMoEConfig,
)

MODEL = {
    "model_type": "glm_moe_dsa",
    "hidden_size": 32, "num_hidden_layers": 3, "vocab_size": 128,
    "intermediate_size": 48, "rms_norm_eps": 1e-5,
    "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
    "num_attention_heads": 4, "q_lora_rank": 16, "kv_lora_rank": 8,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 12,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "index_n_heads": 2, "index_head_dim": 8, "index_topk": 8,
    "moe_intermediate_size": 16, "n_routed_experts": 8, "router_experts": 16,
    "expert_offset": 4, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "num_experts_per_tok": 4,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    # a wider draw than the 0.02 of the real widths (hybrid_tiny.py)
    "init_std": 0.15,
}
# chance accepts a draft now and then over sixteen ids
SMALL_VOCAB = {**MODEL, "vocab_size": 16}
SEED = 3


def config(model=MODEL, **kw) -> LatentMoEConfig:
    kw = {"dtype": jnp.float32, "param_dtype": jnp.float32,
          "store_multiple": 8, **kw}
    return LatentMoEConfig.from_published(model, **kw)


def both_sides(model=MODEL, seed=SEED):
    """(program params, reference `top`, reference `layer_fn`)."""
    top = weights.make_top(seed, model, jnp.float32)
    return (weights.make_program_weights(seed, model, jnp.float32), top,
            weights.layer_fn(seed, model, jnp.float32))
