"""A tiny A.X-K1-shaped block (one kind of layer: plain MLA without indexer,
window or gate, under YaRN; the dense layer and four expert layers, 16
experts of which 8 are held) with the benchmark's seeded weights on both
sides: the program's tree and the plain reference's layers. Shared by
test_latent_moe.py / test_latent_serving.py.

YaRN at a size where every regime shows in a row of 40 positions: rope 8
(four frequencies), theta 100, factor 4 from 16 original positions, so the
first frequency is kept, the second blended, the last two divided by 4;
`mscale_all_dim` 0.5 makes the softmax scale's factor and the amplitude of
cos and sin both differ from 1."""

import os
import sys

import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import mla_moe_weights as weights  # noqa: E402
from benchmark.reference import mla_moe_decoder as reference  # noqa: E402
from llama_pipeline_parallel_tpu.models.latent_moe.config import (  # noqa: E402
    LatentMoEConfig,
)

MODEL = {
    "hidden_size": 32, "num_hidden_layers": 5, "vocab_size": 128,
    "intermediate_size": 48, "rms_norm_eps": 1e-6,
    "first_k_dense_replace": 1,
    "num_attention_heads": 4, "q_lora_rank": 16, "kv_lora_rank": 8,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
    "rope_theta": 100,
    "rope_scaling": {"beta_fast": 2, "beta_slow": 0.5, "factor": 4,
                     "mscale": 1, "mscale_all_dim": 0.5,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "moe_intermediate_size": 16, "n_routed_experts": 8, "router_experts": 16,
    "expert_offset": 4, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "num_experts_per_tok": 4,
    "scoring_func": "sigmoid", "topk_method": "none", "n_group": 2,
    "topk_group": 1,
    # a wider draw than the 0.02 of the real widths (hybrid_tiny.py)
    "init_std": 0.15,
}
SEED = 5


def config(model=MODEL, **kw) -> LatentMoEConfig:
    kw = {"dtype": jnp.float32, "param_dtype": jnp.float32,
          "store_multiple": 8, **kw}
    return LatentMoEConfig.from_published(model, **kw)


def both_sides(model=MODEL, seed=SEED):
    """(program params, reference `top`, reference `layer_fn`)."""
    top = weights.make_top(seed, model, jnp.float32)
    return (weights.make_program_weights(seed, model, jnp.float32), top,
            weights.layer_fn(seed, model, jnp.float32))
