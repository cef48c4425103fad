"""A tiny latent-attention block (the dense layer and two periods, 16 experts
of which 8 are held, `index_topk` 8, a window of 5, a ring of 6) with the
benchmark's seeded weights on both sides: the program's tree and the plain
reference's layers. Shared by test_latent_moe.py / test_latent_serving.py."""

import os
import sys

import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import latent_moe_weights as weights  # noqa: E402
from benchmark.reference import latent_moe_decoder as reference  # noqa: E402
from llama_pipeline_parallel_tpu.models.latent_moe.config import (  # noqa: E402
    LatentMoEConfig,
)

_PERIOD = ["full_attention"] + ["sliding_attention"] * 3
MODEL = {
    "hidden_size": 32, "num_hidden_layers": 9, "vocab_size": 128,
    "intermediate_size": 48, "rms_norm_eps": 1e-5,
    "layer_types": ["full_attention"] + _PERIOD * 3,
    "first_k_dense_replace": 1, "apply_mla_qkv_lora_rescale": True,
    "attention_gate_type": "headwise", "swa_attention_gate_type": "headwise",
    "num_attention_heads": 4, "q_lora_rank": 16, "kv_lora_rank": 8,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "rope_theta": 80000000, "rope_scaling": None,
    "index_n_heads": 2, "index_head_dim": 8, "index_topk": 8,
    "swa_num_attention_heads": 2, "swa_q_lora_rank": 16,
    "swa_kv_lora_rank": 12, "swa_qk_nope_head_dim": 12,
    "swa_qk_rope_head_dim": 4, "swa_v_head_dim": 8, "swa_rope_theta": 50000,
    "sliding_window_size": 5,
    "moe_intermediate_size": 16, "n_routed_experts": 8, "router_experts": 16,
    "expert_offset": 4, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 4,
    "scoring_func": "sigmoid",
    # a wider draw than the 0.02 of the real widths (hybrid_tiny.py)
    "init_std": 0.15,
}
SEED = 3


def config(model=MODEL, **kw) -> LatentMoEConfig:
    kw = {"dtype": jnp.float32, "param_dtype": jnp.float32,
          "ring_multiple": 2, "store_multiple": 8, **kw}
    return LatentMoEConfig.from_published(model, **kw)


def both_sides(model=MODEL, seed=SEED):
    """(program params, reference `top`, reference `layer_fn`)."""
    top = weights.make_top(seed, model, jnp.float32)
    return (weights.make_program_weights(seed, model, jnp.float32), top,
            weights.layer_fn(seed, model, jnp.float32))
