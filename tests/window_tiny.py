"""A tiny window / full softmax block (seven layers in the published order of
one period behind the leading dense layer, `0 1 1 1 1 1 0`; 8 query heads of
24 numbers, 8 of them rotated, values of 16; 2 KV heads in the full layers,
4 in the window layers; a window of 8; 16 experts of which 8 are held, top-4,
no shared expert) with the benchmark's seeded weights on both sides: the
program's tree and the plain reference's layers. With pages of 4 a ring of 8
wraps inside a bucket of 16 and a chunk of 8 crosses pages. Shared by
test_window_moe.py / test_window_serving.py."""

import os
import sys

import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import window_moe_weights as weights  # noqa: E402
from benchmark.reference import window_moe_decoder as reference  # noqa: E402
from llama_pipeline_parallel_tpu.models.window_moe.config import (  # noqa: E402
    WindowMoEConfig,
)

MODEL = {
    "hidden_size": 32, "num_hidden_layers": 7, "vocab_size": 128,
    "hybrid_layer_pattern": [0, 1, 1, 1, 1, 1, 0],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1],
    "num_attention_heads": 8, "head_dim": 24, "v_head_dim": 16,
    "swa_num_attention_heads": 8, "swa_head_dim": 24, "swa_v_head_dim": 16,
    "num_key_value_heads": 2, "swa_num_key_value_heads": 4,
    "partial_rotary_factor": 0.334,         # 8 of 24, as 64 of 192
    "rope_theta": 5000000, "swa_rope_theta": 10000,
    "sliding_window": 8, "sliding_window_size": 8, "attention_chunk_size": 8,
    "attention_value_scale": 0.707,
    "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": False,
    "attention_bias": False, "hidden_act": "silu",
    "layernorm_epsilon": 1e-5, "intermediate_size": 64,
    "n_routed_experts": 8, "router_experts": 16, "expert_offset": 4,
    "num_experts_per_tok": 4, "n_group": 1, "topk_group": 1,
    "moe_intermediate_size": 24, "n_shared_experts": None,
    "norm_topk_prob": True, "scoring_func": "sigmoid",
    "routed_scaling_factor": None,
    # a wider draw than the 0.02 of the real widths (hybrid_tiny.py)
    "init_std": 0.15,
}
SEED = 7
PAGE, WINDOW = 4, 8


def config(model=MODEL, **kw) -> WindowMoEConfig:
    kw = {"dtype": jnp.float32, "param_dtype": jnp.float32, **kw}
    return WindowMoEConfig.from_published(model, **kw)


def both_sides(model=MODEL, seed=SEED):
    """(program params, reference `top`, reference `layer_fn`)."""
    top = weights.make_top(seed, model, jnp.float32)
    return (weights.make_program_weights(seed, model, jnp.float32), top,
            weights.layer_fn(seed, model, jnp.float32))
