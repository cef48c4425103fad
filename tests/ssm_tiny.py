"""A tiny state-space / expert block (seven layers that hold all three
kinds, `MEM*EME`; 16 experts of which 8 are held, top-4, a latent of 16) with
the benchmark's seeded weights on both sides: the program's tree and the
plain reference's layers. Shared by test_ssm_moe.py / test_ssm_serving.py."""

import os
import sys

import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import ssm_moe_weights as weights  # noqa: E402
from benchmark.reference import ssm_moe_decoder as reference  # noqa: E402
from llama_pipeline_parallel_tpu.models.ssm_moe.config import (  # noqa: E402
    SsmMoEConfig,
)

MODEL = {
    "hidden_size": 32, "num_hidden_layers": 7,
    "hybrid_override_pattern": "MEM*EME", "vocab_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "expand": 2,
    "ssm_state_size": 8, "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
    "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
    "use_conv_bias": True, "mamba_proj_bias": False, "mlp_bias": False,
    "attention_bias": False, "norm_eps": 1e-5,
    "n_routed_experts": 8, "router_experts": 16, "expert_offset": 4,
    "num_experts_per_tok": 4, "n_group": 1, "topk_group": 1,
    "moe_latent_size": 16, "moe_intermediate_size": 24,
    "n_shared_experts": 1, "moe_shared_expert_intermediate_size": 48,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    # a wider draw than the 0.02 of the real widths (hybrid_tiny.py)
    "init_std": 0.15,
}
SEED = 7


def config(model=MODEL, **kw) -> SsmMoEConfig:
    kw = {"dtype": jnp.float32, "param_dtype": jnp.float32, **kw}
    return SsmMoEConfig.from_published(model, **kw)


def both_sides(model=MODEL, seed=SEED):
    """(program params, reference `top`, reference `layer_fn`)."""
    top = weights.make_top(seed, model, jnp.float32)
    return (weights.make_program_weights(seed, model, jnp.float32), top,
            weights.layer_fn(seed, model, jnp.float32))
