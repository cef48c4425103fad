"""A tiny hybrid block (two periods, 16 experts of which 8 are held) with the
benchmark's seeded weights on both sides: the program's tree and the plain
reference's layers. Shared by test_hybrid_moe.py / test_hybrid_serving.py."""

import os
import sys

import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import hybrid_moe_weights as weights  # noqa: E402
from benchmark.reference import hybrid_moe_decoder as reference  # noqa: E402
from llama_pipeline_parallel_tpu.models.hybrid_moe import model as hybrid  # noqa: E402
from llama_pipeline_parallel_tpu.models.hybrid_moe.config import (  # noqa: E402
    HybridMoEConfig,
)
from llama_pipeline_parallel_tpu.utils import trace  # noqa: E402

MODEL = {
    "hidden_size": 32, "num_hidden_layers": 8, "num_attention_heads": 4,
    "head_dim": 8, "num_key_value_heads": 2, "vocab_size": 128,
    "moe_intermediate_size": 16, "rms_norm_eps": 1e-5,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 8,
                           "num_heads": 2, "num_kv_heads": None},
    "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12], "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "n_routed_experts": 8, "router_experts": 16, "expert_offset": 4,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 4, "kda_low_rank": 4,
    # a wider draw than the 0.02 of the real widths: at 32 wide that would
    # leave gates at 1/2 and scores flat, and a term could drop out unseen
    "init_std": 0.15,
}
SEED = 3


def config(model=MODEL, **kw) -> HybridMoEConfig:
    kw = {"dtype": jnp.float32, "param_dtype": jnp.float32, **kw}
    return HybridMoEConfig.from_published(model, **kw)


def both_sides(model=MODEL, seed=SEED):
    """(program params, reference `top`, reference `layer_fn`)."""
    top = weights.make_top(seed, model, jnp.float32)
    return (weights.make_program_weights(seed, model, jnp.float32), top,
            weights.layer_fn(seed, model, jnp.float32))


# -- the expert layer, which every tiny model of the three families shares -------

def stack_of_one(moe):
    """One layer's own routed experts as `moe_block` takes them: a stack of
    one period."""
    return {name: moe[name][None] for name in hybrid.EXPERT_LEAVES}


def moe_block_alone(moe, x, valid, cfg, **kw):
    """`moe_block` on one layer's own leaves, at place 0 of a stack of one."""
    return hybrid.moe_block(moe, stack_of_one(moe), 0, x, valid, cfg, **kw)


def biased(moe, case):
    """A layer's router (16 wide, experts [4, 12) held) under a selection
    bias that makes the case: `idle` keeps every row off held expert 6, `one`
    sends every row to held expert 5 and to three experts that are not
    held; any other case leaves the seeded router alone."""
    bias = np.zeros(16, np.float32)
    if case == "idle":
        bias[6] = -10.0
    elif case == "one":
        bias[[5, 0, 1, 2]] = 10.0
    return {**moe, "router_bias": jnp.asarray(bias)}


def expert_operands(eqns, cfg):
    """Of a traced program's equations: (the leading size of every grouped
    product's right operand, the one three-dimensional operand of the
    `grouped_matmul` kernel; the equations whose result is ONE layer's
    experts, `[held, d, f]` or `[held, f, d]`)."""
    d, f = cfg.hidden_size, cfg.moe_intermediate_size
    alone = {(cfg.held, d, f), (cfg.held, f, d)}
    products = [stack.aval.shape[0] for e in eqns
                if e.primitive.name == "pallas_call"
                and e.params["name"] == trace.KERNEL_GROUPED_MATMUL
                for stack in e.invars if len(stack.aval.shape) == 3]
    sliced = [e for e in eqns
              if any(tuple(v.aval.shape) in alone for v in e.outvars)]
    return products, sliced
