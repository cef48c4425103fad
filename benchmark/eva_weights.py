"""Seeded weights of the compressed-window decoder
(`benchmark/reference/eva_decoder.py`), for both sides.

As `weights.py`, whose draw this is: normal(0, 0.02) (`seeded_init_std` in a
configuration file sets another), one PRNG key a tensor in the order of
LEAVES, layer leaves stacked on a leading depth axis, one jitted call on the
device in the dtype asked for. The published `init_std` (0.01275) is NOT
used: with it the seeded model's logits are too flat for the comparison to
read (the configuration file's `assumed`). What this
family adds to the dense tree: the pooling vectors `mu` / `phi` [L, heads,
head_dim], normal(0, 1) so that a chunk's pooling weights are not uniform
(their scores spread by about 1.3); norm OFFSETS at zero (the scale is `1 +
g`); a head of `num_pred_heads x vocab` columns, of which both sides read the
first `vocab`.

The served model takes the tree as it is (`make_program_weights`: the
program's layout is this one); the plain reference takes the same values
widened to float32 (`make_reference_weights`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

INIT_STD, POOL_STD = 0.02, 1.0
LEAVES = ("embed", "wq", "wk", "wv", "wo", "gate", "up", "down", "lm_head",
          "mu", "phi")


def shapes(model: dict) -> dict:
    n, d, f, v = (model["num_hidden_layers"], model["hidden_size"],
                  model["intermediate_size"], model["vocab_size"])
    heads = model["num_key_value_heads"]
    hd = d // model["num_attention_heads"]
    return {"embed": (v, d), "wq": (n, d, d), "wk": (n, d, heads * hd),
            "wv": (n, d, heads * hd), "wo": (n, d, d), "gate": (n, d, f),
            "up": (n, d, f), "down": (n, f, d),
            "lm_head": (d, model["num_pred_heads"] * v),
            "mu": (n, heads, hd), "phi": (n, heads, hd)}


def _build(seed, *, model_items: tuple, dtype) -> dict:
    model = dict(model_items)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(LEAVES))
    sh = shapes(model)
    std = lambda name: POOL_STD if name in ("mu", "phi") else model["std"]
    w = {name: (jax.random.normal(key, sh[name], jnp.float32)
                * std(name)).astype(dtype)
         for name, key in zip(LEAVES, keys)}
    n, d = model["num_hidden_layers"], model["hidden_size"]
    return {
        "embed": {"embedding": w["embed"]},
        "layers": {
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo", "mu", "phi")},
            "mlp": {k: w[k] for k in ("gate", "up", "down")},
            "input_norm": jnp.zeros((n, d), dtype),
            "post_norm": jnp.zeros((n, d), dtype),
        },
        "norm": jnp.zeros((d,), dtype),
        "lm_head": w["lm_head"],
    }


def _builder(model: dict, dtype):
    sizes = tuple(sorted((k, model[k]) for k in (
        "num_hidden_layers", "hidden_size", "intermediate_size", "vocab_size",
        "num_attention_heads", "num_key_value_heads", "num_pred_heads")))
    sizes += (("std", model.get("seeded_init_std", INIT_STD)),)
    return functools.partial(_build, model_items=sizes, dtype=dtype)


def abstract(model: dict, dtype=jnp.bfloat16) -> dict:
    """The tree of shapes, with nothing allocated."""
    return jax.eval_shape(_builder(model, dtype), jnp.uint32(0))


def make_program_weights(seed: int, model: dict, dtype=jnp.bfloat16) -> dict:
    """The parameter tree in the program's layout (`models/eva/model.py`
    `init_params`'s). `seed` must be below 2**32."""
    if not 0 <= seed < 2 ** 32:
        raise ValueError(f"weights seed {seed} outside [0, 2**32)")
    return jax.jit(_builder(model, dtype))(jnp.asarray(seed, jnp.uint32))


def make_reference_weights(seed: int, model: dict, dtype=jnp.bfloat16) -> dict:
    """The same values, made in `dtype` (what the engine held) and widened
    to float32."""
    return jax.tree.map(lambda x: x.astype(jnp.float32),
                        make_program_weights(seed, model, dtype))


def param_count(model: dict) -> dict:
    """Counts by part, for the configuration file's arithmetic."""
    import math

    sh = shapes(model)
    size = lambda name: math.prod(sh[name])
    layers = sum(size(k) for k in ("wq", "wk", "wv", "wo", "gate", "up",
                                   "down", "mu", "phi"))
    norms = (2 * model["num_hidden_layers"] + 1) * model["hidden_size"]
    return {"layers": layers, "embed": size("embed"),
            "lm_head": size("lm_head"), "norms": norms,
            "total": layers + size("embed") + size("lm_head") + norms}
