"""Seeded weights: data made from `--seed`, by the benchmark, for both sides.

The served model gets them from here (`ServeEngine` takes its parameters as
an argument), and so does the plain reference. A trainer started through
`train.py`'s entry point makes its own from `seed`; the draw below is the same
one (normal 0.02, norm scales 1, one key per tensor in the order of LEAVES),
so the reference regenerates the weights a training run starts from without
taking anything from it. `tests/benchmark_harness` holds the two equal at a
tiny size, and every training run's step-1 loss would show a drift.

One jitted call on the device, in the dtype asked for.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02
# one PRNG key each, in this order; norm scales are ones and take no key
LEAVES = ("embed", "wq", "wk", "wv", "wo", "gate", "up", "down", "lm_head")


def shapes(model: dict) -> dict:
    n, d, f, v = (model["num_hidden_layers"], model["hidden_size"],
                  model["intermediate_size"], model["vocab_size"])
    kv = model["num_key_value_heads"] * (d // model["num_attention_heads"])
    return {"embed": (v, d), "wq": (n, d, d), "wk": (n, d, kv),
            "wv": (n, d, kv), "wo": (n, d, d), "gate": (n, d, f),
            "up": (n, d, f), "down": (n, f, d), "lm_head": (d, v)}


def _build(seed, *, model_items: tuple, dtype) -> dict:
    model = dict(model_items)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(LEAVES))
    sh = shapes(model)
    w = {name: (jax.random.normal(key, sh[name], jnp.float32)
                * INIT_STD).astype(dtype)
         for name, key in zip(LEAVES, keys)}
    n, d = model["num_hidden_layers"], model["hidden_size"]
    return {
        "embed": {"embedding": w["embed"]},
        "layers": {
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "mlp": {k: w[k] for k in ("gate", "up", "down")},
            "input_norm": jnp.ones((n, d), dtype),
            "post_norm": jnp.ones((n, d), dtype),
        },
        "norm": jnp.ones((d,), dtype),
        "lm_head": w["lm_head"],
    }


def _builder(model: dict, dtype):
    sizes = tuple(sorted((k, model[k]) for k in (
        "num_hidden_layers", "hidden_size", "intermediate_size", "vocab_size",
        "num_attention_heads", "num_key_value_heads")))
    return functools.partial(_build, model_items=sizes, dtype=dtype)


def abstract(model: dict, dtype=jnp.float32) -> dict:
    """The tree of shapes, with nothing allocated."""
    return jax.eval_shape(_builder(model, dtype), jnp.uint32(0))


def make_weights(seed: int, model: dict, dtype=jnp.float32,
                 shardings=None) -> dict:
    """The parameter tree (layer leaves stacked on a leading depth axis).
    `seed` must be below 2**32 (the key is [0, seed], as PRNGKey(seed))."""
    if not 0 <= seed < 2 ** 32:
        raise ValueError(f"weights seed {seed} outside [0, 2**32)")
    return jax.jit(_builder(model, dtype), out_shardings=shardings)(
        jnp.asarray(seed, jnp.uint32))


def param_count(model: dict) -> dict:
    """Counts by part, for the configuration files' arithmetic."""
    sh = shapes(model)
    n, d = model["num_hidden_layers"], model["hidden_size"]
    size = lambda name: math.prod(sh[name])
    layer = sum(size(k) for k in ("wq", "wk", "wv", "wo", "gate", "up", "down"))
    return {"layers": layer + 2 * n * d, "embed": size("embed"),
            "lm_head": size("lm_head"), "norm": d,
            "total": layer + 2 * n * d + size("embed") + size("lm_head") + d}
