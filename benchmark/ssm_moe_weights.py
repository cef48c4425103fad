"""Seeded weights of the state-space / expert block (Mamba-2, grouped-query
softmax and latent expert layers, one kind a layer), for both sides, one
layer at a time.

A layer's leaves are a function of (seed, layer index) alone and have ONE
layout, the program's own (`models/ssm_moe/model.py`: a dict of the layer's
leaves, nothing stacked), so the served model takes all of them at once
(`make_program_weights`, in the dtype the configuration states) and the plain
reference one layer at a time, widened to float32 (`layer_fn`): the float32
weights of the whole model do not fit one chip beside each other.

The draw, as `benchmark/hybrid_moe_weights.py` draws its own: normal(0, 0.02)
(`init_std` in a configuration file sets another: the tiny test models use a
larger one, so that every term is alive at their widths) for every
projection, embedding and head; norm scales 1; convolution taps normal(0,
0.3) and the convolution's bias normal(0, 0.1); `A_log = log U(1, 16)` and
`dt_bias` the inverse softplus of `logU(1e-3, 1e-1)` a head, so that a step's
decay is neither 0 nor 1; `D` ones; the router's selection bias 0. The
router, its bias, `A_log`, `D` and `dt_bias` are float32 whatever the dtype
asked for. Keys are of the `rbg` implementation, for the reason given there.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.ssm_moe_decoder import dims

INIT_STD = 0.02
CONV_STD, CONV_BIAS_STD = 0.3, 0.1
TOP_KEY = 1 << 20         # folded into the seed's key for embed / head
KEY_IMPL = "rbg"


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _mamba(keys, dm: dict, dtype) -> dict:
    d, H, inner = dm["d"], dm["H"], dm["H"] * dm["P"]
    cw = inner + 2 * dm["G"] * dm["N"]
    proj = lambda shape: _normal(next(keys), shape, dm["std"], dtype)
    step = jnp.exp(jax.random.uniform(
        next(keys), (H,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    return {
        "input_norm": jnp.ones((d,), dtype),
        "in_proj": proj((d, inner + cw + H)),
        "conv_w": _normal(next(keys), (dm["conv"], cw), CONV_STD, dtype),
        "conv_b": _normal(next(keys), (cw,), CONV_BIAS_STD, dtype),
        "dt_bias": jnp.log(jnp.expm1(step)),        # softplus^-1
        "A_log": jnp.log(jax.random.uniform(next(keys), (H,), jnp.float32,
                                            1.0, 16.0)),
        "D": jnp.ones((H,), jnp.float32),
        "gate_norm": jnp.ones((inner,), dtype), "out_proj": proj((inner, d)),
    }


def _softmax(keys, dm: dict, dtype) -> dict:
    d, q, kv = dm["d"], dm["heads"] * dm["hd"], dm["kv"] * dm["hd"]
    proj = lambda shape: _normal(next(keys), shape, dm["std"], dtype)
    return {"input_norm": jnp.ones((d,), dtype), "wq": proj((d, q)),
            "wk": proj((d, kv)), "wv": proj((d, kv)), "wo": proj((q, d))}


def _experts(keys, dm: dict, dtype) -> dict:
    d, lat, f, fs, held = dm["d"], dm["latent"], dm["f"], dm["fs"], dm["held"]
    proj = lambda shape: _normal(next(keys), shape, dm["std"], dtype)
    return {
        "post_norm": jnp.ones((d,), dtype),
        "router": _normal(next(keys), (d, dm["router"]), dm["std"], jnp.float32),
        "router_bias": jnp.zeros((dm["router"],), jnp.float32),
        "latent_in": proj((d, lat)), "up": proj((held, lat, f)),
        "down": proj((held, f, lat)), "latent_out": proj((lat, d)),
        "shared_up": proj((d, fs)), "shared_down": proj((fs, d)),
    }


_KINDS = {"M": _mamba, "*": _softmax, "E": _experts}


def _layer_leaves(seed, index, dm: dict, dtype, kind: str) -> dict:
    key = jax.random.fold_in(jax.random.key(seed, impl=KEY_IMPL), index)
    return _KINDS[kind](iter(jax.random.split(key, 12)), dm, dtype)


@functools.partial(jax.jit, static_argnames=("kind", "dm_items", "dtype"))
def _layer(seed, index, *, kind: str, dm_items: tuple, dtype) -> dict:
    """One program a KIND of layer: the index is an argument."""
    return _layer_leaves(seed, index, dict(dm_items), dtype, kind)


def _top_leaves(seed, dm: dict, dtype) -> dict:
    key = jax.random.fold_in(jax.random.key(seed, impl=KEY_IMPL), TOP_KEY)
    k_embed, k_head = jax.random.split(key)
    return {"embed": _normal(k_embed, (dm["vocab"], dm["d"]), dm["std"], dtype),
            "norm": jnp.ones((dm["d"],), dtype),
            "lm_head": _normal(k_head, (dm["d"], dm["vocab"]), dm["std"], dtype)}


@functools.partial(jax.jit, static_argnames=("dm_items", "dtype"))
def _top(seed, *, dm_items: tuple, dtype) -> dict:
    return _top_leaves(seed, dict(dm_items), dtype)


@functools.partial(jax.jit, static_argnames=("dm_items", "dtype"))
def _program(seed, *, dm_items: tuple, dtype) -> dict:
    """The whole tree in the program's layout, in one program."""
    dm = dict(dm_items)
    top = _top_leaves(seed, dm, dtype)
    return {"embed": {"embedding": top["embed"]},
            "layers": [_layer_leaves(seed, i, dm, dtype, kind)
                       for i, kind in enumerate(dm["pattern"])],
            "norm": top["norm"], "lm_head": top["lm_head"]}


def _dims(model: dict) -> tuple:
    return tuple(sorted({**dims(model),
                         "std": model.get("init_std", INIT_STD)}.items()))


def _seed(seed: int):
    if not 0 <= seed < 2 ** 32:
        raise ValueError(f"weights seed {seed} outside [0, 2**32)")
    return jnp.asarray(seed, jnp.uint32)


def make_layer(seed: int, index: int, model: dict, dtype=jnp.float32) -> dict:
    """Layer `index`'s leaves, of the kind the pattern gives it."""
    return _layer(_seed(seed), jnp.asarray(index, jnp.uint32),
                  kind=dims(model)["pattern"][index], dm_items=_dims(model),
                  dtype=dtype)


def make_top(seed: int, model: dict, dtype=jnp.float32) -> dict:
    return _top(_seed(seed), dm_items=_dims(model), dtype=dtype)


def layer_fn(seed: int, model: dict, dtype):
    """`i -> layer i` made in `dtype` and widened to float32: the values the
    served model holds, as the reference takes them."""
    widen = lambda x: x.astype(jnp.float32)
    return lambda i: jax.tree.map(widen, make_layer(seed, i, model, dtype))


def make_program_weights(seed: int, model: dict, dtype) -> dict:
    return _program(_seed(seed), dm_items=_dims(model), dtype=dtype)


def param_count(model: dict) -> dict:
    """Counts by part, for the configuration file's arithmetic."""
    dm = dict(_dims(model))
    size = lambda tree: sum(math.prod(x.shape) for x in jax.tree.leaves(tree))
    shape_of = lambda kind: jax.eval_shape(
        lambda s: _layer_leaves(s, 0, dm, jnp.float32, kind), jnp.uint32(0))
    layers = {kind: shape_of(kind) for kind in _KINDS}
    experts = size({k: layers["E"][k] for k in ("up", "down")})
    top = 2 * dm["vocab"] * dm["d"] + dm["d"]
    return {"mamba_layer": size(layers["M"]), "softmax_layer": size(layers["*"]),
            "expert_layer": size(layers["E"]),
            "routed_experts_per_layer": experts, "embed_head_norm": top,
            "total": top + sum(size(layers[kind]) for kind in dm["pattern"])}
