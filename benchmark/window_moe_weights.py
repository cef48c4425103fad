"""Seeded weights of the window / full softmax block (grouped-query mixers
of two kinds, a dense feed-forward or sigmoid-routed experts), for both
sides, one layer at a time.

A layer's leaves are a function of (seed, layer index) alone and have ONE
layout, the program's own (`models/window_moe/model.py`: a dict of the
layer's leaves, nothing stacked), so the served model takes all of them at
once (`make_program_weights`, in the dtype the configuration states) and the
plain reference one layer at a time, widened to float32 (`layer_fn`): the
float32 weights of the whole model do not fit one chip beside each other.

The draw, as `benchmark/hybrid_moe_weights.py` draws its own: normal(0, 0.02)
(`init_std` in a configuration file sets another: the tiny test models use a
larger one, so that every term is alive at their widths) for every
projection, embedding and head; norm scales 1; the sinks normal(0, 1), so
that a sink weighs about what a key does; the router's selection bias
normal(0, 0.005), enough to move a choice now and then (a test counts them)
and no more: a sigmoid packs the largest of 256 scores together (the 8th
lies near 0.92, where the slope is 0.08), so that at the cell's widths a
bias of 0.005 moves one choice in eighteen, leaves the experts' popularity
within a quarter to a third of even and a chip's sixteenth of the router
within 5 to 9% a layer, and the held experts that a tick of 62 rows hits at
83 to 85% (an even router: 86%), whatever the seed; at 0.05 it moved two
choices in five, an expert's popularity scattered by 100% of the mean and
a sixteenth's share by 20 to 29%, so the SEED set how many of the held
experts a tick read (57 to 66% on the chip) and the tokens a second with it
(PERF.md, PR 48). The sinks, the router and its
bias are float32 whatever the dtype asked for. Keys are of the `rbg`
implementation, for the reason given there.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.window_moe_decoder import WINDOW, dims

INIT_STD = 0.02
SINK_STD = 1.0
BIAS_STD = 0.005
TOP_KEY = 1 << 20         # folded into the seed's key for embed / head
KEY_IMPL = "rbg"


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _layer_leaves(seed, index, dm: dict, dtype, kind: int, moe: int) -> dict:
    key = jax.random.fold_in(jax.random.key(seed, impl=KEY_IMPL), index)
    keys = iter(jax.random.split(key, 12))
    proj = lambda *shape: _normal(next(keys), shape, dm["std"], dtype)
    d, H, dk, dv = dm["d"], dm["heads"], dm["dk"], dm["dv"]
    G = dm["kv_window"] if kind == WINDOW else dm["kv_full"]
    layer = {"input_norm": jnp.ones((d,), dtype), "wq": proj(d, H * dk),
             "wk": proj(d, G * dk), "wv": proj(d, G * dv),
             "wo": proj(H * dv, d), "post_norm": jnp.ones((d,), dtype)}
    if kind == WINDOW:
        layer["sink"] = _normal(next(keys), (H,), SINK_STD, jnp.float32)
    if moe:
        held, f = dm["held"], dm["f"]
        layer.update(
            router=_normal(next(keys), (d, dm["router"]), dm["std"],
                           jnp.float32),
            router_bias=_normal(next(keys), (dm["router"],), BIAS_STD,
                                jnp.float32),
            gate=proj(held, d, f), up=proj(held, d, f), down=proj(held, f, d))
    else:
        layer["mlp"] = {"gate": proj(d, dm["ffn"]), "up": proj(d, dm["ffn"]),
                        "down": proj(dm["ffn"], d)}
    return layer


@functools.partial(jax.jit,
                   static_argnames=("kind", "moe", "dm_items", "dtype"))
def _layer(seed, index, *, kind: int, moe: int, dm_items: tuple,
           dtype) -> dict:
    """One program a KIND of layer: the index is an argument."""
    return _layer_leaves(seed, index, dict(dm_items), dtype, kind, moe)


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
            "layers": [_layer_leaves(seed, i, dm, dtype, dm["pattern"][i],
                                     dm["moe"][i])
                       for i in range(dm["layers"])],
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
    dm = dims(model)
    return _layer(_seed(seed), jnp.asarray(index, jnp.uint32),
                  kind=dm["pattern"][index], moe=dm["moe"][index],
                  dm_items=_dims(model), dtype=dtype)


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
    shape_of = lambda kind, moe: jax.eval_shape(
        lambda s: _layer_leaves(s, 0, dm, jnp.float32, kind, moe),
        jnp.uint32(0))
    layers = [shape_of(k, m) for k, m in zip(dm["pattern"], dm["moe"])]
    attention = ("input_norm", "wq", "wk", "wv", "wo", "sink")
    of_kind = lambda kind: next(
        (size({k: v for k, v in layer.items() if k in attention})
         for layer, p in zip(layers, dm["pattern"]) if p == kind), 0)
    top = 2 * dm["vocab"] * dm["d"] + dm["d"]
    return {"full_attention": of_kind(0), "window_attention": of_kind(WINDOW),
            "expert": 3 * dm["d"] * dm["f"],
            "dense_feed_forward": 3 * dm["d"] * dm["ffn"],
            "embed_head_norm": top,
            "total": top + sum(size(layer) for layer in layers)}
