"""Seeded weights of the dense state-space block (a Mamba-2 or a softmax
mixer and a dense SwiGLU half a layer, the head tied to the table), for both
sides, one layer at a time.

A published layer's leaves are a function of (seed, layer index) alone. The
plain reference takes them a layer at a time, widened to float32
(`layer_fn`: the mixer's leaves, `post_norm` and `mlp` in one dict); the
served model takes all of them at once (`make_program_weights`, in the dtype
the configuration states) in the program's layout (`models/ssm_moe/model.py`:
a list with one entry a HALF, the mixer's dict then the feed-forward's, and
no `lm_head`), made a layer a call so that no program ever holds more than
one layer's float32 draws.

The draw is `benchmark/ssm_moe_weights.py`'s own functions (`_mamba`,
`_softmax`, called): normal(0, 0.02) (`init_std` in
a configuration file sets another: the tiny test models use a larger one)
for every projection and the table; norm scales 1; convolution taps
normal(0, 0.3) and the convolution's bias normal(0, 0.1); `A_log = log U(1,
16)` and `dt_bias` the inverse softplus of `logU(1e-3, 1e-1)` a head; `D`
ones. `A_log`, `D` and `dt_bias` are float32 whatever the dtype asked for.
Keys are of the `rbg` implementation, for the reason given there.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.granite_hybrid_decoder import dims
from benchmark.ssm_moe_weights import (  # the expert block's draws, as they are
    INIT_STD,
    KEY_IMPL,
    TOP_KEY,
    _mamba,
    _normal,
    _seed,
    _softmax,
)


def _dense(keys, dm: dict, dtype) -> dict:
    d, f = dm["d"], dm["f"]
    proj = lambda shape: _normal(next(keys), shape, dm["std"], dtype)
    return {"post_norm": jnp.ones((d,), dtype),
            "mlp": {"gate": proj((d, f)), "up": proj((d, f)),
                    "down": proj((f, d))}}


_MIXERS = {"mamba": _mamba, "attention": _softmax}


def _layer_leaves(seed, index, dm: dict, dtype, kind: str) -> dict:
    """A layer's mixer leaves, `post_norm` and `mlp`, in one dict."""
    key = jax.random.fold_in(jax.random.key(seed, impl=KEY_IMPL), index)
    keys = iter(jax.random.split(key, 12))
    return {**_MIXERS[kind](keys, dm, dtype), **_dense(keys, dm, dtype)}


@functools.partial(jax.jit, static_argnames=("kind", "dm_items", "dtype"))
def _layer(seed, index, *, kind: str, dm_items: tuple, dtype) -> dict:
    """One program a KIND of layer: the index is an argument."""
    return _layer_leaves(seed, index, dict(dm_items), dtype, kind)


@functools.partial(jax.jit, static_argnames=("dm_items", "dtype"))
def _top(seed, *, dm_items: tuple, dtype) -> dict:
    dm = dict(dm_items)
    key = jax.random.fold_in(jax.random.key(seed, impl=KEY_IMPL), TOP_KEY)
    return {"embed": _normal(key, (dm["vocab"], dm["d"]), dm["std"], dtype),
            "norm": jnp.ones((dm["d"],), dtype)}


def _dims(model: dict) -> tuple:
    dm = dims(model)
    if not dm["tied"]:
        raise ValueError("these weights are of a head tied to the table")
    return tuple(sorted({**dm, "std": model.get("init_std", INIT_STD)}.items()))


def make_layer(seed: int, index: int, model: dict, dtype=jnp.float32) -> dict:
    """Layer `index`'s leaves (both halves), of the kind `layer_types`
    gives it."""
    return _layer(_seed(seed), jnp.asarray(index, jnp.uint32),
                  kind=dims(model)["types"][index], dm_items=_dims(model),
                  dtype=dtype)


def make_top(seed: int, model: dict, dtype=jnp.float32) -> dict:
    """The table (`embed`, which is the head too) and the final norm."""
    return _top(_seed(seed), dm_items=_dims(model), dtype=dtype)


def layer_fn(seed: int, model: dict, dtype):
    """`i -> layer i` made in `dtype` and widened to float32: the values the
    served model holds, as the reference takes them."""
    widen = lambda x: x.astype(jnp.float32)
    return lambda i: jax.tree.map(widen, make_layer(seed, i, model, dtype))


def make_program_weights(seed: int, model: dict, dtype) -> dict:
    """The whole tree in the program's layout: a layer's mixer and its
    feed-forward as two entries of `layers`, and no `lm_head`."""
    top = make_top(seed, model, dtype)
    halves = []
    for i in range(model["num_hidden_layers"]):
        layer = make_layer(seed, i, model, dtype)
        dense = {"post_norm": layer.pop("post_norm"), "mlp": layer.pop("mlp")}
        halves += [layer, dense]
    return {"embed": {"embedding": top["embed"]}, "layers": halves,
            "norm": top["norm"]}


def param_count(model: dict) -> dict:
    """Counts by part, for the configuration file's arithmetic."""
    dm = dict(_dims(model))
    size = lambda tree: sum(math.prod(x.shape) for x in jax.tree.leaves(tree))
    shape_of = lambda kind: jax.eval_shape(
        lambda s: _layer_leaves(s, 0, dm, jnp.float32, kind), jnp.uint32(0))
    layers = {kind: size(shape_of(kind)) for kind in _MIXERS}
    top = dm["vocab"] * dm["d"] + dm["d"]
    return {"mamba_layer": layers["mamba"], "softmax_layer": layers["attention"],
            "table_and_norm": top,
            "total": top + sum(layers[kind] for kind in dm["types"])}
