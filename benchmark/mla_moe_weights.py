"""Seeded weights of a decoder of one kind of layer (plain MLA, a leading
dense layer, sparse experts: `benchmark/reference/mla_moe_decoder.py`), for
both sides, one layer at a time.

As `latent_moe_weights.py`, whose draw, keys and feed-forward leaves these
are: a layer's leaves are a function of (seed, layer index) alone, so the
served model takes all of them at once (`make_program_weights`: the
program's layout, in the dtype the configuration states) and the plain
reference one layer at a time, widened to float32 (`layer_fn`).
normal(0, 0.02) (`init_std` in a configuration file sets another) for the
down-projections, the output projection, the feed-forwards, the router,
embedding and head; norm scales 1. The up-projections out of a latent
(`wqb`, `wkb_k`, `wkb_v`) are drawn so that their OUTPUT has standard
deviation `OUT_STD` = 1.43 whatever the widths, as for dots3-note-prev: the
attention logits then spread by about 4 at the published widths under
YaRN's scale (1.81 / sqrt(192)), so that what a query reads of a long row
shows in the logits. This mixer has no gate, no indexer and no rescale of
its latents (their RMS after the norm is 1).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import latent_moe_weights as shared
from benchmark.reference.mla_moe_decoder import dims

INIT_STD, OUT_STD = shared.INIT_STD, shared.OUT_STD


def _mixer(keys, dm: dict, dtype) -> dict:
    d, H, rq, rkv = dm["d"], dm["heads"], dm["rq"], dm["rkv"]
    proj = lambda shape: shared._normal(next(keys), shape, dm["std"], dtype)
    out = lambda shape, fan_in: shared._normal(
        next(keys), shape, OUT_STD / math.sqrt(fan_in), dtype)
    return {
        "wqa": proj((d, rq)), "q_norm": jnp.ones((rq,), dtype),
        "wqb": out((rq, H * (dm["nope"] + dm["rope"])), rq),
        "wkva": proj((d, rkv + dm["rope"])),
        "kv_norm": jnp.ones((rkv,), dtype),
        "wkb_k": out((rkv, H, dm["nope"]), rkv),
        "wkb_v": out((rkv, H, dm["v"]), rkv),
        "wo": proj((H * dm["v"], d)),
    }


def _layer_leaves(seed, index, dm: dict, dtype, dense: bool) -> dict:
    key = jax.random.fold_in(jax.random.key(seed, impl=shared.KEY_IMPL), index)
    keys = iter(jax.random.split(key, 32))
    layer = {"input_norm": jnp.ones((dm["d"],), dtype),
             "post_norm": jnp.ones((dm["d"],), dtype),
             "mixer": _mixer(keys, dm, dtype)}
    if dense:
        layer["mlp"] = shared._mlp(keys, dm, dtype)
    else:
        layer["moe"] = shared._moe(keys, dm, dtype)
    return layer


@functools.partial(jax.jit, static_argnames=("dense", "dm_items", "dtype"))
def _layer(seed, index, *, dense: bool, dm_items: tuple, dtype) -> dict:
    """One program a KIND of layer: the index is an argument."""
    return _layer_leaves(seed, index, dict(dm_items), dtype, dense)


@functools.partial(jax.jit, static_argnames=("dm_items", "dtype"))
def _top(seed, *, dm_items: tuple, dtype) -> dict:
    return shared._top_leaves(seed, dict(dm_items), dtype)


@functools.partial(jax.jit, static_argnames=("dm_items", "dtype"))
def _program(seed, *, dm_items: tuple, dtype) -> dict:
    """The whole tree in the program's layout, in one program: the stacked
    leaves are written where they stay (no second copy of the experts)."""
    dm = dict(dm_items)
    layers = [_layer_leaves(seed, i, dm, dtype, i == 0)
              for i in range(dm["layers"])]
    return stack_for_program(shared._top_leaves(seed, dm, dtype), layers)


def _dims(model: dict) -> tuple:
    return tuple(sorted({**dims(model),
                         "std": model.get("init_std", INIT_STD)}.items()))


def make_layer(seed: int, index: int, model: dict, dtype=jnp.float32) -> dict:
    """Layer `index` in the reference's layout: `input_norm`, `post_norm`,
    `mixer` and `mlp` (layer 0) or `moe`."""
    return _layer(shared._seed(seed), jnp.asarray(index, jnp.uint32),
                  dense=index == 0, dm_items=_dims(model), dtype=dtype)


def make_top(seed: int, model: dict, dtype=jnp.float32) -> dict:
    return _top(shared._seed(seed), dm_items=_dims(model), dtype=dtype)


def layer_fn(seed: int, model: dict, dtype):
    """`i -> layer i` made in `dtype` and widened to float32: the values the
    served model holds, as the reference takes them."""
    widen = lambda x: x.astype(jnp.float32)
    return lambda i: jax.tree.map(widen, make_layer(seed, i, model, dtype))


def stack_for_program(top: dict, layers: list) -> dict:
    """The program's tree (models/latent_moe/model.py `init_params`) for a
    period of one full layer: layer 0 under `first`, the later layers'
    mixers stacked under `periods.full`, their expert halves under
    `periods.moe[0]`, no sliding layers (`periods.win` empty)."""
    stack = lambda trees: jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
    with_norm = lambda l: {"input_norm": l["input_norm"], **l["mixer"]}
    first, rest = layers[0], layers[1:]
    return {
        "embed": {"embedding": top["embed"]},
        "first": {"attn": with_norm(first), "post_norm": first["post_norm"],
                  "mlp": first["mlp"]},
        "periods": {
            "full": stack([with_norm(l) for l in rest]), "win": [],
            "moe": [stack([{"post_norm": l["post_norm"], **l["moe"]}
                           for l in rest])]},
        "norm": top["norm"], "lm_head": top["lm_head"],
    }


def make_program_weights(seed: int, model: dict, dtype) -> dict:
    return _program(shared._seed(seed), dm_items=_dims(model), dtype=dtype)


def param_count(model: dict) -> dict:
    """Counts by part, for the configuration file's arithmetic."""
    dm = dict(_dims(model))
    size = lambda tree: sum(math.prod(x.shape) for x in jax.tree.leaves(tree))
    shape_of = lambda i: jax.eval_shape(
        lambda s: _layer_leaves(s, i, dm, jnp.float32, i == 0), jnp.uint32(0))
    first, later = shape_of(0), shape_of(1)
    top = 2 * dm["vocab"] * dm["d"] + dm["d"]
    return {"first_layer": size(first), "mixer": size(later["mixer"]),
            "dense_ffn": size(first["mlp"]), "expert_half": size(later["moe"]),
            "routed_experts_per_layer": size(
                {k: later["moe"][k] for k in ("gate", "up", "down")}),
            "embed_head_norm": top,
            "total": size(first) + (dm["layers"] - 1) * size(later) + top}
