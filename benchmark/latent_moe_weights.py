"""Seeded weights of the latent-attention block (MLA layers of two kinds, an
indexer on the full kind, a leading dense layer, sparse experts), for both
sides, one layer at a time.

A layer's leaves are a function of (seed, layer index) alone, so the served
model can take all of them at once (`make_program_weights`: the program's
layout, in the dtype the configuration states) and the plain reference one
layer at a time, widened to float32 (`layer_fn`), as
`hybrid_moe_weights.py` does for its family.

The draw: normal(0, 0.02) (`init_std` in a configuration file sets another:
the tiny test models use a larger one) for the down-projections, the gates,
the output projections, the feed-forwards, the router, embedding and head;
norm scales 1, the index keys' LayerNorm bias 0, the router's selection bias
0. The up-projections out of a latent (`wqb`, `wkb_k`, `wkb_v`, `wqi`) and
the indexer's head weights (`ww`) are drawn so that their OUTPUT has
standard deviation `OUT_STD` = 1.43 whatever the widths: what normal(0,
0.02) gives at the published widths (0.02 x the latent's RMS sqrt(5120 /
rank) x sqrt(rank)). Attention logits then spread by about 2 and index
scores by about 1.5 at the published widths and at the tiny ones alike:
near-uniform attention would hide a wrong selection. The router and its
bias are float32 whatever the dtype asked for.

The keys are of JAX's `rbg` implementation, as in `hybrid_moe_weights.py`
(a threefry program of these sizes takes the TPU compiler a quarter of a
minute a layer).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.latent_moe_decoder import PERIOD, dims, kind_dims, kind_of

INIT_STD = 0.02
OUT_STD = 1.43
TOP_KEY = 1 << 20         # folded into the seed's key for embed / head
KEY_IMPL = "rbg"


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _mixer(keys, dm: dict, kind: str, dtype) -> dict:
    kd, d = kind_dims(dm, kind), dm["d"]
    H, rq, rkv = kd["heads"], kd["rq"], kd["rkv"]
    proj = lambda shape: _normal(next(keys), shape, dm["std"], dtype)
    # output std OUT_STD for an input of RMS `rms` and width `fan_in`
    out = lambda shape, fan_in, rms: _normal(
        next(keys), shape, OUT_STD / (rms * math.sqrt(fan_in)), dtype)
    mixer = {
        "wqa": proj((d, rq)), "q_norm": jnp.ones((rq,), dtype),
        "wqb": out((rq, H * (kd["nope"] + kd["rope"])), rq, kd["rq_scale"]),
        "wkva": proj((d, rkv + kd["rope"])),
        "kv_norm": jnp.ones((rkv,), dtype),
        "wkb_k": out((rkv, H, kd["nope"]), rkv, kd["rkv_scale"]),
        "wkb_v": out((rkv, H, kd["v"]), rkv, kd["rkv_scale"]),
        "wg": proj((d, H)), "wo": proj((H * kd["v"], d)),
    }
    if kind == "full":
        nh, hd = dm["i_heads"], dm["i_hd"]
        mixer.update({
            "wqi": out((rq, nh * hd), rq, kd["rq_scale"]),
            "wki": proj((d, hd)), "ki_norm": jnp.ones((hd,), dtype),
            "ki_bias": jnp.zeros((hd,), dtype),
            "ww": out((d, nh), d, 1.0)})
    return mixer


def _moe(keys, dm: dict, dtype) -> dict:
    d, f, fs, held = dm["d"], dm["f"], dm["fs"], dm["held"]
    proj = lambda shape: _normal(next(keys), shape, dm["std"], dtype)
    return {
        "router": _normal(next(keys), (d, dm["router"]), dm["std"], jnp.float32),
        "router_bias": jnp.zeros((dm["router"],), jnp.float32),
        "gate": proj((held, d, f)), "up": proj((held, d, f)),
        "down": proj((held, f, d)),
        "shared_gate": proj((d, fs)), "shared_up": proj((d, fs)),
        "shared_down": proj((fs, d)),
    }


def _mlp(keys, dm: dict, dtype) -> dict:
    d, f = dm["d"], dm["ffn"]
    proj = lambda shape: _normal(next(keys), shape, dm["std"], dtype)
    return {"gate": proj((d, f)), "up": proj((d, f)), "down": proj((f, d))}


def _layer_leaves(seed, index, dm: dict, dtype, kind: str, dense: bool) -> dict:
    key = jax.random.fold_in(jax.random.key(seed, impl=KEY_IMPL), index)
    keys = iter(jax.random.split(key, 32))
    layer = {"input_norm": jnp.ones((dm["d"],), dtype),
             "post_norm": jnp.ones((dm["d"],), dtype),
             "mixer": _mixer(keys, dm, kind, dtype)}
    if dense:
        layer["mlp"] = _mlp(keys, dm, dtype)
    else:
        layer["moe"] = _moe(keys, dm, dtype)
    return layer


@functools.partial(jax.jit, static_argnames=("kind", "dense", "dm_items",
                                             "dtype"))
def _layer(seed, index, *, kind: str, dense: bool, dm_items: tuple,
           dtype) -> dict:
    """One program a KIND of layer: the index is an argument."""
    return _layer_leaves(seed, index, dict(dm_items), dtype, kind, dense)


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
    """The whole tree in the program's layout, in one program: the stacked
    leaves are written where they stay (no second copy of the experts)."""
    dm = dict(dm_items)
    layers = [_layer_leaves(seed, i, dm, dtype, kind_of(i), i == 0)
              for i in range(dm["layers"])]
    return stack_for_program(_top_leaves(seed, dm, dtype), layers)


def _dims(model: dict) -> tuple:
    return tuple(sorted({**dims(model),
                         "std": model.get("init_std", INIT_STD)}.items()))


def _seed(seed: int):
    if not 0 <= seed < 2 ** 32:
        raise ValueError(f"weights seed {seed} outside [0, 2**32)")
    return jnp.asarray(seed, jnp.uint32)


def make_layer(seed: int, index: int, model: dict, dtype=jnp.float32) -> dict:
    """Layer `index` in the reference's layout: `input_norm`, `post_norm`,
    `mixer` and `mlp` (layer 0) or `moe`."""
    return _layer(_seed(seed), jnp.asarray(index, jnp.uint32),
                  kind=kind_of(index), dense=index == 0,
                  dm_items=_dims(model), dtype=dtype)


def make_top(seed: int, model: dict, dtype=jnp.float32) -> dict:
    return _top(_seed(seed), dm_items=_dims(model), dtype=dtype)


def layer_fn(seed: int, model: dict, dtype):
    """`i -> layer i` made in `dtype` and widened to float32: the values the
    served model holds, as the reference takes them."""
    widen = lambda x: x.astype(jnp.float32)
    return lambda i: jax.tree.map(widen, make_layer(seed, i, model, dtype))


def stack_for_program(top: dict, layers: list) -> dict:
    """The program's tree (models/latent_moe/model.py `init_params`): layer 0
    under `first` (its mixer's leaves beside `input_norm` under `attn`, its
    feed-forward as the dense decoder's `post_norm` + `mlp`), the periods'
    leaves stacked [periods, ...]: the full layer's under `full`, the j-th
    sliding layer's under `win[j]`, the expert half of the j-th layer under
    `moe[j]`."""
    stack = lambda trees: jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
    n = len(PERIOD)
    groups = [layers[i:i + n] for i in range(1, len(layers), n)]
    with_norm = lambda l: {"input_norm": l["input_norm"], **l["mixer"]}
    moe = lambda l: {"post_norm": l["post_norm"], **l["moe"]}
    first = layers[0]
    return {
        "embed": {"embedding": top["embed"]},
        "first": {"attn": with_norm(first), "post_norm": first["post_norm"],
                  "mlp": first["mlp"]},
        "periods": {
            "full": stack([with_norm(g[0]) for g in groups]),
            "win": [stack([with_norm(g[j]) for g in groups])
                    for j in range(1, n)],
            "moe": [stack([moe(g[j]) for g in groups]) for j in range(n)],
        },
        "norm": top["norm"], "lm_head": top["lm_head"],
    }


def make_program_weights(seed: int, model: dict, dtype) -> dict:
    return _program(_seed(seed), dm_items=_dims(model), dtype=dtype)


def param_count(model: dict) -> dict:
    """Counts by part, for the configuration file's arithmetic."""
    dm = dict(_dims(model))
    size = lambda tree: sum(math.prod(x.shape) for x in jax.tree.leaves(tree))
    shape_of = lambda i: jax.eval_shape(
        lambda s: _layer_leaves(s, i, dm, jnp.float32, kind_of(i), i == 0),
        jnp.uint32(0))
    first, full, sliding = shape_of(0), shape_of(1), shape_of(2)
    top = 2 * dm["vocab"] * dm["d"] + dm["d"]
    periods = (dm["layers"] - 1) // len(PERIOD)
    return {"first_layer": size(first), "full_mixer": size(full["mixer"]),
            "sliding_mixer": size(sliding["mixer"]),
            "dense_ffn": size(first["mlp"]),
            "expert_half": size(full["moe"]),
            "routed_experts_per_layer": size(
                {k: full["moe"][k] for k in ("gate", "up", "down")}),
            "embed_head_norm": top,
            "total": size(first) + periods * (
                size(full) + (len(PERIOD) - 1) * size(sliding)) + top}
