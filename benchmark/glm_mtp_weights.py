"""Seeded weights of a latent-attention decoder with a multi-token-prediction
module (`benchmark/reference/glm_dsa_mtp_decoder.py`: every layer MLA under an
indexer, no gate; `first_k_dense_replace` leading dense layers, sparse experts
after; one MTP module behind the last layer), for both sides, one layer at a
time.

A layer's leaves are a function of (seed, layer index) alone, drawn by
`latent_moe_weights.py`'s rules (its `_layer_leaves`, called: normal(0, 0.02)
or `init_std`, the up-projections out of a latent and the indexer's head
weights so that their output has standard deviation 1.43 whatever the widths,
so that attention logits and index scores spread as they do at the published
widths; norm scales 1, biases 0, the router float32), less the gate this
model does not have. The module's layer is layer `num_hidden_layers` of the
same draw; its `enorm`, `hnorm` and `shared_head_norm` are 1 and `eh_proj`
normal(0, std), from a key of their own. Table and head are the trunk's.

`make_program_weights` gives the program's tree (models/latent_moe/model.py
`init_params` for a model of one kind of layer that drafts) in one program;
`layer_fn` the reference's layers one at a time, widened to float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import latent_moe_weights as base
from benchmark.reference.glm_dsa_mtp_decoder import dims

MTP_KEY = (1 << 20) + 1   # folded into the seed's key for the module's own


def _layer_leaves(seed, index, dm: dict, dtype, dense: bool) -> dict:
    layer = base._layer_leaves(seed, index, dm, dtype, "full", dense)
    del layer["mixer"]["wg"]            # no gate in this model
    return layer


def _top_leaves(seed, dm: dict, dtype) -> dict:
    top = base._top_leaves(seed, dm, dtype)
    key = jax.random.fold_in(jax.random.key(seed, impl=base.KEY_IMPL), MTP_KEY)
    d = dm["d"]
    ones = jnp.ones((d,), dtype)
    top["mtp"] = {"enorm": ones, "hnorm": ones, "shared_head_norm": ones,
                  "eh_proj": base._normal(key, (2 * d, d), dm["std"], dtype)}
    return top


@functools.partial(jax.jit, static_argnames=("dense", "dm_items", "dtype"))
def _layer(seed, index, *, dense: bool, dm_items: tuple, dtype) -> dict:
    """One program a KIND of layer (dense or sparse): the index is an
    argument."""
    return _layer_leaves(seed, index, dict(dm_items), dtype, dense)


@functools.partial(jax.jit, static_argnames=("dm_items", "dtype"))
def _top(seed, *, dm_items: tuple, dtype) -> dict:
    return _top_leaves(seed, dict(dm_items), dtype)


@functools.partial(jax.jit, static_argnames=("dm_items", "dtype"))
def _program(seed, *, dm_items: tuple, dtype) -> dict:
    """The whole tree in the program's layout, in one program."""
    dm = dict(dm_items)
    layers = [_layer_leaves(seed, i, dm, dtype, i < dm["dense"])
              for i in range(dm["layers"] + 1)]
    return stack_for_program(_top_leaves(seed, dm, dtype), layers[:-1],
                             layers[-1])


def _dims(model: dict) -> tuple:
    dm = dims(model)
    if dm["dense"] != 1:
        raise ValueError("the program's tree has exactly one leading dense "
                         "layer")
    return tuple(sorted({**dm, "std": model.get("init_std",
                                                base.INIT_STD)}.items()))


def make_layer(seed: int, index: int, model: dict, dtype=jnp.float32) -> dict:
    """Layer `index` in the reference's layout (`input_norm`, `post_norm`,
    `mixer`, and `mlp` or `moe`); `num_hidden_layers` is the module's."""
    dm_items = _dims(model)
    return _layer(base._seed(seed), jnp.asarray(index, jnp.uint32),
                  dense=index < dict(dm_items)["dense"], dm_items=dm_items,
                  dtype=dtype)


def make_top(seed: int, model: dict, dtype=jnp.float32) -> dict:
    return _top(base._seed(seed), dm_items=_dims(model), dtype=dtype)


def layer_fn(seed: int, model: dict, dtype):
    """`i -> layer i` made in `dtype` and widened to float32: the values the
    served model holds, as the reference takes them."""
    widen = lambda x: x.astype(jnp.float32)
    return lambda i: jax.tree.map(widen, make_layer(seed, i, model, dtype))


def stack_for_program(top: dict, layers: list, module: dict) -> dict:
    """The program's tree: layer 0 under `first`, the later layers' leaves
    stacked [periods, ...] (`full` the mixers, `moe[0]` the expert halves;
    no sliding layers), the module under `mtp` (its mixer as a layer's, its
    expert half a stack of one)."""
    stack = lambda trees: jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
    with_norm = lambda l: {"input_norm": l["input_norm"], **l["mixer"]}
    moe = lambda l: {"post_norm": l["post_norm"], **l["moe"]}
    first, rest = layers[0], layers[1:]
    mtp = top["mtp"]
    return {
        "embed": {"embedding": top["embed"]},
        "first": {"attn": with_norm(first), "post_norm": first["post_norm"],
                  "mlp": first["mlp"]},
        "periods": {"full": stack([with_norm(l) for l in rest]), "win": [],
                    "moe": [stack([moe(l) for l in rest])]},
        "norm": top["norm"], "lm_head": top["lm_head"],
        "mtp": {"enorm": mtp["enorm"], "hnorm": mtp["hnorm"],
                "eh_proj": mtp["eh_proj"], "attn": with_norm(module),
                "moe": stack([moe(module)]),
                "shared_head_norm": mtp["shared_head_norm"]},
    }


def make_program_weights(seed: int, model: dict, dtype) -> dict:
    return _program(base._seed(seed), dm_items=_dims(model), dtype=dtype)


def param_count(model: dict) -> dict:
    """Counts by part, for the configuration file's arithmetic."""
    dm = dict(_dims(model))
    size = lambda tree: sum(math.prod(x.shape) for x in jax.tree.leaves(tree))
    shape_of = lambda i: jax.eval_shape(
        lambda s: _layer_leaves(s, i, dm, jnp.float32, i < dm["dense"]),
        jnp.uint32(0))
    first, sparse = shape_of(0), shape_of(dm["layers"])
    table_head = 2 * dm["vocab"] * dm["d"] + dm["d"]
    module_own = 2 * dm["d"] * dm["d"] + 3 * dm["d"]
    routed = size({k: sparse["moe"][k] for k in ("gate", "up", "down")})
    return {"dense_layer": size(first), "expert_layer": size(sparse),
            "mixer": size(sparse["mixer"]),
            "routed_experts_per_layer": routed,
            "outside_routed_experts": size(sparse) - routed,
            "module": size(sparse) + module_own, "table_head_norm": table_head,
            "total": size(first) + dm["layers"] * size(sparse) + module_own
            + table_head}
