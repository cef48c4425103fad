"""The plain reference of the window / full softmax block: every layer a
grouped-query softmax mixer of one of two kinds in the order
`hybrid_layer_pattern` gives (0 FULL: every earlier position; 1 WINDOW: the
last `sliding_window`, with a learned sink a query head in the softmax), then
a dense gated feed-forward or sigmoid-routed sparse experts with no shared
expert (`moe_layer_freq`), in `jax.numpy`.

Written from the published configuration of MiMo-V2-Flash (`config.json`:
`hybrid_layer_pattern`, `swa_num_key_value_heads`, `swa_rope_theta`,
`partial_rotary_factor`, `attention_value_scale`, `v_head_dim`,
`add_swa_attention_sink_bias`, `sliding_window`, `n_routed_experts`,
`num_experts_per_tok`, `scoring_func`, `n_shared_experts: null`,
`routed_scaling_factor: null`). float32 throughout, under
`jax.default_matmul_precision("highest")` and with every matrix
multiplication at `highest` precision besides. The unpadded sequence goes
through in blocks of queries, each against every key of the sequence under a
mask built from the positions alone (`j <= i`, and `j > i - window` in a
window layer); the sink is one more column of the softmax, with no value
behind it; keys and values are repeated to the query heads; the router's
selection is a sort; every held expert's term is a dense product masked by
the routing. There is no cache, no ring and no batching trick. It imports
nothing from `llama_pipeline_parallel_tpu`.

    u = rmsnorm(x; w, eps)
    q = W_q u [H, dk]   k = W_k u [G, dk]   v = c W_v u [G, dv]
    rotate-half on the leading `rot` numbers of every q and k head, base
    theta of the layer's kind; the other dk - rot pass
    s_ij = q_i . k_j / sqrt(dk)
    full:    p_ij = exp(s_ij) / sum_j exp(s_ij),                    j <= i
    window:  p_ij = exp(s_ij) / (exp(b_h) + sum_j exp(s_ij)),       i - w < j <= i
    h = x + W_o concat_h(sum_j p_ij v_j)
    dense:   y = h + W_d (silu(W_g n) * W_u n),  n = rmsnorm(h)
    experts: r = sigmoid(W_r n); chosen = top-k of (r + bias);
             w = r[chosen] / sum r[chosen]  (times `routed_scaling_factor`,
             1 where the config gives none)
             y = h + sum_e w_e W_d^e (silu(W_g^e n) * W_u^e n)   nothing else

Departures from the published description, each forced by what `config.json`
leaves out (the configuration file lists them under `assumed`):
- the window holds `sliding_window` keys, the query's own among them;
- `partial_rotary_factor` 0.334 of 192 stands for the leading 64 numbers,
  rotate-half among them;
- `attention_value_scale` multiplies the values where they are made (linear:
  the same result as scaling the mixer's output);
- `attention_chunk_size` changes no equation;
- the sinks, the router and its selection bias are float32 in every
  `precision`;
- the layer is told which experts it holds (`expert_offset`,
  `n_routed_experts`): it routes over all of `router_experts`, adds the terms
  of the held ones and leaves the others out, as one chip of an
  expert-parallel deployment computes before the exchange;
- the multi-token-prediction layers are not part of the forward pass.

`precision="fp8"` is the CONTROL (see `dense_decoder`): every weight
multiplication but the router's as a float8 recipe computes it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import HIGHEST, _mm, rms_norm
# queries in blocks of 128 and the gated feed-forward as the MLA reference
# writes them; the router's selection by a sort as the state-space one does
from benchmark.reference.mla_moe_decoder import _by_query_blocks, _swiglu
from benchmark.reference.ssm_moe_decoder import route

FULL, WINDOW = 0, 1


def dims(model: dict) -> dict:
    """The numbers of a configuration file the block needs, under short
    names, as a flat dict of hashable values."""
    n = model["num_hidden_layers"]
    pattern = tuple(model["hybrid_layer_pattern"])
    moe = tuple(model["moe_layer_freq"])
    if len(pattern) != n or len(moe) != n or set(pattern) - {FULL, WINDOW}:
        raise ValueError(f"hybrid_layer_pattern {pattern!r} / moe_layer_freq "
                         f"{moe!r} do not give {n} layers")
    if not model["add_swa_attention_sink_bias"] or \
            model["add_full_attention_sink_bias"]:
        raise ValueError("this block's window layers have a sink and its "
                         "full layers none")
    if model["scoring_func"] != "sigmoid" or model["hidden_act"] != "silu":
        raise ValueError("this block's router is a sigmoid and its "
                         "feed-forwards SiLU-gated")
    if model.get("n_shared_experts"):
        raise ValueError("this block has no shared expert")
    scaling = model.get("routed_scaling_factor")
    return {
        "d": model["hidden_size"], "layers": n, "pattern": pattern,
        "moe": moe, "vocab": model["vocab_size"],
        "eps": model["layernorm_epsilon"],
        "heads": model["num_attention_heads"], "dk": model["head_dim"],
        "dv": model["v_head_dim"],
        "rot": 2 * int(model["partial_rotary_factor"] * model["head_dim"] / 2),
        "v_scale": float(model["attention_value_scale"]),
        "kv_full": model["num_key_value_heads"],
        "kv_window": model["swa_num_key_value_heads"],
        "theta_full": float(model["rope_theta"]),
        "theta_window": float(model["swa_rope_theta"]),
        "window": model["sliding_window"],
        "ffn": model["intermediate_size"],
        "router": model.get("router_experts", model["n_routed_experts"]),
        "held": model["n_routed_experts"],
        "offset": model.get("expert_offset", 0),
        "topk": model["num_experts_per_tok"],
        "f": model["moe_intermediate_size"],
        "norm_topk": bool(model["norm_topk_prob"]),
        "scale": 1.0 if scaling is None else float(scaling),
    }


def rotary_front(x, positions, rot: int, theta: float):
    """Rotate-half rotary embedding on the leading `rot` numbers of x [b, s,
    h, dk] at `positions` [b, s]; the rest pass."""
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[..., None] * inv      # [b, s, rot/2]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, :, None, :]
    front = x[..., :rot]
    half = rot // 2
    turned = jnp.concatenate([-front[..., half:], front[..., :half]], axis=-1)
    return jnp.concatenate(
        [front * jnp.cos(ang) + turned * jnp.sin(ang), x[..., rot:]], axis=-1)


def softmax_mixer(layer, h, positions, kind: int, dm: dict, precision: str):
    """One mixer's output [b, s, d]; `kind`: FULL or WINDOW."""
    b, s, _ = h.shape
    H, dk, dv = dm["heads"], dm["dk"], dm["dv"]
    G = dm["kv_window"] if kind == WINDOW else dm["kv_full"]
    theta = dm["theta_window"] if kind == WINDOW else dm["theta_full"]
    roped = lambda a: rotary_front(a, positions, dm["rot"], theta)
    q = roped(_mm(h, layer["wq"], precision).reshape(b, s, H, dk))
    k = roped(_mm(h, layer["wk"], precision).reshape(b, s, G, dk))
    v = dm["v_scale"] * _mm(h, layer["wv"], precision).reshape(b, s, G, dv)
    k, v = (jnp.repeat(a, H // G, axis=2) for a in (k, v))    # a head its own
    j = jnp.arange(s, dtype=jnp.int32)[None, None, :]

    def block(args):
        q_blk, i_blk = args                       # [b, B, H, dk], [b, B]
        i = i_blk[:, :, None]
        dots = jnp.einsum("bthd,bshd->bhts", q_blk, k,
                          precision=HIGHEST) * dk ** -0.5
        seen = j <= i
        if kind == WINDOW:
            seen = seen & (j > i - dm["window"])
        dots = jnp.where(seen[:, None], dots, -jnp.inf)
        if kind == WINDOW:
            # the sink: one more column of the softmax, no value behind it
            column = jnp.broadcast_to(layer["sink"][None, :, None, None],
                                      dots.shape[:3] + (1,))
            dots = jnp.concatenate([dots, column], axis=-1)
        probs = jax.nn.softmax(dots, axis=-1)[..., :s]
        return jnp.einsum("bhts,bshd->bthd", probs, v, precision=HIGHEST)

    out = _by_query_blocks(
        block, (q, jnp.broadcast_to(positions.astype(jnp.int32), (b, s))), s)
    return _mm(out.reshape(b, s, H * dv), layer["wo"], precision)


def moe_layer(layer, h, dm: dict, precision: str):
    """The held experts' terms, each a dense product over every token masked
    by the routing, and nothing else: there is no shared expert."""
    combine = route(layer, h, dm)
    held = jax.lax.dynamic_slice_in_dim(combine, dm["offset"], dm["held"], 2)

    def one_expert(total, xs):
        gate, up, down, weight = xs
        return total + weight[..., None] * _swiglu(h, gate, up, down,
                                                   precision), None

    total, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (layer["gate"], layer["up"], layer["down"], jnp.moveaxis(held, 2, 0)))
    return total


def block(layer, x, positions, kind: int, dm: dict,
          precision: str = "float32"):
    """One layer of the given kind. The layer is dense when it has `mlp`,
    sparse when it has `router`."""
    x = x + softmax_mixer(layer, rms_norm(x, layer["input_norm"], dm["eps"]),
                          positions, kind, dm, precision)
    h = rms_norm(x, layer["post_norm"], dm["eps"])
    if "mlp" in layer:
        m = layer["mlp"]
        return x + _swiglu(h, m["gate"], m["up"], m["down"], precision)
    return x + moe_layer(layer, h, dm, precision)


@functools.partial(jax.jit, static_argnames=("kind", "dm_items", "precision"))
def _block_jit(layer, x, positions, *, kind, dm_items, precision):
    return block(layer, x, positions, kind, dict(dm_items), precision)


def _freeze(dm: dict) -> tuple:
    return tuple(sorted(dm.items()))


def hidden_states(top: dict, layer_fn, rows: list, model: dict,
                  precision: str = "float32") -> list:
    """Per request (a list of token ids, each of its own length) the hidden
    state after the last layer, [1, s, d]. `layer_fn(i)` gives layer `i`'s
    weights in float32, one layer at a time (the layer is dropped before the
    next is made); requests run one at a time inside a layer, so a layer's
    weights are made once for all of them."""
    dm = dims(model)
    with jax.default_matmul_precision("highest"):
        xs = [top["embed"][jnp.asarray(row, jnp.int32)[None]] for row in rows]
        where = [jnp.arange(x.shape[1], dtype=jnp.int32)[None] for x in xs]
        for i in range(dm["layers"]):
            layer = layer_fn(i)
            for r, x in enumerate(xs):
                xs[r] = _block_jit(layer, x, where[r], kind=dm["pattern"][i],
                                   dm_items=_freeze(dm), precision=precision)
            del layer
    return xs


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, norm, lm_head, *, eps, precision):
    return _mm(rms_norm(x, norm, eps), lm_head, precision)


def logits_fn(top: dict, layer_fn, ids, model: dict,
              precision: str = "float32"):
    """[b, s] token ids -> logits [b, s, vocab]."""
    eps = model["layernorm_epsilon"]
    with jax.default_matmul_precision("highest"):
        xs = hidden_states(top, layer_fn, [list(row) for row in
                                           jax.device_get(ids)], model,
                           precision)
        return _head(jnp.concatenate(xs, axis=0), top["norm"], top["lm_head"],
                     eps=eps, precision=precision)


def served_token_gaps(top: dict, layer_fn, prompts: list, served: list,
                      model: dict, pad_to: int,
                      precision: str = "float32") -> list:
    """Per request, for each served token, the float32 reference's best
    logit minus its logit of the served token (under a lower `precision`: of
    the token that precision puts first). A request goes through at its own
    length, prompt + served tokens, padded at the END to a whole number of
    `pad_to` places (a few compiled shapes for any sample), which no mask
    looks at; the head runs over the served positions alone."""
    rows = []
    for prompt, tokens in zip(prompts, served):
        seq = list(prompt) + list(tokens)
        rows.append(seq + [0] * (-len(seq) % pad_to))
    eps = model["layernorm_epsilon"]

    def at_served(precision):
        out = []
        states = hidden_states(top, layer_fn, rows, model, precision)
        with jax.default_matmul_precision("highest"):
            for x, prompt, tokens in zip(states, prompts, served):
                first = len(prompt) - 1      # logits here predict served[0]
                out.append(_head(x[0, first:first + len(tokens)], top["norm"],
                                 top["lm_head"], eps=eps,
                                 precision=precision))
        return out

    ref = at_served("float32")
    chosen = [jnp.asarray(tokens, jnp.int32) for tokens in served]
    if precision != "float32":
        chosen = [jnp.argmax(l, axis=-1) for l in at_served(precision)]
    return [jax.device_get(
        jnp.max(l, axis=-1)
        - jnp.take_along_axis(l, c[:, None], axis=-1)[:, 0]).tolist()
        for l, c in zip(ref, chosen)]
