"""The plain reference of the dense state-space block: every layer a mixer, a
Mamba-2 state-space mixer or a grouped-query softmax layer without any
positional embedding, in the order `layer_types` gives, AND a dense SwiGLU
feed-forward, each half under its own norm, with four scalar multipliers and
the head tied to the embedding table, in `jax.numpy`.

Written from the published configuration of ibm-granite/granite-4.0-h-micro
(`config.json`, `model_type: granitemoehybrid`: `layer_types`,
`mamba_n_heads`, `mamba_d_head`, `mamba_d_state`, `mamba_n_groups`,
`mamba_d_conv`, `shared_intermediate_size`, `embedding_multiplier`,
`residual_multiplier`, `attention_multiplier`, `logits_scaling`,
`tie_word_embeddings`, `position_embedding_type: nope`) and the description
of Mamba-2 it rests on. float32 throughout, under
`jax.default_matmul_precision("highest")` and with every matrix
multiplication at `highest` precision besides. The unpadded sequence goes
through in one pass: the recurrence token by token under `lax.scan` (no
chunking), the convolution as an explicit sum over its four taps, attention
under an explicit causal mask with the keys repeated to the query heads, the
queries a block at a time so that the scores of a 17k-token request fit;
there is no cache. One layer's float32 weights at a time. It imports nothing
from `llama_pipeline_parallel_tpu`.

    h0 = E[ids] * embedding_multiplier
    for each layer:  h <- h + residual_multiplier * mixer(rmsnorm(h; w1))
                     h <- h + residual_multiplier * mlp(rmsnorm(h; w2))
    mlp(u) = (silu(u W_g) * (u W_u)) W_d
    logits = rmsnorm(h; w) E^T / logits_scaling

Mamba-2, per head h of H with P channels, state S [P, N], group g = h // (H / G):
    [z | xBC | dt] = u W_in
    xBC <- silu(conv1d_causal_depthwise(xBC; taps) + bias)
    x [H, P], B [G, N], C [G, N] = split(xBC)
    dt = softplus(dt + dt_bias),  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
    out = rmsnorm_grouped(y * silu(z); w, G groups) W_out
Softmax: q (heads x head_dim), k, v (kv heads x head_dim), head_dim =
hidden_size / heads, no rotary, causal, the scores times
`attention_multiplier` (NOT head_dim ** -0.5), `out = attn W_o`.

What `config.json` leaves out (the configuration file lists these under
`assumed`): float32 state, `A_log`, `D`, `dt_bias`; `y * silu(z)` BEFORE the
gated norm, over groups of `H P / mamba_n_groups` channels; no clip on `dt`;
pre-norm on each half; `rope_theta` is carried and unused.

`precision="fp8"` is the CONTROL (see `dense_decoder`): every weight
multiplication, the tied head's too, as a float8 recipe computes it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import HIGHEST, _mm, rms_norm
# the Mamba-2 mixer is the expert block's reference's, as it stands there (the
# same equations: a head reads its group's B and C, here the one group's)
from benchmark.reference.ssm_moe_decoder import mamba_mixer

QUERY_BLOCK = 512         # queries whose scores are formed at once


def dims(model: dict) -> dict:
    """The numbers of a configuration file the block needs, under short
    names, as a flat dict of hashable values."""
    types = tuple(model["layer_types"])
    if len(types) != model["num_hidden_layers"] or \
            set(types) - {"mamba", "attention"}:
        raise ValueError(f"layer_types does not give "
                         f"{model['num_hidden_layers']} layers of mamba or "
                         f"attention")
    if model["num_local_experts"] or model["position_embedding_type"] != "nope" \
            or model["hidden_act"] != "silu":
        raise ValueError("this block is dense, SiLU-gated and carries no "
                         "positional embedding")
    return {
        "d": model["hidden_size"], "types": types,
        "vocab": model["vocab_size"], "eps": model["rms_norm_eps"],
        "heads": model["num_attention_heads"],
        "kv": model["num_key_value_heads"],
        "hd": model["hidden_size"] // model["num_attention_heads"],
        "H": model["mamba_n_heads"], "P": model["mamba_d_head"],
        "N": model["mamba_d_state"], "G": model["mamba_n_groups"],
        "conv": model["mamba_d_conv"], "f": model["shared_intermediate_size"],
        "embed_x": float(model["embedding_multiplier"]),
        "residual_x": float(model["residual_multiplier"]),
        "attn_x": float(model["attention_multiplier"]),
        "logits_div": float(model["logits_scaling"]),
        "tied": bool(model["tie_word_embeddings"]),
    }


def softmax_mixer(layer, h, dm: dict, precision: str):
    """Causal softmax attention at the stated scale, the keys and values
    repeated to the query heads, a block of queries at a time."""
    b, s, _ = h.shape
    heads, kv, hd = dm["heads"], dm["kv"], dm["hd"]
    q = _mm(h, layer["wq"], precision).reshape(b, s, heads, hd)
    k = _mm(h, layer["wk"], precision).reshape(b, s, kv, hd)
    v = _mm(h, layer["wv"], precision).reshape(b, s, kv, hd)
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))
    block = min(QUERY_BLOCK, s)
    pad = -s % block
    places = jnp.arange(s + pad, dtype=jnp.int32).reshape(-1, block)
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    q = jnp.moveaxis(q.reshape(b, -1, block, heads, hd), 1, 0)
    key_place = jnp.arange(s, dtype=jnp.int32)

    def one_block(args):
        q_blk, i_blk = args                       # [b, B, heads, hd], [B]
        dots = jnp.einsum("bthd,bshd->bhts", q_blk, k,
                          precision=HIGHEST) * dm["attn_x"]
        seen = key_place[None, :] <= i_blk[:, None]           # [B, s]
        probs = jax.nn.softmax(jnp.where(seen, dots, -jnp.inf), axis=-1)
        return jnp.einsum("bhts,bshd->bthd", probs, v, precision=HIGHEST)

    out = jax.lax.map(one_block, (q, places))     # [blocks, b, B, heads, hd]
    out = jnp.moveaxis(out, 0, 1).reshape(b, s + pad, heads * hd)[:, :s]
    return _mm(out, layer["wo"], precision)


def swiglu(layer, h, precision: str):
    gate = jax.nn.silu(_mm(h, layer["gate"], precision))
    return _mm(gate * _mm(h, layer["up"], precision), layer["down"], precision)


def block(layer, x, kind: str, dm: dict, precision: str = "float32"):
    """One layer: its mixer (`mamba` or `attention`), then its feed-forward,
    each under its own norm and the residual multiplier."""
    mixer = mamba_mixer if kind == "mamba" else softmax_mixer
    x = x + dm["residual_x"] * mixer(
        layer, rms_norm(x, layer["input_norm"], dm["eps"]), dm, precision)
    return x + dm["residual_x"] * swiglu(
        layer["mlp"], rms_norm(x, layer["post_norm"], dm["eps"]), precision)


@functools.partial(jax.jit, static_argnames=("kind", "dm_items", "precision"))
def _block_jit(layer, x, *, kind, dm_items, precision):
    return block(layer, x, kind, dict(dm_items), precision)


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
        xs = [top["embed"][jnp.asarray(row, jnp.int32)[None]] * dm["embed_x"]
              for row in rows]
        for i, kind in enumerate(dm["types"]):
            layer = layer_fn(i)
            for r, x in enumerate(xs):
                xs[r] = _block_jit(layer, x, kind=kind, dm_items=_freeze(dm),
                                   precision=precision)
            del layer
    return xs


@functools.partial(jax.jit, static_argnames=("eps", "divisor", "precision"))
def _head(x, norm, table, *, eps, divisor, precision):
    """The tied head: the normed states against the table, over the
    divisor."""
    return _mm(rms_norm(x, norm, eps), table.T, precision) / divisor


def _head_table(top: dict, dm: dict):
    return top["embed"] if dm["tied"] else top["lm_head"].T


def logits_fn(top: dict, layer_fn, ids, model: dict,
              precision: str = "float32"):
    """[b, s] token ids -> logits [b, s, vocab]."""
    dm = dims(model)
    with jax.default_matmul_precision("highest"):
        xs = hidden_states(top, layer_fn,
                           [list(row) for row in jax.device_get(ids)], model,
                           precision)
        return _head(jnp.concatenate(xs, axis=0), top["norm"],
                     _head_table(top, dm), eps=dm["eps"],
                     divisor=dm["logits_div"], precision=precision)


def served_token_gaps(top: dict, layer_fn, prompts: list, served: list,
                      model: dict, pad_to: int,
                      precision: str = "float32") -> list:
    """Per request, for each served token, the float32 reference's best
    logit minus its logit of the served token (under a lower `precision`: of
    the token that precision puts first). A request goes through at its own
    length, prompt + served tokens, padded at the END to a whole number of
    `pad_to` places (a few compiled shapes for any sample), which neither
    the causal mask nor the recurrence looks at; the head runs over the
    served positions alone."""
    dm = dims(model)
    rows = []
    for prompt, tokens in zip(prompts, served):
        seq = list(prompt) + list(tokens)
        rows.append(seq + [0] * (-len(seq) % pad_to))

    def at_served(precision):
        out = []
        states = hidden_states(top, layer_fn, rows, model, precision)
        with jax.default_matmul_precision("highest"):
            for x, prompt, tokens in zip(states, prompts, served):
                first = len(prompt) - 1      # logits here predict served[0]
                out.append(_head(x[0, first:first + len(tokens)], top["norm"],
                                 _head_table(top, dm), eps=dm["eps"],
                                 divisor=dm["logits_div"],
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
