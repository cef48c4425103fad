"""The plain reference of the state-space / expert block: every layer ONE of
a Mamba-2 state-space mixer (`M`), a grouped-query softmax layer without
rotary embedding (`*`) or a sparse expert feed-forward whose routed experts
work in a latent width (`E`), in the order `hybrid_override_pattern` gives,
in `jax.numpy`.

Written from the published configuration of NVIDIA-Nemotron-3-Super-120B-A12B
(`config.json`: `hybrid_override_pattern`, `mamba_num_heads`,
`mamba_head_dim`, `ssm_state_size`, `n_groups`, `conv_kernel`,
`n_routed_experts`, `num_experts_per_tok`, `moe_latent_size`,
`moe_shared_expert_intermediate_size`, `mlp_hidden_act: relu2`) and the
description of Mamba-2 it rests on. float32 throughout, under
`jax.default_matmul_precision("highest")` and with every matrix
multiplication at `highest` precision besides. The unpadded sequence goes
through in one pass: the recurrence token by token under `lax.scan` (no
chunking), the convolution as an explicit sum over its taps, attention under
an explicit causal mask, the router's selection by a sort, every held
expert's term as a dense product masked by the routing; there is no cache
and no batching trick. It imports nothing from `llama_pipeline_parallel_tpu`.

Every layer is `x <- x + f(rmsnorm(x; w, eps))`; after the last, a final
norm and the untied head.

M, per head h of H with P channels, state S [P, N], group g = h // (H / G):
    [z | xBC | dt] = u W_in
    xBC <- silu(conv1d_causal_depthwise(xBC; taps) + bias)
    x [H, P], B [G, N], C [G, N] = split(xBC)
    dt = softplus(dt + dt_bias),  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
    out = rmsnorm_grouped(y * silu(z); w, G groups) W_out
*: q (heads x head_dim), k, v (kv heads x head_dim), no rotary, causal
softmax at 1/sqrt(head_dim), `out = attn W_o`.
E: `s = sigmoid(h W_r)` over the published number of experts, the `k`
largest of `s + bias` selected, a selected expert's weight `s_e / sum of the
selected s` times `routed_scaling_factor`; `l = h W_down`;
`y = (sum_selected w_e relu(l U_e)^2 V_e) W_up + relu(h U_s)^2 V_s`.

Departures from the published description, each forced by what `config.json`
leaves out (the configuration file lists them under `assumed`):
- no rotary embedding in the softmax layers (the config still carries
  `rope_theta`);
- the router and the shared expert read the full-width hidden state; the
  latent projections wrap the routed experts only;
- the gated norm multiplies by `silu(z)` BEFORE the norm, over groups of
  `HP / n_groups` channels;
- `dt` has no upper clip: `time_step_min` / `max` / `floor` only seed
  `dt_bias`;
- the router runs in float32 in every `precision`;
- the layer is told which experts it holds (`expert_offset`,
  `n_routed_experts`): it routes over all of `router_experts`, adds the terms
  of the held ones and leaves the others out, as one chip of an
  expert-parallel deployment computes before the exchange;
- the multi-token-prediction module is not part of the forward pass.

`precision="fp8"` is the CONTROL (see `dense_decoder`): every weight
multiplication but the router's as a float8 recipe computes it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (
    HIGHEST,
    _mm,
    causal_attention,
    rms_norm,
)


def dims(model: dict) -> dict:
    """The numbers of a configuration file the block needs, under short
    names, as a flat dict of hashable values."""
    pattern = model["hybrid_override_pattern"]
    if len(pattern) != model["num_hidden_layers"] or set(pattern) - set("M*E"):
        raise ValueError(f"hybrid_override_pattern {pattern!r} does not give "
                         f"{model['num_hidden_layers']} layers of M, * or E")
    if model["mlp_hidden_act"] != "relu2" or model["mamba_hidden_act"] != "silu":
        raise ValueError("this block's experts are relu^2 and its state-space "
                         "layers SiLU")
    return {
        "d": model["hidden_size"], "pattern": pattern,
        "vocab": model["vocab_size"], "eps": model["norm_eps"],
        "heads": model["num_attention_heads"],
        "kv": model["num_key_value_heads"], "hd": model["head_dim"],
        "H": model["mamba_num_heads"], "P": model["mamba_head_dim"],
        "N": model["ssm_state_size"], "G": model["n_groups"],
        "conv": model["conv_kernel"],
        "router": model.get("router_experts", model["n_routed_experts"]),
        "held": model["n_routed_experts"],
        "offset": model.get("expert_offset", 0),
        "topk": model["num_experts_per_tok"],
        "latent": model["moe_latent_size"],
        "f": model["moe_intermediate_size"],
        "fs": (model["n_shared_experts"]
               * model["moe_shared_expert_intermediate_size"]),
        "norm_topk": bool(model["norm_topk_prob"]),
        "scale": float(model["routed_scaling_factor"]),
    }


def causal_conv(x, taps, bias):
    """x: [b, s, c]; taps: [width, c], the last row meeting the newest input:
    y_t = bias + sum_j taps[j] * x_{t - (width - 1) + j}, zeros before the
    start."""
    width, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return bias + sum(padded[:, j:j + s] * taps[j] for j in range(width))


def mamba_mixer(layer, h, dm: dict, precision: str):
    b, s, _ = h.shape
    H, P, N, G = dm["H"], dm["P"], dm["N"], dm["G"]
    inner = H * P
    zxbcdt = _mm(h, layer["in_proj"], precision)
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:2 * inner + 2 * G * N]
    dt = jax.nn.softplus(zxbcdt[..., 2 * inner + 2 * G * N:] + layer["dt_bias"])
    xbc = jax.nn.silu(causal_conv(xbc, layer["conv_w"], layer["conv_b"]))
    x = xbc[..., :inner].reshape(b, s, H, P)
    of_head = lambda a: jnp.repeat(a.reshape(b, s, G, N), H // G, axis=2)
    B = of_head(xbc[..., inner:inner + G * N])               # [b, s, H, N]
    C = of_head(xbc[..., inner + G * N:])
    A = -jnp.exp(layer["A_log"])

    def step(state, xs):                       # state: [b, H, P, N]
        x_t, B_t, C_t, dt_t = xs
        state = (jnp.exp(dt_t * A)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, C_t,
                                 precision=HIGHEST)

    by_time = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((b, H, P, N), jnp.float32),
                        tuple(by_time(a) for a in (x, B, C, dt)))
    y = by_time(y) + layer["D"][:, None] * x
    y = (y.reshape(b, s, inner) * jax.nn.silu(z)).reshape(b, s, G, inner // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + dm["eps"])
    return _mm(y.reshape(b, s, inner) * layer["gate_norm"], layer["out_proj"],
               precision)


def softmax_mixer(layer, h, dm: dict, precision: str):
    b, s, _ = h.shape
    heads, kv, hd = dm["heads"], dm["kv"], dm["hd"]
    q = _mm(h, layer["wq"], precision).reshape(b, s, heads, hd)
    k = _mm(h, layer["wk"], precision).reshape(b, s, kv, hd)
    v = _mm(h, layer["wv"], precision).reshape(b, s, kv, hd)
    out = causal_attention(q, k, v).reshape(b, s, heads * hd)
    return _mm(out, layer["wo"], precision)


def route(layer, h, dm: dict):
    """[b, s, d] -> combine weights [b, s, router]: a selected expert's weight
    at its place, 0 elsewhere. The selection is a sort of `s + bias`,
    largest first, of which the first `topk` are taken."""
    scores = jax.nn.sigmoid(jnp.matmul(h, layer["router"], precision=HIGHEST))
    order = jnp.argsort(-(scores + layer["router_bias"]), axis=-1, stable=True)
    chosen = order[..., :dm["topk"]]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if dm["norm_topk"]:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    picked = picked * dm["scale"]
    onehot = jax.nn.one_hot(chosen, dm["router"], dtype=jnp.float32)
    return jnp.einsum("bsk,bske->bse", picked, onehot, precision=HIGHEST)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def latent_moe(layer, h, dm: dict, precision: str, shared: bool = True):
    """The held experts' terms, each a dense product over every token masked
    by the routing, summed in the latent width and projected up once, plus
    the shared expert's (`shared=False` leaves it out: the shares of several
    chips count it once)."""
    combine = route(layer, h, dm)
    held = jax.lax.dynamic_slice_in_dim(combine, dm["offset"], dm["held"], 2)
    latent = _mm(h, layer["latent_in"], precision)

    def one_expert(total, xs):
        up, down, weight = xs
        term = _mm(_relu2(_mm(latent, up, precision)), down, precision)
        return total + weight[..., None] * term, None

    total, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(latent),
        (layer["up"], layer["down"], jnp.moveaxis(held, 2, 0)))
    y = _mm(total, layer["latent_out"], precision)
    if shared:
        y = y + _mm(_relu2(_mm(h, layer["shared_up"], precision)),
                    layer["shared_down"], precision)
    return y


def block(layer, x, kind: str, dm: dict, precision: str = "float32"):
    """One layer of the given kind (`M`, `*` or `E`)."""
    if kind == "E":
        return x + latent_moe(layer, rms_norm(x, layer["post_norm"], dm["eps"]),
                              dm, precision)
    mixer = mamba_mixer if kind == "M" else softmax_mixer
    return x + mixer(layer, rms_norm(x, layer["input_norm"], dm["eps"]), dm,
                     precision)


@functools.partial(jax.jit, static_argnames=("kind", "dm_items", "precision"))
def _block_jit(layer, x, *, kind, dm_items, precision):
    return block(layer, x, kind, dict(dm_items), precision)


def _freeze(dm: dict) -> tuple:
    return tuple(sorted(dm.items()))


def logits_fn(top: dict, layer_fn, ids, model: dict,
              precision: str = "float32"):
    """[b, s] token ids -> [b, s, vocab]. `top` holds `embed`, `norm` and
    `lm_head`; `layer_fn(i)` gives layer `i`'s weights in float32, one layer
    at a time so that a model whose float32 weights do not fit the device
    beside each other can still be followed (the layer is dropped before
    the next is made)."""
    dm = dims(model)
    with jax.default_matmul_precision("highest"):
        x = top["embed"][ids]
        for i, kind in enumerate(dm["pattern"]):
            x = _block_jit(layer_fn(i), x, kind=kind, dm_items=_freeze(dm),
                           precision=precision)
        x = rms_norm(x, top["norm"], dm["eps"])
        return _mm(x, top["lm_head"], precision)


def served_token_gaps(top: dict, layer_fn, prompts: list, served: list,
                      model: dict, pad_to: int,
                      precision: str = "float32") -> list:
    """As `hybrid_moe_decoder.served_token_gaps`: several requests in one
    batch (each layer's weights are made once for all of them), prompt +
    served tokens padded at the END to `pad_to`, which neither causal
    attention nor the recurrence looks at. Per request, for each served
    token, the float32 reference's best logit minus its logit of the served
    token (under a lower `precision`: of the token that precision puts
    first)."""
    rows = []
    for prompt, tokens in zip(prompts, served):
        seq = list(prompt) + list(tokens)
        if len(seq) > pad_to:
            raise ValueError(f"{len(seq)} tokens exceed pad_to={pad_to}")
        rows.append(seq + [0] * (pad_to - len(seq)))
    ids = jnp.asarray(rows, jnp.int32)
    ref = logits_fn(top, layer_fn, ids, model, "float32")
    chosen = jnp.concatenate(
        [ids[:, 1:], jnp.zeros((ids.shape[0], 1), jnp.int32)], axis=1)
    if precision != "float32":
        chosen = jnp.argmax(logits_fn(top, layer_fn, ids, model, precision),
                            axis=-1)
    picked = jnp.take_along_axis(ref, chosen[..., None], axis=-1)[..., 0]
    gaps = jax.device_get(jnp.max(ref, axis=-1) - picked)
    out = []
    for row, prompt, tokens in zip(gaps, prompts, served):
        first = len(prompt) - 1          # logits here predict served[0]
        out.append(row[first:first + len(tokens)].tolist())
    return out
