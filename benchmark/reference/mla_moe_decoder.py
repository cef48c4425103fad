"""The plain reference of a decoder of ONE kind of layer: plain multi-head
latent attention (MLA) that reads every earlier position, under YaRN, a
leading dense layer and sparse experts with a shared expert after it, in
`jax.numpy`.

Written from the published configuration of A.X-K1 (`config.json`,
`model_type: axk1`: `q_lora_rank`, `kv_lora_rank`, `qk_nope_head_dim`,
`qk_rope_head_dim`, `v_head_dim`, `rope_scaling`, `first_k_dense_replace`,
`n_routed_experts`, `scoring_func`, `routed_scaling_factor`, ...), of
multi-head latent attention (DeepSeek-V2) and of YaRN as DeepSeek-V3
publishes it for these key names. float32 throughout, every matrix
multiplication at `highest` precision. Attention is the PROJECTED form (keys
and values of every head made from the latent, no absorption) under an
explicit causal mask; the experts run one at a time under `lax.scan` over
the held ones; there is no cache. Queries run in blocks only so that the
[heads, block, S] scores of an 18k row fit beside a layer's weights. It
imports nothing from `llama_pipeline_parallel_tpu`.

Pre-norm residual block, RMSNorm: `h += mixer(norm(h)); h += ffn(norm(h))`,
`x = norm(h)`.

Mixer (H 64, latents 1536 / 512, nope 128 + rope 64, v 128):
    cq = rmsnorm(W_qa x);  [q^N_h; q^R_h] = W_qb,h cq,  q^R roped
    [c; k^R] = W_kva x;  c = rmsnorm(c),  k^R roped, shared by the heads
    k_h,s = [W_kb,h^K c_s; k^R_s],  v_h,s = W_kb,h^V c_s
    o_h,t = sum_{s <= t} softmax_s(scale q_h,t . k_h,s) v_h,s
    y_t = W_o [o_h,t]_h
No gate, no rescale of the latents, no window, no indexer.
YaRN (theta 1e4, rope 64, factor 32 from 4096, beta_fast 32, beta_slow 1,
mscale = mscale_all_dim = 1), `yarn_inv_freq` below: frequency j is
theta^(-2j/64) where it turns more than 32 times over the original 4096
positions, that over 32 where it turns less than once, a linear ramp between
the two correction dimensions; cos and sin times yarn_mscale(32, mscale) /
yarn_mscale(32, mscale_all_dim) = 1; scale = (128 + 64)^-1/2 x m^2 with m =
0.1 x mscale_all_dim x ln 32 + 1.
Feed-forward: layer 0 a SwiGLU of width `intermediate_size`; later layers
`s = sigmoid(W_r x)` over the router's width in float32, the k largest, a
selected expert's weight `s_e / sum of the selected s` times
`routed_scaling_factor`, `y = sum_selected w_e SwiGLU_e(x) + SwiGLU_shared(x)`.

Readings of what `config.json` leaves open (the configuration file lists
them under `assumed`):
- `topk_method: "none"`: no selection bias and no group restriction, so
  `n_group` / `topk_group` select nothing;
- rotate-half rope (a fixed permutation of the published interleaving);
- the layer is told which experts it holds (`expert_offset`,
  `n_routed_experts`): it routes over all of `router_experts`, adds the
  terms of the held ones and leaves the others out.

`precision="fp8"` is the CONTROL (see `dense_decoder`): every weight
multiplication but the router's as a float8 recipe computes it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import HIGHEST, _mm, rms_norm

QUERY_BLOCK = 128          # queries attended at a time
YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "mscale", "mscale_all_dim")


def dims(model: dict) -> dict:
    """The numbers of a configuration file the block needs, under short
    names, as a flat dict of hashable values."""
    if model.get("layer_types") or "index_topk" in model or \
            model.get("attention_gate_type"):
        raise ValueError("this reference is of one kind of layer: plain MLA "
                         "without indexer, window or gate")
    if model["first_k_dense_replace"] != 1:
        raise ValueError("this block has exactly one leading dense layer")
    if model["scoring_func"] != "sigmoid":
        raise ValueError("the router scores with a sigmoid")
    yarn = model["rope_scaling"]
    if yarn is None or yarn["type"] != "yarn":
        raise ValueError("the rope is rescaled by YaRN")
    out = {
        "d": model["hidden_size"], "layers": model["num_hidden_layers"],
        "vocab": model["vocab_size"], "eps": model["rms_norm_eps"],
        "heads": model["num_attention_heads"],
        "rq": model["q_lora_rank"], "rkv": model["kv_lora_rank"],
        "nope": model["qk_nope_head_dim"], "rope": model["qk_rope_head_dim"],
        "v": model["v_head_dim"], "theta": float(model["rope_theta"]),
        # feed-forward
        "ffn": model["intermediate_size"],
        "router": model.get("router_experts", model["n_routed_experts"]),
        "held": model["n_routed_experts"],
        "offset": model.get("expert_offset", 0),
        "topk_experts": model["num_experts_per_tok"],
        "f": model["moe_intermediate_size"],
        "fs": model["n_shared_experts"] * model["moe_intermediate_size"],
        "norm_topk": bool(model["norm_topk_prob"]),
        "scale": float(model["routed_scaling_factor"]),
    }
    out.update({f"yarn_{key}": float(yarn[key]) for key in YARN_KEYS})
    return out


# -- YaRN, written out -----------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_correction_dim(rotations: float, dim: int, theta: float,
                        original: float) -> float:
    """The (fractional) frequency index that turns `rotations` times over
    `original` positions."""
    return dim * math.log(original / (rotations * 2 * math.pi)) / (
        2 * math.log(theta))


def yarn_inv_freq(dm: dict):
    """[rope / 2] frequencies."""
    dim, theta, factor = dm["rope"], dm["theta"], dm["yarn_factor"]
    original = dm["yarn_original_max_position_embeddings"]
    low = max(math.floor(yarn_correction_dim(dm["yarn_beta_fast"], dim, theta,
                                             original)), 0)
    high = min(math.ceil(yarn_correction_dim(dm["yarn_beta_slow"], dim, theta,
                                             original)), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for j in range(dim // 2):
        plain = theta ** (-2.0 * j / dim)
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        out.append(plain / factor * ramp + plain * (1.0 - ramp))
    return jnp.asarray(out, jnp.float32)


def rotary(x, positions, dm: dict):
    """Rotate-half rotary embedding under YaRN. x: [b, s, h, rope];
    positions: [b, s]."""
    angles = positions.astype(jnp.float32)[..., None] * yarn_inv_freq(dm)
    angles = jnp.concatenate([angles, angles], axis=-1)[:, :, None, :]
    amplitude = (yarn_mscale(dm["yarn_factor"], dm["yarn_mscale"])
                 / yarn_mscale(dm["yarn_factor"], dm["yarn_mscale_all_dim"]))
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return (x * jnp.cos(angles) + rotated * jnp.sin(angles)) * amplitude


def softmax_scale(dm: dict) -> float:
    m = yarn_mscale(dm["yarn_factor"], dm["yarn_mscale_all_dim"])
    return (dm["nope"] + dm["rope"]) ** -0.5 * m * m


# -- the layers -------------------------------------------------------------------

def _by_query_blocks(fn, arrays, s: int):
    """`fn` over blocks of QUERY_BLOCK positions of axis 1 of each array;
    the results concatenated on axis 1 and cut back to `s`."""
    pad = -s % QUERY_BLOCK
    n = (s + pad) // QUERY_BLOCK
    split = lambda a: jnp.moveaxis(
        jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)).reshape(
            a.shape[0], n, QUERY_BLOCK, *a.shape[2:]), 1, 0)
    out = jax.lax.map(fn, tuple(split(a) for a in arrays))    # [n, b, B, ...]
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape(out.shape[0], n * QUERY_BLOCK, *out.shape[3:])[:, :s]


def mla_mixer(mixer, x, positions, dm: dict, precision: str,
              alter: tuple = ()):
    """One mixer's output [b, s, d]. `alter` names departures a test makes
    on purpose (tests only)."""
    b, s, _ = x.shape
    H, nope, rope, v_dim = dm["heads"], dm["nope"], dm["rope"], dm["v"]
    cq = rms_norm(_mm(x, mixer["wqa"], precision), mixer["q_norm"], dm["eps"])
    q = _mm(cq, mixer["wqb"], precision).reshape(b, s, H, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], rotary(q[..., nope:], positions, dm)], axis=-1)
    ckv = _mm(x, mixer["wkva"], precision)
    c = rms_norm(ckv[..., :dm["rkv"]], mixer["kv_norm"], dm["eps"])
    k_rope = rotary(ckv[..., None, dm["rkv"]:], positions, dm)
    k_nope = _mm(c, mixer["wkb_k"].reshape(dm["rkv"], H * nope),
                 precision).reshape(b, s, H, nope)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, H, rope))], axis=-1)
    v = _mm(c, mixer["wkb_v"].reshape(dm["rkv"], H * v_dim),
            precision).reshape(b, s, H, v_dim)
    scale = ((nope + rope) ** -0.5 if "plain_scale" in alter
             else softmax_scale(dm))
    t_idx = jnp.arange(s, dtype=jnp.int32)[None, :]

    def block(args):
        q_blk, t_blk = args                       # [b, B, H, hd], [b, B]
        dots = jnp.einsum("bthd,bshd->bhts", q_blk, k, precision=HIGHEST)
        mask = (jnp.arange(s)[None, None, :] <= t_blk[:, :, None])[:, None]
        dots = jnp.where(mask, dots * scale, -jnp.inf)
        return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(dots, axis=-1), v,
                          precision=HIGHEST)

    out = _by_query_blocks(block, (q, jnp.broadcast_to(t_idx, (b, s))), s)
    return _mm(out.reshape(b, s, H * v_dim), mixer["wo"], precision)


def _swiglu(h, gate, up, down, precision):
    return _mm(jax.nn.silu(_mm(h, gate, precision)) * _mm(h, up, precision),
               down, precision)


def route(moe, h, dm: dict):
    """[b, s, d] -> combine weights [b, s, router]: a selected expert's weight
    at its place, 0 elsewhere."""
    scores = jax.nn.sigmoid(jnp.matmul(h, moe["router"], precision=HIGHEST))
    _, chosen = jax.lax.top_k(scores, dm["topk_experts"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if dm["norm_topk"]:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    picked = picked * dm["scale"]
    onehot = jax.nn.one_hot(chosen, dm["router"], dtype=jnp.float32)
    return jnp.einsum("bsk,bske->bse", picked, onehot, precision=HIGHEST)


def moe_layer(moe, h, dm: dict, precision: str, shared: bool = True):
    """The held experts' terms plus the shared expert's (`shared=False`
    leaves it out: the shares of several chips count it once)."""
    combine = route(moe, h, dm)
    held = jax.lax.dynamic_slice_in_dim(combine, dm["offset"], dm["held"], 2)

    def one_expert(total, xs):
        gate, up, down, weight = xs
        return total + weight[..., None] * _swiglu(h, gate, up, down,
                                                   precision), None

    total, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (moe["gate"], moe["up"], moe["down"], jnp.moveaxis(held, 2, 0)))
    if shared:
        total = total + _swiglu(h, moe["shared_gate"], moe["shared_up"],
                                moe["shared_down"], precision)
    return total


def block(layer, x, positions, dm: dict, precision: str = "float32",
          alter: tuple = ()):
    """One layer. The layer is dense when it has `mlp`, sparse when it has
    `moe`."""
    h = rms_norm(x, layer["input_norm"], dm["eps"])
    x = x + mla_mixer(layer["mixer"], h, positions, dm, precision, alter)
    h = rms_norm(x, layer["post_norm"], dm["eps"])
    if "mlp" in layer:
        m = layer["mlp"]
        return x + _swiglu(h, m["gate"], m["up"], m["down"], precision)
    return x + moe_layer(layer["moe"], h, dm, precision)


@functools.partial(jax.jit, static_argnames=("dm_items", "precision", "alter"))
def _block_jit(layer, x, positions, *, dm_items, precision, alter):
    return block(layer, x, positions, dict(dm_items), precision, alter)


def _freeze(dm: dict) -> tuple:
    return tuple(sorted(dm.items()))


def forward(top: dict, layer_fn, ids, model: dict, precision: str = "float32",
            alter: tuple = ()):
    """[b, s] token ids -> logits [b, s, vocab]. `top` holds `embed`, `norm`
    and `lm_head`; `layer_fn(i)` gives layer `i`'s weights in float32, one
    layer at a time (the layer is dropped before the next is made). Requests
    run one at a time inside a layer, so a layer's weights are made once for
    all of them."""
    dm = dims(model)
    b, s = ids.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (1, s))
    xs = [top["embed"][ids[i:i + 1]] for i in range(b)]
    for i in range(dm["layers"]):
        layer = layer_fn(i)
        for r in range(b):
            xs[r] = _block_jit(layer, xs[r], positions, dm_items=_freeze(dm),
                               precision=precision, alter=tuple(alter))
        del layer
    x = rms_norm(jnp.concatenate(xs, axis=0), top["norm"], dm["eps"])
    return _mm(x, top["lm_head"], precision)


logits_fn = forward


def served_token_gaps(top: dict, layer_fn, prompts: list, served: list,
                      model: dict, pad_to: int, precision: str = "float32",
                      alter: tuple = ()):
    """As `hybrid_moe_decoder.served_token_gaps`: prompt + served tokens
    padded at the END to `pad_to`, which no causal mask looks at. Per
    request, for each served token, the float32 reference's best logit minus
    its logit of the served token (under a lower `precision`: of the token
    that precision puts first)."""
    seqs = []
    for prompt, tokens in zip(prompts, served):
        seq = list(prompt) + list(tokens)
        if len(seq) > pad_to:
            raise ValueError(f"{len(seq)} tokens exceed pad_to={pad_to}")
        seqs.append(seq + [0] * (pad_to - len(seq)))
    ids = jnp.asarray(seqs, jnp.int32)
    ref = forward(top, layer_fn, ids, model, "float32", alter)
    chosen = jnp.concatenate(
        [ids[:, 1:], jnp.zeros((ids.shape[0], 1), jnp.int32)], axis=1)
    if precision != "float32":
        chosen = jnp.argmax(forward(top, layer_fn, ids, model, precision),
                            axis=-1)
    picked = jnp.take_along_axis(ref, chosen[..., None], axis=-1)[..., 0]
    gaps = jax.device_get(jnp.max(ref, axis=-1) - picked)
    out = []
    for row, prompt, tokens in zip(gaps, prompts, served):
        first = len(prompt) - 1          # logits here predict served[0]
        out.append(row[first:first + len(tokens)].tolist())
    return out
