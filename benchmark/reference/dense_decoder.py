"""The plain reference: a dense decoder-only transformer in `jax.numpy`.

Written from the published description of the Mistral-7B / DeepSeek-LLM-7B
family (pre-norm blocks: RMSNorm, rotary embedding in the rotate-half
convention, causal attention with grouped or full key/value heads, SwiGLU
feed-forward, final RMSNorm, untied output head, mean next-token
cross-entropy) and of AdamW with global-norm clipping. float32 throughout, every
matrix multiplication at `highest` precision. No kernels, no cache, no
batching tricks; attention is computed one key/value head at a time only so
that the [S, S] scores of a 4k row fit beside the weights.

It imports nothing from `llama_pipeline_parallel_tpu` and is given nothing
the program made: weights come from `benchmark.weights`, rows and prompts
from `benchmark.traffic`, both drawn from the seed.

`precision` selects the arithmetic of the matrix multiplications:
`"float32"` is the reference; `"fp8"` is the CONTROL, the same mathematics
with every weight multiplication computed as a float8 recipe would (operands
rounded to e4m3 under a per-tensor scale, cotangents to e5m2), the nearest
precision below the bfloat16 the configurations state. A sound comparison has
to tell the two apart (tests/benchmark_harness).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_E4M3_MAX = 448.0      # largest finite float8_e4m3fn
F8_E5M2_MAX = 57344.0    # largest finite float8_e5m2


def _round(x, dtype, largest):
    """x rounded to a float8 `dtype` under a per-tensor scale."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / largest
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _mm_fp8(x, w):
    """x @ w as a float8 training recipe computes it: both operands rounded
    to e4m3 going forward, and coming back the cotangent rounded to e5m2
    before it meets the rounded operands. Accumulation stays float32."""
    return _mm_fp8_fwd(x, w)[0]


def _mm_fp8_fwd(x, w):
    xq = _round(x, jnp.float8_e4m3fn, F8_E4M3_MAX)
    wq = _round(w, jnp.float8_e4m3fn, F8_E4M3_MAX)
    return jnp.matmul(xq, wq, precision=HIGHEST), (xq, wq)


def _mm_fp8_bwd(saved, g):
    xq, wq = saved
    gq = _round(g, jnp.float8_e5m2, F8_E5M2_MAX)
    dx = jnp.matmul(gq, wq.T, precision=HIGHEST)
    dw = jnp.matmul(xq.reshape(-1, xq.shape[-1]).T,
                    gq.reshape(-1, gq.shape[-1]), precision=HIGHEST)
    return dx, dw


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(x, w, precision: str):
    """x @ w in float32, or as float8 computes it (the control)."""
    if precision == "fp8":
        return _mm_fp8(x, w)
    if precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return weight * (x * jax.lax.rsqrt(var + eps))


def rotary(x, positions, theta):
    """x: [b, s, h, hd]; positions: [b, s]. Rotate-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[..., None] * inv      # [b, s, hd/2]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, :, None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def causal_attention(q, k, v):
    """q: [b, s, h, hd]; k, v: [b, s, kv, hd] -> [b, s, h, hd]."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    group = h // kv
    qg = q.reshape(b, s, kv, group, hd).transpose(2, 0, 3, 1, 4)  # [kv,b,g,s,hd]
    kg = k.transpose(2, 0, 1, 3)                                  # [kv,b,s,hd]
    vg = v.transpose(2, 0, 1, 3)
    keep = jnp.tril(jnp.ones((s, s), bool))

    def one_head(args):
        qh, kh, vh = args
        scores = jnp.einsum("bgqd,bkd->bgqk", qh, kh,
                            precision=HIGHEST) * (hd ** -0.5)
        scores = jnp.where(keep, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bgqk,bkd->bgqd", probs, vh, precision=HIGHEST)

    # checkpointed, so a backward pass keeps q, k, v of a head and not the
    # [S, S] scores of every head at once
    out = jax.lax.map(jax.checkpoint(one_head), (qg, kg, vg))     # [kv,b,g,s,hd]
    return out.transpose(1, 3, 0, 2, 4).reshape(b, s, h, hd)


def block(layer, x, positions, model, precision):
    b, s, d = x.shape
    heads = model["num_attention_heads"]
    hd = d // heads
    kv = model["num_key_value_heads"]
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    h = rms_norm(x, layer["input_norm"], eps)
    q = _mm(h, layer["attn"]["wq"], precision).reshape(b, s, heads, hd)
    k = _mm(h, layer["attn"]["wk"], precision).reshape(b, s, kv, hd)
    v = _mm(h, layer["attn"]["wv"], precision).reshape(b, s, kv, hd)
    q, k = rotary(q, positions, theta), rotary(k, positions, theta)
    a = causal_attention(q, k, v).reshape(b, s, d)
    x = x + _mm(a, layer["attn"]["wo"], precision)
    h = rms_norm(x, layer["post_norm"], eps)
    gate = jax.nn.silu(_mm(h, layer["mlp"]["gate"], precision))
    up = _mm(h, layer["mlp"]["up"], precision)
    return x + _mm(gate * up, layer["mlp"]["down"], precision)


def hidden_states(params, ids, model, precision="float32"):
    """[b, s] token ids -> [b, s, d] after the final norm."""
    b, s = ids.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = params["embed"]["embedding"][ids]

    @jax.checkpoint
    def body(x, layer):
        return block(layer, x, positions, model, precision), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return rms_norm(x, params["norm"], model["rms_norm_eps"])


def logits_fn(params, ids, model, precision="float32"):
    return _mm(hidden_states(params, ids, model, precision),
               params["lm_head"], precision)


def mean_next_token_loss(params, ids, model, precision="float32"):
    """Mean cross-entropy of token t+1 given tokens <= t, over all rows."""
    logits = logits_fn(params, ids, model, precision)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(ll)


# ---------------------------------------------------------------------------
# Training: AdamW with global-norm clipping, followed step by step
# ---------------------------------------------------------------------------

def _sumsq(tree):
    return sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree))


def _per_group(layers_tree, groups: int):
    """sqrt of the sum of squares of the layer leaves, by `groups` equal
    runs of consecutive layers (a pipeline stage's share) -> [groups]."""
    total = 0.0
    for leaf in jax.tree.leaves(layers_tree):
        n = leaf.shape[0]
        per_layer = jnp.sum(jnp.square(leaf).reshape(n, -1), axis=1)
        total = total + per_layer.reshape(groups, n // groups).sum(axis=1)
    return jnp.sqrt(total)


@functools.partial(jax.jit, static_argnames=("model_items", "hp_items",
                                             "groups", "precision"),
                   donate_argnames=("params", "mu", "nu"))
def _train_step(params, mu, nu, count, ids, *, model_items, hp_items, groups,
                precision):
    model, hp = dict(model_items), dict(hp_items)
    loss, grads = jax.value_and_grad(mean_next_token_loss)(
        params, ids, model, precision)
    gnorm = jnp.sqrt(_sumsq(grads))
    out = {"loss": loss, "grad_norm": gnorm,
           "grad_norm_per_stage": _per_group(grads["layers"], groups)}
    clip = jnp.minimum(1.0, hp["max_grad_norm"] / jnp.maximum(gnorm, 1e-30))
    lr = hp["learning_rate"] * (1.0 - count / hp["total_steps"])
    b1, b2 = hp["adam_beta1"], hp["adam_beta2"]
    t = count + 1.0

    def leaf(p, g, m, v):
        g = g * clip
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        step = (m / (1.0 - b1 ** t)) / (jnp.sqrt(v / (1.0 - b2 ** t))
                                          + hp["adam_eps"])
        upd = -lr * (step + hp["weight_decay"] * p)
        return p + upd, m, v, upd

    flat_p, tree = jax.tree.flatten(params)
    res = [leaf(p, g, m, v) for p, g, m, v in zip(
        flat_p, jax.tree.leaves(grads), jax.tree.leaves(mu),
        jax.tree.leaves(nu))]
    new_p, new_m, new_v, upd = (jax.tree.unflatten(tree, [r[i] for r in res])
                                for i in range(4))
    out["update_norm_per_stage"] = _per_group(upd["layers"], groups)
    return new_p, new_m, new_v, out


def _static(model: dict) -> tuple:
    """The numbers of a configuration as a hashable jit argument."""
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float))))


def follow_training(params, step_rows, model: dict, hp: dict, groups: int = 1,
                    precision: str = "float32") -> list[dict]:
    """Drive AdamW from `params` over `step_rows` (one [rows, s] id array
    per optimizer step). `hp`: learning_rate (peak, decaying linearly to 0
    at total_steps, no warm-up), weight_decay (decoupled, every leaf),
    adam_beta1/2, adam_eps, max_grad_norm, total_steps. Returns per step the
    loss, the global gradient norm before clipping, and by group of layers
    the gradient norm and the norm of the update applied. `params` is
    consumed."""
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    model_items = _static(model)
    hp_items = tuple(sorted(hp.items()))
    readings = []
    for i, ids in enumerate(step_rows):
        params, mu, nu, out = _train_step(
            params, mu, nu, jnp.float32(i), ids, model_items=model_items,
            hp_items=hp_items, groups=groups, precision=precision)
        readings.append({k: jax.device_get(v).tolist() for k, v in out.items()})
    del params, mu, nu
    return readings


# ---------------------------------------------------------------------------
# Serving: how far below the reference's best does each served token lie
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("model_items", "precision"))
def _gaps(params, ids, next_ids, *, model_items, precision):
    model = dict(model_items)
    ref = logits_fn(params, ids, model, "float32")[0]            # [S, V]
    if precision == "float32":
        chosen = next_ids
    else:
        chosen = jnp.argmax(logits_fn(params, ids, model, precision)[0], -1)
    picked = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - picked


def served_token_gaps(params, prompt, served, model: dict, pad_to: int,
                      precision: str = "float32"):
    """One forward over prompt + served tokens (padded at the END to
    `pad_to`, which causal attention never looks at, so that every request
    runs one compiled shape). For each served token, the reference logit of
    its best token minus the reference logit of the served one: 0 where the
    served token is the reference's own choice. Under a lower `precision`
    the token read at each position is the one THAT precision puts first
    (the control; `served` then only fixes the context). Returns a list of
    len(served) floats."""
    model_items = _static(model)
    seq = list(prompt) + list(served)
    if len(seq) > pad_to:
        raise ValueError(f"{len(seq)} tokens exceed pad_to={pad_to}")
    ids = jnp.asarray([seq + [0] * (pad_to - len(seq))], jnp.int32)
    next_ids = jnp.concatenate([ids[0, 1:], jnp.zeros((1,), jnp.int32)])
    gaps = _gaps(params, ids, next_ids, model_items=model_items,
                 precision=precision)
    first = len(prompt) - 1              # logits here predict served[0]
    return jax.device_get(gaps)[first:first + len(served)].tolist()
