"""The plain reference of the compressed-window decoder (EvaByte's block): one
forward pass over the UNPADDED sequence in `jax.numpy`, float32, every product
at `highest` precision. No pages, no ring, no cache, no kernel: what a query
reads is two explicit SETS built from positions, and the summaries are pooled
from the whole sequence's keys.

Written from the EVA paper's form (exact terms for a local set, one shared
control variate a chunk for the rest, one self-normalised sum) with learned
per-head pooling vectors in place of random features, as ISSUE 36 sets it
out. With `W = window_size`, `C = chunk_size`, `s = head_dim ** -0.5`:

    h = rmsnorm(x) (1 + g);  q, k, v = h Wq, h Wk, h Wv;  rope(q, k; p)
    chunk j = positions C j .. C j + C - 1:
        k~_j = sum_m softmax_m(s k_m . mu)  k_m
        v~_j = sum_m softmax_m(s k_m . phi) v_m
    query at p, window w = p // W:
        E = {m : m // W == w, m <= p}          (`exact_set`)
        S = {j : (C j) // W < w}               (`summary_set`)
        out_p = (sum_E e^{s q.k_m} v_m + sum_S e^{s q.k~_j} v~_j)
                / (sum_E e^{s q.k_m} + sum_S e^{s q.k~_j})
    then Wo, the residual, rmsnorm (1 + g), SwiGLU, the residual; the final
    rmsnorm (1 + g) and head 0 of `lm_head` ([hidden, heads x vocab]).

Departures from the published model, each listed in the configuration file's
`assumed`: (a) which vector pools keys and which values, and that nothing is
added to a pooled key (`pool_chunks`); (b) windows are aligned blocks of `W`
positions (`exact_set`, `summary_set`); the weights are seeded, not the
checkpoint's; only head 0 of the eight output heads is computed.

It imports nothing from `llama_pipeline_parallel_tpu` and is given nothing the
program made. Beside its sibling `dense_decoder.py` it takes that file's
`rms_norm`, `rotary` and `_mm` (the float8 CONTROL's products), which say
nothing of this block.

`alter` holds the names of changes for controls and tests: "no_summaries"
leaves `S` empty (the reference as a model that dropped its summaries would
compute it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (
    HIGHEST,
    _mm,
    _static,
    rms_norm,
    rotary,
)

QUERY_BLOCK = 256


def pool_chunks(k, v, mu, phi, chunk: int):
    """k, v: [S, H, hd] (keys after rope), S a whole number of chunks; mu,
    phi: [H, hd] -> ([S / chunk, H, hd]) x 2. ASSUMED (a): the chunk's keys
    choose both sets of weights, `mu` the pooled key's, `phi` the pooled
    value's; no bias."""
    S, H, hd = k.shape
    kc = k.reshape(S // chunk, chunk, H, hd)
    vc = v.reshape(S // chunk, chunk, H, hd)
    scale = hd ** -0.5
    weights = lambda vector: jax.nn.softmax(
        scale * jnp.einsum("jmhd,hd->jmh", kc, vector, precision=HIGHEST),
        axis=1)[..., None]
    return (weights(mu) * kc).sum(axis=1), (weights(phi) * vc).sum(axis=1)


def exact_set(q_pos, k_pos, window: int):
    """[Q, S] bool: key position m is in E of query position p. ASSUMED (b):
    windows are aligned blocks of `window` positions."""
    return ((k_pos[None, :] // window == q_pos[:, None] // window)
            & (k_pos[None, :] <= q_pos[:, None]))


def summary_set(q_pos, n_chunks: int, window: int, chunk: int):
    """[Q, n_chunks] bool: chunk j is in S of query position p: it lies in
    an EARLIER window, never the query's own."""
    first = jnp.arange(n_chunks) * chunk
    return first[None, :] // window < q_pos[:, None] // window


def attention(q, k, v, sk, sv, positions, model: dict, alter: tuple):
    """q, k, v: [S, H, hd]; sk, sv: [S / C, H, hd] -> [S, H, hd]: for each
    query one softmax over its two sets, a block of queries at a time so
    that the [H, block, S + S / C] scores fit."""
    S, H, hd = q.shape
    W, C = model["window_size"], model["chunk_size"]
    scale = hd ** -0.5
    block = min(QUERY_BLOCK, S)

    def one_block(args):
        qb, pb = args                                   # [block, H, hd], [block]
        in_e = exact_set(pb, positions, W)              # [block, S]
        in_s = summary_set(pb, sk.shape[0], W, C)       # [block, S / C]
        if "no_summaries" in alter:
            in_s = jnp.zeros_like(in_s)
        scores = jnp.concatenate([
            jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST),
            jnp.einsum("qhd,khd->hqk", qb, sk, precision=HIGHEST)],
            axis=-1) * scale
        seen = jnp.concatenate([in_e, in_s], axis=-1)[None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        values = jnp.concatenate([v, sv], axis=0)
        return jnp.einsum("hqk,khd->qhd", probs, values, precision=HIGHEST)

    out = jax.lax.map(one_block, (q.reshape(S // block, block, H, hd),
                                  positions.reshape(S // block, block)))
    return out.reshape(S, H, hd)


def block(layer, x, positions, model: dict, precision: str, alter: tuple):
    """One layer over an unpadded sequence x: [S, d]."""
    S, d = x.shape
    H = model["num_attention_heads"]
    if model["num_key_value_heads"] != H:
        raise ValueError("the reference is written for full multi-head keys")
    hd = d // H
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    h = rms_norm(x, 1.0 + layer["input_norm"], eps)
    proj = lambda w: _mm(h, w, precision).reshape(1, S, H, hd)
    q = rotary(proj(layer["attn"]["wq"]), positions[None], theta)[0]
    k = rotary(proj(layer["attn"]["wk"]), positions[None], theta)[0]
    v = proj(layer["attn"]["wv"])[0]
    sk, sv = pool_chunks(k, v, layer["attn"]["mu"], layer["attn"]["phi"],
                         model["chunk_size"])
    a = attention(q, k, v, sk, sv, positions, model, alter).reshape(S, d)
    x = x + _mm(a, layer["attn"]["wo"], precision)
    h = rms_norm(x, 1.0 + layer["post_norm"], eps)
    gate = jax.nn.silu(_mm(h, layer["mlp"]["gate"], precision))
    up = _mm(h, layer["mlp"]["up"], precision)
    return x + _mm(gate * up, layer["mlp"]["down"], precision)


def logits_fn(params, ids, model: dict, precision: str = "float32",
              alter: tuple = ()):
    """[S] token ids (S a whole number of chunks and of query blocks) -> [S,
    vocab] float32 logits of head 0."""
    positions = jnp.arange(ids.shape[0], dtype=jnp.int32)
    x = params["embed"]["embedding"][ids]

    def body(x, layer):
        return block(layer, x, positions, model, precision, alter), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, 1.0 + params["norm"], model["rms_norm_eps"])
    return _mm(x, params["lm_head"][:, :model["vocab_size"]], precision)


@functools.partial(jax.jit, static_argnames=("model_items", "precision",
                                             "alter"))
def _gaps(params, ids, next_ids, *, model_items, precision, alter):
    model = dict(model_items)
    ref = logits_fn(params, ids, model)                           # [S, V]
    if precision == "float32" and not alter:
        chosen = next_ids
    else:
        chosen = jnp.argmax(logits_fn(params, ids, model, precision, alter),
                            axis=-1)
    picked = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - picked, ref


def _padded(prompt, served, pad_to: int):
    seq = list(prompt) + list(served)
    if len(seq) > pad_to:
        raise ValueError(f"{len(seq)} tokens exceed pad_to={pad_to}")
    return jnp.asarray(seq + [0] * (pad_to - len(seq)), jnp.int32)


def served_token_gaps(params, prompt, served, model: dict, pad_to: int,
                      precision: str = "float32", alter: tuple = ()):
    """One forward over prompt + served tokens, padded at the END to
    `pad_to` (what lies there no real query reads: it is after every one of
    them and in no earlier window), so that every request runs one compiled
    shape. For each served token, the reference logit of its best token
    minus the reference logit of the served one: 0 where the served token is
    the reference's own choice. Under a lower `precision`, or an `alter`,
    the token read at each position is the one THAT computation puts first
    (the controls; `served` then only fixes the context). Returns a list of
    len(served) floats."""
    ids = _padded(prompt, served, pad_to)
    next_ids = jnp.concatenate([ids[1:], jnp.zeros((1,), jnp.int32)])
    gaps, _ = _gaps(params, ids, next_ids, model_items=_static(model),
                    precision=precision, alter=tuple(alter))
    first = len(prompt) - 1              # logits here predict served[0]
    return jax.device_get(gaps)[first:first + len(served)].tolist()


def sequence_logits(params, ids, model: dict, pad_to: int):
    """[len(ids), vocab] float32 logits of an unpadded sequence (the tests'
    comparison by logits)."""
    padded = _padded(ids, [], pad_to)
    _, ref = _gaps(params, padded, padded, model_items=_static(model),
                   precision="float32", alter=())
    return ref[:len(ids)]
