"""The compressed-window block's own mathematics: the pooling of a finished
chunk, the rule of what a query sees, and the forward pass over a whole
prompt that `decode.prefill_prompt` runs. The projections, rope, feed-forward
and head are the dense family's (`models/llama/`), called, not copied.

The layer. `h = rmsnorm(x) (1 + g)`; `q, k, v = h Wq, h Wk, h Wv`; rope on q
and k by the token's position `p`. With `W = window_size`, `C = chunk_size`,
`s = head_dim ** -0.5` and two learned vectors a head, `mu` and `phi`:

- chunk `j` holds positions `C j .. C j + C - 1`; once all exist its summary
  is `k~_j = sum_m softmax_m(s k_m . mu) k_m`, `v~_j = sum_m softmax_m(s k_m
  . phi) v_m` (`pool_chunks`, float32 statistics);
- the query at `p` (window `w = p // W`) reads, in ONE softmax, the exact
  keys `m` of its own window with `m <= p` and the summary of every chunk of
  every EARLIER window (`visible_interval`, `summary_tags`);
- then `Wo`, the residual, `rmsnorm (1 + g)`, SwiGLU, the residual. The
  residual stream is float32; products are in `cfg.dtype`.

`lm_head` is `[hidden, num_pred_heads x vocab]`; head 0 is the next token, and
the only one these programs compute (`head0`). The others are kept in the
tree as published and read by nothing yet (ROADMAP B: multi-token steps).

Two details of the published layer are not settled by its paper and are each
isolated in one function here (and one of the plain reference), so that a
correction is a two-line change: which vector pools keys and which values,
with no bias on either (`pool_chunks`); and that windows are aligned blocks
of `W` positions, not a span that slides (`visible_interval`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from llama_pipeline_parallel_tpu.models.eva.config import EvaConfig
from llama_pipeline_parallel_tpu.models.llama import decode as dense_decode
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.ops.eva_prefill_attention import (
    PAD_HI,
    PAD_LO,
    eva_prefill_attention,
)
from llama_pipeline_parallel_tpu.ops.rope import rope_cos_sin
from llama_pipeline_parallel_tpu.utils import trace

Params = dict


def init_params(rng: jax.Array, cfg: EvaConfig) -> Params:
    """The tree as a checkpoint holds it: the dense decoder's leaves (stacked
    on a leading layer axis), `mu` / `phi` [L, kv_heads, head_dim] beside a
    layer's projections, norm offsets `g` at zero (the scale is `1 + g`) and
    a head of `num_pred_heads x vocab` columns. normal(0, 0.02); the pooling
    vectors normal(0, 1), so that a chunk's pooling is not uniform."""
    n, d, f, v = (cfg.num_hidden_layers, cfg.hidden_size,
                  cfg.intermediate_size, cfg.vocab_size)
    kv_dim = cfg.kv_heads * cfg.head_dim
    keys = jax.random.split(rng, 11)
    pd = cfg.param_dtype
    nrm = lambda key, shape, std=0.02: (
        jax.random.normal(key, shape, jnp.float32) * std).astype(pd)
    return {
        "embed": {"embedding": nrm(keys[0], (v, d))},
        "layers": {
            "attn": {
                "wq": nrm(keys[1], (n, d, d)),
                "wk": nrm(keys[2], (n, d, kv_dim)),
                "wv": nrm(keys[3], (n, d, kv_dim)),
                "wo": nrm(keys[4], (n, d, d)),
                "mu": nrm(keys[9], (n, cfg.kv_heads, cfg.head_dim), 1.0),
                "phi": nrm(keys[10], (n, cfg.kv_heads, cfg.head_dim), 1.0),
            },
            "mlp": {
                "gate": nrm(keys[5], (n, d, f)),
                "up": nrm(keys[6], (n, d, f)),
                "down": nrm(keys[7], (n, f, d)),
            },
            "input_norm": jnp.zeros((n, d), pd),
            "post_norm": jnp.zeros((n, d), pd),
        },
        "norm": jnp.zeros((d,), pd),
        "lm_head": nrm(keys[8], (d, cfg.num_pred_heads * v)),
    }


def with_unit_offset(params: Params, cfg: EvaConfig) -> Params:
    """`params` as the dense family's functions read them: every norm's
    scale is `1 + g` (float32), and the head is head 0's columns."""
    one_plus = lambda g: 1.0 + g.astype(jnp.float32)
    layers = params["layers"]
    return {**params,
            "layers": {**layers,
                       "input_norm": one_plus(layers["input_norm"]),
                       "post_norm": one_plus(layers["post_norm"])},
            "norm": one_plus(params["norm"]),
            "lm_head": params["lm_head"][:, :cfg.vocab_size]}


def embed(params: Params, ids: jnp.ndarray, cfg: EvaConfig) -> jnp.ndarray:
    """The float32 residual stream's first value."""
    return llama.embed(params, ids, cfg).astype(jnp.float32)


def head0(params: Params, x: jnp.ndarray, cfg: EvaConfig) -> jnp.ndarray:
    """Final norm and head 0 of `with_unit_offset`'s tree: float32 logits
    [..., vocab]; the product in `cfg.dtype`."""
    x = llama.final_norm(params, x, cfg).astype(cfg.dtype)
    return llama.lm_head(params, x, cfg)


def pool_chunks(k: jnp.ndarray, v: jnp.ndarray, mu: jnp.ndarray,
                phi: jnp.ndarray, cfg: EvaConfig):
    """One pooled key and one pooled value a chunk. k, v: [..., n, kv_h,
    hd], `n` a whole number of chunks, keys after rope; mu, phi: [kv_h, hd].
    Returns ([..., n / C, kv_h, hd]) x 2 in k's and v's dtypes. The chunk's
    KEYS choose both sets of weights, `mu` those of the pooled key and `phi`
    those of the pooled value, and nothing is added to either (ASSUMED (a),
    the configuration file; its twin is the reference's `pool_chunks`)."""
    *lead, n, kvh, hd = k.shape
    C = cfg.chunk_size
    with jax.named_scope(trace.EVA_POOL):
        shape = (*lead, n // C, C, kvh, hd)
        kf = k.astype(jnp.float32).reshape(shape)
        vf = v.astype(jnp.float32).reshape(shape)
        scale = hd ** -0.5

        def weights(vector):
            scores = scale * jnp.einsum("...chd,hd->...ch", kf,
                                        vector.astype(jnp.float32))
            return jax.nn.softmax(scores, axis=-2)[..., None]

        return ((weights(mu) * kf).sum(axis=-3).astype(k.dtype),
                (weights(phi) * vf).sum(axis=-3).astype(v.dtype))


def visible_interval(positions: jnp.ndarray, valid: jnp.ndarray,
                     cfg: EvaConfig):
    """A query's interval of exact keys `[q_lo, q_hi]`: from the first
    position of its own window to its own position; the summaries it reads
    are those of chunks that start before `q_lo`. Windows are ALIGNED blocks
    of `W` positions (ASSUMED (b); the reference's `exact_set` /
    `summary_set` are its twins). A pad (`valid` false) sees nothing."""
    W = cfg.window_size
    return (jnp.where(valid, positions // W * W, PAD_LO),
            jnp.where(valid, positions, PAD_HI))


def summary_tags(n: int, cfg: EvaConfig) -> jnp.ndarray:
    """[n]: the first position of summary entry j's chunk."""
    return jnp.arange(n, dtype=jnp.int32) * cfg.chunk_size


def attend_span(q, k_sum, v_sum, tag_sum, k_exact, v_exact, tag_exact,
                positions, valid, cfg: EvaConfig) -> jnp.ndarray:
    """The span's attention, both kinds of key in one softmax
    (`ops/eva_prefill_attention.py`). q: [b, T, h, hd]; keys and values [b,
    S, kv_h, hd]; tags [b, S]; positions / valid: [b, T]. -> [b, T, h, hd]."""
    b, T, h, hd = q.shape
    flat = lambda x: x.reshape(*x.shape[:2], -1)
    q_lo, q_hi = visible_interval(positions, valid, cfg)
    with jax.named_scope(trace.EVA_ATTN_PREFILL):
        out = eva_prefill_attention(
            flat(q), flat(k_sum), flat(v_sum), tag_sum, flat(k_exact),
            flat(v_exact), tag_exact, q_lo, q_hi, h, hd ** -0.5)
    return out.reshape(b, T, h, hd)


def visible_counts(positions: jnp.ndarray, valid: jnp.ndarray,
                   cfg: EvaConfig):
    """(exact entries, summary entries) the valid queries read in ONE layer,
    int32 scalars: `p mod W + 1` and `(p // W) x (W / C)` a query."""
    W = cfg.window_size
    on = valid.astype(jnp.int32)
    return (jnp.sum(on * (positions % W + 1)),
            jnp.sum(on * (positions // W) * cfg.chunks_per_window))


def forward_prompt(params: Params, input_ids: jnp.ndarray,
                   attention_mask: jnp.ndarray, cfg: EvaConfig) -> dict:
    """A whole LEFT-padded prompt in one pass, nothing cached before it.
    input_ids / attention_mask: [b, P]. Every chunk of the prompt is pooled
    from the prompt's own keys (a query reads only those of earlier windows);
    returns {"logits": [b, vocab] float32 at the last place, "cache": {"k",
    "v": [L, b, P, kv_h, hd] the exact keys and values by ROW place, "sk",
    "sv": [L, b, P / C, kv_h, hd] the pooled ones by chunk of POSITION},
    "counters": int32[3] as `decode.COUNTERS`, "next_pos": [b]}."""
    b, P = input_ids.shape
    C = cfg.chunk_size
    mask = attention_mask.astype(jnp.int32)
    valid = mask > 0
    positions = jnp.clip(jnp.cumsum(mask, axis=1) - 1, 0, None).astype(
        jnp.int32)
    pad = P - mask.sum(axis=1)                                  # [b]
    # row place of position m (left padding: pad + m), for the pooling
    by_position = jnp.clip(pad[:, None] + jnp.arange(P)[None, :], 0, P - 1)
    tag_exact = jnp.where(valid, positions, -1)
    tag_sum = jnp.broadcast_to(summary_tags(P // C, cfg), (b, P // C))
    view = with_unit_offset(params, cfg)
    x = embed(view, input_ids, cfg)
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                            dtype=cfg.dtype)

    def body(h, layer):
        q, k, v = dense_decode._project_qkv(layer, h, cos, sin, cfg)
        take = lambda x: jnp.take_along_axis(
            x, by_position[:, :, None, None], axis=1)
        sk, sv = pool_chunks(take(k), take(v), layer["attn"]["mu"],
                             layer["attn"]["phi"], cfg)
        out = attend_span(q, sk, sv, tag_sum, k, v, tag_exact, positions,
                          valid, cfg)
        h = dense_decode._attn_out_and_mlp(layer, h, out, cfg)
        return h, {"k": k, "v": v, "sk": sk, "sv": sv}

    x, cache = jax.lax.scan(body, x, view["layers"])
    logits = head0(view, x[:, -1:, :], cfg)[:, -1]
    seen_w, seen_s = visible_counts(positions, valid, cfg)
    # every window a row's tokens finish is pooled (and, spliced by
    # `write_pages`, written) once
    written = (mask.sum(axis=1) // cfg.window_size).sum() * (
        cfg.chunks_per_window)
    return {"logits": logits, "cache": cache,
            "counters": jnp.stack([seen_w, seen_s, written]).astype(
                jnp.int32) * cfg.num_hidden_layers,
            "next_pos": positions[:, -1] + 1}
