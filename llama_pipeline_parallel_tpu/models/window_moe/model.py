"""Layers of the window / full softmax block (config.py): a grouped-query
mixer whose kind (full or window) sets its KV heads, its rotary base, its
mask and whether a learned sink stands in the softmax's denominator; rotary
on the leading `rotary_dim` numbers of a head; values scaled where they are
made; the output projection reading `heads x v_head_dim` where the query
projection writes `heads x head_dim`. The second half of a layer is
`llama.mlp_block` (a dense layer) or `hybrid_moe.model.moe_block` with
nothing beside the routed sum: one implementation each.

Parameter tree (`init_params`). The layers' kinds follow a published list
that has a period only after a leading layer of its own, and a kind sets a
leaf's SHAPE (4 against 8 KV heads), so the layers are a list, one dict of
the layer's own leaves each, and the serving programs unroll it. Nothing is
stacked, so nothing is ever sliced: the grouped product
(`ops/grouped_matmul.py`) takes a layer's routed experts as the buffers they
are stored in (a stack of one layer, seen through a reshape).

    embed.embedding [V, d]   norm [d]   lm_head [d, V]
    layers[i]: input_norm [d], wq [d, H dk], wk [d, G dk], wv [d, G dv],
               wo [H dv, d]; a window layer adds sink [H] (float32);
               post_norm [d], then a dense layer's mlp.gate / up [d, F],
               mlp.down [F, d], or an expert layer's router [d, R],
               router_bias [R] (float32), gate / up [held, d, f],
               down [held, f, d]

    u = rmsnorm(x);  q = W_q u [H, dk];  k = W_k u [G, dk];  v = c W_v u [G, dv]
    rotate-half on the leading `rotary_dim` numbers of every q and k head
    s_ij = q_i . k_j / sqrt(dk);  full: j <= i;  window: i - w < j <= i
    full:    p_ij = exp(s_ij) / sum_j exp(s_ij)
    window:  p_ij = exp(s_ij) / (exp(sink_h) + sum_j exp(s_ij))
    h = x + W_o concat_h(sum_j p_ij v_j)

What a layer keeps of a token is its roped keys and its scaled values. A
store keeps a key padded with zeros to whole lanes of the chip (`stored_key`:
192 numbers in 256; a 192-wide row is copied, pool and all, in front of
every kernel that reads it); a query meets a stored key padded likewise, so
the padding adds nothing to any product and the scores' factor stays
`dk ** -0.5`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from llama_pipeline_parallel_tpu.models.hybrid_moe import model as hybrid
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.model import cast_weight
from llama_pipeline_parallel_tpu.models.window_moe.config import (
    KindDims,
    WindowMoEConfig,
)
from llama_pipeline_parallel_tpu.ops.gqa_prefill_attention import (
    full_prefill_attention,
    window_prefill_attention,
)
from llama_pipeline_parallel_tpu.ops.rmsnorm import rms_norm
from llama_pipeline_parallel_tpu.ops.rope import apply_rope, rope_cos_sin
from llama_pipeline_parallel_tpu.utils import trace

Params = dict
INIT_STD = 0.02
LANES = 128
# the expert layers' six, then the ring entries and the page entries the
# softmax layers' queries read (summed over rows or queries and layers; pads
# and rows that are not decoding count for nothing)
COUNTERS = hybrid.COUNTERS + ("window_entries_read", "full_entries_read")
_N_MOE = len(hybrid.COUNTERS)


# -- parameters ---------------------------------------------------------------

def init_params(rng: jax.Array, cfg: WindowMoEConfig) -> Params:
    """Seeded parameters in the tree above: normal(0, 0.02) projections,
    unit norm scales, sinks normal(0, 1), router bias zero; sinks, router
    and its bias float32."""
    d, pd = cfg.hidden_size, cfg.param_dtype
    keys = iter(jax.random.split(rng, 12 * cfg.num_hidden_layers + 2))
    normal = lambda shape, std=INIT_STD: jax.random.normal(
        next(keys), shape, jnp.float32) * std
    proj = lambda *shape: normal(shape).astype(pd)
    ones = lambda n: jnp.ones((n,), pd)
    H, dk, dv = cfg.num_attention_heads, cfg.head_dim, cfg.v_head_dim
    F, f, held = cfg.intermediate_size, cfg.moe_intermediate_size, cfg.held

    def one_layer(i: int) -> Params:
        kd = cfg.kind(i)
        layer = {"input_norm": ones(d), "wq": proj(d, H * dk),
                 "wk": proj(d, kd.kv_heads * dk),
                 "wv": proj(d, kd.kv_heads * dv), "wo": proj(H * dv, d),
                 "post_norm": ones(d)}
        if kd.sink:
            layer["sink"] = normal((H,), 1.0)
        if cfg.moe_layers[i]:
            layer.update(
                router=normal((d, cfg.router_experts)),
                router_bias=jnp.zeros((cfg.router_experts,), jnp.float32),
                gate=proj(held, d, f), up=proj(held, d, f),
                down=proj(held, f, d))
        else:
            layer["mlp"] = {"gate": proj(d, F), "up": proj(d, F),
                            "down": proj(F, d)}
        return layer

    return {"embed": {"embedding": proj(cfg.vocab_size, d)},
            "layers": [one_layer(i) for i in range(cfg.num_hidden_layers)],
            "norm": ones(d), "lm_head": proj(d, cfg.vocab_size)}


# -- the mixer's projections ---------------------------------------------------

def key_store_width(cfg: WindowMoEConfig) -> int:
    """Numbers a store keeps of a key: `head_dim` in whole lanes."""
    return -(-cfg.head_dim // LANES) * LANES


def stored_key(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """A key (or a query that meets stored keys) padded with zeros to the
    width a store keeps."""
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, width - x.shape[-1]),))


def _rope_front(x: jnp.ndarray, positions: jnp.ndarray, n: int, theta: float,
                dtype) -> jnp.ndarray:
    """Rotate-half rope on the leading `n` numbers of x [b, s, h, dk] at
    `positions` [b, s]; the rest pass."""
    cos, sin = rope_cos_sin(positions, n, theta, dtype=dtype)
    front = x[..., :n]
    return jnp.concatenate([apply_rope(front, front, cos, sin)[0], x[..., n:]],
                           axis=-1)


def project(layer: Params, x: jnp.ndarray, positions: jnp.ndarray,
            kd: KindDims, cfg: WindowMoEConfig):
    """Input norm, the three projections, rope and the values' scale. x: [b,
    s, d]; positions: [b, s] the tokens' own (pads not counted). Returns q
    [b, s, H, dk], k [b, s, G, dk] (both roped), v [b, s, G, dv] (scaled)."""
    b, s, _ = x.shape
    dt = cfg.dtype
    with jax.named_scope(trace.SCOPE_ATTN_QKV):
        hidden = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
        heads = lambda name, width: (
            hidden @ cast_weight(layer[name], dt)).reshape(b, s, -1, width)
        rope = lambda a: _rope_front(a, positions, cfg.rotary_dim,
                                     kd.rope_theta, dt)
        q, k = rope(heads("wq", cfg.head_dim)), rope(heads("wk", cfg.head_dim))
        v = heads("wv", cfg.v_head_dim) * jnp.asarray(cfg.value_scale, dt)
    return q, k, v


def attn_output(layer: Params, x: jnp.ndarray, out: jnp.ndarray,
                cfg: WindowMoEConfig) -> jnp.ndarray:
    """Output projection (from `heads x v_head_dim`) and the residual."""
    b, s, _ = x.shape
    with jax.named_scope(trace.SCOPE_ATTN_OUT):
        return x + out.reshape(b, s, -1) @ cast_weight(layer["wo"], cfg.dtype)


# -- a span of queries -----------------------------------------------------------

def window_span(layer: Params, x: jnp.ndarray, q, k, v,
                before_k: jnp.ndarray, before_v: jnp.ndarray,
                before_valid: jnp.ndarray, q_valid: jnp.ndarray,
                cfg: WindowMoEConfig) -> jnp.ndarray:
    """A window layer's mixer for C consecutive queries of each row. The
    context is the W places BEFORE the span (`before_k` [b, W, G, dk],
    `before_v` [b, W, G, dv], oldest first, `before_valid` [b, W]; W =
    `window_context` of ops/gqa_prefill_attention.py) and the span's own (k, v, valid where `q_valid`); query i
    sees context places (W + i - window, W + i]. Only the tiles the band
    touches are computed. Returns x + y."""
    ctx_k = jnp.concatenate([before_k.astype(k.dtype), k], axis=1)
    ctx_v = jnp.concatenate([before_v.astype(v.dtype), v], axis=1)
    ctx_valid = jnp.concatenate([before_valid, q_valid], axis=1)
    with jax.named_scope(trace.WINDOW_PREFILL_ATTN):
        out = window_prefill_attention(q, ctx_k, ctx_v, ctx_valid,
                                       layer["sink"], cfg.sliding_window)
    return attn_output(layer, x, out, cfg)


def full_span(layer: Params, x: jnp.ndarray, q, keys: jnp.ndarray,
              values: jnp.ndarray, key_valid: jnp.ndarray,
              q_start: jnp.ndarray, cfg: WindowMoEConfig) -> jnp.ndarray:
    """A full layer's mixer for T consecutive queries of each row against S
    cached places of the same row, the queries' own among them: every query
    reads every valid place up to its own. keys: [b, S, G, dk]; values: [b,
    S, G, dv]; key_valid: [b, S]; `q_start`: int32 scalar, the place of the
    first query among the S. The scores live in the kernel. Returns x + y."""
    with jax.named_scope(trace.FULL_PREFILL_ATTN):
        out = full_prefill_attention(q, keys, values, key_valid, q_start)
    return attn_output(layer, x, out, cfg)


def span_counts(row_valid: jnp.ndarray, q_place: jnp.ndarray,
                q_valid: jnp.ndarray, cfg: WindowMoEConfig) -> jnp.ndarray:
    """int32[2]: the entries a span's queries read in ONE window layer and
    in ONE full layer. row_valid: [b, S] bool, the row so far (pads lie in
    front of every token); q_place: [b, T] the queries' places among the S;
    q_valid: [b, T]. A query reads the valid places up to its own in a full
    layer, the last `sliding_window` of them in a window layer."""
    upto = jnp.cumsum(row_valid.astype(jnp.int32), axis=1)
    seen = jnp.where(q_valid, jnp.take_along_axis(upto, q_place, axis=1), 0)
    return jnp.stack([jnp.sum(jnp.minimum(seen, cfg.sliding_window)),
                      jnp.sum(seen)]).astype(jnp.int32)


def ring_mask(newest: jnp.ndarray, row_valid: jnp.ndarray,
              cfg: WindowMoEConfig) -> jnp.ndarray:
    """What a query at logical place `newest` [b] sees of its slot's ring,
    once its own entry is in it. Place r of a ring of R holds the newest
    place p <= `newest` with p % R == r; it is visible when p lies in the
    window and `row_valid` [b, max_len] (the slot's mask row) says p is a
    token. Returns bool [b, R]."""
    R = cfg.ring_len
    r = jnp.arange(R, dtype=jnp.int32)[None, :]
    held = newest[:, None] - (newest[:, None] - r) % R          # [b, R]
    inside = (held >= 0) & (held > newest[:, None] - cfg.sliding_window)
    valid = jnp.take_along_axis(row_valid, jnp.clip(held, 0, None), axis=1) > 0
    return inside & valid


# -- the second half of a layer ---------------------------------------------------

def feed_forward(layer: Params, x: jnp.ndarray, valid: jnp.ndarray,
                 is_moe: bool, cfg: WindowMoEConfig, mlp_scope: str):
    """A dense layer's gated feed-forward, or an expert layer's routed sum
    with NOTHING beside it (no shared expert; the weights are the chosen
    scores over their sum, times a factor of 1), each with its norm and the
    residual. Returns (x + y, the expert counters int32[6]: zeros for a
    dense layer)."""
    if not is_moe:
        return (llama.mlp_block(layer, x, cfg, scope=mlp_scope),
                jnp.zeros((_N_MOE,), jnp.int32))
    # the layer's experts as a stack of one layer, at place 0
    experts = {name: layer[name][None] for name in hybrid.EXPERT_LEAVES}
    return hybrid.moe_block(layer, experts, 0, x, valid, cfg, shared=False)

