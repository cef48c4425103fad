"""Layers of the latent-attention block (config.py): the MLA mixer of either
kind in its PROJECTED form (keys and values of every head made from the
latent; the prefill of a sliding layer and of a full layer without an
indexer) and its ABSORBED form (the up-projection folded into the query and
the output, so attention runs over the latents as the cache keeps them; a
full layer under an indexer, and every layer's tick), the indexer and its
exact top-k, the window's mask, the headwise gate, YaRN's rope. The expert
half of a layer is `hybrid_moe.model.moe_block`, the dense layer's
feed-forward `llama.mlp_block`: one implementation each.

Parameter tree (`init_params`; the periods' leaves stacked so that the
serving programs scan over periods with a period's layers unrolled, layers of
a period separate leaves as in models/hybrid_moe/model.py):

    embed.embedding [V, d]   norm [d]   lm_head [d, V]
    first.attn.*  first.post_norm  first.mlp.*     layer 0: full, dense
    periods.full.*    [P, ...]     the full layer of each period
    periods.win[j].*  [P, ...]     its j-th sliding layer (`cfg.period`'s
                                   sliding layers; none: an empty list)
    periods.moe[j].*  [P, ...]     the expert half of its j-th layer
    mtp.*                          a model with a multi-token-prediction
                                   module (`cfg.drafts`): `enorm`, `hnorm`
                                   [d], `eh_proj` [2 d, d], `attn.*` (a full
                                   layer's mixer), `moe.*` (an expert half,
                                   a stack of one), `shared_head_norm` [d];
                                   table and head are the trunk's

A mixer's leaves (H heads, latents of rank rq / rkv, a head nope + rope
wide, values v wide): `input_norm [d]`, `wqa [d, rq]`, `q_norm [rq]`,
`wqb [rq, H (nope + rope)]`, `wkva [d, rkv + rope]`, `kv_norm [rkv]`,
`wkb_k [rkv, H, nope]`, `wkb_v [rkv, H, v]`, `wo [H v, d]`; `wg [d, H]`
where the kind has a gate; a full layer under an indexer adds `wqi [rq, Hi
di]`, `wki [d, di]`, `ki_norm`, `ki_bias [di]`, `ww [d, Hi]`.

    cq = r_q rmsnorm(W_qa x);  [q^N_h; q^R_h] = W_qb,h cq,  q^R roped
    [c; k^R] = W_kva x;  c = r_kv rmsnorm(c),  k^R roped, shared by the heads
    projected:  k_h,s = [W_kb,h^K c_s; k^R_s],  v_h,s = W_kb,h^V c_s
    absorbed:   q'_h = W_kb,h^K^T q^N_h;  scores q'_h . c_s + q^R_h . k^R_s;
                o'_h = sum_s p_s c_s;  o_h = W_kb,h^V o'_h
    y_t = W_o [sigmoid(W_g x_t)_h o_h,t]_h        (no gate: W_o [o_h,t]_h)

What a layer keeps of a token is the ENTRY `[c_s; k^R_s]` (after norm,
rescale and rope): 576 numbers in a full layer, 1088 in a sliding one. A
store keeps it padded with zeros to whole tiles of the chip (`stored`: 640
and 1152); a query meets a stored entry padded with zeros likewise, so the
padding adds nothing to any product.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from llama_pipeline_parallel_tpu.models.hybrid_moe import model as hybrid
from llama_pipeline_parallel_tpu.models.latent_moe.config import (
    LatentMoEConfig,
    MixerDims,
)
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.model import cast_weight
from llama_pipeline_parallel_tpu.ops.attention import NEG_INF
from llama_pipeline_parallel_tpu.ops.latent_prefill_attention import (
    latent_prefill_attention,
)
from llama_pipeline_parallel_tpu.ops.rmsnorm import rms_norm
from llama_pipeline_parallel_tpu.ops.rope import apply_rope, rope_cos_sin
from llama_pipeline_parallel_tpu.ops.sparse_latent_attention import (
    sparse_latent_attention,
)
from llama_pipeline_parallel_tpu.utils import trace

Params = dict
INIT_STD = 0.02
LN_EPS = 1e-6
QUERY_BLOCK = 128          # queries of a prefill attended at a time
WINDOW_BLOCK = 256         # queries of a sliding layer's prefill at a time


# -- parameters ---------------------------------------------------------------

def init_params(rng: jax.Array, cfg: LatentMoEConfig) -> Params:
    """Seeded parameters in the tree above: normal(0, 0.02) projections,
    unit norm scales, zero biases; the router and its bias float32."""
    d, P, pd = cfg.hidden_size, cfg.periods, cfg.param_dtype
    keys = iter(jax.random.split(rng, 256))
    normal = lambda shape: jax.random.normal(next(keys), shape,
                                             jnp.float32) * INIT_STD
    proj = lambda *shape: normal(shape).astype(pd)

    def mixer(sliding: bool, lead: tuple) -> Params:
        kd = cfg.kind(sliding)
        H = kd.heads
        out = {"input_norm": jnp.ones(lead + (d,), pd),
               "wqa": proj(*lead, d, kd.rq),
               "q_norm": jnp.ones(lead + (kd.rq,), pd),
               "wqb": proj(*lead, kd.rq, H * (kd.nope + kd.rope)),
               "wkva": proj(*lead, d, kd.rkv + kd.rope),
               "kv_norm": jnp.ones(lead + (kd.rkv,), pd),
               "wkb_k": proj(*lead, kd.rkv, H, kd.nope),
               "wkb_v": proj(*lead, kd.rkv, H, kd.v),
               "wo": proj(*lead, H * kd.v, d)}
        if kd.gate:
            out["wg"] = proj(*lead, d, H)
        if not sliding and cfg.has_indexer:
            nh, hd = cfg.index_n_heads, cfg.index_head_dim
            out.update(wqi=proj(*lead, kd.rq, nh * hd), wki=proj(*lead, d, hd),
                       ki_norm=jnp.ones(lead + (hd,), pd),
                       ki_bias=jnp.zeros(lead + (hd,), pd),
                       ww=proj(*lead, d, nh))
        return out

    def moe_layer(P: int = P) -> Params:
        f, fs, held = (cfg.moe_intermediate_size, cfg.shared_intermediate_size,
                       cfg.held)
        return {"post_norm": jnp.ones((P, d), pd),
                "router": normal((P, d, cfg.router_experts)),
                "router_bias": jnp.zeros((P, cfg.router_experts), jnp.float32),
                "gate": proj(P, held, d, f), "up": proj(P, held, d, f),
                "down": proj(P, held, f, d),
                "shared_gate": proj(P, d, fs), "shared_up": proj(P, d, fs),
                "shared_down": proj(P, fs, d)}

    ffn = cfg.intermediate_size
    n = len(cfg.period)
    out = {"embed": {"embedding": proj(cfg.vocab_size, d)},
           "first": {"attn": mixer(False, ()), "post_norm": jnp.ones((d,), pd),
                     "mlp": {"gate": proj(d, ffn), "up": proj(d, ffn),
                             "down": proj(ffn, d)}},
           "periods": {"full": mixer(False, (P,)),
                       "win": [mixer(True, (P,)) for _ in range(n - 1)],
                       "moe": [moe_layer() for _ in range(n)]},
           "norm": jnp.ones((d,), pd), "lm_head": proj(d, cfg.vocab_size)}
    if cfg.drafts:
        out["mtp"] = {"enorm": jnp.ones((d,), pd), "hnorm": jnp.ones((d,), pd),
                      "eh_proj": proj(2 * d, d), "attn": mixer(False, ()),
                      "moe": moe_layer(1), "shared_head_norm": jnp.ones((d,), pd)}
    return out


# -- the mixer's projections ---------------------------------------------------

def _rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float, dtype,
          scaling: tuple | None = None):
    """Rotate-half rope on x [b, s, h, n] at `positions` [b, s]; `scaling`:
    YaRN's numbers as the configuration keeps them."""
    cos, sin = rope_cos_sin(positions, x.shape[-1], theta, dtype=dtype,
                            scaling=dict(scaling) if scaling else None)
    return apply_rope(x, x, cos, sin)[0]


def project(layer: Params, x: jnp.ndarray, positions: jnp.ndarray,
            kd: MixerDims, cfg: LatentMoEConfig) -> dict:
    """Everything a mixer takes from its input before attention. x: [b, s,
    d]; positions: [b, s] rope positions. Returns `hidden` (the normed
    input) [b, s, d], `cq` [b, s, rq], `q_nope` [b, s, H, nope], `q_rope`
    [b, s, H, rope] (roped) and `entry` [b, s, rkv + rope]: what the cache
    keeps of each token."""
    b, s, _ = x.shape
    dt = cfg.dtype
    w = lambda name: cast_weight(layer[name], dt)
    with jax.named_scope(trace.MLA_PROJ):
        hidden = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
        cq = rms_norm(hidden @ w("wqa"), layer["q_norm"], cfg.rms_norm_eps)
        cq = (cq * kd.rq_scale).astype(dt)
        q = (cq @ w("wqb")).reshape(b, s, kd.heads, kd.nope + kd.rope)
        q_rope = _rope(q[..., kd.nope:], positions, kd.theta, dt,
                       kd.rope_scaling)
        ckv = hidden @ w("wkva")
        c = rms_norm(ckv[..., :kd.rkv], layer["kv_norm"], cfg.rms_norm_eps)
        c = (c * kd.rkv_scale).astype(dt)
        k_rope = _rope(ckv[..., None, kd.rkv:], positions, kd.theta, dt,
                       kd.rope_scaling)[:, :, 0]
        entry = jnp.concatenate([c, k_rope], axis=-1)
    return {"hidden": hidden, "cq": cq, "q_nope": q[..., :kd.nope],
            "q_rope": q_rope, "entry": entry}


def absorb(layer: Params, q_nope: jnp.ndarray, q_rope: jnp.ndarray,
           cfg: LatentMoEConfig) -> jnp.ndarray:
    """The query as it meets an entry: [W_kb^K^T q^N; q^R], [b, s, H, rkv +
    rope]."""
    with jax.named_scope(trace.MLA_PROJ):
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope,
                           cast_weight(layer["wkb_k"], cfg.dtype))
        return jnp.concatenate([q_lat, q_rope], axis=-1)


def stored(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """x padded with zeros on its last axis to `width` (a store's row)."""
    pad = width - x.shape[-1]
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),)) if pad else x


def attend_entries(q_abs: jnp.ndarray, entries: jnp.ndarray,
                   mask: jnp.ndarray, kd: MixerDims) -> jnp.ndarray:
    """Absorbed attention of queries over entries. q_abs: [b, T, H, w];
    entries: [b, S, w] (every query of a row against the same S) or [b, T,
    S, w] (each query against its own); mask: [b, T, S] bool -> o' [b, T, H,
    rkv] in the entries' dtype: the softmax-weighted sum of the latents. The
    weighted sum runs over the whole entry (its rope columns are dropped
    after), so no copy of the latents' columns is made."""
    own = "bt" if entries.ndim == 4 else "b"
    q_abs = stored(q_abs, entries.shape[-1])
    scores = jnp.einsum(f"bthw,{own}sw->bths", q_abs, entries,
                        preferred_element_type=jnp.float32) * kd.softmax_scale
    scores = jnp.where(mask[:, :, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(entries.dtype)
    out = jnp.einsum(f"bths,{own}sw->bthw", probs, entries)
    return out[..., :kd.rkv]


def attend_chosen(q_abs: jnp.ndarray, entries: jnp.ndarray, ok: jnp.ndarray,
                  kd: MixerDims) -> jnp.ndarray:
    """`attend_entries` for queries that each gathered their OWN entries
    (entries: [b, T, K, w]; ok: [b, T, K], the places that hold a selected
    position), as one kernel: a query's entries come to VMEM once and its
    scores never leave it (`ops/sparse_latent_attention.py`)."""
    b, T, K, width = entries.shape
    out = sparse_latent_attention(
        stored(q_abs, width).reshape(b * T, -1, width),
        entries.reshape(b * T, K, width), ok.reshape(b * T, K),
        kd.softmax_scale)
    return out.reshape(b, T, -1, width)[..., :kd.rkv]


def unabsorb(layer: Params, o_lat: jnp.ndarray,
             cfg: LatentMoEConfig) -> jnp.ndarray:
    """o' [b, s, H, rkv] -> o [b, s, H, v]."""
    with jax.named_scope(trace.MLA_PROJ):
        return jnp.einsum("bshr,rhv->bshv", o_lat,
                          cast_weight(layer["wkb_v"], cfg.dtype))


def expand(layer: Params, entries: jnp.ndarray, kd: MixerDims,
           cfg: LatentMoEConfig):
    """The projected form's keys and values of entries [b, S, w]: k [b, S,
    H, nope + rope] (the roped part shared by the heads), v [b, S, H, v]."""
    b, S, _ = entries.shape
    with jax.named_scope(trace.MLA_PROJ):
        c = entries[..., :kd.rkv]
        k_nope = jnp.einsum("bsr,rhn->bshn", c,
                            cast_weight(layer["wkb_k"], cfg.dtype))
        k_rope = jnp.broadcast_to(
            entries[:, :, None, kd.rkv:kd.rkv + kd.rope],
            (b, S, kd.heads, kd.rope))
        v = jnp.einsum("bsr,rhv->bshv", c,
                       cast_weight(layer["wkb_v"], cfg.dtype))
        return jnp.concatenate([k_nope, k_rope], axis=-1), v


def attend_projected(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     mask: jnp.ndarray, kd: MixerDims) -> jnp.ndarray:
    """q: [b, T, H, hd]; k: [b, S, H, hd]; v: [b, S, H, v]; mask: [b, T, S]
    bool -> [b, T, H, v]."""
    scores = jnp.einsum("bthd,bshd->bhts", q, k,
                        preferred_element_type=jnp.float32) * kd.softmax_scale
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhts,bshv->bthv", probs, v)


def output(layer: Params, x: jnp.ndarray, hidden: jnp.ndarray, o: jnp.ndarray,
           cfg: LatentMoEConfig) -> jnp.ndarray:
    """The headwise gate where the layer has one (one number a head, from
    the layer's normed input), the output projection and the residual. o:
    [b, s, H, v] or [b, s, H v]."""
    b, s, _ = x.shape
    if "wg" in layer:
        with jax.named_scope(trace.ATTN_GATE):
            gate = jax.nn.sigmoid(hidden @ cast_weight(layer["wg"], cfg.dtype))
            o = gate[..., None] * o.reshape(b, s, gate.shape[-1], -1)
    with jax.named_scope(trace.SCOPE_ATTN_OUT):
        return x + o.reshape(b, s, -1) @ cast_weight(layer["wo"], cfg.dtype)


# -- the multi-token-prediction module -------------------------------------------

def mtp_project(mtp: Params, next_ids: jnp.ndarray, hidden: jnp.ndarray,
                params: Params, cfg: LatentMoEConfig) -> jnp.ndarray:
    """The module's input at positions whose NEXT token is known: u_i =
    [enorm(E[t_{i+1}]) | hnorm(h_i)] W_eh. next_ids: [b, s] (t_{i+1}; any id
    in range where the position is not valid); hidden: [b, s, d], the
    trunk's last layer's output at i, before its final norm; `params`: the
    trunk's tree, whose table the module shares -> [b, s, d]."""
    with jax.named_scope(trace.MTP_EMBED):
        e = jnp.take(cast_weight(params["embed"]["embedding"], cfg.dtype),
                     next_ids, axis=0)
    with jax.named_scope(trace.MTP_PROJ):
        both = jnp.concatenate(
            [rms_norm(e, mtp["enorm"], cfg.rms_norm_eps),
             rms_norm(hidden.astype(cfg.dtype), mtp["hnorm"],
                      cfg.rms_norm_eps)], axis=-1)
        return both @ cast_weight(mtp["eh_proj"], cfg.dtype)


# -- the indexer ---------------------------------------------------------------

def _rope_front(x: jnp.ndarray, positions: jnp.ndarray, n: int, theta: float,
                dtype) -> jnp.ndarray:
    return jnp.concatenate(
        [_rope(x[..., :n], positions, theta, dtype), x[..., n:]], axis=-1)


def index_project(layer: Params, hidden: jnp.ndarray, cq: jnp.ndarray,
                  positions: jnp.ndarray, cfg: LatentMoEConfig):
    """The indexer's side of a full layer: queries qI [b, s, Hi, di] from the
    query latent, ONE key kI [b, s, di] a token (what the index cache keeps:
    a LayerNorm of its projection), both roped on their first `rope`
    numbers, and the heads' weights w [b, s, Hi] float32, scaled by
    (Hi di)^-1/2."""
    b, s, _ = hidden.shape
    nh, hd, dt = cfg.index_n_heads, cfg.index_head_dim, cfg.dtype
    rope, theta = cfg.qk_rope_head_dim, cfg.rope_theta
    w = lambda name: cast_weight(layer[name], dt)
    with jax.named_scope(trace.INDEX_PROJ):
        qi = _rope_front((cq @ w("wqi")).reshape(b, s, nh, hd), positions,
                         rope, theta, dt)
        ki = (hidden @ w("wki")).astype(jnp.float32)
        mean = jnp.mean(ki, axis=-1, keepdims=True)
        var = jnp.mean((ki - mean) ** 2, axis=-1, keepdims=True)
        ki = ((ki - mean) * jax.lax.rsqrt(var + LN_EPS)
              * layer["ki_norm"].astype(jnp.float32)
              + layer["ki_bias"].astype(jnp.float32)).astype(dt)
        ki = _rope_front(ki[:, :, None, :], positions, rope, theta, dt)[:, :, 0]
        weights = (hidden @ w("ww")).astype(jnp.float32) * (
            nh ** -0.5 * hd ** -0.5)
    return qi, ki, weights


def index_scores(qi: jnp.ndarray, weights: jnp.ndarray,
                 keys: jnp.ndarray) -> jnp.ndarray:
    """I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) in float32. qi: [b, T,
    Hi, di]; weights: [b, T, Hi]; keys: [b, S, di] -> [b, T, S]."""
    with jax.named_scope(trace.INDEX_SCORE):
        dots = jnp.einsum("btjd,bsd->btjs", qi, keys,
                          preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(dots) * weights[..., None], axis=2)


def select(scores: jnp.ndarray, before: jnp.ndarray, own: jnp.ndarray,
           topk: int):
    """The selection S_t of every query, exact. scores: [..., S] float32;
    `before`: [..., S] bool, the valid positions strictly before the query;
    `own`: [..., S] bool, the query's own position (always selected: it takes
    one place). Returns (chosen [..., K] positions, ok [..., K] bool) with K
    = min(S, topk): the K largest, ties to the lower position (`lax.top_k`),
    `ok` false for the places no visible position fills. Where S <= topk
    every position is its own place and no sort runs: the same set."""
    S = scores.shape[-1]
    with jax.named_scope(trace.INDEX_TOPK):
        ranked = jnp.where(before, scores, -jnp.inf)
        ranked = jnp.where(own, jnp.inf, ranked)
        if S <= topk:
            chosen = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                      ranked.shape)
            return chosen, ranked > -jnp.inf
        values, chosen = jax.lax.top_k(ranked, topk)
        return chosen.astype(jnp.int32), values > -jnp.inf


def index_counts(before: jnp.ndarray, own: jnp.ndarray, ok: jnp.ndarray,
                 rows: jnp.ndarray) -> jnp.ndarray:
    """int32[2]: positions visible to, and selected by, the queries of
    `rows` ([..] bool over the leading axes of the [.., S] / [.., K] masks):
    what `index_visible` and `index_selected` sum."""
    return jnp.stack([jnp.sum((before | own) & rows[..., None]),
                      jnp.sum(ok & rows[..., None])]).astype(jnp.int32)


# -- a full layer over a span of queries ----------------------------------------

def _block_size(n: int, target: int) -> int:
    """The largest divisor of `n` that is <= target."""
    return next(b for b in range(min(n, target), 0, -1) if n % b == 0)


def full_span(layer: Params, x: jnp.ndarray, q_valid: jnp.ndarray,
              q_index: jnp.ndarray, pr: dict,
              entries: jnp.ndarray, index_keys: jnp.ndarray,
              key_valid: jnp.ndarray, cfg: LatentMoEConfig,
              absorbed: bool = True):
    """A full layer's mixer for T queries of each row against S cached
    tokens of the same row, the queries' own among them. x: [b, T, d];
    `q_index`: [b, T] the queries' places among the S; `pr`: `project`'s
    result for x; entries: [b, S, w]; index_keys: [b, S, di]; key_valid:
    [b, S] bool. Queries run in blocks of QUERY_BLOCK so that neither the
    index scores [T, Hi, S] nor the gathered entries [T, topk, w] exist
    whole. Returns (x + y, int32[2]: positions visible to the valid queries
    and positions they selected, summed, and the last query's selection
    (chosen [b, K], ok [b, K]))."""
    b, T, _ = x.shape
    S = entries.shape[1]
    kd = cfg.kind(False)
    qi, _, weights = pr["index"]
    if absorbed:
        q_in = absorb(layer, pr["q_nope"], pr["q_rope"], cfg)
    else:
        k_all, v_all = expand(layer, entries, kd, cfg)
        q_in = jnp.concatenate([pr["q_nope"], pr["q_rope"]], axis=-1)
    block = _block_size(T, QUERY_BLOCK)
    places = jnp.arange(S, dtype=jnp.int32)

    def one_block(args):
        qi_b, w_b, q_b, at_b, ok_b = args             # [b, B, ...]
        scores = index_scores(qi_b, w_b, index_keys)            # [b, B, S]
        before = key_valid[:, None, :] & (places < at_b[..., None])
        own = places == at_b[..., None]
        chosen, ok = select(scores, before, own, cfg.index_topk)
        if not absorbed:
            mask = jnp.zeros((b, block, S), bool).at[
                jnp.arange(b)[:, None, None], jnp.arange(block)[None, :, None],
                chosen].max(ok)
            with jax.named_scope(trace.SPARSE_ATTN):
                o = attend_projected(q_b, k_all, v_all, mask, kd)
        else:
            if S <= cfg.index_topk:                 # every place: no gather
                with jax.named_scope(trace.SPARSE_ATTN):
                    o = attend_entries(q_b, entries, ok, kd)    # [b, B, H, rkv]
            else:
                with jax.named_scope(trace.LATENT_GATHER):
                    # `chosen` comes from `top_k` over these S places: in
                    # bounds, so no pass over the gathered entries to fill
                    # what is not
                    picked = jnp.take_along_axis(
                        entries[:, None], chosen[..., None], axis=2,
                        mode="promise_in_bounds")
                with jax.named_scope(trace.SPARSE_ATTN):
                    o = attend_chosen(q_b, picked, ok, kd)
        return o, index_counts(before, own, ok, ok_b), chosen, ok

    split = lambda a: jnp.moveaxis(
        a.reshape(b, T // block, block, *a.shape[2:]), 1, 0)
    o, counted, chosen, ok = jax.lax.map(one_block, tuple(
        split(a) for a in (qi, weights, q_in, q_index, q_valid)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, T, *o.shape[3:])
    if absorbed:
        o = unabsorb(layer, o, cfg)
    last = (chosen[-1, :, -1], ok[-1, :, -1])
    return (output(layer, x, pr["hidden"], o, cfg), jnp.sum(counted, axis=0),
            last)


# -- a full layer without an indexer over a span of queries ----------------------

def visible_count(key_valid: jnp.ndarray, q_place: jnp.ndarray,
                  q_valid: jnp.ndarray) -> jnp.ndarray:
    """int32[1]: the valid places at or before each valid query's own,
    summed: what `latent_visible` sums for one layer. key_valid: [b, S]
    bool; q_place: [b, T] the queries' places among the S; q_valid: [b, T]."""
    upto = jnp.cumsum(key_valid.astype(jnp.int32), axis=1)
    seen = jnp.take_along_axis(upto, q_place, axis=1)
    return jnp.sum(jnp.where(q_valid, seen, 0))[None].astype(jnp.int32)


def dense_span(layer: Params, x: jnp.ndarray, q_start: jnp.ndarray, pr: dict,
               entries: jnp.ndarray, key_valid: jnp.ndarray,
               cfg: LatentMoEConfig) -> jnp.ndarray:
    """A full layer's mixer, WITHOUT an indexer, for T consecutive queries
    of each row against S cached tokens of the same row, the queries' own
    among them: every query reads every valid place up to its own. x: [b, T,
    d]; `q_start`: int32 scalar, the place of the first query among the S;
    `pr`: `project`'s result for x; entries: [b, S, w]; key_valid: [b, S]
    bool. The projected form: each entry's keys and values are made once
    for all the queries, and the scores live in the kernel
    (`ops/latent_prefill_attention.py`). Returns x + y."""
    kd = cfg.kind(False)
    dt = cfg.dtype
    with jax.named_scope(trace.MLA_PROJ):
        c = entries[..., :kd.rkv]
        k_nope = jnp.einsum("bsr,rhn->bhsn", c, cast_weight(layer["wkb_k"], dt))
        v = jnp.einsum("bsr,rhv->bhsv", c, cast_weight(layer["wkb_v"], dt))
        k_rope = entries[..., kd.rkv:kd.rkv + kd.rope]
        q_nope = jnp.moveaxis(pr["q_nope"], 2, 1)
        q_rope = jnp.moveaxis(pr["q_rope"], 2, 1)
    with jax.named_scope(trace.LATENT_READ_PREFILL):
        o = latent_prefill_attention(q_nope, q_rope, k_nope, k_rope, v,
                                     key_valid, q_start, kd.softmax_scale)
    return output(layer, x, pr["hidden"], o, cfg)


# -- a sliding layer over a span of queries --------------------------------------

def window_span(layer: Params, x: jnp.ndarray, pr: dict,
                before: jnp.ndarray, before_valid: jnp.ndarray,
                q_valid: jnp.ndarray, cfg: LatentMoEConfig,
                absorbed: bool = False) -> jnp.ndarray:
    """A sliding layer's mixer for C consecutive queries of each row. The
    context is the window - 1 entries BEFORE the span (`before` [b, window
    - 1, w], oldest first, `before_valid` [b, window - 1]) and the span's
    own (`pr["entry"]`, valid where `q_valid`); query i sees context places
    [i, i + window - 1], its own the last of them. Queries run in blocks of
    WINDOW_BLOCK, each against its own stretch of the context. The
    projected form by default (a prefill makes each key once for many
    queries); `absorbed=True` is the tick's arithmetic, for the tests."""
    b, C, _ = x.shape
    kd = cfg.kind(True)
    prev = cfg.sliding_window_size - 1
    ctx = jnp.concatenate(
        [before.astype(pr["entry"].dtype),
         stored(pr["entry"], before.shape[-1])], axis=1)        # [b, prev + C, w]
    ctx_valid = jnp.concatenate([before_valid, q_valid], axis=1)
    block = _block_size(C, WINDOW_BLOCK)
    span = block + prev
    i_loc = jnp.arange(block)[:, None]
    k_loc = jnp.arange(span)[None, :]
    band = (k_loc >= i_loc) & (k_loc <= i_loc + prev)           # [B, span]
    if absorbed:
        q_all = absorb(layer, pr["q_nope"], pr["q_rope"], cfg)
    else:
        q_all = jnp.concatenate([pr["q_nope"], pr["q_rope"]], axis=-1)
        k_all, v_all = expand(layer, ctx, kd, cfg)

    def one_block(args):
        q_b, start = args                                       # [b, B, H, *]
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, span, axis=1)
        mask = band[None] & cut(ctx_valid)[:, None, :]
        with jax.named_scope(trace.WINDOW_ATTN):
            if absorbed:
                return attend_entries(q_b, cut(ctx), mask, kd)
            return attend_projected(q_b, cut(k_all), cut(v_all), mask, kd)

    n = C // block
    q_blocks = jnp.moveaxis(q_all.reshape(b, n, block, *q_all.shape[2:]), 1, 0)
    o = jax.lax.map(one_block, (q_blocks, jnp.arange(n) * block))
    o = jnp.moveaxis(o, 0, 1).reshape(b, C, *o.shape[3:])
    if absorbed:
        o = unabsorb(layer, o, cfg)
    return output(layer, x, pr["hidden"], o, cfg)


def ring_mask(newest: jnp.ndarray, row_valid: jnp.ndarray,
              cfg: LatentMoEConfig):
    """What a query at logical position `newest` [b] sees of its slot's
    ring, once its own entry is in it. Place r of a ring of R holds the
    newest position p <= `newest` with p % R == r; it is visible when p lies
    in the window and `row_valid` [b, max_len] (the slot's mask row) says p
    is a token. Returns bool [b, R]."""
    R = cfg.ring_len
    r = jnp.arange(R, dtype=jnp.int32)[None, :]
    held = newest[:, None] - (newest[:, None] - r) % R          # [b, R]
    inside = (held >= 0) & (held > newest[:, None] - cfg.sliding_window_size)
    valid = jnp.take_along_axis(row_valid, jnp.clip(held, 0, None), axis=1) > 0
    return inside & valid


# -- the layers in order -----------------------------------------------------------

def walk(params: Params, x: jnp.ndarray, valid: jnp.ndarray, stores: dict,
         cfg: LatentMoEConfig, full_layer, window_layer, mlp_scope: str):
    """Run every layer with the stores in the carry. `full_layer(layer, h,
    stores, depth) -> (h, stores, counted, selection)` (depth: the layer's
    place in the latent and index pages; `counted`: the full layers' own
    counters of `decode.counters(cfg)`, int32[2] or int32[1]) and
    `window_layer(layer, h, stores, index) -> (h, stores)` (index: its place
    in the ring store) are the caller's mixers; a full layer's `selection`
    is (chosen, ok) of each row's last query under an indexer, () without.
    Layer 0 is followed by the dense feed-forward, every other layer by its
    expert half, which takes the routed experts of every period whole and
    the period's place among them. Returns the hidden state, the stores, the
    counters summed over layers (the trunk's part of `decode.counters(cfg)`)
    and the selections stacked over the full layers."""
    n = len(cfg.period)
    periods, experts = hybrid.split_experts(params["periods"])

    h, stores, indexed, first_sel = full_layer(params["first"]["attn"], x,
                                               stores, 0)
    h = llama.mlp_block(params["first"], h, cfg, scope=mlp_scope)

    def body(carry, xs):
        h, stores, routed, indexed = carry
        period, p = xs
        h, stores, counted, sel = full_layer(period["full"], h, stores, 1 + p)
        indexed = indexed + counted
        for j in range(n):
            if j:
                h, stores = window_layer(period["win"][j - 1], h, stores,
                                         p * (n - 1) + j - 1)
            h, counted = hybrid.moe_block(period["moe"][j], experts[j], p, h,
                                          valid, cfg)
            routed = routed + counted
        return (h, stores, routed, indexed), sel

    zero = jnp.zeros((len(hybrid.COUNTERS),), jnp.int32)
    (h, stores, routed, indexed), sels = jax.lax.scan(
        body, (h, stores, zero, indexed),
        (periods, jnp.arange(cfg.periods)))
    selection = jax.tree.map(lambda a, rest: jnp.concatenate([a[None], rest]),
                             first_sel, sels)
    return h, stores, jnp.concatenate([routed, indexed]), selection


def kept_positions(C: int, cfg: LatentMoEConfig) -> int:
    """How many of a span's last positions go into the ring: all that a
    later query can still see, and no place twice."""
    return min(C, cfg.ring_len)


def param_count(cfg: LatentMoEConfig) -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))
