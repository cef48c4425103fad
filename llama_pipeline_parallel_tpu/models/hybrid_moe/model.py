"""Layers of the hybrid block (config.py): the KDA mixer in its chunked and
its one-step form, the gated softmax mixer's projections, and the expert
layer that is told which experts it holds.

Parameter tree (`init_params`; leaves stacked by period so that the serving
programs scan over periods with a period's layers unrolled; the layers of a
period are separate leaves):

    embed.embedding [V, d]   norm [d]   lm_head [d, V]
    periods.attn.*    [P, ...]     the softmax layer of each period
    periods.kda[j].*  [P, ...]     its j-th KDA layer (period - 1 of them)
    periods.moe[j].*  [P, ...]     the expert half of its j-th layer

The grouped product (`ops/grouped_matmul.py`) is a kernel of its own that takes
whole buffers: handed a slice of a stacked leaf, XLA:TPU first copies the
slice out, a layer's experts read and written for every product. So no
slice of the routed experts' `gate` / `up` / `down` is ever taken, within a
period (separate leaves) or across periods: the layer loops keep those three
leaves of every `periods.moe[j]` out of the scan's `xs` (`split_experts`)
and close over them whole, `[P, held, d, f]`; `moe_block` gets the stack and
the period's place in it and gives the other periods' experts groups of no
rows. Everything else of a period (norms, router, bias, the shared expert,
the mixers) rides `xs`: a plain matmul reads its slice of a stack in place.

KDA (Kimi Delta Attention: a gated delta rule with a decay per channel), per
head, `c(.)` a causal depthwise convolution then SiLU:

    q_t = l2norm(c(W_q x_t)) / sqrt(d_k), k_t = l2norm(c(W_k x_t)), v_t = c(W_v x_t)
    a_t = exp(-exp(A_log) * softplus(W_a2 W_a1 x_t + dt_bias)),  b_t = 2 sigmoid(W_b x_t)
    S_t = (I - b_t k_t k_t^T) diag(a_t) S_{t-1} + b_t k_t v_t^T,  o_t = S_t^T q_t
    y_t = W_o [sigmoid(W_g2 W_g1 x_t) * rmsnorm_head(o_t)]

A position that is not valid (left padding, a slot that is not decoding)
contributes zero convolution input, b = 0 and a = 1: the state passes it
unchanged, so a left-padded prompt leaves exactly the state the unpadded
prompt would.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from llama_pipeline_parallel_tpu.models.hybrid_moe.config import HybridMoEConfig
from llama_pipeline_parallel_tpu.models.llama.model import (
    add_residual,
    cast_weight,
)
from llama_pipeline_parallel_tpu.ops.grouped_matmul import (
    group_metadata,
    grouped_matmul,
)
from llama_pipeline_parallel_tpu.ops.rmsnorm import rms_norm
from llama_pipeline_parallel_tpu.utils import trace

Params = dict
HIGHEST = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6
KDA_CHUNK = 64            # positions a chunk of the chunked form covers
KDA_SUB = 16              # sub-block inside which decays are taken pairwise
INIT_STD = 0.02
COUNTERS = ("routed_total", "routed_here", "experts_hit", "expert_load_max",
            "experts_held", "expert_visits")
# the grouped product's operands HERE: three matrices an expert at the
# model's width, `silu(gate) * up`, then `down`. What an expert is (how many
# matrices, its activation, the width it reads) is this family's; the router,
# the sort by held expert and the combine (`route`, `dispatch_rows`,
# `combine_rows`) take what the configuration states (`router_experts`,
# `num_experts_per_tok`, `expert_offset`, `held`, the scaling) and serve the
# state-space family's latent experts too (models/ssm_moe/model.py)
EXPERT_LEAVES = ("gate", "up", "down")


# -- parameters ---------------------------------------------------------------

def init_params(rng: jax.Array, cfg: HybridMoEConfig) -> Params:
    """Seeded parameters in the tree above: normal(0, 0.02) projections
    (the second factor of the decay's and the gate's low-rank pair normal(0,
    rank^-1/2)), unit norm scales, `A_log = log U(1, 16)`, `dt_bias` the
    inverse softplus of logU(1e-3, 1e-1), convolution taps normal(0, 0.3);
    router, its bias, `A_log` and `dt_bias` float32."""
    d, P, n = cfg.hidden_size, cfg.periods, cfg.attn_period
    pd = cfg.param_dtype
    keys = iter(jax.random.split(rng, 32 * n))
    normal = lambda shape, std: jax.random.normal(next(keys), shape,
                                                  jnp.float32) * std
    proj = lambda *shape: normal(shape, INIT_STD).astype(pd)
    q_w = cfg.num_attention_heads * cfg.head_dim
    kv_w = cfg.num_key_value_heads * cfg.head_dim
    w, r = cfg.kda_width, cfg.kda_rank
    f, fs, held = (cfg.moe_intermediate_size, cfg.shared_intermediate_size,
                   cfg.held)

    def kda_layer():
        second = lambda: normal((P, r, w), r ** -0.5).astype(pd)
        taps = lambda: normal((P, cfg.kda_conv, w), 0.3).astype(pd)
        step = jnp.exp(jax.random.uniform(next(keys), (P, w), jnp.float32,
                                          math.log(1e-3), math.log(1e-1)))
        return {"input_norm": jnp.ones((P, d), pd),
                "wq": proj(P, d, w), "wk": proj(P, d, w), "wv": proj(P, d, w),
                "conv_q": taps(), "conv_k": taps(), "conv_v": taps(),
                "wa1": proj(P, d, r), "wa2": second(),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (P, cfg.kda_heads), jnp.float32, 1.0, 16.0)),
                "dt_bias": jnp.log(jnp.expm1(step)),
                "wb": proj(P, d, cfg.kda_heads),
                "wg1": proj(P, d, r), "wg2": second(),
                "o_norm": jnp.ones((P, cfg.kda_head_dim), pd),
                "wo": proj(P, w, d)}

    def moe_layer():
        return {"post_norm": jnp.ones((P, d), pd),
                "router": normal((P, d, cfg.router_experts), INIT_STD),
                "router_bias": jnp.zeros((P, cfg.router_experts), jnp.float32),
                "gate": proj(P, held, d, f), "up": proj(P, held, d, f),
                "down": proj(P, held, f, d),
                "shared_gate": proj(P, d, fs), "shared_up": proj(P, d, fs),
                "shared_down": proj(P, fs, d)}

    attn = {"input_norm": jnp.ones((P, d), pd), "wq": proj(P, d, q_w),
            "wk": proj(P, d, kv_w), "wv": proj(P, d, kv_w),
            "wg": proj(P, d, q_w), "wo": proj(P, q_w, d)}
    return {"embed": {"embedding": proj(cfg.vocab_size, d)},
            "periods": {"attn": attn,
                        "kda": [kda_layer() for _ in range(n - 1)],
                        "moe": [moe_layer() for _ in range(n)]},
            "norm": jnp.ones((d,), pd), "lm_head": proj(d, cfg.vocab_size)}


# -- KDA ---------------------------------------------------------------------

def l2norm(x: jnp.ndarray) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def conv_silu(x: jnp.ndarray, history: jnp.ndarray, taps: jnp.ndarray):
    """Causal depthwise convolution then SiLU, in float32. x: [b, s, c] new
    inputs; history: [b, width - 1, c] the inputs before them; taps:
    [width, c], the last row meeting the newest input. Returns the output
    [b, s, c] and the new history (the last width - 1 inputs)."""
    width, s = taps.shape[0], x.shape[1]
    full = jnp.concatenate([history.astype(jnp.float32),
                            x.astype(jnp.float32)], axis=1)
    taps = taps.astype(jnp.float32)
    y = sum(full[:, j:j + s] * taps[j] for j in range(width))
    return jax.nn.silu(y), full[:, s:]


def kda_project(layer: Params, x: jnp.ndarray, valid: jnp.ndarray,
                conv_history: jnp.ndarray, cfg: HybridMoEConfig) -> dict:
    """Everything a KDA layer takes from its input before the recurrence. x:
    [b, s, d]; valid: [b, s] bool; conv_history: [b, width - 1, 3 * w] (q, k,
    v inputs side by side). Returns float32 q, k, v [b, s, H, dk], the log
    decay `g` [b, s, H, dk] (<= 0), `beta` [b, s, H], the output gate
    [b, s, w] and the new convolution history."""
    b, s, _ = x.shape
    H, dk, dt = cfg.kda_heads, cfg.kda_head_dim, cfg.dtype
    w = lambda name: cast_weight(layer[name], dt)
    with jax.named_scope(trace.KDA_PROJ):
        hidden = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
        keep = valid[..., None]
        qkv_in = jnp.concatenate(
            [hidden @ w("wq"), hidden @ w("wk"), hidden @ w("wv")], axis=-1)
        qkv_in = jnp.where(keep, qkv_in, 0)
        taps = jnp.concatenate(
            [layer["conv_q"], layer["conv_k"], layer["conv_v"]], axis=-1)
        qkv, history = conv_silu(qkv_in, conv_history, taps)
        q, k, v = (part.reshape(b, s, H, dk)
                   for part in jnp.split(qkv, 3, axis=-1))
        q, k = l2norm(q) * dk ** -0.5, l2norm(k)
        step = jax.nn.softplus(
            ((hidden @ w("wa1")) @ w("wa2")).astype(jnp.float32)
            + layer["dt_bias"])
        g = -jnp.exp(layer["A_log"])[:, None] * step.reshape(b, s, H, dk)
        g = jnp.where(keep[..., None], g, 0.0)
        beta = (2.0 if cfg.kda_neg_eigval else 1.0) * jax.nn.sigmoid(
            (hidden @ w("wb")).astype(jnp.float32))
        beta = jnp.where(keep, beta, 0.0)
        gate = jax.nn.sigmoid(
            ((hidden @ w("wg1")) @ w("wg2")).astype(jnp.float32))
    return {"q": q, "k": k, "v": v, "g": g, "beta": beta, "gate": gate,
            "conv": history.astype(conv_history.dtype)}


def kda_output(layer: Params, x: jnp.ndarray, o: jnp.ndarray,
               gate: jnp.ndarray, cfg: HybridMoEConfig) -> jnp.ndarray:
    """Per-head norm, output gate, output projection and the residual. o:
    [b, s, H, dv] float32."""
    b, s, _ = x.shape
    with jax.named_scope(trace.KDA_PROJ):
        o = rms_norm(o, layer["o_norm"], cfg.rms_norm_eps)
        o = (gate * o.reshape(b, s, -1)).astype(cfg.dtype)
        return x + o @ cast_weight(layer["wo"], cfg.dtype)


def kda_step(q, k, v, g, beta, state):
    """The recurrence for ONE position of every row. q, k, v, g: [b, H, dk];
    beta: [b, H]; state: [b, H, dk, dv] float32. Returns (o [b, H, dv], new
    state). The state is read in one pass for both products it enters:
    o = q^T S_t = (q a)^T S + (q . k) u with u = b (v - (k a)^T S)."""
    with jax.named_scope(trace.KDA_STEP):
        a = jnp.exp(g)
        both = jnp.einsum("bhck,bhkv->bhcv", jnp.stack([k * a, q * a], axis=2),
                          state, precision=HIGHEST)
        u = beta[..., None] * (v - both[:, :, 0])
        state = a[..., None] * state + k[..., None] * u[:, :, None, :]
        o = both[:, :, 1] + jnp.sum(q * k, axis=-1, keepdims=True) * u
        return o, state


def _decayed_products(x, k, gc, sub: int):
    """M[t, i] = sum_c x_t[c] k_i[c] exp(gc_t[c] - gc_i[c]) for i <= t, 0
    above the diagonal. x, k, gc: [..., C, dk]; gc is the running sum of the
    log decays inside the chunk, so every exponent taken is <= 0: inside a
    sub-block of `sub` positions the decays are taken pairwise; a row block
    meets the columns before it through the running sum at its own start
    (x_t exp(gc_t - r) against k_i exp(r - gc_i), both <= 1)."""
    *lead, C, dk = x.shape
    nb = C // sub
    blocks = lambda a: a.reshape(*lead, nb, sub, dk)
    xb, kb, gb = blocks(x), blocks(k), blocks(gc)
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    diff = gb[..., :, None, :] - gb[..., None, :, :]       # [.., nb, t, i, dk]
    pair = jnp.exp(jnp.where(lower[..., None], diff, -jnp.inf))
    diag = jnp.sum(xb[..., :, None, :] * kb[..., None, :, :] * pair, axis=-1)
    out = jnp.einsum("...jti,jl->...jtli", diag, jnp.eye(nb, dtype=x.dtype))
    out = out.reshape(*lead, C, C)
    if nb > 1:
        # r[j]: the running sum at the last position before row block j
        ref = jnp.concatenate([jnp.zeros_like(gb[..., :1, 0, :]),
                               gb[..., :-1, -1, :]], axis=-2)   # [.., nb, dk]
        x_plus = xb * jnp.exp(gb - ref[..., None, :])
        before = (jnp.arange(C)[None, :] < (jnp.arange(nb) * sub)[:, None])
        k_minus = k[..., None, :, :] * jnp.exp(
            jnp.minimum(ref[..., None, :] - gc[..., None, :, :], 0.0))
        k_minus = jnp.where(before[..., None], k_minus, 0.0)  # [.., nb, C, dk]
        off = jnp.einsum("...jtc,...jic->...jti", x_plus, k_minus,
                         precision=HIGHEST)
        out = out + off.reshape(*lead, C, C)
    return out


def kda_chunked(q, k, v, g, beta, state, chunk: int = KDA_CHUNK):
    """The same recurrence over a whole sequence in its chunked (WY / UT
    transform) form. q, k, v, g: [b, s, H, dk] float32; beta: [b, s, H];
    state: [b, H, dk, dv] float32, the state before position 0. Returns
    (o [b, s, H, dv], the state after the last position).

    Inside a chunk, with G the running product of the decays and U the
    pseudo-values u_t = b_t (v_t - S_{t-1}^T (a_t k_t)):
        (I + A) U = diag(b) (V - (K G) S_0),  A[t, i] = b_t M_k[t, i], i < t
        O = (Q G) S_0 + tril(M_q) U
        S_C = diag(G_C) S_0 + (K G_C / G)^T U
    with M_x the decayed products above. (I + A) is unit lower triangular;
    it is solved, not inverted by a series. A sequence that is not a whole
    number of chunks is padded on the LEFT with positions that leave the
    state alone (b = 0, a = 1)."""
    b, s, H, dk = q.shape
    dv = v.shape[-1]
    pad = -s % chunk
    if pad:
        padded = lambda a: jnp.pad(a, ((0, 0), (pad, 0)) + ((0, 0),) * (a.ndim - 2))
        q, k, v, g, beta = (padded(a) for a in (q, k, v, g, beta))
    n = (s + pad) // chunk
    sub = math.gcd(chunk, KDA_SUB)
    with jax.named_scope(trace.KDA_CHUNK):
        # [b, H, n, C, *]
        split = lambda a: jnp.moveaxis(
            a.reshape(b, n, chunk, H, -1), 3, 1)
        qc, kc, vc, gc = split(q), split(k), split(v), split(g)
        bc = split(beta[..., None])                          # [b, H, n, C, 1]
        gc = jnp.cumsum(gc, axis=-2)
        decay_in = jnp.exp(gc)                               # G_t
        decay_out = jnp.exp(gc[..., -1:, :] - gc)            # G_C / G_t
        m_k = _decayed_products(kc, kc, gc, sub)
        m_q = _decayed_products(qc, kc, gc, sub)
        strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
        system = jnp.where(strict, bc * m_k, 0.0) + jnp.eye(chunk, dtype=q.dtype)
        rhs = jnp.concatenate([bc * vc, bc * kc * decay_in], axis=-1)
        solved = jax.scipy.linalg.solve_triangular(
            system, rhs, lower=True, unit_diagonal=True)
        u_free, w = solved[..., :dv], solved[..., dv:]       # T bV, T b(K G)
        q_in, k_out = qc * decay_in, kc * decay_out
        total = decay_in[..., -1, :]                         # G_C [b, H, n, dk]

        def one_chunk(S, xs):
            u_free, w, q_in, k_out, m_q, total = xs
            u = u_free - jnp.einsum("bhck,bhkv->bhcv", w, S, precision=HIGHEST)
            o = (jnp.einsum("bhck,bhkv->bhcv", q_in, S, precision=HIGHEST)
                 + jnp.einsum("bhti,bhiv->bhtv", m_q, u, precision=HIGHEST))
            S = total[..., None] * S + jnp.einsum(
                "bhck,bhcv->bhkv", k_out, u, precision=HIGHEST)
            return S, o

        by_chunk = lambda a: jnp.moveaxis(a, 2, 0)
        state, o = jax.lax.scan(one_chunk, state, tuple(
            by_chunk(a) for a in (u_free, w, q_in, k_out, m_q, total)))
        # [n, b, H, C, dv] -> [b, s, H, dv]
        o = jnp.moveaxis(o, 0, 2).reshape(b, H, n * chunk, -1)
        o = jnp.moveaxis(o, 1, 2)[:, pad:]
    return o, state


# -- the expert layer ---------------------------------------------------------

def route(moe: Params, hidden: jnp.ndarray, cfg: HybridMoEConfig):
    """[T, d] -> (chosen [T, k] expert ids, weights [T, k] float32). Sigmoid
    scores over every expert of the router, float32; the k largest of
    score + bias are chosen; a chosen expert's weight is its score over the
    sum of the chosen scores (`norm_topk_prob`), times the scaling factor."""
    with jax.named_scope(trace.MOE_ROUTER):
        scores = jax.nn.sigmoid(jnp.matmul(
            hidden.astype(jnp.float32), moe["router"].astype(jnp.float32),
            precision=HIGHEST))
        _, chosen = jax.lax.top_k(scores + moe["router_bias"],
                                  cfg.num_experts_per_tok)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if cfg.norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return chosen, weights * cfg.routed_scaling_factor


def split_experts(periods: Params) -> tuple[Params, list]:
    """`params["periods"]` as the layer loops take it: (what rides the
    scan's `xs`: every leaf but the routed experts'; the routed experts'
    `gate` / `up` / `down` of each `moe[j]`, stacked over periods, for the
    loop's body to close over whole)."""
    experts = [{name: moe[name] for name in EXPERT_LEAVES}
               for moe in periods["moe"]]
    rest = [{name: leaf for name, leaf in moe.items()
             if name not in EXPERT_LEAVES} for moe in periods["moe"]]
    return {**periods, "moe": rest}, experts


def dispatch_rows(chosen: jnp.ndarray, ok: jnp.ndarray, hidden: jnp.ndarray,
                  cfg, stack: int, place):
    """The rows the held experts multiply, sorted by expert. chosen: [T, k]
    expert ids of the whole router; ok: [T, 1] bool, rows that are routed at
    all; hidden: [T, w] what an expert reads of a token. Returns (`here`
    [T, k] bool: the assignments that land on [expert_offset, expert_offset +
    held); `order` [T * k]: the assignments sorted by held expert, the ones
    that land nowhere last; their `sorted_group` (held: nowhere); `sizes`
    [held] rows an expert; `stack_sizes` [stack]: `sizes` at [place * held,
    (place + 1) * held) of a stack whose other experts get no rows; `taken`
    [T * k, w] the sorted rows' inputs)."""
    T, k = chosen.shape
    held = cfg.held
    with jax.named_scope(trace.MOE_DISPATCH):
        local = chosen - cfg.expert_offset
        here = (local >= 0) & (local < held) & ok            # [T, k]
        group = jnp.where(here, local, held).reshape(T * k)
        order = jnp.argsort(group, stable=True)
        sorted_group = group[order]
        rows = order // k                                    # token of a row
        sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
        # the other periods' experts get no rows: the sorted rows meet the
        # experts at [place * held, (place + 1) * held) of the stack
        stack_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((stack,), jnp.int32), sizes, (place * held,))
        taken = hidden[rows]                                 # [T * k, w]
    return here, order, sorted_group, sizes, stack_sizes, taken


def combine_rows(out: jnp.ndarray, order: jnp.ndarray,
                 sorted_group: jnp.ndarray, weights: jnp.ndarray,
                 held: int) -> jnp.ndarray:
    """The sorted rows' products [T * k, w] back in token order and summed
    under the router's weights [T, k]: float32 [T, w]."""
    T, k = weights.shape
    with jax.named_scope(trace.MOE_COMBINE):
        # rows past the last group belong to no held expert: the product
        # never wrote them (they hold anything, NaN too), so what is there
        # is dropped, not scaled
        in_group = (sorted_group < held)[:, None]
        out = jnp.where(in_group, out, 0)
        back = jnp.zeros((T * k,), jnp.int32).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32))              # order^-1
        terms = out[back].reshape(T, k, -1).astype(jnp.float32)
        return jnp.sum(terms * weights[..., None], axis=1)


def moe_block(moe: Params, experts: Params, place, x: jnp.ndarray,
              valid: jnp.ndarray, cfg: HybridMoEConfig, shared: bool = True):
    """Post-norm expert half of a layer, with the residual: gated experts of
    three matrices at the model's width (`EXPERT_LEAVES`), on top of `route`,
    `dispatch_rows` and `combine_rows`, which any family's expert layer that
    is told what it holds can stand on. moe: the layer's
    own norm, router, bias and shared expert; experts: the routed experts'
    `gate` / `up` [P, held, d, f] and `down` [P, held, f, d] of EVERY period
    as they are stored, and `place` (int32 scalar, may be traced) this
    layer's period among the P: the grouped product takes the stack whole,
    seen as P * held experts of which only the `held` at `place` get rows
    (module docstring; a single layer is a stack of one at place 0). x:
    [b, s, d]; valid: [b, s] bool (positions that are not valid are routed
    nowhere and counted nowhere). Routes over all `router_experts`, computes
    the terms of the experts held here ([expert_offset, expert_offset +
    held)) for the tokens routed to them, and the shared expert; the absent
    experts' terms are left out. Dropless: the sorted rows are sized for
    every assignment landing here, and `grouped_matmul` multiplies each run
    of rows by its own expert, reading an expert once for each row tile it
    has a row in and no expert without one (`expert_visits`: those (row
    tile, expert) pairs of ONE of the three products, which share the
    metadata). The stack is multiplied in the dtype it is stored in, which
    has to be `cfg.dtype`: a conversion here would convert P layers' experts
    at every layer, so a tree stored otherwise is refused.
    Returns (x + y, counters int32[6] in the order of COUNTERS)."""
    b, s, d = x.shape
    T, k, held, dt = b * s, cfg.num_experts_per_tok, cfg.held, cfg.dtype
    for name in EXPERT_LEAVES:
        if experts[name].dtype != dt:
            raise ValueError(
                f"the routed experts' {name!r} is stored {experts[name].dtype} "
                f"and cfg.dtype is {jnp.dtype(dt)}: the grouped product takes "
                f"the stack of layers as stored; convert the tree once first")
    stack = experts["gate"].shape[0] * held   # experts the product sees
    hidden = rms_norm(x, moe["post_norm"], cfg.rms_norm_eps).reshape(T, d)
    chosen, weights = route(moe, hidden, cfg)
    ok = valid.reshape(T, 1)

    here, order, sorted_group, sizes, stack_sizes, taken = dispatch_rows(
        chosen, ok, hidden, cfg, stack, place)

    with jax.named_scope(trace.MOE_EXPERTS):
        meta = group_metadata(stack_sizes, T * k)     # one for the three
        grouped = lambda lhs, name: grouped_matmul(
            lhs, experts[name].reshape(stack, *experts[name].shape[2:]),
            meta)
        act = jax.nn.silu(grouped(taken, "gate")) * grouped(taken, "up")
        out = grouped(act, "down")                           # [T * k, d]

    y = combine_rows(out, order, sorted_group, weights, held)

    if shared:
        with jax.named_scope(trace.MOE_SHARED):
            w = lambda name: cast_weight(moe[name], dt)
            y = y + ((jax.nn.silu(hidden @ w("shared_gate"))
                      * (hidden @ w("shared_up"))) @ w("shared_down")
                     ).astype(jnp.float32)

    counters = jnp.stack([
        jnp.sum(ok) * k, jnp.sum(here), jnp.sum(sizes > 0), jnp.max(sizes),
        jnp.int32(held), meta.visits]).astype(jnp.int32)
    return x + y.reshape(b, s, d).astype(x.dtype), counters


# -- the gated softmax layer's projections ------------------------------------

def attn_project(layer: Params, x: jnp.ndarray, cfg: HybridMoEConfig):
    """Input norm and q/k/v projections, no rotary embedding. x: [b, s, d] ->
    hidden [b, s, d], q [b, s, h, hd], k/v [b, s, kv_h, hd]."""
    b, s, _ = x.shape
    hd, dt = cfg.head_dim, cfg.dtype
    with jax.named_scope(trace.SCOPE_ATTN_QKV):
        hidden = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
        q = (hidden @ cast_weight(layer["wq"], dt)).reshape(b, s, -1, hd)
        k = (hidden @ cast_weight(layer["wk"], dt)).reshape(b, s, -1, hd)
        v = (hidden @ cast_weight(layer["wv"], dt)).reshape(b, s, -1, hd)
    return hidden, q, k, v


def attn_output(layer: Params, x: jnp.ndarray, hidden: jnp.ndarray,
                attn_out: jnp.ndarray, cfg: HybridMoEConfig) -> jnp.ndarray:
    """Output gate (elementwise, from the layer's normed input), output
    projection and the residual."""
    b, s, _ = x.shape
    attn_out = attn_out.reshape(b, s, -1)
    if cfg.attn_gate:
        with jax.named_scope(trace.ATTN_GATE):
            attn_out = jax.nn.sigmoid(
                hidden @ cast_weight(layer["wg"], cfg.dtype)) * attn_out
    with jax.named_scope(trace.SCOPE_ATTN_OUT):
        return add_residual(x, attn_out @ cast_weight(layer["wo"], cfg.dtype),
                            cfg)
