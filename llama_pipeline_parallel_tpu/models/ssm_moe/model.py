"""Layers of the state-space / expert block (config.py): the Mamba-2 mixer
in its chunked form (its one-step form is `ops/ssm_state_step.py`, which
steps the decode tick's store in place), and the expert layer whose routed
experts work in a latent width. The dense gated feed-forward (`-`) is the
dense decoder's (`llama.model.mlp_block`), and so are the embedding, the
final norm and the head, under the configuration's multipliers (`embed`,
`logits`; `llama.model.add_residual`). The softmax layer's projections are the
hybrid block's (`hybrid_moe.model.attn_project` / `attn_output`, without a
gate), and so are the router, the sort by held expert and the combine
(`route`, `dispatch_rows`, `combine_rows`): the expert half here is its own
on top of them because what lies between differs in kind, not in a number:
an expert is TWO matrices with relu^2 between, it reads a projection of the
token and its weighted sum goes back up through one more matrix.

Parameter tree (`init_params`). A layer is one of three kinds in the order
`cfg.pattern` gives, which has no period in general, so the layers are a
list, one dict of the layer's own leaves each, and the serving programs
unroll it. Nothing is stacked, so nothing is ever sliced: the grouped
product (`ops/grouped_matmul.py`) takes a layer's routed experts as the
buffers they are stored in.

    embed.embedding [V, d]   norm [d]   lm_head [d, V] (absent where tied)
    layers[i], by kind:
      M  input_norm [d], in_proj [d, 2 HP + 2 GN + H], conv_w [width, HP + 2 GN],
         conv_b, dt_bias [H], A_log [H], D [H], gate_norm [HP], out_proj [HP, d]
      *  input_norm [d], wq, wk, wv, wo
      E  post_norm [d], router [d, R], router_bias [R], latent_in [d, l],
         up [held, l, f], down [held, f, l], latent_out [l, d],
         shared_up [d, fs], shared_down [fs, d]
      -  post_norm [d], mlp.gate [d, fd], mlp.up [d, fd], mlp.down [fd, d]

Every layer is `x <- x + m f(rmsnorm(x))`, `m` the residual multiplier. Mamba-2, per head h of H with P
channels, state `S [P, N]` float32, group `g = h // (H / G)`:

    [z | xBC | dt] = u W_in;  xBC <- silu(conv1d_causal_depthwise(xBC) + bias)
    x [H, P], B [G, N], C [G, N] = split(xBC)
    dt = softplus(dt + dt_bias) [H],  A = -exp(A_log) [H]
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,   y_t = S_t C_t + D x_t
    out = rmsnorm_grouped(y * silu(z); G groups) W_out

A position that is not valid (left padding, a slot that is not decoding) has
a zero convolution input and dt = 0: the state passes it unchanged, so a
left-padded prompt leaves exactly the state the unpadded prompt would.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from llama_pipeline_parallel_tpu.models.hybrid_moe import model as hybrid
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.model import (
    add_residual,
    cast_weight,
)
from llama_pipeline_parallel_tpu.models.ssm_moe.config import SsmMoEConfig
from llama_pipeline_parallel_tpu.ops.grouped_matmul import (
    group_metadata,
    grouped_matmul,
)
from llama_pipeline_parallel_tpu.ops.rmsnorm import rms_norm
from llama_pipeline_parallel_tpu.utils import trace

Params = dict
HIGHEST = jax.lax.Precision.HIGHEST
INIT_STD = 0.02
CONV_STD, CONV_BIAS_STD = 0.3, 0.1
# the expert layers' six, then the family's own, each summed over layers:
# rows the Mamba-2 layers advanced one step, positions they scanned, entries
# the softmax layers' queries read, and what a chunk carried in of its
# slot's row (rows, bytes)
COUNTERS = hybrid.COUNTERS + ("ssm_rows", "ssm_positions", "kv_entries_read",
                              "state_carries", "state_bytes_carried")
EXPERT_LEAVES = ("up", "down")           # the grouped product's operands


# -- parameters ---------------------------------------------------------------

def init_params(rng: jax.Array, cfg: SsmMoEConfig) -> Params:
    """Seeded parameters in the tree above: normal(0, 0.02) projections,
    unit norm scales, convolution taps normal(0, 0.3) and bias normal(0,
    0.1), `A_log = log U(1, 16)`, `dt_bias` the inverse softplus of
    logU(1e-3, 1e-1), `D` ones, router bias zero; router, its bias, `A_log`,
    `D` and `dt_bias` float32."""
    d, pd = cfg.hidden_size, cfg.param_dtype
    keys = iter(jax.random.split(rng, 12 * cfg.num_hidden_layers + 2))
    normal = lambda shape, std: jax.random.normal(next(keys), shape,
                                                  jnp.float32) * std
    proj = lambda *shape: normal(shape, INIT_STD).astype(pd)
    ones = lambda n: jnp.ones((n,), pd)
    H, inner, cw = cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv_width
    q_w = cfg.num_attention_heads * cfg.head_dim
    kv_w = cfg.num_key_value_heads * cfg.head_dim
    lat, f, fs = (cfg.moe_latent_size, cfg.moe_intermediate_size,
                  cfg.shared_intermediate_size)
    fd = cfg.dense_intermediate_size

    def ssm_layer():
        step = jnp.exp(jax.random.uniform(next(keys), (H,), jnp.float32,
                                          math.log(1e-3), math.log(1e-1)))
        return {"input_norm": ones(d), "in_proj": proj(d, inner + cw + H),
                "conv_w": normal((cfg.ssm_conv, cw), CONV_STD).astype(pd),
                "conv_b": normal((cw,), CONV_BIAS_STD).astype(pd),
                "dt_bias": jnp.log(jnp.expm1(step)),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (H,), jnp.float32, 1.0, 16.0)),
                "D": jnp.ones((H,), jnp.float32),
                "gate_norm": ones(inner), "out_proj": proj(inner, d)}

    def attn_layer():
        return {"input_norm": ones(d), "wq": proj(d, q_w), "wk": proj(d, kv_w),
                "wv": proj(d, kv_w), "wo": proj(q_w, d)}

    def expert_layer():
        return {"post_norm": ones(d),
                "router": normal((d, cfg.router_experts), INIT_STD),
                "router_bias": jnp.zeros((cfg.router_experts,), jnp.float32),
                "latent_in": proj(d, lat), "up": proj(cfg.held, lat, f),
                "down": proj(cfg.held, f, lat), "latent_out": proj(lat, d),
                "shared_up": proj(d, fs), "shared_down": proj(fs, d)}

    def dense_layer():
        return {"post_norm": ones(d),
                "mlp": {"gate": proj(d, fd), "up": proj(d, fd),
                        "down": proj(fd, d)}}

    make = {"M": ssm_layer, "*": attn_layer, "E": expert_layer,
            "-": dense_layer}
    params = {"embed": {"embedding": proj(cfg.vocab_size, d)},
              "layers": [make[kind]() for kind in cfg.pattern],
              "norm": ones(d)}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = proj(d, cfg.vocab_size)
    return params


# -- the ends: embedding and head under their multipliers ---------------------

def embed(params: Params, input_ids: jnp.ndarray,
          cfg: SsmMoEConfig) -> jnp.ndarray:
    """The embedded tokens times `embedding_multiplier` (1: the dense
    decoder's `embed` and nothing more)."""
    x = llama.embed(params, input_ids, cfg)
    if cfg.embedding_multiplier == 1.0:
        return x
    return (x.astype(jnp.float32) * cfg.embedding_multiplier).astype(x.dtype)


def logits(params: Params, x: jnp.ndarray, cfg: SsmMoEConfig) -> jnp.ndarray:
    """float32 logits of normed hidden states: the dense decoder's `lm_head`
    over the tree's own head, or over the embedding table where the head is
    tied to it (the product contracts the table's second axis: nothing is
    transposed in memory), divided by `logits_scaling`."""
    if cfg.tie_word_embeddings:
        params = {"lm_head": params["embed"]["embedding"].T}
    out = llama.lm_head(params, x, cfg)
    if cfg.logits_scaling == 1.0:
        return out
    return out * (1.0 / cfg.logits_scaling)


# -- the softmax layer: a stated scale, narrow heads packed a page row --------

def scaled_queries(q: jnp.ndarray, cfg: SsmMoEConfig) -> jnp.ndarray:
    """The queries for an attention that scales its scores by `head_dim **
    -0.5` itself (the chunk's kernel, which whole buckets run too): multiplied
    by what `attention_multiplier` is over that, so the scores come out at
    the stated scale (Granite's 1/64 over 1/8 is 1/8, exact in any dtype).
    No multiplier stated: the queries as they are."""
    if cfg.attention_multiplier is None:
        return q
    return q * jnp.asarray(cfg.attn_scale * cfg.head_dim ** 0.5, q.dtype)


def _own_part(cfg: SsmMoEConfig) -> np.ndarray:
    """[heads]: which of a packed row's `kv_pack` parts holds a query
    head's KV head (head j reads KV head j // g, part (j // g) % kv_pack of
    row (j // g) // kv_pack)."""
    g = cfg.num_attention_heads // cfg.num_key_value_heads
    return (np.arange(cfg.num_attention_heads) // g) % cfg.kv_pack


def packed_kv(a: jnp.ndarray, cfg: SsmMoEConfig) -> jnp.ndarray:
    """Keys or values [..., kv_h, hd] as a page keeps them: `kv_pack` heads
    side by side a row, [..., kv_h / kv_pack, kv_pack * hd] (a reshape: heads
    2p and 2p + 1 are neighbours)."""
    return a.reshape(*a.shape[:-2], cfg.kv_heads // cfg.kv_pack,
                     cfg.kv_pack * cfg.head_dim)


def packed_queries(q: jnp.ndarray, cfg: SsmMoEConfig) -> jnp.ndarray:
    """One-token queries [b, h, hd] against packed rows: [b, h, kv_pack *
    hd], a head's numbers in the part of the row its KV head lies in and
    zeros in the others, so that its product with a packed row is its
    product with its own KV head's key."""
    if cfg.kv_pack == 1:
        return q
    own = jnp.asarray(_own_part(cfg)[:, None] == np.arange(cfg.kv_pack),
                      q.dtype)                               # [h, pack]
    b, h, hd = q.shape
    return (q[:, :, None, :] * own[None, :, :, None]).reshape(b, h, -1)


def unpacked_heads(out: jnp.ndarray, cfg: SsmMoEConfig) -> jnp.ndarray:
    """A packed attention's output [b, h, kv_pack * hd] -> [b, h, hd]: the
    part that a head's own KV head's values fill (the other parts hold the
    head's weights over its row-mates' values, and are dropped)."""
    if cfg.kv_pack == 1:
        return out
    b, h, _ = out.shape
    parts = out.reshape(b, h, cfg.kv_pack, cfg.head_dim)
    return parts[:, np.arange(h), _own_part(cfg)]


# -- Mamba-2 ------------------------------------------------------------------

def conv_bias_silu(x: jnp.ndarray, history: jnp.ndarray, taps: jnp.ndarray,
                   bias: jnp.ndarray):
    """Causal depthwise convolution, its bias, then SiLU, in float32. x:
    [b, s, c] new inputs; history: [b, width - 1, c] the inputs before them;
    taps: [width, c], the last row meeting the newest input. Returns the
    output [b, s, c] and the new history (the last width - 1 inputs)."""
    with jax.named_scope(trace.SSM_CONV):
        width, s = taps.shape[0], x.shape[1]
        full = jnp.concatenate([history.astype(jnp.float32),
                                x.astype(jnp.float32)], axis=1)
        taps = taps.astype(jnp.float32)
        y = sum(full[:, j:j + s] * taps[j] for j in range(width))
        return jax.nn.silu(y + bias.astype(jnp.float32)), full[:, s:]


def ssm_project(layer: Params, x: jnp.ndarray, valid: jnp.ndarray,
                conv_history: jnp.ndarray, cfg: SsmMoEConfig) -> dict:
    """Everything a Mamba-2 layer takes from its input before the
    recurrence. x: [b, s, d]; valid: [b, s] bool; conv_history: [b, width -
    1, HP + 2 GN]. Returns the gate `z` [b, s, HP], float32 `x` [b, s, H, P],
    `B` / `C` [b, s, G, N], `dt` [b, s, H] (0 where not valid) and the new
    convolution history."""
    b, s, _ = x.shape
    H, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    inner = cfg.ssm_inner
    keep = valid[..., None]
    with jax.named_scope(trace.SSM_PROJ):
        hidden = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
        zxbcdt = hidden @ cast_weight(layer["in_proj"], cfg.dtype)
        z, xbc, dt = jnp.split(zxbcdt, [inner, inner + cfg.ssm_conv_width],
                               axis=-1)
        xbc = jnp.where(keep, xbc, 0)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + layer["dt_bias"])
        dt = jnp.where(keep, dt, 0.0)
    xbc, history = conv_bias_silu(xbc, conv_history, layer["conv_w"],
                                  layer["conv_b"])
    xs, B, C = jnp.split(xbc, [inner, inner + G * N], axis=-1)
    return {"z": z, "x": xs.reshape(b, s, H, P), "B": B.reshape(b, s, G, N),
            "C": C.reshape(b, s, G, N), "dt": dt,
            "conv": history.astype(conv_history.dtype)}


def ssm_output(layer: Params, x: jnp.ndarray, y: jnp.ndarray, xs: jnp.ndarray,
               z: jnp.ndarray, cfg: SsmMoEConfig) -> jnp.ndarray:
    """The skip `D x`, the gate (`y * silu(z)` BEFORE the norm), the norm
    over each of the G groups of HP / G channels, the output projection and
    the residual. y, xs: [b, s, H, P] float32; z: [b, s, HP]."""
    b, s, _ = x.shape
    G = cfg.ssm_groups
    with jax.named_scope(trace.SSM_NORM):
        y = y + layer["D"][:, None] * xs
        y = y.reshape(b, s, -1) * jax.nn.silu(z.astype(jnp.float32))
        y = y.reshape(b, s, G, -1)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + cfg.rms_norm_eps)
        y = (y.reshape(b, s, -1)
             * layer["gate_norm"].astype(jnp.float32)).astype(cfg.dtype)
    with jax.named_scope(trace.SSM_PROJ):
        return add_residual(x, y @ cast_weight(layer["out_proj"], cfg.dtype),
                            cfg)


def ssm_chunked(x, dt, A, B, C, state, chunk: int):
    """The recurrence over a whole sequence in its chunked (state-space dual)
    form. x: [b, s, H, P] float32; dt: [b, s, H]; A: [H] (< 0); B, C: [b, s,
    G, N]; state: [b, H, P, N] float32, the state before position 0. Returns
    (y [b, s, H, P] without the skip, the state after the last position). A
    head reads its group's B and C by shape: the state is seen as [b, G,
    H / G, P, N].

    With `a_t = dt_t A` and `cs` its running sum inside a chunk, a chunk
    that starts from S_0 gives
        y_t = exp(cs_t) C_t S_0 + sum_{i <= t} exp(cs_t - cs_i) (C_t . B_i) dt_i x_i
        S_C = exp(cs_C) S_0 + sum_i exp(cs_C - cs_i) dt_i x_i (x) B_i
    and the chunks' states follow one from the other in a short scan. Every
    exponent taken is <= 0. A sequence that is not a whole number of chunks
    is padded on the LEFT with positions that leave the state alone (dt =
    0)."""
    b, s, H, P = x.shape
    G, N = B.shape[2:]
    pad = -s % chunk
    if pad:
        padded = lambda a: jnp.pad(a, ((0, 0), (pad, 0)) + ((0, 0),) * (a.ndim - 2))
        x, dt, B, C = (padded(a) for a in (x, dt, B, C))
    n = (s + pad) // chunk
    with jax.named_scope(trace.SSM_SCAN):
        # [b, n, chunk, G, H / G, ...]: a head beside its group's B and C
        split = lambda a, *tail: a.reshape(b, n, chunk, G, *tail)
        xdt = split(x * dt[..., None], H // G, P)
        cs = jnp.cumsum(split(dt * A, H // G), axis=2)
        Bc, Cc = split(B, N), split(C, N)
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        diff = cs[:, :, :, None] - cs[:, :, None]          # [b, n, t, i, G, h]
        pair = jnp.exp(jnp.where(lower[:, :, None, None], diff, -jnp.inf))
        cb = jnp.einsum("bntgs,bnigs->bntig", Cc, Bc, precision=HIGHEST)
        y_in = jnp.einsum("bntigh,bnighp->bntghp", pair * cb[..., None], xdt,
                          precision=HIGHEST)
        total = cs[:, :, -1]                                # [b, n, G, h]
        grown = jnp.einsum(
            "bnighp,bnigs->bnghps",
            jnp.exp(total[:, :, None] - cs)[..., None] * xdt, Bc,
            precision=HIGHEST)

        def one_chunk(S, xs):
            grown, decay = xs
            return decay[..., None, None] * S + grown, S

        by_chunk = lambda a: jnp.moveaxis(a, 1, 0)
        state, before = jax.lax.scan(
            one_chunk, state.reshape(b, G, H // G, P, N),
            (by_chunk(grown), by_chunk(jnp.exp(total))))
        y_out = jnp.einsum("bntgs,nbghps->bntghp", Cc, before,
                           precision=HIGHEST) * jnp.exp(cs)[..., None]
        y = (y_in + y_out).reshape(b, n * chunk, H, P)[:, pad:]
    return y, state.reshape(b, H, P, N)


# -- the expert layer ---------------------------------------------------------

def relu2(h: jnp.ndarray) -> jnp.ndarray:
    """relu(h)^2, squared in float32 and rounded once to h's dtype."""
    return jnp.square(jax.nn.relu(h).astype(jnp.float32)).astype(h.dtype)


def latent_moe_block(layer: Params, x: jnp.ndarray, valid: jnp.ndarray,
                     cfg: SsmMoEConfig, shared: bool = True):
    """An expert layer, with its norm and the residual. x: [b, s, d]; valid:
    [b, s] bool (positions that are not valid are routed nowhere and counted
    nowhere). The router and the shared expert read the normed token at the
    model's width; the routed experts read its projection to
    `moe_latent_size` (`latent_in`), each is `relu(l U_e)^2 V_e`, and their
    weighted sum goes back up through `latent_out`, once a token. Routes over
    all `router_experts`, computes the terms of the experts held here
    ([expert_offset, expert_offset + held)) for the tokens routed to them,
    and the shared expert; the absent experts' terms are left out. Dropless,
    and multiplied in the dtype the experts are stored in, as the hybrid
    block's `moe_block` (whose router, sort and combine these are). Returns
    (x + y, counters int32[6] in the order of `hybrid.COUNTERS`)."""
    b, s, d = x.shape
    T, k, held, dt = b * s, cfg.num_experts_per_tok, cfg.held, cfg.dtype
    for name in EXPERT_LEAVES:
        if layer[name].dtype != dt:
            raise ValueError(
                f"the routed experts' {name!r} is stored {layer[name].dtype} "
                f"and cfg.dtype is {jnp.dtype(dt)}: the grouped product takes "
                f"the experts as stored; convert the tree once first")
    w = lambda name: cast_weight(layer[name], dt)
    hidden = rms_norm(x, layer["post_norm"], cfg.rms_norm_eps).reshape(T, d)
    chosen, weights = hybrid.route(layer, hidden, cfg)
    ok = valid.reshape(T, 1)
    with jax.named_scope(trace.MOE_LATENT_IN):
        latent = hidden @ w("latent_in")                     # [T, l]
    here, order, sorted_group, sizes, stack_sizes, taken = hybrid.dispatch_rows(
        chosen, ok, latent, cfg, held, 0)

    with jax.named_scope(trace.MOE_EXPERTS):
        meta = group_metadata(stack_sizes, T * k)      # one for the two
        act = relu2(grouped_matmul(taken, layer["up"], meta))
        out = grouped_matmul(act, layer["down"], meta)       # [T * k, l]

    y = hybrid.combine_rows(out, order, sorted_group, weights, held)
    with jax.named_scope(trace.MOE_LATENT_OUT):
        y = (y.astype(dt) @ w("latent_out")).astype(jnp.float32)
    if shared:
        with jax.named_scope(trace.MOE_SHARED):
            y = y + (relu2(hidden @ w("shared_up")) @ w("shared_down")
                     ).astype(jnp.float32)

    counters = jnp.stack([
        jnp.sum(ok) * k, jnp.sum(here), jnp.sum(sizes > 0), jnp.max(sizes),
        jnp.int32(held), meta.visits]).astype(jnp.int32)
    return add_residual(x, y.reshape(b, s, d).astype(x.dtype), cfg), counters
