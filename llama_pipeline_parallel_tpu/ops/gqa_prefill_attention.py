"""Pallas TPU attention of a span of queries over grouped keys and values
whose widths differ (models/window_moe/: keys of 192 beside values of 128, 16
or 8 query heads a KV head), in the two masks that family's layers have:

- `window_prefill_attention`: a WINDOW layer's prefill or chunk. Query t sees
  the `window` places that end at its own, and a learned logit a head, the
  SINK, stands in its softmax's denominator with no value behind it. Only
  the key tiles the band touches are visited: a block of queries meets
  `ceil((window - 1) / block_k) + block_q / block_k` key blocks whatever the
  row's length, where a causal kernel under a band mask would walk (and a
  masked XLA product compute) every earlier tile: 128 times the useful work
  at 32k places and a window of 128.
- `full_prefill_attention`: a FULL layer's prefill or chunk, causal over
  every earlier place of the slot's row, blocked over keys so that the
  `[heads, T, S]` scores never leave VMEM (what
  `ops/latent_prefill_attention.py` does for latents: 17 GB of float32
  scores for a 2048-token chunk at the end of a 32k row otherwise).
  `ops/flash_attention.py`, the training kernel, takes one width for keys
  and values and a query's place from its index; here the queries' places
  among the S keys start at `q_start`.

    s_h[t, s] = q_h,t . k_g(h),s / sqrt(dk)
    full:    o_h,t = sum_s softmax_s(s_h[t, s]) v_g(h),s          valid s <= place(t)
    window:  p = exp(s) / (exp(sink_h) + sum_s exp(s)),  place(t) - window < s <= place(t)

The g query heads of one KV head run in ONE program: their blocks are
stacked into `[g * block_q, dk]` rows against the group's one key tile, so a
key or value tile is fetched once for the g heads that read it, and the MXU
sees 1024 or 2048 rows a step where a program a head would give it 128.

Schedule: grid (batch, KV head, query block, key step), the key axis
innermost carrying the running max / sum / accumulator (float32) in VMEM
scratch (FlashAttention-2). Key step `ki` of a query block reads key block
`lo + ki`, `lo` the first block that holds a place the block's queries can
see (after the row's leading pads, and inside the band); steps past the
block of its last query clamp their index to it (no new fetch) and skip the
compute. A full layer's key axis ends with the block of the span's last
place, a bound of the grid read at run time: a chunk handed its slot's whole
row walks none of it past its own end. Tiles of a full layer in which every pair is visible skip the mask.
The sink is where the running state starts (max = the sink, sum = 1) where a
plain softmax starts from nothing. The output is written `[b, T, heads *
dv]`, the layout the output projection takes.

Numerics: keys and values as given, the query scaled in its own dtype before
the kernel (as `ops/paged_attention.py` scales it), float32 scores, softmax
statistics and accumulator; the exponentials are rounded to the values'
dtype for the value product, as `ops/attention.attention` rounds its
probabilities. A query that sees nothing (a pad) gets zeros.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llama_pipeline_parallel_tpu.ops.attention import NEG_INF
from llama_pipeline_parallel_tpu.ops.pallas_common import (
    compiler_params,
    interpret_mode,
)
from llama_pipeline_parallel_tpu.utils import trace

# (batch, KV head, query block) programs are independent; the key axis
# carries the softmax state
_COMPILER_PARAMS = compiler_params("parallel", "parallel", "parallel",
                                   "arbitrary")
BLOCK_Q = 128              # queries a head of a step; g heads are stacked
BLOCK_K = 512              # keys of a full layer's step
WINDOW_BLOCK_K = 128       # keys of a window layer's step


def _block(n: int, target: int) -> int:
    """The largest divisor of `n` that is <= target, in whole lanes where
    `n` has such a divisor (a block of keys is the last axis of the mask's
    block, which Mosaic takes in whole lanes or whole)."""
    divisors = [b for b in range(min(n, target), 0, -1) if n % b == 0]
    return next((b for b in divisors if b % 128 == 0), divisors[0])


def window_context(span: int, window: int) -> int:
    """Places `window_prefill_attention` takes in front of a span of `span`
    queries: the window's other `window - 1`, in whole key blocks."""
    bk = _block(_block(span, BLOCK_Q), WINDOW_BLOCK_K)
    return -(-(window - 1) // bk) * bk


def _needed(row, qi, q_start_ref, first_ref, bq: int, bk: int, window: int):
    """(first, last) key block a query block needs: from the block of the
    first place its queries can see (the row's first token; inside the band,
    the first query's oldest key) to the block of its own last place."""
    q_first = q_start_ref[0] + qi * bq
    lo = first_ref[row]
    if window:
        lo = jnp.maximum(lo, q_first - (window - 1))
    return lo // bk, (q_first + bq - 1) // bk


def _kernel(q_start_ref, first_ref, *refs, g: int, bq: int, bk: int,
            window: int, sink: bool):
    if sink:
        sink_ref, refs = refs[0], refs[1:]
    q_ref, k_ref, v_ref, valid_ref, o_ref, m_scr, l_scr, acc_scr = refs
    row, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    dv = v_ref.shape[-1]

    @pl.when(ki == 0)
    def _init():
        if sink:
            for j in range(g):
                m_scr[j * bq:(j + 1) * bq, :] = jnp.broadcast_to(
                    sink_ref[j:j + 1, :], (bq, m_scr.shape[1]))
            l_scr[:] = jnp.ones_like(l_scr)
        else:
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    lo, hi = _needed(row, qi, q_start_ref, first_ref, bq, bk, window)
    kb = lo + ki                                 # the key block of this step
    q_first = q_start_ref[0] + qi * bq

    def update(masked: bool):
        q = q_ref[...].reshape(g * bq, q_ref.shape[-1])
        s = jax.lax.dot_general(q, k_ref[...], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            q_place = q_first + jax.lax.rem(
                jax.lax.broadcasted_iota(jnp.int32, (g * bq, bk), 0), bq)
            k_place = kb * bk + jax.lax.broadcasted_iota(
                jnp.int32, (g * bq, bk), 1)
            ok = (k_place <= q_place) & (valid_ref[...] > 0)
            if window:
                ok = ok & (k_place > q_place - window)
            s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        correction = jnp.exp(m_prev - m_cur)
        w = jnp.exp(s - m_cur)
        if masked:
            # masked pairs contribute ZERO even while a query has seen
            # nothing (m_cur == NEG_INF would make exp(s - m_cur) = 1)
            w = jnp.where(ok, w, 0.0)
        l_scr[:] = jnp.broadcast_to(
            correction * l_scr[:, :1] + w.sum(axis=-1, keepdims=True),
            l_scr.shape)
        v = v_ref[...]
        acc_scr[:] = acc_scr[:] * correction + jax.lax.dot_general(
            w.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # [g bq, dv]
        m_scr[:] = jnp.broadcast_to(m_cur, m_scr.shape)

    run = kb <= hi
    if window:
        # the band's tiles all lie on a mask's edge
        pl.when(run)(lambda: update(True))
    else:
        # every pair of the tile is visible: the tile ends at or before the
        # block's first query, and starts at or after the row's first token
        whole = ((kb + 1) * bk - 1 <= q_first) & (kb * bk >= first_ref[row])
        pl.when(run & whole)(lambda: update(False))
        pl.when(run & jnp.logical_not(whole))(lambda: update(True))

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        l = l_scr[:, :1]
        out = jnp.where(l > 0.0, acc_scr[:] / jnp.where(l > 0.0, l, 1.0),
                        0.0).astype(o_ref.dtype)
        for j in range(g):                      # head j of the group
            o_ref[:, j * dv:(j + 1) * dv] = out[j * bq:(j + 1) * bq]


def _operands(q, k, v, key_valid, q_start, sink, bq: int, bk: int,
              window: int, steps: int):
    """The grid, the specs and the arguments the two calls share. q: [b, T,
    H, dk]; k: [b, S, G, dk]; v: [b, S, G, dv]; `steps`: key steps a query
    block (the key axis of the grid)."""
    b, T, H, dk = q.shape
    S, G, dv = k.shape[1], k.shape[2], v.shape[-1]
    g = H // G
    valid = key_valid.astype(jnp.int32)
    # the row's leading pads: the first valid place (S where there is none)
    first = jnp.where(jnp.any(valid > 0, axis=1),
                      jnp.argmax(valid > 0, axis=1), S).astype(jnp.int32)
    scaled = q * jnp.asarray(dk ** -0.5, q.dtype)
    by_group = jnp.moveaxis(scaled.reshape(b, T, G, g, dk), 1, 3)
    by_key_head = lambda a: jnp.moveaxis(a, 2, 1)           # [b, G, S, *]

    def key_block(row, qi, ki, q_start_ref, first_ref):
        lo, hi = _needed(row, qi, q_start_ref, first_ref, bq, bk, window)
        return jnp.minimum(lo + ki, hi)

    per_key = lambda width: pl.BlockSpec(
        (None, None, bk, width),
        lambda r, kv, qi, ki, *refs: (r, kv, key_block(r, qi, ki, *refs), 0))
    sink_in = [] if sink is None else [jnp.broadcast_to(
        sink.astype(jnp.float32).reshape(G, g, 1), (G, g, 128))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, G, T // bq, steps),
        in_specs=[
            *(pl.BlockSpec((None, g, 128), lambda r, kv, qi, ki, *_: (kv, 0, 0))
              for _ in sink_in),
            pl.BlockSpec((None, None, g, bq, dk),
                         lambda r, kv, qi, ki, *_: (r, kv, 0, qi, 0)),
            per_key(dk), per_key(dv),
            pl.BlockSpec((None, 1, bk), lambda r, kv, qi, ki, *refs: (
                r, 0, key_block(r, qi, ki, *refs))),
        ],
        out_specs=pl.BlockSpec((None, bq, g * dv),
                               lambda r, kv, qi, ki, *_: (r, qi, kv)),
        scratch_shapes=[
            pltpu.VMEM((g * bq, 128), jnp.float32),
            pltpu.VMEM((g * bq, 128), jnp.float32),
            pltpu.VMEM((g * bq, dv), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, g=g, bq=bq, bk=bk, window=window,
                               sink=sink is not None)
    args = (jnp.reshape(q_start, (1,)).astype(jnp.int32), first, *sink_in,
            by_group, by_key_head(k), by_key_head(v), valid[:, None, :])
    return kernel, grid_spec, jax.ShapeDtypeStruct((b, T, H * dv), v.dtype), args


def full_prefill_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           key_valid: jnp.ndarray,
                           q_start: jnp.ndarray) -> jnp.ndarray:
    """Causal attention of T consecutive queries over S keys of their row.
    q: [b, T, H, dk]; k: [b, S, G, dk]; v: [b, S, G, dv]; key_valid: [b, S],
    0 = a place that holds no token; q_start: int32 scalar, the place of the
    first query among the S (query t sits at q_start + t and sees the valid
    places up to its own). Returns [b, T, H * dv] in v's dtype."""
    T, S = q.shape[1], k.shape[1]
    bq, bk = _block(T, BLOCK_Q), _block(S, BLOCK_K)
    # the key axis ends with the block of the last query's own place: a
    # bound of the grid read at run time, so S may be the row's whole length
    steps = jnp.minimum((q_start + T - 1) // bk + 1, S // bk).astype(jnp.int32)
    kernel, grid_spec, out_shape, args = _operands(
        q, k, v, key_valid, q_start, None, bq, bk, 0, steps)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        compiler_params=_COMPILER_PARAMS, interpret=interpret_mode(),
        name=trace.KERNEL_FULL_CHUNK_ATTN,
    )(*args)


def window_prefill_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                             key_valid: jnp.ndarray, sink: jnp.ndarray,
                             window: int) -> jnp.ndarray:
    """Banded attention with a sink of T consecutive queries over the S = W
    + T keys of their context: the W places before the span (W a whole
    number of key blocks, >= window - 1; the caller pads the front with
    places that are not valid) and the span's own. q: [b, T, H, dk]; k: [b,
    S, G, dk]; v: [b, S, G, dv]; key_valid: [b, S]; sink: float32 [H]. Query
    t sits at W + t and sees the valid places (W + t - window, W + t].
    Returns [b, T, H * dv] in v's dtype."""
    T, S = q.shape[1], k.shape[1]
    bq = _block(T, BLOCK_Q)
    bk = _block(bq, WINDOW_BLOCK_K)     # a query block is whole key blocks
    before = S - T
    if before % bk or before < window - 1:
        raise ValueError(f"{before} places before the span: need a multiple "
                         f"of {bk} that holds the window's other "
                         f"{window - 1}")
    steps = -(-(window - 1) // bk) + bq // bk
    kernel, grid_spec, out_shape, args = _operands(
        q, k, v, key_valid, jnp.int32(before), sink, bq, bk, window, steps)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        compiler_params=_COMPILER_PARAMS, interpret=interpret_mode(),
        name=trace.KERNEL_WINDOW_PREFILL_ATTN,
    )(*args)
