"""Pallas TPU attention of a span of queries over two kinds of key in ONE
softmax (models/eva/: a prefill's or a chunk's queries): the exact keys of a
query's own window up to its own position, and one pooled key and value a
chunk of every earlier window.

What a query sees is a rule on integers, so one kernel serves a whole prompt
(every window's keys side by side) and a chunk (the ring as it stood beside
the chunk's own keys) alike. A query carries an interval `[q_lo, q_hi]`: the
first position of its window and its own position. An exact key carries its
position `p` and is visible where `q_lo <= p <= q_hi`; a summary carries the
first position `c` of its chunk and is visible where `c < q_lo` (its window
lies before the query's). A key that holds nothing carries -1, and a pad
query the empty interval `[0, -2]`: it sees nothing and gets zeros.

Through XLA the float32 scores of 2048 queries against 2048 + 2048 exact keys
and 1,600 summaries are 32 x 2048 x 5,696 x 4 B = 1.5 GB a layer; here they
never leave VMEM.

Schedule: grid (batch, head, query block, key block), the key axis innermost,
the summaries' blocks first and then the exact keys', carrying the running
max / sum / accumulator (float32) in VMEM scratch (FlashAttention-2). Both
kinds are operands of their own: a step of one kind holds the other's block
index where it was, so nothing is fetched for it. `run` (scalar prefetch, one
flag a (query block, key block), made from the tags by the caller's program)
predicates off the tiles in which no pair is visible: the other window's keys,
the causal future, summaries not before any of the block's queries.

Layout: queries, keys, values and the output are `[b, n, heads * head_dim]`,
as the projections give and take them; a head's block is a lane-aligned
column slice, so no transpose exists. Grouped keys share a block by the index
map (`head // group`).

Numerics: keys and values as given, the query scaled in its own dtype before
the kernel (as `ops/paged_attention.py` scales it), float32 scores, softmax
statistics and accumulator; the exponentials are rounded to the values'
dtype for the value product.
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

_COMPILER_PARAMS = compiler_params("parallel", "parallel", "parallel",
                                   "arbitrary")
BLOCK_Q = 1024
BLOCK_K = 512
# a pad query's interval: no position lies in it, no summary before it
PAD_LO, PAD_HI = 0, -2


def _block(n: int, target: int) -> int:
    """The largest divisor of `n` that is <= target."""
    return next(b for b in range(min(n, target), 0, -1) if n % b == 0)


def _whole_blocks(k, v, tag):
    """Keys, values and tags padded (tag -1: holds nothing) so that the key
    axis is whole blocks Mosaic takes: of `BLOCK_K` where there are that
    many, else of one lane tile, else as it is (one block)."""
    n = k.shape[1]
    tile = BLOCK_K if n >= BLOCK_K else 128 if n >= 128 else 1
    extra = -n % tile
    if not extra:
        return k, v, tag
    rows = ((0, 0), (0, extra), (0, 0))
    return (jnp.pad(k, rows), jnp.pad(v, rows),
            jnp.pad(tag, rows[:2], constant_values=-1))


def block_runs(q_lo, q_hi, tag_s, tag_e, bq: int, bs: int, be: int):
    """[b, T / bq, S_s / bs + S_e / be] int32: 1 where some pair of the tile
    may be visible, by the blocks' extremes (never 0 for a tile that holds a
    visible pair)."""
    b = q_lo.shape[0]
    real = q_hi >= 0
    lo = q_lo.reshape(b, -1, bq)
    lo_max = lo.max(axis=-1)[:, :, None]
    lo_min = jnp.where(real, q_lo, jnp.iinfo(jnp.int32).max).reshape(
        b, -1, bq).min(axis=-1)[:, :, None]
    hi_max = q_hi.reshape(b, -1, bq).max(axis=-1)[:, :, None]
    s_min = jnp.where(tag_s >= 0, tag_s, jnp.iinfo(jnp.int32).max).reshape(
        b, -1, bs).min(axis=-1)[:, None, :]
    e = tag_e.reshape(b, -1, be)
    e_max = e.max(axis=-1)[:, None, :]
    e_min = jnp.where(e >= 0, e, jnp.iinfo(jnp.int32).max).min(
        axis=-1)[:, None, :]
    summaries = (s_min < lo_max) & (hi_max >= 0)
    exact = (e_min <= hi_max) & (e_max >= lo_min)
    return jnp.concatenate([summaries, exact], axis=-1).astype(jnp.int32)


def _kernel(run_ref, q_ref, lo_ref, hi_ref, ks_ref, vs_ref, ts_ref, ke_ref,
            ve_ref, te_ref, o_ref, m_scr, l_scr, acc_scr, *, ns: int):
    r, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nq, nk = pl.num_programs(2), pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def update(k_ref, v_ref, ok):
        s = jax.lax.dot_general(q_ref[...], k_ref[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        correction = jnp.exp(m_prev - m_cur)
        # masked pairs contribute ZERO even while a query has seen nothing
        # (m_cur == NEG_INF would make exp(s - m_cur) = 1)
        w = jnp.where(ok, jnp.exp(s - m_cur), 0.0)
        l_scr[:] = jnp.broadcast_to(
            correction * l_scr[:, :1] + w.sum(axis=-1, keepdims=True),
            l_scr.shape)
        v = v_ref[...]
        acc_scr[:] = acc_scr[:] * correction + jax.lax.dot_general(
            w.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_cur, m_scr.shape)

    go = run_ref[(r * nq + qi) * nk + ki] > 0

    @pl.when(go & (ki < ns))
    def _summaries():
        tag = ts_ref[...]                                   # [1, bs]
        update(ks_ref, vs_ref, (tag >= 0) & (tag < lo_ref[...]))

    @pl.when(go & (ki >= ns))
    def _exact():
        tag = te_ref[...]                                   # [1, be]
        update(ke_ref, ve_ref, (tag >= lo_ref[...]) & (tag <= hi_ref[...]))

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[...] = jnp.where(
            l > 0.0, acc_scr[:] / jnp.where(l > 0.0, l, 1.0),
            0.0).astype(o_ref.dtype)


def eva_prefill_attention(q: jnp.ndarray, k_sum: jnp.ndarray,
                          v_sum: jnp.ndarray, tag_sum: jnp.ndarray,
                          k_exact: jnp.ndarray, v_exact: jnp.ndarray,
                          tag_exact: jnp.ndarray, q_lo: jnp.ndarray,
                          q_hi: jnp.ndarray, heads: int,
                          scale: float) -> jnp.ndarray:
    """q: [b, T, heads * hd]; k_sum / v_sum: [b, S_s, kv_heads * hd] pooled
    keys and values, tag_sum: [b, S_s] the first position of each one's chunk
    (-1: holds nothing); k_exact / v_exact: [b, S_e, kv_heads * hd], tag_exact
    [b, S_e] each key's position (-1: holds nothing); q_lo / q_hi: [b, T] a
    query's interval (`PAD_LO`, `PAD_HI` for a pad). Returns [b, T, heads *
    hd] in v's dtype: one softmax over a query's visible keys of both
    kinds, zeros where it sees none."""
    b, T, width = q.shape
    hd = width // heads
    group = heads // (k_exact.shape[-1] // hd)
    k_sum, v_sum, tag_sum = _whole_blocks(k_sum, v_sum, tag_sum)
    k_exact, v_exact, tag_exact = _whole_blocks(k_exact, v_exact, tag_exact)
    s_sum, s_exact = k_sum.shape[1], k_exact.shape[1]
    bq, bs, be = _block(T, BLOCK_Q), _block(s_sum, BLOCK_K), _block(
        s_exact, BLOCK_K)
    ns, ne = s_sum // bs, s_exact // be
    i32 = lambda x: x.astype(jnp.int32)
    q_lo, q_hi, tag_sum, tag_exact = map(i32, (q_lo, q_hi, tag_sum,
                                               tag_exact))
    run = block_runs(q_lo, q_hi, tag_sum, tag_exact, bq, bs, be)

    # a step of the other kind keeps this kind's block where it was
    sum_block = lambda ki: jnp.minimum(ki, ns - 1)
    exact_block = lambda ki: jnp.maximum(ki - ns, 0)
    heads_block = pl.BlockSpec((None, bq, hd),
                               lambda r, h, qi, ki, *_: (r, qi, h))
    interval = pl.BlockSpec((None, bq, 1), lambda r, h, qi, ki, *_: (r, qi, 0))
    keys = lambda rows, block: pl.BlockSpec(
        (None, rows, hd), lambda r, h, qi, ki, *_: (r, block(ki), h // group))
    tags = lambda rows, block: pl.BlockSpec(
        (None, 1, rows), lambda r, h, qi, ki, *_: (r, 0, block(ki)))
    return pl.pallas_call(
        functools.partial(_kernel, ns=ns),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, heads, T // bq, ns + ne),
            in_specs=[
                heads_block, interval, interval,
                keys(bs, sum_block), keys(bs, sum_block), tags(bs, sum_block),
                keys(be, exact_block), keys(be, exact_block),
                tags(be, exact_block),
            ],
            out_specs=heads_block,
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, T, width), v_exact.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret_mode(),
        name=trace.KERNEL_EVA_PREFILL_ATTN,
    )(run.reshape(-1), q * jnp.asarray(scale, q.dtype), q_lo[:, :, None],
      q_hi[:, :, None], k_sum, v_sum, tag_sum[:, None, :], k_exact, v_exact,
      tag_exact[:, None, :])
