"""Pallas TPU paged decode attention: one query a slot row, straight from the
page pool.

Gathering a slot's logical row and handing it to `ops/attention.attention`
(what the prefills and an int8 tick do, `decode._gather_pages`) reads the KV
cache three times a layer when the query is one token: the gather's copy of
the whole row, a float32 copy of the gathered keys (XLA:TPU runs a one-query
product with float32 operands) and the product itself: 68% of the dense
serving tick on the v5e (PERF.md, PR 27), over every logical page whatever
the request's length. This kernel walks the page table: the block index map
reads it (scalar prefetch), so a page goes from the pool in HBM to VMEM once,
in the pool's dtype, and only pages that hold tokens are fetched at all.

Layout. The pool stays `[L, pages + 1, page, kv_h, hd]` (the write paths and
`serve/pages.py` rest on it); the values' pool may keep another width a head
than the keys' (`hd_v`: the output's), and the scores are scaled by the
keys' `hd ** -0.5`. A page is read as the matrix it already is in
memory, `[page * kv_h, hd]`: row `r` is token `r // kv_h` of KV head
`r % kv_h`. The query heads `[h, hd]` are multiplied against ALL of a page's
rows on the MXU (`[h, page * kv_h]` scores, lane-dense) and the rows of other
KV heads are masked like padding: the value product over the same rows then
yields `[h, hd]` directly. A query head's own rows are `r % kv_h == head //
g`, so `g = h // kv_h` grouped queries share a KV head's rows by shape and
no `repeat_kv` copy exists. The MXU does kv_h times the useful FLOPs; at one
query a row it is idle otherwise, and no in-kernel relayout is needed.

Schedule: grid (slot, page step); a step holds `n` pages (chosen from the
shapes, `_pages_per_step`), each its own block of the same pool operand. The
page axis carries the running max / sum / accumulator (float32) in VMEM
scratch. Steps past a row's live pages clamp their block index to the last
live page, so the pipeline re-uses the buffer it holds and fetches nothing,
and skip the compute (`pl.when`). A row with no live page returns zeros.

A SINK (`sink`, one float32 logit a query head; None: a plain softmax) is a
key with no value that every query of the head sees: `exp(sink)` in the
denominator and nothing in the numerator. It is where the running state
starts (max = the sink, sum = 1, accumulator 0) where a plain softmax starts
from nothing; the pages then go by as they do without one.

Numerics: keys and values as stored, float32 scores, softmax statistics and
accumulator. The query is scaled in its own dtype before the kernel and the
exponentials are rounded to the values' dtype for the value product, as
`ops/attention.attention` rounds its probabilities; nothing else is rounded.
Masked positions contribute exactly zero (finite `NEG_INF`, then a select), so
a wholly masked page inside the live range is harmless.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llama_pipeline_parallel_tpu.ops.attention import NEG_INF
from llama_pipeline_parallel_tpu.ops.pallas_common import (
    compiler_params,
    interpret_mode,
)
from llama_pipeline_parallel_tpu.utils import trace

# slot rows are independent; the page axis carries the softmax state
_COMPILER_PARAMS = compiler_params("parallel", "arbitrary")

# keys + values one grid step brings to VMEM (double-buffered by the
# pipeline): enough that a step's fixed cost (~0.35 us) is small beside its
# DMA, and no more: every block of a step is fetched when the row changes,
# the ones past a short row's live pages too. On the v5e (PERF.md, PR 29) 1
# MiB, one 32-head page or four 8-head pages of 64 bf16 tokens, was the
# fastest of 1 / 2 / 4 / 8 MiB at both serving cells' shapes.
_STEP_BYTES = 1 << 20


def _pages_per_step(pmax: int, page_bytes: int) -> int:
    """How many pages one grid step holds: as many as `_STEP_BYTES` of keys
    and values allow, at least one, at most the row's `pmax`."""
    return max(1, min(pmax, _STEP_BYTES // (2 * page_bytes)))


def _kernel(layer_ref, table_ref, live_ref, q_ref, own_ref, mask_ref, *rest,
            n: int, sink: bool = False):
    del layer_ref, table_ref            # read by the index maps only
    if sink:
        sink_ref, rest = rest[0], rest[1:]
    k_refs, v_refs = rest[:n], rest[n:2 * n]
    o_ref, m_scr, l_scr, acc_scr = rest[2 * n:]
    s = pl.program_id(0)
    j = pl.program_id(1)
    live = live_ref[s]

    @pl.when(j == 0)
    def _init():
        if sink:
            m_scr[:] = sink_ref[...]
            l_scr[:] = jnp.ones_like(l_scr)
        else:
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    for i in range(n):
        p = j * n + i

        @pl.when(p < live)
        def _page(i=i, p=p):
            q = q_ref[...]                                  # [h, hd]
            k = k_refs[i][...]                              # [page * kv_h, hd]
            v = v_refs[i][...]
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)         # [h, page * kv_h]
            # a row counts for a query head if it is the head's own KV head
            # and its token is not masked
            ok = (own_ref[...] * mask_ref[pl.ds(p, 1), :]) > 0
            sc = jnp.where(ok, sc, NEG_INF)
            m_prev = m_scr[:, :1]
            m_cur = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
            correction = jnp.exp(m_prev - m_cur)
            # masked rows contribute ZERO even while every row so far is
            # masked (m_cur == NEG_INF would make exp(sc - m_cur) = 1)
            e = jnp.where(ok, jnp.exp(sc - m_cur), 0.0)
            l_scr[:] = jnp.broadcast_to(
                correction * l_scr[:, :1] + e.sum(axis=-1, keepdims=True),
                l_scr.shape)
            acc_scr[:] = acc_scr[:] * correction + jax.lax.dot_general(
                e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [h, hd]
            m_scr[:] = jnp.broadcast_to(m_cur, m_scr.shape)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[...] = jnp.where(
            l > 0.0, acc_scr[:] / jnp.where(l > 0.0, l, 1.0),
            0.0).astype(o_ref.dtype)


def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, layer: jnp.ndarray,
                           page_table: jnp.ndarray, live_pages: jnp.ndarray,
                           kv_mask: jnp.ndarray,
                           sink: jnp.ndarray | None = None,
                           scale: float | None = None) -> jnp.ndarray:
    """One-query softmax attention of every slot row over its live pages.

    q: [S, h, hd]; k_pool / v_pool: the pool's arrays whole, [L, pages + 1,
    page, kv_h, hd] and [..., hd_v] (never sliced: the layer is an index);
    sink: float32 [h] or None; scale: the scores' factor (None: the keys'
    `hd ** -0.5`; a family that stores its keys padded to whole tiles gives
    the factor of the width they have); layer: int32 scalar; page_table:
    [S, Pmax] physical page per logical page;
    live_pages: [S] how many leading logical pages of a row hold tokens (0:
    the row is not decoding and gets zeros); kv_mask: [S, Pmax * page], 0 =
    the position is not attended. Returns [S, h, hd_v] in q's dtype: what
    `attention(q[:, None], gathered_k, gathered_v, kv_mask, causal=False)`
    gives over the gathered logical rows, for rows whose mask is zero past
    their live pages."""
    S, h, hd = q.shape
    L, pages, page, kv_h, _ = k_pool.shape
    hd_v = v_pool.shape[-1]
    pmax = page_table.shape[1]
    g = h // kv_h
    rows = page * kv_h
    n = _pages_per_step(
        pmax, rows * (hd + hd_v) * k_pool.dtype.itemsize // 2)
    steps = pl.cdiv(pmax, n)

    # the views the kernel reads: a page as the [page * kv_h, hd] matrix its
    # bytes already are, the mask a row of the page's rows
    k2 = k_pool.reshape(L, pages, rows, hd)
    v2 = v_pool.reshape(L, pages, rows, hd_v)
    mask = jnp.repeat(kv_mask.reshape(S, pmax, page).astype(jnp.int32), kv_h,
                      axis=-1)                              # [S, Pmax, rows]
    own = jnp.asarray(np.arange(rows)[None, :] % kv_h
                      == np.arange(h)[:, None] // g, jnp.int32)  # [h, rows]
    q = q * jnp.asarray(hd ** -0.5 if scale is None else scale, q.dtype)

    def page_block(i, width):
        def index(s, j, layer_ref, table_ref, live_ref):
            # past the live pages: the last live page again (no new fetch)
            p = jnp.minimum(j * n + i, jnp.maximum(live_ref[s] - 1, 0))
            return layer_ref[0], table_ref[s * pmax + p], 0, 0
        return pl.BlockSpec((None, None, rows, width), index)

    def row(s, j, *_):
        return s, 0, 0

    # the sink a lane-wide block, as the running max is kept
    sink_in = [] if sink is None else [
        jnp.broadcast_to(sink.astype(jnp.float32)[:, None], (h, 128))]
    return pl.pallas_call(
        functools.partial(_kernel, n=n, sink=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, steps),
            in_specs=[
                pl.BlockSpec((None, h, hd), row),
                pl.BlockSpec((h, rows), lambda s, j, *_: (0, 0)),
                pl.BlockSpec((None, pmax, rows), row),
                *(pl.BlockSpec((h, 128), lambda s, j, *_: (0, 0))
                  for _ in sink_in),
                *(page_block(i, hd) for i in range(n)),
                *(page_block(i, hd_v) for i in range(n)),
            ],
            out_specs=pl.BlockSpec((None, h, hd_v), row),
            scratch_shapes=[
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, hd_v), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, h, hd_v), q.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret_mode(),
        name=trace.KERNEL_PAGED_DECODE_ATTN,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      page_table.reshape(-1).astype(jnp.int32),
      live_pages.astype(jnp.int32), q, own, mask, *sink_in,
      *([k2] * n), *([v2] * n))
