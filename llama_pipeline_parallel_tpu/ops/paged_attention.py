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

Schedule: a 1-D grid over the VISITS, the (slot, step) pairs in which the
step holds a live page of the slot's row; a step is `n` consecutive logical
pages (chosen from the shapes, `pages_per_step`), each its own block of the
same pool operand. A row of `live` pages is `cdiv(live, n)` visits whatever
its table's width, and a row with none is one visit that writes its zeros:
the grid's bound is the number of visits, a traced scalar, and the block
index maps read a visit's slot and step from scalar-prefetched arrays
(`_visits`, a few integer operations on `live_pages` in front of the call;
`ops/grouped_matmul.py` walks its visits so). A slot's visits are
consecutive and carry its running max / sum / accumulator (float32) in VMEM
scratch: set on the slot's first step, written out on its last. A visit is
ONE running-softmax update over all its pages: the n score tiles are n
independent products laid side by side, `[h, n * page * kv_h]`, under one
mask (the head's own KV head, the step's mask row, and the page's place
below the row's live pages), one max, one exponential, one sum and one
rescaling of the running state, then n independent value products into the
accumulator. A page's cost is then its bytes: the n bodies a step held
before were n dependency chains, each waiting for the one before it, and
cost 1.3 us a page whatever the page held (PERF.md, PR 48 and PR 49). In a
row's last visit the blocks past its live pages clamp their index to the
last live page, so the pipeline fetches nothing for them, and count for zero.

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
a wholly masked page inside the live range is harmless. One update over n
pages is the page-by-page update with fewer rescalings of the running state:
the same sums in another order.
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

# a slot's visits are consecutive and carry its softmax state
_COMPILER_PARAMS = compiler_params("arbitrary")

# keys + values one grid step brings to VMEM (double-buffered by the
# pipeline): enough that a step's one softmax update and its 2n block fetches
# are small beside its DMA (a live step of five 196 KB pages is 1.5 us, 653
# GB/s), and no more: every block of a row's LAST step is fetched, the ones
# past its live pages too (the last live page again). On the v5e (PERF.md,
# PR 49, as PR 29 before it) 1 MiB, one 32-head page, four 8-head pages or
# five of the window family's 4-head pages of 64 bf16 tokens, was the
# fastest of 1 / 2 / 4 MiB at four of the five serving cells' shapes; 2 MiB
# was 7% faster at the fifth and 2 to 16% slower at the others.
_STEP_BYTES = 1 << 20


def _pages_per_step(pmax: int, page_bytes: int) -> int:
    """How many pages one grid step holds: as many as `_STEP_BYTES` of keys
    and values allow, at least one, at most the row's `pmax`."""
    return max(1, min(pmax, _STEP_BYTES // (2 * page_bytes)))


def pages_per_step(k_pool, v_pool, pmax: int) -> int:
    """`_pages_per_step` for pools of these shapes and dtypes (anything with
    `.shape` and `.dtype`: `[L, pages + 1, ...a page...]`) under a page table
    `pmax` wide: what `paged_decode_attention` itself asks, and what a
    counter of its grid steps asks (`serve/engine.py`)."""
    page_bytes = sum(int(np.prod(a.shape[2:])) * np.dtype(a.dtype).itemsize
                     for a in (k_pool, v_pool))
    return _pages_per_step(pmax, page_bytes // 2)


def _visits(live_pages: jnp.ndarray, n: int, steps: int):
    """The (slot, step) pairs the grid walks, in order: a slot's steps that
    hold a live page, `cdiv(live, n)` of them, and one for a slot with none
    (its zeros are written there). Returns (slot_of, step_of, visits): two
    int32 arrays sized for the most visits any rows can make, slots x steps,
    of which the grid reads the first `visits` (a traced scalar); the entries
    past them name the last slot and a step it has."""
    slots = live_pages.shape[0]
    per_slot = jnp.maximum(-(-live_pages // n), 1)
    ends = jnp.cumsum(per_slot, dtype=jnp.int32)
    visit = jnp.arange(slots * steps, dtype=jnp.int32)
    # the slot whose run of visits holds this one (compared against every
    # slot: a few thousand integers, one fusion, as `group_metadata` does)
    slot_of = jnp.minimum(
        jnp.sum(visit[:, None] >= ends[None, :], axis=1, dtype=jnp.int32),
        slots - 1)
    step_of = jnp.clip(visit - (ends - per_slot)[slot_of], 0, steps - 1)
    return slot_of, step_of, ends[-1]


def _kernel(layer_ref, table_ref, live_ref, slot_ref, step_ref, q_ref,
            own_ref, mask_ref, *rest, n: int, sink: bool = False):
    del layer_ref, table_ref            # read by the index maps only
    if sink:
        sink_ref, rest = rest[0], rest[1:]
    k_refs, v_refs = rest[:n], rest[n:2 * n]
    o_ref, m_scr, l_scr, acc_scr = rest[2 * n:]
    rows = k_refs[0].shape[0]           # of one page's matrix
    visit = pl.program_id(0)
    j = step_ref[visit]
    live = live_ref[slot_ref[visit]]

    @pl.when(j == 0)
    def _init():
        if sink:
            m_scr[:] = sink_ref[...]
            l_scr[:] = jnp.ones_like(l_scr)
        else:
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j * n < live)
    def _step():
        q = q_ref[...]                                      # [h, hd]
        # the n pages' scores side by side: n products that wait for nothing
        sc = jnp.concatenate([
            jax.lax.dot_general(
                q, k_ref[...], (((1,), (1,)), ((), ())),    # [page * kv_h, hd]
                preferred_element_type=jnp.float32)
            for k_ref in k_refs], axis=-1)                  # [h, n * rows]
        # a row counts for a query head if its page is live (a block past the
        # live pages holds the last live page again), its token is not masked
        # and it is the head's own KV head
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, n * rows), 1)
        seen = jnp.where(lane < (live - j * n) * rows, mask_ref[...], 0)
        ok = (own_ref[...] * seen) > 0
        sc = jnp.where(ok, sc, NEG_INF)
        m_prev = m_scr[:, :1]
        m_cur = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
        correction = jnp.exp(m_prev - m_cur)
        # masked rows contribute ZERO even while every row so far is masked
        # (m_cur == NEG_INF would make exp(sc - m_cur) = 1)
        e = jnp.where(ok, jnp.exp(sc - m_cur), 0.0)
        l_scr[:] = jnp.broadcast_to(
            correction * l_scr[:, :1] + e.sum(axis=-1, keepdims=True),
            l_scr.shape)
        e = e.astype(v_refs[0].dtype)
        acc_scr[:] = acc_scr[:] * correction + sum(
            jax.lax.dot_general(
                e[:, i * rows:(i + 1) * rows], v_ref[...],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [h, hd_v]
            for i, v_ref in enumerate(v_refs))
        m_scr[:] = jnp.broadcast_to(m_cur, m_scr.shape)

    @pl.when((j + 1) * n >= live)
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
    n = pages_per_step(k_pool, v_pool, pmax)
    steps = pl.cdiv(pmax, n)

    # the views the kernel reads: a page as the [page * kv_h, hd] matrix its
    # bytes already are, a step's mask ONE row of its n pages' rows (the
    # table's width padded to whole steps: a place no page holds is masked)
    k2 = k_pool.reshape(L, pages, rows, hd)
    v2 = v_pool.reshape(L, pages, rows, hd_v)
    mask = jnp.repeat(kv_mask.reshape(S, pmax, page).astype(jnp.int32), kv_h,
                      axis=-1)                              # [S, Pmax, rows]
    mask = jnp.pad(mask, ((0, 0), (0, steps * n - pmax), (0, 0))).reshape(
        S, steps, 1, n * rows)
    own = jnp.asarray(np.arange(n * rows)[None, :] % kv_h
                      == np.arange(h)[:, None] // g, jnp.int32)  # [h, n * rows]
    q = q * jnp.asarray(hd ** -0.5 if scale is None else scale, q.dtype)

    # a table's width bounds a row's live pages, as the visits' arrays assume
    live_pages = jnp.clip(live_pages.astype(jnp.int32), 0, pmax)
    slot_of, step_of, visits = _visits(live_pages, n, steps)

    def page_block(i, width):
        def index(visit, layer_ref, table_ref, live_ref, slot_ref, step_ref):
            s = slot_ref[visit]
            # past the live pages: the last live page again (no new fetch)
            p = jnp.minimum(step_ref[visit] * n + i,
                            jnp.maximum(live_ref[s] - 1, 0))
            return layer_ref[0], table_ref[s * pmax + p], 0, 0
        return pl.BlockSpec((None, None, rows, width), index)

    def row(visit, layer_ref, table_ref, live_ref, slot_ref, step_ref):
        return slot_ref[visit], 0, 0

    def whole(visit, *_):
        return 0, 0

    # the sink a lane-wide block, as the running max is kept
    sink_in = [] if sink is None else [
        jnp.broadcast_to(sink.astype(jnp.float32)[:, None], (h, 128))]
    return pl.pallas_call(
        functools.partial(_kernel, n=n, sink=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(visits,),
            in_specs=[
                pl.BlockSpec((None, h, hd), row),
                pl.BlockSpec((h, n * rows), whole),
                pl.BlockSpec(
                    (None, None, 1, n * rows),
                    lambda visit, layer_ref, table_ref, live_ref, slot_ref,
                    step_ref: (slot_ref[visit], step_ref[visit], 0, 0)),
                *(pl.BlockSpec((h, 128), whole) for _ in sink_in),
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
      live_pages, slot_of, step_of, q, own, mask, *sink_in,
      *([k2] * n), *([v2] * n))
