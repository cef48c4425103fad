"""Pallas TPU paged decode attention over a LATENT cache: one query a slot
row in the absorbed form of multi-head latent attention, straight from the
latent pool's own pages (models/latent_moe/, a full layer without an
indexer: the query reads every position it can see).

What a layer keeps of a token is one ENTRY for all heads, `[c; k^R]` (the
normed latent and the shared roped key, stored padded with zeros to whole
tiles: 576 numbers as 640). The absorbed query of head h, `[W_kb,h^K^T q^N;
q^R]` padded likewise, meets an entry in one product, and the head's output
in the latent space is the softmax-weighted sum of the entries' first
`rank` numbers:

    s_h  = scale * q_abs_h . e_s            over the row's visible places s
    o'_h = sum_s softmax(s_h)_s e_s[:rank]

Gathering the row and handing it to `model.attend_entries` copies every
row's whole table a layer a tick (32 rows x 18,432 places x 640 x 2 B = 755
MB at the serving cell's shapes) and reads the copy twice. This kernel walks
the page table as `ops/paged_attention.py` does for keys and values: the
block index map reads it (scalar prefetch), so a page goes from the pool in
HBM to VMEM once, only pages that hold tokens are fetched at all, and both
products read the same block.

Schedule: grid (slot, page step); a step holds `n` pages (`_pages_per_step`),
each its own block `[page, width]` of the same pool operand, copied side by
side into one VMEM buffer `[n * page, width]` so that both products run over
whole MXU tiles: a page of 64 entries alone fills half a 128-wide tile, and
a product a page (the first form of this kernel) ran at 11% of the HBM
roofline on the v5e (PERF.md, PR 32). All heads of a row are the rows of one
matrix `[h, width]`: scores `[h, n * page]`, lane-dense, and the weighted
sum `[h, rank]`. The page axis carries the running max / sum / accumulator
(float32) in VMEM scratch. Blocks past a row's live pages clamp their index
to the last live page (no new fetch; what they hold is masked), steps wholly
past them skip the compute; a row with no live page returns zeros.

Numerics: entries as stored, float32 scores (scaled in float32, as
`attend_entries` scales them), softmax statistics and accumulator; the
exponentials are rounded to the entries' dtype for the weighted sum, as
`attend_entries` rounds its probabilities. Masked places (left pads, places
past the write position) contribute exactly zero.
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

# slot rows are independent; the page axis carries the softmax state
_COMPILER_PARAMS = compiler_params("parallel", "arbitrary")

# entries one grid step brings to VMEM (double-buffered by the pipeline): see
# `ops/paged_attention._STEP_BYTES`; a page of 64 entries of 640 bf16
# numbers is 80 KiB, so a step holds 12 of them
_STEP_BYTES = 1 << 20


def _pages_per_step(pmax: int, page_bytes: int) -> int:
    return max(1, min(pmax, _STEP_BYTES // page_bytes))


def _kernel(layer_ref, table_ref, live_ref, q_ref, mask_ref, *rest, n: int,
            page: int, scale: float, rank: int):
    del layer_ref, table_ref            # read by the index maps only
    e_refs = rest[:n]
    o_ref, e_scr, m_scr, l_scr, acc_scr = rest[n:]
    s = pl.program_id(0)
    j = pl.program_id(1)
    live = live_ref[s]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j * n < live)
    def _step():
        for i in range(n):
            e_scr[pl.ds(i * page, page), :] = e_refs[i][...]
        e = e_scr[...]                                      # [n * page, width]
        sc = jax.lax.dot_general(
            q_ref[...], e, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [h, n * page]
        # a place counts if its page is live and the mask holds it
        place = jax.lax.broadcasted_iota(jnp.int32, (1, n * page), 1)
        ok = (mask_ref[...] > 0) & (place < (live - j * n) * page)
        sc = jnp.where(ok, sc, NEG_INF)
        m_prev = m_scr[:, :1]
        m_cur = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
        correction = jnp.exp(m_prev - m_cur)
        # masked places contribute ZERO even while every place so far is
        # masked (m_cur == NEG_INF would make exp(sc - m_cur) = 1)
        w = jnp.where(ok, jnp.exp(sc - m_cur), 0.0)
        l_scr[:] = jnp.broadcast_to(
            correction * l_scr[:, :1] + w.sum(axis=-1, keepdims=True),
            l_scr.shape)
        acc_scr[:] = acc_scr[:] * correction + jax.lax.dot_general(
            w.astype(e.dtype), e[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [h, rank]
        m_scr[:] = jnp.broadcast_to(m_cur, m_scr.shape)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[...] = jnp.where(
            l > 0.0, acc_scr[:] / jnp.where(l > 0.0, l, 1.0),
            0.0).astype(o_ref.dtype)


def paged_latent_decode_attention(q_abs: jnp.ndarray, pool: jnp.ndarray,
                                  layer: jnp.ndarray, page_table: jnp.ndarray,
                                  live_pages: jnp.ndarray,
                                  kv_mask: jnp.ndarray, scale: float,
                                  rank: int) -> jnp.ndarray:
    """One-query absorbed attention of every slot row over its live pages.

    q_abs: [S, h, width] absorbed queries at the stored entry's width (zeros
    past the entry); pool: the latent pool whole, [L, pages + 1, page,
    width] (never sliced: the layer is an index); layer: int32 scalar;
    page_table: [S, Pmax] physical page per logical page; live_pages: [S]
    how many leading logical pages of a row hold tokens (0: the row is not
    decoding and gets zeros); kv_mask: [S, Pmax * page], 0 = the place is not
    attended; `scale` multiplies the scores; `rank`: the entry's leading
    numbers that are summed (the latent). Returns [S, h, rank] in the pool's
    dtype: what `model.attend_entries` gives over the gathered logical rows,
    for rows whose mask is zero past their live pages."""
    S, h, width = q_abs.shape
    _, _, page, _ = pool.shape
    pmax = page_table.shape[1]
    n = _pages_per_step(pmax, page * width * pool.dtype.itemsize)
    steps = pl.cdiv(pmax, n)
    # a step's places as one lane-dense row: [S, steps, 1, n * page]
    mask = jnp.pad(kv_mask.astype(jnp.int32),
                   ((0, 0), (0, steps * n * page - pmax * page))
                   ).reshape(S, steps, 1, n * page)

    def page_block(i):
        def index(s, j, layer_ref, table_ref, live_ref):
            # past the live pages: the last live page again (no new fetch)
            p = jnp.minimum(j * n + i, jnp.maximum(live_ref[s] - 1, 0))
            return layer_ref[0], table_ref[s * pmax + p], 0, 0
        return pl.BlockSpec((None, None, page, width), index)

    def row(s, j, *_):
        return s, 0, 0

    return pl.pallas_call(
        functools.partial(_kernel, n=n, page=page, scale=scale, rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, steps),
            in_specs=[
                pl.BlockSpec((None, h, width), row),
                pl.BlockSpec((None, None, 1, n * page),
                             lambda s, j, *_: (s, j, 0, 0)),
                *[page_block(i) for i in range(n)],
            ],
            out_specs=pl.BlockSpec((None, h, rank), row),
            scratch_shapes=[
                pltpu.VMEM((n * page, width), pool.dtype),
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, rank), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, h, rank), pool.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret_mode(),
        name=trace.KERNEL_PAGED_LATENT_DECODE_ATTN,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      page_table.reshape(-1).astype(jnp.int32),
      live_pages.astype(jnp.int32), q_abs.astype(pool.dtype), mask,
      *([pool] * n))
