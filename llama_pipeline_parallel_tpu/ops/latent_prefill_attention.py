"""Pallas TPU causal attention of a span of queries over the PROJECTED keys
and values of multi-head latent attention (models/latent_moe/, a full layer
without an indexer: prefill and chunked prefill read every earlier
position).

In the projected form a head's key is `[W_kb,h^K c_s; k^R_s]`: a part of its
own made from the latent, and the roped part, one for all heads; its value
`W_kb,h^V c_s` has another size than its key (192 numbers against 128 at the
published widths), which `ops/flash_attention.py` does not take. For many
queries against the same keys this form costs 2 (nope + rope + v) FLOPs a
head and pair where the absorbed form costs 2 (2 rank + rope): 3.4 times
fewer at 64 heads. Through XLA (`model.attend_projected`) the float32 scores
`[heads, T, S]` of a 2048-token chunk at the end of a 16k row are 9.7 GB;
here they never leave VMEM.

    s_h[t, s] = scale (q^N_h,t . k^N_h,s + q^R_h,t . k^R_s)
    o_h,t     = sum_s softmax_s(s_h[t, s]) v_h,s      over valid s <= place(t)

The roped key stays one array `[b, S, rope]`: every head's program reads the
same block, and no copy of it a head exists in HBM. The queries' places
among the S keys are consecutive from `q_start` (a chunk's rows follow the
slot's earlier ones); `key_valid` masks the left pads of a prompt bucket.

Schedule: grid (batch, head, query block, key block), the key axis innermost
carrying the running max / sum / accumulator (float32) in VMEM scratch
(FlashAttention-2). Key blocks wholly after a query block's last place, or
wholly inside the row's leading pads, are skipped: their block index is
clamped to a block the program holds or needs anyway (no new fetch) and the
compute is predicated off. The output is written `[b, T, heads * v]`, the
layout the output projection takes.

Tiles of which every pair is visible (all of a long row's but the diagonal's
and the pads') skip the mask: on the v5e the float32 passes over a tile's
scores, not its products, bound the kernel (PERF.md, PR 32).

Numerics: keys and values as given, the query scaled in its own dtype before
the kernel (as `ops/paged_attention.py` scales it), float32 scores, softmax
statistics and accumulator; the exponentials are rounded to the values'
dtype for the value product, as `model.attend_projected` rounds its
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

# (batch, head, query block) programs are independent; the key axis carries
# the softmax state
_COMPILER_PARAMS = compiler_params("parallel", "parallel", "parallel",
                                   "arbitrary")
BLOCK_Q = 1024
BLOCK_K = 512


def _block(n: int, target: int) -> int:
    """The largest divisor of `n` that is <= target."""
    return next(b for b in range(min(n, target), 0, -1) if n % b == 0)


def _needed(row, qi, q_start_ref, first_ref, block_q: int, block_k: int):
    """(first, last) key block a query block needs: from the block that
    holds the row's first valid place to the one that holds its own last
    place."""
    q_last = q_start_ref[0] + (qi + 1) * block_q - 1
    return first_ref[row] // block_k, q_last // block_k


def _kernel(q_start_ref, first_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref,
            valid_ref, o_ref, m_scr, l_scr, acc_scr, *, block_q: int,
            block_k: int):
    row, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    lo, hi = _needed(row, qi, q_start_ref, first_ref, block_q, block_k)
    q_first = q_start_ref[0] + qi * block_q
    # every pair of the tile is visible: the tile ends at or before the
    # block's first query, and starts at or after the row's first token
    whole = ((ki + 1) * block_k - 1 <= q_first) & (
        ki * block_k >= first_ref[row])

    def update(masked: bool):
        contract = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(qn_ref[...], kn_ref[...], contract,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[...], kr_ref[...], contract,
                                   preferred_element_type=jnp.float32))
        if masked:
            q_place = q_first + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_place = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            ok = (k_place <= q_place) & (valid_ref[...] > 0)  # [bq, bk]
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
            preferred_element_type=jnp.float32)              # [bq, v]
        m_scr[:] = jnp.broadcast_to(m_cur, m_scr.shape)

    run = (ki >= lo) & (ki <= hi)
    pl.when(run & whole)(lambda: update(False))
    pl.when(run & jnp.logical_not(whole))(lambda: update(True))

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[...] = jnp.where(
            l > 0.0, acc_scr[:] / jnp.where(l > 0.0, l, 1.0),
            0.0).astype(o_ref.dtype)


def latent_prefill_attention(q_nope: jnp.ndarray, q_rope: jnp.ndarray,
                             k_nope: jnp.ndarray, k_rope: jnp.ndarray,
                             v: jnp.ndarray, key_valid: jnp.ndarray,
                             q_start: jnp.ndarray, scale: float
                             ) -> jnp.ndarray:
    """q_nope: [b, H, T, nope]; q_rope: [b, H, T, rope] (roped); k_nope: [b,
    H, S, nope]; k_rope: [b, S, rope] (roped, shared by the heads); v: [b,
    H, S, v]; key_valid: [b, S], 0 = a place that holds no token; q_start:
    int32 scalar, the place of the first query among the S (query t sits at
    q_start + t and sees the valid places up to its own). Returns [b, T, H *
    v] in v's dtype."""
    b, H, T, nope = q_nope.shape
    S, rope, dv = k_nope.shape[2], q_rope.shape[-1], v.shape[-1]
    bq, bk = _block(T, BLOCK_Q), _block(S, BLOCK_K)
    valid = key_valid.astype(jnp.int32)
    scaled = jnp.asarray(scale, q_nope.dtype)
    # the row's leading pads: the first valid place (S where there is none)
    first = jnp.where(jnp.any(valid > 0, axis=1),
                      jnp.argmax(valid > 0, axis=1), S).astype(jnp.int32)

    def key_block(row, qi, ki, q_start_ref, first_ref):
        lo, hi = _needed(row, qi, q_start_ref, first_ref, bq, bk)
        return jnp.minimum(jnp.maximum(ki, jnp.minimum(lo, hi)), hi)

    per_query = lambda width: pl.BlockSpec(
        (None, None, bq, width), lambda r, h, qi, ki, *_: (r, h, qi, 0))
    per_key = lambda width: pl.BlockSpec(
        (None, None, bk, width),
        lambda r, h, qi, ki, *refs: (r, h, key_block(r, qi, ki, *refs), 0))
    return pl.pallas_call(
        functools.partial(_kernel, block_q=bq, block_k=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, H, T // bq, S // bk),
            in_specs=[
                per_query(nope), per_query(rope), per_key(nope),
                pl.BlockSpec((None, bk, rope), lambda r, h, qi, ki, *refs: (
                    r, key_block(r, qi, ki, *refs), 0)),
                per_key(dv),
                pl.BlockSpec((None, 1, bk), lambda r, h, qi, ki, *refs: (
                    r, 0, key_block(r, qi, ki, *refs))),
            ],
            out_specs=pl.BlockSpec((None, bq, dv),
                                   lambda r, h, qi, ki, *_: (r, qi, h)),
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, T, H * dv), v.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret_mode(),
        name=trace.KERNEL_LATENT_PREFILL_ATTN,
    )(jnp.reshape(q_start, (1,)).astype(jnp.int32), first,
      q_nope * scaled, q_rope * scaled, k_nope, k_rope, v, valid[:, None, :])
