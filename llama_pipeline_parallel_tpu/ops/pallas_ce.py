"""Pallas TPU fused lm-head + cross-entropy, with custom VJP.

The Pallas promotion of ops/cross_entropy.py (ROADMAP item 5): the same
vocab-chunked online-logsumexp schedule, but the per-chunk fp32 logits block
lives in VMEM scratch instead of round-tripping HBM. The XLA scan saves only
[tokens]-sized statistics, yet each iteration still materializes a
`[tokens, V/chunks]` fp32 logits buffer (forward AND backward recompute) plus
a `[tokens, d]` fp32 `dh` accumulator carried through the backward scan —
exactly the traffic a kernel keeps on-chip. Under `schedule: zb1` every byte
saved here is saved TWICE: the W-drain replays the chunk forward to form
dW (parallel/pipeline.py), so the loss head's HBM traffic is paid once in
the B unit and once in the replay.

Schedule: grid (token_blocks, vocab_blocks), vocab innermost, carrying the
running max / sum-of-exp / picked-target-logit in VMEM scratch; the lse and
target-logit rows ([tokens, 1]) are written on the last vocab step. Backward
recomputes each tile's logits from the saved lse (two kernels, flash-style:
`dh` accumulates over vocab tiles in VMEM and writes once per token block;
`dW` accumulates over token blocks and writes once per vocab tile). Logits
never exist in HBM at ANY chunk granularity.

Parity contract vs `fused_ce_sum_count` (tests/test_pallas_ce.py):
- loss_sum / count: BIT-equal fp32 — the kernel runs the identical update
  formulas at the same vocab-block width (V/num_chunks), the per-token
  statistics are elementwise across tokens (token blocking cannot reorder
  them), and the final masked sum is the same XLA epilogue.
- dh: bit-equal (same per-row fold order over vocab tiles).
- dW: pinned tolerance — the kernel folds token blocks sequentially where
  the XLA path does one einsum per chunk over all tokens.

`interpret=` gating is ops/pallas_common.py's: interpreted off-TPU only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llama_pipeline_parallel_tpu.ops.cross_entropy import IGNORE_INDEX
from llama_pipeline_parallel_tpu.ops.pallas_common import (
    compiler_params,
    interpret_mode,
    token_block,
)
from llama_pipeline_parallel_tpu.utils import trace

# every kernel here: outer grid axis independent, inner axis accumulates
_COMPILER_PARAMS = compiler_params("parallel", "arbitrary")

def _token_block(n: int, block_tokens: int | None) -> int:
    return token_block(n, block_tokens)


def _check_shapes(w: jnp.ndarray, num_chunks: int) -> int:
    d, v = w.shape
    if v % num_chunks:
        raise ValueError(f"vocab {v} not divisible by num_chunks={num_chunks}")
    return v // num_chunks


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(h_ref, w_ref, t_ref, lse_ref, tgt_ref, m_scr, z_scr, p_scr,
                *, block_v):
    vi = pl.program_id(1)
    n_v = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        z_scr[:] = jnp.zeros_like(z_scr)
        p_scr[:] = jnp.zeros_like(p_scr)

    # the [bn, bv] fp32 logits tile — VMEM-resident, never written to HBM
    logits = jnp.dot(h_ref[...], w_ref[...],
                     preferred_element_type=jnp.float32)
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    z_scr[:] = jnp.broadcast_to(
        z_scr[:, :1] * jnp.exp(m_prev - m_new)
        + jnp.exp(logits - m_new).sum(axis=-1, keepdims=True), z_scr.shape)
    li = t_ref[...] - vi * block_v                       # [bn, 1] int32
    owned = (li >= 0) & (li < block_v)
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    picked = jnp.where(col == li, logits, 0.0).sum(axis=-1, keepdims=True)
    p_scr[:] = jnp.broadcast_to(
        jnp.where(owned, picked, p_scr[:, :1]), p_scr.shape)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(vi == n_v - 1)
    def _finalize():
        lse_ref[...] = m_scr[:, :1] + jnp.log(z_scr[:, :1])
        tgt_ref[...] = p_scr[:, :1]


def _fwd_stats(hN, w, safe_t, num_chunks, block_tokens):
    """lse / picked-target-logit rows ([n] fp32 each) of the fused head."""
    n, d = hN.shape
    bv = _check_shapes(w, num_chunks)
    bn = _token_block(n, block_tokens)
    row = lambda ni, vi: (ni, 0)
    lse, tgt = pl.pallas_call(
        functools.partial(_fwd_kernel, block_v=bv),
        grid=(n // bn, num_chunks),
        in_specs=[
            pl.BlockSpec((bn, d), row),
            pl.BlockSpec((d, bv), lambda ni, vi: (0, vi)),
            pl.BlockSpec((bn, 1), row),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), row),
            pl.BlockSpec((bn, 1), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, 128), jnp.float32),
            pltpu.VMEM((bn, 128), jnp.float32),
            pltpu.VMEM((bn, 128), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        name=trace.KERNEL_CE_FWD,
        interpret=interpret_mode(),
    )(hN, w, safe_t[:, None])
    return lse[:, 0], tgt[:, 0]


def _flatten(h, targets):
    return h.reshape(-1, h.shape[-1]), targets.reshape(-1)


def _forward(h, w, targets, num_chunks, block_tokens):
    hN, tN = _flatten(h, targets)
    valid = tN != IGNORE_INDEX
    safe_t = jnp.where(valid, tN, 0).astype(jnp.int32)
    lse, tgt = _fwd_stats(hN, w, safe_t, num_chunks, block_tokens)
    # same XLA epilogue as ops/cross_entropy.py — the bit-parity contract
    loss_sum = jnp.where(valid, lse - tgt, 0.0).sum()
    return loss_sum, valid.sum(), lse, valid


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _tile_grad(logits, t_ref, s_ref, lse_ref, off, block_v, dtype):
    """d(loss_sum)/d(logits) tile = (softmax - onehot) * valid*ct, cast to
    the compute dtype BEFORE the matmuls (mirrors the XLA backward)."""
    p = jnp.exp(logits - lse_ref[...])
    li = t_ref[...] - off
    owned = (li >= 0) & (li < block_v)
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    onehot = ((col == li) & owned).astype(jnp.float32)
    return ((p - onehot) * s_ref[...]).astype(dtype)


def _dh_kernel(h_ref, w_ref, t_ref, lse_ref, s_ref, dh_ref, dh_scr,
               *, block_v, g_dtype):
    vi = pl.program_id(1)
    n_v = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        dh_scr[:] = jnp.zeros_like(dh_scr)

    logits = jnp.dot(h_ref[...], w_ref[...],
                     preferred_element_type=jnp.float32)
    g = _tile_grad(logits, t_ref, s_ref, lse_ref, vi * block_v, block_v,
                   g_dtype)
    dh_scr[:] += jax.lax.dot_general(
        g, w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(vi == n_v - 1)
    def _finalize():
        dh_ref[...] = dh_scr[:]


def _dw_kernel(h_ref, w_ref, t_ref, lse_ref, s_ref, dw_ref, dw_scr,
               *, block_v, g_dtype):
    vi = pl.program_id(0)
    ni = pl.program_id(1)
    n_n = pl.num_programs(1)

    @pl.when(ni == 0)
    def _init():
        dw_scr[:] = jnp.zeros_like(dw_scr)

    logits = jnp.dot(h_ref[...], w_ref[...],
                     preferred_element_type=jnp.float32)
    g = _tile_grad(logits, t_ref, s_ref, lse_ref, vi * block_v, block_v,
                   g_dtype)
    dw_scr[:] += jax.lax.dot_general(
        h_ref[...], g, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ni == n_n - 1)
    def _finalize():
        dw_ref[...] = dw_scr[:]


def _backward(h, w, targets, lse, valid, ct_loss, num_chunks, block_tokens):
    hN, tN = _flatten(h, targets)
    n, d = hN.shape
    v = w.shape[1]
    bv = _check_shapes(w, num_chunks)
    bn = _token_block(n, block_tokens)
    safe_t = jnp.where(valid, tN, 0).astype(jnp.int32)[:, None]
    svec = (valid.astype(jnp.float32) * ct_loss)[:, None]
    lse2 = lse[:, None]
    common = dict(block_v=bv, g_dtype=h.dtype)
    row = lambda ni, vi: (ni, 0)
    dh = pl.pallas_call(
        functools.partial(_dh_kernel, **common),
        grid=(n // bn, num_chunks),
        in_specs=[
            pl.BlockSpec((bn, d), row),
            pl.BlockSpec((d, bv), lambda ni, vi: (0, vi)),
            pl.BlockSpec((bn, 1), row),
            pl.BlockSpec((bn, 1), row),
            pl.BlockSpec((bn, 1), row),
        ],
        out_specs=pl.BlockSpec((bn, d), row),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        name=trace.KERNEL_CE_BWD_DH,
        interpret=interpret_mode(),
    )(hN, w, safe_t, lse2, svec)
    # dW: vocab tiles outer, token blocks inner (accumulated in VMEM).
    row_t = lambda vi, ni: (ni, 0)
    dw = pl.pallas_call(
        functools.partial(_dw_kernel, **common),
        grid=(num_chunks, n // bn),
        in_specs=[
            pl.BlockSpec((bn, d), row_t),
            pl.BlockSpec((d, bv), lambda vi, ni: (0, vi)),
            pl.BlockSpec((bn, 1), row_t),
            pl.BlockSpec((bn, 1), row_t),
            pl.BlockSpec((bn, 1), row_t),
        ],
        out_specs=pl.BlockSpec((d, bv), lambda vi, ni: (0, vi)),
        out_shape=jax.ShapeDtypeStruct((d, v), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d, bv), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        name=trace.KERNEL_CE_BWD_DW,
        interpret=interpret_mode(),
    )(hN, w, safe_t, lse2, svec)
    return dh.astype(h.dtype).reshape(h.shape), dw.astype(w.dtype)


# ---------------------------------------------------------------------------
# Public op with custom VJP (drop-in for fused_ce_sum_count)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def pallas_ce_sum_count(h: jnp.ndarray, w: jnp.ndarray, targets: jnp.ndarray,
                        num_chunks: int = 8, block_tokens: int | None = None
                        ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(loss_sum fp32, valid count int32) of a fused h @ w classifier —
    `fused_ce_sum_count`'s signature and semantics, Pallas execution
    (`kernels.ce: pallas`). `num_chunks` is the vocab tile count (the
    bit-parity anchor: the same width the XLA scan uses); `block_tokens`
    pins the token-block height (default: largest of {256..8} dividing the
    flattened token count). On TPU, size num_chunks so the kernel's VMEM
    blocks fit (~the [d, V/chunks] weight tile + the [bn, V/chunks] fp32
    logits tile): at d=8192/V=32000 that means hundreds of chunks (250 ->
    lane-exact 128-wide tiles), NOT the 8 the XLA scan typically uses —
    and never 1, which holds the whole [d, V] weight as one block.
    Interpret mode (off-TPU) has no such limit."""
    loss_sum, count, _, _ = _forward(h, w, targets, num_chunks, block_tokens)
    return loss_sum, count


def _vjp_fwd(h, w, targets, num_chunks, block_tokens):
    loss_sum, count, lse, valid = _forward(h, w, targets, num_chunks,
                                           block_tokens)
    return (loss_sum, count), (h, w, targets, lse, valid)


def _vjp_bwd(num_chunks, block_tokens, res, cts):
    ct_loss, _ = cts  # count is integer-valued: no cotangent
    h, w, targets, lse, valid = res
    dh, dw = _backward(h, w, targets, lse, valid, ct_loss, num_chunks,
                       block_tokens)
    return dh, dw, None


pallas_ce_sum_count.defvjp(_vjp_fwd, _vjp_bwd)


# ---------------------------------------------------------------------------
# Analytic traffic model (bench.py extra:kernel-ce; docs/KERNELS.md)
# ---------------------------------------------------------------------------

def ce_head_traffic_bytes(tokens: int, hidden: int, vocab: int,
                          num_chunks: int) -> int:
    """HBM bytes ONE loss-head fwd+bwd moves through logits-block and
    dh-accumulator buffers on the XLA path — the traffic the Pallas kernel
    keeps in VMEM. Per chunk the scan writes + reads a [tokens, V/chunks]
    fp32 logits block in forward, recomputes it in backward (write + read
    again), and — when chunked — the backward scan carries the
    [tokens, hidden] fp32 dh accumulator (read + write per chunk; at
    num_chunks=1 the XLA twin is the dense head, which has no scan and no
    accumulator). The kernel's own unavoidable traffic (h and W tiles,
    [tokens] stats) is common to both paths and excluded — this is the
    MODELED SAVING, the number bench.py prints next to the measured
    step-time delta."""
    logits_block = tokens * (vocab // num_chunks) * 4
    dh_acc = tokens * hidden * 4 if num_chunks > 1 else 0
    return num_chunks * (4 * logits_block + 2 * dh_acc)
