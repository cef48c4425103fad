"""Pallas TPU fused RMSNorm -> RoPE -> QKV prologue, with custom VJP.

The per-layer prologue is the hottest non-attention region of the decoder
block after the MLP: the XLA path writes the normed hidden `[n, d]` to HBM,
reads it back three times for the q/k/v projections, then round-trips q and
k once more for the rotary rotation. This kernel does norm, the three
projections, and the rotation in one pass over the token rows — the normed
hidden and the pre-rope q/k never exist in HBM.

Schedule: 1-D grid over token blocks; the weight shards (wq/wk/wv) are held
fully VMEM-resident per grid step, which sizes the kernel for TP-SHARDED
layers (a 7B layer at tp=8 holds ~4 MiB of bf16 weight per projection) or
small models — `fused_prologue` is gated behind `kernels.prologue: pallas`
and the bench row measures, not asserts, the win. Backward is flash-style
two kernels: `dhidden` (rope-transpose + the three transposed projections,
per token block) and `dW` (hidden recompute + outer products, accumulated
in VMEM over the whole grid, written once) — so under the zb1 split
backward, DCE keeps only the dhidden kernel in the B unit and only the dW
kernel in the W replay (parallel/pipeline.py).

Numerics match the composed ops/rmsnorm.py -> ops/rope.py -> matmul
reference (models/llama/model.py decoder_layer): fp32 variance with
input-dtype scale, HF `rotate_half` convention, fp32 matmul accumulation
rounded once to the compute dtype. bf16 forward is bit-equal; fp32 is
within ~1 ulp (a single blocked-vs-unblocked matmul rounding) — the pinned
tolerance in tests/test_pallas_prologue.py.

TP composition: the reference places `tp_copy` (identity fwd / psum bwd)
between the norm and the column-sharded projections. Passing `tp_axis`
reproduces it exactly: the forward emits no collective, and the backward
psums dhidden across the tp axis BEFORE the norm backward, so norm/embed
grads stay correctly summed (parallel/tp.py's contract).

cos/sin are positional data, not parameters: their cotangents are zero
(the pipeline differentiates w.r.t. params and stage inputs only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llama_pipeline_parallel_tpu.ops.pallas_common import (
    compiler_params,
    interpret_mode,
    token_block,
)
from llama_pipeline_parallel_tpu.ops.rmsnorm import rms_norm
from llama_pipeline_parallel_tpu.utils import trace

def _token_block(n: int, block_tokens: int | None) -> int:
    return token_block(n, block_tokens)


def _norm_block(x, w_norm, eps):
    """ops/rmsnorm.py numerics on one [bn, d] tile: fp32 variance,
    input-dtype scale."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    variance = jnp.mean(xf * xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(variance + eps)
    return (w_norm.astype(jnp.float32) * xf).astype(dtype)


def _rope_block(x, cos, sin, head_dim):
    """HF rotate_half rotation on a [bn, heads*hd] tile (cos/sin [bn, hd]),
    in the input dtype — ops/rope.py numerics."""
    bn, width = x.shape
    half = head_dim // 2
    x3 = x.reshape(bn, width // head_dim, head_dim)
    rot = jnp.concatenate([-x3[..., half:], x3[..., :half]], axis=-1)
    return (x3 * cos[:, None, :] + rot * sin[:, None, :]).reshape(bn, width)


def _unrope_block(dy, cos, sin, head_dim):
    """Transpose of `_rope_block`: rotate_half's adjoint is
    R^T(y) = concat(y2, -y1)."""
    bn, width = dy.shape
    half = head_dim // 2
    y3 = dy.reshape(bn, width // head_dim, head_dim)
    ys = y3 * sin[:, None, :]
    rt = jnp.concatenate([ys[..., half:], -ys[..., :half]], axis=-1)
    return (y3 * cos[:, None, :] + rt).reshape(bn, width)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, nw_ref, wq_ref, wk_ref, wv_ref, cos_ref, sin_ref,
                q_ref, k_ref, v_ref, *, eps, head_dim):
    dt = x_ref.dtype
    hidden = _norm_block(x_ref[...], nw_ref[0, :], eps)
    proj = lambda w_ref: jnp.dot(
        hidden, w_ref[...], preferred_element_type=jnp.float32).astype(dt)
    cos, sin = cos_ref[...], sin_ref[...]
    q_ref[...] = _rope_block(proj(wq_ref), cos, sin, head_dim).astype(dt)
    k_ref[...] = _rope_block(proj(wk_ref), cos, sin, head_dim).astype(dt)
    v_ref[...] = proj(wv_ref)


def _fwd(xN, norm_w, wq, wk, wv, cosN, sinN, eps, head_dim, block_tokens):
    n, d = xN.shape
    dq, dkv = wq.shape[1], wk.shape[1]
    bn = _token_block(n, block_tokens)
    row = lambda ni: (ni, 0)
    full = lambda ni: (0, 0)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, head_dim=head_dim),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), row),
            pl.BlockSpec((1, d), full),
            pl.BlockSpec((d, dq), full),
            pl.BlockSpec((d, dkv), full),
            pl.BlockSpec((d, dkv), full),
            pl.BlockSpec((bn, head_dim), row),
            pl.BlockSpec((bn, head_dim), row),
        ],
        out_specs=[
            pl.BlockSpec((bn, dq), row),
            pl.BlockSpec((bn, dkv), row),
            pl.BlockSpec((bn, dkv), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, dq), xN.dtype),
            jax.ShapeDtypeStruct((n, dkv), xN.dtype),
            jax.ShapeDtypeStruct((n, dkv), xN.dtype),
        ],
        compiler_params=compiler_params("parallel"),
        name=trace.KERNEL_PROLOGUE_FWD,
        interpret=interpret_mode(),
    )(xN, norm_w[None, :], wq, wk, wv, cosN, sinN)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _dhidden_kernel(dq_ref, dk_ref, dv_ref, wq_ref, wk_ref, wv_ref,
                    cos_ref, sin_ref, dh_ref, *, head_dim):
    cos, sin = cos_ref[...], sin_ref[...]
    dq_pre = _unrope_block(dq_ref[...], cos, sin, head_dim)
    dk_pre = _unrope_block(dk_ref[...], cos, sin, head_dim)
    tdot = lambda a, w_ref: jax.lax.dot_general(
        a, w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dh_ref[...] = (tdot(dq_pre, wq_ref) + tdot(dk_pre, wk_ref)
                   + tdot(dv_ref[...], wv_ref))


def _dw_kernel(x_ref, nw_ref, dq_ref, dk_ref, dv_ref, cos_ref, sin_ref,
               dwq_ref, dwk_ref, dwv_ref, dwq_scr, dwk_scr, dwv_scr,
               *, eps, head_dim):
    ni = pl.program_id(0)
    n_n = pl.num_programs(0)

    @pl.when(ni == 0)
    def _init():
        dwq_scr[:] = jnp.zeros_like(dwq_scr)
        dwk_scr[:] = jnp.zeros_like(dwk_scr)
        dwv_scr[:] = jnp.zeros_like(dwv_scr)

    hidden = _norm_block(x_ref[...], nw_ref[0, :], eps)
    cos, sin = cos_ref[...], sin_ref[...]
    dq_pre = _unrope_block(dq_ref[...], cos, sin, head_dim)
    dk_pre = _unrope_block(dk_ref[...], cos, sin, head_dim)
    outer = lambda g: jax.lax.dot_general(
        hidden, g, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dwq_scr[:] += outer(dq_pre)
    dwk_scr[:] += outer(dk_pre)
    dwv_scr[:] += outer(dv_ref[...])

    @pl.when(ni == n_n - 1)
    def _finalize():
        dwq_ref[...] = dwq_scr[:]
        dwk_ref[...] = dwk_scr[:]
        dwv_ref[...] = dwv_scr[:]


def _bwd(xN, norm_w, wq, wk, wv, cosN, sinN, dqN, dkN, dvN, eps, head_dim,
         tp_axis, block_tokens):
    n, d = xN.shape
    dq_w, dkv_w = wq.shape[1], wk.shape[1]
    bn = _token_block(n, block_tokens)
    dt = xN.dtype
    dqN, dkN, dvN = dqN.astype(dt), dkN.astype(dt), dvN.astype(dt)
    row = lambda ni: (ni, 0)
    full = lambda ni: (0, 0)
    dhidden = pl.pallas_call(
        functools.partial(_dhidden_kernel, head_dim=head_dim),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, dq_w), row),
            pl.BlockSpec((bn, dkv_w), row),
            pl.BlockSpec((bn, dkv_w), row),
            pl.BlockSpec((d, dq_w), full),
            pl.BlockSpec((d, dkv_w), full),
            pl.BlockSpec((d, dkv_w), full),
            pl.BlockSpec((bn, head_dim), row),
            pl.BlockSpec((bn, head_dim), row),
        ],
        out_specs=pl.BlockSpec((bn, d), row),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        compiler_params=compiler_params("parallel"),
        name=trace.KERNEL_PROLOGUE_BWD_DX,
        interpret=interpret_mode(),
    )(dqN, dkN, dvN, wq, wk, wv, cosN, sinN)
    dwq, dwk, dwv = pl.pallas_call(
        functools.partial(_dw_kernel, eps=eps, head_dim=head_dim),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), row),
            pl.BlockSpec((1, d), full),
            pl.BlockSpec((bn, dq_w), row),
            pl.BlockSpec((bn, dkv_w), row),
            pl.BlockSpec((bn, dkv_w), row),
            pl.BlockSpec((bn, head_dim), row),
            pl.BlockSpec((bn, head_dim), row),
        ],
        out_specs=[
            pl.BlockSpec((d, dq_w), full),
            pl.BlockSpec((d, dkv_w), full),
            pl.BlockSpec((d, dkv_w), full),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, dq_w), jnp.float32),
            jax.ShapeDtypeStruct((d, dkv_w), jnp.float32),
            jax.ShapeDtypeStruct((d, dkv_w), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((d, dq_w), jnp.float32),
            pltpu.VMEM((d, dkv_w), jnp.float32),
            pltpu.VMEM((d, dkv_w), jnp.float32),
        ],
        compiler_params=compiler_params("arbitrary"),
        name=trace.KERNEL_PROLOGUE_BWD_DW,
        interpret=interpret_mode(),
    )(xN, norm_w[None, :], dqN, dkN, dvN, cosN, sinN)
    # The reference's tp_copy sits between norm and projections: its
    # backward psums the hidden cotangent across tp BEFORE the norm
    # backward, so the (replicated) norm/embed grads are full sums.
    dh_dt = dhidden.astype(dt)
    if tp_axis is not None:
        dh_dt = jax.lax.psum(dh_dt, tp_axis)
    # norm backward: the AD of ops/rmsnorm.py itself — identical graph to
    # the composed reference's norm backward
    _, norm_vjp = jax.vjp(lambda xx, ww: rms_norm(xx, ww, eps), xN, norm_w)
    dx, dnw = norm_vjp(dh_dt)
    return dx, dnw, dwq.astype(wq.dtype), dwk.astype(wk.dtype), \
        dwv.astype(wv.dtype)


# ---------------------------------------------------------------------------
# Public op with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _prologue(xN, norm_w, wq, wk, wv, cosN, sinN, eps, head_dim, tp_axis,
              block_tokens):
    return _fwd(xN, norm_w, wq, wk, wv, cosN, sinN, eps, head_dim,
                block_tokens)


def _prologue_fwd(xN, norm_w, wq, wk, wv, cosN, sinN, eps, head_dim, tp_axis,
                  block_tokens):
    out = _fwd(xN, norm_w, wq, wk, wv, cosN, sinN, eps, head_dim,
               block_tokens)
    return out, (xN, norm_w, wq, wk, wv, cosN, sinN)


def _prologue_bwd(eps, head_dim, tp_axis, block_tokens, res, cts):
    xN, norm_w, wq, wk, wv, cosN, sinN = res
    dqN, dkN, dvN = cts
    dx, dnw, dwq, dwk, dwv = _bwd(xN, norm_w, wq, wk, wv, cosN, sinN,
                                  dqN, dkN, dvN, eps, head_dim, tp_axis,
                                  block_tokens)
    # cos/sin are positional data (never differentiated): zero cotangents
    return (dx, dnw, dwq, dwk, dwv, jnp.zeros_like(cosN),
            jnp.zeros_like(sinN))


_prologue.defvjp(_prologue_fwd, _prologue_bwd)


def fused_prologue(
    x: jnp.ndarray,
    norm_w: jnp.ndarray,
    wq: jnp.ndarray,
    wk: jnp.ndarray,
    wv: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    *,
    eps: float,
    head_dim: int,
    tp_axis: str | None = None,
    block_tokens: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused rms_norm(x) -> (q|k|v) projection -> RoPE(q, k).

    x: [b, s, d]; norm_w: [d]; wq: [d, h_local*hd]; wk/wv: [d, kv_local*hd]
    (LOCAL shards under tp — head counts derive from the shard widths, like
    decoder_layer); cos/sin: [b, s, hd]. Returns q [b, s, h_local, hd],
    k [b, s, kv_local, hd], v [b, s, kv_local, hd] with RoPE applied to
    q and k — exactly the tensors the attention call consumes.
    """
    b, s, d = x.shape
    if wq.shape[1] % head_dim or wk.shape[1] % head_dim:
        raise ValueError(
            f"projection widths ({wq.shape[1]}, {wk.shape[1]}) must be "
            f"multiples of head_dim={head_dim}")
    if head_dim % 2:
        raise ValueError(f"head_dim must be even for rotate_half, got {head_dim}")
    if wk.shape != wv.shape:
        raise ValueError(f"wk {wk.shape} and wv {wv.shape} must match")
    n = b * s
    q, k, v = _prologue(
        x.reshape(n, d), norm_w, wq, wk, wv,
        cos.reshape(n, head_dim), sin.reshape(n, head_dim),
        eps, head_dim, tp_axis, block_tokens)
    h_local = wq.shape[1] // head_dim
    kv_local = wk.shape[1] // head_dim
    return (q.reshape(b, s, h_local, head_dim),
            k.reshape(b, s, kv_local, head_dim),
            v.reshape(b, s, kv_local, head_dim))


def prologue_traffic_bytes(tokens: int, hidden: int, q_width: int,
                           kv_width: int, dtype_bytes: int = 2) -> int:
    """HBM bytes ONE prologue fwd+bwd saves vs the composed XLA path: the
    normed hidden written once + read three times (projections) forward and
    recomputed/re-read in backward, plus the pre-rope q/k round trip the
    separate rotation pays. Common traffic (x, weights, final q/k/v) is
    excluded — the modeled saving bench.py's extra:kernel-prologue row
    prints next to the measured delta."""
    hidden_bytes = tokens * hidden * dtype_bytes
    qk_bytes = tokens * (q_width + kv_width) * dtype_bytes
    # fwd: hidden write + 3 reads; bwd: same for the recompute; rope: q/k
    # written pre-rope + read + written again (fwd), mirrored in bwd
    return 2 * (4 * hidden_bytes) + 2 * (2 * qk_bytes)
