"""Ring attention: exact causal attention over a sequence-sharded (`sp`) axis.

Context parallelism is absent from the reference (SURVEY.md §5.7 — sequence
length fixed at 512, O(L^2) materialized masks); here it is first-class: each
`sp` rank holds a contiguous sequence slab of q/k/v, KV slabs rotate around
the ICI ring via `jax.lax.ppermute`, and per-slab partial results merge
through a streaming log-sum-exp combine. Per-rank memory is O(L/n); the
attention stays EXACT (this is ring attention, not a sliding-window
approximation).

The VJP is custom at the RING level: the backward pass re-rotates KV (and
carries travelling dk/dv accumulators that arrive home after a full loop)
instead of saving per-step slabs — autodiff through the forward scan would
have stashed every rotated KV copy, reconstructing the full sequence per rank
and defeating the point.

Inner per-slab math has two backends sharing the flash kernels' offset
contract (q_offset/kv_offset):
- "exact": jnp einsum path, runs anywhere (CPU-mesh tests);
- "flash": the Pallas kernels from ops/flash_attention.py (TPU).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from llama_pipeline_parallel_tpu.ops import flash_attention as fa
from llama_pipeline_parallel_tpu.parallel.mesh import AXIS_SP
from llama_pipeline_parallel_tpu.utils import trace

NEG_INF = fa.NEG_INF


# ---------------------------------------------------------------------------
# Per-slab forward/backward (exact backend); [b, h, s, hd] layout throughout
# ---------------------------------------------------------------------------

def _seg_mask_exact(s, seg_q, seg_kv):
    """Cross-segment masking for packed rows (same rule as the flash
    kernels' _seg_tile_mask): a score survives only where q and kv carry the
    SAME nonzero segment id. seg_* are [b, s, 1] int32 (0 = pad)."""
    q_ids = seg_q[:, None, :, :]                      # [b, 1, sq, 1]
    k_ids = seg_kv[:, :, 0][:, None, None, :]         # [b, 1, 1, skv]
    ok = (q_ids == k_ids) & (k_ids != 0)
    return jnp.where(ok, s, NEG_INF)


def _slab_fwd_exact(q, k, v, *, causal, scale, q_offset, kv_offset,
                    seg_q=None, seg_kv=None):
    """-> (out [b,h,sq,hd] f32, lse [b,h,sq,1] f32); empty rows -> (0, NEG_INF)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    if causal:
        qpos = q_offset + jnp.arange(q.shape[2])[:, None]
        kpos = kv_offset + jnp.arange(k.shape[2])[None, :]
        s = jnp.where((qpos >= kpos)[None, None], s, NEG_INF)
    if seg_q is not None:
        s = _seg_mask_exact(s, seg_q, seg_kv)
    m = s.max(axis=-1, keepdims=True)
    nonempty = m > NEG_INF / 2
    p = jnp.where(nonempty, jnp.exp(s - jnp.where(nonempty, m, 0.0)), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    safe_l = jnp.where(l > 0.0, l, 1.0)
    out = jnp.where(l > 0.0, out / safe_l, 0.0)
    lse = jnp.where(l > 0.0, m + jnp.log(safe_l), NEG_INF)
    return out, lse


def _slab_bwd_exact(q, k, v, do, lse, delta, *, causal, scale, q_offset, kv_offset,
                    seg_q=None, seg_kv=None):
    """Block grads given the GLOBAL row lse (FlashAttention-2 recompute)."""
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)
    if causal:
        qpos = q_offset + jnp.arange(q.shape[2])[:, None]
        kpos = kv_offset + jnp.arange(k.shape[2])[None, :]
        s = jnp.where((qpos >= kpos)[None, None], s, NEG_INF)
    if seg_q is not None:
        s = _seg_mask_exact(s, seg_q, seg_kv)
    p = jnp.where(lse > NEG_INF / 2, jnp.exp(s - lse), 0.0)  # [b,h,q,k]
    dof = do.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
    dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vf)
    ds = p * (dp - delta)
    dq = scale * jnp.einsum("bhqk,bhkd->bhqd", ds, kf)
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)  # qf carries the scale
    return dq, dk, dv


def _slab_fwd(backend, q, k, v, *, seg_q=None, seg_kv=None, **kw):
    if backend == "flash":
        # adaptive blocks: a 6144-seq sp=4 run has 1536-long slabs — tile
        # with 768 blocks instead of abandoning the flash backend
        return fa._fwd(q, k, v, block_q=fa._auto_block(q.shape[2]),
                       block_k=fa._auto_block(k.shape[2]),
                       segments_q=seg_q, segments_kv=seg_kv, **kw)
    return _slab_fwd_exact(q, k, v, seg_q=seg_q, seg_kv=seg_kv, **kw)


def _slab_bwd(backend, q, k, v, do, lse, delta, *, seg_q=None, seg_kv=None, **kw):
    if backend == "flash":
        # fa._bwd consumes/produces [b,h,s,hd] with full heads
        return fa._bwd(q, k, v, delta, lse, do,
                       block_q=fa._auto_block(q.shape[2]),
                       block_k=fa._auto_block(k.shape[2]),
                       segments_q=seg_q, segments_kv=seg_kv, **kw)
    return _slab_bwd_exact(q, k, v, do, lse, delta, seg_q=seg_q, seg_kv=seg_kv, **kw)


# ---------------------------------------------------------------------------
# The ring (called INSIDE shard_map with axis_name bound)
# ---------------------------------------------------------------------------

def _rotate(xs, axis_name):
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    with jax.named_scope(trace.SCOPE_SP_COLLECTIVE):
        return tuple(jax.lax.ppermute(x, axis_name, perm) for x in xs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _ring(q, k, v, seg, causal, scale, axis_name, backend):
    out, _ = _ring_fwd_impl(q, k, v, seg, causal, scale, axis_name, backend)
    return out


def _ring_fwd_impl(q, k, v, seg, causal, scale, axis_name, backend):
    """`seg`: this rank's [b, s_local, 1] int32 segment-id slab (packing),
    or None. The kv copy rotates around the ring WITH its k/v slabs so the
    cross-segment test always pairs positions of the slab actually visiting;
    the q copy stays home."""
    n = jax.lax.axis_size(axis_name)
    s_local = q.shape[2]
    # Slab offsets only gate CAUSAL masking (segment masking travels with the
    # seg ids). Skip axis_index entirely when non-causal: the dead equation
    # survives DCE through the custom_vjp call and older jax then lowers it
    # to a bare PartitionId the SPMD partitioner rejects.
    rank = jax.lax.axis_index(axis_name) if causal else 0
    q_off = rank * s_local

    b, h, sq, hd = q.shape
    m0 = jnp.full((b, h, sq, 1), NEG_INF, jnp.float32)
    w0 = jnp.zeros((b, h, sq, hd), jnp.float32)
    z0 = jnp.zeros((b, h, sq, 1), jnp.float32)

    def step(carry, t):
        k_t, v_t, seg_t, m, w, z = carry
        src = (rank - t) % n
        o_t, lse_t = _slab_fwd(backend, q, k_t, v_t, causal=causal, scale=scale,
                               q_offset=q_off, kv_offset=src * s_local,
                               seg_q=seg, seg_kv=seg_t)
        m_new = jnp.maximum(m, lse_t)
        # empty slabs have lse_t == NEG_INF -> weight exactly 0
        alpha = jnp.where(m > NEG_INF / 2, jnp.exp(m - m_new), 0.0)
        beta = jnp.where(lse_t > NEG_INF / 2, jnp.exp(lse_t - m_new), 0.0)
        w = w * alpha + o_t * beta
        z = z * alpha + beta
        if seg is None:
            k_t, v_t = _rotate((k_t, v_t), axis_name)
        else:
            k_t, v_t, seg_t = _rotate((k_t, v_t, seg_t), axis_name)
        return (k_t, v_t, seg_t, m_new, w, z), None

    (k_n, v_n, seg_n, m, w, z), _ = jax.lax.scan(
        step, (k, v, seg, m0, w0, z0), jnp.arange(n))
    safe_z = jnp.where(z > 0.0, z, 1.0)
    out = jnp.where(z > 0.0, w / safe_z, 0.0).astype(q.dtype)
    lse = jnp.where(z > 0.0, m + jnp.log(safe_z), NEG_INF)
    return out, lse


def _ring_vjp_fwd(q, k, v, seg, causal, scale, axis_name, backend):
    out, lse = _ring_fwd_impl(q, k, v, seg, causal, scale, axis_name, backend)
    return out, (q, k, v, seg, out, lse)


def _ring_vjp_bwd(causal, scale, axis_name, backend, res, dout):
    q, k, v, seg, out, lse = res
    n = jax.lax.axis_size(axis_name)
    s_local = q.shape[2]
    rank = jax.lax.axis_index(axis_name) if causal else 0  # see fwd note
    q_off = rank * s_local
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)

    def step(carry, t):
        k_t, v_t, seg_t, dk_t, dv_t, dq = carry
        src = (rank - t) % n
        dq_b, dk_b, dv_b = _slab_bwd(
            backend, q, k_t, v_t, dout, lse, delta, causal=causal, scale=scale,
            q_offset=q_off, kv_offset=src * s_local, seg_q=seg, seg_kv=seg_t)
        dq = dq + dq_b
        dk_t = dk_t + dk_b
        dv_t = dv_t + dv_b
        # dk/dv accumulators travel WITH their kv slab (and its segment ids);
        # after the n-th rotation every slab (and its finished gradient) is
        # home again.
        if seg is None:
            k_t, v_t, dk_t, dv_t = _rotate((k_t, v_t, dk_t, dv_t), axis_name)
        else:
            k_t, v_t, seg_t, dk_t, dv_t = _rotate(
                (k_t, v_t, seg_t, dk_t, dv_t), axis_name)
        return (k_t, v_t, seg_t, dk_t, dv_t, dq), None

    (_, _, _, dk, dv, dq), _ = jax.lax.scan(
        step, (k, v, seg, dk0, dv0, dq0), jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), None


_ring.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    padding_mask: Any = None,
    *,
    causal: bool = True,
    axis_name: str = AXIS_SP,
    backend: str = "exact",
    q_offset: int = 0,
    kv_offset: int = 0,
) -> jnp.ndarray:
    """Sequence-parallel exact attention; call inside shard_map with the
    sequence dim sharded over `axis_name`.

    Takes/returns [b, s_local, h, hd] (the model's layout). padding_mask
    carries SEGMENT IDS for this rank's slab ([b, s_local] int32, 0 = pad,
    packed examples numbered 1..k — the flash kernel's contract,
    ops/flash_attention.py): when given, the kv segment slab rotates around
    the ring with its k/v so packed examples never attend across pack
    boundaries. For plain right-padded causal batches pass None — causal
    masking already excludes pad keys, and None skips the mask streams.
    GQA callers must expand kv heads first (slab rotation needs uniform
    shapes).
    """
    if q_offset != 0 or kv_offset != 0:
        raise ValueError("ring_attention derives offsets from the sp rank")
    if k.shape[2] != q.shape[2]:
        raise ValueError("ring_attention requires expanded kv heads (GQA: "
                         "repeat kv to q heads before the call)")
    scale = q.shape[-1] ** -0.5
    seg = (None if padding_mask is None
           else jnp.asarray(padding_mask, jnp.int32)[:, :, None])  # [b, s, 1]
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = _ring(qt, kt, vt, seg, causal, scale, axis_name, backend)
    return out.transpose(0, 2, 1, 3)
