"""Ring attention (sp context parallelism) vs single-device full attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from llama_pipeline_parallel_tpu.ops.attention import attention
from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig, make_mesh
from llama_pipeline_parallel_tpu.parallel.ring_attention import ring_attention


def rand_qkv(b, s, h, hd, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, s, h, hd), jnp.float32) for _ in range(3))


def run_ring(q, k, v, sp, causal=True):
    mesh = make_mesh(MeshConfig(sp=sp))
    fn = shard_map(
        lambda q, k, v: ring_attention(q, k, v, causal=causal),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
        check_vma=False,
    )
    return jax.jit(fn)(q, k, v)


# sp=2 (minimal ring) and sp=8 (whole-mesh ring, every rank both ends of
# the rotation) are the boundary rows; the interior sp=4 adds no new
# block-order case and rides the round gate.
@pytest.mark.parametrize("sp", [2, pytest.param(4, marks=pytest.mark.slow), 8])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_full(devices, sp, causal):
    q, k, v = rand_qkv(b=2, s=64, h=2, hd=16)
    full = attention(q, k, v, None, causal=causal)
    ring = run_ring(q, k, v, sp=sp, causal=causal)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("sp", [2, 4])
def test_ring_gradients_match_full(devices, sp):
    q, k, v = rand_qkv(b=1, s=32, h=2, hd=8)

    def loss_full(q, k, v):
        return (attention(q, k, v, None, causal=True).astype(jnp.float32) ** 2).sum()

    mesh = make_mesh(MeshConfig(sp=sp))

    def local(q, k, v):
        out = ring_attention(q, k, v, causal=True)
        # psum over sp: each rank contributes its local slab's loss
        return jax.lax.psum((out.astype(jnp.float32) ** 2).sum(), "sp")

    def loss_ring(q, k, v):
        fn = shard_map(local, mesh=mesh,
                       in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
                       out_specs=P(), check_vma=False)
        return fn(q, k, v)

    g_full = jax.grad(loss_full, (0, 1, 2))(q, k, v)
    g_ring = jax.grad(jax.jit(loss_ring), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ring, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3, err_msg=f"d{name}")


def test_ring_flash_backend_matches(devices):
    """The flash (Pallas) backend inside the ring — interpret mode on CPU."""
    q, k, v = rand_qkv(b=1, s=64, h=2, hd=16)
    full = attention(q, k, v, None, causal=True)
    mesh = make_mesh(MeshConfig(sp=4))
    fn = shard_map(
        lambda q, k, v: ring_attention(q, k, v, causal=True, backend="flash"),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False)
    out = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full), rtol=2e-4, atol=2e-4)

    # gradients through the flash backend
    def local(q, k, v):
        o = ring_attention(q, k, v, causal=True, backend="flash")
        return jax.lax.psum((o.astype(jnp.float32) ** 2).sum(), "sp")

    loss_fn = shard_map(local, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                        out_specs=P(), check_vma=False)
    g_ring = jax.grad(jax.jit(loss_fn), (0, 1, 2))(q, k, v)
    g_full = jax.grad(lambda q, k, v: (attention(q, k, v, None, causal=True)
                                       .astype(jnp.float32) ** 2).sum(), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ring, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3, err_msg=f"d{name}")


def make_packed_segments(b, s, seed=5):
    """Random packed rows: 2-3 segments numbered 1..k plus trailing pad
    (the packed collator's mask contract, data/collator.py)."""
    r = np.random.RandomState(seed)
    seg = np.zeros((b, s), np.int32)
    for row in range(b):
        at = 0
        for sid in range(1, int(r.randint(2, 4)) + 1):
            n = int(r.randint(2, max(3, s // 3)))
            if at + n > s - 1:
                break
            seg[row, at:at + n] = sid
            at += n
    return jnp.asarray(seg)


def seg_loss(out, seg):
    """Sum-of-squares over REAL positions only: the exact op softens
    all-masked pad rows to a uniform softmax while the ring emits exact 0
    there — both are dont-cares (pad losses are IGNORE_INDEX-masked), so the
    comparison must not read them."""
    real = (seg != 0)[:, :, None, None]
    return (jnp.where(real, out.astype(jnp.float32), 0.0) ** 2).sum()


@pytest.mark.parametrize("sp", [2, 4])
@pytest.mark.parametrize("backend", ["exact", "flash"])
def test_ring_segments_match_full(devices, sp, backend):
    """Packed segment ids through the ring (the rotating seg slab) agree
    with full-sequence exact attention's pairwise segment mask — forward and
    input gradients, both slab backends."""
    q, k, v = rand_qkv(b=2, s=32, h=2, hd=8, seed=11)
    seg = make_packed_segments(b=2, s=32)
    mesh = make_mesh(MeshConfig(sp=sp))

    def local(q, k, v, seg):
        out = ring_attention(q, k, v, seg, causal=True, backend=backend)
        return jax.lax.psum(seg_loss(out, seg), "sp")

    ring_loss = shard_map(local, mesh=mesh,
                          in_specs=(P(None, "sp"),) * 3 + (P(None, "sp"),),
                          out_specs=P(), check_vma=False)
    full_loss = lambda q, k, v, seg: seg_loss(
        attention(q, k, v, seg, causal=True), seg)

    vr, gr = jax.value_and_grad(jax.jit(ring_loss), (0, 1, 2))(q, k, v, seg)
    vf, gf = jax.value_and_grad(full_loss, (0, 1, 2))(q, k, v, seg)
    np.testing.assert_allclose(float(vr), float(vf), rtol=2e-4)
    for name, a, b in zip("qkv", gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3, err_msg=f"d{name}")


def test_ring_segment_isolation(devices):
    """A segment's outputs are identical whether or not OTHER segments share
    the row — packed examples can't leak across boundaries through the ring
    (including across slab rotations: segments straddle the sp=4 slab cuts)."""
    b, s, h, hd = 1, 32, 2, 8
    q, k, v = rand_qkv(b=b, s=s, h=h, hd=hd, seed=13)
    mesh = make_mesh(MeshConfig(sp=4))

    def run(seg):
        fn = shard_map(
            lambda q, k, v, seg: ring_attention(q, k, v, seg, causal=True),
            mesh=mesh, in_specs=(P(None, "sp"),) * 4,
            out_specs=P(None, "sp"), check_vma=False)
        return np.asarray(jax.jit(fn)(q, k, v, seg))

    seg_ab = np.zeros((b, s), np.int32)
    seg_ab[0, :12], seg_ab[0, 12:26] = 1, 2   # crosses the 8-wide slab cuts
    # the SECOND segment is the leak-sensitive one: causality alone would let
    # its queries (positions 12..25) see segment 1's keys (positions 0..11)
    alone = np.zeros((b, s), np.int32)
    alone[0, 12:26] = 1
    out_packed = run(jnp.asarray(seg_ab))
    out_alone = run(jnp.asarray(alone))
    np.testing.assert_allclose(out_packed[0, 12:26], out_alone[0, 12:26],
                               rtol=2e-5, atol=2e-5)


def test_ring_flash_adaptive_slab_blocks(devices):
    """A 6144-seq sp=4 run hands the flash backend 1536-long slabs — not a
    1024 multiple. The adaptive block selection (fa._auto_block -> 768)
    keeps the flash path instead of erroring (round-3 verdict #5); forward
    parity vs full exact attention (interpret mode, minimal heads to bound
    CPU cost)."""
    q, k, v = rand_qkv(b=1, s=6144, h=1, hd=8, seed=9)
    full = attention(q, k, v, None, causal=True)
    mesh = make_mesh(MeshConfig(sp=4))
    fn = shard_map(
        lambda q, k, v: ring_attention(q, k, v, causal=True, backend="flash"),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False)
    out = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


def test_ring_requires_expanded_kv(devices):
    q, k, v = rand_qkv(b=1, s=32, h=4, hd=8)
    k2 = k[:, :, :2]
    mesh = make_mesh(MeshConfig(sp=2))
    with pytest.raises(ValueError, match="expanded kv"):
        fn = shard_map(lambda q, k, v: ring_attention(q, k, v),
                       mesh=mesh,
                       in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
                       check_vma=False)
        jax.jit(fn)(q, k2, v[:, :, :2])
