"""Ulysses all-to-all sequence parallelism vs full attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from llama_pipeline_parallel_tpu.ops.attention import attention
from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig, make_mesh
from llama_pipeline_parallel_tpu.parallel.ulysses import ulysses_attention


def rand_qkv(b, s, h, hd, h_kv=None, seed=0):
    rng = np.random.RandomState(seed)
    h_kv = h_kv or h
    q = jnp.asarray(rng.randn(b, s, h, hd), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h_kv, hd), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h_kv, hd), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("sp,h_kv", [(2, 4), (4, 4), (4, 2), (2, 1)])
def test_ulysses_matches_full(devices, sp, h_kv):
    q, k, v = rand_qkv(b=2, s=32, h=4, hd=16, h_kv=h_kv)
    full = attention(q, k, v, None, causal=True)
    mesh = make_mesh(MeshConfig(sp=sp))
    fn = shard_map(lambda q, k, v: ulysses_attention(q, k, v),
                   mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                   out_specs=P(None, "sp"), check_vma=False)
    out = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


def test_ulysses_gradients_match(devices):
    q, k, v = rand_qkv(b=1, s=16, h=4, hd=8)
    mesh = make_mesh(MeshConfig(sp=4))

    def loss_full(q, k, v):
        return (attention(q, k, v, None, causal=True) ** 2).sum()

    def local(q, k, v):
        o = ulysses_attention(q, k, v)
        return jax.lax.psum((o ** 2).sum(), "sp")

    loss_sp = shard_map(local, mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                        out_specs=P(), check_vma=False)
    g_sp = jax.grad(jax.jit(loss_sp), (0, 1, 2))(q, k, v)
    g_full = jax.grad(loss_full, (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_sp, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3, err_msg=f"d{name}")


def test_ulysses_with_padding_mask(devices):
    q, k, v = rand_qkv(b=1, s=32, h=4, hd=8)
    mask = np.ones((1, 32), np.int32)
    mask[:, -8:] = 0
    full = attention(q, k, v, jnp.asarray(mask), causal=True)
    mesh = make_mesh(MeshConfig(sp=4))
    fn = shard_map(lambda q, k, v, m: ulysses_attention(q, k, v, m),
                   mesh=mesh, in_specs=(P(None, "sp"),) * 3 + (P(None, "sp"),),
                   out_specs=P(None, "sp"), check_vma=False)
    out = jax.jit(fn)(q, k, v, jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


def test_ulysses_head_divisibility(devices):
    q, k, v = rand_qkv(b=1, s=32, h=6, hd=8)
    mesh = make_mesh(MeshConfig(sp=4))
    with pytest.raises(ValueError, match="divisible"):
        fn = shard_map(lambda q, k, v: ulysses_attention(q, k, v),
                       mesh=mesh, in_specs=(P(None, "sp"),) * 3,
                       out_specs=P(None, "sp"), check_vma=False)
        jax.jit(fn)(q, k, v)
