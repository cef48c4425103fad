"""Tensor-parallel primitives (Megatron-style f/g pair) for use inside shard_map.

The reference has no tensor parallelism (its `mp_world_size` is a stub that
writes every tensor to shard 0 — reference convert2ckpt.py:16,25-36); here it
is a first-class `tp` mesh axis. Column-parallel qkv/gate/up and row-parallel
wo/down need the classic operator pair:

- `tp_copy` ("f"): identity forward, psum backward — placed where a
  replicated activation fans out into column-sharded matmuls, so the
  replicated-input gradients (and through them the norm/embedding grads)
  are summed across tp ranks.
- `tp_reduce` ("g"): psum forward, identity backward — placed on the
  partial outputs of row-sharded matmuls.

Both are explicit custom-VJP ops because the pipeline's shard_map runs with
replication checking off: nothing would otherwise insert the backward psum,
and gradients of every parameter upstream of a column-parallel matmul would
silently be 1/tp of their true value on each rank.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from llama_pipeline_parallel_tpu.utils import trace


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_copy(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    return x


def _copy_fwd(x, axis_name):
    return x, None


def _copy_bwd(axis_name, _, g):
    with jax.named_scope(trace.SCOPE_TP_COLLECTIVE):
        return (jax.lax.psum(g, axis_name),)


tp_copy.defvjp(_copy_fwd, _copy_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_reduce(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    with jax.named_scope(trace.SCOPE_TP_COLLECTIVE):
        return jax.lax.psum(x, axis_name)


def _reduce_fwd(x, axis_name):
    with jax.named_scope(trace.SCOPE_TP_COLLECTIVE):
        return jax.lax.psum(x, axis_name), None


def _reduce_bwd(axis_name, _, g):
    return (g,)


tp_reduce.defvjp(_reduce_fwd, _reduce_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_max(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Cross-rank max with ZERO gradient — for numerical-stability shifts
    (the subtracted max cancels mathematically, and `lax.pmax` has no
    differentiation rule at all, even under stop_gradient)."""
    with jax.named_scope(trace.SCOPE_TP_COLLECTIVE):
        return jax.lax.pmax(x, axis_name)


def _max_fwd(x, axis_name):
    with jax.named_scope(trace.SCOPE_TP_COLLECTIVE):
        return jax.lax.pmax(x, axis_name), jnp.shape(x)


def _max_bwd(axis_name, shape, g):
    return (jnp.zeros(shape, g.dtype),)


tp_max.defvjp(_max_fwd, _max_bwd)
