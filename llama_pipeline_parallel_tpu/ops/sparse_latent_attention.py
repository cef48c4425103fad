"""Pallas TPU attention of queries over the entries each one SELECTED: the
read by token of a latent cache under a learned sparse-attention indexer
(models/latent_moe/).

A full layer's query attends `index_topk` cached entries of its own
choosing, gathered for it into `[queries, K, w]` (`model.full_span`, the
tick). Through XLA the gathered entries are read twice, by the score product
and by the weighted sum, with the float32 scores `[queries, heads, K]`
written and read between them and the probabilities after: 31 of a layer's
45 ms in a 2048-token chunk on the v5e (PERF.md, PR 30). This kernel takes one
query a grid step: its entries `[K, w]` come to VMEM once, scores, softmax
and probabilities never leave it.

    s = q_abs e^T * scale + bias      [heads, K]  float32
    o' = softmax(s) e                 [heads, w]

`q_abs` is the absorbed query `[W_kb^K^T q^N; q^R]` padded with zeros to the
stored entry's width, so the rope columns and the padding ride the one
product; `o'`'s first `kv_lora_rank` columns are the weighted sum of the
latents (the caller cuts the rest). `bias` is 0 for a place that holds a
selected position and NEG_INF for one that holds none (a row shorter than
`index_topk`); the query's own position is always selected, so no row is
empty.

Numerics: entries as stored, float32 scores and softmax statistics, the
exponentials rounded to the entries' dtype for the weighted sum, as
`model.attend_entries` (the XLA form, kept for rows that share their entries)
rounds its probabilities.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from llama_pipeline_parallel_tpu.ops.attention import NEG_INF
from llama_pipeline_parallel_tpu.ops.pallas_common import (
    compiler_params,
    interpret_mode,
)
from llama_pipeline_parallel_tpu.utils import trace

# every query is independent
_COMPILER_PARAMS = compiler_params("parallel")


def _kernel(q_ref, bias_ref, e_ref, o_ref, *, scale: float):
    q = q_ref[...]                                          # [h, w]
    e = e_ref[...]                                          # [K, w]
    s = jax.lax.dot_general(q, e, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * scale + bias_ref[...]                           # [h, K] + [1, K]
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1, keepdims=True)
    o = jax.lax.dot_general(p.astype(e.dtype), e, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[...] = (o / l).astype(o_ref.dtype)


def sparse_latent_attention(q_abs: jnp.ndarray, entries: jnp.ndarray,
                            ok: jnp.ndarray, scale: float) -> jnp.ndarray:
    """q_abs: [N, h, w] absorbed queries at the entries' width; entries:
    [N, K, w], each query's own gathered entries; ok: [N, K] bool, the places
    that hold a selected position (at least one a query). Returns [N, h, w]
    in the entries' dtype: `softmax(q_abs e^T * scale) e` over the places
    that are `ok`."""
    N, h, w = q_abs.shape
    K = entries.shape[1]
    bias = jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)[:, None, :]
    per_query = lambda *shape: pl.BlockSpec((None,) + shape,
                                            lambda i: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid=(N,),
        in_specs=[per_query(h, w), per_query(1, K), per_query(K, w)],
        out_specs=per_query(h, w),
        out_shape=jax.ShapeDtypeStruct((N, h, w), entries.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret_mode(),
        name=trace.KERNEL_SPARSE_LATENT_ATTN,
    )(q_abs.astype(entries.dtype), bias, entries)

