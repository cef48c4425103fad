"""Ulysses-style sequence parallelism: all-to-all head scatter.

The second sequence-parallel strategy from SURVEY.md §2.2 (absent in the
reference): instead of rotating KV around a ring, two `all_to_all`
collectives re-shard the activations from sequence-sharded to head-sharded
and back. Each sp rank then runs ordinary (flash or exact) attention over the
FULL sequence for its slice of heads — which makes it compose directly with
the Pallas flash kernel, at the cost of requiring num_heads % sp == 0.

Trade-off vs ring attention (parallel/ring_attention.py): Ulysses moves
activations twice per attention (2 x all-to-all, bandwidth 2*b*s*d/n per
chip) but computes each head's attention in one shot with no per-step
latency chain; ring keeps heads whole and overlaps compute with KV-slab
transfers. Both are exact.

Autodiff needs no custom VJP here: the transpose of all_to_all is the
reverse all_to_all, so the backward pass re-shards gradients symmetrically.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from llama_pipeline_parallel_tpu.ops.attention import attention, repeat_kv
from llama_pipeline_parallel_tpu.parallel.mesh import AXIS_SP
from llama_pipeline_parallel_tpu.utils import trace


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    padding_mask: Any = None,
    *,
    causal: bool = True,
    axis_name: str = AXIS_SP,
    inner_attn: Callable = attention,
    q_offset: int = 0,
    kv_offset: int = 0,
) -> jnp.ndarray:
    """Call inside shard_map with the sequence dim sharded over `axis_name`.

    q: [b, s_local, h, hd]; k/v: [b, s_local, h_kv, hd]. GQA groups whose
    kv-head count does not divide the sp size are expanded first.
    `inner_attn` is any AttnFn (exact or Pallas flash) — it sees the full
    sequence, so no offsets are needed.
    """
    if q_offset != 0 or kv_offset != 0:
        raise ValueError("ulysses_attention re-shards to full sequence; offsets "
                         "are derived internally")
    n = jax.lax.axis_size(axis_name)
    h, h_kv = q.shape[2], k.shape[2]
    if h % n:
        raise ValueError(f"num heads {h} must be divisible by sp={n}")
    if h_kv % n:
        # minimal GQA expansion: smallest repeat making kv heads divide sp
        # (full expansion would double the all-to-all traffic for nothing —
        # the inner attention re-expands groups itself)
        group = h // h_kv
        r = next(r for r in range(1, group + 1)
                 if group % r == 0 and (h_kv * r) % n == 0)
        k = repeat_kv(k, r)
        v = repeat_kv(v, r)

    def scatter_heads(x):
        # [b, s_local, h', hd] -> [b, s_full, h'/n, hd]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def gather_seq(x):
        # [b, s_full, h/n, hd] -> [b, s_local, h, hd]
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    with jax.named_scope(trace.SCOPE_SP_COLLECTIVE):
        qg, kg, vg = scatter_heads(q), scatter_heads(k), scatter_heads(v)
        if padding_mask is not None:
            padding_mask = jax.lax.all_gather(padding_mask, axis_name, axis=1,
                                              tiled=True)
    out = inner_attn(qg, kg, vg, padding_mask, causal=causal)
    with jax.named_scope(trace.SCOPE_SP_COLLECTIVE):
        return gather_seq(out)
