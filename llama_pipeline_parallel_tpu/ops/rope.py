"""Rotary position embeddings, HF-LLaMA `rotate_half` convention.

Numerics match `transformers.models.llama.modeling_llama.apply_rotary_pos_emb`
so HF checkpoints load bit-compatibly (reference uses HF's attention unchanged,
models/llama_ds_mp_wrap.py:8-13).
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor: 0.1 mscale ln(factor) + 1 (1 at factor <= 1)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(head_dim: int, theta: float, yarn: dict) -> jnp.ndarray:
    """YaRN's frequencies [head_dim / 2] as DeepSeek-V3 publishes them:
    frequency j is theta^(-2j/hd) where it turns more than `beta_fast` times
    over the original context, that over `factor` where it turns fewer than
    `beta_slow` times, and a linear blend between the two correction
    dimensions."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    extrapolated = 1.0 / theta ** exponent
    interpolated = extrapolated / yarn["factor"]

    def correction_dim(rotations: float) -> float:
        return head_dim * math.log(
            yarn["original_max_position_embeddings"]
            / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(yarn["beta_slow"])), head_dim - 1)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low if high != low else 0.001), 0.0, 1.0)
    return interpolated * ramp + extrapolated * (1.0 - ramp)


def rope_cos_sin(position_ids: jnp.ndarray, head_dim: int, theta: float = 10000.0,
                 dtype=jnp.float32, scaling: dict | None = None
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for the given positions.

    position_ids: [batch, seq] int32 -> cos, sin: [batch, seq, head_dim].
    `scaling`: YaRN's numbers (`factor`, `original_max_position_embeddings`,
    `beta_fast`, `beta_slow`, `mscale`, `mscale_all_dim`): the frequencies of
    `yarn_inv_freq`, cos and sin multiplied by the ratio of the attention
    factors at `mscale` and at `mscale_all_dim`. None: the plain table.
    """
    amplitude = 1.0
    if scaling is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    else:
        inv_freq = yarn_inv_freq(head_dim, theta, scaling)
        amplitude = (yarn_mscale(scaling["factor"], scaling["mscale"])
                     / yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]))
    freqs = position_ids.astype(jnp.float32)[..., None] * inv_freq  # [b, s, hd/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [b, s, hd]
    cos, sin = jnp.cos(emb), jnp.sin(emb)
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude
    return cos.astype(dtype), sin.astype(dtype)


def _rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def apply_rope(q: jnp.ndarray, k: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Apply rotary embedding.

    q: [b, s, n_heads, hd], k: [b, s, n_kv_heads, hd], cos/sin: [b, s, hd].
    """
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    q_rot = q * cos + _rotate_half(q) * sin
    k_rot = k * cos + _rotate_half(k) * sin
    return q_rot.astype(q.dtype), k_rot.astype(k.dtype)
