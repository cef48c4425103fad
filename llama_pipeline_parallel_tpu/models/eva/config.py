"""Configuration of the compressed-window block: softmax attention whose
query reads the exact keys and values of its own WINDOW (an aligned block of
`window_size` positions) and, for every earlier window, one pooled key and
one pooled value a CHUNK of `chunk_size` positions, all in one softmax; a
SwiGLU feed-forward; a float32 residual stream; `num_pred_heads` output
heads of which the serving programs read the first.

The fourth block family beside `models/llama/`, `models/hybrid_moe/` and
`models/latent_moe/`. Named for what it is: any model of this shape is served
by it (docs/SERVING.md "Block families"). `from_published` takes the keys of
a published `config.json` (EvaByte's) under their own names.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class EvaConfig:
    vocab_size: int = 320
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-5
    rope_theta: float = 100000.0
    window_size: int = 2048            # W: exact keys of the query's own window
    chunk_size: int = 16               # C: one pooled key and value a chunk
    num_pred_heads: int = 8            # lm_head is [hidden, heads x vocab]
    # bf16 weights and products as the family is published; the residual
    # stream, the softmax statistics and the logits are float32 whatever
    # these say
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    family = "eva"                     # class attribute, not a field

    def __post_init__(self) -> None:
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must be a multiple of "
                             "num_attention_heads")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if self.chunk_size < 1 or self.window_size % self.chunk_size:
            raise ValueError(
                f"window_size ({self.window_size}) must be a whole number of "
                f"chunks of {self.chunk_size}")
        if self.num_pred_heads < 1:
            raise ValueError("num_pred_heads must be >= 1")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads

    @property
    def chunks_per_window(self) -> int:
        return self.window_size // self.chunk_size

    @classmethod
    def from_published(cls, published: dict, **overrides) -> "EvaConfig":
        """The configuration a published `config.json` describes. What the
        programs here cannot honour is refused by name, not ignored."""
        want = {"attention_class": "eva", "attention_bias": False,
                "hidden_act": "silu", "norm_add_unit_offset": True,
                "fp32_skip_add": True, "fp32_logits": True,
                "tie_word_embeddings": False, "rope_scaling": None}
        wrong = {k: published[k] for k, v in want.items()
                 if k in published and published[k] != v}
        if wrong:
            raise ValueError(f"the eva family runs {want}; the configuration "
                             f"says {wrong}")
        names = [f.name for f in dataclasses.fields(cls)
                 if f.name not in ("dtype", "param_dtype")]
        return cls(**{k: published[k] for k in names if k in published},
                   **overrides)
