"""Configuration of the latent-attention block: multi-head latent attention
(MLA) layers of two kinds in one model (a FULL kind whose queries read the
keys a learned indexer chooses, a SLIDING kind of other sizes that sees a
window), an output gate of one number a head on both, one leading dense
layer, and a sparse expert feed-forward with a shared expert in every later
layer.

The third block family beside `models/llama/` and `models/hybrid_moe/`.
Named for what it is: any model of this shape is served by it
(docs/SERVING.md "Block families").
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp

PERIOD = ("full", "sliding", "sliding", "sliding")


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    vocab_size: int = 152064
    hidden_size: int = 5120
    # layer 0 (full, dense feed-forward), then whole periods of PERIOD
    num_hidden_layers: int = 45
    intermediate_size: int = 13824     # the dense layer's SwiGLU
    # full layers
    num_attention_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    # their indexer: which `index_topk` positions a query reads
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    # sliding layers
    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    sliding_window_size: int = 513     # counts the query's own position
    # the latents after their norms are scaled by sqrt(hidden / rank)
    lora_rescale: bool = True
    # expert layer: the router's width, and the range of experts held here
    router_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1536
    shared_intermediate_size: int = 1536
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    expert_offset: int = 0             # first expert this process holds
    experts_held: int | None = None    # how many it holds; None -> all
    rms_norm_eps: float = 1e-5
    # a slot's ring of the sliding layers holds the window rounded up to a
    # multiple of this many positions
    ring_multiple: int = 64
    # an entry is stored padded with zeros to a multiple of this many
    # numbers: the chip's tiles are 128 wide, and a store whose rows are not
    # whole tiles is handed to every program in another layout than it
    # computes in (a copy of the whole store in and out: 9 of a tick's 22 ms
    # on the v5e at 576 wide, PERF.md PR 30)
    store_multiple: int = 128
    # bf16 weights and activations as the family is published; index scores,
    # the router and every softmax are float32 whatever these say
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    family = "latent_moe"              # class attribute, not a field

    def __post_init__(self) -> None:
        if self.num_hidden_layers < 1 or \
                (self.num_hidden_layers - 1) % len(PERIOD):
            raise ValueError(
                f"num_hidden_layers ({self.num_hidden_layers}) must be the "
                f"leading dense layer plus a whole number of periods of "
                f"{len(PERIOD)} (full, then {len(PERIOD) - 1} sliding)")
        if self.index_topk < 1 or self.sliding_window_size < 1:
            raise ValueError("index_topk and sliding_window_size must be >= 1")
        if self.qk_rope_head_dim % 2 or self.swa_qk_rope_head_dim % 2 or \
                self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError("rope sizes must be even, and the indexer's "
                             "head at least as wide as the rope part")
        if not 0 < self.num_experts_per_tok <= self.router_experts:
            raise ValueError("num_experts_per_tok must be in (0, router_experts]")
        if self.expert_offset < 0 or self.held < 1 or \
                self.expert_offset + self.held > self.router_experts:
            raise ValueError(
                f"held experts [{self.expert_offset}, "
                f"{self.expert_offset + self.held}) outside the router's "
                f"{self.router_experts}")

    # -- the layout ----------------------------------------------------------

    @property
    def periods(self) -> int:
        return (self.num_hidden_layers - 1) // len(PERIOD)

    @property
    def full_layers(self) -> int:
        """Layers that keep latent and index pages: layer 0 and one a
        period. The page pool's depth."""
        return 1 + self.periods

    @property
    def window_layers(self) -> int:
        """Layers that keep a ring a slot: the ring store's depth."""
        return self.periods * (len(PERIOD) - 1)

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - 1

    @property
    def latent_width(self) -> int:
        """Numbers a full layer keeps of a token: the latent and the shared
        roped key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def ring_width(self) -> int:
        return self.swa_kv_lora_rank + self.swa_qk_rope_head_dim

    def stored(self, width: int) -> int:
        m = self.store_multiple
        return -(-width // m) * m

    @property
    def latent_store_width(self) -> int:
        return self.stored(self.latent_width)

    @property
    def ring_store_width(self) -> int:
        return self.stored(self.ring_width)

    @property
    def ring_len(self) -> int:
        m = self.ring_multiple
        return -(-self.sliding_window_size // m) * m

    @property
    def held(self) -> int:
        return (self.router_experts if self.experts_held is None
                else self.experts_held)

    def kind(self, sliding: bool) -> "MixerDims":
        """The sizes of one kind of mixer under common names."""
        d = self.hidden_size
        scale = lambda rank: (d / rank) ** 0.5 if self.lora_rescale else 1.0
        if sliding:
            return MixerDims(
                self.swa_num_attention_heads, self.swa_q_lora_rank,
                self.swa_kv_lora_rank, self.swa_qk_nope_head_dim,
                self.swa_qk_rope_head_dim, self.swa_v_head_dim,
                self.swa_rope_theta, scale(self.swa_q_lora_rank),
                scale(self.swa_kv_lora_rank))
        return MixerDims(
            self.num_attention_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            self.rope_theta, scale(self.q_lora_rank),
            scale(self.kv_lora_rank))

    @staticmethod
    def from_published(config: dict, **kw) -> "LatentMoEConfig":
        """From the keys of a published `config.json` of this shape
        (`layer_types`, `q_lora_rank`, the `swa_*` and `index_*` keys, ...).
        `n_routed_experts` counts the experts HELD where `router_experts`
        gives the router's width beside it (one chip's share of an
        expert-parallel deployment, with `expert_offset`)."""
        layers = config["num_hidden_layers"]
        kinds = [t.split("_")[0] for t in config["layer_types"][:layers]]
        if kinds != ["full"] + list(PERIOD) * ((layers - 1) // len(PERIOD)):
            raise ValueError(
                f"layer_types[:{layers}] is not one full layer then whole "
                f"periods of {PERIOD}: {kinds}")
        if config["first_k_dense_replace"] != 1:
            raise ValueError("this block has exactly one leading dense layer")
        for key in ("attention_gate_type", "swa_attention_gate_type"):
            if config[key] != "headwise":
                raise ValueError(f"{key}: {config[key]!r}: the gate is one "
                                 f"number a head")
        if config["scoring_func"] != "sigmoid" or config.get("rope_scaling"):
            raise ValueError("the router scores with a sigmoid, and the "
                             "rope is not rescaled")
        width = config["moe_intermediate_size"]
        copied = (
            "vocab_size", "hidden_size", "num_hidden_layers",
            "intermediate_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "index_n_heads", "index_head_dim", "index_topk",
            "swa_num_attention_heads", "swa_q_lora_rank", "swa_kv_lora_rank",
            "swa_qk_nope_head_dim", "swa_qk_rope_head_dim", "swa_v_head_dim",
            "sliding_window_size", "num_experts_per_tok", "rms_norm_eps")
        base = {key: config[key] for key in copied}
        base.update(
            rope_theta=float(config["rope_theta"]),
            swa_rope_theta=float(config["swa_rope_theta"]),
            lora_rescale=bool(config["apply_mla_qkv_lora_rescale"]),
            router_experts=config.get("router_experts",
                                      config["n_routed_experts"]),
            moe_intermediate_size=width,
            shared_intermediate_size=config["n_shared_experts"] * width,
            norm_topk_prob=bool(config["norm_topk_prob"]),
            routed_scaling_factor=float(config["routed_scaling_factor"]),
            expert_offset=config.get("expert_offset", 0),
            experts_held=config["n_routed_experts"])
        base.update(kw)
        return LatentMoEConfig(**base)

    @staticmethod
    def tiny(**kw) -> "LatentMoEConfig":
        """The dense layer and two periods of a toy size for the CPU tests
        (float32): `index_topk` 8, a window of 5, a ring of 6."""
        base = dict(
            vocab_size=128, hidden_size=32, num_hidden_layers=9,
            intermediate_size=48, num_attention_heads=4, q_lora_rank=16,
            kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, index_n_heads=2, index_head_dim=8, index_topk=8,
            swa_num_attention_heads=2, swa_q_lora_rank=16,
            swa_kv_lora_rank=12, swa_qk_nope_head_dim=12,
            swa_qk_rope_head_dim=4, swa_v_head_dim=8, sliding_window_size=5,
            router_experts=16, num_experts_per_tok=4,
            moe_intermediate_size=16, shared_intermediate_size=16,
            ring_multiple=2, store_multiple=8, dtype=jnp.float32,
            param_dtype=jnp.float32)
        base.update(kw)
        return LatentMoEConfig(**base)


@dataclasses.dataclass(frozen=True)
class MixerDims:
    heads: int
    rq: int
    rkv: int
    nope: int
    rope: int
    v: int
    theta: float
    rq_scale: float
    rkv_scale: float

    @property
    def softmax_scale(self) -> float:
        return (self.nope + self.rope) ** -0.5
