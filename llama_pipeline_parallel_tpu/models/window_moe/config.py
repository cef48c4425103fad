"""Configuration of the window / full softmax block: every layer is a
grouped-query softmax mixer of ONE of two kinds, in the order a published
list gives (`pattern`: 0 a FULL layer that sees every earlier position, 1 a
WINDOW layer that sees the last `sliding_window` and adds a learned logit a
query head, a sink, to its softmax's denominator), each kind with its own
number of KV heads and its own rotary base; keys and queries are `head_dim`
wide, of which the leading `rotary_dim` numbers are rotated, values
`v_head_dim`; a layer's second half is a dense gated feed-forward or sparse
experts (`moe_layers`), sigmoid-routed, with nothing beside the routed sum.

The sixth block family beside `models/llama/`. Named for what it is: any
model of this shape is served by it (docs/SERVING.md "Block families").
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp

FULL, WINDOW = 0, 1


@dataclasses.dataclass(frozen=True)
class KindDims:
    """What one kind of softmax layer is."""
    kv_heads: int
    rope_theta: float
    sink: bool           # a learned logit a query head in the denominator


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig:
    vocab_size: int = 152576
    hidden_size: int = 4096
    pattern: tuple = (0, 1, 1, 1, 1, 1, 0)      # a layer an entry, in order
    moe_layers: tuple = (0, 1, 1, 1, 1, 1, 1)   # 1: sparse experts, 0: dense
    num_attention_heads: int = 64
    head_dim: int = 192                 # queries and keys
    v_head_dim: int = 128
    rotary_dim: int = 64                # leading numbers of a head that rotate
    value_scale: float = 0.707          # values are stored scaled
    full_kv_heads: int = 4
    full_rope_theta: float = 5e6
    window_kv_heads: int = 8
    window_rope_theta: float = 1e4
    sliding_window: int = 128           # keys a window query sees, its own among them
    intermediate_size: int = 16384      # the dense feed-forward
    # the router's width, the range of experts held here, an expert's width
    router_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    expert_offset: int = 0              # first expert this process holds
    experts_held: int | None = None     # how many it holds; None -> all
    rms_norm_eps: float = 1e-5
    # bf16 weights and activations as the family is published; the sinks,
    # the router and its bias are float32 whatever these say
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    family = "window_moe"               # class attribute, not a field

    def __post_init__(self) -> None:
        # a checkpoint's meta.json gives the two lists back as lists
        object.__setattr__(self, "pattern", tuple(self.pattern))
        object.__setattr__(self, "moe_layers", tuple(self.moe_layers))
        if not self.pattern or set(self.pattern) - {FULL, WINDOW}:
            raise ValueError(f"pattern {self.pattern!r}: a layer is {FULL} "
                             f"(full) or {WINDOW} (window)")
        if len(self.moe_layers) != len(self.pattern) or \
                set(self.moe_layers) - {0, 1}:
            raise ValueError(f"moe_layers {self.moe_layers!r} does not say "
                             f"0 or 1 for each of {len(self.pattern)} layers")
        for kv in (self.full_kv_heads, self.window_kv_heads):
            if self.num_attention_heads % kv:
                raise ValueError("num_attention_heads must be a multiple of "
                                 "each kind's KV heads")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError("rotary_dim must be even and in (0, head_dim]")
        if not 0 < self.num_experts_per_tok <= self.router_experts:
            raise ValueError("num_experts_per_tok must be in (0, router_experts]")
        if self.expert_offset < 0 or self.held < 1 or \
                self.expert_offset + self.held > self.router_experts:
            raise ValueError(
                f"held experts [{self.expert_offset}, "
                f"{self.expert_offset + self.held}) outside the router's "
                f"{self.router_experts}")

    @property
    def num_hidden_layers(self) -> int:
        return len(self.pattern)

    @property
    def full_layers(self) -> int:
        """Layers that keep every position: the page pool's depth."""
        return self.pattern.count(FULL)

    @property
    def window_layers(self) -> int:
        """Layers that keep the last `sliding_window`: the ring's depth."""
        return self.pattern.count(WINDOW)

    @property
    def held(self) -> int:
        return (self.router_experts if self.experts_held is None
                else self.experts_held)

    @property
    def ring_len(self) -> int:
        """Places of a slot's ring: the window's keys, no more. A query's
        own entry takes the place of the one that just left its window."""
        return self.sliding_window

    def kind_of(self, kind: int) -> KindDims:
        """What a layer of kind `FULL` or `WINDOW` is."""
        if kind == WINDOW:
            return KindDims(self.window_kv_heads, self.window_rope_theta, True)
        return KindDims(self.full_kv_heads, self.full_rope_theta, False)

    def kind(self, layer: int) -> KindDims:
        return self.kind_of(self.pattern[layer])

    def kind_index(self, layer: int) -> int:
        """Layer `layer`'s place among the layers of its own kind."""
        return self.pattern[:layer].count(self.pattern[layer])

    @staticmethod
    def from_published(config: dict, **kw) -> "WindowMoEConfig":
        """From the keys of a published `config.json` of this shape
        (`hybrid_layer_pattern`, `moe_layer_freq`, `swa_num_key_value_heads`,
        `swa_rope_theta`, `partial_rotary_factor`, `attention_value_scale`,
        `add_swa_attention_sink_bias`, ...). `n_routed_experts` counts the
        experts HELD where `router_experts` gives the router's width beside
        it (one chip's share of an expert-parallel deployment, with
        `expert_offset`)."""
        n = config["num_hidden_layers"]
        pattern, moe = config["hybrid_layer_pattern"], config["moe_layer_freq"]
        if len(pattern) != n or len(moe) != n:
            raise ValueError(
                f"hybrid_layer_pattern / moe_layer_freq have {len(pattern)} / "
                f"{len(moe)} entries for {n} layers")
        if not config["add_swa_attention_sink_bias"] or \
                config["add_full_attention_sink_bias"]:
            raise ValueError("this block's window layers have a sink and its "
                             "full layers none")
        same = (("swa_num_attention_heads", "num_attention_heads"),
                ("swa_head_dim", "head_dim"), ("swa_v_head_dim", "v_head_dim"),
                ("sliding_window_size", "sliding_window"))
        for a, b in same:
            if config[a] != config[b]:
                raise ValueError(f"{a} and {b} differ: the two kinds differ "
                                 f"in KV heads, rotary base and mask only")
        if config["scoring_func"] != "sigmoid" or config["hidden_act"] != "silu":
            raise ValueError("this block's router is a sigmoid and its "
                             "feed-forwards SiLU-gated")
        if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
            raise ValueError("the router selects over one group")
        if config.get("n_shared_experts") or config.get("attention_bias"):
            raise ValueError("this block has no shared expert and no bias")
        scaling = config.get("routed_scaling_factor")
        base = dict(
            vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
            pattern=tuple(pattern), moe_layers=tuple(moe),
            num_attention_heads=config["num_attention_heads"],
            head_dim=config["head_dim"], v_head_dim=config["v_head_dim"],
            # `partial_rotary_factor` is published rounded (0.334 of 192):
            # the even count it stands for
            rotary_dim=2 * int(config["partial_rotary_factor"]
                               * config["head_dim"] / 2),
            value_scale=float(config["attention_value_scale"]),
            full_kv_heads=config["num_key_value_heads"],
            full_rope_theta=float(config["rope_theta"]),
            window_kv_heads=config["swa_num_key_value_heads"],
            window_rope_theta=float(config["swa_rope_theta"]),
            sliding_window=config["sliding_window"],
            intermediate_size=config["intermediate_size"],
            router_experts=config.get("router_experts",
                                      config["n_routed_experts"]),
            num_experts_per_tok=config["num_experts_per_tok"],
            moe_intermediate_size=config["moe_intermediate_size"],
            norm_topk_prob=bool(config["norm_topk_prob"]),
            routed_scaling_factor=1.0 if scaling is None else float(scaling),
            expert_offset=config.get("expert_offset", 0),
            experts_held=config["n_routed_experts"],
            rms_norm_eps=config["layernorm_epsilon"])
        base.update(kw)
        return WindowMoEConfig(**base)
