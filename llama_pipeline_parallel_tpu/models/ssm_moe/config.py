"""Configuration of the state-space / expert block: every letter of
`pattern` is ONE thing with its own norm and residual, in the order a
published string or list gives: `M` a Mamba-2 state-space mixer, `*` a
grouped-query softmax layer without rotary embedding, `E` a sparse expert
feed-forward whose routed experts work in a latent width beside one shared
expert at the model's width, `-` a dense gated (SwiGLU) feed-forward. A
published layer of TWO halves (a mixer and its feed-forward, each under its
own norm) is two letters, `M-` or `*-`, and counts as one layer.

Four scalar multipliers, each 1 (or, for the scores, `head_dim ** -0.5`)
unless a configuration states another, and then adding NO operation to a
program: `embedding_multiplier` on the embedded tokens, `residual_multiplier`
on what every letter adds to the residual stream, `attention_multiplier` the
scores' scale, `logits_scaling` the divisor of the logits. With
`tie_word_embeddings` the head is the embedding table and the tree holds no
`lm_head`.

The fifth block family beside `models/llama/`. Named for what it is: any
model of this shape is served by it (docs/SERVING.md "Block families").
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp

KINDS = "M*E-"
MIXERS = "M*"                   # what a `-` stands behind
LANES = 128                     # the last axis of a tile of the device's memory


@dataclasses.dataclass(frozen=True)
class SsmMoEConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    pattern: str = "MEMEMEMEM*E"        # a layer a letter, in order
    # `*`: grouped-query softmax, no rotary embedding, no gate
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # `M`: Mamba-2
    ssm_heads: int = 128
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 8                 # B and C are shared by heads / groups
    ssm_conv: int = 4                   # causal depthwise convolution width
    ssm_chunk: int = 128                # positions a chunk of the prefill covers
    # `E`: the router's width, the range of experts held here, the latent
    # width the routed experts read and write
    router_experts: int = 512
    num_experts_per_tok: int = 22
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688
    shared_intermediate_size: int = 5376
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    expert_offset: int = 0              # first expert this process holds
    experts_held: int | None = None     # how many it holds; None -> all
    # `-`: the dense gated feed-forward's width
    dense_intermediate_size: int = 0
    # the four multipliers and the tied head (the module's docstring)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float | None = None   # None: head_dim ** -0.5
    logits_scaling: float = 1.0
    tie_word_embeddings: bool = False
    rms_norm_eps: float = 1e-5
    # bf16 weights and activations as the family is published; the state,
    # `A_log`, `D`, `dt_bias` and the router are float32 whatever these say
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    family = "ssm_moe"                  # class attribute, not a field
    attn_gate = False                   # the softmax layer has no output gate

    def __post_init__(self) -> None:
        if not self.pattern or set(self.pattern) - set(KINDS):
            raise ValueError(f"pattern {self.pattern!r}: a layer is one of "
                             f"{tuple(KINDS)}")
        if any(kind == "-" and (i == 0 or self.pattern[i - 1] not in MIXERS)
               for i, kind in enumerate(self.pattern)):
            raise ValueError(f"pattern {self.pattern!r}: a dense half `-` "
                             f"stands behind its layer's mixer, `M` or `*`")
        if "-" in self.pattern and self.dense_intermediate_size < 1:
            raise ValueError("a pattern with dense halves needs "
                             "dense_intermediate_size")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if self.ssm_heads % self.ssm_groups:
            raise ValueError("ssm_heads must be a multiple of ssm_groups")
        if not 0 < self.num_experts_per_tok <= self.router_experts:
            raise ValueError("num_experts_per_tok must be in (0, router_experts]")
        if self.expert_offset < 0 or self.held < 1 or \
                self.expert_offset + self.held > self.router_experts:
            raise ValueError(
                f"held experts [{self.expert_offset}, "
                f"{self.expert_offset + self.held}) outside the router's "
                f"{self.router_experts}")

    # -- what the serving stack asks of any family's configuration ----------

    @property
    def num_hidden_layers(self) -> int:
        """Layers as published: a dense half belongs to the mixer before
        it."""
        return len(self.pattern) - self.pattern.count("-")

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads

    @property
    def kv_pack(self) -> int:
        """KV heads of one token that share a row of a page. Heads narrower
        than a lane tile are stored side by side, as many as fill the tile (2
        heads of 64), so that a page is a matrix of whole tiles which every
        program reads and writes where it lies (models/ssm_moe/decode.py);
        where they do not fill it in whole rows, and at a head of a tile or
        more, 1: a head a row, the dense layout."""
        pack = LANES // self.head_dim if LANES % self.head_dim == 0 else 1
        return pack if self.num_key_value_heads % pack == 0 else 1

    @property
    def kv_cache_layers(self) -> int:
        """Layers that keep keys and values: the page pool's depth."""
        return self.pattern.count("*")

    @property
    def recurrent_layers(self) -> int:
        """Layers that keep a recurrent state: the state store's depth."""
        return self.pattern.count("M")

    @property
    def expert_layers(self) -> int:
        return self.pattern.count("E")

    @property
    def attn_scale(self) -> float:
        """The factor of the softmax layers' scores."""
        return (self.head_dim ** -0.5 if self.attention_multiplier is None
                else self.attention_multiplier)

    @property
    def held(self) -> int:
        return (self.router_experts if self.experts_held is None
                else self.experts_held)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """Channels the convolution runs over: x, B and C side by side."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    def kind_index(self, layer: int) -> int:
        """Layer `layer`'s place among the layers of its own kind."""
        return self.pattern[:layer].count(self.pattern[layer])

    @staticmethod
    def from_published(config: dict, **kw) -> "SsmMoEConfig":
        """From the keys of a published `config.json` of this shape
        (`hybrid_override_pattern`, `mamba_num_heads`, `ssm_state_size`,
        `moe_latent_size`, ...). `n_routed_experts` counts the experts HELD
        where `router_experts` gives the router's width beside it (one
        chip's share of an expert-parallel deployment, with
        `expert_offset`). A `granitemoehybrid` configuration (`layer_types`,
        `mamba_n_heads`, `shared_intermediate_size`, the four multipliers) is
        read by `_from_granite`."""
        if config.get("model_type") == "granitemoehybrid":
            return SsmMoEConfig._from_granite(config, **kw)
        pattern = config["hybrid_override_pattern"]
        if len(pattern) != config["num_hidden_layers"]:
            raise ValueError(
                f"hybrid_override_pattern has {len(pattern)} letters for "
                f"{config['num_hidden_layers']} layers")
        inner = config["mamba_num_heads"] * config["mamba_head_dim"]
        if inner != config["expand"] * config["hidden_size"]:
            raise ValueError("mamba_num_heads x mamba_head_dim is not "
                             "expand x hidden_size")
        if config["mlp_hidden_act"] != "relu2" or \
                config["mamba_hidden_act"] != "silu":
            raise ValueError("this block's experts are relu^2 and its "
                             "state-space layers SiLU")
        if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
            raise ValueError("the router selects over one group")
        if not config.get("use_conv_bias", True) or \
                config.get("mamba_proj_bias") or config.get("mlp_bias") or \
                config.get("attention_bias"):
            raise ValueError("this block has a bias on the convolution and "
                             "nowhere else")
        base = dict(
            vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
            pattern=pattern,
            num_attention_heads=config["num_attention_heads"],
            num_key_value_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            ssm_heads=config["mamba_num_heads"],
            ssm_head_dim=config["mamba_head_dim"],
            ssm_state=config["ssm_state_size"], ssm_groups=config["n_groups"],
            ssm_conv=config["conv_kernel"], ssm_chunk=config["chunk_size"],
            router_experts=config.get("router_experts",
                                      config["n_routed_experts"]),
            num_experts_per_tok=config["num_experts_per_tok"],
            moe_latent_size=config["moe_latent_size"],
            moe_intermediate_size=config["moe_intermediate_size"],
            shared_intermediate_size=(
                config["n_shared_experts"]
                * config["moe_shared_expert_intermediate_size"]),
            norm_topk_prob=bool(config["norm_topk_prob"]),
            routed_scaling_factor=float(config["routed_scaling_factor"]),
            expert_offset=config.get("expert_offset", 0),
            experts_held=config["n_routed_experts"],
            rms_norm_eps=config["norm_eps"])
        base.update(kw)
        return SsmMoEConfig(**base)

    @staticmethod
    def _from_granite(config: dict, **kw) -> "SsmMoEConfig":
        """From the keys of a published `granitemoehybrid` `config.json`:
        every layer a mixer (`layer_types`: `mamba` or `attention`) AND a
        dense SwiGLU half of `shared_intermediate_size`, no experts beside
        it, no positional embedding, heads of `hidden_size /
        num_attention_heads`, the head tied to the table."""
        letters = {"mamba": "M-", "attention": "*-"}
        types = config["layer_types"]
        if len(types) != config["num_hidden_layers"] or set(types) - set(letters):
            raise ValueError(
                f"layer_types gives {len(types)} layers of {sorted(set(types))} "
                f"for {config['num_hidden_layers']} of {sorted(letters)}")
        if config["num_local_experts"] or config["num_experts_per_tok"]:
            raise ValueError("this reader takes the dense block: no experts "
                             "beside the shared feed-forward")
        if config["position_embedding_type"] != "nope":
            raise ValueError("this block's softmax layers carry no positional "
                             "embedding (`nope`)")
        if config["hidden_act"] != "silu" or \
                config["normalization_function"] != "rmsnorm":
            raise ValueError("this block is SiLU-gated under RMSNorm")
        if not config["mamba_conv_bias"] or config["mamba_proj_bias"] or \
                config["attention_bias"]:
            raise ValueError("this block has a bias on the convolution and "
                             "nowhere else")
        inner = config["mamba_n_heads"] * config["mamba_d_head"]
        if inner != config["mamba_expand"] * config["hidden_size"]:
            raise ValueError("mamba_n_heads x mamba_d_head is not "
                             "mamba_expand x hidden_size")
        if config["hidden_size"] % config["num_attention_heads"]:
            raise ValueError("hidden_size is not whole heads")
        base = dict(
            vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
            pattern="".join(letters[t] for t in types),
            num_attention_heads=config["num_attention_heads"],
            num_key_value_heads=config["num_key_value_heads"],
            head_dim=config["hidden_size"] // config["num_attention_heads"],
            ssm_heads=config["mamba_n_heads"],
            ssm_head_dim=config["mamba_d_head"],
            ssm_state=config["mamba_d_state"],
            ssm_groups=config["mamba_n_groups"],
            ssm_conv=config["mamba_d_conv"],
            ssm_chunk=config["mamba_chunk_size"],
            dense_intermediate_size=config["shared_intermediate_size"],
            embedding_multiplier=float(config["embedding_multiplier"]),
            residual_multiplier=float(config["residual_multiplier"]),
            attention_multiplier=float(config["attention_multiplier"]),
            logits_scaling=float(config["logits_scaling"]),
            tie_word_embeddings=bool(config["tie_word_embeddings"]),
            rms_norm_eps=config["rms_norm_eps"])
        base.update(kw)
        return SsmMoEConfig(**base)

    @staticmethod
    def tiny(dense: bool = False, **kw) -> "SsmMoEConfig":
        """All three kinds of layer at a toy size for the CPU tests
        (float32); `dense`: the block of two halves instead (a mixer and a
        dense gated feed-forward a layer, 2 + 1 + 2 layers, multipliers that
        are not 1, the head tied, two KV heads a page row)."""
        if dense:
            # heads of 64, so that two KV heads pack a page row as they do at
            # the published width
            base = dict(
                vocab_size=128, hidden_size=256, pattern="M-M-*-M-M-",
                num_attention_heads=4, num_key_value_heads=2, head_dim=64,
                ssm_heads=8, ssm_head_dim=64, ssm_state=16,
                ssm_groups=1, ssm_chunk=8, dense_intermediate_size=128,
                embedding_multiplier=3.0, residual_multiplier=0.6,
                attention_multiplier=0.25, logits_scaling=2.0,
                tie_word_embeddings=True,
                dtype=jnp.float32, param_dtype=jnp.float32)
            base.update(kw)
            return SsmMoEConfig(**base)
        base = dict(
            vocab_size=128, hidden_size=32, pattern="MEM*EME",
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            ssm_heads=4, ssm_head_dim=8, ssm_state=8, ssm_groups=2,
            ssm_chunk=8, router_experts=16, num_experts_per_tok=4,
            moe_latent_size=16, moe_intermediate_size=24,
            shared_intermediate_size=48,
            dtype=jnp.float32, param_dtype=jnp.float32)
        base.update(kw)
        return SsmMoEConfig(**base)
