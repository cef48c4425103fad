"""Configuration of the latent-attention block: multi-head latent attention
(MLA) layers, one leading dense layer, and a sparse expert feed-forward with
a shared expert in every later layer. What the published configuration says
decides the rest: the kinds of its layers in order (a FULL kind that reads
the whole cache, or only the keys a learned indexer chooses where the
configuration has one; a SLIDING kind of other sizes that sees a window),
whether a mixer has an output gate of one number a head, whether the latents
are rescaled, and how the rope's frequencies are scaled (YaRN).

The third block family beside `models/llama/` and `models/hybrid_moe/`.
Named for what it is: any model of this shape is served by it
(docs/SERVING.md "Block families").
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp

from llama_pipeline_parallel_tpu.ops.rope import yarn_mscale

YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "mscale", "mscale_all_dim")


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    vocab_size: int = 152064
    hidden_size: int = 5120
    # layer 0 (full, dense feed-forward), then whole periods of `period`
    num_hidden_layers: int = 45
    # the kinds of the layers that repeat after layer 0: a full layer, then
    # the sliding layers that follow it (none: every layer is full)
    period: tuple = ("full", "sliding", "sliding", "sliding")
    intermediate_size: int = 13824     # the dense layer's SwiGLU
    # full layers
    num_attention_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    # YaRN's numbers as sorted (key, value) pairs (`YARN_KEYS`; hashable: the
    # configuration is a static argument of every program), None: the rope's
    # frequencies and the softmax scale as they are
    rope_scaling: tuple | None = None
    # their indexer: which `index_topk` positions a query reads; 0: no
    # indexer, a query reads every position it can see
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    # an output gate of one number a head, on the full and the sliding kind
    attention_gate: bool = True
    swa_attention_gate: bool = True
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
    # multi-token-prediction modules after the last layer (0 or 1). One: the
    # model DRAFTS one token a row a tick with it and verifies the draft in
    # the next tick (models/latent_moe/draft.py); the module is a whole full
    # layer with an expert half, with latent and index pages of its own
    num_nextn_predict_layers: int = 0
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
        # a checkpoint's meta.json hands tuples back as lists
        object.__setattr__(self, "period", tuple(self.period))
        if self.rope_scaling is not None:
            object.__setattr__(self, "rope_scaling", tuple(
                (key, float(value)) for key, value in self.rope_scaling))
            if tuple(k for k, _ in self.rope_scaling) != tuple(sorted(YARN_KEYS)):
                raise ValueError(
                    f"rope_scaling holds YaRN's {sorted(YARN_KEYS)} as "
                    f"sorted pairs; got {self.rope_scaling}")
        n = len(self.period)
        if self.period != ("full",) + ("sliding",) * (n - 1):
            raise ValueError(f"a period is one full layer, then its sliding "
                             f"layers; got {self.period}")
        if self.num_hidden_layers < 1 or (self.num_hidden_layers - 1) % n:
            raise ValueError(
                f"num_hidden_layers ({self.num_hidden_layers}) must be the "
                f"leading dense layer plus a whole number of periods of "
                f"{n} (full, then {n - 1} sliding)")
        if self.index_topk < 0 or self.sliding_window_size < 1:
            raise ValueError("index_topk must be >= 0 (0: no indexer) and "
                             "sliding_window_size >= 1")
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
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError(
                f"num_nextn_predict_layers {self.num_nextn_predict_layers}: "
                f"0 (no module) or 1 (one module drafting one token a tick); "
                f"more than one drafted token a tick is not served")
        if self.drafts and not (self.has_indexer and not self.window_layers):
            raise ValueError(
                "a multi-token-prediction module is served for a model of "
                "full layers under an indexer (no sliding layers)")

    # -- the layout ----------------------------------------------------------

    @property
    def periods(self) -> int:
        return (self.num_hidden_layers - 1) // len(self.period)

    @property
    def has_indexer(self) -> bool:
        return self.index_topk > 0

    @property
    def full_layers(self) -> int:
        """The trunk's layers that keep latent pages (and index pages, under
        an indexer): layer 0 and one a period."""
        return 1 + self.periods

    @property
    def drafts(self) -> bool:
        """Whether the model drafts with a multi-token-prediction module."""
        return self.num_nextn_predict_layers > 0

    @property
    def page_depth(self) -> int:
        """The page pool's depth: the trunk's full layers and, behind them,
        the module's one where the model has a module."""
        return self.full_layers + self.num_nextn_predict_layers

    @property
    def window_layers(self) -> int:
        """Layers that keep a ring a slot: the ring store's depth (0: the
        model keeps no store a slot)."""
        return self.periods * (len(self.period) - 1)

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
                scale(self.swa_kv_lora_rank), self.swa_attention_gate)
        return MixerDims(
            self.num_attention_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            self.rope_theta, scale(self.q_lora_rank),
            scale(self.kv_lora_rank), self.attention_gate, self.rope_scaling)

    @staticmethod
    def from_published(config: dict, **kw) -> "LatentMoEConfig":
        """From the keys of a published `config.json` of this shape, as they
        are. `layer_types` gives the kinds of the layers in order (absent:
        every layer is full, and the `swa_*` keys are not read); the
        `index_*` keys an indexer on the full layers (absent: none);
        `attention_gate_type` / `swa_attention_gate_type` a gate a head
        (absent: none); `apply_mla_qkv_lora_rescale` the latents' rescale
        (absent: none); `rope_scaling` YaRN's numbers (null: none).
        `n_routed_experts` counts the experts HELD where `router_experts`
        gives the router's width beside it (one chip's share of an
        expert-parallel deployment, with `expert_offset`).
        `rope_parameters.rope_theta` is read where `rope_theta` is absent
        (`glm_moe_dsa`). `first_k_dense_replace` counts the leading dense
        layers of the PUBLISHED depth; this block runs exactly one (a cut of
        a deeper model counts its leading dense layers once and says so in
        its `reduced`), so 1 is the one value taken.
        `num_nextn_predict_layers` (absent: 0) takes 0 or 1: one
        multi-token-prediction module, which then drafts."""
        layers = config["num_hidden_layers"]
        kinds = tuple(t.split("_")[0] for t in config.get(
            "layer_types", ["full_attention"] * layers)[:layers])
        rest = kinds[1:]
        period = rest[:next((i for i, kind in enumerate(rest[1:], 1)
                             if kind == "full"), len(rest))] or ("full",)
        if kinds != ("full",) + period * ((layers - 1) // len(period)):
            raise ValueError(
                f"layer_types[:{layers}] is not one full layer then whole "
                f"periods of a full layer and its sliding layers: {kinds}")
        if config["first_k_dense_replace"] != 1:
            raise ValueError(
                f"first_k_dense_replace {config['first_k_dense_replace']}: "
                f"this block runs exactly one leading dense layer (layer 0); "
                f"a cut of a model with more states 1 and lists the key in "
                f"its `reduced`")
        nextn = config.get("num_nextn_predict_layers", 0)
        if nextn not in (0, 1):
            raise ValueError(
                f"num_nextn_predict_layers {nextn}: 0 or 1 (one module, one "
                f"drafted token a tick)")
        sliding = "sliding" in period
        gates = ("attention_gate_type",) + (
            ("swa_attention_gate_type",) if sliding else ())
        for key in gates:
            if config.get(key) not in (None, "headwise"):
                raise ValueError(f"{key}: {config[key]!r}: the gate is one "
                                 f"number a head")
        if config["scoring_func"] != "sigmoid":
            raise ValueError("the router scores with a sigmoid")
        scaling = config.get("rope_scaling")
        theta = config["rope_theta"] if "rope_theta" in config else \
            config["rope_parameters"]["rope_theta"]
        if scaling is not None:
            if scaling.get("type", scaling.get("rope_type")) != "yarn" or sliding:
                raise ValueError(
                    f"rope_scaling {scaling}: the rope is rescaled by YaRN, "
                    f"in a model without sliding layers, or not at all")
            scaling = tuple(sorted((key, float(scaling[key]))
                                   for key in YARN_KEYS))
        width = config["moe_intermediate_size"]
        indexer = ("index_n_heads", "index_head_dim")
        swa = ("swa_num_attention_heads", "swa_q_lora_rank",
               "swa_kv_lora_rank", "swa_qk_nope_head_dim",
               "swa_qk_rope_head_dim", "swa_v_head_dim", "sliding_window_size")
        copied = (
            "vocab_size", "hidden_size", "num_hidden_layers",
            "intermediate_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "num_experts_per_tok", "rms_norm_eps") + (
                indexer if "index_topk" in config else ()) + (
                    swa if sliding else ())
        base = {key: config[key] for key in copied}
        if sliding:
            base["swa_rope_theta"] = float(config["swa_rope_theta"])
        base.update(
            period=period, index_topk=config.get("index_topk", 0),
            rope_theta=float(theta), rope_scaling=scaling,
            num_nextn_predict_layers=nextn,
            attention_gate=config.get("attention_gate_type") is not None,
            swa_attention_gate=config.get("swa_attention_gate_type") is not None,
            lora_rescale=bool(config.get("apply_mla_qkv_lora_rescale", False)),
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
    """The sizes of one kind of mixer, whether it has a gate, and how its
    rope is scaled."""

    heads: int
    rq: int
    rkv: int
    nope: int
    rope: int
    v: int
    theta: float
    rq_scale: float
    rkv_scale: float
    gate: bool = True
    rope_scaling: tuple | None = None

    @property
    def softmax_scale(self) -> float:
        """1 / sqrt(head) and, under YaRN, the square of its attention
        factor at `mscale_all_dim` (as DeepSeek-V3 publishes it)."""
        scale = (self.nope + self.rope) ** -0.5
        if self.rope_scaling is None:
            return scale
        yarn = dict(self.rope_scaling)
        return scale * yarn_mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2
