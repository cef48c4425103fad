"""Pure-functional LLaMA with stacked layer parameters.

Design notes (vs the reference):
- The reference cuts an HF `LlamaForCausalLM` into a flat list of DeepSpeed
  `LayerSpec`s (reference models/llama_ds_mp_wrap.py:209-224: EmbeddingPipe,
  k x ParallelTransformerLayerPipe, LayerNormPipe, LMLayerPipe). Here the same
  partition exists as *data layout*: all decoder layers share one pytree whose
  leaves carry a leading `num_hidden_layers` axis. A single-device forward
  `lax.scan`s over that axis; the pipeline runtime reshapes it to
  `[num_stages, layers_per_stage, ...]` and shards the stage axis over the
  `pp` mesh axis (see parallel/pipeline.py). No per-layer Python objects, no
  filename arithmetic.
- Embedding / final norm / lm-head are separate top-level entries, placed on
  the first/last stage by the pipeline runtime (reference stage predicates
  trainer_base_ds_mp.py:309).
- No weight tying between embed and lm_head (reference README.md:44-46).
- Params are kept in `param_dtype` (fp32 master) and cast to `dtype` (bf16)
  at forward entry — the bf16 analogue of DeepSpeed's fp16 master-weight
  machinery (reference conf yaml fp16 block), with no loss scaling needed.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.ops.attention import attention
from llama_pipeline_parallel_tpu.ops.rmsnorm import rms_norm
from llama_pipeline_parallel_tpu.ops.rope import apply_rope, rope_cos_sin
from llama_pipeline_parallel_tpu.utils import trace

Params = dict
AttnFn = Callable[..., jnp.ndarray]

IGNORE_INDEX = -100  # label value excluded from the loss (reference data/flan.py:187)


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: LlamaConfig) -> Params:
    """Random init (normal 0.02, HF default) with stacked layer leaves."""
    n, d, f, v = (cfg.num_hidden_layers, cfg.hidden_size,
                  cfg.intermediate_size, cfg.vocab_size)
    kv_dim = cfg.kv_heads * cfg.head_dim
    keys = jax.random.split(rng, 9)
    pd = cfg.param_dtype

    def nrm(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(pd)

    return {
        "embed": {"embedding": nrm(keys[0], (v, d))},
        "layers": {
            "attn": {
                "wq": nrm(keys[1], (n, d, d)),
                "wk": nrm(keys[2], (n, d, kv_dim)),
                "wv": nrm(keys[3], (n, d, kv_dim)),
                "wo": nrm(keys[4], (n, d, d)),
            },
            "mlp": {
                "gate": nrm(keys[5], (n, d, f)),
                "up": nrm(keys[6], (n, d, f)),
                "down": nrm(keys[7], (n, f, d)),
            },
            "input_norm": jnp.ones((n, d), pd),
            "post_norm": jnp.ones((n, d), pd),
        },
        "norm": jnp.ones((d,), pd),
        "lm_head": nrm(keys[8], (d, v)),
    }


def cast_weight(w: jnp.ndarray, dtype) -> jnp.ndarray:
    """Master-dtype weight -> compute dtype at its point of use, under the
    `cast_weights` scope so the cast is its own line in a trace (a no-op,
    and no operation, where the dtypes already agree)."""
    with jax.named_scope(trace.SCOPE_CAST_WEIGHTS):
        return w.astype(dtype)


def cast_params(params: Params, dtype) -> Params:
    return jax.tree.map(lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
                        params)


# ---------------------------------------------------------------------------
# Forward pieces (each maps onto one reference pipe-layer class)
# ---------------------------------------------------------------------------

def add_residual(x: jnp.ndarray, y: jnp.ndarray, cfg) -> jnp.ndarray:
    """`x + m y`, `m` the configuration's `residual_multiplier`: a family
    that has none, or states 1, adds no operation. The product is formed in
    float32 and rounded once to `y`'s dtype."""
    m = getattr(cfg, "residual_multiplier", 1.0)
    if m == 1.0:
        return x + y
    return x + (y.astype(jnp.float32) * m).astype(y.dtype)


def embed(params: Params, input_ids: jnp.ndarray, cfg: LlamaConfig) -> jnp.ndarray:
    """Token embedding (reference EmbeddingPipe, models/llama_ds_mp_wrap.py:128-132)."""
    with jax.named_scope(trace.SCOPE_EMBED):
        return cast_weight(params["embed"]["embedding"], cfg.dtype)[input_ids]


def decoder_layer(
    layer: Params,
    x: jnp.ndarray,
    padding_mask: jnp.ndarray | None,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    cfg: LlamaConfig,
    attn_fn: AttnFn = attention,
    tp_axis: str | None = None,
    pallas_prologue: bool = False,
) -> jnp.ndarray:
    """One transformer block (reference ParallelTransformerLayerPipe,
    models/llama_ds_mp_wrap.py:135-181, which wraps HF LlamaDecoderLayer).

    `tp_axis`: when set (inside shard_map with column/row-sharded weights),
    qkv/gate/up are column-parallel and wo/down row-parallel, with the
    Megatron f/g operator pair from parallel/tp.py. Head counts are derived
    from the LOCAL weight shards, so the same code runs tp=1 and tp=N.

    `pallas_prologue` (config `kernels.prologue: pallas`) runs
    rms_norm -> RoPE -> q/k/v as one fused Pallas kernel
    (ops/pallas_prologue.py) — same numerics within the pinned tolerance,
    the normed hidden never round-trips HBM; its custom VJP carries the
    tp_copy psum internally, so both branches compose with tp identically.
    """
    b, s, d = x.shape
    hd = cfg.head_dim
    dt = cfg.dtype

    if tp_axis is not None:
        from llama_pipeline_parallel_tpu.parallel.tp import tp_copy, tp_reduce
    residual = x
    with jax.named_scope(trace.SCOPE_ATTN_QKV):
        wq = cast_weight(layer["attn"]["wq"], dt)
        wk = cast_weight(layer["attn"]["wk"], dt)
        wv = cast_weight(layer["attn"]["wv"], dt)
        h_local = wq.shape[-1] // hd
        kv_local = wk.shape[-1] // hd
        if pallas_prologue:
            from llama_pipeline_parallel_tpu.ops.pallas_prologue import fused_prologue

            q, k, v = fused_prologue(
                x, layer["input_norm"], wq, wk, wv, cos, sin,
                eps=cfg.rms_norm_eps, head_dim=hd, tp_axis=tp_axis)
        else:
            hidden = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps)
            if tp_axis is not None:
                hidden = tp_copy(hidden, tp_axis)
            q = (hidden @ wq).reshape(b, s, h_local, hd)
            k = (hidden @ wk).reshape(b, s, kv_local, hd)
            v = (hidden @ wv).reshape(b, s, kv_local, hd)
            q, k = apply_rope(q, k, cos, sin)
    with jax.named_scope(trace.SCOPE_ATTN_CORE):
        attn_out = attn_fn(q, k, v, padding_mask, causal=True)
    with jax.named_scope(trace.SCOPE_ATTN_OUT):
        attn_out = attn_out.reshape(b, s, -1) @ cast_weight(
            layer["attn"]["wo"], dt)
        if tp_axis is not None:
            attn_out = tp_reduce(attn_out, tp_axis)
        x = residual + attn_out

    return mlp_block(layer, x, cfg, tp_axis=tp_axis)


def mlp_block(layer: Params, x: jnp.ndarray, cfg: LlamaConfig,
              tp_axis: str | None = None,
              scope: str = trace.SCOPE_MLP) -> jnp.ndarray:
    """Post-norm SwiGLU half of a decoder block (shared with the KV-cache
    decode path, models/llama/decode.py — one implementation, no numerics
    drift between training and generation; `scope` is the name its work
    carries in a trace, which the decode programs set to their own)."""
    dt = cfg.dtype
    residual = x
    with jax.named_scope(scope):
        # in the compute dtype (no operation where `x` already is; a
        # float32 residual stream, models/eva/, keeps `residual` as it is)
        hidden = rms_norm(x, layer["post_norm"], cfg.rms_norm_eps).astype(dt)
        if tp_axis is not None:
            from llama_pipeline_parallel_tpu.parallel.tp import tp_copy, tp_reduce

            hidden = tp_copy(hidden, tp_axis)
        gate = jax.nn.silu(hidden @ cast_weight(layer["mlp"]["gate"], dt))
        up = hidden @ cast_weight(layer["mlp"]["up"], dt)
        mlp_out = (gate * up) @ cast_weight(layer["mlp"]["down"], dt)
        if tp_axis is not None:
            mlp_out = tp_reduce(mlp_out, tp_axis)
        return add_residual(residual, mlp_out, cfg)


def run_layers(
    layers: Params,
    x: jnp.ndarray,
    padding_mask: jnp.ndarray | None,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    cfg: LlamaConfig,
    attn_fn: AttnFn = attention,
    remat: bool = False,
    tp_axis: str | None = None,
    remat_policy: str = "nothing_saveable",
    slot_valid: jnp.ndarray | None = None,
    pallas_prologue: bool = False,
) -> jnp.ndarray:
    """Apply a stack of layers (leading axis on every leaf) via lax.scan.

    `remat=True` recomputes each layer in backward — the analogue of
    `deepspeed.checkpointing.checkpoint` per layer (reference
    models/llama_ds_mp_wrap.py:57,166; flag conf yaml `activation_checkpointing`).
    `remat_policy` trades recompute FLOPs for memory: `nothing_saveable`
    (max memory savings), `dots_saveable` / `dots_with_no_batch_dims_saveable`
    (keep matmul outputs, recompute only elementwise — cheaper backward).
    `slot_valid` ([num_layers] bool): cond-skip invalid slots — the uneven
    pipeline partition's zero-weight padding (parallel/pipeline.py). The
    caller must ONLY pass this when the layer body is collective-free
    (tp_axis None, no sp attention): a collective inside a branch that other
    devices skip aborts the runtime.
    """

    def compute(layer, h):
        return decoder_layer(layer, h, padding_mask, cos, sin, cfg, attn_fn,
                             tp_axis=tp_axis, pallas_prologue=pallas_prologue)

    if slot_valid is None:
        def body(h, layer):
            return compute(layer, h), None

        xs = layers
    else:
        if tp_axis is not None:
            raise ValueError("slot_valid cond-skip cannot be combined with "
                             "tp collectives inside the layer")

        def body(h, xs_):
            layer, valid = xs_
            return jax.lax.cond(valid, compute, lambda layer_, h_: h_, layer, h), None

        xs = (layers, slot_valid)

    if remat:
        body = jax.checkpoint(body, policy=resolve_remat_policy(remat_policy))
    x, _ = jax.lax.scan(body, x, xs)
    return x


# Directly-usable jax.checkpoint policies, by config name. Factory attributes
# (save_only_these_names, ...) need construction arguments and are excluded —
# name-based selection would fail cryptically at first trace.
REMAT_POLICIES = (
    "nothing_saveable",
    "everything_saveable",
    "dots_saveable",
    "checkpoint_dots",  # alias of dots_saveable
    "dots_with_no_batch_dims_saveable",
    "checkpoint_dots_with_no_batch_dims",  # alias
)


def resolve_remat_policy(name: str):
    if name not in REMAT_POLICIES:
        raise ValueError(f"unknown remat_policy {name!r}; choose one of {REMAT_POLICIES}")
    return getattr(jax.checkpoint_policies, name)


def final_norm(params: Params, x: jnp.ndarray, cfg: LlamaConfig) -> jnp.ndarray:
    """Final RMSNorm (reference LayerNormPipe, models/llama_ds_mp_wrap.py:184-188)."""
    with jax.named_scope(trace.SCOPE_FINAL_NORM):
        return rms_norm(x, params["norm"], cfg.rms_norm_eps)


def lm_head(params: Params, x: jnp.ndarray, cfg: LlamaConfig) -> jnp.ndarray:
    """Logits projection (reference LMLayerPipe, models/llama_ds_mp_wrap.py:191-195).
    Returns fp32 logits for a stable softmax-CE."""
    with jax.named_scope(trace.SCOPE_LM_HEAD):
        return (x @ cast_weight(params["lm_head"], cfg.dtype)).astype(
            jnp.float32)


def forward(
    params: Params,
    input_ids: jnp.ndarray,
    attention_mask: jnp.ndarray | None = None,
    position_ids: jnp.ndarray | None = None,
    *,
    cfg: LlamaConfig,
    attn_fn: AttnFn = attention,
    remat: bool = False,
    pallas_prologue: bool = False,
) -> jnp.ndarray:
    """Single-device full forward: the PP=1 degenerate schedule.

    Batch protocol matches the reference collator output
    `(input_ids, attention_mask, position_ids)` (reference data/flan.py:304-307)
    with `attention_mask` as per-token [b, s] SEGMENT IDS (0 = pad; packed
    batches number each example 1..k and attention masks cross-segment
    pairs; plain batches use all-1s) — NOT a materialized [b, 1, L, L]
    tensor (SURVEY.md §3.5 fix). See ops/attention.py.
    """
    b, s = input_ids.shape
    if position_ids is None:
        position_ids = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    cos, sin = rope_cos_sin(position_ids, cfg.head_dim, cfg.rope_theta, dtype=cfg.dtype)
    x = embed(params, input_ids, cfg)
    x = run_layers(params["layers"], x, attention_mask, cos, sin, cfg, attn_fn,
                   remat, pallas_prologue=pallas_prologue)
    x = final_norm(params, x, cfg)
    return lm_head(params, x, cfg)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def token_loss_sum_and_count_preshifted(
    logits: jnp.ndarray, target_labels: jnp.ndarray
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """CE where `target_labels[:, i]` is already the next-token target for
    `logits[:, i]` (positions with no target carry IGNORE_INDEX). This is the
    form sequence-parallel shards need: the causal shift crosses sp-shard
    boundaries, so the caller aligns targets (parallel/pipeline.py
    `_sp_shift_labels`) and the loss itself stays shard-local."""
    valid = target_labels != IGNORE_INDEX
    safe_labels = jnp.where(valid, target_labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    token_ll = jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
    loss_sum = jnp.where(valid, -token_ll, 0.0).sum()
    return loss_sum, valid.sum()


def token_loss_sum_and_count(logits: jnp.ndarray, labels: jnp.ndarray
                             ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Shifted causal-LM cross-entropy: (sum of token losses, valid-token count).

    The single source of truth for shift/IGNORE_INDEX masking semantics —
    both the single-device loss below and the pipeline's last-stage loss
    (parallel/pipeline.py) build on it, so they cannot drift apart.
    """
    return token_loss_sum_and_count_preshifted(logits[:, :-1, :], labels[:, 1:])


def loss_fn(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Token-mean shifted cross-entropy with IGNORE_INDEX masking.

    Mirrors the reference `loss_fn` (models/llama_ds_mp_wrap.py:105-116) minus
    its index-column bug (labels there carried a smuggled extra column,
    SURVEY.md §3.5): labels here are exactly [b, s].
    """
    loss_sum, count = token_loss_sum_and_count(logits, labels)
    return loss_sum / jnp.maximum(count, 1)
