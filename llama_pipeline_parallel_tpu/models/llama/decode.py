"""Autoregressive KV-cache decoding.

Fills the reference's dead prediction surface with a real one: its config
gestures at an evaluator/prediction step (reference conf yaml:107-115
`prediction_cfg`, `general_util.evaluator.DiscriminatorForwardFn` — the class
is absent and no predict path exists, SURVEY.md §2.4), while this module
implements batched generation the TPU way:

- ONE jitted program per phase: a prefill pass over the (left-padded) prompt
  and a `lax.scan` decode loop with a static-shape KV cache — no per-token
  retracing, no dynamic shapes, nothing for XLA to re-tile.
- The KV cache is a stacked `[n_layers, b, max_len, kv_heads, head_dim]`
  array pair written with `dynamic_update_slice` — the same stacked-leading-
  axis layout the training stack uses for layer params, so the layer loop
  stays a `lax.scan` over layers.
- Left-padded prompts: per-row rope positions come from the attention mask's
  cumulative sum, causality during decode reduces to the KV validity mask
  (a single [b, max_len] 0/1 array), and every row writes the same cache slot
  each step — no per-row dynamic slicing.

Models too big for one chip shard WITHOUT code changes: Megatron-shard the
params over a tp mesh (column-parallel qkv/gate/up, row-parallel wo/down,
vocab-parallel lm_head) and call the same jitted `generate` — GSPMD inserts
the collectives, and tokens match the unsharded run exactly
(tests/test_decode.py::test_generate_with_tp_sharded_params). Pipelined
decode across pp stages is a training-economy trade the reference never had
either and is out of scope.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.ops.attention import attention
from llama_pipeline_parallel_tpu.ops.paged_attention import (
    paged_decode_attention,
)
from llama_pipeline_parallel_tpu.ops.rmsnorm import rms_norm
from llama_pipeline_parallel_tpu.ops.rope import apply_rope, rope_cos_sin
from llama_pipeline_parallel_tpu.utils import trace

Params = dict


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    max_new_tokens: int = 64
    temperature: float = 0.0     # 0 -> greedy
    top_k: int = 0               # 0 -> full distribution
    top_p: float = 1.0           # nucleus mass; 1.0 -> no nucleus filter
    eos_token_id: int | None = None
    pad_token_id: int = 0        # emitted after a row hits eos

    def __post_init__(self) -> None:
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1 (the decode loop "
                             "always emits the prefill-sampled token)")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")


def init_kv_cache(cfg: LlamaConfig, batch: int, max_len: int) -> dict:
    """Zeroed static-shape cache. k/v: [n_layers, b, max_len, kv_h, hd]."""
    shape = (cfg.num_hidden_layers, batch, max_len, cfg.kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def _project_qkv(layer: Params, x: jnp.ndarray, cos: jnp.ndarray,
                 sin: jnp.ndarray, cfg: LlamaConfig):
    """Input norm, q/k/v projections and rope of one cached layer, as every
    decode and prefill program runs them: x [b, s, d] -> q [b, s, h, hd],
    k/v [b, s, kv_h, hd]. The normed rows enter the products in
    `cfg.dtype` (no operation where `x` already is: every family but the one
    with a float32 residual stream, models/eva/)."""
    b, s, _ = x.shape
    hd, dt = cfg.head_dim, cfg.dtype
    with jax.named_scope(trace.SCOPE_ATTN_QKV):
        hidden = rms_norm(x, layer["input_norm"], cfg.rms_norm_eps).astype(dt)
        q = (hidden @ llama.cast_weight(layer["attn"]["wq"], dt)
             ).reshape(b, s, -1, hd)
        k = (hidden @ llama.cast_weight(layer["attn"]["wk"], dt)
             ).reshape(b, s, -1, hd)
        v = (hidden @ llama.cast_weight(layer["attn"]["wv"], dt)
             ).reshape(b, s, -1, hd)
        return (*apply_rope(q, k, cos, sin), v)


def _attn_out_and_mlp(layer: Params, x: jnp.ndarray, attn_out: jnp.ndarray,
                      cfg: LlamaConfig) -> jnp.ndarray:
    """Output projection, residual and the SwiGLU half that follow the
    attention of one cached layer."""
    b, s, _ = x.shape
    with jax.named_scope(trace.SCOPE_ATTN_OUT):
        x = x + attn_out.reshape(b, s, -1) @ llama.cast_weight(
            layer["attn"]["wo"], cfg.dtype)
    return llama.mlp_block(layer, x, cfg, scope=trace.SCOPE_DECODE_MLP)


def _layer_forward_cached(layer: Params, x: jnp.ndarray, cache_k: jnp.ndarray,
                          cache_v: jnp.ndarray, write_pos, kv_mask: jnp.ndarray,
                          cos: jnp.ndarray, sin: jnp.ndarray, cfg: LlamaConfig,
                          causal: bool) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decoder layer reading/writing its KV cache slice.

    x: [b, s, d] (s = prompt length at prefill, 1 at decode);
    cache_k/v: [b, max_len, kv_h, hd]; write_pos: scalar slot index for x's
    first position (uniform across rows — left padding makes that possible);
    kv_mask: [b, max_len] validity of every cache slot INCLUDING x's own
    positions.

    `causal=True` is the PREFILL contract: the block is the entire visible
    history (write_pos must be 0), so attention runs over the freshly
    projected k/v at prompt-length cost — never over the max_len cache whose
    future slots are all masked anyway. `causal=False` is the decode step:
    x is one token attending over the whole cache, visibility is purely
    kv_mask.
    """
    s = x.shape[1]
    q, k, v = _project_qkv(layer, x, cos, sin, cfg)

    with jax.named_scope(trace.SCOPE_KV_WRITE):
        cache_k = jax.lax.dynamic_update_slice(cache_k, k, (0, write_pos, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(cache_v, v, (0, write_pos, 0, 0))

    with jax.named_scope(trace.SCOPE_DECODE_ATTN):
        if causal:  # prefill: nothing precedes the block; attend within it
            attn_out = attention(q, k, v, kv_mask[:, :s], causal=True)
        else:       # decode: one token over the full cache, mask-gated
            attn_out = attention(q, cache_k, cache_v, kv_mask, causal=False)
    return _attn_out_and_mlp(layer, x, attn_out, cfg), cache_k, cache_v


def forward_with_cache(params: Params, input_ids: jnp.ndarray, cache: dict,
                       positions: jnp.ndarray, write_pos, kv_mask: jnp.ndarray,
                       cfg: LlamaConfig, causal: bool = True,
                       last_only: bool = False) -> tuple[jnp.ndarray, dict]:
    """Embed -> cached layers (lax.scan) -> final norm -> logits.

    positions: [b, s] rope positions of input_ids (per-row under left
    padding). Returns fp32 logits [b, s, V] and the updated cache.
    `last_only` projects logits for the FINAL position only (prefill needs
    just the next-token distribution — [b, P, V] fp32 logits for a long
    prompt would be the dominant prefill allocation, for one used row).
    """
    x = llama.embed(params, input_ids, cfg)
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta, dtype=cfg.dtype)

    def body(h, xs):
        layer, ck, cv = xs
        h, ck, cv = _layer_forward_cached(layer, h, ck, cv, write_pos, kv_mask,
                                          cos, sin, cfg, causal)
        return h, (ck, cv)

    x, (new_k, new_v) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    if last_only:
        x = x[:, -1:, :]
    x = llama.final_norm(params, x, cfg)
    return llama.lm_head(params, x, cfg), {"k": new_k, "v": new_v}


def _top_p_mask(logits: jnp.ndarray, top_p) -> jnp.ndarray:
    """Nucleus filter: keep the smallest descending-sorted prefix whose
    cumulative probability reaches `top_p`; everything else to -inf.

    Keep rule is `cumulative mass BEFORE the token < top_p`, so the argmax
    always survives (a top_p below the top token's own probability degrades
    to greedy, never to an empty support). Shape-agnostic over leading dims
    — the serving path runs it per row with a traced scalar `top_p`, and
    both paths share this exact arithmetic so their tokens match bit-for-bit.
    """
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    before = jnp.cumsum(probs, axis=-1) - probs
    keep = before < top_p
    cutoff = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1,
                     keepdims=True)
    return jnp.where(logits < cutoff, -jnp.inf, logits)


def _sample(logits: jnp.ndarray, gen: GenerationConfig, rng: jax.Array) -> jnp.ndarray:
    """[b, V] fp32 logits -> [b] int32 next tokens."""
    if gen.temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / gen.temperature
    if gen.top_k > 0:
        kth = jax.lax.top_k(logits, gen.top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if gen.top_p < 1.0:
        logits = _top_p_mask(logits, gen.top_p)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def _top_p_mask_sorted(logits: jnp.ndarray, sorted_desc: jnp.ndarray,
                       top_p) -> jnp.ndarray:
    """`_top_p_mask` for a caller that already holds the row's descending
    sort: the same arithmetic on the same array, without the sort."""
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    before = jnp.cumsum(probs, axis=-1) - probs
    keep = before < top_p
    cutoff = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1,
                     keepdims=True)
    return jnp.where(logits < cutoff, -jnp.inf, logits)


def _sample_row(logits: jnp.ndarray, temperature, top_k, top_p,
                key: jax.Array, filters: bool = True) -> jnp.ndarray:
    """[V] logits -> scalar token, with PER-REQUEST knobs as traced values.

    The serving batch mixes requests with different GenerationConfigs, so
    the static branches of `_sample` become data: greedy is selected by
    `where(temperature > 0)`, the top-k threshold is the k-th largest VALUE
    (the same element `lax.top_k` finds, read off a descending sort), and
    the nucleus filter is `_top_p_mask`'s arithmetic. Every arithmetic path
    mirrors `_sample` exactly, which is what makes a slot-served request
    reproduce an independent `generate()` call token-for-token.

    Cost: ONE sort of the row, and none with `filters=False`, which
    `sample_rowwise` passes for a batch in which no sampling row has a
    top-k or a top-p (both `where`s would pass the row through). The
    nucleus filter wants the descending sort of the top-k-masked row;
    masking the sort gives that array, ties included (what lies below the
    k-th value is a suffix of the sort), so the row is not sorted again.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.where(temperature > 0.0, temperature, 1.0)
    l = logits / safe_t
    if filters:
        sorted_desc = jnp.sort(l, axis=-1)[..., ::-1]
        kth = sorted_desc[jnp.clip(top_k, 1, logits.shape[-1]) - 1]
        l = jnp.where((top_k > 0) & (l < kth), -jnp.inf, l)
        sorted_desc = jnp.where((top_k > 0) & (sorted_desc < kth), -jnp.inf,
                                sorted_desc)
        l = jnp.where(top_p < 1.0,
                      _top_p_mask_sorted(l, sorted_desc, top_p), l)
    sampled = jax.random.categorical(key, l, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


def _greedy_rows(logits, temperature, top_k, top_p, keys):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def sampler_branch(temperature, top_k, top_p):
    """What a batch's knobs ask of the sampler: 0, no row samples (an
    argmax a row); 1, some row samples and no sampling row filters (a
    draw); 2, some sampling row has `top_k > 0` or `top_p < 1` (a sort a
    row). Rows with temperature 0, unoccupied slots among them, never raise
    it whatever their other knobs. Takes numpy or jax arrays:
    `sample_rowwise` branches on it in the program and the engine counts
    `ticks_sampled` / `ticks_sorted` with it on the host."""
    samples = temperature > 0.0
    filters = samples & ((top_k > 0) | (top_p < 1.0))
    return samples.any().astype("int32") + filters.any().astype("int32")


def sample_rowwise(logits: jnp.ndarray, temperature: jnp.ndarray,
                   top_k: jnp.ndarray, top_p: jnp.ndarray,
                   keys: jnp.ndarray) -> jnp.ndarray:
    """[b, V] logits + [b] per-row knobs + [b, 2] keys -> [b] tokens.

    The tokens are `vmap(_sample_row)`'s, bit for bit; the work is what the
    batch asks for. One `lax.switch` on `sampler_branch` of the knobs,
    outside the `vmap` (inside it a condition is a select and both sides
    run), so only the chosen branch runs on the device: an all-greedy batch
    takes an argmax a row, a batch that samples without filters adds a
    draw, and only a batch in which a sampling row has a top-k or a top-p
    sorts, once a row, every row."""
    return jax.lax.switch(
        sampler_branch(temperature, top_k, top_p),
        (_greedy_rows, jax.vmap(partial(_sample_row, filters=False)),
         jax.vmap(_sample_row)),
        logits, temperature, top_k, top_p, keys)


@partial(jax.jit, static_argnames=("cfg", "gen"))
def generate(params: Params, input_ids: jnp.ndarray, attention_mask: jnp.ndarray,
             cfg: LlamaConfig, gen: GenerationConfig,
             rng: jax.Array | None = None) -> dict:
    """Batched generation from LEFT-padded prompts.

    input_ids/attention_mask: [b, P] with pads on the left (mask 0 = pad).
    Returns {"tokens": [b, max_new_tokens] int32 (pad_token_id after eos),
    "done": [b] bool (row hit eos within the budget)}.

    Params are the CANONICAL (unstacked) layout — `pl.unstack_stages` a
    training tree first, or load one with `tools/convert_hf.py` output.
    """
    b, prompt_len = input_ids.shape
    max_len = prompt_len + gen.max_new_tokens
    if rng is None:
        rng = jax.random.PRNGKey(0)
    mask = attention_mask.astype(jnp.int32)

    # Per-row rope positions: pads get clipped to 0, real tokens count from 0.
    positions = jnp.clip(jnp.cumsum(mask, axis=1) - 1, 0, None).astype(jnp.int32)

    cache = init_kv_cache(cfg, b, max_len)
    kv_mask = jnp.pad(mask, ((0, 0), (0, gen.max_new_tokens)))
    logits, cache = forward_with_cache(
        params, input_ids, cache, positions, 0, kv_mask, cfg, causal=True,
        last_only=True)

    next_pos = positions[:, -1] + 1            # [b] rope position of token P
    rng, first_key = jax.random.split(rng)     # use-once key discipline
    first = _sample(logits[:, -1, :], gen, first_key)

    def step(carry, t):
        cache, token, pos, kv_mask, done, rng = carry
        rng, sub = jax.random.split(rng)
        write_pos = prompt_len + t
        kv_mask = kv_mask.at[:, write_pos].set(1)
        logits, cache = forward_with_cache(
            params, token[:, None], cache, pos[:, None], write_pos, kv_mask,
            cfg, causal=False)
        nxt = _sample(logits[:, -1, :], gen, sub)
        out = jnp.where(done, gen.pad_token_id, token)
        if gen.eos_token_id is not None:
            done = done | (token == gen.eos_token_id)
        nxt = jnp.where(done, token, nxt)      # freeze finished rows
        return (cache, nxt, pos + 1, kv_mask, done, rng), out

    # Scan T-1 steps: the T-th sampled token needs no forward pass of its
    # own (nothing consumes its logits), so the final emission happens
    # outside the loop — at max_new_tokens=1 the decode scan is empty.
    carry = (cache, first, next_pos, kv_mask, jnp.zeros((b,), bool), rng)
    (_, token, _, _, done, _), tokens = jax.lax.scan(
        step, carry, jnp.arange(gen.max_new_tokens - 1))
    last = jnp.where(done, gen.pad_token_id, token)
    if gen.eos_token_id is not None:
        done = done | (token == gen.eos_token_id)
    tokens = jnp.concatenate([tokens, last[None]], axis=0)
    return {"tokens": tokens.T, "done": done}


# -- serving entry points (serve/) -------------------------------------------
#
# `generate()` owns a whole batch cradle-to-grave: one shared prompt bucket,
# one scalar write position, a cache made anew per call. Serving runs the same
# arithmetic with the batch axis reinterpreted as SLOTS that requests join and
# leave independently, and with the keys and values in a page pool
# (serve/pages.py) that is allocated once: `prefill_prompt` produces a row,
# `write_pages` splices it into the slot's pages, and `paged_decode_step`
# advances every slot one token with PER-ROW write positions, rope positions,
# rng chains and sampling knobs. The contract (serve/engine.py,
# tests/test_serving.py): a served request emits the tokens of an independent
# `generate()` call with its seed, on the prompt left-padded to its bucket.


# The leaves every program below converts to `cfg.dtype` where it uses them
# (`llama.cast_weight`): the table, a layer's seven products, the head. The
# norm scales are used in float32 (ops/rmsnorm.py) and are not among them.
_CAST_AT_USE = (("embed", "embedding"),
                *(("layers", "attn", w) for w in ("wq", "wk", "wv", "wo")),
                *(("layers", "mlp", w) for w in ("gate", "up", "down")),
                ("lm_head",))


@partial(jax.jit, static_argnames=("dtype",))
def _cast_leaves(leaves: list, dtype) -> list:
    return [llama.cast_weight(x, dtype) for x in leaves]


def serving_weights(params: Params, cfg: LlamaConfig) -> Params:
    """`params` as an engine holds them: the `_CAST_AT_USE` leaves converted
    to `cfg.dtype` once, by one program, every other leaf the caller's own
    array. `cast_weight` is no operation on a leaf already in `cfg.dtype`,
    so the serving programs given this tree compile without the converts and
    compute what they computed: `astype` of the same value gives the same
    value whenever it runs. A tree with nothing to convert comes back as it
    is. The caller's arrays are neither donated nor deleted."""
    dtype = jnp.dtype(cfg.dtype)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    leaves = [x for _, x in flat]
    todo = [i for i, (path, x) in enumerate(flat)
            if tuple(k.key for k in path) in _CAST_AT_USE
            and jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != dtype]
    if not todo:
        return params
    for i, x in zip(todo, _cast_leaves([leaves[i] for i in todo], dtype)):
        leaves[i] = x
    return jax.tree_util.tree_unflatten(treedef, leaves)


@partial(jax.jit, static_argnames=("cfg", "max_len"))
def prefill_prompt(params: Params, input_ids: jnp.ndarray,
                   attention_mask: jnp.ndarray, cfg: LlamaConfig,
                   max_len: int) -> dict:
    """Prefill LEFT-padded prompts into fresh max_len-sized cache rows.

    input_ids/attention_mask: [b, P] (P = the prompt bucket; per-request
    length variation lives in the left padding, so one compile per bucket).
    Returns {"logits": [b, V] fp32 last-position logits, "cache": k/v
    [L, b, max_len, kv_h, hd] with prompt kv at [0, P), "kv_mask":
    [b, max_len], "next_pos": [b] rope position of the first generated
    token}. The next write position is P — uniform, the caller knows it
    statically.
    """
    b, prompt_len = input_ids.shape
    if prompt_len > max_len:
        raise ValueError(f"prompt bucket {prompt_len} exceeds cache max_len "
                         f"{max_len}")
    mask = attention_mask.astype(jnp.int32)
    positions = jnp.clip(jnp.cumsum(mask, axis=1) - 1, 0, None).astype(jnp.int32)
    cache = init_kv_cache(cfg, b, max_len)
    kv_mask = jnp.pad(mask, ((0, 0), (0, max_len - prompt_len)))
    logits, cache = forward_with_cache(
        params, input_ids, cache, positions, 0, kv_mask, cfg, causal=True,
        last_only=True)
    return {"logits": logits[:, -1], "cache": cache, "kv_mask": kv_mask,
            "next_pos": positions[:, -1] + 1}


# -- the page pool (serve/pages.py) -------------------------------------------
#
# A slot's keys and values live in fixed-size PAGES from a shared pool, found
# through a slot->page table, so resident HBM tracks tokens actually written
# and not one worst-case row a slot. The programs keep a static shape (one
# compile each, no per-batch retracing): the logical view a slot sees is
# `[max_len]` = `pages_per_slot * page_size`, reconstituted per layer by the
# gather below (the prefills, an int8 tick) or walked page by page where it
# lies (the fp tick, `ops/paged_attention.py`). That is `generate()`'s cache
# row, so the fp path emits `generate()`'s tokens: pages a slot does not own
# (the garbage page included) only ever contribute through masked positions,
# whose scores are the same NEG_INF constant and whose softmax weights are
# exactly 0.0, or, in the fp tick, are not read at all.
#
# How the pool is walked: the three paged programs scan over (layer weights,
# layer index) only and CARRY the whole pool through `_walk_pool`. A layer's
# write is one scatter into the full pool at `[layer, page, offset]`, its
# read one gather whose indices hold the layer too (`pool[layer,
# page_table]`) or one kernel given the pool whole and the layer as an
# index, so no slice of a whole layer's pages stands between the pool and
# the work. The pool is never the scan's `xs`/`ys`: a scan's `ys`
# is a fresh stacked array that a donated argument cannot alias, which cost
# a layer-sized slice in, a layer-sized store out and a whole-pool copy
# every tick (38% of the tick's busy time on the v5e, PERF.md PR 25). As a
# carry of a donated argument the loop updates the one buffer in place.
#
# int8 pages (`quant="int8"`) store one fp32 scale per (layer, page,
# kv_head): prefill writes whole pages and set the scale from the block
# absmax; decode writes claim a fresh page at offset 0 (pages fill in
# strict logical order) and set its scale from the first token, later
# offsets saturate against it. Dequantization happens on read, in fp32,
# before the cast to the compute dtype — serve/engine.py tolerance-gates
# this path instead of claiming bit parity.


def init_page_pool(cfg: LlamaConfig, num_pages: int, page_size: int,
                   quant: str = "fp") -> dict:
    """Zeroed page pool. k/v: [n_layers, num_pages + 1, page_size, kv_h, hd]
    — ONE extra garbage page at index `num_pages`: released/inactive slots
    point every logical page at it, so their rides through the static-shape
    decode step scatter there instead of into live data. int8 pools carry
    k_scale/v_scale: [n_layers, num_pages + 1, kv_h] fp32 per-page scales."""
    shape = (cfg.num_hidden_layers, num_pages + 1, page_size, cfg.kv_heads,
             cfg.head_dim)
    dt = jnp.int8 if quant == "int8" else cfg.dtype
    pool = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    if quant == "int8":
        sshape = (cfg.num_hidden_layers, num_pages + 1, cfg.kv_heads)
        pool["k_scale"] = jnp.zeros(sshape, jnp.float32)
        pool["v_scale"] = jnp.zeros(sshape, jnp.float32)
    return pool


# absmax floor: an all-zero block quantizes against this instead of 0/0
_SCALE_FLOOR = 1e-8


def quant_page_block(x: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """fp -> int8 against a per-(page, kv_head) scale (broadcast over the
    page and head_dim axes). Saturating: values beyond the scale clip."""
    q = jnp.round(x.astype(jnp.float32) * (127.0 / scale))
    return jnp.clip(q, -127, 127).astype(jnp.int8)


def dequant_page_block(q: jnp.ndarray, scale: jnp.ndarray,
                       dtype) -> jnp.ndarray:
    """int8 -> fp32 dequant against the per-page scale, then the compute-
    dtype cast (the 'fp32 dequant-on-read' half of the contract)."""
    return (q.astype(jnp.float32) * (scale / 127.0)).astype(dtype)


def _block_amax(x: jnp.ndarray, axes) -> jnp.ndarray:
    return jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axes),
                       _SCALE_FLOOR)


def _gather_pages(pool: dict, layer_idx, page_table: jnp.ndarray, dtype):
    """Reconstitute logical kv rows of layer `layer_idx` from the full pool:
    [*, Pmax] page indices -> [*, Pmax * page_size, kv_h, hd] in the compute
    dtype. ONE gather per array, the layer among its indices."""
    with jax.named_scope(trace.SCOPE_KV_GATHER):
        gk = pool["k"][layer_idx, page_table]
        gv = pool["v"][layer_idx, page_table]
        if "k_scale" in pool:
            sk = pool["k_scale"][layer_idx, page_table]
            sv = pool["v_scale"][layer_idx, page_table]
            gk = dequant_page_block(gk, sk[..., None, :, None], dtype)
            gv = dequant_page_block(gv, sv[..., None, :, None], dtype)
        *lead, pmax, page, kvh, hd = gk.shape
        return (gk.reshape(*lead, pmax * page, kvh, hd),
                gv.reshape(*lead, pmax * page, kvh, hd))


def _attend_gathered(q: jnp.ndarray, pool: dict, layer_idx,
                     page_table: jnp.ndarray, mask: jnp.ndarray, dtype,
                     **causality) -> jnp.ndarray:
    """Attention of q ([b, s, h, hd]) over `page_table`'s logical rows of
    layer `layer_idx`, gathered whole: the read of every program whose
    queries are longer than one token or whose pages dequantize on read."""
    gk, gv = _gather_pages(pool, layer_idx, page_table, dtype)
    with jax.named_scope(trace.SCOPE_DECODE_ATTN):
        return attention(q, gk, gv, mask, **causality)


def _walk_pool(params: Params, x: jnp.ndarray, pool: dict, cos: jnp.ndarray,
               sin: jnp.ndarray, cfg: LlamaConfig, write,
               attend) -> tuple[jnp.ndarray, dict]:
    """Run the cached layers over the page pool IN PLACE: the one way the
    paged programs walk it. The scan's `xs` are the layer weights and the
    layer index; the pool (k, v and, for int8, their scales) rides in the
    carry and is only ever touched through full-pool scatters and gathers.

    Per layer, in the order every cached layer runs: q/k/v projection,
    `write(pages, scales, layer_idx, rows) -> (pages, scales)` once for k
    and once for v (`scales` is None for fp pools; `rows` is [b, s, kv_h,
    hd]), `attend(q, pool, layer_idx)` over the pool as just written (the
    prefills and the int8 tick gather the logical rows, `_attend_gathered`;
    the fp tick reads the pages where they lie), output projection and MLP.
    Returns the hidden state and the pool."""
    def body(carry, xs):
        h, pool = carry
        layer, i = xs
        q, k, v = _project_qkv(layer, h, cos, sin, cfg)
        with jax.named_scope(trace.SCOPE_KV_WRITE):
            pool = dict(pool)
            for name, rows in (("k", k), ("v", v)):
                pool[name], scales = write(
                    pool[name], pool.get(f"{name}_scale"), i, rows)
                if scales is not None:
                    pool[f"{name}_scale"] = scales
        attn_out = attend(q, pool, i)
        return (_attn_out_and_mlp(layer, h, attn_out, cfg), pool), None

    (x, pool), _ = jax.lax.scan(
        body, (x, pool), (params["layers"], jnp.arange(pool["k"].shape[0])))
    return x, pool


@partial(jax.jit, donate_argnames=("pool", "kv_mask"))
def write_pages(pool: dict, kv_mask: jnp.ndarray, slot: jnp.ndarray,
                page_rows: jnp.ndarray, row_cache: dict,
                row_kv_mask: jnp.ndarray) -> tuple[dict, jnp.ndarray]:
    """Splice one prefilled request into its physical pages. `row_cache` is
    a `prefill_prompt` result taken at max_len == the prompt bucket (k/v:
    [L, 1, bucket, kv_h, hd], bucket a multiple of page_size), `page_rows`
    the [bucket / page_size] physical pages the slot owns for it. The
    logical kv_mask row `slot` is rewritten WHOLE (zeros past the bucket),
    so whatever a previous occupant left in the row is dead after
    admission."""
    L, _, bucket, kvh, hd = row_cache["k"].shape
    n_pages = page_rows.shape[0]
    page = bucket // n_pages
    quant = pool["k"].dtype == jnp.int8
    out = dict(pool)
    for name in ("k", "v"):
        blocks = row_cache[name].reshape(L, n_pages, page, kvh, hd)
        if quant:
            scale = _block_amax(blocks, axes=(2, 4))          # [L, n, kvh]
            out[f"{name}_scale"] = out[f"{name}_scale"].at[:, page_rows].set(
                scale)
            blocks = quant_page_block(blocks, scale[:, :, None, :, None])
        out[name] = out[name].at[:, page_rows].set(blocks)
    lmax = kv_mask.shape[1]
    row = jnp.pad(row_kv_mask.astype(kv_mask.dtype),
                  ((0, 0), (0, lmax - bucket)))
    kv_mask = jax.lax.dynamic_update_slice(kv_mask, row, (slot, 0))
    return out, kv_mask


def _write_tokens(pages, scales, layer_idx, rows: jnp.ndarray,
                  w_page: jnp.ndarray, w_off: jnp.ndarray,
                  claimed: jnp.ndarray, claimer=None):
    """Scatter n tokens' kv rows ([n, kv_h, hd]) into (layer_idx, w_page[n],
    w_off[n]) of the full pool. int8: a token in a page this write CLAIMS
    (`claimed`: [n, 1] bool; pages fill in strict logical order, so a
    page's offset 0 is its first write) takes the page's new scale, the
    absmax of token `claimer[n]` (None: its own); every other token
    saturates against the scale its page already has. Duplicate pages in
    one scatter all carry the same scale, so write order within it cannot
    matter."""
    if scales is None:
        return pages.at[layer_idx, w_page, w_off].set(rows), None
    amax = _block_amax(rows, axes=-1)                          # [n, kvh]
    if claimer is not None:
        amax = amax[claimer]
    scale = jnp.where(claimed, amax,
                      jnp.maximum(scales[layer_idx, w_page], _SCALE_FLOOR))
    scales = scales.at[layer_idx, w_page].set(scale)
    pages = pages.at[layer_idx, w_page, w_off].set(
        quant_page_block(rows, scale[:, :, None]))
    return pages, scales


def tick_logits(params: Params, token: jnp.ndarray, pool: dict,
                page_table: jnp.ndarray, pos: jnp.ndarray,
                write_pos: jnp.ndarray, kv_mask: jnp.ndarray,
                active: jnp.ndarray, cfg: LlamaConfig):
    """The decode tick up to its logits: (float32 logits [S, V], pool,
    kv_mask). `paged_decode_step` samples from these; the tests compare
    them with the gathered rows' (tests/test_paged_serving.py)."""
    b = token.shape[0]
    page = pool["k"].shape[2]
    garbage = pool["k"].shape[1] - 1
    # .max(): active rows mark write_pos valid (as a generate() step), inactive
    # rows keep whatever their mask row already says
    kv_mask = kv_mask.at[jnp.arange(b), write_pos].max(
        active.astype(kv_mask.dtype))
    w_page = jnp.take_along_axis(page_table, (write_pos // page)[:, None],
                                 axis=1)[:, 0]
    w_page = jnp.where(active > 0, w_page, garbage)
    w_off = write_pos % page

    x = llama.embed(params, token[:, None], cfg)
    cos, sin = rope_cos_sin(pos[:, None], cfg.head_dim, cfg.rope_theta,
                            dtype=cfg.dtype)

    def write(pages, scales, i, rows):
        # a decode write at offset 0 claims a fresh page with its own absmax
        return _write_tokens(pages, scales, i, rows[:, 0], w_page, w_off,
                             claimed=(w_off == 0)[:, None])

    # the leading logical pages of a row that hold tokens: its write
    # position's page and those before it; a row that is not decoding has
    # none. Pages past them point at the garbage page (serve/pages.py gives
    # decode pages as write_pos crosses into them) and are masked whole.
    live_pages = jnp.where(active > 0, write_pos // page + 1, 0)

    def attend(q, pool, i):
        if "k_scale" in pool:
            # int8 pages dequantize on read: the gathered rows
            return _attend_gathered(q, pool, i, page_table, kv_mask,
                                    cfg.dtype, causal=False)
        with jax.named_scope(trace.SCOPE_DECODE_ATTN):
            return paged_decode_attention(
                q[:, 0], pool["k"], pool["v"], i, page_table, live_pages,
                kv_mask)[:, None]

    x, pool = _walk_pool(params, x, pool, cos, sin, cfg, write, attend)
    x = llama.final_norm(params, x, cfg)
    return llama.lm_head(params, x, cfg)[:, -1, :], pool, kv_mask


@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("pool", "kv_mask"))
def paged_decode_step(params: Params, token: jnp.ndarray, pool: dict,
                      page_table: jnp.ndarray, pos: jnp.ndarray,
                      write_pos: jnp.ndarray, kv_mask: jnp.ndarray,
                      active: jnp.ndarray, keys: jnp.ndarray,
                      temperature: jnp.ndarray, top_k: jnp.ndarray,
                      top_p: jnp.ndarray, cfg: LlamaConfig) -> dict:
    """One decode tick over every slot row, with kv residency resolved
    through `page_table` ([S, pages_per_slot] physical page per logical
    page).

    token/pos/write_pos: [S] int32; kv_mask: [S, max_len]; keys: [S, 2]
    per-request rng chains; temperature/top_k/top_p: [S] per-request
    sampling knobs; `active`: [S] 0/1, the rows actually decoding. Each
    active row mirrors one `generate()` scan step exactly: mark write_pos
    valid BEFORE the forward (the token attends to itself), advance the rng
    chain with the same `split(rng) -> (chain, sub)` discipline, sample with
    the same arithmetic. Inactive rows still ride the static shape (one
    compile); their sampled tokens are discarded by the host scheduler,
    their kv writes are steered to the garbage page and their kv_mask rows
    left untouched: a slot can be MID-CHUNKED-PREFILL during the tick,
    already owning live pages and live mask spans that a stray write_pos=0
    write would corrupt. A row's logical view is [pages_per_slot * page_size]
    == [max_len], `generate()`'s cache row, so the fp path emits
    `generate()`'s tokens wherever the logits do not tie within the
    rounding of a softmax summed page by page (pinned in
    tests/test_paged_serving.py); int8 pools dequantize on read and are
    tolerance-gated instead. Each layer writes this token's kv into (layer,
    w_page, w_off), in place (`_walk_pool`), and attends each slot's live
    pages where they lie in the pool (`ops/paged_attention.py`; the choice
    is the pool's dtype, there is no second fp path); an int8 pool's rows
    are gathered and dequantized as the prefills gather theirs. What the
    sampler costs is the batch's own (`sample_rowwise`): an argmax a row
    while no row has a temperature, a draw on top where one has, and a sort
    of every row only in a tick where a sampling row has a top-k or a
    top-p; inactive rows are staged greedy and never ask for more. Returns
    {"token": [S] next tokens, "pool", "kv_mask", "keys"}; rope and write
    positions advance by one, and the caller tracks them host-side."""
    logits, pool, kv_mask = tick_logits(params, token, pool, page_table, pos,
                                        write_pos, kv_mask, active, cfg)
    with jax.named_scope(trace.SCOPE_SAMPLE):
        split = jax.vmap(jax.random.split)(keys)        # [b, 2, 2]
        nxt = sample_rowwise(logits, temperature, top_k, top_p, split[:, 1])
    return {"token": nxt, "pool": pool, "kv_mask": kv_mask,
            "keys": split[:, 0]}


def _prefill_slot_row(params: Params, input_ids: jnp.ndarray,
                      attention_mask: jnp.ndarray, positions: jnp.ndarray,
                      pool: dict, page_table_row: jnp.ndarray,
                      slot: jnp.ndarray, kv_mask: jnp.ndarray,
                      write_start: jnp.ndarray, cfg: LlamaConfig,
                      write) -> dict:
    """What the chunk and the span prefill share: mark [write_start,
    write_start + C) of logical row `slot` valid, run the cached layers over
    the pool with the caller's `write`, each position attending the slot's
    FULL gathered row with a causal offset, and return the LAST position's
    fp32 logits."""
    mask = attention_mask.astype(jnp.int32)
    kv_mask = jax.lax.dynamic_update_slice(kv_mask, mask, (slot, write_start))
    lmax = kv_mask.shape[1]
    row_mask = jax.lax.dynamic_slice(kv_mask, (slot, 0), (1, lmax))

    x = llama.embed(params, input_ids, cfg)
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                            dtype=cfg.dtype)

    def attend(q, pool, i):
        return _attend_gathered(q, pool, i, page_table_row[None], row_mask,
                                cfg.dtype, causal=True, q_offset=write_start)

    x, pool = _walk_pool(params, x, pool, cos, sin, cfg, write, attend)
    x = llama.final_norm(params, x[:, -1:, :], cfg)
    logits = llama.lm_head(params, x, cfg)
    return {"logits": logits[:, -1], "pool": pool, "kv_mask": kv_mask}


@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("pool", "kv_mask"))
def paged_prefill_chunk(params: Params, input_ids: jnp.ndarray,
                        attention_mask: jnp.ndarray, positions: jnp.ndarray,
                        pool: dict, page_table_row: jnp.ndarray,
                        slot: jnp.ndarray, kv_mask: jnp.ndarray,
                        write_start: jnp.ndarray, cfg: LlamaConfig) -> dict:
    """One bounded prefill chunk of slot `slot`: embed chunk tokens
    ([1, C], C a multiple of page_size, logical span [write_start,
    write_start + C)), write their kv into the slot's pages, and attend
    each chunk position over the slot's FULL gathered logical row (history
    pages + the chunk itself) with a causal offset — the incremental half
    of chunked batched prefill. The engine interleaves these under the
    per-tick token budget so in-flight decodes never stall behind a long
    prompt. Returns the LAST position's fp32 logits (only the final chunk's
    are consumed, to sample the request's first token)."""
    _, C = input_ids.shape
    page = pool["k"].shape[2]
    chunk_pages = page_table_row[write_start // page +
                                 jnp.arange(C // page)]  # [C/page] physical

    def write(pages, scales, i, rows):
        # whole pages: [C / page, page, kv_h, hd] blocks, scale = block absmax
        blocks = rows[0].reshape(C // page, page, -1, cfg.head_dim)
        if scales is not None:
            scale = _block_amax(blocks, axes=(1, 3))           # [C/page, kvh]
            scales = scales.at[i, chunk_pages].set(scale)
            blocks = quant_page_block(blocks, scale[:, None, :, None])
        return pages.at[i, chunk_pages].set(blocks), scales

    return _prefill_slot_row(params, input_ids, attention_mask, positions,
                             pool, page_table_row, slot, kv_mask, write_start,
                             cfg, write)


@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("pool", "kv_mask"))
def paged_prefill_span(params: Params, input_ids: jnp.ndarray,
                       attention_mask: jnp.ndarray, positions: jnp.ndarray,
                       pool: dict, page_table_row: jnp.ndarray,
                       slot: jnp.ndarray, kv_mask: jnp.ndarray,
                       write_start: jnp.ndarray, cfg: LlamaConfig) -> dict:
    """`paged_prefill_chunk` without the page-alignment constraints: prefill
    logical span [write_start, write_start + C) of slot `slot` where
    NEITHER the start nor the length is a page multiple — the tail a
    prefix-cache hit recomputes from its divergence point (serve/pages.py).
    Writes are per-token scatters into (page, offset) pairs instead of
    whole-page blocks, so the span can begin mid-page inside a freshly
    forked copy-on-write page and end anywhere in the bucket; attention
    still runs each span position over the slot's FULL gathered logical row
    (shared prefix pages + the span itself) with a causal offset. int8
    pages follow the decode-write discipline: a page whose offset-0
    position falls inside the span is claimed by that token's absmax,
    earlier (copied/pre-owned) pages keep their scale and the span's writes
    into them saturate against it. One program compiles per distinct span
    length C (write_start is traced); the engine accepts the retrace — a
    cache-hit tail is exactly the work the hit did NOT save."""
    _, C = input_ids.shape
    page = pool["k"].shape[2]

    w_pos = write_start + jnp.arange(C)              # [C] logical positions
    w_page = page_table_row[w_pos // page]           # [C] physical pages
    w_off = w_pos % page                             # [C] offsets within
    # index (within the span) of each token's page-offset-0 position:
    # >= 0 iff the page is CLAIMED by this span (its first position is
    # ours to write), < 0 for the fork page the span enters mid-way
    first_idx = w_pos - w_off - write_start          # [C] signed
    in_span = (first_idx >= 0)[:, None]              # [C, 1]
    first_idx = jnp.clip(first_idx, 0, C - 1)

    def write(pages, scales, i, rows):
        # a claimed page takes the absmax of its offset-0 token
        return _write_tokens(pages, scales, i, rows[0], w_page, w_off,
                             claimed=in_span, claimer=first_idx)

    return _prefill_slot_row(params, input_ids, attention_mask, positions,
                             pool, page_table_row, slot, kv_mask, write_start,
                             cfg, write)
