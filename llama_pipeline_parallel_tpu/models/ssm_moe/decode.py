"""Serving programs of the state-space / expert block: `prefill_prompt`,
`paged_decode_step` and `write_pages`, with the signatures of their
`models/llama/decode.py` namesakes, so `serve/engine.py` and `serve/pages.py`
drive the family through `models/family.py` without naming it.

Two stores ride one donated tree (`pool`), as the hybrid block's do: the page
pool `k` / `v` [softmax layers, pages + 1, page, kv_h, hd], which only the
`*` layers touch, and the recurrent store of the Mamba-2 layers, `state`
float32 [M layers, slots, H, P, N] and `conv` [M layers, slots, width - 1,
HP + 2 GN] (the last inputs of the convolution). A slot's row of the
recurrent store is written whole at admission (`write_pages`, the hybrid
block's: it splices any `state` / `conv` leaves a row a slot) and updated in
place every tick: the float32 `state` by `ops/ssm_state_step.py`, a kernel
that takes the whole store aliased to its output and steps one layer's rows
where they lie (read once, written once); `conv`, 20 MB a tick in all, by
XLA's gather and `dynamic-update-slice`. Nothing is ever freed.

The layers are unrolled in the order `cfg.pattern` gives (it has no period
in general), each reading and writing its own index of its kind's store;
every weight is the buffer it is stored in (models/ssm_moe/model.py).

What a model with recurrent layers cannot do yet is refused by name where the
engine is built (`models/family.py`): a prefix cache, chunked and span
prefill, int8 pages.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from llama_pipeline_parallel_tpu.models.hybrid_moe import decode as hybrid_decode
from llama_pipeline_parallel_tpu.models.hybrid_moe import model as hybrid
from llama_pipeline_parallel_tpu.models.llama import decode as dense_decode
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.ssm_moe import model as ssm
from llama_pipeline_parallel_tpu.models.ssm_moe.config import SsmMoEConfig
from llama_pipeline_parallel_tpu.ops.attention import attention
from llama_pipeline_parallel_tpu.ops.paged_attention import (
    paged_decode_attention,
)
from llama_pipeline_parallel_tpu.ops.ssm_state_step import ssm_state_step
from llama_pipeline_parallel_tpu.utils import trace

Params = dict
COUNTERS = ssm.COUNTERS
write_pages = hybrid_decode.write_pages


def init_page_pool(cfg: SsmMoEConfig, num_pages: int, page_size: int,
                   quant: str = "fp") -> dict:
    """Zeroed page pool of the softmax layers, with the garbage page
    (`models/llama/decode.init_page_pool`)."""
    if quant != "fp":
        raise ValueError(f"the state-space block keeps fp pages only, got "
                         f"{quant!r}")
    shape = (cfg.kv_cache_layers, num_pages + 1, page_size, cfg.kv_heads,
             cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def init_recurrent_store(cfg: SsmMoEConfig, max_slots: int) -> dict:
    """Zeroed recurrent store, one row a slot and Mamba-2 layer."""
    layers = cfg.recurrent_layers
    return {
        "state": jnp.zeros((layers, max_slots, cfg.ssm_heads, cfg.ssm_head_dim,
                            cfg.ssm_state), jnp.float32),
        "conv": jnp.zeros((layers, max_slots, cfg.ssm_conv - 1,
                           cfg.ssm_conv_width), cfg.dtype)}


def _walk(params: Params, x: jnp.ndarray, valid: jnp.ndarray, stores: dict,
          cfg: SsmMoEConfig, softmax_layer, ssm_layer):
    """Run every layer in the pattern's order. `softmax_layer(layer, h,
    stores, index) -> (h, stores)` and `ssm_layer(layer, h, stores, index)
    -> (h, stores)` are the caller's mixers, `index` the layer's place among
    those of its kind (its row of the kind's store). Returns the hidden
    state, the stores and the counters summed over layers (int32[7],
    `COUNTERS`: the expert layers' six, then the rows the Mamba-2 layers
    advanced)."""
    experts = jnp.zeros((len(hybrid.COUNTERS),), jnp.int32)
    for i, (kind, layer) in enumerate(zip(cfg.pattern, params["layers"])):
        index = cfg.kind_index(i)
        if kind == "M":
            x, stores = ssm_layer(layer, x, stores, index)
        elif kind == "*":
            x, stores = softmax_layer(layer, x, stores, index)
        else:
            x, counted = ssm.latent_moe_block(layer, x, valid, cfg)
            experts = experts + counted
    ssm_rows = jnp.sum(valid).astype(jnp.int32) * cfg.recurrent_layers
    return x, stores, jnp.concatenate([experts, ssm_rows[None]])


@partial(jax.jit, static_argnames=("cfg", "max_len"))
def prefill_prompt(params: Params, input_ids: jnp.ndarray,
                   attention_mask: jnp.ndarray, cfg: SsmMoEConfig,
                   max_len: int) -> dict:
    """Prefill LEFT-padded prompts ([b, P]) into fresh rows of both stores.
    Returns what the dense `prefill_prompt` returns ({"logits", "cache",
    "kv_mask", "next_pos"}), the cache holding `k` / `v` [softmax layers, b,
    max_len, kv_h, hd] with the prompt at [0, P) and the rows' `state` /
    `conv` after the last position, plus "counters" (int32[7])."""
    b, prompt_len = input_ids.shape
    if prompt_len > max_len:
        raise ValueError(f"prompt bucket {prompt_len} exceeds cache max_len "
                         f"{max_len}")
    mask = attention_mask.astype(jnp.int32)
    valid = mask > 0
    kv_shape = (cfg.kv_cache_layers, b, max_len, cfg.kv_heads, cfg.head_dim)
    stores = {"k": jnp.zeros(kv_shape, cfg.dtype),
              "v": jnp.zeros(kv_shape, cfg.dtype),
              **init_recurrent_store(cfg, b)}
    x = llama.embed(params, input_ids, cfg)

    def softmax_layer(layer, h, stores, index):
        hidden, q, k, v = hybrid.attn_project(layer, h, cfg)
        with jax.named_scope(trace.SCOPE_KV_WRITE):
            stores = {**stores,
                      "k": stores["k"].at[index, :, :prompt_len].set(k),
                      "v": stores["v"].at[index, :, :prompt_len].set(v)}
        with jax.named_scope(trace.SCOPE_ATTN_CORE):
            out = attention(q, k, v, mask, causal=True)
        return hybrid.attn_output(layer, h, hidden, out, cfg), stores

    def ssm_layer(layer, h, stores, index):
        pr = ssm.ssm_project(layer, h, valid, stores["conv"][index], cfg)
        y, state = ssm.ssm_chunked(
            pr["x"], pr["dt"], -jnp.exp(layer["A_log"]), pr["B"], pr["C"],
            stores["state"][index], cfg.ssm_chunk)
        with jax.named_scope(trace.STATE_WRITE):
            stores = {**stores,
                      "state": stores["state"].at[index].set(state),
                      "conv": stores["conv"].at[index].set(pr["conv"])}
        return ssm.ssm_output(layer, h, y, pr["x"], pr["z"], cfg), stores

    x, stores, counters = _walk(params, x, valid, stores, cfg, softmax_layer,
                                ssm_layer)
    x = llama.final_norm(params, x[:, -1:, :], cfg)
    logits = llama.lm_head(params, x, cfg)
    return {"logits": logits[:, -1], "cache": stores,
            "kv_mask": jnp.pad(mask, ((0, 0), (0, max_len - prompt_len))),
            "next_pos": jnp.sum(mask, axis=1).astype(jnp.int32),
            "counters": counters}


def tick_logits(params: Params, token: jnp.ndarray, pool: dict,
                page_table: jnp.ndarray, write_pos: jnp.ndarray,
                kv_mask: jnp.ndarray, active: jnp.ndarray,
                cfg: SsmMoEConfig):
    """The decode tick up to its logits: (float32 logits [b, V], both stores,
    kv_mask, counters). `paged_decode_step` samples from these; the tests
    compare them with the reference's."""
    b = token.shape[0]
    page = pool["k"].shape[2]
    garbage = pool["k"].shape[1] - 1
    kv_mask = kv_mask.at[jnp.arange(b), write_pos].max(
        active.astype(kv_mask.dtype))
    w_page = jnp.take_along_axis(page_table, (write_pos // page)[:, None],
                                 axis=1)[:, 0]
    w_page = jnp.where(active > 0, w_page, garbage)
    w_off = write_pos % page
    valid = (active > 0)[:, None]
    live_pages = jnp.where(active > 0, write_pos // page + 1, 0)

    x = llama.embed(params, token[:, None], cfg)

    def softmax_layer(layer, h, stores, index):
        hidden, q, k, v = hybrid.attn_project(layer, h, cfg)
        with jax.named_scope(trace.SCOPE_KV_WRITE):
            stores = dict(stores)
            for name, rows in (("k", k), ("v", v)):
                stores[name], _ = dense_decode._write_tokens(
                    stores[name], None, index, rows[:, 0], w_page, w_off, None)
        with jax.named_scope(trace.SCOPE_DECODE_ATTN):
            out = paged_decode_attention(
                q[:, 0], stores["k"], stores["v"], index, page_table,
                live_pages, kv_mask)[:, None]
        return hybrid.attn_output(layer, h, hidden, out, cfg), stores

    def ssm_layer(layer, h, stores, index):
        with jax.named_scope(trace.STATE_GATHER):
            conv = stores["conv"][index]
        pr = ssm.ssm_project(layer, h, valid, conv, cfg)
        with jax.named_scope(trace.SSM_STEP):
            # the float32 state is stepped where it lies in the store
            y, state = ssm_state_step(
                stores["state"], index, pr["x"][:, 0], pr["dt"][:, 0],
                -jnp.exp(layer["A_log"]), pr["B"][:, 0], pr["C"][:, 0])
        with jax.named_scope(trace.STATE_WRITE):
            # a row that is not decoding keeps its convolution inputs too
            conv = jnp.where(valid[..., None], pr["conv"], conv)
            stores = {**stores, "state": state,
                      "conv": stores["conv"].at[index].set(conv)}
        return ssm.ssm_output(layer, h, y[:, None], pr["x"], pr["z"],
                              cfg), stores

    x, pool, counters = _walk(params, x, valid, pool, cfg, softmax_layer,
                              ssm_layer)
    x = llama.final_norm(params, x, cfg)
    return llama.lm_head(params, x, cfg)[:, -1, :], pool, kv_mask, counters


@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("pool", "kv_mask"))
def paged_decode_step(params: Params, token: jnp.ndarray, pool: dict,
                      page_table: jnp.ndarray, pos: jnp.ndarray,
                      write_pos: jnp.ndarray, kv_mask: jnp.ndarray,
                      active: jnp.ndarray, keys: jnp.ndarray,
                      temperature: jnp.ndarray, top_k: jnp.ndarray,
                      top_p: jnp.ndarray, cfg: SsmMoEConfig) -> dict:
    """One decode tick over every slot row, the arguments of the dense
    `paged_decode_step` (`pos` is unused: no layer is rotary). A softmax
    layer writes this token's keys and values into (layer, w_page, w_off)
    and attends each slot's live pages where they lie in the pool
    (`ops/paged_attention.py`, its 16 query heads a KV head by shape); a
    Mamba-2 layer applies one step of the recurrence to its rows of the
    recurrent store in place (`ops/ssm_state_step.py`). Rows that are not
    `active` leave both stores as they were (their page writes go to the
    garbage page; their recurrence runs with dt = 0) and are routed to no
    expert. Returns the dense tick's outputs plus "counters" (int32[7],
    `COUNTERS`)."""
    del pos
    logits, pool, kv_mask, counters = tick_logits(
        params, token, pool, page_table, write_pos, kv_mask, active, cfg)
    with jax.named_scope(trace.SCOPE_SAMPLE):
        split = jax.vmap(jax.random.split)(keys)        # [b, 2, 2]
        nxt = dense_decode.sample_rowwise(logits, temperature, top_k, top_p,
                                          split[:, 1])
    return {"token": nxt, "pool": pool, "kv_mask": kv_mask,
            "keys": split[:, 0], "counters": counters}
