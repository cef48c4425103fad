"""Serving programs of the hybrid block: `prefill_prompt`, `paged_decode_step`
and `write_pages`, with the signatures of their `models/llama/decode.py`
namesakes, so `serve/engine.py` and `serve/pages.py` drive either family
through `models/family.py` without naming one.

Two stores ride one donated tree (`pool`): the page pool `k` / `v`
[periods, pages + 1, page, kv_h, hd], which only the softmax layers touch (one
layer a period: the pool's depth is the number of periods, not of layers), and
the recurrent store of the KDA layers, `state` float32 [kda layers, slots, H,
dk, dv] and `conv` [kda layers, slots, width - 1, 3 * H * dk] (the last inputs
of the q, k, v convolutions). A slot's row of the recurrent store is written
whole at admission and updated in place every tick; nothing is ever freed.

The layer loop scans over PERIODS, a period's layers unrolled in the body.
Both stores ride its carry and are touched only by indexed reads and writes
(never the scan's `xs` / `ys`, which cannot alias a donated argument:
models/llama/decode.py "How the pool is walked"). A period's weights ride
`xs`, where a plain matmul reads its slice of a stacked leaf in place, all
but the routed experts' `gate` / `up` / `down`: the grouped product would be
handed a copy of a slice, so the body closes over those leaves whole and
`moe_block` takes the stack and the period's place in it
(`model.split_experts`, models/hybrid_moe/model.py).

What a model with recurrent layers cannot do yet is refused by name where the
engine is built (`models/family.py`): a prefix cache, chunked and span
prefill, int8 pages, the dense slot cache.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from llama_pipeline_parallel_tpu.models.hybrid_moe import model as hybrid
from llama_pipeline_parallel_tpu.models.hybrid_moe.config import HybridMoEConfig
from llama_pipeline_parallel_tpu.models.llama import decode as dense_decode
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.ops.attention import attention
from llama_pipeline_parallel_tpu.ops.paged_attention import (
    paged_decode_attention,
)
from llama_pipeline_parallel_tpu.utils import trace

Params = dict
COUNTERS = hybrid.COUNTERS


def init_page_pool(cfg: HybridMoEConfig, num_pages: int, page_size: int,
                   quant: str = "fp") -> dict:
    """Zeroed page pool of the softmax layers, with the garbage page
    (`models/llama/decode.init_page_pool`)."""
    if quant != "fp":
        raise ValueError(f"the hybrid block keeps fp pages only, got {quant!r}")
    shape = (cfg.kv_cache_layers, num_pages + 1, page_size, cfg.kv_heads,
             cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def init_recurrent_store(cfg: HybridMoEConfig, max_slots: int) -> dict:
    """Zeroed recurrent store, one row a slot and KDA layer."""
    layers, H, dk = cfg.recurrent_layers, cfg.kda_heads, cfg.kda_head_dim
    return {
        "state": jnp.zeros((layers, max_slots, H, dk, dk), jnp.float32),
        "conv": jnp.zeros((layers, max_slots, cfg.kda_conv - 1,
                           3 * cfg.kda_width), cfg.dtype)}


def _walk(params: Params, x: jnp.ndarray, valid: jnp.ndarray, stores: dict,
          cfg: HybridMoEConfig, softmax_layer, kda_layer):
    """Run every layer with both stores in the carry. `softmax_layer(layer,
    h, stores, period) -> (h, stores)` and `kda_layer(layer, h, stores,
    kda_index) -> (h, stores)` are the caller's mixers; each is followed by
    its expert half, which takes the routed experts of every period whole
    and the period's place among them. Returns the hidden state, the stores
    and the expert layers' counters summed over layers (int32[6],
    `COUNTERS`)."""
    n = cfg.attn_period
    periods, experts = hybrid.split_experts(params["periods"])

    def body(carry, xs):
        h, stores, counters = carry
        period, p = xs
        for j in range(n):
            if j == 0:
                h, stores = softmax_layer(period["attn"], h, stores, p)
            else:
                h, stores = kda_layer(period["kda"][j - 1], h, stores,
                                      p * (n - 1) + j - 1)
            h, counted = hybrid.moe_block(period["moe"][j], experts[j], p, h,
                                          valid, cfg)
            counters = counters + counted
        return (h, stores, counters), None

    zero = jnp.zeros((len(COUNTERS),), jnp.int32)
    (x, stores, counters), _ = jax.lax.scan(
        body, (x, stores, zero),
        (periods, jnp.arange(cfg.periods)))
    return x, stores, counters


@partial(jax.jit, static_argnames=("cfg", "max_len"))
def prefill_prompt(params: Params, input_ids: jnp.ndarray,
                   attention_mask: jnp.ndarray, cfg: HybridMoEConfig,
                   max_len: int) -> dict:
    """Prefill LEFT-padded prompts ([b, P]) into fresh rows of both stores.
    Returns what the dense `prefill_prompt` returns ({"logits", "cache",
    "kv_mask", "next_pos"}), the cache holding `k` / `v` [periods, b,
    max_len, kv_h, hd] with the prompt at [0, P) and the rows' `state` /
    `conv` after the last position, plus "counters" (int32[6])."""
    b, prompt_len = input_ids.shape
    if prompt_len > max_len:
        raise ValueError(f"prompt bucket {prompt_len} exceeds cache max_len "
                         f"{max_len}")
    mask = attention_mask.astype(jnp.int32)
    valid = mask > 0
    rows = init_recurrent_store(cfg, b)
    kv_shape = (cfg.kv_cache_layers, b, max_len, cfg.kv_heads, cfg.head_dim)
    stores = {"k": jnp.zeros(kv_shape, cfg.dtype),
              "v": jnp.zeros(kv_shape, cfg.dtype), **rows}
    x = llama.embed(params, input_ids, cfg)

    def softmax_layer(layer, h, stores, p):
        hidden, q, k, v = hybrid.attn_project(layer, h, cfg)
        with jax.named_scope(trace.SCOPE_KV_WRITE):
            stores = {**stores,
                      "k": stores["k"].at[p, :, :prompt_len].set(k),
                      "v": stores["v"].at[p, :, :prompt_len].set(v)}
        with jax.named_scope(trace.SCOPE_ATTN_CORE):
            out = attention(q, k, v, mask, causal=True)
        return hybrid.attn_output(layer, h, hidden, out, cfg), stores

    def kda_layer(layer, h, stores, index):
        pr = hybrid.kda_project(layer, h, valid, stores["conv"][index], cfg)
        o, state = hybrid.kda_chunked(pr["q"], pr["k"], pr["v"], pr["g"],
                                      pr["beta"], stores["state"][index])
        with jax.named_scope(trace.STATE_WRITE):
            stores = {**stores,
                      "state": stores["state"].at[index].set(state),
                      "conv": stores["conv"].at[index].set(pr["conv"])}
        return hybrid.kda_output(layer, h, o, pr["gate"], cfg), stores

    x, stores, counters = _walk(params, x, valid, stores, cfg, softmax_layer,
                                kda_layer)
    x = llama.final_norm(params, x[:, -1:, :], cfg)
    logits = llama.lm_head(params, x, cfg)
    return {"logits": logits[:, -1], "cache": stores,
            "kv_mask": jnp.pad(mask, ((0, 0), (0, max_len - prompt_len))),
            "next_pos": jnp.sum(mask, axis=1).astype(jnp.int32),
            "counters": counters}


@partial(jax.jit, donate_argnames=("pool", "kv_mask"))
def write_pages(pool: dict, kv_mask: jnp.ndarray, slot: jnp.ndarray,
                page_rows: jnp.ndarray, row_cache: dict,
                row_kv_mask: jnp.ndarray) -> tuple[dict, jnp.ndarray]:
    """Splice one prefilled request (`prefill_prompt` at b == 1, max_len ==
    the bucket) into both stores: its keys and values into the slot's pages
    as the dense `write_pages` does, its recurrent rows whole into row `slot`
    (whatever the last occupant left there is gone)."""
    pool, kv_mask = dense_decode.write_pages(
        pool, kv_mask, slot, page_rows,
        {"k": row_cache["k"], "v": row_cache["v"]}, row_kv_mask)
    with jax.named_scope(trace.STATE_WRITE):
        for name in ("state", "conv"):
            start = (0, slot) + (0,) * (pool[name].ndim - 2)
            pool[name] = jax.lax.dynamic_update_slice(
                pool[name], row_cache[name].astype(pool[name].dtype), start)
    return pool, kv_mask


def tick_logits(params: Params, token: jnp.ndarray, pool: dict,
                page_table: jnp.ndarray, write_pos: jnp.ndarray,
                kv_mask: jnp.ndarray, active: jnp.ndarray,
                cfg: HybridMoEConfig):
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

    def softmax_layer(layer, h, stores, p):
        hidden, q, k, v = hybrid.attn_project(layer, h, cfg)
        with jax.named_scope(trace.SCOPE_KV_WRITE):
            stores = dict(stores)
            for name, rows in (("k", k), ("v", v)):
                stores[name], _ = dense_decode._write_tokens(
                    stores[name], None, p, rows[:, 0], w_page, w_off, None)
        with jax.named_scope(trace.SCOPE_DECODE_ATTN):
            out = paged_decode_attention(
                q[:, 0], stores["k"], stores["v"], p, page_table, live_pages,
                kv_mask)[:, None]
        return hybrid.attn_output(layer, h, hidden, out, cfg), stores

    def kda_layer(layer, h, stores, index):
        with jax.named_scope(trace.STATE_GATHER):
            state, conv = stores["state"][index], stores["conv"][index]
        pr = hybrid.kda_project(layer, h, valid, conv, cfg)
        o, state = hybrid.kda_step(pr["q"][:, 0], pr["k"][:, 0], pr["v"][:, 0],
                                   pr["g"][:, 0], pr["beta"][:, 0], state)
        with jax.named_scope(trace.STATE_WRITE):
            stores = {**stores,
                      "state": stores["state"].at[index].set(state),
                      "conv": stores["conv"].at[index].set(pr["conv"])}
        return hybrid.kda_output(layer, h, o[:, None], pr["gate"], cfg), stores

    x, pool, counters = _walk(params, x, valid, pool, cfg, softmax_layer,
                              kda_layer)
    x = llama.final_norm(params, x, cfg)
    return llama.lm_head(params, x, cfg)[:, -1, :], pool, kv_mask, counters


@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("pool", "kv_mask"))
def paged_decode_step(params: Params, token: jnp.ndarray, pool: dict,
                      page_table: jnp.ndarray, pos: jnp.ndarray,
                      write_pos: jnp.ndarray, kv_mask: jnp.ndarray,
                      active: jnp.ndarray, keys: jnp.ndarray,
                      temperature: jnp.ndarray, top_k: jnp.ndarray,
                      top_p: jnp.ndarray, cfg: HybridMoEConfig) -> dict:
    """One decode tick over every slot row, the arguments of the dense
    `paged_decode_step` (`pos` is unused: no layer is rotary). A softmax
    layer writes this token's keys and values into (period, w_page, w_off)
    and attends each slot's live pages where they lie in the pool
    (`ops/paged_attention.py`, its 8 query heads a KV head by shape; the
    output gate stays outside); a KDA layer reads its rows of the recurrent
    store, applies one step of the recurrence and writes them back. Rows
    that are not `active` leave both stores as they were (their page writes
    go to the garbage page; their recurrence runs with b = 0, a = 1) and are
    routed to no expert. The sampler's cost is the batch's own
    (`sample_rowwise`: an argmax a row unless an active row samples, a sort
    only where one filters). Returns the dense tick's outputs plus
    "counters" (int32[6], `COUNTERS`, summed over the expert layers)."""
    del pos
    logits, pool, kv_mask, counters = tick_logits(
        params, token, pool, page_table, write_pos, kv_mask, active, cfg)
    with jax.named_scope(trace.SCOPE_SAMPLE):
        split = jax.vmap(jax.random.split)(keys)        # [b, 2, 2]
        nxt = dense_decode.sample_rowwise(logits, temperature, top_k, top_p,
                                          split[:, 1])
    return {"token": nxt, "pool": pool, "kv_mask": kv_mask,
            "keys": split[:, 0], "counters": counters}
