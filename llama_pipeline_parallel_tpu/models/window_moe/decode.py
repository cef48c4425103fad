"""Serving programs of the window / full softmax block: `prefill_prompt`,
`paged_prefill_chunk`, `paged_decode_step` and `write_pages`, with the
signatures of their `models/llama/decode.py` namesakes, so `serve/engine.py`
and `serve/pages.py` drive this family through `models/family.py` without
naming it.

Two stores of different shape for one slot ride one donated tree (`pool`):

- pages, for the FULL layers, in the pages the slot's table names: `k` [full
  layers, pages + 1, page * kv_h, 256] and `v` [full layers, pages + 1, page
  * kv_h, 128], a page as the matrix the tick's kernel reads (row r is token
  r // kv_h of KV head r % kv_h; a key's 192 numbers padded to whole lanes,
  `model.stored_key`). XLA:TPU tiles the two last axes, so behind a KV-head
  axis of 4 a 192-wide page, and a 256-wide one too, is copied whole in
  front of the kernel, and a chunk's scatter of whole pages turns the values'
  pool to a layout of its own and back (1.9 GB, every chunk): only a page
  stored as its matrix is read and written in place by every program;
- a ring a slot, for the WINDOW layers (`init_recurrent_store`, as the
  latent family keeps its sliding layers' entries): `ring_k` [window layers,
  slots, R, kv_h, 256] and `ring_v` [window layers, slots, R, kv_h, 128], R =
  the window, logical place p at p % R. Older places are overwritten: the
  layer never sees them again. The tick reads a slot's ring as a pool of one
  page a slot through the same kernel as the pages
  (`ops/paged_attention.py`), with the layer's sinks.

So a token costs the full layers' `kv_h x (256 + 128)` numbers for the life
of the request and a window layer nothing once 128 more have come: a sixth
of what seven full layers of 8 KV heads would keep (a seventh at the keys'
published width).

The layers are unrolled in the order `cfg.pattern` gives, each reading and
writing its own index of its kind's store; every weight is the buffer it is
stored in (models/window_moe/model.py).

Positions. A slot's LOGICAL row is its left-padded prompt bucket followed by
what it decoded, as the mask row `kv_mask[slot]` describes it; pages, ring
places, the causal order and the window all count logical places, and a pad
is never visible (pads lie in front of every token, so a window of logical
places holds the same tokens as a window of positions). Rope takes the
token's own position (pads not counted), as the engine passes it.

A chunk of a full layer gathers the slot's row of pages (105 MB a layer at
34,304 places: a fifth of a millisecond of HBM) and attends it causally in a
kernel blocked over keys, whose key axis ends with the chunk's own last
place; a chunk of a window layer reads the ring for the places before it,
attends the band, and leaves its last places in the ring.

What this family cannot do yet is refused by name where the engine is built
(`models/family.py`): a prefix cache and the span prefill, int8 pages.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from llama_pipeline_parallel_tpu.models.llama import decode as dense_decode
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.window_moe import model as window
from llama_pipeline_parallel_tpu.models.window_moe.config import (
    FULL,
    WINDOW,
    WindowMoEConfig,
)
from llama_pipeline_parallel_tpu.ops.gqa_prefill_attention import (
    window_context,
)
from llama_pipeline_parallel_tpu.ops.paged_attention import (
    paged_decode_attention,
)
from llama_pipeline_parallel_tpu.utils import trace

Params = dict
COUNTERS = window.COUNTERS


def init_page_pool(cfg: WindowMoEConfig, num_pages: int, page_size: int,
                   quant: str = "fp") -> dict:
    """Zeroed pages of the full layers, with the garbage page
    (`models/llama/decode.init_page_pool`)."""
    if quant != "fp":
        raise ValueError(f"the window block keeps fp pages only, got "
                         f"{quant!r}")
    lead = (cfg.full_layers, num_pages + 1)
    G = cfg.full_kv_heads
    return {"k": jnp.zeros(lead + (page_size * G, window.key_store_width(cfg)),
                           cfg.dtype),
            "v": jnp.zeros(lead + (page_size * G, cfg.v_head_dim), cfg.dtype)}


def init_recurrent_store(cfg: WindowMoEConfig, max_slots: int) -> dict:
    """The per-slot store: a zeroed ring a slot and window layer."""
    lead = (cfg.window_layers, max_slots, cfg.ring_len, cfg.window_kv_heads)
    return {"ring_k": jnp.zeros(lead + (window.key_store_width(cfg),),
                                cfg.dtype),
            "ring_v": jnp.zeros(lead + (cfg.v_head_dim,), cfg.dtype)}


def _walk(params: Params, x: jnp.ndarray, valid: jnp.ndarray, stores: dict,
          cfg: WindowMoEConfig, full_layer, window_layer, read: jnp.ndarray,
          mlp_scope: str):
    """Run every layer in the pattern's order. `full_layer(layer, h, stores,
    index) -> (h, stores)` and `window_layer(layer, h, stores, index) -> (h,
    stores)` are the caller's mixers, `index` the layer's place among those
    of its kind (its row of the kind's store); `read`: int32[2], the entries
    the queries read in ONE window layer and in ONE full layer. Returns the
    hidden state, the stores and the counters summed over layers
    (`COUNTERS`)."""
    experts = jnp.zeros((len(COUNTERS) - 2,), jnp.int32)
    for i, layer in enumerate(params["layers"]):
        mixer = window_layer if cfg.pattern[i] == WINDOW else full_layer
        x, stores = mixer(layer, x, stores, cfg.kind_index(i))
        x, counted = window.feed_forward(layer, x, valid, cfg.moe_layers[i],
                                         cfg, mlp_scope)
        experts = experts + counted
    layers = jnp.asarray([cfg.window_layers, cfg.full_layers], jnp.int32)
    return x, stores, jnp.concatenate([experts, read * layers])


@partial(jax.jit, static_argnames=("cfg", "max_len"))
def prefill_prompt(params: Params, input_ids: jnp.ndarray,
                   attention_mask: jnp.ndarray, cfg: WindowMoEConfig,
                   max_len: int) -> dict:
    """Prefill LEFT-padded prompts ([b, P]) into fresh rows of the stores.
    Returns what the dense `prefill_prompt` returns ({"logits", "cache",
    "kv_mask", "next_pos"}), the cache holding `k` [full layers, b, max_len,
    kv_h, 256] and `v` [..., 128] with the prompt at [0, P) and `ring_k` /
    `ring_v` [window layers, b, R, kv_h, *] with the prompt's last places at
    p % R, plus "counters" (`COUNTERS`)."""
    b, P = input_ids.shape
    if P > max_len:
        raise ValueError(f"prompt bucket {P} exceeds cache max_len {max_len}")
    mask = attention_mask.astype(jnp.int32)
    valid = mask > 0
    positions = jnp.clip(jnp.cumsum(mask, axis=1) - 1, 0, None).astype(jnp.int32)
    places = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (b, P))
    W = window.key_store_width(cfg)
    lead = (cfg.full_layers, b, max_len, cfg.full_kv_heads)
    stores = {"k": jnp.zeros(lead + (W,), cfg.dtype),
              "v": jnp.zeros(lead + (cfg.v_head_dim,), cfg.dtype),
              **init_recurrent_store(cfg, b)}
    before = window_context(P, cfg.sliding_window)
    kept = min(P, cfg.ring_len)
    ring_at = (P - kept + jnp.arange(kept)) % cfg.ring_len
    x = llama.embed(params, input_ids, cfg)

    def full_layer(layer, h, stores, index):
        q, k, v = window.project(layer, h, positions, cfg.kind_of(FULL), cfg)
        with jax.named_scope(trace.SCOPE_KV_WRITE):
            stores = {**stores,
                      "k": stores["k"].at[index, :, :P].set(
                          window.stored_key(k, W)),
                      "v": stores["v"].at[index, :, :P].set(v)}
        return window.full_span(layer, h, q, k, v, valid, jnp.int32(0),
                                cfg), stores

    def window_layer(layer, h, stores, index):
        kd = cfg.kind_of(WINDOW)
        q, k, v = window.project(layer, h, positions, kd, cfg)
        nothing = lambda width: jnp.zeros((b, before, kd.kv_heads, width),
                                          cfg.dtype)
        h = window.window_span(
            layer, h, q, k, v, nothing(cfg.head_dim), nothing(cfg.v_head_dim),
            jnp.zeros((b, before), bool), valid, cfg)
        with jax.named_scope(trace.RING_WRITE):
            at = (index, jnp.arange(b)[:, None], ring_at[None, :])
            stores = {**stores,
                      "ring_k": stores["ring_k"].at[at].set(
                          window.stored_key(k[:, P - kept:], W)),
                      "ring_v": stores["ring_v"].at[at].set(v[:, P - kept:])}
        return h, stores

    x, stores, counters = _walk(
        params, x, valid, stores, cfg, full_layer, window_layer,
        window.span_counts(valid, places, valid, cfg), trace.SCOPE_MLP)
    x = llama.final_norm(params, x[:, -1:, :], cfg)
    logits = llama.lm_head(params, x, cfg)
    return {"logits": logits[:, -1], "cache": stores,
            "kv_mask": jnp.pad(mask, ((0, 0), (0, max_len - P))),
            "next_pos": jnp.sum(mask, axis=1).astype(jnp.int32),
            "counters": counters}


@partial(jax.jit, donate_argnames=("pool", "kv_mask"))
def write_pages(pool: dict, kv_mask: jnp.ndarray, slot: jnp.ndarray,
                page_rows: jnp.ndarray, row_cache: dict,
                row_kv_mask: jnp.ndarray) -> tuple[dict, jnp.ndarray]:
    """Splice one prefilled request (`prefill_prompt` at b == 1, max_len ==
    the bucket) into the stores: its keys and values into the slot's pages,
    its rings whole into row `slot` (whatever the last occupant left there
    is gone), and the mask row rewritten whole."""
    out = dict(pool)
    n_pages = page_rows.shape[0]
    with jax.named_scope(trace.SCOPE_KV_WRITE):
        for name in ("k", "v"):
            # a page's rows in the shape the pool keeps them
            blocks = row_cache[name].reshape(
                row_cache[name].shape[0], n_pages, *pool[name].shape[2:])
            out[name] = out[name].at[:, page_rows].set(blocks)
    with jax.named_scope(trace.RING_WRITE):
        for name in ("ring_k", "ring_v"):
            out[name] = jax.lax.dynamic_update_slice(
                out[name], row_cache[name].astype(out[name].dtype),
                (0, slot, 0, 0, 0))
    row = jnp.pad(row_kv_mask.astype(kv_mask.dtype),
                  ((0, 0), (0, kv_mask.shape[1] - row_kv_mask.shape[1])))
    return out, jax.lax.dynamic_update_slice(kv_mask, row, (slot, 0))


def tick_logits(params: Params, token: jnp.ndarray, pool: dict,
                page_table: jnp.ndarray, pos: jnp.ndarray,
                write_pos: jnp.ndarray, kv_mask: jnp.ndarray,
                active: jnp.ndarray, cfg: WindowMoEConfig):
    """The decode tick up to its logits: (float32 logits [b, V], the stores,
    kv_mask, counters). `paged_decode_step` samples from these; the tests
    compare them with the reference's."""
    b = token.shape[0]
    G = cfg.full_kv_heads
    full_depth, n_pool, page_rows, dv = pool["v"].shape
    page = page_rows // G
    garbage = n_pool - 1
    W = window.key_store_width(cfg)
    scale = cfg.head_dim ** -0.5
    kv_mask = kv_mask.at[jnp.arange(b), write_pos].max(
        active.astype(kv_mask.dtype))
    w_page = jnp.take_along_axis(page_table, (write_pos // page)[:, None],
                                 axis=1)[:, 0]
    w_page = jnp.where(active > 0, w_page, garbage)
    w_off = write_pos % page
    rows = active > 0
    valid = rows[:, None]
    positions = pos[:, None]
    by_row = jnp.arange(b)
    ring_at = write_pos % cfg.ring_len
    live_pages = jnp.where(rows, write_pos // page + 1, 0)
    seen = window.ring_mask(write_pos, kv_mask, cfg) & valid     # [b, R]
    # a decoding row reads the valid places up to its own in a full layer
    places = jnp.arange(kv_mask.shape[1], dtype=jnp.int32)[None, :]
    visible = (kv_mask > 0) & (places <= write_pos[:, None]) & valid
    read = jnp.stack([jnp.sum(seen), jnp.sum(visible)]).astype(jnp.int32)
    # a slot's ring as a pool of one page a slot
    ring_table = by_row[:, None].astype(jnp.int32)

    x = llama.embed(params, token[:, None], cfg)

    def full_layer(layer, h, stores, index):
        q, k, v = window.project(layer, h, positions, cfg.kind_of(FULL), cfg)
        with jax.named_scope(trace.SCOPE_KV_WRITE):
            # a token's KV heads at rows [w_off * kv_h, (w_off + 1) * kv_h)
            # of the page's matrix
            at = (index, w_page[:, None],
                  w_off[:, None] * G + jnp.arange(G)[None, :])
            stores = {**stores,
                      "k": stores["k"].at[at].set(
                          window.stored_key(k[:, 0], W)),
                      "v": stores["v"].at[at].set(v[:, 0])}
        with jax.named_scope(trace.FULL_DECODE_ATTN):
            # the pages' matrices seen with their KV-head axis (the kernel
            # reads them as the matrices they are: no copy)
            by_head = lambda a: a.reshape(full_depth, n_pool, page, G, -1)
            out = paged_decode_attention(
                window.stored_key(q[:, 0], W), by_head(stores["k"]),
                by_head(stores["v"]), index, page_table, live_pages, kv_mask,
                None, scale)[:, None]
        return window.attn_output(layer, h, out, cfg), stores

    def window_layer(layer, h, stores, index):
        q, k, v = window.project(layer, h, positions, cfg.kind_of(WINDOW), cfg)
        with jax.named_scope(trace.RING_WRITE):
            # a row that is not decoding keeps the place as it was: a slot in
            # the middle of a chunked prefill already owns its ring
            stores = dict(stores)
            for name, new in (("ring_k", window.stored_key(k[:, 0], W)),
                              ("ring_v", v[:, 0])):
                old = stores[name][index, by_row, ring_at]
                stores[name] = stores[name].at[index, by_row, ring_at].set(
                    jnp.where(valid[..., None], new, old))
        with jax.named_scope(trace.WINDOW_DECODE_ATTN):
            out = paged_decode_attention(
                window.stored_key(q[:, 0], W), stores["ring_k"],
                stores["ring_v"], index, ring_table,
                active.astype(jnp.int32), seen.astype(jnp.int32),
                layer["sink"], scale)[:, None]
        return window.attn_output(layer, h, out, cfg), stores

    x, pool, counters = _walk(params, x, valid, pool, cfg, full_layer,
                              window_layer, read, trace.SCOPE_DECODE_MLP)
    x = llama.final_norm(params, x, cfg)
    return llama.lm_head(params, x, cfg)[:, -1, :], pool, kv_mask, counters


@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("pool", "kv_mask"))
def paged_decode_step(params: Params, token: jnp.ndarray, pool: dict,
                      page_table: jnp.ndarray, pos: jnp.ndarray,
                      write_pos: jnp.ndarray, kv_mask: jnp.ndarray,
                      active: jnp.ndarray, keys: jnp.ndarray,
                      temperature: jnp.ndarray, top_k: jnp.ndarray,
                      top_p: jnp.ndarray, cfg: WindowMoEConfig) -> dict:
    """One decode tick over every slot row, the arguments of the dense
    `paged_decode_step`. A full layer writes this token's keys and values
    into (layer, w_page, w_off) and attends each slot's live pages where
    they lie in the pool; a window layer writes them at `write_pos % R` of
    the row's ring and attends the ring under the layer's sinks; both
    through `ops/paged_attention.py`. Rows that are not `active` leave the
    stores as they were (page writes go to the garbage page, the ring place
    is rewritten with what it held), are routed to no expert and count for
    nothing. Returns the dense tick's outputs plus "counters" (int32[8],
    `COUNTERS`)."""
    logits, pool, kv_mask, counters = tick_logits(
        params, token, pool, page_table, pos, write_pos, kv_mask, active, cfg)
    with jax.named_scope(trace.SCOPE_SAMPLE):
        split = jax.vmap(jax.random.split)(keys)        # [b, 2, 2]
        nxt = dense_decode.sample_rowwise(logits, temperature, top_k, top_p,
                                          split[:, 1])
    return {"token": nxt, "pool": pool, "kv_mask": kv_mask,
            "keys": split[:, 0], "counters": counters}


@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("pool", "kv_mask"))
def paged_prefill_chunk(params: Params, input_ids: jnp.ndarray,
                        attention_mask: jnp.ndarray, positions: jnp.ndarray,
                        pool: dict, page_table_row: jnp.ndarray,
                        slot: jnp.ndarray, kv_mask: jnp.ndarray,
                        write_start: jnp.ndarray,
                        cfg: WindowMoEConfig) -> dict:
    """One bounded prefill chunk of slot `slot`, the arguments of the dense
    `paged_prefill_chunk`: chunk tokens [1, C] at logical places
    [write_start, write_start + C), C a multiple of the page. A full layer
    writes the chunk's keys and values into its pages, gathers the slot's
    row of pages and every query attends all it can see (the kernel's key
    axis ends at the chunk's own end); a window layer reads the ring for the
    places before the chunk, attends the band, and leaves the chunk's last
    places in the ring. A chunk of nothing but left pads changes no visible
    state, so the engine starts a row behind them. Returns the LAST
    position's float32 logits, the stores, the mask and "counters"."""
    _, C = input_ids.shape
    G, dv = cfg.full_kv_heads, cfg.v_head_dim
    page = pool["v"].shape[2] // G
    L = page_table_row.shape[0] * page
    W = window.key_store_width(cfg)
    R = cfg.ring_len
    mask = attention_mask.astype(jnp.int32)
    valid = mask > 0
    kv_mask = jax.lax.dynamic_update_slice(kv_mask, mask, (slot, write_start))
    row_valid = jax.lax.dynamic_slice(kv_mask, (slot, 0), (1, L)) > 0
    chunk_pages = page_table_row[write_start // page + jnp.arange(C // page)]
    places = (write_start + jnp.arange(C, dtype=jnp.int32))[None, :]
    before = window_context(C, cfg.sliding_window)
    earlier = write_start - before + jnp.arange(before)         # may be < 0
    earlier_valid = ((earlier >= 0)
                     & row_valid[0, jnp.clip(earlier, 0, None)])[None, :]
    kept = min(C, R)
    ring_at = (write_start + C - kept + jnp.arange(kept)) % R

    x = llama.embed(params, input_ids, cfg)

    def full_layer(layer, h, stores, index):
        q, k, v = window.project(layer, h, positions, cfg.kind_of(FULL), cfg)
        with jax.named_scope(trace.SCOPE_KV_WRITE):
            stores = {**stores,
                      "k": stores["k"].at[index, chunk_pages].set(
                          window.stored_key(k[0], W).reshape(
                              C // page, page * G, W)),
                      "v": stores["v"].at[index, chunk_pages].set(
                          v[0].reshape(C // page, page * G, dv))}
        with jax.named_scope(trace.SCOPE_KV_GATHER):
            keys = stores["k"][index, page_table_row].reshape(
                1, L, G, W)[..., :cfg.head_dim]
            values = stores["v"][index, page_table_row].reshape(1, L, G, dv)
        return window.full_span(layer, h, q, keys, values, row_valid,
                                write_start, cfg), stores

    def window_layer(layer, h, stores, index):
        q, k, v = window.project(layer, h, positions, cfg.kind_of(WINDOW), cfg)
        with jax.named_scope(trace.RING_GATHER):
            at = (index, slot, earlier % R)
            before_k = stores["ring_k"][at][None, ..., :cfg.head_dim]
            before_v = stores["ring_v"][at][None]
        h = window.window_span(layer, h, q, k, v, before_k, before_v,
                               earlier_valid, valid, cfg)
        with jax.named_scope(trace.RING_WRITE):
            at = (index, slot, ring_at)
            stores = {**stores,
                      "ring_k": stores["ring_k"].at[at].set(
                          window.stored_key(k[0, C - kept:], W)),
                      "ring_v": stores["ring_v"].at[at].set(v[0, C - kept:])}
        return h, stores

    x, pool, counters = _walk(
        params, x, valid, pool, cfg, full_layer, window_layer,
        window.span_counts(row_valid, places, valid, cfg), trace.SCOPE_MLP)
    x = llama.final_norm(params, x[:, -1:, :], cfg)
    logits = llama.lm_head(params, x, cfg)
    return {"logits": logits[:, -1], "pool": pool, "kv_mask": kv_mask,
            "counters": counters}
