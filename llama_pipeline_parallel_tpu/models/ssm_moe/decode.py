"""Serving programs of the state-space / expert block: `prefill_prompt`,
`paged_prefill_chunk`, `paged_decode_step` and `write_pages`, with the signatures of their
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

A page's layout follows the head's width. Heads of a whole lane tile (128)
lie a head a row, the dense decoder's `[.., page, kv_h, hd]`. Narrower heads
(`cfg.kv_pack` > 1: two heads of 64) lie side by side in ONE 128-lane row,
and a page is stored as the matrix the tick's kernel reads, `[softmax
layers, pages + 1, page * kv_h / kv_pack, kv_pack * hd]` (row r is token r //
(kv_h / kv_pack), heads kv_pack * (r % (kv_h / kv_pack)) and up): behind a
KV-head axis XLA:TPU tiles the two last axes, pads a 64-wide row to 128
lanes and copies the pool in front of the kernel; a matrix of whole tiles
is read and written where it lies by every program. The kernel
(`ops/paged_attention.py`) is the one every family runs: it sees `kv_h /
kv_pack` heads of `kv_pack * hd`, a query head's numbers stand in its own KV
head's part of the row with zeros beside them (`model.packed_queries`), and
of its output the same part is kept.

A prefill CHUNK carries a slot's row forward (`paged_prefill_chunk`): a
Mamba-2 layer reads `state[m, slot]` and `conv[m, slot]`, scans the chunk's
places from them and writes both back; a softmax layer writes the chunk's
pages and every query attends what it can see of the slot's row. A row
whose mask holds no token before the chunk starts from ZEROS whatever the
store's row held: slots are reused, and the whole-bucket path's moment of
overwriting the row (`write_pages`) never comes on the chunk path. So the
leading chunks of a bucket that hold nothing but left pads need not run
(`serve/engine.py` starts a row behind them).

The layers are unrolled in the order `cfg.pattern` gives (it has no period
in general), each reading and writing its own index of its kind's store;
every weight is the buffer it is stored in (models/ssm_moe/model.py).

What a model with recurrent layers cannot do yet is refused by name where the
engine is built (`models/family.py`): a prefix cache and the span prefill
(the slot's row at the divergence point is not kept), int8 pages.
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
from llama_pipeline_parallel_tpu.ops.gqa_prefill_attention import (
    full_prefill_attention,
)
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
    shape = (cfg.kv_cache_layers, num_pages + 1) + _page_shape(cfg, page_size)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def _page_shape(cfg: SsmMoEConfig, page_size: int) -> tuple:
    """One page of one layer: a head a row, or (narrow heads) the matrix of
    `kv_pack` heads a row (the module's docstring)."""
    if cfg.kv_pack == 1:
        return (page_size, cfg.kv_heads, cfg.head_dim)
    return (page_size * cfg.kv_heads // cfg.kv_pack,
            cfg.kv_pack * cfg.head_dim)


def _page_size(pool: dict, cfg: SsmMoEConfig) -> int:
    rows = pool["k"].shape[2]
    return rows if cfg.kv_pack == 1 else rows * cfg.kv_pack // cfg.kv_heads


def _by_head(pages: jnp.ndarray, cfg: SsmMoEConfig, page: int) -> jnp.ndarray:
    """The pool as the tick's kernel takes it, `[L, pages + 1, page, heads a
    row, width]`: what a head-a-row pool is, and a view of packed matrices
    (the kernel reads them as the matrices they are: no copy)."""
    if cfg.kv_pack == 1:
        return pages
    return pages.reshape(*pages.shape[:2], page, cfg.kv_heads // cfg.kv_pack,
                         -1)


def init_recurrent_store(cfg: SsmMoEConfig, max_slots: int) -> dict:
    """Zeroed recurrent store, one row a slot and Mamba-2 layer."""
    layers = cfg.recurrent_layers
    return {
        "state": jnp.zeros((layers, max_slots, cfg.ssm_heads, cfg.ssm_head_dim,
                            cfg.ssm_state), jnp.float32),
        "conv": jnp.zeros((layers, max_slots, cfg.ssm_conv - 1,
                           cfg.ssm_conv_width), cfg.dtype)}


def _walk(params: Params, x: jnp.ndarray, valid: jnp.ndarray, stores: dict,
          cfg: SsmMoEConfig, softmax_layer, ssm_layer, own: dict,
          mlp_scope: str = trace.SCOPE_MLP):
    """Run every layer in the pattern's order. `softmax_layer(layer, h,
    stores, index) -> (h, stores)` and `ssm_layer(layer, h, stores, index)
    -> (h, stores)` are the caller's mixers, `index` the layer's place among
    those of its kind (its row of the kind's store); a dense half is the
    dense decoder's `mlp_block` under `mlp_scope`. `own`: what the caller's
    program counts in ONE layer of a kind, by counter name (`ssm_positions`
    and the carries a Mamba-2 layer, `kv_entries_read` a softmax layer;
    absent: 0). Returns the hidden state, the stores and the counters summed
    over layers (int32[11], `COUNTERS`: the expert layers' six, the rows the
    Mamba-2 layers advanced, then `own`'s)."""
    experts = jnp.zeros((len(hybrid.COUNTERS),), jnp.int32)
    for i, (kind, layer) in enumerate(zip(cfg.pattern, params["layers"])):
        index = cfg.kind_index(i)
        if kind == "M":
            x, stores = ssm_layer(layer, x, stores, index)
        elif kind == "*":
            x, stores = softmax_layer(layer, x, stores, index)
        elif kind == "-":
            x = llama.mlp_block(layer, x, cfg, scope=mlp_scope)
        else:
            x, counted = ssm.latent_moe_block(layer, x, valid, cfg)
            experts = experts + counted
    ssm_rows = jnp.sum(valid).astype(jnp.int32) * cfg.recurrent_layers
    depth = {"kv_entries_read": cfg.kv_cache_layers}
    mine = [jnp.asarray(own.get(name, 0), jnp.int32)
            * depth.get(name, cfg.recurrent_layers)
            for name in COUNTERS[len(hybrid.COUNTERS) + 1:]]
    return x, stores, jnp.concatenate([experts, ssm_rows[None],
                                       jnp.stack(mine)])


def _own_layout(a: jnp.ndarray) -> jnp.ndarray:
    """`a` [1, ...] behind a flattening the compiler may not look through. A
    chunk's convolution runs with the sequence along the lanes, and XLA:TPU
    hands that layout on through a slice or an update of a slot's `conv`
    row to the whole `conv` store: three places padded to 128 lanes, a copy
    of the store 1.8 GB large in front of the chunk and another behind it.
    Flat, the row has one layout; the store keeps its own."""
    flat = jax.lax.optimization_barrier(a.reshape(a.shape[0], -1))
    return flat.reshape(a.shape)


def _entries_seen(row_valid: jnp.ndarray, q_place: jnp.ndarray,
                  q_valid: jnp.ndarray) -> jnp.ndarray:
    """Entries the queries of a span read in ONE softmax layer: a query at
    place p reads the row's valid places up to p. row_valid: [b, S] bool;
    q_place: [b, T] int32; q_valid: [b, T] bool."""
    upto = jnp.cumsum(row_valid.astype(jnp.int32), axis=1)
    return jnp.sum(jnp.where(q_valid,
                             jnp.take_along_axis(upto, q_place, axis=1), 0))


@partial(jax.jit, static_argnames=("cfg", "max_len"))
def prefill_prompt(params: Params, input_ids: jnp.ndarray,
                   attention_mask: jnp.ndarray, cfg: SsmMoEConfig,
                   max_len: int) -> dict:
    """Prefill LEFT-padded prompts ([b, P]) into fresh rows of both stores.
    Returns what the dense `prefill_prompt` returns ({"logits", "cache",
    "kv_mask", "next_pos"}), the cache holding `k` / `v` [softmax layers, b,
    max_len, kv_h, hd] with the prompt at [0, P) and the rows' `state` /
    `conv` after the last position, plus "counters" (`COUNTERS`)."""
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
    x = ssm.embed(params, input_ids, cfg)

    def softmax_layer(layer, h, stores, index):
        hidden, q, k, v = hybrid.attn_project(layer, h, cfg)
        with jax.named_scope(trace.SCOPE_KV_WRITE):
            stores = {**stores,
                      "k": stores["k"].at[index, :, :prompt_len].set(k),
                      "v": stores["v"].at[index, :, :prompt_len].set(v)}
        with jax.named_scope(trace.SCOPE_ATTN_CORE):
            # the chunk's kernel over the bucket itself: blocked over keys,
            # it never forms a bucket's scores (at 2048 places and 32 heads
            # 0.5 GB of float32 and as much again in exponentials)
            out = full_prefill_attention(ssm.scaled_queries(q, cfg), k, v,
                                         mask, jnp.int32(0))
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

    places = jnp.broadcast_to(jnp.arange(prompt_len, dtype=jnp.int32),
                              (b, prompt_len))
    x, stores, counters = _walk(
        params, x, valid, stores, cfg, softmax_layer, ssm_layer,
        {"ssm_positions": jnp.sum(valid),
         "kv_entries_read": _entries_seen(valid, places, valid)})
    x = llama.final_norm(params, x[:, -1:, :], cfg)
    logits = ssm.logits(params, x, cfg)
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
    page = _page_size(pool, cfg)
    garbage = pool["k"].shape[1] - 1
    kv_mask = kv_mask.at[jnp.arange(b), write_pos].max(
        active.astype(kv_mask.dtype))
    w_page = jnp.take_along_axis(page_table, (write_pos // page)[:, None],
                                 axis=1)[:, 0]
    w_page = jnp.where(active > 0, w_page, garbage)
    w_off = write_pos % page
    valid = (active > 0)[:, None]
    live_pages = jnp.where(active > 0, write_pos // page + 1, 0)
    # a decoding row reads the valid places up to its own in a softmax layer
    places = jnp.arange(kv_mask.shape[1], dtype=jnp.int32)[None, :]
    visible = (kv_mask > 0) & (places <= write_pos[:, None]) & valid
    packed_rows = cfg.kv_heads // cfg.kv_pack

    x = ssm.embed(params, token[:, None], cfg)

    def write_token(pages, index, rows):
        if cfg.kv_pack == 1:
            return dense_decode._write_tokens(pages, None, index, rows, w_page,
                                              w_off, None)[0]
        # a token's packed rows at [w_off, w_off + 1) x kv_h / kv_pack of the
        # page's matrix
        at = (index, w_page[:, None],
              w_off[:, None] * packed_rows + jnp.arange(packed_rows)[None, :])
        return pages.at[at].set(ssm.packed_kv(rows, cfg))

    def softmax_layer(layer, h, stores, index):
        hidden, q, k, v = hybrid.attn_project(layer, h, cfg)
        with jax.named_scope(trace.SCOPE_KV_WRITE):
            stores = dict(stores)
            for name, rows in (("k", k), ("v", v)):
                stores[name] = write_token(stores[name], index, rows[:, 0])
        with jax.named_scope(trace.SCOPE_DECODE_ATTN):
            out = ssm.unpacked_heads(paged_decode_attention(
                ssm.packed_queries(q[:, 0], cfg),
                _by_head(stores["k"], cfg, page),
                _by_head(stores["v"], cfg, page), index, page_table,
                live_pages, kv_mask, None, cfg.attn_scale), cfg)[:, None]
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
                              ssm_layer, {"kv_entries_read": jnp.sum(visible)},
                              trace.SCOPE_DECODE_MLP)
    x = llama.final_norm(params, x, cfg)
    return ssm.logits(params, x, cfg)[:, -1, :], pool, kv_mask, counters


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
    expert. Returns the dense tick's outputs plus "counters" (int32[11],
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


@partial(jax.jit, donate_argnames=("pool", "kv_mask"))
def write_packed_pages(pool: dict, kv_mask: jnp.ndarray, slot: jnp.ndarray,
                       page_rows: jnp.ndarray, row_cache: dict,
                       row_kv_mask: jnp.ndarray) -> tuple[dict, jnp.ndarray]:
    """`write_pages` for pages stored as matrices of packed heads: a page's
    tokens in the shape the pool keeps them (a reshape: a token's heads are
    neighbours), the recurrent rows whole into row `slot`, the mask row
    rewritten whole."""
    out = dict(pool)
    n_pages = page_rows.shape[0]
    with jax.named_scope(trace.SCOPE_KV_WRITE):
        for name in ("k", "v"):
            blocks = row_cache[name].reshape(
                row_cache[name].shape[0], n_pages, *pool[name].shape[2:])
            out[name] = out[name].at[:, page_rows].set(blocks)
    with jax.named_scope(trace.STATE_WRITE):
        for name in ("state", "conv"):
            start = (0, slot) + (0,) * (pool[name].ndim - 2)
            out[name] = jax.lax.dynamic_update_slice(
                out[name], row_cache[name].astype(out[name].dtype), start)
    row = jnp.pad(row_kv_mask.astype(kv_mask.dtype),
                  ((0, 0), (0, kv_mask.shape[1] - row_kv_mask.shape[1])))
    return out, jax.lax.dynamic_update_slice(kv_mask, row, (slot, 0))


@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("pool", "kv_mask"))
def paged_prefill_chunk(params: Params, input_ids: jnp.ndarray,
                        attention_mask: jnp.ndarray, positions: jnp.ndarray,
                        pool: dict, page_table_row: jnp.ndarray,
                        slot: jnp.ndarray, kv_mask: jnp.ndarray,
                        write_start: jnp.ndarray, cfg: SsmMoEConfig) -> dict:
    """One bounded prefill chunk of slot `slot`, the arguments of the dense
    `paged_prefill_chunk` (`positions` is unused: no layer is rotary): chunk
    tokens [1, C] at logical places [write_start, write_start + C), C a
    multiple of the page. A Mamba-2 layer takes the slot's row of `state`
    and `conv` (zeros where the row's mask holds no token before the chunk:
    the row is the last occupant's), scans the chunk from them (pads pass
    both unchanged: dt = 0, a zero convolution input) and writes both back;
    a softmax layer writes the chunk's keys and values into its pages,
    gathers the slot's row of pages and every query attends the valid places
    up to its own (`ops/gqa_prefill_attention.py`, blocked over keys as far
    as the chunk's own end). A chunk of nothing but left pads leaves zeros
    behind a mask row of zeros, which is what the next chunk starts from
    anyway, so the engine starts a row behind them. Returns the LAST
    position's float32 logits, the stores, the mask and "counters"."""
    del positions
    _, C = input_ids.shape
    page = _page_size(pool, cfg)
    L = page_table_row.shape[0] * page
    mask = attention_mask.astype(jnp.int32)
    valid = mask > 0
    before = jax.lax.dynamic_slice(kv_mask, (slot, 0), (1, L)) > 0
    row_places = jnp.arange(L, dtype=jnp.int32)[None, :]
    # the row holds a token before this chunk: its state is this request's
    carried = jnp.any(before & (row_places < write_start))
    kv_mask = jax.lax.dynamic_update_slice(kv_mask, mask, (slot, write_start))
    row_valid = jax.lax.dynamic_slice(kv_mask, (slot, 0), (1, L)) > 0
    chunk_pages = page_table_row[write_start // page + jnp.arange(C // page)]
    places = (write_start + jnp.arange(C, dtype=jnp.int32))[None, :]

    x = ssm.embed(params, input_ids, cfg)

    def softmax_layer(layer, h, stores, index):
        hidden, q, k, v = hybrid.attn_project(layer, h, cfg)
        with jax.named_scope(trace.SCOPE_KV_WRITE):
            whole_pages = lambda a: a[0].reshape(C // page,
                                                 *stores["k"].shape[2:])
            stores = {**stores,
                      "k": stores["k"].at[index, chunk_pages].set(
                          whole_pages(k)),
                      "v": stores["v"].at[index, chunk_pages].set(
                          whole_pages(v))}
        with jax.named_scope(trace.SCOPE_KV_GATHER):
            row = lambda a: a[index, page_table_row].reshape(
                1, L, cfg.kv_heads, cfg.head_dim)
            keys, values = row(stores["k"]), row(stores["v"])
        with jax.named_scope(trace.SCOPE_ATTN_CORE):
            out = full_prefill_attention(ssm.scaled_queries(q, cfg), keys,
                                         values, row_valid, write_start)
        return hybrid.attn_output(layer, h, hidden, out, cfg), stores

    def ssm_layer(layer, h, stores, index):
        with jax.named_scope(trace.STATE_CARRY_IN):
            # ONE slice of the store, the slot's row of this layer (a layer
            # sliced out first is a copy of every slot's row: 100 MB a layer)
            mine = lambda a: jnp.where(carried, jax.lax.dynamic_slice(
                a, (index, slot) + (0,) * (a.ndim - 2),
                (1, 1) + a.shape[2:])[0], 0)
            state = mine(stores["state"])
            conv = _own_layout(mine(stores["conv"]))
        pr = ssm.ssm_project(layer, h, valid, conv, cfg)
        y, state = ssm.ssm_chunked(
            pr["x"], pr["dt"], -jnp.exp(layer["A_log"]), pr["B"], pr["C"],
            state, cfg.ssm_chunk)
        with jax.named_scope(trace.STATE_CARRY_OUT):
            put = lambda a, new: jax.lax.dynamic_update_slice(
                a, new[None].astype(a.dtype),
                (index, slot) + (0,) * (a.ndim - 2))
            stores = {**stores, "state": put(stores["state"], state),
                      "conv": put(stores["conv"], _own_layout(pr["conv"]))}
        return ssm.ssm_output(layer, h, y, pr["x"], pr["z"], cfg), stores

    row_bytes = sum(
        pool[name][0, 0].size * pool[name].dtype.itemsize
        for name in ("state", "conv")) if cfg.recurrent_layers else 0
    carries = carried.astype(jnp.int32)
    x, pool, counters = _walk(
        params, x, valid, pool, cfg, softmax_layer, ssm_layer,
        {"ssm_positions": jnp.sum(valid),
         "kv_entries_read": _entries_seen(row_valid, places, valid),
         "state_carries": carries, "state_bytes_carried": carries * row_bytes})
    x = llama.final_norm(params, x[:, -1:, :], cfg)
    logits = ssm.logits(params, x, cfg)
    return {"logits": logits[:, -1], "pool": pool, "kv_mask": kv_mask,
            "counters": counters}
