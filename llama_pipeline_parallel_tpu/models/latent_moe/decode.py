"""Serving programs of the latent-attention block: `prefill_prompt`,
`paged_prefill_chunk`, `paged_decode_step` and `write_pages`, with the
signatures of their `models/llama/decode.py` namesakes, so `serve/engine.py`
and `serve/pages.py` drive this family through `models/family.py` without
naming it.

What the configuration has decides the stores and each full layer's
program: under an indexer (`cfg.has_indexer`) a full layer keeps index pages
and reads the positions it selects; without one it reads every position the
query can see; the ring exists where the model has sliding layers.

Up to three stores ride one donated tree (`pool`):

- `latent` [full layers, pages + 1, page, 640]: what a full layer keeps of a
  token, the normed latent and the shared roped key (`model.project`'s
  `entry`, 576 numbers, stored padded to whole tiles: config.py
  `store_multiple`), in the pages the slot's table names;
- `index` [full layers, pages + 1, page, 128] (under an indexer only): the
  indexer's key of the same token in the same page and place. It exists
  only to choose which `index_topk` of the latents a query reads;
- `ring` [sliding layers, slots, R, 1152] (a model with sliding layers
  only): a sliding layer's entries (1088 numbers, padded likewise) of the
  last R >= window positions of each slot, logical position p at p % R.
  Older positions are overwritten: the layer never sees them again.

Layer 0 (full, dense feed-forward) runs before a scan over PERIODS whose body
unrolls `cfg.period`: a full layer and its sliding layers (a model of one
kind of layer scans over its layers). The stores ride its carry and are
touched only by indexed reads and writes (never the scan's `xs` / `ys`:
models/llama/decode.py "How the pool is walked"). A period's weights ride
`xs`, all but the routed experts' `gate` / `up` / `down`: the body closes
over those leaves whole, `[P, held, d, f]`, and `moe_block` takes the stack
and the period's place in it, because a slice of them in front of the
grouped product is a copy of a layer's experts (models/hybrid_moe/model.py).

Positions. A slot's LOGICAL row is its left-padded prompt bucket followed by
what it decoded, as the mask row `kv_mask[slot]` describes it; pages, ring
places, the causal order and the window all count logical places, and a pad
is never visible: not to the indexer, not to the window, and it counts for
nothing in `index_visible`. Rope takes the token's own position (pads not
counted), as the engine passes it.

Under an indexer a full layer's read is by TOKEN, not by page: index scores
against every index key of the slot's table (one gather of its pages), an
exact top-k (`lax.top_k`; a row of at most `index_topk` places selects every
visible one without a sort: the same set), then a gather of the chosen
latents: through the page table in the tick, from the slot's gathered row in
a prefill, whose queries run in blocks (`model.full_span`).

Without one the read is DENSE. The tick computes the absorbed form in one
kernel that walks the slot's page table over the pool's own live pages
(`ops/paged_latent_attention.py`: no gathered row, no `top_k`); a prefill or
a chunk computes the projected form, the earlier entries expanded to keys
and values once for all its queries, in a kernel blocked over keys
(`model.dense_span`, `ops/latent_prefill_attention.py`: no `[heads, T, S]`
scores).

What this family cannot do yet is refused by name where the engine is built
(`models/family.py`): a prefix cache and the span prefill, int8 pages.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from llama_pipeline_parallel_tpu.models.hybrid_moe import model as hybrid
from llama_pipeline_parallel_tpu.models.latent_moe import draft
from llama_pipeline_parallel_tpu.models.latent_moe import model as latent
from llama_pipeline_parallel_tpu.models.latent_moe.config import LatentMoEConfig
from llama_pipeline_parallel_tpu.models.llama import decode as dense_decode
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.ops.paged_latent_attention import (
    paged_latent_decode_attention,
)
from llama_pipeline_parallel_tpu.utils import trace

Params = dict
KEY_REACHES = 16          # branches of a chunk's full layer, by its reach


def counters(cfg: LatentMoEConfig) -> tuple:
    """Names of the int32 counters every program returns, in order: the
    expert layers' and the full layers' own. Under an indexer the positions
    its queries could see and the ones they selected; without one the
    positions they could see and therefore read (`latent_visible`). Summed
    over rows and full layers; pads and rows that are not decoding count for
    nothing. A model that drafts adds what its verify tick counts
    (`draft.COUNTERS`), its module's layer counted with the trunk's."""
    return hybrid.COUNTERS + (("index_visible", "index_selected")
                              if cfg.has_indexer else ("latent_visible",)) + (
                                  draft.COUNTERS if cfg.drafts else ())


def _page_leaves(cfg: LatentMoEConfig) -> dict:
    """{leaf with a page axis: numbers it keeps of a token}."""
    leaves = {"latent": cfg.latent_store_width}
    if cfg.has_indexer:
        leaves["index"] = cfg.index_head_dim
    return leaves


def init_page_pool(cfg: LatentMoEConfig, num_pages: int, page_size: int,
                   quant: str = "fp") -> dict:
    """Zeroed pages of the full layers (latents, and index keys under an
    indexer; behind the trunk's, the multi-token-prediction module's layer
    where the model has one), with the garbage page
    (`models/llama/decode.init_page_pool`)."""
    if quant != "fp":
        raise ValueError(f"the latent block keeps fp pages only, got {quant!r}")
    lead = (cfg.page_depth, num_pages + 1, page_size)
    return {name: jnp.zeros(lead + (width,), cfg.dtype)
            for name, width in _page_leaves(cfg).items()}


def init_recurrent_store(cfg: LatentMoEConfig, max_slots: int) -> dict:
    """The per-slot store: a zeroed ring a slot and sliding layer for a
    model with sliding layers; the draft for one that drafts
    (`draft.init_store`); nothing for any other."""
    if cfg.drafts:
        return draft.init_store(cfg, max_slots)
    if not cfg.window_layers:
        return {}
    return {"ring": jnp.zeros((cfg.window_layers, max_slots, cfg.ring_len,
                               cfg.ring_store_width), cfg.dtype)}


@partial(jax.jit, static_argnames=("cfg", "max_len"))
def prefill_prompt(params: Params, input_ids: jnp.ndarray,
                   attention_mask: jnp.ndarray, cfg: LatentMoEConfig,
                   max_len: int) -> dict:
    """Prefill LEFT-padded prompts ([b, P]) into fresh rows of the stores.
    Returns what the dense `prefill_prompt` returns ({"logits", "cache",
    "kv_mask", "next_pos"}), the cache holding `latent` (and `index`) [full
    layers, b, max_len, *] with the prompt at [0, P) and, for a model with
    sliding layers, `ring` [sliding layers, b, R, 1152] with the prompt's
    last positions at p % R, plus "counters" (`counters(cfg)`) and
    "selection"."""
    b, P = input_ids.shape
    if P > max_len:
        raise ValueError(f"prompt bucket {P} exceeds cache max_len {max_len}")
    mask = attention_mask.astype(jnp.int32)
    valid = mask > 0
    positions = jnp.clip(jnp.cumsum(mask, axis=1) - 1, 0, None).astype(jnp.int32)
    places = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (b, P))
    full, win = cfg.kind(False), cfg.kind(True)
    lead = (cfg.page_depth, b, max_len)
    stores = {name: jnp.zeros(lead + (width,), cfg.dtype)
              for name, width in _page_leaves(cfg).items()}
    stores.update(init_recurrent_store(cfg, b))
    prev = cfg.sliding_window_size - 1
    kept = latent.kept_positions(P, cfg)
    ring_at = (P - kept + jnp.arange(kept)) % cfg.ring_len
    x = llama.embed(params, input_ids, cfg)

    def full_layer(layer, h, stores, depth):
        pr = latent.project(layer, h, positions, full, cfg)
        new = {"latent": latent.stored(pr["entry"], cfg.latent_store_width)}
        if cfg.has_indexer:
            pr["index"] = latent.index_project(layer, pr["hidden"], pr["cq"],
                                               positions, cfg)
            new["index"] = pr["index"][1]
        with jax.named_scope(trace.LATENT_WRITE):
            stores = {**stores, **{
                name: stores[name].at[depth, :, :P].set(rows)
                for name, rows in new.items()}}
        if not cfg.has_indexer:
            h = latent.dense_span(layer, h, jnp.int32(0), pr, pr["entry"],
                                  valid, cfg)
            return h, stores, latent.visible_count(valid, places, valid), ()
        h, counted, sel = latent.full_span(
            layer, h, valid, places, pr, pr["entry"], pr["index"][1], valid,
            cfg)
        return h, stores, counted, sel

    def window_layer(layer, h, stores, index):
        pr = latent.project(layer, h, positions, win, cfg)
        h = latent.window_span(
            layer, h, pr, jnp.zeros((b, prev, cfg.ring_store_width), cfg.dtype),
            jnp.zeros((b, prev), bool), valid, cfg)
        with jax.named_scope(trace.RING_WRITE):
            ring = stores["ring"].at[
                index, jnp.arange(b)[:, None], ring_at[None, :]].set(
                    latent.stored(pr["entry"][:, P - kept:],
                                  cfg.ring_store_width))
        return h, {**stores, "ring": ring}

    x, stores, counters, selection = latent.walk(
        params, x, valid, stores, cfg, full_layer, window_layer,
        trace.SCOPE_MLP)
    if cfg.drafts:
        # the module's entries of the prompt; its last position waits for
        # the first token (the row's first tick: draft.py)
        pr, known = draft.prompt_module(
            params, x, input_ids, jnp.full((b,), draft.NO_DRAFT, jnp.int32),
            valid, positions, cfg)
        with jax.named_scope(trace.LATENT_WRITE):
            at = (cfg.full_layers, slice(None), slice(0, P))
            stores = {
                **stores,
                "latent": stores["latent"].at[at].set(
                    latent.stored(pr["entry"], cfg.latent_store_width)),
                "index": stores["index"].at[at].set(pr["index"][1]),
                "mtp_draft": jnp.full((b,), draft.NO_DRAFT, jnp.int32)}
        counters = draft.prefill_counters(counters, known)
        hidden = {"hidden": x[:, -1]}
    x = llama.final_norm(params, x[:, -1:, :], cfg)
    logits = llama.lm_head(params, x, cfg)
    return {"logits": logits[:, -1], "cache": stores,
            "kv_mask": jnp.pad(mask, ((0, 0), (0, max_len - P))),
            "next_pos": jnp.sum(mask, axis=1).astype(jnp.int32),
            "counters": counters, "selection": selection,
            **(hidden if cfg.drafts else {})}


@partial(jax.jit, donate_argnames=("pool", "kv_mask"))
def write_pages(pool: dict, kv_mask: jnp.ndarray, slot: jnp.ndarray,
                page_rows: jnp.ndarray, row_cache: dict,
                row_kv_mask: jnp.ndarray) -> tuple[dict, jnp.ndarray]:
    """Splice one prefilled request (`prefill_prompt` at b == 1, max_len ==
    the bucket) into the stores: its latents (and index keys) into the
    slot's pages, its ring, where the model has one, whole into row `slot`
    (whatever the last occupant left there is gone), and the mask row
    rewritten whole."""
    out = dict(pool)
    n_pages = page_rows.shape[0]
    with jax.named_scope(trace.LATENT_WRITE):
        for name in row_cache:
            if name == "ring" or name in draft.STORE:
                continue
            depth, _, bucket, width = row_cache[name].shape
            blocks = row_cache[name].reshape(depth, n_pages, bucket // n_pages,
                                             width)
            out[name] = out[name].at[:, page_rows].set(blocks)
    if "ring" in row_cache:
        with jax.named_scope(trace.RING_WRITE):
            out["ring"] = jax.lax.dynamic_update_slice(
                out["ring"], row_cache["ring"].astype(out["ring"].dtype),
                (0, slot, 0, 0))
    for name in draft.STORE:
        if name in row_cache:       # a drafting model's row of the slot
            out[name] = jax.lax.dynamic_update_slice(
                out[name], row_cache[name].astype(out[name].dtype),
                (slot,) + (0,) * (out[name].ndim - 1))
    row = jnp.pad(row_kv_mask.astype(kv_mask.dtype),
                  ((0, 0), (0, kv_mask.shape[1] - row_kv_mask.shape[1])))
    return out, jax.lax.dynamic_update_slice(kv_mask, row, (slot, 0))


def tick_logits(params: Params, token: jnp.ndarray, pool: dict,
                page_table: jnp.ndarray, pos: jnp.ndarray,
                write_pos: jnp.ndarray, kv_mask: jnp.ndarray,
                active: jnp.ndarray, cfg: LatentMoEConfig):
    """The decode tick up to its logits: (float32 logits [b, V], the stores,
    kv_mask, counters, selection). `paged_decode_step` samples from these;
    the tests compare them with the reference's."""
    b = token.shape[0]
    _, n_pool, page, _ = pool["latent"].shape
    garbage = n_pool - 1
    L = page_table.shape[1] * page
    kv_mask = kv_mask.at[jnp.arange(b), write_pos].max(
        active.astype(kv_mask.dtype))
    w_page = jnp.take_along_axis(page_table, (write_pos // page)[:, None],
                                 axis=1)[:, 0]
    w_page = jnp.where(active > 0, w_page, garbage)
    w_off = write_pos % page
    rows = active > 0
    valid = rows[:, None]
    positions = pos[:, None]
    places = jnp.arange(L, dtype=jnp.int32)[None, :]
    before = (kv_mask > 0) & (places < write_pos[:, None])
    own = places == write_pos[:, None]
    full, win = cfg.kind(False), cfg.kind(True)
    ring_at = write_pos % cfg.ring_len
    by_row = jnp.arange(b)

    live_pages = jnp.where(rows, write_pos // page + 1, 0)
    visible = jnp.sum((before | own) & valid)[None].astype(jnp.int32)

    x = llama.embed(params, token[:, None], cfg)

    def write(stores, depth, new: dict):
        with jax.named_scope(trace.LATENT_WRITE):
            stores = dict(stores)
            for name, rows_new in new.items():
                stores[name], _ = dense_decode._write_tokens(
                    stores[name], None, depth, rows_new[:, 0], w_page, w_off,
                    None)
        return stores

    def dense_layer(layer, h, stores, depth):
        pr = latent.project(layer, h, positions, full, cfg)
        stores = write(stores, depth, {
            "latent": latent.stored(pr["entry"], cfg.latent_store_width)})
        q_abs = latent.stored(
            latent.absorb(layer, pr["q_nope"], pr["q_rope"], cfg),
            cfg.latent_store_width)
        with jax.named_scope(trace.LATENT_READ):
            o = paged_latent_decode_attention(
                q_abs[:, 0], stores["latent"], depth, page_table, live_pages,
                kv_mask, full.softmax_scale, full.rkv)
        h = latent.output(layer, h, pr["hidden"],
                          latent.unabsorb(layer, o[:, None], cfg), cfg)
        return h, stores, visible, ()

    def indexed_layer(layer, h, stores, depth):
        pr = latent.project(layer, h, positions, full, cfg)
        qi, ki, weights = latent.index_project(layer, pr["hidden"], pr["cq"],
                                               positions, cfg)
        stores = write(stores, depth, {
            "latent": latent.stored(pr["entry"], cfg.latent_store_width),
            "index": ki})
        with jax.named_scope(trace.LATENT_GATHER):
            keys = stores["index"][depth, page_table].reshape(b, L, -1)
        scores = latent.index_scores(qi, weights, keys)[:, 0]       # [b, L]
        chosen, ok = latent.select(scores, before, own, cfg.index_topk)
        with jax.named_scope(trace.LATENT_GATHER):
            if L <= cfg.index_topk:
                entries = stores["latent"][depth, page_table].reshape(b, L, -1)
            else:
                phys = jnp.take_along_axis(page_table, chosen // page, axis=1)
                entries = stores["latent"][depth, phys, chosen % page]
        q_abs = latent.absorb(layer, pr["q_nope"], pr["q_rope"], cfg)
        with jax.named_scope(trace.SPARSE_ATTN):
            if L <= cfg.index_topk:
                o = latent.attend_entries(q_abs, entries, ok[:, None], full)
            else:
                o = latent.attend_chosen(q_abs, entries[:, None],
                                         ok[:, None], full)
        h = latent.output(layer, h, pr["hidden"],
                          latent.unabsorb(layer, o, cfg), cfg)
        return h, stores, latent.index_counts(before, own, ok, rows), (chosen, ok)

    def window_layer(layer, h, stores, index):
        pr = latent.project(layer, h, positions, win, cfg)
        with jax.named_scope(trace.RING_WRITE):
            # a row that is not decoding keeps the place as it was: a slot in
            # the middle of a chunked prefill already owns its ring
            old = stores["ring"][index, by_row, ring_at]
            new = jnp.where(valid, latent.stored(pr["entry"][:, 0],
                                                 cfg.ring_store_width), old)
            ring = stores["ring"].at[index, by_row, ring_at].set(new)
        with jax.named_scope(trace.RING_GATHER):
            held = ring[index]                                      # [b, R, w]
        q_abs = latent.absorb(layer, pr["q_nope"], pr["q_rope"], cfg)
        with jax.named_scope(trace.WINDOW_ATTN):
            seen = latent.ring_mask(write_pos, kv_mask, cfg)
            o = latent.attend_entries(q_abs, held, seen[:, None], win)
        h = latent.output(layer, h, pr["hidden"],
                          latent.unabsorb(layer, o, cfg), cfg)
        return h, {**stores, "ring": ring}

    x, pool, counters, selection = latent.walk(
        params, x, valid, pool, cfg,
        indexed_layer if cfg.has_indexer else dense_layer, window_layer,
        trace.SCOPE_DECODE_MLP)
    x = llama.final_norm(params, x, cfg)
    return (llama.lm_head(params, x, cfg)[:, -1, :], pool, kv_mask, counters,
            selection)


@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("pool", "kv_mask"))
def paged_decode_step(params: Params, token: jnp.ndarray, pool: dict,
                      page_table: jnp.ndarray, pos: jnp.ndarray,
                      write_pos: jnp.ndarray, kv_mask: jnp.ndarray,
                      active: jnp.ndarray, keys: jnp.ndarray,
                      temperature: jnp.ndarray, top_k: jnp.ndarray,
                      top_p: jnp.ndarray, cfg: LatentMoEConfig) -> dict:
    """One decode tick over every slot row, the arguments of the dense
    `paged_decode_step`. A full layer writes this token's entry (and index
    key) into (depth, w_page, w_off); under an indexer it scores the query
    against every index key of the row's table, selects, gathers the chosen
    entries through the table and attends them in the absorbed form; without
    one it attends every entry of the row's live pages in the absorbed form,
    in place (`ops/paged_latent_attention.py`); a sliding layer writes its
    entry at `write_pos % R` of the row's ring and attends the ring. Rows
    that are not `active` leave the stores as they were (page writes go to
    the garbage page, the ring place is rewritten with what it held), are
    routed to no expert and count for nothing. The sampler's cost is the
    batch's own (`sample_rowwise`: an argmax a row unless an active row
    samples, a sort only where one filters). Returns the dense tick's
    outputs plus "counters" (`counters(cfg)`) and "selection" (the
    places each row's query selected in each full layer, and which of them
    hold a position: read by tests and by the benchmark's check, never by
    the engine). A model that drafts (`cfg.drafts`) runs its VERIFY tick
    under the same thirteen arguments, two queries a row and up to two tokens
    (`draft.verify_step`, which says what it returns)."""
    if cfg.drafts:
        return draft.verify_step(params, token, pool, page_table, pos,
                                 write_pos, kv_mask, active, keys, temperature,
                                 top_k, top_p, cfg)
    logits, pool, kv_mask, counters, selection = tick_logits(
        params, token, pool, page_table, pos, write_pos, kv_mask, active, cfg)
    with jax.named_scope(trace.SCOPE_SAMPLE):
        split = jax.vmap(jax.random.split)(keys)        # [b, 2, 2]
        nxt = dense_decode.sample_rowwise(logits, temperature, top_k, top_p,
                                          split[:, 1])
    return {"token": nxt, "pool": pool, "kv_mask": kv_mask,
            "keys": split[:, 0], "counters": counters, "selection": selection}


@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("pool", "kv_mask"))
def paged_prefill_chunk(params: Params, input_ids: jnp.ndarray,
                        attention_mask: jnp.ndarray, positions: jnp.ndarray,
                        pool: dict, page_table_row: jnp.ndarray,
                        slot: jnp.ndarray, kv_mask: jnp.ndarray,
                        write_start: jnp.ndarray,
                        cfg: LatentMoEConfig,
                        next_id: jnp.ndarray | None = None) -> dict:
    """One bounded prefill chunk of slot `slot`, the arguments of the dense
    `paged_prefill_chunk`: chunk tokens [1, C] at logical places
    [write_start, write_start + C), C a multiple of the page. A full layer
    writes the chunk's entries (and index keys) into its pages; under an
    indexer it scores the chunk's queries against the slot's index keys so
    far, selects, and attends the selected entries of the slot's gathered
    row; without one it expands the slot's entries so far to keys and values
    and every query attends all it can see; either only as far as the
    chunk's own end reaches (a sort, a gather or an expansion of 4k places
    for a chunk that ends at 4k, not of the whole row); a sliding layer
    reads the ring for the window - 1 places before the chunk, attends, and
    leaves its last places in the ring. A chunk of nothing but left pads
    changes no visible state (the engine runs none). Returns the LAST place's
    float32 logits, the stores, the mask, "counters" and "selection". For a
    model that drafts, `next_id` (int32 [1]) is the id that follows the chunk
    in its bucket, -1 behind the bucket's last chunk: the module keeps the
    chunk's positions with the ids shifted by one, in its own pages, and the
    chunk's last hidden state comes back as "hidden" (draft.py)."""
    _, C = input_ids.shape
    _, _, page, _ = pool["latent"].shape
    L = page_table_row.shape[0] * page
    mask = attention_mask.astype(jnp.int32)
    valid = mask > 0
    kv_mask = jax.lax.dynamic_update_slice(kv_mask, mask, (slot, write_start))
    row_valid = jax.lax.dynamic_slice(kv_mask, (slot, 0), (1, L)) > 0
    chunk_pages = page_table_row[write_start // page + jnp.arange(C // page)]
    places = (write_start + jnp.arange(C, dtype=jnp.int32))[None, :]
    full, win = cfg.kind(False), cfg.kind(True)
    prev = cfg.sliding_window_size - 1
    earlier = write_start - prev + jnp.arange(prev)            # may be < 0
    earlier_valid = ((earlier >= 0)
                     & row_valid[0, jnp.clip(earlier, 0, None)])[None, :]
    kept = latent.kept_positions(C, cfg)
    ring_at = (write_start + C - kept + jnp.arange(kept)) % cfg.ring_len
    # a full layer's work grows with the places its queries can see: one
    # branch for each reach of `step` places (at most KEY_REACHES of them),
    # chosen by where the chunk ends
    step = C * -(-L // (C * KEY_REACHES))

    x = llama.embed(params, input_ids, cfg)

    def full_layer(layer, h, stores, depth):
        pr = latent.project(layer, h, positions, full, cfg)
        new = {"latent": latent.stored(pr["entry"], cfg.latent_store_width)}
        if cfg.has_indexer:
            pr["index"] = latent.index_project(layer, pr["hidden"], pr["cq"],
                                               positions, cfg)
            new["index"] = pr["index"][1]
        with jax.named_scope(trace.LATENT_WRITE):
            stores = dict(stores)
            for name, rows_new in new.items():
                stores[name] = stores[name].at[depth, chunk_pages].set(
                    rows_new[0].reshape(C // page, page, -1))

        def over_first(n_pages: int):
            """The mixer against the row's first `n_pages` pages: all a
            query of this chunk can see when the chunk ends inside them."""
            def run(_):
                rows, upto = page_table_row[:n_pages], n_pages * page
                with jax.named_scope(trace.LATENT_GATHER):
                    entries = stores["latent"][depth, rows].reshape(1, upto, -1)
                if not cfg.has_indexer:
                    out = latent.dense_span(layer, h, write_start, pr, entries,
                                            row_valid[:, :upto], cfg)
                    return out, latent.visible_count(row_valid, places,
                                                     valid), ()
                with jax.named_scope(trace.LATENT_GATHER):
                    keys = stores["index"][depth, rows].reshape(1, upto, -1)
                out, counted, (chosen, ok) = latent.full_span(
                    layer, h, valid, places, pr, entries, keys,
                    row_valid[:, :upto], cfg)
                # one shape for every branch: places past `upto` hold nothing
                grow = min(L, cfg.index_topk) - chosen.shape[-1]
                return out, counted, (
                    jnp.pad(chosen, ((0, 0), (0, grow))),
                    jnp.pad(ok, ((0, 0), (0, grow))))
            return run

        reach = [min(k * step, L) // page
                 for k in range(1, -(-L // step) + 1)]
        h, counted, sel = jax.lax.switch(
            (write_start + C - 1) // step, [over_first(n) for n in reach],
            None)
        return h, stores, counted, sel

    def window_layer(layer, h, stores, index):
        pr = latent.project(layer, h, positions, win, cfg)
        with jax.named_scope(trace.RING_GATHER):
            before = stores["ring"][index, slot, earlier % cfg.ring_len][None]
        h = latent.window_span(layer, h, pr, before, earlier_valid, valid, cfg)
        with jax.named_scope(trace.RING_WRITE):
            ring = stores["ring"].at[index, slot, ring_at].set(
                latent.stored(pr["entry"][0, C - kept:],
                              cfg.ring_store_width))
        return h, {**stores, "ring": ring}

    x, pool, counters, selection = latent.walk(
        params, x, valid, pool, cfg, full_layer, window_layer, trace.SCOPE_MLP)
    if cfg.drafts:
        pr, known = draft.prompt_module(params, x, input_ids, next_id, valid,
                                        positions, cfg)
        with jax.named_scope(trace.LATENT_WRITE):
            paged = lambda rows: rows[0].reshape(C // page, page, -1)
            at = (cfg.full_layers, chunk_pages)
            pool = {
                **pool,
                "latent": pool["latent"].at[at].set(paged(
                    latent.stored(pr["entry"], cfg.latent_store_width))),
                "index": pool["index"].at[at].set(paged(pr["index"][1])),
                "mtp_draft": jax.lax.dynamic_update_slice(
                    pool["mtp_draft"],
                    jnp.full((1,), draft.NO_DRAFT, jnp.int32), (slot,))}
        counters = draft.prefill_counters(counters, known)
        hidden = {"hidden": x[:, -1]}
    x = llama.final_norm(params, x[:, -1:, :], cfg)
    logits = llama.lm_head(params, x, cfg)
    return {"logits": logits[:, -1], "pool": pool, "kv_mask": kv_mask,
            "counters": counters, "selection": selection,
            **(hidden if cfg.drafts else {})}
