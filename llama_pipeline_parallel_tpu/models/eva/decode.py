"""Serving programs of the compressed-window block: `prefill_prompt`,
`paged_prefill_chunk`, `paged_decode_step` and `write_pages`, with the
signatures of their `models/llama/decode.py` namesakes, so `serve/engine.py`
and `serve/pages.py` drive this family through `models/family.py` without
naming it.

One pool, two kinds of page. The pool is the dense family's own (`k` / `v`
`[L, pages + 1, page, kv_h, hd]`, `init_page_pool`); a page is a WINDOW page
(`page` positions' exact keys and values) or a SUMMARY page (`page` chunks'
pooled keys and values: `page x C` positions), told apart only by where the
slot's table lists it:

    table row = [ summary pages, ceil(max_len / (page x C)) | ring, W / page ]

- the RING is the slot's current window: position `p` lives at ring column `p
  mod W`, so the ring is reused window after window and a left-padded bucket
  changes nothing a query sees (places are counted by POSITION, never by the
  padded row; the `[max_slots, max_len]` mask rides along untouched and says
  nothing here);
- summary entry `j` is chunk `j`'s pooled key and value, written ONCE, by the
  write that completes the chunk's window: the window's `W / C` chunks are
  pooled from the ring's exact entries (`model.pool_chunks`) into the next
  `W / (C x page)` summary pages BEFORE position `p + 1` overwrites ring
  column 0. Nothing is recomputed later. In a chunk of a prefill that is part
  of every layer's pass (a later query of the same chunk reads the summaries
  just made); in a tick it is one pass over the layers after the layer loop,
  under one `lax.cond` on whether any row completed a window.

What a slot's pages are (`table_width`, `table_columns`: what `serve/pages.py`
asks of a family): the ring's pages as the first window fills, then two
summary pages (at the published sizes) a finished window. At 25k positions a
row holds 32 + 24 pages where the dense family would hold 400.

The tick's read is the dense family's kernel (`ops/paged_attention.py`), as
it stands: the row's live summary pages and live ring pages are listed side by
side (`_live_pages`), with a mask of the ring's live entries, and one running
softmax walks them. A chunk's read is `ops/eva_prefill_attention.py` over the
slot's summaries, the ring as it stood and the chunk's own keys.

Counters (`COUNTERS`, summed over layers, on every program's output): exact
entries the queries read, summary entries they read, summary entries written.

What this family cannot do yet is refused by name where the engine is built
(`models/family.py`): a prefix cache and the span prefill (a shared page
would need the ring as it stood at the divergence point), int8 pages.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from llama_pipeline_parallel_tpu.models.eva import model as eva
from llama_pipeline_parallel_tpu.models.eva.config import EvaConfig
from llama_pipeline_parallel_tpu.models.llama import decode as dense_decode
from llama_pipeline_parallel_tpu.models.llama.decode import (  # noqa: F401
    init_page_pool,
    sample_rowwise,
)
from llama_pipeline_parallel_tpu.ops.paged_attention import (
    paged_decode_attention,
)
from llama_pipeline_parallel_tpu.ops.rope import rope_cos_sin
from llama_pipeline_parallel_tpu.utils import trace

Params = dict
COUNTERS = ("eva_window_visible", "eva_summary_visible",
            "eva_summaries_written")


# -- what a slot's pages are (serve/pages.py asks) ---------------------------

def _layout(cfg: EvaConfig, page_size: int) -> tuple[int, int]:
    """(ring pages, summary pages a finished window)."""
    W, per_window = cfg.window_size, cfg.chunks_per_window
    if W % page_size or per_window % page_size:
        raise ValueError(
            f"page_size {page_size} must divide the window ({W} positions) "
            f"and a window's summaries ({per_window} chunks): a window page "
            f"and a summary page are whole pages of one pool")
    return W // page_size, per_window // page_size


def summary_columns(cfg: EvaConfig, table_width: int, page_size: int) -> int:
    """How many leading columns of a table row are summary pages."""
    return table_width - cfg.window_size // page_size


def table_width(cfg: EvaConfig, max_len: int, page_size: int) -> int:
    """Entries of a slot's row of the page table: a summary page every `page
    x C` positions of `max_len`, then the ring."""
    ring, _ = _layout(cfg, page_size)
    return -(-max_len // (page_size * cfg.chunk_size)) + ring


def table_columns(cfg: EvaConfig, tokens: int, max_len: int,
                  page_size: int) -> np.ndarray:
    """The table columns that hold pages once `tokens` places of the slot's
    row are written (ascending, and growing with `tokens`): the summary
    pages of every window that `tokens` positions finish, and the ring's
    pages up to the window's size. A left-padded row holds fewer positions
    than places, so this is never too few."""
    ring, per_window = _layout(cfg, page_size)
    n_sum = table_width(cfg, max_len, page_size) - ring
    return np.concatenate([
        np.arange(min(tokens // cfg.window_size * per_window, n_sum)),
        n_sum + np.arange(min(-(-tokens // page_size), ring))])


# -- a layer's reads and writes ----------------------------------------------

def _walk(params: Params, x: jnp.ndarray, pool: dict, cos, sin,
          cfg: EvaConfig, attend) -> tuple[jnp.ndarray, dict]:
    """The layers over the pool IN PLACE, as `dense_decode._walk_pool` walks
    it (the pool rides the carry, touched only by indexed reads and writes),
    with the layer's read and writes in one callback, since a chunk must
    read the ring before it overwrites it: `attend(layer, i, q, k, v, pool)
    -> (attention output, pool)`."""
    def body(carry, xs):
        h, pool = carry
        layer, i = xs
        q, k, v = dense_decode._project_qkv(layer, h, cos, sin, cfg)
        out, pool = attend(layer, i, q, k, v, pool)
        return (dense_decode._attn_out_and_mlp(layer, h, out, cfg), pool), None

    (x, pool), _ = jax.lax.scan(
        body, (x, pool), (params["layers"], jnp.arange(pool["k"].shape[0])))
    return x, pool


def _write_ring(pool: dict, i, k, v, w_page, col) -> dict:
    """Tokens' keys and values ([n, kv_h, hd]) into layer `i` at ring column
    `col` [n] (`p mod W`) of pages `w_page` [n]: the ring page that holds
    the column, or the garbage page for what must not be kept (a pad, a row
    that is not decoding). The dense family's per-token write."""
    off = col % pool["k"].shape[2]
    with jax.named_scope(trace.SCOPE_KV_WRITE):
        return {**pool, **{
            name: dense_decode._write_tokens(pool[name], None, i, rows,
                                             w_page, off, claimed=None)[0]
            for name, rows in (("k", k), ("v", v))}}


def _write_summaries(pool: dict, i, sk, sv, pages) -> dict:
    """A window's pooled entries ([W / C, kv_h, hd]) into whole summary
    pages `pages` of layer `i`."""
    page = pool["k"].shape[2]
    blocks = lambda x: x.reshape(pages.shape[0], page, *x.shape[1:])
    with jax.named_scope(trace.EVA_SUMMARY_WRITE):
        return {**pool, "k": pool["k"].at[i, pages].set(blocks(sk)),
                "v": pool["v"].at[i, pages].set(blocks(sv))}


def _window_summary_pages(table_row, window, n_sum: int, per_window: int,
                          done, garbage: int) -> jnp.ndarray:
    """The `per_window` summary pages of window `window` in a slot's table
    row, or the garbage page where `done` is false."""
    cols = window * per_window + jnp.arange(per_window)
    return jnp.where(done & (cols < n_sum),
                     table_row[jnp.clip(cols, 0, n_sum - 1)], garbage)


# -- prefill of a whole bucket, and its splice into the pages ----------------

@partial(jax.jit, static_argnames=("cfg", "max_len"))
def prefill_prompt(params: Params, input_ids: jnp.ndarray,
                   attention_mask: jnp.ndarray, cfg: EvaConfig,
                   max_len: int) -> dict:
    """Prefill LEFT-padded prompts whole (`model.forward_prompt`): {"logits":
    [b, vocab] float32 at the last place, "cache", "kv_mask": [b, max_len],
    "next_pos": [b], "counters"}. The cache is what `write_pages` splices,
    already in the pages' order: "k" / "v" [L, b, min(P, W), kv_h, hd] the
    LAST window's exact entries at ring column `p mod W` (what lies past the
    prompt's end there is never read), "sk" / "sv" [L, b, (P // W) x (W / C),
    kv_h, hd] the pooled entries of the windows a bucket can finish."""
    b, prompt_len = input_ids.shape
    if prompt_len > max_len:
        raise ValueError(f"prompt bucket {prompt_len} exceeds max_len "
                         f"{max_len}")
    W = cfg.window_size
    out = eva.forward_prompt(params, input_ids, attention_mask, cfg)
    mask = attention_mask.astype(jnp.int32)
    n = mask.sum(axis=1)                                        # [b]
    # ring column c holds position (last window's first) + c: row place
    # pad + that
    first = (prompt_len - n) + jnp.maximum(n - 1, 0) // W * W
    places = jnp.clip(first[:, None] + jnp.arange(min(prompt_len, W)), 0,
                      prompt_len - 1)[None, :, :, None, None]
    cache = out["cache"]
    finished = prompt_len // W * cfg.chunks_per_window
    out["cache"] = {
        "k": jnp.take_along_axis(cache["k"], places, axis=2),
        "v": jnp.take_along_axis(cache["v"], places, axis=2),
        "sk": cache["sk"][:, :, :finished], "sv": cache["sv"][:, :, :finished]}
    out["kv_mask"] = jnp.pad(mask, ((0, 0), (0, max_len - prompt_len)))
    return out


@partial(jax.jit, donate_argnames=("pool", "kv_mask"))
def write_pages(pool: dict, kv_mask: jnp.ndarray, slot: jnp.ndarray,
                page_rows: jnp.ndarray, row_cache: dict,
                row_kv_mask: jnp.ndarray) -> tuple[dict, jnp.ndarray]:
    """Splice one prefilled request (`prefill_prompt` of one row, bucket a
    multiple of the page size) into its pages. `page_rows` are the slot's
    table at `table_columns(bucket)`: the summary pages of the windows a
    bucket can finish, then the ring's; the cache's two parts are in that
    order already, whole pages each. The mask is returned as it came: it says
    nothing of what this family reads."""
    del slot, row_kv_mask
    page = pool["k"].shape[2]
    n_sum = row_cache["sk"].shape[2] // page
    out = dict(pool)
    for name, part, rows in (("k", "sk", page_rows[:n_sum]),
                             ("v", "sv", page_rows[:n_sum]),
                             ("k", "k", page_rows[n_sum:]),
                             ("v", "v", page_rows[n_sum:])):
        if rows.shape[0]:
            entries = row_cache[part][:, 0]                # [L, n, kv_h, hd]
            out[name] = out[name].at[:, rows].set(entries.reshape(
                entries.shape[0], rows.shape[0], page, *entries.shape[2:]))
    return out, kv_mask


# -- a chunk of a prefill ----------------------------------------------------

@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("pool", "kv_mask"))
def paged_prefill_chunk(params: Params, input_ids: jnp.ndarray,
                        attention_mask: jnp.ndarray, positions: jnp.ndarray,
                        pool: dict, page_table_row: jnp.ndarray,
                        slot: jnp.ndarray, kv_mask: jnp.ndarray,
                        write_start: jnp.ndarray, cfg: EvaConfig) -> dict:
    """One prefill chunk of a slot: input_ids / attention_mask / positions
    [1, T] (T <= W: a chunk touches at most two windows), the row's places
    [write_start, write_start + T) of a LEFT-padded bucket, so the chunk's
    tokens are its last `sum(mask)` places and their positions consecutive.
    Per layer: the ring as it stood is gathered; if the chunk's tokens
    complete the window of its first token, that window's chunks are pooled
    (ring entries before the chunk, the chunk's own after) and written to
    its summary pages; the queries read the slot's summaries, the ring as it
    stood and the chunk's own keys in one softmax; then its keys and values
    go to the ring at `p mod W` (the engine runs no chunk of pads alone).
    Returns the last float32 logits, the pool, the mask and "counters"."""
    del slot, write_start
    _, T = input_ids.shape
    W, per_window = cfg.window_size, cfg.chunks_per_window
    if T > W:
        raise ValueError(f"a prefill chunk of {T} tokens is longer than the "
                         f"window ({W}): its keys would meet in the ring")
    page = pool["k"].shape[2]
    garbage = pool["k"].shape[1] - 1
    n_sum = summary_columns(cfg, page_table_row.shape[0], page)
    sum_pages, ring_pages = page_table_row[:n_sum], page_table_row[n_sum:]
    valid = attention_mask[0] > 0                               # [T]
    pos = positions[0].astype(jnp.int32)
    n = valid.sum().astype(jnp.int32)
    p_last = pos[-1]
    p_first = p_last - n + 1
    window = jnp.maximum(p_first, 0) // W      # of the chunk's first token
    before = p_first - window * W              # ring entries of that window
    done = (n > 0) & (p_last >= (window + 1) * W - 1)
    # the first token's window, whole: ring column c as it stood where c <
    # `before`, the chunk's place (T - n) + (c - before) from there on
    c = jnp.arange(W)
    from_ring = (c < before)[:, None, None]
    own = jnp.clip(T - n + c - before, 0, T - 1)
    window_pages = _window_summary_pages(page_table_row, window, n_sum,
                                         per_window // page, done, garbage)
    col = pos % W
    w_page = jnp.where(valid, ring_pages[col // page], garbage)
    tag_ring = jnp.where(c < before, window * W + c, -1)[None]
    tag_own = jnp.where(valid, pos, -1)[None]
    tag_exact = jnp.concatenate([tag_ring, tag_own], axis=1)
    tag_sum = eva.summary_tags(n_sum * page, cfg)[None]

    view = eva.with_unit_offset(params, cfg)
    x = eva.embed(view, input_ids, cfg)
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                            dtype=cfg.dtype)

    def attend(layer, i, q, k, v, pool):
        ring_k, ring_v = dense_decode._gather_pages(      # [W, kv_h, hd]
            pool, i, ring_pages, cfg.dtype)
        sk, sv = eva.pool_chunks(
            jnp.where(from_ring, ring_k, k[0][own]),
            jnp.where(from_ring, ring_v, v[0][own]),
            layer["attn"]["mu"], layer["attn"]["phi"], cfg)
        pool = _write_summaries(pool, i, sk, sv, window_pages)
        sum_k, sum_v = dense_decode._gather_pages(pool, i, sum_pages,
                                                  cfg.dtype)
        out = eva.attend_span(
            q, sum_k[None], sum_v[None], tag_sum,
            jnp.concatenate([ring_k[None], k], axis=1),
            jnp.concatenate([ring_v[None], v], axis=1), tag_exact,
            positions, valid[None], cfg)
        return out, _write_ring(pool, i, k[0], v[0], w_page, col)

    x, pool = _walk(view, x, pool, cos, sin, cfg, attend)
    logits = eva.head0(view, x[:, -1:, :], cfg)[:, -1]
    L = cfg.num_hidden_layers
    seen_w, seen_s = eva.visible_counts(pos, valid, cfg)
    counters = jnp.stack([seen_w * L, seen_s * L,
                          done.astype(jnp.int32) * per_window * L])
    return {"logits": logits, "pool": pool, "kv_mask": kv_mask,
            "counters": counters}


# -- the tick ----------------------------------------------------------------

def _live_pages(page_table, n_summaries, n_window, n_sum: int, page: int):
    """A row's live pages side by side, summaries first: (table [S, Pmax],
    live pages [S], mask [S, Pmax x page]) as `paged_decode_attention` takes
    them. `n_summaries` [S] is a whole number of pages; `n_window` [S] live
    ring entries."""
    pmax = page_table.shape[1]
    sp = (n_summaries // page)[:, None]
    wp = -(-n_window // page)[:, None]
    j = jnp.arange(pmax)[None, :]
    source = jnp.where(j < sp, j, jnp.minimum(n_sum + j - sp, pmax - 1))
    table = jnp.take_along_axis(page_table, source, axis=1)
    e = jnp.arange(pmax * page)[None, :]
    in_ring = e - sp * page                 # a ring entry's column, or < 0
    mask = (in_ring < 0) | (in_ring < n_window[:, None])
    return table, (sp + wp)[:, 0], mask.astype(jnp.int32)


def _pool_finished(params: Params, pool: dict, page_table, pos, finished,
                   cfg: EvaConfig) -> dict:
    """Every layer's pooling of the windows the rows in `finished` [S] have
    just completed: a row at a time, its ring's W exact entries to W / C
    pooled ones in the window's summary pages (a row that completed none
    writes the garbage page)."""
    page = pool["k"].shape[2]
    garbage = pool["k"].shape[1] - 1
    n_sum = summary_columns(cfg, page_table.shape[1], page)
    per_window = cfg.chunks_per_window // page
    mu, phi = params["layers"]["attn"]["mu"], params["layers"]["attn"]["phi"]

    def row(pool, xs):
        table_row, p, done = xs
        pages = _window_summary_pages(table_row, p // cfg.window_size, n_sum,
                                      per_window, done, garbage)

        def layer(i, pool):
            ring = dense_decode._gather_pages(pool, i, table_row[n_sum:],
                                              cfg.dtype)
            sk, sv = eva.pool_chunks(*ring, mu[i], phi[i], cfg)
            return _write_summaries(pool, i, sk, sv, pages)

        return jax.lax.fori_loop(0, pool["k"].shape[0], layer, pool), None

    return jax.lax.scan(row, pool, (page_table, pos, finished))[0]


def tick_logits(params: Params, token: jnp.ndarray, pool: dict,
                page_table: jnp.ndarray, pos: jnp.ndarray,
                write_pos: jnp.ndarray, kv_mask: jnp.ndarray,
                active: jnp.ndarray, cfg: EvaConfig):
    """The decode tick up to its logits: (float32 logits [S, vocab], pool,
    kv_mask as it came, counters). A row's token at position `p` is written
    to ring column `p mod W`, reads the `(p // W) x (W / C)` summaries of the
    earlier windows and the ring's `p mod W + 1` live entries in one softmax
    (`ops/paged_attention.py`), and where it completes its window the
    window is pooled before the next tick overwrites column 0. `write_pos`
    (the padded row's place) is not read: places are positions here."""
    del write_pos
    W = cfg.window_size
    page = pool["k"].shape[2]
    n_sum = summary_columns(cfg, page_table.shape[1], page)
    on = active > 0
    col = pos % W
    n_window = jnp.where(on, col + 1, 0)
    n_summaries = jnp.where(on, pos // W * cfg.chunks_per_window, 0)
    table, live, mask = _live_pages(page_table, n_summaries, n_window, n_sum,
                                    page)
    # a row's token goes to ITS ring at `p mod W`; a row that is not decoding
    # writes the garbage page
    w_page = jnp.where(on, jnp.take_along_axis(
        page_table, (n_sum + col // page)[:, None], axis=1)[:, 0],
        pool["k"].shape[1] - 1)
    view = eva.with_unit_offset(params, cfg)
    x = eva.embed(view, token[:, None], cfg)
    cos, sin = rope_cos_sin(pos[:, None], cfg.head_dim, cfg.rope_theta,
                            dtype=cfg.dtype)

    def attend(layer, i, q, k, v, pool):
        pool = _write_ring(pool, i, k[:, 0], v[:, 0], w_page, col)
        with jax.named_scope(trace.EVA_ATTN):
            out = paged_decode_attention(q[:, 0], pool["k"], pool["v"], i,
                                         table, live, mask)
        return out[:, None], pool

    x, pool = _walk(view, x, pool, cos, sin, cfg, attend)
    finished = on & (col == W - 1)
    pool = jax.lax.cond(
        finished.any(),
        lambda pool: _pool_finished(params, pool, page_table, pos, finished,
                                    cfg),
        lambda pool: pool, pool)
    L = cfg.num_hidden_layers
    counters = jnp.stack([
        n_window.sum() * L, n_summaries.sum() * L,
        finished.sum() * cfg.chunks_per_window * L]).astype(jnp.int32)
    return eva.head0(view, x, cfg)[:, -1, :], pool, kv_mask, counters


@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("pool", "kv_mask"))
def paged_decode_step(params: Params, token: jnp.ndarray, pool: dict,
                      page_table: jnp.ndarray, pos: jnp.ndarray,
                      write_pos: jnp.ndarray, kv_mask: jnp.ndarray,
                      active: jnp.ndarray, keys: jnp.ndarray,
                      temperature: jnp.ndarray, top_k: jnp.ndarray,
                      top_p: jnp.ndarray, cfg: EvaConfig) -> dict:
    """One decode tick over every slot row, `dense_decode.paged_decode_step`'s
    contract (the same rng discipline, the same sampler, inactive rows ride
    the static shape and write the garbage page) over this family's pages
    (`tick_logits`). Returns {"token", "pool", "kv_mask", "keys",
    "counters"}."""
    logits, pool, kv_mask, counters = tick_logits(
        params, token, pool, page_table, pos, write_pos, kv_mask, active, cfg)
    with jax.named_scope(trace.SCOPE_SAMPLE):
        split = jax.vmap(jax.random.split)(keys)
        nxt = sample_rowwise(logits, temperature, top_k, top_p, split[:, 1])
    return {"token": nxt, "pool": pool, "kv_mask": kv_mask,
            "keys": split[:, 0], "counters": counters}
