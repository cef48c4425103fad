"""Self-drafting with the model's multi-token-prediction module
(`cfg.drafts`: a configuration that states `num_nextn_predict_layers` 1): the
VERIFY tick of two queries a row, the module's part of a prefill, and the
per-slot row that carries a draft from tick to tick.

The module, for a position i whose NEXT token t_{i+1} is known:

    u_i = [enorm(E[t_{i+1}]) | hnorm(h_i)] W_eh      (`model.mtp_project`)
    m_i = layer(u_i)       one whole full layer (MLA + indexer over latent and
                           index pages of its OWN, the pool's last depth) and
                           an expert half, CALLED: `pair_layer`, `moe_block`
    logits_i = rmsnorm_sh(m_i) W_head                a distribution for t_{i+2}

`h_i` is the trunk's last layer's output at i before the final norm; table
and head are the trunk's. The DRAFT is its argmax.

A tick, for a row whose last emitted token t sits at logical place w (rope
position p) with a draft d for the token after it:

1. the trunk runs on TWO queries, t at w and d at w + 1; the second reads the
   first's fresh entry and index key and selects its own `index_topk`;
2. y is drawn from the first query's logits with the row's key, exactly as a
   one-token tick draws it. If y == d the second query's logits are the
   model's own for the token after y, and z is drawn from them with the next
   key of the row's chain: two tokens this tick. If not, y alone is emitted;
   the entries written at w + 1 are dead (never marked in the mask, and
   overwritten by the next tick's first query);
3. the module runs on the accepted positions, w with next token y and, when
   the draft was accepted, w + 1 with next token z, writes their entries into
   its own pages and leaves the next draft in the slot's row.

The rng chain advances one key an EMITTED token and a token is only ever
drawn from logits of a prefix of emitted tokens, so the emitted stream is
token for token what one-token ticks emit under the same keys, greedy or
sampled.

A prefill leaves the module's entries of the prompt in its pages (the
projections of u_i alone: nothing reads m_i of a prompt position), each
position with the id after it, and returns its last position's hidden state
("hidden"). The prompt's LAST position has no next token until the first
token is drawn (`tick_io.first_token`, on the device): `first_draft` then
runs the module on that one position, with the token read from the vector
`first_token` wrote it into, and leaves the row's first draft. Static
shapes: two trunk queries and two module positions a row in every tick, the
second of each masked where there is no draft or the draft was refused.

The draft rides the donated pool beside the pages, one number a slot
(`mtp_draft` [slots] int32, -1: none): the host never sees a draft. What it
does not know a tick ahead any more is a row's position: `pos`, `write_pos`,
token and key of the next tick are this tick's own results, taken on the
device (`models/tick_io.py`).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from llama_pipeline_parallel_tpu.models.hybrid_moe import model as hybrid
from llama_pipeline_parallel_tpu.models.latent_moe import model as latent
from llama_pipeline_parallel_tpu.models.latent_moe.config import LatentMoEConfig
from llama_pipeline_parallel_tpu.models.llama import decode as dense_decode
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.ops.rmsnorm import rms_norm
from llama_pipeline_parallel_tpu.utils import trace

Params = dict
# summed like `tokens` on `serve_decode_step`; `mtp_positions` on a
# `serve_prefill` unit too. Drafts offered (row-ticks that had one), drafts
# accepted, tokens emitted (row-ticks + accepted), cache places written and
# not kept (a refused draft's entry in every trunk layer), positions the
# module ran or projected
COUNTERS = ("spec_offered", "spec_accepted", "spec_tokens",
            "spec_dead_entries", "mtp_positions")
STORE = ("mtp_draft",)
NO_DRAFT = -1


def init_store(cfg: LatentMoEConfig, max_slots: int) -> dict:
    """The per-slot row of a drafting model: the draft (-1: none)."""
    return {"mtp_draft": jnp.full((max_slots,), NO_DRAFT, jnp.int32)}


def expert_half(mtp: Params) -> tuple[Params, Params]:
    """The module's expert half as `hybrid.moe_block` takes a layer's: (its
    own norm, router, bias and shared expert; the routed experts as the
    stack of one layer they are stored as, at place 0)."""
    moe = mtp["moe"]
    return ({name: leaf[0] for name, leaf in moe.items()
             if name not in hybrid.EXPERT_LEAVES},
            {name: moe[name] for name in hybrid.EXPERT_LEAVES})


# -- the module's part of a prefill ---------------------------------------------

def prompt_module(params: Params, h: jnp.ndarray, input_ids: jnp.ndarray,
                  next_id: jnp.ndarray, valid: jnp.ndarray,
                  positions: jnp.ndarray, cfg: LatentMoEConfig):
    """What the module keeps of a span of prompt positions. h: [b, C, d] the
    trunk's last layer's output; input_ids: [b, C]; next_id: [b] the id that
    follows the span (-1: none yet, the prompt ends here); valid: [b, C].
    Returns (`project`'s result for u with `index` beside it: the entries and
    index keys to write, the span's places as the trunk wrote them; int32
    positions whose next token was known). The last position of a span
    without a next id is projected from a stand-in id and written like the
    rest: `first_draft` writes it anew before anything reads it."""
    mtp = params["mtp"]
    nxt = jnp.concatenate([input_ids[:, 1:], next_id[:, None]], axis=1)
    known = valid & jnp.concatenate(
        [jnp.ones_like(valid[:, 1:]), (next_id >= 0)[:, None]], axis=1)
    u = latent.mtp_project(mtp, jnp.clip(nxt, 0, None), h, params, cfg)
    with jax.named_scope(trace.MTP_LAYER):
        pr = latent.project(mtp["attn"], u, positions, cfg.kind(False), cfg)
        pr["index"] = latent.index_project(mtp["attn"], pr["hidden"], pr["cq"],
                                           positions, cfg)
    return pr, jnp.sum(known).astype(jnp.int32)


def prefill_counters(counted: jnp.ndarray, mtp_positions) -> jnp.ndarray:
    """A prefill unit's counters of a drafting model: the trunk's, then
    `COUNTERS` with nothing offered and the module's positions."""
    spec = jnp.zeros((len(COUNTERS),), jnp.int32).at[-1].set(mtp_positions)
    return jnp.concatenate([counted, spec])


# -- the verify tick ---------------------------------------------------------------

def pair_layer(layer: Params, h: jnp.ndarray, stores: dict, depth,
               places: jnp.ndarray, positions: jnp.ndarray,
               q_valid: jnp.ndarray, seen: jnp.ndarray,
               page_table: jnp.ndarray, cfg: LatentMoEConfig):
    """A full layer's mixer, under an indexer, for T consecutive queries of
    each row in a tick (T = 2). h: [b, T, d]; places, positions, q_valid:
    [b, T] (logical places, rope positions, which queries are run for real);
    `seen`: [b, L] bool, the places that hold a token once the FIRST query's
    own is marked. Every query writes its entry and index key (one that is
    not valid into the garbage page), then scores the row's index keys,
    selects among the places before its own and attends what it chose: the
    second query sees the first's fresh entry. Returns (h + y, stores,
    int32[2] positions visible to and selected by the valid queries,
    (chosen [b, T, K], ok [b, T, K]))."""
    b, T, _ = h.shape
    _, n_pool, page, _ = stores["latent"].shape
    L = page_table.shape[1] * page
    full = cfg.kind(False)
    pr = latent.project(layer, h, positions, full, cfg)
    qi, ki, weights = latent.index_project(layer, pr["hidden"], pr["cq"],
                                           positions, cfg)
    w_page = jnp.take_along_axis(page_table, places // page, axis=1)
    w_page = jnp.where(q_valid, w_page, n_pool - 1)
    with jax.named_scope(trace.LATENT_WRITE):
        stores = dict(stores)
        for name, rows_new in (
                ("latent", latent.stored(pr["entry"], cfg.latent_store_width)),
                ("index", ki)):
            stores[name], _ = dense_decode._write_tokens(
                stores[name], None, depth, rows_new, w_page, places % page,
                None)
    with jax.named_scope(trace.LATENT_GATHER):
        keys = stores["index"][depth, page_table].reshape(b, L, -1)
    scores = latent.index_scores(qi, weights, keys)                 # [b, T, L]
    grid = jnp.arange(L, dtype=jnp.int32)[None, None, :]
    before = seen[:, None, :] & (grid < places[..., None])
    own = grid == places[..., None]
    chosen, ok = latent.select(scores, before, own, cfg.index_topk)
    q_abs = latent.absorb(layer, pr["q_nope"], pr["q_rope"], cfg)
    if L <= cfg.index_topk:
        with jax.named_scope(trace.LATENT_GATHER):
            entries = stores["latent"][depth, page_table].reshape(b, L, -1)
        with jax.named_scope(trace.SPARSE_ATTN):
            o = latent.attend_entries(q_abs, entries, ok, full)
    else:
        with jax.named_scope(trace.LATENT_GATHER):
            # the chosen places' pages, by compare and sum over the row's
            # table: a gather of as many single numbers costs the chip a
            # millisecond a layer (PERF.md section 6, PR 55), this nothing
            column = jnp.arange(page_table.shape[1], dtype=jnp.int32)
            phys = jnp.sum(jnp.where(
                (chosen // page)[..., None] == column,
                page_table[:, None, None, :], 0), axis=-1)
            entries = stores["latent"][depth, phys, chosen % page]
        with jax.named_scope(trace.SPARSE_ATTN):
            o = latent.attend_chosen(q_abs, entries, ok, full)
    h = latent.output(layer, h, pr["hidden"], latent.unabsorb(layer, o, cfg),
                      cfg)
    return (h, stores, latent.index_counts(before, own, ok, q_valid),
            (chosen, ok))


def module_positions(params: Params, hidden: jnp.ndarray, nxt: jnp.ndarray,
                     pool: dict, places: jnp.ndarray, positions: jnp.ndarray,
                     valid: jnp.ndarray, seen: jnp.ndarray,
                     page_table: jnp.ndarray, cfg: LatentMoEConfig):
    """The module at T consecutive positions of each row whose next tokens
    are `nxt` [b, T]: hidden [b, T, d] the trunk's last layer's output
    there; the rest as `pair_layer` takes it. Returns (float32 logits [b, T,
    V], the stores, the layer's index counters int32[2], its expert half's
    int32[6], its selection)."""
    mtp = params["mtp"]
    u = latent.mtp_project(mtp, nxt, hidden, params, cfg)
    with jax.named_scope(trace.MTP_LAYER):
        m, pool, counted, selection = pair_layer(
            mtp["attn"], u, pool, cfg.full_layers, places, positions, valid,
            seen, page_table, cfg)
        moe, experts = expert_half(mtp)
        m, routed = hybrid.moe_block(moe, experts, 0, m, valid, cfg)
    with jax.named_scope(trace.MTP_HEAD):
        m = rms_norm(m, mtp["shared_head_norm"], cfg.rms_norm_eps)
        return llama.lm_head(params, m, cfg), pool, counted, routed, selection


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("pool",))
def first_draft(params: Params, hidden: jnp.ndarray, prev: jnp.ndarray,
                pool: dict, page_table_row: jnp.ndarray, slot: jnp.ndarray,
                kv_mask: jnp.ndarray, pos: jnp.ndarray, place: jnp.ndarray,
                cfg: LatentMoEConfig) -> dict:
    """A prefilled row's FIRST draft, once its first token is drawn: the
    module at the prompt's last position (`hidden` [1, d], what the row's
    last prefill unit returned; rope position `pos`, logical place `place`)
    with the first token as its next, read where `tick_io.first_token` wrote
    it (`prev[slot]`). Writes the position's entry and index key into the
    module's pages and the draft into the slot's row. The stores come back
    donated; the position counts on no span (the host counts a request's
    first draft by the request)."""
    token = jax.lax.dynamic_slice(prev, (slot,), (1,))
    L = page_table_row.shape[0] * pool["latent"].shape[2]
    seen = jax.lax.dynamic_slice(kv_mask, (slot, 0), (1, L)) > 0
    one = lambda a: jnp.reshape(a, (1, 1))
    logits, pool, _, _, _ = module_positions(
        params, hidden[:, None], one(token), pool, one(place), one(pos),
        jnp.ones((1, 1), bool), seen, page_table_row[None], cfg)
    draft = jnp.argmax(logits[0, 0]).astype(jnp.int32)
    return {**pool, "mtp_draft": jax.lax.dynamic_update_slice(
        pool["mtp_draft"], draft[None], (slot,))}


def verify_step(params: Params, token: jnp.ndarray, pool: dict,
                page_table: jnp.ndarray, pos: jnp.ndarray,
                write_pos: jnp.ndarray, kv_mask: jnp.ndarray,
                active: jnp.ndarray, keys: jnp.ndarray,
                temperature: jnp.ndarray, top_k: jnp.ndarray,
                top_p: jnp.ndarray, cfg: LatentMoEConfig) -> dict:
    """The decode tick of a drafting model, the arguments of every family's
    `paged_decode_step` (module docstring). Returns "tokens" [b, 2] (the
    second valid where "count" is 2), "count" [b] (1 or 2 for a row that
    decodes), "token" [b] (the last emitted: the next tick's), "keys" (the
    row's chain after what was emitted), "pos" / "write_pos" (the next
    tick's), "pool", "kv_mask", "counters" (`decode.counters(cfg)`), and for
    tests and checks "logits" / "mtp_logits" (float32 [b, 2, V]), "drafted"
    [b] (the draft this tick verified, -1: none), "second" [b] (the second
    query's first choice, read whether or not the draft is accepted; both are
    the tick's record in the fetched vector), "draft" [b] (the one it
    leaves), "module_valid" [b, 2], "selection" (the trunk's, [full layers,
    b, 2, K]) and "mtp_selection"."""
    b = token.shape[0]
    _, _, page, _ = pool["latent"].shape
    L = page_table.shape[1] * page
    rows = active > 0
    by_row = jnp.arange(b)
    drafted = pool["mtp_draft"]
    offered = rows & (drafted >= 0)
    pair = jnp.arange(2, dtype=jnp.int32)[None, :]
    # a row's last tick may sit on the last place of its row: the dead second
    # query then writes over the first's place in the garbage page's stead
    places = jnp.minimum(write_pos[:, None] + pair, L - 1)
    positions = pos[:, None] + pair
    q_valid = jnp.stack([rows, offered], axis=1)
    kv_mask = kv_mask.at[by_row, write_pos].max(active.astype(kv_mask.dtype))
    seen = kv_mask > 0

    ids = jnp.stack([token, jnp.clip(drafted, 0, None)], axis=1)
    x = llama.embed(params, ids, cfg)

    def trunk_layer(layer, h, stores, depth):
        return pair_layer(layer, h, stores, depth, places, positions, q_valid,
                          seen, page_table, cfg)

    h, pool, counted, selection = latent.walk(
        params, x, q_valid, pool, cfg, trunk_layer, None,
        trace.SCOPE_DECODE_MLP)
    logits = llama.lm_head(params, llama.final_norm(params, h, cfg), cfg)

    with jax.named_scope(trace.SCOPE_SAMPLE):
        split = jax.vmap(jax.random.split)(keys)                    # [b, 2, 2]
        again = jax.vmap(jax.random.split)(split[:, 0])
        twice = lambda a: jnp.concatenate([a, a])
        drawn = dense_decode.sample_rowwise(
            jnp.concatenate([logits[:, 0], logits[:, 1]]), twice(temperature),
            twice(top_k), twice(top_p),
            jnp.concatenate([split[:, 1], again[:, 1]]))
        y, z = drawn[:b], drawn[b:]
    with jax.named_scope(trace.SPEC_ACCEPT):
        accepted = offered & (y == drafted)
        count = jnp.where(accepted, 2, 1).astype(jnp.int32)
        last = jnp.where(accepted, z, y)
        chain = jnp.where(accepted[:, None], again[:, 0], split[:, 0])
        kv_mask = kv_mask.at[by_row, places[:, 1]].max(
            accepted.astype(kv_mask.dtype))
        seen = kv_mask > 0

    # the module on the accepted positions: this one with the token drawn
    # after it and, behind an accepted draft, the next with the second
    m_valid = jnp.stack([rows, accepted], axis=1)
    mtp_logits, pool, m_counted, m_routed, m_selection = module_positions(
        params, h, jnp.stack([y, z], axis=1), pool, places, positions,
        m_valid, seen, page_table, cfg)
    with jax.named_scope(trace.MTP_HEAD):
        best = jnp.argmax(mtp_logits, axis=-1).astype(jnp.int32)    # [b, 2]
        draft = jnp.where(rows, jnp.where(accepted, best[:, 1], best[:, 0]),
                          drafted)
    pool = {**pool, "mtp_draft": draft}

    n_moe = len(hybrid.COUNTERS)
    spec = jnp.stack([
        jnp.sum(offered), jnp.sum(accepted), jnp.sum(jnp.where(rows, count, 0)),
        jnp.sum(offered & ~accepted) * cfg.full_layers,
        jnp.sum(m_valid)]).astype(jnp.int32)
    counters = jnp.concatenate([counted[:n_moe] + m_routed,
                                counted[n_moe:] + m_counted, spec])
    return {"tokens": jnp.stack([y, last], axis=1), "count": count,
            "token": last, "keys": chain, "pos": pos + count,
            "write_pos": write_pos + count, "pool": pool, "kv_mask": kv_mask,
            "counters": counters, "logits": logits, "mtp_logits": mtp_logits,
            "drafted": jnp.where(offered, drafted, NO_DRAFT),
            "second": jnp.argmax(logits[:, 1], axis=-1).astype(jnp.int32),
            "draft": draft,
            "module_valid": m_valid, "selection": selection,
            "mtp_selection": m_selection}
