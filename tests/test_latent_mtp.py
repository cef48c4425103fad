"""A latent-attention model that drafts with its multi-token-prediction
module (`models/latent_moe/draft.py`), against the plain reference
(`benchmark/reference/glm_dsa_mtp_decoder.py`): the configuration from the
published `glm_moe_dsa` keys, trunk and module over a whole prompt, prefill
then VERIFY ticks through the pages on both queries, the emitted stream
against the reference's self-drafting loop and against one-token decoding,
a forced accept, a refused draft's dead entries, a chunked prompt's module
pages, and the shares of an expert layer. float32 on the CPU at a tiny size
(`index_topk` 8, pages of 4, contexts on both sides of it); logits are
compared at 1e-4 (both sides float32; they differ in the order of sums)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import glm_mtp_tiny as tiny
import latent_tiny
from llama_pipeline_parallel_tpu import serve
from llama_pipeline_parallel_tpu.models import family as families
from llama_pipeline_parallel_tpu.models.latent_moe import decode as latent_decode
from llama_pipeline_parallel_tpu.models.latent_moe import draft
from llama_pipeline_parallel_tpu.models.latent_moe import model as latent
from llama_pipeline_parallel_tpu.models.latent_moe.config import LatentMoEConfig

TOL = 1e-4
SLOTS, MAX_LEN, PAGE, PAGES = 2, 64, 4, 40
reference = tiny.reference


def _cache(cfg):
    return serve.PagedKVCache(cfg, SLOTS, MAX_LEN, PAGE, PAGES)


def _padded(prompt, bucket):
    pad = bucket - len(prompt)
    ids = np.zeros((1, bucket), np.int32)
    ids[0, pad:] = prompt
    mask = np.zeros((1, bucket), np.int32)
    mask[0, pad:] = 1
    positions = np.clip(np.cumsum(mask, axis=1) - 1, 0, None).astype(np.int32)
    return ids, mask, positions


def prefill(params, cfg, cache, slot, prompt, bucket, chunk):
    """The engine's admission by hand: whole (chunk 0) or in chunks, each
    chunk with the id that follows it."""
    ids, mask, positions = _padded(prompt, bucket)
    if not chunk:
        out = latent_decode.prefill_prompt(params, jnp.asarray(ids),
                                           jnp.asarray(mask), cfg, bucket)
        cache.admit(slot, out)
        return out
    cache.reset_mask_row(slot)
    for c0 in range(0, bucket, chunk):
        c1 = c0 + chunk
        cache.ensure_capacity(slot, c1)
        after = ids[0, c1:c1 + 1] if c1 < bucket else np.full(1, -1, np.int32)
        out = latent_decode.paged_prefill_chunk(
            params, jnp.asarray(ids[:, c0:c1]), jnp.asarray(mask[:, c0:c1]),
            jnp.asarray(positions[:, c0:c1]), cache.pool,
            jnp.asarray(cache.page_table[slot]), jnp.int32(slot),
            cache.kv_mask, jnp.int32(c0), cfg, next_id=jnp.asarray(after))
        cache.pool, cache.kv_mask = out["pool"], out["kv_mask"]
    return out


def admit(cache, params, cfg, slot, prompt, bucket, new, chunk=0):
    demand = cache.demand_pages(bucket, new + 2)
    assert cache.reserve(demand)
    assert cache.acquire(f"r{slot}", demand) == slot
    out = prefill(params, cfg, cache, slot, prompt, bucket, chunk)
    first = int(np.argmax(out["logits"][0]))
    # the row's first draft, as the engine asks for it: the first token where
    # `tick_io.first_token` would have written it
    prev = np.zeros(7 * SLOTS + 13, np.int32)
    prev[slot] = first
    cache.pool = draft.first_draft(
        params, out["hidden"], jnp.asarray(prev), cache.pool,
        jnp.asarray(cache.page_table[slot]), jnp.int32(slot), cache.kv_mask,
        jnp.int32(len(prompt) - 1), jnp.int32(bucket - 1), cfg)
    return {"seq": list(prompt) + [first], "prompt": len(prompt),
            "write": bucket, "out": out}


def tick(params, cfg, cache, rows, keys=None, temperature=0.0):
    """One verify tick over `rows` ({slot: row}): each row's last token at
    its place; the rows advance by what the tick emitted. Returns the tick's
    outputs as numpy."""
    token, pos, write, active = (np.zeros(SLOTS, np.int32) for _ in range(4))
    for slot, row in rows.items():
        token[slot], pos[slot] = row["seq"][-1], len(row["seq"]) - 1
        write[slot], active[slot] = row["write"], 1
        cache.ensure_capacity(slot, row["write"] + 2)
    out = latent_decode.paged_decode_step(
        params, jnp.asarray(token), cache.pool, jnp.asarray(cache.page_table),
        jnp.asarray(pos), jnp.asarray(write), cache.kv_mask,
        jnp.asarray(active),
        jnp.zeros((SLOTS, 2), jnp.uint32) if keys is None else keys,
        jnp.full((SLOTS,), temperature, jnp.float32),
        jnp.zeros((SLOTS,), jnp.int32), jnp.ones((SLOTS,), jnp.float32), cfg)
    cache.update_from_step(out)
    got = {k: np.asarray(v) for k, v in out.items()
           if k not in ("pool", "kv_mask", "selection", "mtp_selection")}
    for slot, row in rows.items():
        n = int(got["count"][slot])
        row["seq"] += got["tokens"][slot, :1].tolist() if n == 1 else \
            got["tokens"][slot].tolist()
        row["write"] += n
        assert got["pos"][slot] == len(row["seq"]) - 1
        assert got["write_pos"][slot] == row["write"]
    return got


# -- the configuration -------------------------------------------------------------

def test_the_published_keys_make_a_model_of_one_kind_that_drafts():
    cfg = tiny.config()
    assert cfg.period == ("full",) and cfg.periods == 2 and cfg.has_indexer
    assert cfg.drafts and cfg.num_nextn_predict_layers == 1
    assert (cfg.full_layers, cfg.page_depth, cfg.window_layers) == (3, 4, 0)
    assert cfg.rope_theta == 1e6 and cfg.rope_scaling is None
    assert not cfg.attention_gate and not cfg.lora_rescale
    assert cfg.v_head_dim == 12 and cfg.qk_nope_head_dim == 8
    assert cfg.routed_scaling_factor == 2.5 and cfg.held == 8
    fam = families.family_of(cfg)
    assert fam.drafts and fam.recurrent and fam.fetch_rows == 9
    assert fam.counters[-5:] == draft.COUNTERS
    plain = tiny.config({**tiny.MODEL, "num_nextn_predict_layers": 0})
    assert not plain.drafts and plain.page_depth == 3
    assert not families.family_of(plain).drafts
    assert families.family_of(plain).fetch_rows == 3
    # the other configurations of the family read as they did
    assert not latent_tiny.config().drafts


@pytest.mark.parametrize("key,value,says", [
    ("num_nextn_predict_layers", 2, "0 or 1"),
    ("first_k_dense_replace", 3, "exactly one leading dense layer"),
])
def test_what_the_family_does_not_run_is_refused_by_name(key, value, says):
    with pytest.raises(ValueError, match=says):
        tiny.config({**tiny.MODEL, key: value})


def test_a_module_needs_full_layers_under_an_indexer():
    with pytest.raises(ValueError, match="full layers under an indexer"):
        LatentMoEConfig.tiny(num_nextn_predict_layers=1)


def test_the_tree_is_the_one_init_params_makes():
    cfg = tiny.config()
    params = tiny.both_sides()[0]
    made = jax.eval_shape(
        lambda: latent.init_params(jax.random.PRNGKey(0), cfg))
    shapes = lambda tree: jax.tree.map(lambda x: (x.shape, x.dtype), tree)
    assert shapes(made) == shapes(params)
    assert set(params["mtp"]) == {"enorm", "hnorm", "eh_proj", "attn", "moe",
                                  "shared_head_norm"}
    assert "wg" not in params["mtp"]["attn"] and "wqi" in params["mtp"]["attn"]


# -- trunk and module over a prompt --------------------------------------------------

@pytest.mark.parametrize("chunk", [0, 8, 16])
def test_a_prompts_trunk_logits_and_module_pages_are_the_references(chunk):
    """The last logits are the reference's; the module's pages hold, for
    every prompt position (the last once the first token is drawn), the
    entry of u_i made from the reference's own hidden state and the id after
    it; the first draft is the reference's module's first choice."""
    cfg = tiny.config()
    params, top, layer_fn = tiny.both_sides()
    prompt = np.random.default_rng(5).integers(0, 128, 27).tolist()
    cache = _cache(cfg)
    row = admit(cache, params, cfg, 0, prompt, 32, 9, chunk)
    ref = reference.forward(top, layer_fn, [row["seq"]], tiny.MODEL)
    np.testing.assert_allclose(row["out"]["logits"][0], ref["logits"][0, -2],
                               atol=TOL)
    # the module's entries, from the reference's hidden states
    dm = reference.dims(tiny.MODEL)
    ids = jnp.asarray([row["seq"]], jnp.int32)
    u = reference.mtp_input(top["mtp"], top["embed"], ids, ref["hidden"], dm,
                            "float32")
    pr = latent.project(params["mtp"]["attn"], u,
                        jnp.arange(28, dtype=jnp.int32)[None], cfg.kind(False),
                        cfg)
    table = cache.page_table[0]
    held = np.asarray(cache.pool["latent"])[cfg.full_layers, table].reshape(
        MAX_LEN, -1)
    want = np.asarray(latent.stored(pr["entry"], cfg.latent_store_width))[0]
    np.testing.assert_allclose(held[5:5 + 27], want[:27], atol=TOL)
    np.testing.assert_allclose(row["out"]["hidden"][0], ref["hidden"][0, -2],
                               atol=TOL)
    assert int(cache.pool["mtp_draft"][0]) == int(
        np.argmax(ref["mtp_logits"][0, 26]))
    counted = dict(zip(families.family_of(cfg).counters,
                       np.asarray(row["out"]["counters"]).tolist()))
    if chunk in (0,):
        assert counted["mtp_positions"] == 26
    assert counted["spec_offered"] == counted["spec_tokens"] == 0


def test_a_chunked_prompts_module_cache_equals_a_whole_buckets():
    cfg = tiny.config()
    params = tiny.both_sides()[0]
    prompt = np.random.default_rng(6).integers(0, 128, 21).tolist()
    stores, positions = [], []
    for chunk in (0, 8):
        cache = _cache(cfg)
        out = admit(cache, params, cfg, 0, prompt, 32, 4, chunk)["out"]
        table = cache.page_table[0, :8]
        stores.append({name: np.asarray(cache.pool[name])[:, table]
                       for name in ("latent", "index")})
        stores[-1]["hidden"] = np.asarray(out["hidden"])[0]
        stores[-1]["draft"] = int(cache.pool["mtp_draft"][0])
        positions.append(int(np.asarray(out["counters"])[-1]))
    cut = lambda a: a.reshape(a.shape[0], 32, -1)[:, 11:]
    for name in ("latent", "index"):
        np.testing.assert_allclose(cut(stores[0][name]), cut(stores[1][name]),
                                   atol=1e-5)
    np.testing.assert_allclose(stores[0]["hidden"], stores[1]["hidden"],
                               atol=1e-5)
    assert stores[0]["draft"] == stores[1]["draft"] >= 0
    # a whole bucket counts its positions at once, the last chunk its own
    assert positions == [20, 7]


# -- prefill, then verify ticks ------------------------------------------------------

@pytest.mark.parametrize("chunk", [0, 8])
def test_prefill_then_verify_ticks_are_the_references_full_forward(chunk):
    """Two rows of different depth (one past `index_topk`, one left-padded
    and short) through eight ticks at a 16-id vocabulary: at every tick the
    first query's logits are the reference's at the row's last token, the
    second's the reference's with the draft appended, the module's the
    reference's module given the emitted tokens, the draft its argmax; and
    the counters are what the rows' depths give. The first tick already
    verifies a draft: `first_draft` left it."""
    model = tiny.SMALL_VOCAB
    cfg = tiny.config(model)
    params, top, layer_fn = tiny.both_sides(model)
    fam = families.family_of(cfg)
    rng = np.random.default_rng(7)
    cache = _cache(cfg)
    rows = {0: admit(cache, params, cfg, 0, rng.integers(0, 16, 27).tolist(),
                     32, 20, chunk),
            1: admit(cache, params, cfg, 1, rng.integers(0, 16, 3).tolist(),
                     16, 20, chunk)}
    fwd = lambda seq: reference.forward(top, layer_fn, [seq], model)
    for t in range(8):
        before = {slot: list(row["seq"]) for slot, row in rows.items()}
        got = tick(params, cfg, cache, rows)
        counted = dict(zip(fam.counters, got["counters"].tolist()))
        visible = selected = 0
        for slot, row in rows.items():
            seq, n = before[slot], len(before[slot])
            d = int(got["drafted"][slot])
            assert d >= 0
            np.testing.assert_allclose(got["logits"][slot, 0],
                                       fwd(seq)["logits"][0, -1], atol=TOL)
            queries = [n]
            if d >= 0:
                np.testing.assert_allclose(
                    got["logits"][slot, 1], fwd(seq + [d])["logits"][0, -1],
                    atol=TOL)
                queries.append(n + 1)
            y = int(np.argmax(got["logits"][slot, 0]))
            took = [y] + ([int(np.argmax(got["logits"][slot, 1]))]
                          if y == d else [])
            assert row["seq"] == seq + took
            # the module at the last positions whose next token is known
            now = row["seq"]
            ref = fwd(now)["mtp_logits"][0]
            valid = got["module_valid"][slot]
            assert valid.tolist() == [True, y == d]
            last = 1 if valid[1] else 0
            np.testing.assert_allclose(got["mtp_logits"][slot, last],
                                       ref[len(now) - 2], atol=TOL)
            if valid[1]:
                np.testing.assert_allclose(got["mtp_logits"][slot, 0],
                                           ref[len(now) - 3], atol=TOL)
            assert int(got["draft"][slot]) == int(np.argmax(ref[len(now) - 2]))
            # what the indexer saw: both queries in the trunk's layers, the
            # module's positions in its own
            module_at = [len(now) - 1 - j for j in range(int(valid.sum()))]
            for places, layers in ((queries, cfg.full_layers), (module_at, 1)):
                for q in places:
                    visible += q * layers
                    selected += min(q, cfg.index_topk) * layers
        assert counted["index_visible"] == visible
        assert counted["index_selected"] == selected
        offered = 2
        assert counted["spec_offered"] == offered
        assert counted["spec_tokens"] == 2 + counted["spec_accepted"]
        assert counted["spec_dead_entries"] == (
            offered - counted["spec_accepted"]) * cfg.full_layers
        assert counted["mtp_positions"] == 2 + counted["spec_accepted"]
        assert counted["routed_total"] == cfg.num_experts_per_tok * (
            (2 + offered) * cfg.expert_layers + counted["mtp_positions"])


def test_the_stream_is_the_references_self_drafting_loop_and_one_token_decoding():
    model = tiny.SMALL_VOCAB
    cfg = tiny.config(model)
    params, top, layer_fn = tiny.both_sides(model)
    prompt = np.random.default_rng(30).integers(0, 16, 11).tolist()
    cache = _cache(cfg)
    rows = {0: admit(cache, params, cfg, 0, prompt, 16, 14)}
    counts = []
    while len(rows[0]["seq"]) - 11 < 14:
        counts.append(int(tick(params, cfg, cache, rows)["count"][0]))
    served = rows[0]["seq"][11:][:14]
    loop = reference.self_draft(top, layer_fn, prompt, 14, model)
    plain = reference.self_draft(top, layer_fn, prompt, 14, model,
                                 drafting=False)
    assert served == loop["tokens"] == plain["tokens"]
    # a budget that ends on the first of two tokens leaves the second unseen
    # by the loop; every tick before it is one of the loop's steps
    assert [c == 2 for c in counts][:-1] == loop["accepted"][:-1]
    assert any(loop["accepted"])


def test_a_forced_accept_emits_two_tokens_and_advances_position_and_key_by_two():
    """The row's stored draft set to the reference's own next token: the tick
    emits that token and the one after it, both the reference's; position
    and place advance by two and the key by two splits."""
    cfg = tiny.config()
    params, top, layer_fn = tiny.both_sides()
    prompt = np.random.default_rng(9).integers(0, 128, 13).tolist()
    cache = _cache(cfg)
    rows = {0: admit(cache, params, cfg, 0, prompt, 16, 8)}
    seq = list(rows[0]["seq"])
    logits = reference.logits_fn(top, layer_fn, [seq], tiny.MODEL)[0, -1]
    want = int(np.argmax(logits))
    cache.pool = {**cache.pool,
                  "mtp_draft": cache.pool["mtp_draft"].at[0].set(want)}
    after = int(np.argmax(reference.logits_fn(
        top, layer_fn, [seq + [want]], tiny.MODEL)[0, -1]))
    keys = jnp.asarray(np.stack([np.asarray(jax.random.PRNGKey(11)),
                                 np.zeros(2, np.uint32)]))
    write = rows[0]["write"]
    got = tick(params, cfg, cache, rows, keys=keys)
    assert got["count"][0] == 2 and got["drafted"][0] == want
    assert got["tokens"][0].tolist() == [want, after] == rows[0]["seq"][-2:]
    assert got["token"][0] == after
    assert got["pos"][0] == len(seq) + 1 and got["write_pos"][0] == write + 2
    once = jax.random.split(jax.random.PRNGKey(11))[0]
    twice = jax.random.split(once)[0]
    assert got["keys"][0].tolist() == np.asarray(twice).tolist()
    assert np.asarray(cache.kv_mask)[0, write:write + 3].tolist() == [1, 1, 0]
    # a refused one: one token, one key, the mask's place stays dark
    cache.pool = {**cache.pool, "mtp_draft": cache.pool["mtp_draft"].at[0].set(
        (int(np.argmax(got["logits"][0, 1])) + 1) % 128)}
    write = rows[0]["write"]
    got = tick(params, cfg, cache, rows, keys=keys)
    assert got["count"][0] == 1 and got["pos"][0] == len(seq) + 2
    assert got["keys"][0].tolist() == np.asarray(once).tolist()
    assert np.asarray(cache.kv_mask)[0, write:write + 2].tolist() == [1, 0]


def test_a_refused_drafts_entries_never_reach_a_later_read():
    """Two copies of a row, one whose draft is always refused (its entries
    written behind the row's own and dropped), one that never has a draft to
    write: every later tick's logits are the same, and the reference's."""
    cfg = tiny.config()
    params, top, layer_fn = tiny.both_sides()
    prompt = np.random.default_rng(10).integers(0, 128, 9).tolist()
    logits = []
    for wrong in (True, False):
        cache = _cache(cfg)
        rows = {0: admit(cache, params, cfg, 0, prompt, 16, 12)}
        seen = []
        for t in range(6):
            if t:
                best = int(np.argmax(seen[-1]))
                cache.pool = {**cache.pool, "mtp_draft": cache.pool[
                    "mtp_draft"].at[0].set(
                        (best + 1 + t) % 128 if wrong else draft.NO_DRAFT)}
            got = tick(params, cfg, cache, rows)
            assert got["count"][0] == 1
            seen.append(got["logits"][0, 0])
        logits.append(np.stack(seen))
        ref = reference.logits_fn(top, layer_fn, [rows[0]["seq"][:-1]],
                                  tiny.MODEL)[0, -1]
        np.testing.assert_allclose(seen[-1], ref, atol=TOL)
    np.testing.assert_allclose(logits[0], logits[1], atol=1e-6)


# -- the shares of an expert layer ----------------------------------------------------

def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_reference():
    """The two chips that each hold eight of the sixteen experts: their
    routed terms, the shared expert counted once, are the uncut layer's."""
    model = tiny.MODEL
    uncut = {**model, "n_routed_experts": 16, "router_experts": 16,
             "expert_offset": 0}
    dm_full = reference.dims(uncut)
    layer = tiny.weights.make_layer(tiny.SEED, 1, uncut)["moe"]
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 9, 32), jnp.float32)
    whole = reference.moe_layer(layer, h, dm_full, "float32")
    total = jnp.zeros_like(h)
    for offset in (0, 8):
        share = {**{k: v for k, v in layer.items()},
                 **{k: layer[k][offset:offset + 8]
                    for k in ("gate", "up", "down")}}
        dm = reference.dims({**uncut, "n_routed_experts": 8,
                             "expert_offset": offset})
        total = total + reference.moe_layer(share, h, dm, "float32",
                                            shared=offset == 0)
    np.testing.assert_allclose(total, whole, atol=1e-5)
    # and the program's own layer computes its share
    cfg = tiny.config({**uncut, "n_routed_experts": 8, "expert_offset": 8})
    from llama_pipeline_parallel_tpu.models.hybrid_moe import model as hybrid

    held = {k: layer[k][8:16][None] for k in ("gate", "up", "down")}
    rest = {"post_norm": jnp.ones((32,)), **{
        k: layer[k] for k in ("router", "router_bias", "shared_gate",
                              "shared_up", "shared_down")}}
    out, _ = hybrid.moe_block(rest, held, 0, h, jnp.ones((1, 9), bool), cfg)
    normed = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-5)
    dm = reference.dims({**uncut, "n_routed_experts": 8, "expert_offset": 8})
    want = reference.moe_layer(
        {**layer, **{k: layer[k][8:16] for k in ("gate", "up", "down")}},
        normed, dm, "float32")
    np.testing.assert_allclose(out - h, want, atol=TOL)
