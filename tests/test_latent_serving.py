"""The latent-attention block served through the normal path: `ServeEngine`
/ `PagedKVCache` take its programs from `models/family.py`; whole and chunked
prefill, then decode, through all three stores (latent pages, index pages,
the per-slot ring) are the plain reference's full forward; every store stays
in place in the traced programs; what it cannot run yet is refused by name;
a checkpoint of the family loads through the loader tools/serve.py uses.
float32 on the CPU at a tiny size (`index_topk` 8, a window of 5, a ring of
6, pages of 4, contexts on both sides of each); logits are compared with the
reference's at 1e-4 (both sides float32; they differ in the order of sums)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_tiny
import latent_tiny as tiny
import mla_tiny
import tick_ahead
from llama_pipeline_parallel_tpu import serve
from llama_pipeline_parallel_tpu.models import family as families
from llama_pipeline_parallel_tpu.models.latent_moe import decode as latent_decode
from llama_pipeline_parallel_tpu.models.latent_moe import model as latent
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.serve import pages
from llama_pipeline_parallel_tpu.utils import trace

TOL = 1e-4
SLOTS, MAX_LEN, PAGE, PAGES = 2, 64, 4, 40


def _cache(cfg):
    return serve.PagedKVCache(cfg, SLOTS, MAX_LEN, PAGE, PAGES)


def test_the_manager_holds_two_kinds_of_page_and_a_ring_a_slot():
    cfg = tiny.config()
    cache = _cache(cfg)
    # entries of 12 and 16 numbers, stored in rows of 16 (whole tiles of 8)
    assert cache.pool["latent"].shape == (3, PAGES + 1, PAGE, 16)
    assert cache.pool["index"].shape == (3, PAGES + 1, PAGE, 8)
    assert cache.pool["ring"].shape == (6, SLOTS, 6, 16)
    assert cache._page_leaves == ("latent", "index")
    assert cache.recurrent_store_bytes == cache.pool["ring"].nbytes
    # a page is priced by the family's own page leaves
    assert cache.page_bytes() == 3 * PAGE * (16 + 8) * 4
    assert pages.paged_pool_bytes(cfg, PAGES, PAGE) == (
        cache.pool["latent"].nbytes + cache.pool["index"].nbytes)
    assert pages.dense_kv_cache_bytes(cfg, SLOTS, MAX_LEN) == \
        SLOTS * MAX_LEN * 3 * (16 + 8) * 4


def test_the_other_families_pages_are_priced_as_they_always_were():
    """`paged_pool_bytes`, `page_bytes` and `dense_kv_cache_bytes` read the
    family's own pool leaves; for the dense and the hybrid family that is 2
    x layers that keep keys and values x KV heads x head size, as before."""
    dense = LlamaConfig.tiny()
    size = jnp.dtype(dense.dtype).itemsize
    per_token = 2 * dense.num_hidden_layers * dense.kv_heads * dense.head_dim
    assert pages.dense_kv_cache_bytes(dense, 3, 32) == 3 * 32 * per_token * size
    assert pages.paged_pool_bytes(dense, 10, 8) == 11 * 8 * per_token * size
    assert pages.paged_pool_bytes(dense, 10, 8, "int8") == (
        11 * 8 * per_token
        + 2 * dense.num_hidden_layers * 11 * dense.kv_heads * 4)
    hybrid = hybrid_tiny.config()
    per_token = 2 * hybrid.periods * hybrid.kv_heads * hybrid.head_dim
    assert pages.paged_pool_bytes(hybrid, 12, 8) == 13 * 8 * per_token * 4
    assert pages.dense_kv_cache_bytes(hybrid, 2, 48) == 2 * 48 * per_token * 4
    cache = serve.PagedKVCache(hybrid, 2, 48, 8, 12)
    assert cache.page_bytes() == 8 * per_token * 4
    assert cache.recurrent_store_bytes == (cache.pool["state"].nbytes
                                           + cache.pool["conv"].nbytes)


def _padded(prompt, bucket):
    pad = bucket - len(prompt)
    ids = np.zeros((1, bucket), np.int32)
    ids[0, pad:] = prompt
    mask = np.zeros((1, bucket), np.int32)
    mask[0, pad:] = 1
    positions = np.clip(np.cumsum(mask, axis=1) - 1, 0, None).astype(np.int32)
    return ids, mask, positions


def _prefill(params, cfg, cache, slot, prompt, bucket, chunk):
    """The engine's admission by hand: whole (chunk 0) or in chunks."""
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
        out = latent_decode.paged_prefill_chunk(
            params, jnp.asarray(ids[:, c0:c1]), jnp.asarray(mask[:, c0:c1]),
            jnp.asarray(positions[:, c0:c1]), cache.pool,
            jnp.asarray(cache.page_table[slot]), jnp.int32(slot),
            cache.kv_mask, jnp.int32(c0), cfg)
        cache.pool, cache.kv_mask = out["pool"], out["kv_mask"]
    return out


def _serve_the_plan(family, chunk, counted):
    """Four requests over two slots through `family`'s (a `*_tiny` module)
    programs: a prompt longer than `index_topk` and the window, one shorter
    than both and left-padded past whole chunks, one admitted into the slot
    the first left (nothing of the last occupant's ring or pages may be
    visible), admitted at different ticks; rows of up to 50 positions. At
    every tick the logits of every decoding row are the reference's full
    forward over that request's tokens so far, and the full layers' own
    counters are `counted(rows)`."""
    cfg = family.config()
    params, top, layer_fn = family.both_sides()
    cache = _cache(cfg)
    tick = jax.jit(latent_decode.tick_logits, static_argnames=("cfg",))
    rng = np.random.default_rng(4)
    plan = [  # (admit at tick, slot, prompt, bucket, new tokens)
        (0, 0, rng.integers(0, 128, 27).tolist(), 32, 9),
        (3, 1, rng.integers(0, 128, 3).tolist(), 16, 30),
        (12, 0, rng.integers(0, 128, 9).tolist(), 16, 20),
        (34, 0, rng.integers(0, 128, 14).tolist(), 16, 4)]
    rows, done = {}, []
    for t in range(40):
        for at, slot, prompt, bucket, new in plan:
            if at != t:
                continue
            assert cache.reserve(cache.demand_pages(bucket, new))
            assert cache.acquire(f"r{at}", cache.demand_pages(bucket, new)) == slot
            out = _prefill(params, cfg, cache, slot, prompt, bucket, chunk)
            rows[slot] = {"prompt": prompt, "seq": list(prompt),
                          "logits": [np.asarray(out["logits"][0])],
                          "left": new - 1, "write": bucket}
            rows[slot]["seq"].append(int(np.argmax(out["logits"][0])))
        if not rows:
            continue
        token, write, pos, active = (np.zeros(SLOTS, np.int32) for _ in range(4))
        for slot, r in rows.items():
            token[slot], write[slot], active[slot] = r["seq"][-1], r["write"], 1
            pos[slot] = len(r["seq"]) - 1
            cache.ensure_capacity(slot, r["write"] + 1)
        logits, cache.pool, cache.kv_mask, counters, _ = tick(
            params, jnp.asarray(token), cache.pool,
            jnp.asarray(cache.page_table), jnp.asarray(pos),
            jnp.asarray(write), cache.kv_mask, jnp.asarray(active), cfg)
        assert counters.tolist()[6:] == counted(
            [len(r["seq"]) for r in rows.values()])
        for slot in list(rows):
            r = rows[slot]
            r["logits"].append(np.asarray(logits[slot]))
            r["seq"].append(int(np.argmax(logits[slot])))
            r["write"] += 1
            r["left"] -= 1
            if r["left"] == 0:
                done.append(rows.pop(slot))
                cache.release(slot)
    assert len(done) == 4 and not rows
    for r in done:
        ids = jnp.asarray([r["seq"][:-1]])
        want = family.reference.logits_fn(top, layer_fn, ids, family.MODEL)[0]
        first = len(r["prompt"]) - 1
        got = np.stack(r["logits"])
        np.testing.assert_allclose(got, want[first:first + len(got)], atol=TOL)


@pytest.mark.parametrize("chunk", [0, 4, 8, 16],
                         ids=["whole", "chunk4", "chunk8", "chunk16"])
def test_prefill_then_ticks_through_the_three_stores_are_the_reference(chunk):
    """`_serve_the_plan` through latent pages, index pages and the ring:
    contexts pass `index_topk`, the window and the ring (which wraps more
    than once: 6 places, rows of up to 50); three full layers see every
    context and select at most 8 of it."""
    _serve_the_plan(tiny, chunk, lambda contexts: [
        3 * sum(contexts), 3 * sum(min(c, 8) for c in contexts)])


def test_a_chunk_of_nothing_but_pads_changes_nothing_a_query_can_see():
    """The engine left-pads to the bucket and starts at the first chunk that
    holds a token (tests/test_pad_chunks_skipped.py), and may: a chunk
    before it counts nothing, routes nothing and leaves the slot's mask row
    empty."""
    cfg = tiny.config()
    params, _, _ = tiny.both_sides()
    cache = _cache(cfg)
    assert cache.reserve(4) and cache.acquire("r", 4) == 0
    ids, mask, positions = _padded([5, 6, 7], 16)
    cache.reset_mask_row(0)
    cache.ensure_capacity(0, 8)
    out = latent_decode.paged_prefill_chunk(
        params, jnp.asarray(ids[:, :8]), jnp.asarray(mask[:, :8]),
        jnp.asarray(positions[:, :8]), cache.pool,
        jnp.asarray(cache.page_table[0]), jnp.int32(0), cache.kv_mask,
        jnp.int32(0), cfg)
    # no held expert has a row: the grouped products' grids are empty
    assert out["counters"].tolist() == [0, 0, 0, 0, 8 * 8, 0, 0, 0]
    assert int(jnp.sum(out["kv_mask"])) == 0
    assert bool(jnp.all(jnp.isfinite(out["logits"])))


def test_the_engine_serves_the_family_in_chunks_with_its_counters_on_the_spans():
    """Buckets of 8 (whole), 16 and 32 (chunks of 8 between decode ticks)
    through `ServeEngine`: every served token is the reference's first
    choice; the expert layers' and the indexer's counts ride the tick's and
    every prefill unit's span, and are the host's own counts exactly."""
    cfg = tiny.config()
    params, top, layer_fn = tiny.both_sides()
    scfg = serve.ServeConfig(max_slots=SLOTS, max_len=MAX_LEN,
                             prompt_buckets=(8, 16, 32), page_size=PAGE,
                             num_pages=PAGES, decode_span_every=4,
                             prefill_chunk_tokens=8)
    engine = serve.ServeEngine(params, cfg, scfg)
    spans = []
    listener = lambda rec: spans.append(dict(rec))
    trace.recorder().add_listener(listener)
    try:
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 128, n).tolist() for n in (5, 27, 3, 14, 30)]
        budgets = [9, 17, 6, 12, 5]
        handles = []
        for i, (prompt, n) in enumerate(zip(prompts, budgets)):
            handles.append(engine.submit(serve.ServeRequest(
                input_ids=prompt, seed=i,
                gen=families.GenerationConfig(max_new_tokens=n))))
            engine.step()
        engine.drain()
        engine._flush_decode_span()
    finally:
        trace.recorder().remove_listener(listener)
    served = [h.result() for h in handles]
    assert [len(s) for s in served] == budgets
    gaps = tiny.reference.served_token_gaps(top, layer_fn, prompts, served,
                                            tiny.MODEL, MAX_LEN)
    assert max(max(g) for g in gaps) <= TOL
    assert engine.slots.reused_slot_count() >= 1
    assert engine.prefill_chunks_total == 1 + 4 + 1 + 2 + 4

    ticks = [s for s in spans if s["name"] == "serve_decode_step"]
    units = [s for s in spans if s["name"] == "serve_prefill"]
    assert len(units) == 12
    assert all(set(latent_decode.counters(cfg)) <= set(s) for s in ticks + units)
    total = {k: sum(s[k] for s in ticks) for k in latent_decode.counters(cfg)}
    decoded = sum(n - 1 for n in budgets)
    assert sum(s["tokens"] for s in ticks) == decoded
    # exact: every decoding token chooses 4 experts in each of 8 expert
    # layers, and sees its whole context in each of 3 full layers, of which
    # it selects at most 8
    assert total["routed_total"] == decoded * 4 * 8
    contexts = [len(p) + j for p, n in zip(prompts, budgets)
                for j in range(1, n)]
    assert total["index_visible"] == 3 * sum(contexts)
    assert total["index_selected"] == 3 * sum(min(c, 8) for c in contexts)
    # a prompt's tokens, whatever the units they came in
    prefilled = {k: sum(s[k] for s in units) for k in latent_decode.counters(cfg)}
    assert prefilled["routed_total"] == sum(len(p) for p in prompts) * 4 * 8
    assert prefilled["index_visible"] == 3 * sum(
        t + 1 for p in prompts for t in range(len(p)))
    assert prefilled["index_selected"] == 3 * sum(
        min(t + 1, 8) for p in prompts for t in range(len(p)))
    assert all(0 < s["kv_pages_live"] <= s["kv_pages_table"] for s in ticks)


@pytest.mark.parametrize("model", ["dots3", "a.x-k1"])
def test_a_tick_in_flight_serves_both_models_as_the_serial_order_does(model):
    """Buckets of 8 (whole), 16 and 32 (chunks of 8 between decode ticks),
    greedy and sampled rows, one ended by its eos, with the engine's tick in
    flight and in the serial order (`tests/tick_ahead.py`): the same
    streams, bit for bit; the counters that are exact a row (the experts
    chosen, the positions seen or selected) are the host's own count over
    every row-tick the device ran, the overrun among them."""
    which = tiny if model == "dots3" else mla_tiny
    cfg = which.config()
    params = which.both_sides()[0]
    scfg = serve.ServeConfig(max_slots=SLOTS, max_len=MAX_LEN,
                             prompt_buckets=(8, 16, 32), page_size=PAGE,
                             num_pages=2 * PAGES, decode_span_every=4,
                             prefill_chunk_tokens=8)
    make = lambda: serve.ServeEngine(params, cfg, scfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 128, n).tolist() for n in (5, 27, 3, 14)]
    budgets = [9, 12, 2, 10]
    knobs = [{}, dict(temperature=0.8), {}, dict(temperature=1.1, top_p=0.9)]
    plain = tick_ahead.run(make(), tick_ahead.requests_of(
        prompts, budgets, knobs), serially=True)["tokens"]
    assert [len(t) for t in plain] == budgets
    eos = {1: tick_ahead.eos_of(plain[1])[1]}
    serial, ahead = tick_ahead.both_orders(
        make, lambda: tick_ahead.requests_of(prompts, budgets, knobs, eos))
    assert ahead["sums"]["rows_overrun"] == 1
    assert ahead["tokens"][1][-1] == eos[1]
    assert [len(t) for t in ahead["tokens"]] == [
        9, len(ahead["tokens"][1]), 2, 10]
    expert_layers = 8 if model == "dots3" else 4
    for result, overran in ((serial, ()), (ahead, (1,))):
        contexts = tick_ahead.contexts_run(result, prompts, overran)
        sums = result["sums"]
        assert sums["tokens"] == len(contexts)
        assert sums["routed_total"] == len(contexts) * 4 * expert_layers
        if model == "dots3":
            assert sums["index_visible"] == 3 * sum(contexts)
            assert sums["index_selected"] == 3 * sum(min(c, 8)
                                                     for c in contexts)
        else:
            assert sums["latent_visible"] == 5 * sum(contexts)


# -- structure of the traced programs -------------------------------------------

def _shapes(cfg, pages=PAGES):
    params = jax.eval_shape(lambda: latent.init_params(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), params)
    pool = {**latent_decode.init_page_pool(cfg, pages, PAGE),
            **latent_decode.init_recurrent_store(cfg, SLOTS)}
    return params, pool


def _tick_args(cfg, pages=PAGES):
    params, pool = _shapes(cfg, pages)
    z = jnp.zeros((SLOTS,), jnp.int32)
    return pool, (
        params, z, pool, jnp.zeros((SLOTS, MAX_LEN // PAGE), jnp.int32), z, z,
        jnp.zeros((SLOTS, MAX_LEN), jnp.int32), z,
        jnp.zeros((SLOTS, 2), jnp.uint32), jnp.zeros((SLOTS,), jnp.float32),
        z, jnp.ones((SLOTS,), jnp.float32))


def _chunk_args(cfg, pages=PAGES, C=8):
    params, pool = _shapes(cfg, pages)
    ids = jnp.zeros((1, C), jnp.int32)
    return pool, (
        params, ids, ids, ids, pool, jnp.zeros((MAX_LEN // PAGE,), jnp.int32),
        jnp.int32(0), jnp.zeros((SLOTS, MAX_LEN), jnp.int32), jnp.int32(0))


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for v in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


PROGRAMS = {"tick": (latent_decode.paged_decode_step, _tick_args),
            "chunk": (latent_decode.paged_prefill_chunk, _chunk_args)}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_every_store_rides_the_period_loops_carry(program):
    """As tests/test_pool_walk.py holds the dense pool: latent pages, index
    pages and the ring are carried through the loop over periods whole,
    never its `xs` / `ys` (which a donated argument cannot alias)."""
    cfg = tiny.config()
    fn, make = PROGRAMS[program]
    pool, args = make(cfg)
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, cfg))(*args).jaxpr
    leaves = {name: (leaf.shape, leaf.dtype) for name, leaf in pool.items()}
    loops = [e for e in _equations(jaxpr) if e.primitive.name == "scan"
             and e.params["length"] == cfg.periods
             and leaves["ring"][0] in {v.aval.shape for v in e.invars}]
    assert len(loops) == 1
    loop = loops[0]
    n_consts, n_carry = loop.params["num_consts"], loop.params["num_carry"]
    carried = [(v.aval.shape, v.aval.dtype)
               for v in loop.invars[n_consts:n_consts + n_carry]]
    scanned = {v.aval.shape for v in loop.invars[n_consts + n_carry:]}
    stacked = {v.aval.shape for v in loop.outvars[n_carry:]}
    for name, leaf in leaves.items():
        assert leaf in carried, name
        assert leaf[0] not in scanned and leaf[0] not in stacked, name


@pytest.mark.parametrize("program", sorted(PROGRAMS))
@pytest.mark.parametrize("model", ["dots3", "a.x-k1"])
def test_the_grouped_products_take_the_stack_of_periods_whole(model, program):
    """The alarm for the slice coming back (on the chip a slice of the
    stacked experts in front of the grouped product's kernel is a copy of a
    layer's experts, every product: PERF.md, PR 33; the kernel is
    `ops/grouped_matmul.py`'s `pallas_call` since PR 43, and its right
    operand is still the stored leaf seen as periods x held experts): in the tick and the chunk of both tiny
    models (two periods of four layers, four of one), every grouped
    product's right operand leads with periods x held experts, and no
    equation inside or outside the loop over periods makes an array of one
    layer's expert shape."""
    cfg = {"dots3": tiny, "a.x-k1": mla_tiny}[model].config()
    assert cfg.periods == {"dots3": 2, "a.x-k1": 4}[model]
    fn, make = PROGRAMS[program]
    _, args = make(cfg)
    eqns = list(_equations(jax.make_jaxpr(lambda *a: fn(*a, cfg))(*args).jaxpr))
    products, sliced = hybrid_tiny.expert_operands(eqns, cfg)
    assert products == [cfg.periods * cfg.held] * 3 * len(cfg.period)
    assert not sliced, sliced


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_the_programs_outputs_alias_the_donated_stores(program):
    cfg = tiny.config()
    fn, make = PROGRAMS[program]
    pool, args = make(cfg, pages=2048)
    analysis = fn.lower(*args, cfg).compile().memory_analysis()
    if analysis is None:
        pytest.skip("this backend reports no memory analysis")
    assert analysis.alias_size_in_bytes >= sum(x.nbytes for x in pool.values())


def test_the_tick_reads_the_chosen_entries_through_the_table():
    """No gather of the slots' whole latent rows [S, Pmax, page, w] in the
    tick: the index keys' rows are gathered whole (they are what is scored),
    the entries by the places the selection chose."""
    cfg = tiny.config()
    pool, args = _tick_args(cfg)
    jaxpr = jax.make_jaxpr(
        lambda *a: latent_decode.paged_decode_step(*a, cfg))(*args).jaxpr
    gathers = [tuple(e.outvars[0].aval.shape) for e in _equations(jaxpr)
               if e.primitive.name == "gather"]
    rows = (SLOTS, MAX_LEN // PAGE, PAGE)
    assert rows + (8,) in gathers                    # index keys
    assert rows + (16,) not in gathers               # never the entries
    assert (SLOTS, cfg.index_topk, 16) in gathers    # the chosen ones


# -- what cannot run yet ----------------------------------------------------------

@pytest.mark.parametrize("knobs,named", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(kv_quant="int8"), "kv_quant: int8"),
])
def test_what_the_family_cannot_run_is_refused_by_name(knobs, named):
    cfg = tiny.config()
    params = jax.eval_shape(lambda: latent.init_params(jax.random.PRNGKey(0), cfg))
    base = dict(max_slots=SLOTS, max_len=MAX_LEN, prompt_buckets=(8, 16),
                page_size=PAGE, num_pages=PAGES, prefill_chunk_tokens=8)
    with pytest.raises(families.UnsupportedForFamily, match=named) as err:
        serve.ServeEngine(params, cfg, serve.ServeConfig(**{**base, **knobs}))
    assert "latent_moe" in str(err.value) and "one row a slot" in str(err.value)
    assert "prefill_chunk_tokens" not in str(err.value)


def test_a_store_a_slot_and_chunked_prefill_are_separate_facts():
    """The latent family keeps a per-slot store AND prefills in chunks; the
    hybrid family keeps one and cannot yet; neither has a span prefill."""
    fam = families.family_of(tiny.config())
    assert fam.recurrent and fam.paged_prefill_span is None
    assert fam.paged_prefill_chunk is latent_decode.paged_prefill_chunk
    assert fam.counters == latent_decode.counters(tiny.config())
    assert fam.counters[:6] == families.family_of(hybrid_tiny.config()).counters
    other = families.family_of(hybrid_tiny.config())
    assert other.recurrent and other.paged_prefill_chunk is None
    fam.check_serve_config("fp", 8, False)
    with pytest.raises(families.UnsupportedForFamily, match="chunk to chunk"):
        other.check_serve_config("fp", 8, False)


def test_the_trainer_refuses_the_family_by_name():
    from llama_pipeline_parallel_tpu import train

    with pytest.raises(NotImplementedError, match="latent_moe"):
        train.build_model_config({"family": "latent_moe", "hidden_size": 32})
    node = {"_target_": "llama_pipeline_parallel_tpu.models.latent_moe."
                        "config.LatentMoEConfig.tiny"}
    with pytest.raises(NotImplementedError, match="latent_moe"):
        train.build_model_config(node)


def test_the_engine_names_no_familys_functions():
    import inspect

    from llama_pipeline_parallel_tpu.serve import engine

    for module in (engine, pages):
        assert "models.latent_moe" not in inspect.getsource(module)


# -- the checkpoint ----------------------------------------------------------------

def test_a_checkpoint_of_the_family_round_trips_into_the_serving_loader(tmp_path):
    from llama_pipeline_parallel_tpu.ckpt.checkpoint import (
        CheckpointManager,
        load_module_checkpoint,
    )

    cfg = tiny.config(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = latent.init_params(jax.random.PRNGKey(5), cfg)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save_module(3, params, cfg)
    meta = mgr.load_meta(3)
    assert meta["model_config"]["family"] == "latent_moe"
    loaded, loaded_cfg, _, step = load_module_checkpoint(str(tmp_path))
    assert step == 3 and loaded_cfg == cfg
    assert dataclasses.asdict(loaded_cfg) == dataclasses.asdict(cfg)
    flat, tree = jax.tree.flatten(params)
    flat_loaded, tree_loaded = jax.tree.flatten(loaded)
    assert tree == tree_loaded
    for a, b in zip(flat, flat_loaded):
        assert a.dtype == b.dtype          # bfloat16 stays bfloat16
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    engine = serve.ServeEngine(loaded, loaded_cfg, serve.ServeConfig(
        max_slots=SLOTS, max_len=MAX_LEN, prompt_buckets=(8, 16),
        page_size=PAGE, num_pages=PAGES, prefill_chunk_tokens=8))
    handle = engine.submit(serve.ServeRequest(
        input_ids=list(range(1, 12)),
        gen=families.GenerationConfig(max_new_tokens=3)))
    engine.drain()
    assert len(handle.result()) == 3


# -- dots3's programs compute what they computed before the family was generalised --

# the parent's values (commit 2b9ffa3: `latent_tiny`'s model and seed, 32
# tokens drawn from default_rng(5), chunks of 8 into pages of 4, then three
# greedy ticks), pinned before the refactor of PR 32
PINNED_CHUNK_LOGITS = [-0.414769, -0.44767556, 0.11468346, -0.24117672,
                       0.3280143, 0.72549796, -0.528478, 0.61742216]
PINNED_TICK_LOGITS = [
    [-0.68526167, -0.19654357, 0.05581874, 0.5419101, -1.7161855, -1.9601107,
     -0.23342179, 0.42097023],
    [-0.36830336, 0.07497703, 0.22573265, -1.100463, 0.22639161, -0.9812339,
     -0.9537863, 0.41808766],
    [1.2205951, 0.2876654, 0.49328655, -0.28067613, -0.7199126, -0.4838161,
     1.3023771, -0.29100752]]
# re-pinned at PR 43, which put `expert_visits` sixth (equal to `experts_hit`
# here: a tick's, and an 8-token chunk's, sorted rows are one row tile); the
# logits were not: in float32 the grouped kernel differs from `ragged_dot`
# by the order of its sums alone, inside the 2e-6 these were held to
PINNED_TICK_COUNTERS = [[32, 15, 15, 8, 64, 15, 99, 24],
                        [32, 16, 16, 8, 64, 16, 102, 24],
                        [32, 16, 16, 8, 64, 16, 105, 24]]


def _one_row_tick(tick, params, cfg, cache, slot, token, write, pos):
    token_a, write_a, pos_a, active = (np.zeros(SLOTS, np.int32)
                                       for _ in range(4))
    token_a[slot], write_a[slot], pos_a[slot], active[slot] = (
        token, write, pos, 1)
    cache.ensure_capacity(slot, write + 1)
    logits, cache.pool, cache.kv_mask, counters, _ = tick(
        params, jnp.asarray(token_a), cache.pool,
        jnp.asarray(cache.page_table), jnp.asarray(pos_a),
        jnp.asarray(write_a), cache.kv_mask, jnp.asarray(active), cfg)
    return np.asarray(logits[slot]), counters.tolist()


def test_dots3s_tiny_programs_give_the_logits_they_gave():
    cfg = tiny.config()
    params = tiny.weights.make_program_weights(tiny.SEED, tiny.MODEL, jnp.float32)
    prompt = np.random.default_rng(5).integers(0, 128, 32).tolist()
    cache = _cache(cfg)
    assert cache.reserve(cache.demand_pages(32, 4))
    slot = cache.acquire("pinned", cache.demand_pages(32, 4))
    out = _prefill(params, cfg, cache, slot, prompt, 32, 8)
    np.testing.assert_allclose(out["logits"][0, :8], PINNED_CHUNK_LOGITS,
                               atol=2e-6, rtol=0)
    assert out["counters"].tolist() == [256, 118, 49, 37, 64, 49, 684, 192]
    tick = jax.jit(latent_decode.tick_logits, static_argnames=("cfg",))
    token = int(np.argmax(out["logits"][0]))
    for t in range(3):
        logits, counters = _one_row_tick(tick, params, cfg, cache, slot, token,
                                         32 + t, 32 + t)
        np.testing.assert_allclose(logits[:8], PINNED_TICK_LOGITS[t],
                                   atol=2e-6, rtol=0)
        assert counters == PINNED_TICK_COUNTERS[t]
        token = int(np.argmax(logits))


# -- one kind of layer (A.X-K1's shape) through the same stores and engine ----------



def test_a_model_of_one_kind_of_layer_keeps_latent_pages_and_nothing_else():
    cfg = mla_tiny.config()
    cache = _cache(cfg)
    # entries of 8 + 8 numbers, stored in rows of 16; no index leaf, no ring
    assert set(cache.pool) == {"latent"}
    assert cache.pool["latent"].shape == (5, PAGES + 1, PAGE, 16)
    assert cache._page_leaves == ("latent",)
    assert cache.recurrent_store_bytes == 0
    assert cache.page_bytes() == 5 * PAGE * 16 * 4
    assert pages.paged_pool_bytes(cfg, PAGES, PAGE) == cache.pool["latent"].nbytes
    fam = families.family_of(cfg)
    assert not fam.recurrent and fam.init_recurrent_store is None
    assert fam.counters == latent_decode.counters(cfg)
    assert fam.counters[-1] == "latent_visible"
    assert fam.paged_prefill_chunk is latent_decode.paged_prefill_chunk
    # the same family keeps a ring for the configuration that has windows
    assert families.family_of(tiny.config()).recurrent
    # what it cannot run is refused for what holds for THIS configuration
    fam.check_serve_config("fp", 8, False)
    with pytest.raises(families.UnsupportedForFamily,
                       match="no span prefill") as err:
        fam.check_serve_config("fp", 8, True)
    assert "one row a slot" not in str(err.value)
    with pytest.raises(families.UnsupportedForFamily, match="kv_quant: int8"):
        fam.check_serve_config("int8", 8, False)


@pytest.mark.parametrize("chunk", [0, 4, 8, 16],
                         ids=["whole", "chunk4", "chunk8", "chunk16"])
def test_prefill_then_ticks_of_one_kind_of_layer_are_the_reference(chunk):
    """`_serve_the_plan` for the model without indexer, window or gate, under
    YaRN (rows pass the original context of 16, so every YaRN regime is
    used): five layers each read every position of every context."""
    _serve_the_plan(mla_tiny, chunk,
                    lambda contexts: [5 * sum(contexts)])


def test_the_engine_serves_one_kind_of_layer_in_chunks_and_counts_what_it_read():
    """Buckets of 8 (whole), 16 and 32 (chunks of 8 between decode ticks)
    through `ServeEngine`: every served token is the reference's first
    choice; `latent_visible` rides the tick's and every prefill unit's span
    beside the expert layers' counts, and is the host's own count exactly."""
    cfg = mla_tiny.config()
    params, top, layer_fn = mla_tiny.both_sides()
    scfg = serve.ServeConfig(max_slots=SLOTS, max_len=MAX_LEN,
                             prompt_buckets=(8, 16, 32), page_size=PAGE,
                             num_pages=PAGES, decode_span_every=4,
                             prefill_chunk_tokens=8)
    engine = serve.ServeEngine(params, cfg, scfg)
    spans = []
    listener = lambda rec: spans.append(dict(rec))
    trace.recorder().add_listener(listener)
    try:
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 128, n).tolist() for n in (5, 27, 3, 14, 30)]
        budgets = [9, 17, 6, 12, 5]
        handles = []
        for i, (prompt, n) in enumerate(zip(prompts, budgets)):
            handles.append(engine.submit(serve.ServeRequest(
                input_ids=prompt, seed=i,
                gen=families.GenerationConfig(max_new_tokens=n))))
            engine.step()
        engine.drain()
        engine._flush_decode_span()
    finally:
        trace.recorder().remove_listener(listener)
    served = [h.result() for h in handles]
    assert [len(s) for s in served] == budgets
    gaps = mla_tiny.reference.served_token_gaps(
        top, layer_fn, prompts, served, mla_tiny.MODEL, MAX_LEN)
    assert max(max(g) for g in gaps) <= TOL
    assert engine.prefill_chunks_total == 1 + 4 + 1 + 2 + 4

    ticks = [s for s in spans if s["name"] == "serve_decode_step"]
    units = [s for s in spans if s["name"] == "serve_prefill"]
    names = latent_decode.counters(cfg)
    assert len(units) == 12
    assert all(set(names) <= set(s) for s in ticks + units)
    assert not any("index_visible" in s for s in ticks + units)
    decoded = sum(n - 1 for n in budgets)
    assert sum(s["tokens"] for s in ticks) == decoded
    assert sum(s["routed_total"] for s in ticks) == decoded * 4 * 4
    contexts = [len(p) + j for p, n in zip(prompts, budgets)
                for j in range(1, n)]
    assert sum(s["latent_visible"] for s in ticks) == 5 * sum(contexts)
    # a prompt's tokens, whatever the units they came in
    assert sum(s["routed_total"] for s in units) == \
        sum(len(p) for p in prompts) * 4 * 4
    assert sum(s["latent_visible"] for s in units) == 5 * sum(
        t + 1 for p in prompts for t in range(len(p)))


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_the_latent_pages_of_one_kind_of_layer_ride_the_layer_loops_carry(program):
    """The model scans over its LAYERS (a period of one): the latent pages
    are that loop's carry, never its `xs` / `ys`, and the outputs alias the
    donated pool."""
    cfg = mla_tiny.config()
    fn, make = PROGRAMS[program]
    pool, args = make(cfg)
    assert set(pool) == {"latent"}
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, cfg))(*args).jaxpr
    leaf = (pool["latent"].shape, pool["latent"].dtype)
    loops = [e for e in _equations(jaxpr) if e.primitive.name == "scan"
             and e.params["length"] == cfg.periods == 4
             and leaf[0] in {v.aval.shape for v in e.invars}]
    assert len(loops) == 1
    loop = loops[0]
    n_consts, n_carry = loop.params["num_consts"], loop.params["num_carry"]
    assert leaf in [(v.aval.shape, v.aval.dtype)
                    for v in loop.invars[n_consts:n_consts + n_carry]]
    assert leaf[0] not in {v.aval.shape for v in loop.invars[n_consts + n_carry:]}
    assert leaf[0] not in {v.aval.shape for v in loop.outvars[n_carry:]}
    pool, args = make(cfg, pages=2048)
    analysis = fn.lower(*args, cfg).compile().memory_analysis()
    if analysis is not None:
        assert analysis.alias_size_in_bytes >= pool["latent"].nbytes


def test_the_dense_tick_gathers_no_row_and_sorts_nothing():
    """No gather of the slots' latent rows, no index leaf, no `top_k` in the
    tick of a model without an indexer: the kernel walks the page table."""
    cfg = mla_tiny.config()
    pool, args = _tick_args(cfg)
    jaxpr = jax.make_jaxpr(
        lambda *a: latent_decode.paged_decode_step(*a, cfg))(*args).jaxpr
    eqns = list(_equations(jaxpr))
    gathers = [tuple(e.outvars[0].aval.shape) for e in eqns
               if e.primitive.name == "gather"]
    assert (SLOTS, MAX_LEN // PAGE, PAGE, 16) not in gathers
    # nothing ranks a row's MAX_LEN places (the router's top-k over 16
    # experts and the sampler's sort over the vocabulary are not that)
    assert not [e for e in eqns if e.primitive.name in ("top_k", "sort")
                and e.invars[0].aval.shape[-1] == MAX_LEN]
    kernels = [e.params["name"] for e in eqns
               if e.primitive.name == "pallas_call"]
    # layer 0's read, then the loop's with its expert layer's three products
    assert kernels == ["paged_latent_decode_attn"] * 2 + ["grouped_matmul"] * 3


def test_a_checkpoint_of_one_kind_of_layer_round_trips_with_its_yarn_numbers(tmp_path):
    """`meta.json` hands the period and YaRN's pairs back as lists: the
    loaded configuration is the saved one, and the loader's tree is served."""
    from llama_pipeline_parallel_tpu.ckpt.checkpoint import (
        CheckpointManager,
        load_module_checkpoint,
    )

    cfg = mla_tiny.config()
    params = latent.init_params(jax.random.PRNGKey(5), cfg)
    CheckpointManager(str(tmp_path)).save_module(2, params, cfg)
    loaded, loaded_cfg, _, step = load_module_checkpoint(str(tmp_path))
    assert step == 2 and loaded_cfg == cfg and hash(loaded_cfg) == hash(cfg)
    assert loaded_cfg.period == ("full",)
    assert dict(loaded_cfg.rope_scaling)["mscale_all_dim"] == 0.5
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    engine = serve.ServeEngine(loaded, loaded_cfg, serve.ServeConfig(
        max_slots=SLOTS, max_len=MAX_LEN, prompt_buckets=(8, 16),
        page_size=PAGE, num_pages=PAGES, prefill_chunk_tokens=8))
    handle = engine.submit(serve.ServeRequest(
        input_ids=list(range(1, 12)),
        gen=families.GenerationConfig(max_new_tokens=3)))
    engine.drain()
    assert len(handle.result()) == 3
