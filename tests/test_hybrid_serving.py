"""The hybrid block served through the normal path: `ServeEngine` /
`PagedKVCache` take its programs from `models/family.py`, both stores stay
in place in the traced programs, what it cannot run yet is refused by name,
and a checkpoint of the family loads through the loader tools/serve.py uses.
float32 on the CPU; logits are compared with the plain reference's full
forward at 1e-4 (both sides float32; they differ in the order of sums)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_tiny as tiny
import tick_ahead
from llama_pipeline_parallel_tpu import serve
from llama_pipeline_parallel_tpu.models import family as families
from llama_pipeline_parallel_tpu.models.hybrid_moe import decode as hybrid_decode
from llama_pipeline_parallel_tpu.models.hybrid_moe import model as hybrid
from llama_pipeline_parallel_tpu.models.llama import decode as dense_decode
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.utils import trace

TOL = 1e-4
SLOTS, MAX_LEN, PAGE, PAGES = 2, 48, 8, 12


def _cache(cfg):
    return serve.PagedKVCache(cfg, SLOTS, MAX_LEN, PAGE, PAGES)


def test_the_manager_holds_pages_for_softmax_layers_and_a_row_a_slot():
    cfg = tiny.config()
    cache = _cache(cfg)
    assert cache.pool["k"].shape == (cfg.periods, PAGES + 1, PAGE, 2, 8)
    assert cfg.periods == 2 and cfg.num_hidden_layers == 8
    assert cache.pool["state"].shape == (6, SLOTS, 2, 8, 8)
    assert cache.pool["state"].dtype == jnp.float32
    assert cache.pool["conv"].shape == (6, SLOTS, 3, 3 * 16)
    assert cache.recurrent_store_bytes == (cache.pool["state"].nbytes
                                           + cache.pool["conv"].nbytes)
    # a page is priced by the layers that keep keys and values, not by depth
    assert cache.page_bytes() == 2 * cfg.periods * PAGE * 2 * 8 * 4


def test_prefill_then_ticks_through_both_stores_are_the_reference():
    """Three requests over two slots: admitted at different ticks, the third
    into the slot the first left (whose state must not leak into it). At
    every tick the logits of every decoding row are the reference's full
    forward over that request's tokens so far."""
    cfg = tiny.config()
    params, top, layer_fn = tiny.both_sides()
    cache = _cache(cfg)
    tick = jax.jit(hybrid_decode.tick_logits, static_argnames=("cfg",))
    rng = np.random.default_rng(4)
    plan = [  # (admit at tick, slot, prompt, new tokens)
        (0, 0, rng.integers(0, 128, 5).tolist(), 6),
        (2, 1, rng.integers(0, 128, 11).tolist(), 12),
        (7, 0, rng.integers(0, 128, 3).tolist(), 7)]
    rows = {}            # slot -> {"seq", "left", "logits": [...], "write"}
    done = []
    for t in range(16):
        for at, slot, prompt, new in plan:
            if at != t:
                continue
            bucket = 8 if len(prompt) <= 8 else 16
            pad = bucket - len(prompt)
            ids = np.zeros((1, bucket), np.int32)
            ids[0, pad:] = prompt
            mask = np.zeros((1, bucket), np.int32)
            mask[0, pad:] = 1
            assert cache.reserve(cache.demand_pages(bucket, new))
            assert cache.acquire(f"r{at}", cache.demand_pages(bucket, new)) == slot
            out = hybrid_decode.prefill_prompt(
                params, jnp.asarray(ids), jnp.asarray(mask), cfg, bucket)
            cache.admit(slot, out)
            rows[slot] = {"prompt": prompt, "seq": list(prompt),
                          "logits": [np.asarray(out["logits"][0])],
                          "left": new - 1, "write": bucket}
            rows[slot]["seq"].append(int(np.argmax(out["logits"][0])))
        if not rows:
            continue
        token = np.zeros(SLOTS, np.int32)
        write = np.zeros(SLOTS, np.int32)
        active = np.zeros(SLOTS, np.int32)
        for slot, r in rows.items():
            token[slot], write[slot], active[slot] = r["seq"][-1], r["write"], 1
            cache.ensure_capacity(slot, r["write"] + 1)
        logits, cache.pool, cache.kv_mask, _ = tick(
            params, jnp.asarray(token), cache.pool,
            jnp.asarray(cache.page_table), jnp.asarray(write), cache.kv_mask,
            jnp.asarray(active), cfg)
        for slot in list(rows):
            r = rows[slot]
            r["logits"].append(np.asarray(logits[slot]))
            r["seq"].append(int(np.argmax(logits[slot])))
            r["write"] += 1
            r["left"] -= 1
            if r["left"] == 0:
                done.append(rows.pop(slot))
                cache.release(slot)
    assert len(done) == 3 and not rows
    for r in done:
        ids = jnp.asarray([r["seq"][:-1]])
        want = tiny.reference.logits_fn(top, layer_fn, ids, tiny.MODEL)[0]
        first = len(r["prompt"]) - 1
        got = np.stack(r["logits"])
        np.testing.assert_allclose(got, want[first:first + len(got)], atol=TOL)


@pytest.mark.parametrize("ending", ["by_length", "an_eos"])
def test_a_tick_in_flight_serves_the_family_as_the_serial_order_does(ending):
    """Five requests over two slots, greedy and sampled, one of two tokens,
    with the engine's tick in flight and in the serial order
    (`tests/tick_ahead.py`): the same streams, bit for bit. The recurrent
    store has ONE row a slot: a row that overran its eos advanced the slot's
    state once more after it had left, and the request admitted into the
    slot next is served as if it had not. The expert layers' counter is
    exact over every row-tick run, the overrun among them."""
    cfg = tiny.config()
    params = tiny.both_sides()[0]
    scfg = serve.ServeConfig(max_slots=SLOTS, max_len=MAX_LEN,
                             prompt_buckets=(8, 16), page_size=PAGE,
                             num_pages=2 * PAGES, decode_span_every=4)
    make = lambda: serve.ServeEngine(params, cfg, scfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 128, n).tolist() for n in (5, 11, 3, 14, 7)]
    budgets = [9, 12, 6, 8, 2]
    knobs = [{}, dict(temperature=0.8), {}, dict(temperature=1.1, top_k=6), {}]
    eos = None
    if ending == "an_eos":
        plain = tick_ahead.run(make(), tick_ahead.requests_of(
            prompts, budgets, knobs), serially=True)["tokens"]
        eos = {1: tick_ahead.eos_of(plain[1])[1]}
    serial, ahead = tick_ahead.both_orders(
        make, lambda: tick_ahead.requests_of(prompts, budgets, knobs, eos))
    assert ahead["sums"]["rows_overrun"] == (ending == "an_eos")
    if eos is None:
        assert [len(t) for t in ahead["tokens"]] == budgets
    else:
        assert ahead["tokens"][1][-1] == eos[1]
        assert len(ahead["tokens"][1]) < budgets[1]
    for result in (serial, ahead):
        # every row-tick chooses 4 experts in each of 8 layers
        assert result["sums"]["routed_total"] == (
            result["sums"]["tokens"] * 4 * 8)
    assert ahead["sums"]["tokens"] == (
        serial["sums"]["tokens"] + ahead["sums"]["rows_overrun"])


def test_the_engine_serves_the_family_through_the_same_tick_and_spans():
    cfg = tiny.config()
    params, top, layer_fn = tiny.both_sides()
    scfg = serve.ServeConfig(max_slots=SLOTS, max_len=MAX_LEN,
                             prompt_buckets=(8, 16), kv_cache="paged",
                             page_size=PAGE, num_pages=PAGES,
                             decode_span_every=4)
    engine = serve.ServeEngine(params, cfg, scfg)
    spans = []
    listener = lambda rec: spans.append(dict(rec))
    trace.recorder().add_listener(listener)
    try:
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 128, n).tolist() for n in (5, 11, 3, 14)]
        budgets = [9, 17, 6, 8]
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
    # every served (greedy) token is the reference's own first choice, or
    # within rounding of it
    assert max(max(g) for g in gaps) <= TOL
    assert engine.slots.reused_slot_count() >= 1

    ticks = [s for s in spans if s["name"] == "serve_decode_step"]
    prefills = [s for s in spans if s["name"] == "serve_prefill"]
    assert len(prefills) == 4 and all(s["routed_here"] > 0 for s in prefills)
    total = {k: sum(s[k] for s in ticks) for k in hybrid.COUNTERS}
    decoded = sum(n - 1 for n in budgets)        # tokens that went through a tick
    # exact: every decoding token chooses 4 experts in each of 8 layers
    assert total["routed_total"] == decoded * 4 * 8
    assert 0 < total["routed_here"] < total["routed_total"]
    assert total["experts_held"] == sum(s["ticks"] for s in ticks) * 8 * 8
    assert total["experts_hit"] <= total["routed_here"]
    # a tick's rows lie in one row tile: an expert with a row is read once
    assert total["expert_visits"] == total["experts_hit"]
    assert all(s["expert_visits"] >= s["experts_hit"] > 0 for s in prefills)
    assert all(set(("stage_s", "dispatch_s", "wait_s", "emit_s")) <= set(s)
               for s in ticks)
    # the engine's own count of the pages the tick's attention reads, of
    # those its rows' tables have (no family's business)
    assert all(0 < s["kv_pages_live"] <= s["kv_pages_table"] for s in ticks)
    assert sum(s["kv_pages_table"] for s in ticks) == \
        decoded * (MAX_LEN // PAGE)


def test_the_dense_family_is_handed_the_functions_it_always_called():
    fam = families.family_of(LlamaConfig.tiny())
    for name in ("prefill_prompt", "paged_decode_step", "paged_prefill_chunk",
                 "paged_prefill_span", "write_pages", "init_page_pool",
                 "serving_weights"):
        assert getattr(fam, name) is getattr(dense_decode, name), name
    assert fam.init_recurrent_store is None and fam.init_params is None
    assert not fam.recurrent and fam.counters == ()
    assert families.sample_rowwise is dense_decode.sample_rowwise


def test_a_family_states_only_what_depends_on_its_layers():
    """The seventeen fields, by name: a program that runs the layers, a store
    shaped by them, or a fact about them (since PR 36 what a slot's pages
    are, and why a prefix cannot be shared; since PR 55 whether the model
    drafts, and the program that makes a row's first draft). What touches only the mask or the
    pool's page axis is `serve/pages.py`'s own, and the manager reaches no
    such thing through the family."""
    import inspect

    from llama_pipeline_parallel_tpu.serve import pages

    assert [f.name for f in dataclasses.fields(families.ServingFamily)] == [
        "name", "prefill_prompt", "paged_decode_step", "write_pages",
        "init_page_pool", "init_recurrent_store", "init_params",
        "serving_weights", "paged_prefill_chunk", "paged_prefill_span",
        "kv_quants", "counters", "table_width", "table_columns",
        "prefix_cache_why", "drafts", "first_draft"]
    own = ("copy_page", "reset_kv_mask_row", "set_kv_mask_row")
    source = inspect.getsource(pages)
    for name in own:
        assert callable(getattr(pages, name)), name
        assert not hasattr(dense_decode, name), name
        assert f"family.{name}" not in source, name
    assert "kv_cache" not in inspect.signature(
        families.ServingFamily.check_serve_config).parameters


def test_a_fork_copies_pages_and_leaves_the_recurrent_store_alone():
    """`copy_page` walks the leaves `init_page_pool` returned: the `state`
    and `conv` rows of a pool that carries a recurrent store have slots,
    not pages, on their second axis, and stay the very arrays they were."""
    cfg = tiny.config()
    cache = _cache(cfg)
    assert cache._page_leaves == ("k", "v")
    slot = cache.acquire("r", 0)
    assert cache.reserve(2) and cache.acquire("s", 2) == 1
    rng = np.random.default_rng(0)
    cache.pool = {name: jnp.asarray(rng.standard_normal(x.shape), x.dtype)
                  for name, x in cache.pool.items()}
    before = {name: np.asarray(x) for name, x in cache.pool.items()}
    store = {name: cache.pool[name] for name in ("state", "conv")}
    src = 5
    cache.fork_page(1, src)
    dst = int(cache.page_table[1, 0])
    assert dst != src and cache.cow_forks == 1
    for name in ("k", "v"):
        after = np.asarray(cache.pool[name])
        np.testing.assert_array_equal(after[:, dst], before[name][:, src])
        keep = [p for p in range(PAGES + 1) if p != dst]
        np.testing.assert_array_equal(after[:, keep], before[name][:, keep])
    for name, leaf in store.items():
        assert cache.pool[name] is leaf and not leaf.is_deleted(), name
        np.testing.assert_array_equal(np.asarray(leaf), before[name])
    cache.release(slot)


def test_the_engine_names_no_familys_functions():
    import inspect

    from llama_pipeline_parallel_tpu.serve import engine, pages

    for module in (engine, pages):
        source = inspect.getsource(module)
        assert "models.llama" not in source, module.__name__
        assert "models.hybrid_moe" not in source, module.__name__


# -- structure of the traced programs -------------------------------------------

def _tick_args(cfg, pages=PAGES):
    params = jax.eval_shape(lambda: hybrid.init_params(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), params)
    pool = {**hybrid_decode.init_page_pool(cfg, pages, PAGE),
            **hybrid_decode.init_recurrent_store(cfg, SLOTS)}
    z = jnp.zeros((SLOTS,), jnp.int32)
    return params, pool, (
        params, z, pool, jnp.zeros((SLOTS, MAX_LEN // PAGE), jnp.int32), z, z,
        jnp.zeros((SLOTS, MAX_LEN), jnp.int32), z,
        jnp.zeros((SLOTS, 2), jnp.uint32), jnp.zeros((SLOTS,), jnp.float32),
        z, jnp.ones((SLOTS,), jnp.float32))


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for v in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_both_stores_ride_the_period_loops_carry():
    """As tests/test_pool_walk.py holds the dense pool: the page pool and
    the recurrent store are carried through the loop over periods whole,
    never its `xs` / `ys` (which a donated argument cannot alias)."""
    cfg = tiny.config()
    _, pool, args = _tick_args(cfg)
    jaxpr = jax.make_jaxpr(
        lambda *a: hybrid_decode.paged_decode_step(*a, cfg))(*args).jaxpr
    loops = [e for e in _equations(jaxpr) if e.primitive.name == "scan"
             and e.params["length"] == cfg.periods]
    assert len(loops) == 1
    loop = loops[0]
    n_consts, n_carry = loop.params["num_consts"], loop.params["num_carry"]
    carried = [(v.aval.shape, v.aval.dtype)
               for v in loop.invars[n_consts:n_consts + n_carry]]
    scanned = {v.aval.shape for v in loop.invars[n_consts + n_carry:]}
    stacked = {v.aval.shape for v in loop.outvars[n_carry:]}
    for name, leaf in pool.items():
        assert (leaf.shape, leaf.dtype) in carried, name
        assert leaf.shape not in scanned and leaf.shape not in stacked, name


@pytest.mark.parametrize("program", ["tick", "prefill"])
def test_the_grouped_products_take_the_stack_of_periods_whole(program):
    """The alarm for the slice coming back (on the chip a slice of the
    stacked experts in front of the grouped product's kernel is a copy of a
    layer's experts, every product: PERF.md, PR 33; the kernel is
    `ops/grouped_matmul.py`'s `pallas_call` since PR 43, and its right
    operand is still the stored leaf seen as periods x held experts): in both programs of the family, every
    grouped product's right operand leads with periods x held experts, and
    no equation inside or outside the loop over periods makes an array of
    one layer's expert shape."""
    cfg = tiny.config()
    assert cfg.periods == 2
    params, _, args = _tick_args(cfg)
    if program == "tick":
        jaxpr = jax.make_jaxpr(
            lambda *a: hybrid_decode.paged_decode_step(*a, cfg))(*args).jaxpr
    else:
        ids = jnp.zeros((1, 16), jnp.int32)
        jaxpr = jax.make_jaxpr(lambda *a: hybrid_decode.prefill_prompt(
            *a, cfg, 16))(params, ids, ids).jaxpr
    eqns = list(_equations(jaxpr))
    products, sliced = tiny.expert_operands(eqns, cfg)
    assert products == [cfg.periods * cfg.held] * 3 * cfg.attn_period
    assert not sliced, sliced


def test_the_ticks_temporaries_are_smaller_than_either_store():
    """With both stores large the tick keeps them in place: the outputs
    alias the donated stores, and nothing as large as a pool array is made
    inside the period loop but the in-place writes and the views of them
    that the attention kernel reads.

    The temporaries themselves are held for the chip, in
    tests/test_paged_attention.py `test_a_tick_compiled_for_the_chip_keeps_
    the_pool_in_place[hybrid]`: XLA:CPU runs the kernel through Pallas's
    interpreter, whose loop over the grid carries, and so copies, every
    operand of the kernel, the pool's arrays among them (as
    tests/test_pool_walk.py says of the dense tick)."""
    cfg = tiny.config()
    _, pool, args = _tick_args(cfg, pages=4096)
    compiled = hybrid_decode.paged_decode_step.lower(*args, cfg).compile()
    analysis = compiled.memory_analysis()
    if analysis is None:
        pytest.skip("this backend reports no memory analysis")
    assert analysis.alias_size_in_bytes >= sum(x.nbytes for x in pool.values())

    jaxpr = jax.make_jaxpr(
        lambda *a: hybrid_decode.paged_decode_step(*a, cfg))(*args).jaxpr
    loop, = [e for e in _equations(jaxpr) if e.primitive.name == "scan"
             and e.params["length"] == cfg.periods]
    body = loop.params["jaxpr"].jaxpr
    large = [e for e in _equations(body) for out in e.outvars
             if getattr(out.aval, "size", 0) >= pool["k"].size]
    assert sorted(e.primitive.name for e in large) == [
        "reshape", "reshape", "scatter", "scatter"], large
    kernel, = [e for e in _equations(body)
               if e.primitive.name == "pallas_call"
               and e.params["name"] == trace.KERNEL_PAGED_DECODE_ATTN]
    assert {e.outvars[0] for e in large
            if e.primitive.name == "reshape"} <= set(kernel.invars)


def test_the_tick_reads_its_pages_where_they_lie():
    """The softmax layer's one-query attention is the paged kernel
    (ops/paged_attention.py): no gather of the slots' logical rows [S, Pmax,
    page, kv_h, hd], and no `repeat_kv` broadcast of the 2 KV heads to the 4
    query heads ([b, s, kv_h, n_rep, hd]): grouped queries share a KV
    head's rows by shape. The output gate and the projections are outside
    it, as they were."""
    cfg = tiny.config()
    _, pool, args = _tick_args(cfg)
    jaxpr = jax.make_jaxpr(
        lambda *a: hybrid_decode.paged_decode_step(*a, cfg))(*args).jaxpr
    kernels = [e for e in _equations(jaxpr)
               if e.primitive.name == "pallas_call"]
    # one softmax layer a period, and three grouped products an expert layer
    assert [e.params["name"] for e in kernels] == [
        trace.KERNEL_PAGED_DECODE_ATTN] + [
        trace.KERNEL_GROUPED_MATMUL] * 3 * cfg.attn_period
    rows = (SLOTS, MAX_LEN // PAGE) + pool["k"].shape[2:]
    assert not [e for e in _equations(jaxpr) if e.primitive.name == "gather"
                and tuple(e.outvars[0].aval.shape) == rows]
    assert cfg.num_attention_heads > cfg.kv_heads
    grouped = (cfg.kv_heads, cfg.num_attention_heads // cfg.kv_heads,
               cfg.head_dim)
    assert not [e for e in _equations(jaxpr)
                if e.primitive.name == "broadcast_in_dim"
                and tuple(e.outvars[0].aval.shape[-3:]) == grouped]


# -- what cannot run yet ----------------------------------------------------------

@pytest.mark.parametrize("knobs,named", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(prefill_chunk_tokens=8), "prefill_chunk_tokens"),
    (dict(kv_quant="int8"), "kv_quant: int8"),
])
def test_what_recurrent_layers_cannot_run_is_refused_by_name(knobs, named):
    cfg = tiny.config()
    params = jax.eval_shape(lambda: hybrid.init_params(jax.random.PRNGKey(0), cfg))
    base = dict(max_slots=SLOTS, max_len=MAX_LEN, prompt_buckets=(8, 16),
                kv_cache="paged", page_size=PAGE, num_pages=PAGES)
    with pytest.raises(families.UnsupportedForFamily, match=named) as err:
        serve.ServeEngine(params, cfg, serve.ServeConfig(**{**base, **knobs}))
    assert "hybrid_moe" in str(err.value) and "recurrent" in str(err.value)


def test_the_span_prefill_is_not_among_the_familys_programs():
    fam = families.family_of(tiny.config())
    assert fam.recurrent and fam.paged_prefill_span is None
    assert fam.paged_prefill_chunk is None
    assert fam.counters == hybrid.COUNTERS


def test_the_trainer_refuses_the_family_by_name():
    from llama_pipeline_parallel_tpu import train

    with pytest.raises(NotImplementedError, match="hybrid_moe"):
        train.build_model_config({"family": "hybrid_moe", "hidden_size": 32})
    node = {"_target_": "llama_pipeline_parallel_tpu.models.hybrid_moe."
                        "config.HybridMoEConfig.tiny"}
    with pytest.raises(NotImplementedError, match="hybrid_moe"):
        train.build_model_config(node)


# -- the checkpoint ----------------------------------------------------------------

def test_a_checkpoint_of_the_family_round_trips_into_the_serving_loader(tmp_path):
    from llama_pipeline_parallel_tpu.ckpt.checkpoint import (
        CheckpointManager,
        load_module_checkpoint,
    )

    cfg = tiny.config(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = hybrid.init_params(jax.random.PRNGKey(5), cfg)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save_module(3, params, cfg)
    meta = mgr.load_meta(3)
    assert meta["model_config"]["family"] == "hybrid_moe"
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
        kv_cache="paged", page_size=PAGE, num_pages=PAGES))
    handle = engine.submit(serve.ServeRequest(
        input_ids=[1, 2, 3], gen=families.GenerationConfig(max_new_tokens=3)))
    engine.drain()
    assert len(handle.result()) == 3


def test_a_dense_checkpoints_meta_names_no_family(tmp_path):
    from llama_pipeline_parallel_tpu.ckpt.checkpoint import _config_meta

    assert "family" not in _config_meta(LlamaConfig.tiny())
    assert families.config_from_meta(_config_meta(LlamaConfig.tiny())) == \
        LlamaConfig.tiny(dtype=LlamaConfig().dtype)
