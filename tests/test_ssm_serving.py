"""The state-space / expert block served through the normal path:
`ServeEngine` / `PagedKVCache` take its programs from `models/family.py`,
both stores stay in place in the traced programs, what it cannot run yet is
refused by name, and a checkpoint of the family loads through the loader
tools/serve.py uses. float32 on the CPU; logits are compared with the plain
reference's one pass at 1e-4 (both sides float32; they differ in the order
of sums)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ssm_tiny as tiny
import tick_ahead
from llama_pipeline_parallel_tpu import serve
from llama_pipeline_parallel_tpu.models import family as families
from llama_pipeline_parallel_tpu.models.hybrid_moe import decode as hybrid_decode
from llama_pipeline_parallel_tpu.models.ssm_moe import decode as ssm_decode
from llama_pipeline_parallel_tpu.models.ssm_moe import model as ssm
from llama_pipeline_parallel_tpu.utils import trace

TOL = 1e-4
SLOTS, MAX_LEN, PAGE, PAGES = 2, 48, 8, 12
# the tiny pattern `MEM*EME`: three state-space, one softmax, three expert
# layers, 4 of 16 experts a token
N_SSM, N_EXPERT, TOPK = 3, 3, 4


def _cache(cfg):
    return serve.PagedKVCache(cfg, SLOTS, MAX_LEN, PAGE, PAGES)


def test_the_manager_holds_pages_for_the_softmax_layer_and_a_row_a_slot():
    cfg = tiny.config()
    cache = _cache(cfg)
    assert families.family_of(cfg).name == "ssm_moe"
    assert cache.pool["k"].shape == (1, PAGES + 1, PAGE, 2, 8)
    assert cache.pool["state"].shape == (N_SSM, SLOTS, 8, 8, 8)
    assert cache.pool["state"].dtype == jnp.float32
    assert cache.pool["conv"].shape == (N_SSM, SLOTS, 3, 64 + 2 * 2 * 8)
    assert cache.recurrent_store_bytes == (cache.pool["state"].nbytes
                                           + cache.pool["conv"].nbytes)
    # a page is priced by the layers that keep keys and values, not by depth
    assert cache.page_bytes() == 2 * 1 * PAGE * 2 * 8 * 4
    assert cache._page_leaves == ("k", "v")


def test_prefill_then_ticks_through_both_stores_are_the_reference():
    """Three requests over two slots, prompts in two buckets with left
    pads: admitted at different ticks, the third into the slot a LONGER
    request left (whose state and convolution inputs must not leak into it).
    At every tick the logits of every decoding row are the reference's one
    pass over that request's tokens so far; a row that is not decoding
    leaves its rows of both stores as they were."""
    cfg = tiny.config()
    params, top, layer_fn = tiny.both_sides()
    cache = _cache(cfg)
    tick = jax.jit(ssm_decode.tick_logits, static_argnames=("cfg",))
    rng = np.random.default_rng(4)
    plan = [  # (admit at tick, slot, prompt, new tokens)
        (0, 0, rng.integers(0, 128, 13).tolist(), 8),
        (2, 1, rng.integers(0, 128, 5).tolist(), 12),
        (9, 0, rng.integers(0, 128, 3).tolist(), 7)]
    rows = {}            # slot -> {"seq", "left", "logits": [...], "write"}
    done = []
    for t in range(18):
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
            out = ssm_decode.prefill_prompt(
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
        idle = [s for s in range(SLOTS) if s not in rows]
        before = {name: np.asarray(cache.pool[name][:, idle])
                  for name in ("state", "conv")}
        logits, cache.pool, cache.kv_mask, counters = tick(
            params, jnp.asarray(token), cache.pool,
            jnp.asarray(cache.page_table), jnp.asarray(write), cache.kv_mask,
            jnp.asarray(active), cfg)
        assert counters[0] == len(rows) * TOPK * N_EXPERT
        assert counters[6] == len(rows) * N_SSM
        for name, kept in before.items():
            np.testing.assert_array_equal(
                np.asarray(cache.pool[name][:, idle]), kept)
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


def _generate(params, cfg, prompt, new, bucket):
    """One request alone, from a fresh cache of one slot: prefill, then one
    greedy tick after another. Independent of the engine's batching,
    admission and slot reuse."""
    cache = serve.PagedKVCache(cfg, 1, MAX_LEN, PAGE, MAX_LEN // PAGE)
    tick = jax.jit(ssm_decode.tick_logits, static_argnames=("cfg",))
    pad = bucket - len(prompt)
    ids = np.zeros((1, bucket), np.int32)
    ids[0, pad:] = prompt
    mask = np.zeros((1, bucket), np.int32)
    mask[0, pad:] = 1
    demand = cache.demand_pages(bucket, new)
    assert cache.reserve(demand) and cache.acquire("r", demand) == 0
    out = ssm_decode.prefill_prompt(params, jnp.asarray(ids),
                                    jnp.asarray(mask), cfg, bucket)
    cache.admit(0, out)
    tokens = [int(np.argmax(out["logits"][0]))]
    for step in range(new - 1):
        write = bucket + step
        cache.ensure_capacity(0, write + 1)
        logits, cache.pool, cache.kv_mask, _ = tick(
            params, jnp.asarray([tokens[-1]], jnp.int32), cache.pool,
            jnp.asarray(cache.page_table), jnp.asarray([write], jnp.int32),
            cache.kv_mask, jnp.ones((1,), jnp.int32), cfg)
        tokens.append(int(np.argmax(logits[0])))
    return tokens


def test_the_engine_serves_the_family_through_the_same_tick_and_spans():
    """An engine run of mixed lengths over two slots: the tokens are those
    of each request generated alone, every served token is the reference's
    own first choice, and the spans carry the family's counters."""
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
    for prompt, n, tokens in zip(prompts, budgets, served):
        assert list(tokens) == _generate(params, cfg, prompt, n,
                                         8 if len(prompt) <= 8 else 16)
    gaps = tiny.reference.served_token_gaps(top, layer_fn, prompts, served,
                                            tiny.MODEL, MAX_LEN)
    assert max(max(g) for g in gaps) <= TOL
    assert engine.slots.reused_slot_count() >= 1

    ticks = [s for s in spans if s["name"] == "serve_decode_step"]
    prefills = [s for s in spans if s["name"] == "serve_prefill"]
    assert len(prefills) == 4 and all(s["routed_here"] > 0 for s in prefills)
    for s, prompt in zip(prefills, prompts):
        assert s["ssm_rows"] == len(prompt) * N_SSM
        assert s["routed_total"] == len(prompt) * TOPK * N_EXPERT
    total = {k: sum(s[k] for s in ticks) for k in ssm.COUNTERS}
    decoded = sum(n - 1 for n in budgets)        # tokens that went through a tick
    # exact: every decoding row chooses 4 experts in each of 3 expert layers
    # and advances 3 state-space layers
    assert total["routed_total"] == decoded * TOPK * N_EXPERT
    assert total["ssm_rows"] == decoded * N_SSM
    assert 0 < total["routed_here"] < total["routed_total"]
    assert total["experts_held"] == sum(s["ticks"] for s in ticks) * 8 * N_EXPERT
    assert total["experts_hit"] <= total["routed_here"]
    # a tick's rows lie in one row tile: an expert with a row is read once
    assert total["expert_visits"] == total["experts_hit"]
    assert all(0 < s["kv_pages_live"] <= s["kv_pages_table"] for s in ticks)


@pytest.mark.parametrize("ending", ["by_length", "an_eos"])
def test_a_tick_in_flight_serves_the_family_as_the_serial_order_does(ending):
    """Five requests over two slots, greedy and sampled, one of two tokens,
    with the engine's tick in flight and in the serial order
    (`tests/tick_ahead.py`): the same streams, bit for bit. The recurrent
    store has ONE row a slot: a row that overran its eos advanced the slot's
    state once more after it had left, and the request admitted into the
    slot next is served as if it had not. Both counts are exact over every
    row-tick run, the overrun among them."""
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
        assert result["sums"]["routed_total"] == (
            result["sums"]["tokens"] * TOPK * N_EXPERT)
        assert result["sums"]["ssm_rows"] == result["sums"]["tokens"] * N_SSM
    assert ahead["sums"]["tokens"] == (
        serial["sums"]["tokens"] + ahead["sums"]["rows_overrun"])


def test_the_family_is_registered_beside_the_other_four():
    fam = families.family_of(tiny.config())
    assert fam.name == "ssm_moe" and fam.recurrent
    assert fam.prefill_prompt is ssm_decode.prefill_prompt
    assert fam.paged_decode_step is ssm_decode.paged_decode_step
    # the splice of a prefilled row is the hybrid block's: any `state` /
    # `conv` leaves, a row a slot
    assert fam.write_pages is hybrid_decode.write_pages
    assert fam.init_params is ssm.init_params
    assert fam.serving_weights is None          # served as stored
    # a chunk carries the slot's row forward (tests/test_granite_serving.py)
    assert fam.paged_prefill_chunk is ssm_decode.paged_prefill_chunk
    assert fam.paged_prefill_span is None
    assert fam.kv_quants == ("fp",)
    assert fam.counters == ssm.COUNTERS
    assert fam.counters[6] == "ssm_rows" and len(fam.counters) == 11
    assert {"llama", "hybrid_moe", "latent_moe", "eva", "ssm_moe"} <= set(
        families._FAMILIES)


# -- structure of the traced programs -------------------------------------------

def _tick_args(cfg, pages=PAGES):
    params = jax.eval_shape(lambda: ssm.init_params(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), params)
    pool = {**ssm_decode.init_page_pool(cfg, pages, PAGE),
            **ssm_decode.init_recurrent_store(cfg, SLOTS)}
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


@pytest.mark.parametrize("program", ["tick", "prefill"])
def test_the_grouped_products_take_a_layers_experts_as_they_are_stored(program):
    """Nothing is stacked, so nothing is sliced: in both programs every
    grouped product's right operand is a layer's own `[held, l, f]` or
    `[held, f, l]` leaf, two products an expert layer, and no equation makes
    an array of that shape."""
    cfg = tiny.config()
    params, _, args = _tick_args(cfg)
    if program == "tick":
        jaxpr = jax.make_jaxpr(
            lambda *a: ssm_decode.paged_decode_step(*a, cfg))(*args).jaxpr
    else:
        ids = jnp.zeros((1, 16), jnp.int32)
        jaxpr = jax.make_jaxpr(lambda *a: ssm_decode.prefill_prompt(
            *a, cfg, 16))(params, ids, ids).jaxpr
    eqns = list(_equations(jaxpr))
    lat, f = cfg.moe_latent_size, cfg.moe_intermediate_size
    alone = {(cfg.held, lat, f), (cfg.held, f, lat)}
    products = [tuple(v.aval.shape) for e in eqns
                if e.primitive.name == "pallas_call"
                and e.params["name"] == trace.KERNEL_GROUPED_MATMUL
                for v in e.invars if len(v.aval.shape) == 3]
    assert products == [(cfg.held, lat, f), (cfg.held, f, lat)] * N_EXPERT
    assert not [e for e in eqns
                if any(tuple(v.aval.shape) in alone for v in e.outvars)]


def test_the_tick_keeps_both_stores_in_place_and_reads_its_pages_where_they_lie():
    """The outputs alias the donated stores; the softmax layer's one-query
    attention is the paged kernel (no gather of the slots' logical rows, no
    `repeat_kv` broadcast of the 2 KV heads); the kernels are one paged
    attention, two grouped products an expert layer and one step of the
    state in place a Mamba-2 layer, in the pattern's order; nothing slices
    a layer's state out of the store or splices one back."""
    cfg = tiny.config()
    _, pool, args = _tick_args(cfg)
    compiled = ssm_decode.paged_decode_step.lower(*args, cfg).compile()
    analysis = compiled.memory_analysis()
    if analysis is not None:
        assert analysis.alias_size_in_bytes >= sum(
            x.nbytes for x in pool.values())
    jaxpr = jax.make_jaxpr(
        lambda *a: ssm_decode.paged_decode_step(*a, cfg))(*args).jaxpr
    kernels = [e.params["name"] for e in _equations(jaxpr)
               if e.primitive.name == "pallas_call"]
    step = [trace.KERNEL_SSM_STATE_STEP]
    expert = [trace.KERNEL_GROUPED_MATMUL] * 2
    # M E M * E M E
    assert kernels == (step + expert + step + [trace.KERNEL_PAGED_DECODE_ATTN]
                       + expert + step + expert)
    # no value anywhere is ONE layer's state of the slots
    layer_state = pool["state"].shape[1:]
    assert not [e for e in _equations(jaxpr) for v in (*e.invars, *e.outvars)
                if tuple(getattr(v.aval, "shape", ())) == layer_state]
    rows = (SLOTS, MAX_LEN // PAGE) + pool["k"].shape[2:]
    assert not [e for e in _equations(jaxpr) if e.primitive.name == "gather"
                and tuple(e.outvars[0].aval.shape) == rows]
    shared = (cfg.kv_heads, cfg.num_attention_heads // cfg.kv_heads,
              cfg.head_dim)
    assert not [e for e in _equations(jaxpr)
                if e.primitive.name == "broadcast_in_dim"
                and tuple(e.outvars[0].aval.shape[-3:]) == shared]


def test_the_programs_name_their_work():
    """Every scope of `utils/trace.SSM_SCOPES` is in the path of some
    operation of the tick or the prefill, beside the reused names."""
    cfg = tiny.config()
    params, _, args = _tick_args(cfg)
    ids = jnp.zeros((1, 16), jnp.int32)
    text = (ssm_decode.paged_decode_step.lower(*args, cfg).as_text(
        debug_info=True)
        + ssm_decode.prefill_prompt.lower(params, ids, ids, cfg, 16).as_text(
            debug_info=True))
    for name in trace.SSM_SCOPES + (
            trace.STATE_GATHER, trace.STATE_WRITE, trace.MOE_ROUTER,
            trace.MOE_DISPATCH, trace.MOE_EXPERTS, trace.MOE_SHARED,
            trace.MOE_COMBINE, trace.SCOPE_KV_WRITE, trace.SCOPE_DECODE_ATTN,
            trace.SCOPE_ATTN_QKV, trace.SCOPE_ATTN_OUT, trace.SCOPE_LM_HEAD,
            trace.SCOPE_SAMPLE):
        assert f"/{name}/" in text, name
    assert trace.ATTN_GATE not in text          # the softmax layer has no gate


# -- what cannot run yet ----------------------------------------------------------

@pytest.mark.parametrize("knobs,named", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(prefix_cache=True, prefill_chunk_tokens=8), "prefix_cache"),
    (dict(kv_quant="int8"), "kv_quant: int8"),
])
def test_what_recurrent_layers_cannot_run_is_refused_by_name(knobs, named):
    cfg = tiny.config()
    params = jax.eval_shape(lambda: ssm.init_params(jax.random.PRNGKey(0), cfg))
    base = dict(max_slots=SLOTS, max_len=MAX_LEN, prompt_buckets=(8, 16),
                kv_cache="paged", page_size=PAGE, num_pages=PAGES)
    with pytest.raises(families.UnsupportedForFamily, match=named) as err:
        serve.ServeEngine(params, cfg, serve.ServeConfig(**{**base, **knobs}))
    assert "ssm_moe" in str(err.value) and "recurrent" in str(err.value)


def test_the_trainer_refuses_the_family_by_name():
    from llama_pipeline_parallel_tpu import train

    with pytest.raises(NotImplementedError, match="ssm_moe"):
        train.build_model_config({"family": "ssm_moe", "hidden_size": 32})
    node = {"_target_": "llama_pipeline_parallel_tpu.models.ssm_moe."
                        "config.SsmMoEConfig.tiny"}
    with pytest.raises(NotImplementedError, match="ssm_moe"):
        train.build_model_config(node)


# -- the checkpoint ----------------------------------------------------------------

def test_a_checkpoint_of_the_family_round_trips_into_the_serving_loader(tmp_path):
    from llama_pipeline_parallel_tpu.ckpt.checkpoint import (
        CheckpointManager,
        load_module_checkpoint,
    )

    cfg = tiny.config(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = ssm.init_params(jax.random.PRNGKey(5), cfg)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save_module(3, params, cfg)
    meta = mgr.load_meta(3)
    assert meta["model_config"]["family"] == "ssm_moe"
    assert meta["model_config"]["pattern"] == "MEM*EME"
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
