"""The window / full softmax block served through the normal path:
`ServeEngine` / `PagedKVCache` take its programs from `models/family.py`,
short whole-bucket prefills and chunked long ones share one queue, both
stores stay in place in the traced programs, what it cannot run yet is
refused by name, and a checkpoint of the family loads through the loader
tools/serve.py uses. float32 on the CPU; logits are compared with the plain
reference's one pass at 1e-4 (both sides float32; they differ in the order
of sums)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tick_ahead
import window_tiny as tiny
from llama_pipeline_parallel_tpu import serve
from llama_pipeline_parallel_tpu.models import family as families
from llama_pipeline_parallel_tpu.models.window_moe import decode
from llama_pipeline_parallel_tpu.models.window_moe import model as window
from llama_pipeline_parallel_tpu.utils import trace

TOL = 1e-4
SLOTS, MAX_LEN, PAGE, PAGES = 2, 48, tiny.PAGE, 24
# the tiny pattern `0 1 1 1 1 1 0`: two full and five window layers, six of
# the seven followed by experts, 4 of 16 experts a token, a window of 8
N_FULL, N_WINDOW, N_EXPERT, TOPK, WINDOW = 2, 5, 6, 4, tiny.WINDOW


def _cache(cfg):
    return serve.PagedKVCache(cfg, SLOTS, MAX_LEN, PAGE, PAGES)


def test_the_manager_holds_two_stores_of_different_shape_for_one_slot():
    cfg = tiny.config()
    cache = _cache(cfg)
    assert families.family_of(cfg).name == "window_moe"
    # pages of 2 KV heads for the full layers, a page as the matrix the
    # tick's kernel reads, a key's 24 numbers in whole lanes; values of 16
    assert cache.pool["k"].shape == (N_FULL, PAGES + 1, PAGE * 2, 128)
    assert cache.pool["v"].shape == (N_FULL, PAGES + 1, PAGE * 2, 16)
    # a ring of the window's 8 places a slot, 4 KV heads, for the window layers
    assert cache.pool["ring_k"].shape == (N_WINDOW, SLOTS, WINDOW, 4, 128)
    assert cache.pool["ring_v"].shape == (N_WINDOW, SLOTS, WINDOW, 4, 16)
    assert cache.recurrent_store_bytes == (cache.pool["ring_k"].nbytes
                                           + cache.pool["ring_v"].nbytes)
    # a page is priced by the layers that page, not by depth
    assert cache.page_bytes() == N_FULL * PAGE * 2 * (128 + 16) * 4
    assert cache._page_leaves == ("k", "v")
    assert cache.pages_per_slot == MAX_LEN // PAGE


def _row_inputs(prompt, bucket):
    pad = bucket - len(prompt)
    ids = np.zeros((1, bucket), np.int32)
    ids[0, pad:] = prompt
    mask = np.zeros((1, bucket), np.int32)
    mask[0, pad:] = 1
    return ids, mask


def test_prefill_then_ticks_through_pages_and_rings_are_the_reference():
    """Three requests over two slots, prompts in two buckets with left
    pads: admitted at different ticks, the third into the slot a LONGER
    request left (whose ring and pages must not leak into it). Every request
    decodes past the window, so its ring wraps. At every tick the logits of
    every decoding row are the reference's one pass over that request's
    tokens so far; a row that is not decoding leaves its ring as it was."""
    cfg = tiny.config()
    params, top, layer_fn = tiny.both_sides()
    cache = _cache(cfg)
    tick = jax.jit(decode.tick_logits, static_argnames=("cfg",))
    rng = np.random.default_rng(4)
    plan = [  # (admit at tick, slot, prompt, new tokens)
        (0, 0, rng.integers(0, 128, 13).tolist(), 11),
        (2, 1, rng.integers(0, 128, 5).tolist(), 14),
        (12, 0, rng.integers(0, 128, 3).tolist(), 10)]
    rows, done = {}, []
    for t in range(24):
        for at, slot, prompt, new in plan:
            if at != t:
                continue
            bucket = 8 if len(prompt) <= 8 else 16
            ids, mask = _row_inputs(prompt, bucket)
            assert cache.reserve(cache.demand_pages(bucket, new))
            assert cache.acquire(f"r{at}", cache.demand_pages(bucket, new)) == slot
            out = decode.prefill_prompt(params, jnp.asarray(ids),
                                        jnp.asarray(mask), cfg, bucket)
            cache.admit(slot, out)
            rows[slot] = {"prompt": prompt, "seq": list(prompt),
                          "logits": [np.asarray(out["logits"][0])],
                          "left": new - 1, "write": bucket}
            rows[slot]["seq"].append(int(np.argmax(out["logits"][0])))
        if not rows:
            continue
        token, pos, write, active = (np.zeros(SLOTS, np.int32)
                                     for _ in range(4))
        for slot, r in rows.items():
            token[slot], write[slot], active[slot] = r["seq"][-1], r["write"], 1
            pos[slot] = len(r["seq"]) - 1
            cache.ensure_capacity(slot, r["write"] + 1)
        idle = [s for s in range(SLOTS) if s not in rows]
        before = {name: np.asarray(cache.pool[name][:, idle])
                  for name in ("ring_k", "ring_v")}
        logits, cache.pool, cache.kv_mask, counters = tick(
            params, jnp.asarray(token), cache.pool,
            jnp.asarray(cache.page_table), jnp.asarray(pos),
            jnp.asarray(write), cache.kv_mask, jnp.asarray(active), cfg)
        contexts = [len(r["seq"]) for r in rows.values()]
        assert counters[0] == len(rows) * TOPK * N_EXPERT
        assert counters[6] == N_WINDOW * sum(min(c, WINDOW) for c in contexts)
        assert counters[7] == N_FULL * sum(contexts)
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


def _serve_config(**knobs):
    return serve.ServeConfig(**{**dict(
        max_slots=SLOTS, max_len=MAX_LEN, prompt_buckets=(8, 16, 32),
        kv_cache="paged", page_size=PAGE, num_pages=PAGES,
        prefill_chunk_tokens=8, decode_span_every=4), **knobs})


def test_the_engine_serves_short_and_chunked_long_requests_in_one_queue():
    """Six requests over two slots: prompts that prefill whole (a bucket of
    8) beside prompts of 16 and 32 places that go a chunk of 8 a step
    between decode ticks. Every served token is the reference's own first
    choice, the counters on the spans are exact against the host's count,
    and when the queue has drained every page is free again."""
    cfg = tiny.config()
    params, top, layer_fn = tiny.both_sides()
    engine = serve.ServeEngine(params, cfg, _serve_config(num_pages=64))
    spans = []
    listener = lambda rec: spans.append(dict(rec))
    trace.recorder().add_listener(listener)
    try:
        rng = np.random.default_rng(1)
        lengths, budgets = (5, 27, 3, 14, 31, 8), [9, 12, 6, 11, 5, 13]
        prompts = [rng.integers(0, 128, n).tolist() for n in lengths]
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
                                            tiny.MODEL, 16)
    assert max(max(g) for g in gaps) <= TOL
    assert engine.slots.reused_slot_count() >= 1
    # nothing is held once the queue has drained
    assert engine.slots.pages_free == 64
    assert engine.slots.active_count == 0

    ticks = [s for s in spans if s["name"] == "serve_decode_step"]
    prefills = [s for s in spans if s["name"] == "serve_prefill"]
    # a bucket of 8 is one unit, 16 two chunks, 32 four
    assert sorted(s["chunk"] for s in prefills) == [8] * (1 + 4 + 1 + 2 + 4 + 1)
    assert sum(s["routed_total"] for s in prefills) == (
        sum(lengths) * TOPK * N_EXPERT)
    assert sum(s["full_entries_read"] for s in prefills) == N_FULL * sum(
        n * (n + 1) // 2 for n in lengths)
    total = {k: sum(s[k] for s in ticks) for k in window.COUNTERS}
    decoded = sum(n - 1 for n in budgets)        # tokens that went through a tick
    contexts = [n + j for n, m in zip(lengths, budgets) for j in range(1, m)]
    assert total["routed_total"] == decoded * TOPK * N_EXPERT
    assert total["window_entries_read"] == N_WINDOW * sum(
        min(c, WINDOW) for c in contexts)
    assert total["full_entries_read"] == N_FULL * sum(contexts)
    assert 0 < total["routed_here"] < total["routed_total"]
    assert total["experts_held"] == sum(s["ticks"] for s in ticks) * 8 * N_EXPERT


@pytest.mark.parametrize("ending", ["by_length", "an_eos"])
def test_a_tick_in_flight_serves_the_family_as_the_serial_order_does(ending):
    """Five requests over two slots, greedy and sampled, whole and chunked,
    with the engine's tick and prefill unit in flight and in the serial
    order (`tests/tick_ahead.py`): the same streams, bit for bit. A row that
    overran its eos wrote one more place of its slot's ring after it had
    left, and the request admitted into the slot next is served as if it
    had not."""
    cfg = tiny.config()
    params = tiny.both_sides()[0]
    make = lambda: serve.ServeEngine(params, cfg,
                                     _serve_config(num_pages=2 * PAGES))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 128, n).tolist() for n in (5, 11, 3, 30, 7)]
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
    assert ahead["sums"]["tokens"] == (
        serial["sums"]["tokens"] + ahead["sums"]["rows_overrun"])


def test_the_family_is_registered_beside_the_other_five():
    fam = families.family_of(tiny.config())
    assert fam.name == "window_moe" and fam.recurrent
    assert fam.prefill_prompt is decode.prefill_prompt
    assert fam.paged_decode_step is decode.paged_decode_step
    assert fam.paged_prefill_chunk is decode.paged_prefill_chunk
    assert fam.write_pages is decode.write_pages
    assert fam.init_params is window.init_params
    assert fam.paged_prefill_span is None and fam.kv_quants == ("fp",)
    assert fam.counters == window.COUNTERS and len(fam.counters) == 8
    assert fam.counters[-2:] == ("window_entries_read", "full_entries_read")
    # every position keeps its entry in the full layers' pages
    assert fam.table_width is families.row_table_width
    assert {"llama", "hybrid_moe", "latent_moe", "eva", "ssm_moe",
            "window_moe"} == set(families._FAMILIES)


# -- structure of the traced programs -------------------------------------------

def _tick_args(cfg, pages=PAGES):
    params = jax.eval_shape(lambda: window.init_params(jax.random.PRNGKey(0),
                                                       cfg))
    params = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), params)
    pool = {**decode.init_page_pool(cfg, pages, PAGE),
            **decode.init_recurrent_store(cfg, SLOTS)}
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


def _kernels(jaxpr):
    return [e.params["name"] for e in _equations(jaxpr)
            if e.primitive.name == "pallas_call"]


def test_the_tick_keeps_both_stores_in_place_and_reads_them_where_they_lie():
    """The outputs alias the donated stores; every layer's one-query
    attention is the paged kernel (over the pages, or over the ring as a
    pool of one page a slot: no gather of the slots' logical rows, no
    `repeat_kv` broadcast of the KV heads), then three grouped products an
    expert layer, in the pattern's order; a layer's experts reach the
    product through reshapes alone (a stack of one layer: no slice, no
    copy)."""
    cfg = tiny.config()
    _, pool, args = _tick_args(cfg)
    compiled = decode.paged_decode_step.lower(*args, cfg).compile()
    analysis = compiled.memory_analysis()
    if analysis is not None:
        assert analysis.alias_size_in_bytes >= sum(
            x.nbytes for x in pool.values())
    jaxpr = jax.make_jaxpr(
        lambda *a: decode.paged_decode_step(*a, cfg))(*args).jaxpr
    attn, experts = ([trace.KERNEL_PAGED_DECODE_ATTN],
                     [trace.KERNEL_GROUPED_MATMUL] * 3)
    assert _kernels(jaxpr) == attn + (attn + experts) * 6
    eqns = list(_equations(jaxpr))
    rows = (SLOTS, MAX_LEN // PAGE) + pool["v"].shape[2:]   # a gathered row
    assert not [e for e in eqns if e.primitive.name == "gather"
                and tuple(e.outvars[0].aval.shape) == rows]
    d, f = cfg.hidden_size, cfg.moe_intermediate_size
    alone = {(cfg.held, d, f), (cfg.held, f, d)}
    assert {e.primitive.name for e in eqns
            if any(tuple(v.aval.shape) in alone for v in e.outvars)} <= {
                "reshape", "broadcast_in_dim"}


@pytest.mark.parametrize("program", ["prefill", "chunk"])
def test_a_prefill_attends_through_the_two_kernels_in_the_patterns_order(
        program):
    """A whole bucket and a chunk run the causal kernel ONCE in a full layer
    and the banded kernel in a window layer: a chunk's full layer has no
    branch by its reach (the kernel's key axis ends where the chunk does)."""
    cfg = tiny.config()
    params, pool, _ = _tick_args(cfg)
    ids = jnp.zeros((1, 16), jnp.int32)
    if program == "prefill":
        jaxpr = jax.make_jaxpr(lambda *a: decode.prefill_prompt(
            *a, cfg, 16))(params, ids, ids).jaxpr
    else:
        jaxpr = jax.make_jaxpr(lambda *a: decode.paged_prefill_chunk(
            *a, cfg))(params, ids, ids, ids, pool,
                      jnp.zeros((MAX_LEN // PAGE,), jnp.int32), jnp.int32(0),
                      jnp.zeros((SLOTS, MAX_LEN), jnp.int32), jnp.int32(0)).jaxpr
    full = [trace.KERNEL_FULL_CHUNK_ATTN]
    experts = [trace.KERNEL_GROUPED_MATMUL] * 3
    band = [trace.KERNEL_WINDOW_PREFILL_ATTN] + experts
    assert _kernels(jaxpr) == full + band * 5 + full + experts


def test_the_programs_name_their_work():
    """Every scope of `utils/trace.WINDOW_SCOPES` is in the path of some
    operation of the tick, the prefill or the chunk, beside the reused
    names; nothing runs under a shared expert's."""
    cfg = tiny.config()
    params, pool, args = _tick_args(cfg)
    ids = jnp.zeros((1, 16), jnp.int32)
    text = (
        decode.paged_decode_step.lower(*args, cfg).as_text(debug_info=True)
        + decode.prefill_prompt.lower(params, ids, ids, cfg, 16).as_text(
            debug_info=True)
        + decode.paged_prefill_chunk.lower(
            params, ids, ids, ids, pool,
            jnp.zeros((MAX_LEN // PAGE,), jnp.int32), jnp.int32(0),
            jnp.zeros((SLOTS, MAX_LEN), jnp.int32), jnp.int32(0),
            cfg).as_text(debug_info=True))
    for name in trace.WINDOW_SCOPES + (
            trace.RING_GATHER, trace.RING_WRITE, trace.SCOPE_KV_WRITE,
            trace.SCOPE_KV_GATHER, trace.MOE_ROUTER, trace.MOE_DISPATCH,
            trace.MOE_EXPERTS, trace.MOE_COMBINE, trace.SCOPE_MLP,
            trace.SCOPE_DECODE_MLP, trace.SCOPE_ATTN_QKV, trace.SCOPE_ATTN_OUT,
            trace.SCOPE_LM_HEAD, trace.SCOPE_SAMPLE):
        assert f"/{name}/" in text, name
    assert trace.MOE_SHARED not in text         # nothing beside the routed sum
    for kernel in (trace.KERNEL_WINDOW_PREFILL_ATTN,
                   trace.KERNEL_FULL_CHUNK_ATTN,
                   trace.KERNEL_PAGED_DECODE_ATTN):
        assert kernel in text


# -- what cannot run yet ----------------------------------------------------------

@pytest.mark.parametrize("knobs,named", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(kv_quant="int8"), "kv_quant: int8"),
])
def test_what_the_family_cannot_run_is_refused_by_name(knobs, named):
    cfg = tiny.config()
    params = jax.eval_shape(lambda: window.init_params(jax.random.PRNGKey(0),
                                                       cfg))
    with pytest.raises(families.UnsupportedForFamily, match=named) as err:
        serve.ServeEngine(params, cfg, _serve_config(**knobs))
    assert "window_moe" in str(err.value) and "ring" in str(err.value)


def test_the_trainer_refuses_the_family_by_name():
    from llama_pipeline_parallel_tpu import train

    with pytest.raises(NotImplementedError, match="window_moe"):
        train.build_model_config({"family": "window_moe", "hidden_size": 32})


# -- the checkpoint ----------------------------------------------------------------

def test_a_checkpoint_of_the_family_round_trips_into_the_serving_loader(tmp_path):
    from llama_pipeline_parallel_tpu.ckpt.checkpoint import (
        CheckpointManager,
        load_module_checkpoint,
    )

    cfg = tiny.config(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = window.init_params(jax.random.PRNGKey(5), cfg)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save_module(3, params, cfg)
    meta = mgr.load_meta(3)
    assert meta["model_config"]["family"] == "window_moe"
    assert meta["model_config"]["pattern"] == [0, 1, 1, 1, 1, 1, 0]
    loaded, loaded_cfg, _, step = load_module_checkpoint(str(tmp_path))
    assert step == 3 and loaded_cfg == cfg and hash(loaded_cfg) == hash(cfg)
    assert dataclasses.asdict(loaded_cfg) == dataclasses.asdict(cfg)
    flat, tree = jax.tree.flatten(params)
    flat_loaded, tree_loaded = jax.tree.flatten(loaded)
    assert tree == tree_loaded
    for a, b in zip(flat, flat_loaded):
        assert a.dtype == b.dtype          # bfloat16 and float32 as stored
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
