"""The compressed-window block served through the normal path: `ServeEngine`
/ `PagedKVCache` take its programs and what a slot's pages ARE from
`models/family.py`; whole and chunked prefill, then decode, through the two
kinds of page (a ring of window pages, summary pages that grow) are the plain
reference's one forward pass over the unpadded sequence; the manager's
demand, ring reuse and reservation invariant hold under random admit and
release; what the family cannot run yet is refused by name; a checkpoint of
the family loads through the loader tools/serve.py uses.

float32 on the CPU at a tiny size (`eva_tiny.py`: window 32, chunk 4, pages
of 8, so a request of a hundred positions crosses every boundary); logits
are compared with the reference's at 1e-4 (both sides float32; they differ in
the order of sums, and a pooled entry is made once here and in one expression
there)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import eva_tiny as tiny
import tick_ahead
from benchmark.reference import eva_decoder
from llama_pipeline_parallel_tpu import serve
from llama_pipeline_parallel_tpu.models import family as families
from llama_pipeline_parallel_tpu.models.eva import decode
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.serve import pages
from llama_pipeline_parallel_tpu.utils import trace

TOL = 1e-4
W, C, PAGE = tiny.WINDOW, tiny.CHUNK, tiny.PAGE
SLOTS, MAX_LEN, PAGES = 2, 160, 40
RING, N_SUM = W // PAGE, -(-MAX_LEN // (PAGE * C))      # 4 and 5


def _model_dict(cfg):
    return {k: v for k, v in dataclasses.asdict(cfg).items()
            if isinstance(v, (int, float))}


def _cache(cfg, slots=SLOTS, num_pages=PAGES):
    return serve.PagedKVCache(cfg, slots, MAX_LEN, PAGE, num_pages)


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
        out = decode.prefill_prompt(params, jnp.asarray(ids),
                                    jnp.asarray(mask), cfg, bucket)
        cache.admit(slot, out)
        return out, [out["counters"]]
    cache.reset_mask_row(slot)
    counters = []
    for c0 in range(0, bucket, chunk):
        c1 = c0 + chunk
        cache.ensure_capacity(slot, c1)
        out = decode.paged_prefill_chunk(
            params, jnp.asarray(ids[:, c0:c1]), jnp.asarray(mask[:, c0:c1]),
            jnp.asarray(positions[:, c0:c1]), cache.pool,
            jnp.asarray(cache.page_table[slot]), jnp.int32(slot),
            cache.kv_mask, jnp.int32(c0), cfg)
        cache.pool, cache.kv_mask = out["pool"], out["kv_mask"]
        counters.append(out["counters"])
    return out, counters


_TICK = jax.jit(decode.tick_logits, static_argnames=("cfg",))


def _decode(params, cfg, cache, rows, steps):
    """`steps` ticks over `rows` ({slot: {"seq", "write", "logits"}}), greedy;
    returns the counters of every tick."""
    counted = []
    for _ in range(steps):
        token, write, pos, active = (np.zeros(cache.max_slots, np.int32)
                                     for _ in range(4))
        for slot, r in rows.items():
            token[slot], write[slot], active[slot] = r["seq"][-1], r["write"], 1
            pos[slot] = len(r["seq"]) - 1
            cache.ensure_capacity(slot, r["write"] + 1)
        logits, cache.pool, cache.kv_mask, counters = _TICK(
            params, jnp.asarray(token), cache.pool,
            jnp.asarray(cache.page_table), jnp.asarray(pos),
            jnp.asarray(write), cache.kv_mask, jnp.asarray(active), cfg)
        counted.append((counters.tolist(), [int(p) for p in pos[active > 0]]))
        for slot, r in rows.items():
            r["logits"].append(np.asarray(logits[slot]))
            r["seq"].append(int(np.argmax(logits[slot])))
            r["write"] += 1
    return counted


def _admit(params, cfg, cache, name, prompt, bucket, new, chunk):
    demand = cache.demand_pages(bucket, new)
    assert cache.reserve(demand)
    slot = cache.acquire(name, demand)
    out, counters = _prefill(params, cfg, cache, slot, prompt, bucket, chunk)
    row = {"n": len(prompt), "seq": list(prompt), "write": bucket,
           "logits": [np.asarray(out["logits"][0])]}
    row["seq"].append(int(np.argmax(out["logits"][0])))
    return slot, row, counters


def _against_the_reference(params, cfg, row):
    ref = np.asarray(eva_decoder.sequence_logits(
        params, row["seq"], _model_dict(cfg), 256))
    got = np.stack(row["logits"])
    np.testing.assert_allclose(
        got, ref[row["n"] - 1:row["n"] - 1 + len(got)], atol=TOL, rtol=TOL)


# -- prefill + ticks through the pages against one forward pass -------------------

@pytest.mark.parametrize("n,bucket,chunk,new", [
    (73, 96, 32, 40),     # left pad 23; ends inside a chunk; decodes across 96
    (61, 64, 0, 10),      # whole-bucket prefill spliced by `write_pages`
    (64, 64, 32, 5),      # ends on a window's edge: the next token opens one
    (60, 64, 32, 9),      # ends on a chunk's edge, decodes across the window's
    (30, 32, 16, 70),     # chunks smaller than the window; two windows decoded
    (95, 96, 32, 3),      # the tick completes the window the prefill left open
    (5, 16, 0, 30),       # a bucket smaller than the window, whole
    (40, 96, 32, 4),      # a first unit that is all pads
])
def test_prefill_then_ticks_are_the_references_one_forward_pass(
        n, bucket, chunk, new):
    cfg = tiny.tiny_config()
    params = tiny.tiny_params(cfg)
    cache = _cache(cfg)
    prompt = np.random.default_rng(n).integers(0, cfg.vocab_size, n).tolist()
    slot, row, _ = _admit(params, cfg, cache, "r", prompt, bucket, new, chunk)
    _decode(params, cfg, cache, {slot: row}, new - 1)
    _against_the_reference(params, cfg, row)
    # the request never held more than it reserved, and at its end holds
    # what the family says a row of its places holds
    assert cache.pages_used <= cache.demand_pages(bucket, new)
    assert cache.pages_used == len(decode.table_columns(
        cfg, -(-(bucket + new - 1) // PAGE) * PAGE, MAX_LEN, PAGE))


@pytest.mark.parametrize("bucket", [32, 64, 96])
def test_a_chunked_prefill_is_a_whole_one(bucket):
    """The same prompt whole and in units of a window and of half a window:
    the last place's logits and every page a tick reads afterwards agree."""
    cfg = tiny.tiny_config()
    params = tiny.tiny_params(cfg)
    n = bucket - 5
    prompt = np.random.default_rng(bucket).integers(0, cfg.vocab_size,
                                                    n).tolist()
    rows = []
    for chunk in (0, 32, 16):
        cache = _cache(cfg)
        slot, row, counters = _admit(params, cfg, cache, "r", prompt, bucket,
                                     6, chunk)
        total = np.sum([np.asarray(c) for c in counters], axis=0)
        _decode(params, cfg, cache, {slot: row}, 5)
        rows.append((row, total))
    for row, total in rows[1:]:
        np.testing.assert_allclose(np.stack(row["logits"]),
                                   np.stack(rows[0][0]["logits"]),
                                   atol=TOL, rtol=TOL)
        # however it is cut, the prompt's queries read the same entries and
        # the same summaries are written
        assert total.tolist() == rows[0][1].tolist()


def test_two_slots_decode_together_and_a_reused_slot_sees_nothing_old():
    """Requests of different lengths in two slots, admitted at different
    ticks, one into the slot another left (its ring and summary pages hold
    the last occupant's entries): every row's logits are the reference's, and
    every tick's counters the host's from the positions alone."""
    cfg = tiny.tiny_config()
    params = tiny.tiny_params(cfg)
    cache = _cache(cfg)
    rng = np.random.default_rng(11)
    plan = [  # (admit at tick, prompt length, bucket, new tokens, chunk)
        (0, 90, 96, 12, 32), (2, 20, 32, 40, 16), (14, 50, 64, 20, 32)]
    rows, done, slots_used = {}, [], []
    for t in range(44):
        for at, n, bucket, new, chunk in plan:
            if at == t:
                slot, row, _ = _admit(
                    params, cfg, cache, f"r{at}",
                    rng.integers(0, cfg.vocab_size, n).tolist(), bucket, new,
                    chunk)
                row["left"] = new - 1
                rows[slot] = row
                slots_used.append(slot)
        if not rows:
            continue
        (counters, positions), = _decode(params, cfg, cache, rows, 1)
        L = cfg.num_hidden_layers
        assert counters == [
            L * sum(p % W + 1 for p in positions),
            L * sum(p // W * (W // C) for p in positions),
            L * (W // C) * sum(p % W == W - 1 for p in positions)]
        for slot in list(rows):
            rows[slot]["left"] -= 1
            if not rows[slot]["left"]:
                done.append(rows.pop(slot))
                cache.release(slot)
    assert len(done) == 3 and slots_used == [0, 1, 0]
    for row in done:
        _against_the_reference(params, cfg, row)
    assert cache.pages_used == 0 and cache.pages_reserved == 0


def test_the_tick_pools_a_finished_window_before_its_ring_is_overwritten():
    """A row decodes from inside one window to inside the next: the tick
    that writes the window's last position writes its eight summaries, once,
    and they are the reference's pooling of that window's keys (read back
    from the slot's summary page)."""
    cfg = tiny.tiny_config()
    params = tiny.tiny_params(cfg)
    cache = _cache(cfg)
    prompt = np.random.default_rng(2).integers(0, cfg.vocab_size, 58).tolist()
    slot, row, _ = _admit(params, cfg, cache, "r", prompt, 64, 12, 32)
    before = np.asarray(cache.pool["k"][:, cache.page_table[slot, 1]])
    counted = _decode(params, cfg, cache, {slot: row}, 11)
    written = [c[0][2] for c in counted]
    # positions 58 .. 68 are taken in: 63 completes the second window
    assert written == [0] * 5 + [cfg.num_hidden_layers * (W // C)] + [0] * 5
    page = np.asarray(cache.pool["k"][:, cache.page_table[slot, 1]])
    assert np.abs(page - before).max() > 0
    _against_the_reference(params, cfg, row)


# -- what a slot's pages are ------------------------------------------------------

def test_the_family_states_a_slots_table_and_demand():
    cfg = tiny.tiny_config()
    fam = families.family_of(cfg)
    assert fam.name == "eva" and not fam.recurrent
    assert fam.counters == decode.COUNTERS
    assert fam.table_width(cfg, MAX_LEN, PAGE) == N_SUM + RING == 9
    cols = lambda tokens: fam.table_columns(cfg, tokens, MAX_LEN, PAGE).tolist()
    ring = [N_SUM + i for i in range(RING)]
    assert cols(1) == ring[:1] and cols(8) == ring[:1] and cols(9) == ring[:2]
    assert cols(31) == ring                       # the first window: no summary
    assert cols(32) == [0] + ring                 # one finished: one summary page
    assert cols(100) == [0, 1, 2] + ring
    assert cols(160) == [0, 1, 2, 3, 4] + ring
    cache = _cache(cfg)
    assert cache.pages_per_slot == 9 and cache.page_table.shape == (SLOTS, 9)
    # 32 + 24 pages at the published sizes where a cache of every position
    # holds 400
    big = type(cfg)(num_hidden_layers=1)
    assert fam.table_width(big, 25600, 64) == 57
    assert len(fam.table_columns(big, 25600, 25600, 64)) == 56
    assert families.row_table_width(big, 25600, 64) == 400
    with pytest.raises(ValueError, match="page_size 5 must divide"):
        fam.table_width(cfg, MAX_LEN, 5)


def test_the_other_families_state_todays_values_through_the_same_two():
    dense = LlamaConfig.tiny()
    fam = families.family_of(dense)
    assert fam.table_width(dense, 64, 4) == 16
    assert fam.table_columns(dense, 9, 64, 4).tolist() == [0, 1, 2]
    cache = serve.PagedKVCache(dense, 2, 64, 4, 20)
    for bucket, new in ((8, 1), (8, 2), (8, 5), (8, 6), (16, 30)):
        assert cache.demand_pages(bucket, new) == pages.page_demand(
            bucket, new, 4)
    with pytest.raises(ValueError, match="cannot hold even one full-length "
                                         "request \\(16 pages\\)"):
        serve.PagedKVCache(dense, 2, 64, 4, 15)


def test_demand_growth_and_the_ring_are_what_the_manager_backs():
    cfg = tiny.tiny_config()
    cache = _cache(cfg)
    assert cache.demand_pages(96, 40) == len(decode.table_columns(
        cfg, 136, MAX_LEN, PAGE)) == 4 + RING
    assert cache.demand_pages(16, 1) == 2 and cache.demand_pages(32, 1) == 5
    demand = cache.demand_pages(64, 70)
    assert cache.reserve(demand)
    slot = cache.acquire("r", demand)
    grown = [cache.ensure_capacity(slot, t) for t in range(1, 134)]
    # a page a page of places while the first window fills, then one summary
    # page a finished window and NOTHING for the ring, which is reused
    assert sum(grown) == demand == 4 + RING
    at = [t + 1 for t, g in enumerate(grown) if g]
    assert at == [1, 9, 17, 25, 57, 89, 121]
    row = cache.page_table[slot]
    assert (row[N_SUM:] != cache.garbage_page).all()
    assert (row[:4] != cache.garbage_page).all() and row[4] == cache.garbage_page
    with pytest.raises(RuntimeError, match="page accounting bug"):
        cache.ensure_capacity(slot, 160)
    cache.release(slot)
    assert (cache.page_table[slot] == cache.garbage_page).all()
    assert cache.pages_used == 0
    with pytest.raises(ValueError, match="cannot hold even one full-length "
                                         "request \\(9 pages\\)"):
        _cache(cfg, num_pages=8)
    _cache(cfg, num_pages=9)      # 160 places: 20 pages of places, 9 of this


@pytest.mark.parametrize("seed", range(6))
def test_an_admitted_request_can_always_finish(seed):
    """Random admit / grow / release over a pool too small for everyone:
    a refusal happens only at `reserve`; whoever was admitted grows to its
    last write without the pool running dry, pages are never shared, and a
    release returns them all."""
    cfg = tiny.tiny_config()
    cache = _cache(cfg, slots=4, num_pages=22)
    rng = np.random.default_rng(seed)
    live, refused, finished = {}, 0, 0
    for step in range(300):
        if rng.random() < 0.3 and cache.free_count:
            bucket = int(rng.choice([16, 32, 64, 96]))
            new = int(rng.integers(1, 60))
            demand = cache.demand_pages(bucket, new)
            if not cache.reserve(demand):
                refused += 1
                continue
            slot = cache.acquire(f"r{step}", demand)
            cache.ensure_capacity(slot, bucket)
            live[slot] = [bucket, bucket + new - 1]
        for slot in list(live):
            at, last = live[slot]
            if at >= last:
                cache.release(slot)
                del live[slot]
                finished += 1
                continue
            live[slot][0] = at + int(rng.integers(1, 4))
            cache.ensure_capacity(slot, min(live[slot][0], last))
        held = cache.page_table[cache.page_table != cache.garbage_page]
        assert len(set(held.tolist())) == len(held) == cache.pages_used
        assert cache.pages_reserved <= cache.num_pages
        assert cache.pages_used <= cache.pages_reserved
    assert refused > 0 and finished > 10


# -- the engine ---------------------------------------------------------------------

def _engine(cfg, params, **kw):
    base = dict(max_slots=2, max_len=MAX_LEN, prompt_buckets=(16, 32, 64, 96),
                page_size=PAGE, num_pages=PAGES, prefill_chunk_tokens=32,
                max_queue=8)
    base.update(kw)
    return serve.ServeEngine(params, cfg, serve.ServeConfig(**base))


def _spans(engine, requests):
    """Run `requests` ([(prompt, new tokens)]) through a stepped engine;
    returns (tokens by request, the spans it emitted)."""
    seen = []
    listener = lambda rec: seen.append(dict(rec))
    trace.recorder().add_listener(listener)
    try:
        handles = [engine.submit(serve.ServeRequest(
            input_ids=p, seed=0, gen=families.GenerationConfig(
                max_new_tokens=n, temperature=0.0))) for p, n in requests]
        for _ in range(400):
            if not engine.step():
                break
        engine.shutdown()
    finally:
        trace.recorder().remove_listener(listener)
    return [h.result(timeout=5.0) for h in handles], seen


def test_the_engine_serves_the_family_and_its_spans_carry_the_counters():
    cfg = tiny.tiny_config()
    params = tiny.tiny_params(cfg)
    rng = np.random.default_rng(1)
    requests = [(rng.integers(0, cfg.vocab_size, n).tolist(), new)
                for n, new in ((70, 30), (12, 8), (33, 40))]
    engine = _engine(cfg, params)
    assert engine.slots.page_table.shape == (2, N_SUM + RING)
    tokens, spans = _spans(engine, requests)
    model = _model_dict(cfg)
    for (prompt, new), served in zip(requests, tokens):
        assert len(served) == new
        gaps = eva_decoder.served_token_gaps(params, prompt, served, model, 256)
        assert max(gaps) < TOL            # greedy: the reference's own choices
    ticks = [s for s in spans if s["name"] == "serve_decode_step"]
    units = [s for s in spans if s["name"] == "serve_prefill"]
    assert ticks and all(set(decode.COUNTERS) <= set(s) for s in ticks + units)
    positions = [p for prompt, new in requests
                 for p in range(len(prompt), len(prompt) + new - 1)]
    L = cfg.num_hidden_layers
    assert sum(s["eva_window_visible"] for s in ticks) == L * sum(
        p % W + 1 for p in positions)
    assert sum(s["eva_summary_visible"] for s in ticks) == L * sum(
        p // W * (W // C) for p in positions)
    # every window a request finishes is pooled once, by a unit or a tick
    finished = sum((len(p) + new - 1) // W for p, new in requests)
    assert sum(s["eva_summaries_written"] for s in ticks + units) == (
        L * (W // C) * finished)
    assert engine.slots.pages_used == 0


def test_a_tick_in_flight_serves_the_family_as_the_serial_order_does():
    """Prompts of one to three units, greedy and sampled rows that cross
    window, chunk and page edges while they decode (a tick then pools the
    window it completes), one ended by its eos, with the engine's tick in
    flight and in the serial order (`tests/tick_ahead.py`): the same
    streams, bit for bit, and the two exact counts over every row-tick the
    device ran, the overrun among them. The overrun writes the ring page of
    a slot its row has left; the request admitted there next is served as
    the serial order serves it."""
    cfg = tiny.tiny_config()
    params = tiny.tiny_params(cfg)
    make = lambda: _engine(cfg, params, num_pages=2 * PAGES,
                           decode_span_every=4)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (70, 12, 33, 25)]
    budgets = [30, 8, 40, 2]
    knobs = [dict(temperature=0.9), {}, dict(temperature=1.2, top_k=9), {}]
    plain = tick_ahead.run(make(), tick_ahead.requests_of(
        prompts, budgets, knobs), serially=True)["tokens"]
    assert [len(t) for t in plain] == budgets
    eos = {0: tick_ahead.eos_of(plain[0], least=20)[1]}
    serial, ahead = tick_ahead.both_orders(
        make, lambda: tick_ahead.requests_of(prompts, budgets, knobs, eos))
    assert ahead["sums"]["rows_overrun"] == 1
    assert 20 < len(ahead["tokens"][0]) < 30
    L = cfg.num_hidden_layers
    for result, overran in ((serial, ()), (ahead, (0,))):
        # a row-tick at context c reads position c - 1's window and summaries
        positions = [c - 1 for c in tick_ahead.contexts_run(
            result, prompts, overran)]
        assert result["sums"]["tokens"] == len(positions)
        assert result["sums"]["eva_window_visible"] == L * sum(
            p % W + 1 for p in positions)
        assert result["sums"]["eva_summary_visible"] == L * sum(
            p // W * (W // C) for p in positions)


def test_the_engines_default_pool_is_one_full_length_request_a_slot():
    cfg = tiny.tiny_config()
    engine = _engine(cfg, tiny.tiny_params(cfg), num_pages=None)
    assert engine.slots.num_pages == 2 * (N_SUM + RING)
    scfg = engine.serve_cfg
    assert scfg.pool_pages(cfg) == 18 and scfg.resolved_num_pages == 40
    dense = LlamaConfig.tiny()
    assert scfg.pool_pages(dense) == scfg.resolved_num_pages


@pytest.mark.parametrize("kwargs,names", [
    (dict(kv_quant="int8"), ["kv_quant: int8"]),
    (dict(prefix_cache=True), ["prefix_cache", "the ring as it stood at the "
                               "divergence point"]),
    (dict(kv_quant="int8", prefix_cache=True), ["kv_quant: int8",
                                                "prefix_cache"]),
])
def test_what_the_family_cannot_run_is_refused_by_name(kwargs, names):
    cfg = tiny.tiny_config()
    with pytest.raises(families.UnsupportedForFamily) as e:
        _engine(cfg, tiny.tiny_params(cfg), **kwargs)
    assert "the eva family" in str(e.value)
    for name in names:
        assert name in str(e.value)


def test_a_unit_longer_than_the_window_is_refused_by_name():
    cfg = tiny.tiny_config()
    params = tiny.tiny_params(cfg)
    cache = _cache(cfg)
    ids, mask, positions = _padded(list(range(60)), 64)
    with pytest.raises(ValueError, match="longer than the window"):
        decode.paged_prefill_chunk(
            params, jnp.asarray(ids), jnp.asarray(mask), jnp.asarray(positions),
            cache.pool, jnp.asarray(cache.page_table[0]), jnp.int32(0),
            cache.kv_mask, jnp.int32(0), cfg)


def test_training_refuses_the_family_by_name():
    from llama_pipeline_parallel_tpu.train import build_model_config

    with pytest.raises(NotImplementedError, match="'eva' family"):
        build_model_config({"family": "eva"})


def test_a_checkpoint_of_the_family_loads_through_the_serving_loader(tmp_path):
    from llama_pipeline_parallel_tpu.ckpt.checkpoint import (
        CheckpointManager,
        load_module_checkpoint,
    )

    cfg = tiny.tiny_config()
    params = tiny.tiny_params(cfg)
    CheckpointManager(str(tmp_path)).save_module(3, params, cfg)
    loaded, got_cfg, _, step = load_module_checkpoint(str(tmp_path))
    assert step == 3 and got_cfg == cfg and got_cfg.family == "eva"
    same = jax.tree.map(lambda a, b: bool((a == b).all()), params, loaded)
    assert all(jax.tree.leaves(same))
    meta = families.config_from_meta(
        {"family": "eva", **{k: v for k, v in dataclasses.asdict(cfg).items()
                             if k not in ("dtype", "param_dtype")},
         "dtype": "float32", "param_dtype": "float32"})
    assert meta == cfg


def test_the_stores_stay_in_place_in_the_traced_programs():
    """The pool is donated to the tick and to a chunk and comes back as the
    same buffers' worth: nothing pool-sized is made beside it (the CPU
    interpreter copies a kernel's operands, so this reads the lowered
    programs' aliases, as the other families' tests do)."""
    cfg = tiny.tiny_config()
    params = tiny.tiny_params(cfg)
    cache = _cache(cfg)
    S, width = cache.page_table.shape
    z = jnp.zeros((S,), jnp.int32)
    tick = decode.paged_decode_step.lower(
        params, z, cache.pool, jnp.asarray(cache.page_table), z, z,
        cache.kv_mask, z, jnp.zeros((S, 2), jnp.uint32),
        jnp.zeros((S,), jnp.float32), z, jnp.ones((S,), jnp.float32), cfg)
    assert tick.as_text().count("tf.aliasing_output") >= 3   # k, v, the mask
    text = tick.as_text(debug_info=True)
    for scope in trace.EVA_SCOPES[:3]:
        assert scope in text, scope
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    chunk = decode.paged_prefill_chunk.lower(
        params, i32(1, 32), i32(1, 32), i32(1, 32), cache.pool, i32(width),
        jnp.int32(0), cache.kv_mask, jnp.int32(0), cfg)
    assert chunk.as_text().count("tf.aliasing_output") >= 3
    text = chunk.as_text(debug_info=True)
    for scope in (trace.EVA_POOL, trace.EVA_SUMMARY_WRITE,
                  trace.EVA_ATTN_PREFILL, "eva_prefill_attn"):
        assert scope in text, scope
