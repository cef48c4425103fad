"""The dense state-space block served through the normal path, and the
prefill chunk that carries a slot's state forward, for BOTH configurations of
`models/ssm_moe/` (the dense block and the expert block): a bucket prefilled
in 1, 2 and 4 chunks leaves the logits, the `state`, the `conv` row and the
pages that the whole bucket leaves. float32 on the CPU; logits and stores are
compared at 1e-4 (the chunk's scan meets the same chunk boundaries as the
whole bucket's; the softmax is summed in other tiles)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import granite_tiny
import ssm_tiny
from llama_pipeline_parallel_tpu import serve
from llama_pipeline_parallel_tpu.models import family as families
from llama_pipeline_parallel_tpu.models.ssm_moe import decode as ssm_decode
from llama_pipeline_parallel_tpu.models.ssm_moe import model as ssm
from llama_pipeline_parallel_tpu.utils import trace

TOL = 1e-4
SLOTS, MAX_LEN, PAGE, PAGES = 2, 64, 8, 24
BUCKET = 32
TINY = {"dense_block": granite_tiny, "expert_block": ssm_tiny}
N_SSM, N_SOFTMAX = 4, 1                 # of the dense block's `M-M-*-M-M-`


def _padded(prompt, bucket):
    pad = bucket - len(prompt)
    ids = np.zeros((1, bucket), np.int32)
    ids[0, pad:] = prompt
    mask = np.zeros((1, bucket), np.int32)
    mask[0, pad:] = 1
    return ids, mask


def _cache(cfg):
    return serve.PagedKVCache(cfg, SLOTS, MAX_LEN, PAGE, PAGES)


def _whole(cfg, params, prompt, slot=0):
    """The whole bucket through `prefill_prompt` and the family's splice,
    into a fresh cache."""
    cache = _cache(cfg)
    demand = cache.demand_pages(BUCKET, 4)
    for _ in range(slot + 1):                       # land in `slot`
        assert cache.reserve(demand)
        got = cache.acquire(f"w{_}", demand)
    assert got == slot
    ids, mask = _padded(prompt, BUCKET)
    out = ssm_decode.prefill_prompt(params, jnp.asarray(ids),
                                    jnp.asarray(mask), cfg, BUCKET)
    cache.admit(slot, out)
    return cache, np.asarray(out["logits"][0])


def _chunked(cfg, params, prompt, chunks: int, slot=0, cache=None, start=0):
    """The bucket in `chunks` equal chunks through `paged_prefill_chunk`,
    from chunk `start` on (the ones before it hold nothing but pads), into
    `cache` (a fresh one, or one whose slot another request has left)."""
    cache = cache or _cache(cfg)
    fam = families.family_of(cfg)
    demand = cache.demand_pages(BUCKET, 4)
    assert cache.reserve(demand)
    while cache.acquire(f"c{cache.page_allocations}", demand) != slot:
        assert cache.reserve(demand)
    ids, mask = _padded(prompt, BUCKET)
    cache.reset_mask_row(slot)
    size = BUCKET // chunks
    counters = []
    for c0 in range(start * size, BUCKET, size):
        c1 = c0 + size
        cache.ensure_capacity(slot, c1)
        out = fam.paged_prefill_chunk(
            params, jnp.asarray(ids[:, c0:c1]), jnp.asarray(mask[:, c0:c1]),
            jnp.zeros((1, size), jnp.int32), cache.pool,
            jnp.asarray(cache.page_table[slot]), jnp.int32(slot),
            cache.kv_mask, jnp.int32(c0), cfg)
        cache.pool, cache.kv_mask = out["pool"], out["kv_mask"]
        counters.append(dict(zip(fam.counters, np.asarray(out["counters"]))))
    return cache, np.asarray(out["logits"][0]), counters


def _row(cache, cfg, slot, mask_row):
    """What a slot holds: its recurrent rows, and the keys and values of its
    valid places, gathered through its page table."""
    table = cache.page_table[slot][:BUCKET // PAGE]
    valid = np.asarray(mask_row, bool)
    out = {name: np.asarray(cache.pool[name][:, slot])
           for name in ("state", "conv")}
    for name in ("k", "v"):
        pages = np.asarray(cache.pool[name][:, table])
        rows = pages.reshape(pages.shape[0], BUCKET, cfg.kv_heads,
                             cfg.head_dim)
        out[name] = rows[:, valid]
    return out


def _same_row(a, b):
    for name in ("state", "conv", "k", "v"):
        np.testing.assert_allclose(a[name], b[name], atol=TOL, err_msg=name)


@pytest.fixture(scope="module", params=list(TINY))
def sides(request):
    tiny = TINY[request.param]
    return tiny, tiny.config(), tiny.both_sides()


# -- a chunk that carries the state -------------------------------------------------

@pytest.mark.parametrize("chunks", [1, 2, 4])
def test_a_bucket_in_chunks_is_the_whole_bucket(sides, chunks):
    """Logits, `state`, the `conv` row and the pages, with left pads, for
    both configurations of the family."""
    tiny, cfg, (params, top, layer_fn) = sides
    prompt = np.random.default_rng(2).integers(0, 128, 27).tolist()
    whole, want = _whole(cfg, params, prompt)
    cache, got, counters = _chunked(cfg, params, prompt, chunks)
    np.testing.assert_allclose(got, want, atol=TOL)
    mask = _padded(prompt, BUCKET)[1][0]
    _same_row(_row(cache, cfg, 0, mask), _row(whole, cfg, 0, mask))
    np.testing.assert_array_equal(np.asarray(cache.kv_mask[0, :BUCKET]), mask)
    # and both are the reference's one pass over the unpadded prompt
    ref = tiny.reference.logits_fn(top, layer_fn,
                                   jnp.asarray([prompt], jnp.int32), tiny.MODEL)
    np.testing.assert_allclose(got, np.asarray(ref[0, -1]), atol=TOL)
    # every chunk but the first started from the row the one before left
    layers = cfg.recurrent_layers
    assert [c["state_carries"] for c in counters] == (
        [0] + [layers] * (chunks - 1))
    assert sum(c["ssm_positions"] for c in counters) == len(prompt) * layers
    assert sum(c["kv_entries_read"] for c in counters) == (
        len(prompt) * (len(prompt) + 1) // 2 * cfg.kv_cache_layers)
    row_bytes = sum(cache.pool[name][0, 0].nbytes for name in ("state", "conv"))
    assert all(c["state_bytes_carried"] == c["state_carries"] * row_bytes
               for c in counters)


def test_a_row_starts_behind_its_pad_only_chunks_in_a_slot_another_left(sides):
    """A prompt of 11 in a bucket of four chunks of 8 holds nothing but pads
    in its first two: it starts at the third, as `_start_prefill` starts it,
    in a slot whose row still holds a LONGER request's state, convolution
    inputs and pages; what it leaves is what the whole bucket leaves in a
    fresh cache."""
    tiny, cfg, (params, top, layer_fn) = sides
    rng = np.random.default_rng(5)
    longer, prompt = (rng.integers(0, 128, n).tolist() for n in (31, 11))
    cache, _, _ = _chunked(cfg, params, longer, 4, slot=0)
    held = np.asarray(cache.pool["state"][:, 0])
    assert np.abs(held).max() > 0
    cache.release(0)
    cache, got, counters = _chunked(cfg, params, prompt, 4, slot=0,
                                    cache=cache, start=2)
    whole, want = _whole(cfg, params, prompt)
    np.testing.assert_allclose(got, want, atol=TOL)
    mask = _padded(prompt, BUCKET)[1][0]
    _same_row(_row(cache, cfg, 0, mask), _row(whole, cfg, 0, mask))
    assert [c["state_carries"] for c in counters] == [0, cfg.recurrent_layers]


def test_a_chunk_that_drops_the_carried_state_is_seen(sides):
    """The second of two chunks run behind a mask row that says nothing came
    before it starts from zeros: the comparison that passes with the carried
    row fails by a hundred times its tolerance."""
    tiny, cfg, (params, top, layer_fn) = sides
    prompt = np.random.default_rng(2).integers(0, 128, 30).tolist()
    _, want = _whole(cfg, params, prompt)
    cache, got, _ = _chunked(cfg, params, prompt, 2)
    np.testing.assert_allclose(got, want, atol=TOL)
    # the same second chunk, its slot's mask row zeroed in front of it
    cache = _cache(cfg)
    fam = families.family_of(cfg)
    assert cache.reserve(8) and cache.acquire("r", 8) == 0
    ids, mask = _padded(prompt, BUCKET)
    half = BUCKET // 2
    for c0 in (0, half):
        cache.ensure_capacity(0, c0 + half)
        if c0:
            cache.reset_mask_row(0)
        out = fam.paged_prefill_chunk(
            params, jnp.asarray(ids[:, c0:c0 + half]),
            jnp.asarray(mask[:, c0:c0 + half]),
            jnp.zeros((1, half), jnp.int32), cache.pool,
            jnp.asarray(cache.page_table[0]), jnp.int32(0), cache.kv_mask,
            jnp.int32(c0), cfg)
        cache.pool, cache.kv_mask = out["pool"], out["kv_mask"]
    assert np.max(np.abs(np.asarray(out["logits"][0]) - want)) > 100 * TOL


# -- prefill, then ticks ------------------------------------------------------------

def test_prefill_then_ticks_are_the_references_one_pass():
    """Three requests over two slots, one prefilled whole and two in chunks,
    the third into the slot a LONGER request left: at every tick the logits
    of every decoding row are the reference's one pass over that request's
    tokens so far, and the tick's counters are the rows' own."""
    tiny = granite_tiny
    cfg = tiny.config()
    params, top, layer_fn = tiny.both_sides()
    cache = _cache(cfg)
    tick = jax.jit(ssm_decode.tick_logits, static_argnames=("cfg",))
    rng = np.random.default_rng(4)
    plan = [  # (admit at tick, slot, prompt, new tokens, chunks)
        (0, 0, rng.integers(0, 128, 29).tolist(), 8, 4),
        (2, 1, rng.integers(0, 128, 5).tolist(), 12, 0),
        (9, 0, rng.integers(0, 128, 12).tolist(), 7, 2)]
    rows, done = {}, []
    for t in range(18):
        for at, slot, prompt, new, chunks in plan:
            if at != t:
                continue
            if chunks:
                cache, logits, _ = _chunked(cfg, params, prompt, chunks,
                                            slot=slot, cache=cache)
                bucket = BUCKET
            else:
                bucket = 8
                ids, mask = _padded(prompt, bucket)
                demand = cache.demand_pages(bucket, new)
                assert cache.reserve(demand)
                assert cache.acquire(f"r{at}", demand) == slot
                out = ssm_decode.prefill_prompt(
                    params, jnp.asarray(ids), jnp.asarray(mask), cfg, bucket)
                cache.admit(slot, out)
                logits = np.asarray(out["logits"][0])
            rows[slot] = {"prompt": prompt, "logits": [logits],
                          "seq": list(prompt) + [int(np.argmax(logits))],
                          "left": new - 1, "write": bucket}
        if not rows:
            continue
        token, write, active = (np.zeros(SLOTS, np.int32) for _ in range(3))
        for slot, r in rows.items():
            token[slot], write[slot], active[slot] = r["seq"][-1], r["write"], 1
            cache.ensure_capacity(slot, r["write"] + 1)
        logits, cache.pool, cache.kv_mask, counters = tick(
            params, jnp.asarray(token), cache.pool,
            jnp.asarray(cache.page_table), jnp.asarray(write), cache.kv_mask,
            jnp.asarray(active), cfg)
        counted = dict(zip(ssm.COUNTERS, np.asarray(counters)))
        assert counted["ssm_rows"] == len(rows) * N_SSM
        # a decoding row reads its prompt, what it decoded and itself
        assert counted["kv_entries_read"] == N_SOFTMAX * sum(
            len(r["seq"]) for r in rows.values())
        assert counted["ssm_positions"] == counted["state_carries"] == 0
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
        want = tiny.reference.logits_fn(
            top, layer_fn, jnp.asarray([r["seq"][:-1]]), tiny.MODEL)[0]
        first = len(r["prompt"]) - 1
        got = np.stack(r["logits"])
        np.testing.assert_allclose(got, want[first:first + len(got)], atol=TOL)


# -- the engine ---------------------------------------------------------------------

def test_the_engine_serves_short_and_chunked_prompts_in_one_queue():
    """Six requests over two slots, prompts of 3 to 30 tokens in buckets of
    8, 16 and 32 with a chunk of 8: the small bucket whole, the others in
    chunks less their pad-only ones. Every served token is the reference's
    own first choice, and the program's counters meet the host's counts from
    the lengths alone, exactly."""
    tiny = granite_tiny
    cfg = tiny.config()
    params, top, layer_fn = tiny.both_sides()
    engine = serve.ServeEngine(params, cfg, serve.ServeConfig(
        max_slots=SLOTS, max_len=MAX_LEN, prompt_buckets=(8, 16, 32),
        page_size=PAGE, num_pages=PAGES, prefill_chunk_tokens=8,
        decode_span_every=4, max_queue=8))
    spans = []
    listener = lambda rec: spans.append(dict(rec))
    trace.recorder().add_listener(listener)
    try:
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 128, n).tolist()
                   for n in (5, 30, 11, 3, 20, 9)]
        budgets = [9, 6, 12, 4, 8, 7]
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
                                            tiny.MODEL, 8)
    assert max(max(g) for g in gaps) <= TOL
    assert engine.slots.reused_slot_count() >= 1

    ticks = [s for s in spans if s["name"] == "serve_decode_step"]
    units = [s for s in spans if s["name"] == "serve_prefill"]
    total = {k: sum(s[k] for s in ticks) for k in ssm.COUNTERS}
    decoded = sum(n - 1 for n in budgets)
    assert sum(s["tokens"] for s in ticks) == decoded
    assert total["ssm_rows"] == decoded * N_SSM
    # tick j of a request of n prompt tokens sees n + j places
    contexts = sum((n - 1) * len(p) + (n - 1) * n // 2
                   for p, n in zip(prompts, budgets))
    assert total["kv_entries_read"] == contexts * N_SOFTMAX
    assert total["ssm_positions"] == total["state_carries"] == 0
    # the units: every valid prompt position is scanned once a layer, and a
    # chunk that is not its row's first carries the row in
    assert sum(s["ssm_positions"] for s in units) == (
        sum(len(p) for p in prompts) * N_SSM)
    chunked = [s for s in units if s["chunk"] < s["bucket"]]
    later = [s for s in chunked if "chunks_skipped" not in s]
    assert later and len(later) < len(chunked)
    assert sum(s["state_carries"] for s in units) == len(later) * N_SSM
    assert all(s["state_carries"] == 0 for s in units if s not in later)
    row_bytes = engine.slots.recurrent_store_bytes // SLOTS
    assert sum(s["state_bytes_carried"] for s in units) == (
        len(later) * row_bytes)
    snap = engine.metrics_snapshot()
    assert snap["prefill_state_carries_total"] == len(later)
    assert snap["prefill_chunks_skipped_total"] == sum(
        s.get("chunks_skipped", 0) for s in units) > 0


def test_the_summary_tool_prints_the_familys_counters(tmp_path, capsys):
    import sys

    sys.path.insert(0, str(granite_tiny.REPO))
    from tools import trace_summary

    line = dict(ssm_rows=8, ssm_positions=0, kv_entries_read=40,
                state_carries=0, state_bytes_carried=0)
    unit = dict(ssm_rows=32, ssm_positions=32, kv_entries_read=36,
                state_carries=4, state_bytes_carried=4096)
    path = tmp_path / "spans.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in (
        {"name": "serve_decode_step", **line},
        {"name": "serve_decode_step", **line},
        {"name": "serve_prefill", **unit},
        {"name": "serve_prefill", "bucket": 8})))       # another family's
    found = trace_summary.recurrent_counters(str(path))
    assert found["ticks"]["ssm_rows"] == 16
    assert found["ticks"]["kv_entries_read"] == 80
    assert found["units"] == unit
    other = tmp_path / "other.jsonl"
    other.write_text(json.dumps({"name": "serve_decode_step", "ticks": 1}) + "\n")
    assert trace_summary.recurrent_counters(str(other)) is None


# -- what the family states ---------------------------------------------------------

def test_both_configurations_chunk_and_a_packed_page_has_its_own_splice():
    dense, expert = (families.family_of(TINY[k].config()) for k in TINY)
    for fam in (dense, expert):
        assert fam.name == "ssm_moe" and fam.recurrent
        assert fam.paged_prefill_chunk is ssm_decode.paged_prefill_chunk
        assert fam.paged_prefill_span is None
        assert fam.counters == ssm.COUNTERS
    assert dense.write_pages is ssm_decode.write_packed_pages
    cache = _cache(granite_tiny.config())
    # two KV heads of 64 a row: a page of 8 places is a matrix [8, 128]
    assert cache.pool["k"].shape == (N_SOFTMAX, PAGES + 1, PAGE, 128)
    assert cache.pool["state"].shape == (N_SSM, SLOTS, 8, 64, 16)
    assert cache.page_bytes() == 2 * N_SOFTMAX * PAGE * 2 * 64 * 4


@pytest.mark.parametrize("which", list(TINY))
def test_chunks_are_accepted_and_the_rest_is_still_refused_by_name(which):
    cfg = TINY[which].config()
    fam = families.family_of(cfg)
    fam.check_serve_config("fp", 8, False)              # chunks: no refusal
    for knobs, named in ((("fp", 8, True), "prefix_cache"),
                         (("int8", 0, False), "kv_quant: int8")):
        with pytest.raises(families.UnsupportedForFamily, match=named) as err:
            fam.check_serve_config(*knobs)
        assert "ssm_moe" in str(err.value)
        assert "prefill_chunk_tokens" not in str(err.value)


def test_the_chunk_names_its_work():
    """The two scopes round a chunk's read and write of its slot's row, and
    the names the dense family gives the rest, are in the path of some
    operation of the chunk program."""
    cfg = granite_tiny.config()
    cache = _cache(cfg)
    params = jax.eval_shape(lambda: ssm.init_params(jax.random.PRNGKey(0), cfg))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    text = ssm_decode.paged_prefill_chunk.lower(
        params, i32(1, 8), i32(1, 8), i32(1, 8), cache.pool,
        i32(MAX_LEN // PAGE), i32(), cache.kv_mask, i32(), cfg).as_text(
            debug_info=True)
    for name in trace.STATE_CARRY_SCOPES + (
            trace.SSM_PROJ, trace.SSM_CONV, trace.SSM_SCAN, trace.SSM_NORM,
            trace.SCOPE_KV_WRITE, trace.SCOPE_KV_GATHER, trace.SCOPE_ATTN_QKV,
            trace.SCOPE_ATTN_CORE, trace.SCOPE_ATTN_OUT, trace.SCOPE_MLP,
            trace.SCOPE_LM_HEAD):
        assert f"/{name}/" in text, name
    assert f"/{trace.SSM_STEP}/" not in text
