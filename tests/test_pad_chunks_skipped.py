"""A cold chunked row starts its prefill at the first chunk that holds a real
token (`serve/engine.py` `_start_prefill`): the chunks of nothing but left
pads in front of it are never handed to the device, and what they would have
left in the slot's stores, a previous occupant's pages, rings, index keys and
summaries, stays there unseen behind the slot's zeroed mask row. Every served
token and the prefill's final logits are, bit for bit, those of the family's
own programs run by hand over EVERY chunk of the bucket on a fresh cache.
float32 on the CPU at tiny sizes."""

import jax.numpy as jnp
import numpy as np
import pytest

import eva_tiny
import serving_tiny
import window_tiny
from llama_pipeline_parallel_tpu import serve
from llama_pipeline_parallel_tpu.models import family as families
from llama_pipeline_parallel_tpu.utils import trace

# one bucket of four chunks and one slot, so every request follows another
# into the same slot: (family of serving_tiny, the engine's shape, vocabulary,
# the prompt that fills the bucket and what it decodes, the short prompt)
_FOUR = dict(prompt_buckets=(32,), prefill_chunk_tokens=8, max_len=48)
CASES = {
    "llama": ("llama", dict(_FOUR, page_size=8, num_pages=12), 128, 12, 5),
    # the exact window is 32 positions: the long row finishes two of them, so
    # the slot's summary pages and its whole ring hold its entries
    "eva": ("eva", dict(prompt_buckets=(64,), prefill_chunk_tokens=16,
                        max_len=96, page_size=eva_tiny.PAGE, num_pages=24),
            48, 24, 10),
    # a window of 5 in a ring of 6, `index_topk` 8
    "latent_moe": ("latent_moe.dots3",
                   dict(_FOUR, page_size=4, num_pages=24), 128, 12, 4),
    "latent_moe.one_kind": ("latent_moe.a.x-k1",
                            dict(_FOUR, page_size=4, num_pages=24), 128, 12,
                            5),
    # a window and a ring of 8
    "window_moe": ("window_moe", dict(_FOUR, page_size=window_tiny.PAGE,
                                      num_pages=24), 128, 12, 5),
    # a recurrent state and the convolution's inputs carried from chunk to
    # chunk: the long row leaves its own in the slot's row of the store
    "ssm_moe": ("ssm_moe", dict(_FOUR, page_size=8, num_pages=12), 128, 12,
                5),
    "ssm_moe.dense": ("ssm_moe.dense", dict(_FOUR, page_size=8, num_pages=12),
                      128, 12, 5),
}


def _engine(which: str, **knobs):
    family, shape = CASES[which][:2]
    cfg, params, _ = serving_tiny.build(family)
    return serve.ServeEngine(params, cfg, serve.ServeConfig(**{
        **shape, "max_slots": 1, "max_queue": 16, "decode_span_every": 4,
        **knobs}))


def _padded(prompt, bucket):
    pad = bucket - len(prompt)
    ids = np.zeros((1, bucket), np.int32)
    ids[0, pad:] = prompt
    mask = np.zeros((1, bucket), np.int32)
    mask[0, pad:] = 1
    positions = np.clip(np.cumsum(mask, axis=1) - 1, 0, None).astype(np.int32)
    return ids, mask, positions


def _by_hand(which: str, prompt: list, new: int, **knobs) -> tuple:
    """(the greedy tokens, the prefill's final logits) of `prompt` through
    the family's own programs on a FRESH cache: every chunk of the bucket
    from place 0, then a tick a token."""
    engine = _engine(which, **knobs)          # never stepped: a fresh cache
    slots, family, cfg, params = (engine.slots, engine._family, engine.cfg,
                                  engine.params)
    scfg = engine.serve_cfg
    bucket, chunk = scfg.prompt_buckets[0], scfg.prefill_chunk_tokens
    demand = slots.demand_pages(bucket, new)
    assert slots.reserve(demand) and slots.acquire("hand", demand) == 0
    ids, mask, positions = _padded(prompt, bucket)
    slots.reset_mask_row(0)
    for c0 in range(0, bucket, chunk):
        c1 = c0 + chunk
        slots.ensure_capacity(0, c1)
        out = family.paged_prefill_chunk(
            params, jnp.asarray(ids[:, c0:c1]), jnp.asarray(mask[:, c0:c1]),
            jnp.asarray(positions[:, c0:c1]), slots.pool,
            jnp.asarray(slots.page_table[0]), jnp.int32(0), slots.kv_mask,
            jnp.int32(c0), cfg)
        slots.pool, slots.kv_mask = out["pool"], out["kv_mask"]
    logits = np.asarray(out["logits"])
    tokens = [int(np.argmax(logits[0]))]
    one = lambda value, dtype=np.int32: jnp.asarray(np.full(1, value, dtype))
    for j in range(new - 1):
        slots.ensure_capacity(0, bucket + j + 1)
        out = family.paged_decode_step(
            params, one(tokens[-1]), slots.pool, jnp.asarray(slots.page_table),
            one(len(prompt) + j), one(bucket + j), slots.kv_mask, one(1),
            jnp.zeros((1, 2), jnp.uint32), one(0.0, np.float32), one(0),
            one(1.0, np.float32), cfg)
        slots.pool, slots.kv_mask = out["pool"], out["kv_mask"]
        tokens.append(int(out["token"][0]))
    return tokens, logits


def _serve(engine, requests) -> dict:
    """Serve `requests` [(prompt, new)] one after another to the end:
    {"tokens", "logits" (what each final unit handed the first token's
    program), "units" (each request's `serve_prefill` spans)}."""
    spans, logits = [], []
    listener = lambda rec: spans.append(dict(rec))
    real = engine._first_token

    def first_token(final_logits, *rest):
        logits.append(np.asarray(final_logits))
        return real(final_logits, *rest)

    engine._first_token = first_token
    trace.recorder().add_listener(listener)
    try:
        handles = []
        for i, (prompt, new) in enumerate(requests):
            handles.append(engine.submit(serve.ServeRequest(
                input_ids=prompt, seed=i,
                gen=families.GenerationConfig(max_new_tokens=new))))
            engine.drain()
    finally:
        trace.recorder().remove_listener(listener)
        engine._first_token = real
    units = [s for s in spans if s["name"] == "serve_prefill"]
    of = lambda h: [s for s in units if s["request"] == h.request.request_id]
    return {"tokens": [h.result() for h in handles], "logits": logits,
            "units": [of(h) for h in handles]}


def _runs_every_chunk(engine):
    """The engine as it was: a cold chunked row starts at place 0."""
    real = engine._start_prefill

    def start(*args, **kwargs):
        pf = real(*args, **kwargs)
        if pf is not None and not pf.warm:
            pf.done = pf.start = pf.skipped = 0
        return pf

    engine._start_prefill = start
    return engine


@pytest.mark.parametrize("which", list(CASES))
def test_a_row_that_skipped_its_pad_chunks_is_the_row_that_ran_them(which):
    """One slot, three requests in turn: a LONG one that fills its bucket
    (it skips nothing) and decodes on, so the slot's pages, rings, index keys
    and summaries hold its entries; then one whose real tokens are fewer
    than the family's window, behind three chunks of nothing but pads; then
    one of a single token, which still runs the last chunk. Each is served
    the tokens, and its prefill ends with the logits, of the same programs
    run by hand over every chunk of the bucket on a fresh cache, bit for
    bit; `chunks_skipped` rides each request's first span and the
    snapshot."""
    _, shape, vocab, decoded, short = CASES[which]
    bucket, chunk = shape["prompt_buckets"][0], shape["prefill_chunk_tokens"]
    rng = np.random.default_rng(11)
    requests = [(rng.integers(0, vocab, n).tolist(), new)
                for n, new in ((bucket, decoded), (short, 6), (1, 4))]
    engine = _engine(which)
    served = _serve(engine, requests)
    assert engine.slots.reused_slot_count() == 1
    for (prompt, new), tokens, logits, units in zip(
            requests, served["tokens"], served["logits"], served["units"]):
        want_tokens, want_logits = _by_hand(which, prompt, new)
        assert tokens == want_tokens
        np.testing.assert_array_equal(logits, want_logits)
        skipped = (bucket - len(prompt)) // chunk
        assert [s.get("chunks_skipped") for s in units] == (
            [skipped] + [None] * (len(units) - 1))
        # the units run are the rest of the bucket, in order, to its end
        assert [s["offset"] for s in units] == list(
            range(skipped * chunk, bucket, chunk))
    whole = bucket // chunk
    assert [len(u) for u in served["units"]] == [whole, 1, 1]
    snap = engine.metrics_snapshot()
    assert snap["prefill_chunks_skipped_total"] == 2 * (whole - 1)
    assert snap["prefill_chunks_total"] == whole + 2
    assert (snap["prefill_chunks_total"] + snap["prefill_chunks_skipped_total"]
            == 3 * whole)


def test_a_bucket_no_larger_than_the_chunk_and_an_unchunked_engine_skip_nothing():
    """The whole-bucket path is as it was: its one unit is no first chunk of
    several, and counts no skipped chunk."""
    for knobs in (dict(prefill_chunk_tokens=32), dict(prefill_chunk_tokens=0)):
        engine = _engine("llama", **knobs)
        served = _serve(engine, [([7, 8, 9], 3)])
        (unit,), = served["units"]
        assert unit["chunk"] == unit["bucket"] == 32 and unit["offset"] == 0
        assert unit["chunks_skipped"] == 0
        assert engine.metrics_snapshot()["prefill_chunks_skipped_total"] == 0


@pytest.mark.parametrize("quant", ["fp", "int8"])
def test_a_request_that_shares_a_padded_prefix_is_served_as_before(quant):
    """The prefix cache over rows that skipped their pad chunks (the dense
    family): the pages of the skipped places are registered holding whatever
    they held, and every later reader of them sees pads. A second request
    with the same pad layout maps them and forks the page where it
    diverges; a third, five tokens longer, diverges INSIDE the pads of a
    page that the first never wrote, and forks nothing (the copy would carry
    a scale no write of either row set, for its tokens to saturate against).
    All three are served what the engine that ran every chunk serves, and in
    fp pages the by-hand reference's tokens."""
    rng = np.random.default_rng(3)
    a = rng.integers(1, 128, 5).tolist()
    b = a[:3] + rng.integers(1, 128, 2).tolist()
    c = rng.integers(1, 128, 10).tolist()
    requests = [(a, 6), (b, 6), (c, 6)]
    knobs = dict(prefix_cache=True, kv_quant=quant, max_slots=2)
    engine = _engine("llama", **knobs)
    served = _serve(engine, requests)
    before = _serve(_runs_every_chunk(_engine("llama", **knobs)), requests)
    assert served["tokens"] == before["tokens"]
    # a cold: three chunks of pads skipped. b warm: 24 places of shared pad
    # pages and 6 of a forked one. c warm behind two shared pad pages alone
    assert [u[0].get("chunks_skipped") for u in served["units"]] == [3, 0, 0]
    assert [u[0]["offset"] for u in served["units"]] == [24, 30, 16]
    assert [u[0]["offset"] for u in before["units"]] == [0, 30, 16]
    snap = engine.metrics_snapshot()
    assert snap["prefix_cow_forks"] == 1
    if quant == "fp":
        for (prompt, new), tokens in zip(requests, served["tokens"]):
            assert tokens == _by_hand("llama", prompt, new,
                                      kv_quant=quant)[0]
