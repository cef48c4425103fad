"""The page pool + chunked batched prefill (serve/pages.py, the paged
entry points in models/llama/decode.py, and the engine's scheduler —
docs/SERVING.md "Paged KV cache").

The acceptance contracts live here:
- fp paged decode emits, token for token, what an independent generate()
  call per request emits, on the serving parity grid (staggered
  mixed-config requests, page-boundary crossings, slot + page reuse); its
  logits are the gathered rows' within the rounding of a softmax summed
  page by page (ops/paged_attention.py), so a token moves only on a tie.
- chunked prefill admits a long-prompt request during active decode and
  every in-flight stream keeps producing a token EVERY tick, bounded by
  the per-tick chunk budget — no full-prefill stall.
- admission refuses (ServePagesExhausted -> HTTP 429 + Retry-After) when
  the free-page pool cannot cover a request's worst-case page demand, and
  the SAME request succeeds after a release.
- int8 pages pass a tolerance gate vs the dequantized fp reference, and
  the pool admits >= 2x the concurrent requests of one worst-case row a
  slot at the same HBM budget (>= 4x with int8 pages).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llama_pipeline_parallel_tpu.models.llama import decode
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.models.llama.decode import (
    GenerationConfig,
    generate,
)
from llama_pipeline_parallel_tpu.ops.attention import attention
from llama_pipeline_parallel_tpu.serve import (
    PagedKVCache,
    RequestRejected,
    ServeConfig,
    ServeEngine,
    ServePagesExhausted,
    ServeRequest,
)
from llama_pipeline_parallel_tpu.serve.pages import (
    dense_kv_cache_bytes,
    page_demand,
    paged_pool_bytes,
)

BUCKET = 8
PAGE = 4


@pytest.fixture(scope="module")
def setup():
    cfg = LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_engine(cfg, params, **kw):
    """The standard paged test shape — shared across tests so the paged
    decode/prefill programs compile once per pool dtype."""
    defaults = dict(max_slots=2, max_len=BUCKET + 8, prompt_buckets=(BUCKET,),
                    max_queue=8, metrics_every=1, decode_span_every=1,
                    kv_cache="paged", page_size=PAGE, num_pages=16)
    defaults.update(kw)
    return ServeEngine(params, cfg, ServeConfig(**defaults))


def reference_tokens(params, cfg, prompt, gen, seed, bucket=BUCKET):
    pad = bucket - len(prompt)
    ids = np.concatenate([np.zeros(pad, np.int32),
                          np.asarray(prompt, np.int32)])[None]
    mask = np.asarray([[0] * pad + [1] * len(prompt)], np.int32)
    out = generate(params, jnp.asarray(ids), jnp.asarray(mask), cfg, gen,
                   rng=jax.random.PRNGKey(seed))
    return np.asarray(out["tokens"])[0].tolist()


# -- page lifecycle (host bookkeeping) ----------------------------------------


def test_page_demand_model():
    # prompt pages only at max_new=1 (the budget's last token never writes)
    assert page_demand(8, 1, 4) == 2
    assert page_demand(8, 2, 4) == 3   # one decode write crosses into page 3
    assert page_demand(8, 5, 4) == 3   # writes reach position 11: 3 pages
    assert page_demand(8, 6, 4) == 4   # position 12 opens page 4


def test_page_lifecycle_acquire_append_release_reuse():
    cfg = LlamaConfig.tiny()
    cache = PagedKVCache(cfg, max_slots=2, max_len=16, page_size=4,
                         num_pages=6)
    assert (cache.pages_free, cache.pages_reserved) == (6, 0)
    assert cache.reserve(4) and cache.pages_reserved == 4
    assert not cache.reserve(3)        # 4 + 3 > 6: refusal, not overcommit
    assert cache.reserve(2)

    slot = cache.acquire("r1", 4)
    assert slot == 0 and cache.pages_reserved == 6  # moved, not doubled
    # lazy allocation: pages appear as the write frontier crosses boundaries
    assert cache.ensure_capacity(slot, 1) == 1
    assert cache.ensure_capacity(slot, 4) == 0      # still page 1
    assert cache.ensure_capacity(slot, 5) == 1      # crosses into page 2
    assert cache.ensure_capacity(slot, 16) == 2     # the reservation's rest
    assert cache.pages_used == 4 and cache.pages_free == 2
    assert list(cache.page_table[slot]) == [0, 1, 2, 3]  # lowest-first
    with pytest.raises(RuntimeError):   # past the reservation = scheduler bug
        cache.ensure_capacity(slot, 17)

    # release: pages evicted back to the pool, row points at garbage again
    cache.release(slot)
    assert cache.pages_free == 6 and cache.pages_reserved == 2
    assert set(cache.page_table[slot]) == {cache.garbage_page}
    with pytest.raises(ValueError):
        cache.release(slot)             # double free

    with pytest.raises(ValueError):
        cache.release(7)                # out of range: never held

    # reuse: the released pages are handed out again, lowest-first
    slot2 = cache.acquire("r2", 2)      # consumes the earlier reserve(2)
    assert slot2 == 0
    cache.ensure_capacity(slot2, 8)
    assert list(cache.page_table[slot2][:2]) == [0, 1]
    assert cache.page_allocations == 6  # 4 + 2 cumulative hand-outs
    assert cache.pages_reserved == 2    # all held by the slot now
    with pytest.raises(ValueError):
        cache.unreserve(1)              # nothing queued anymore
    assert cache.reserve(4)             # released capacity reservable again
    cache.unreserve(4)

    # slots: lowest free index first, None when every row is occupied, a
    # freed row handed out again and counted as reused; one pool throughout
    slot3 = cache.acquire("r3", 0)
    assert (slot2, slot3) == (0, 1) and cache.active_count == 2
    assert cache.acquire("r4", 0) is None
    cache.release(slot2)
    assert cache.free_count == 1
    assert cache.acquire("r4", 0) == slot2
    assert cache.reused_slot_count() == 1   # slot 0: r1, r2, r4
    assert [s for s, _ in cache.assignments] == [0, 0, 1, 0]
    assert cache.allocations == 1


def test_paged_config_validation():
    base = dict(max_slots=2, max_len=16, prompt_buckets=(8,),
                kv_cache="paged", page_size=4)
    assert ServeConfig(**base).resolved_num_pages == 8  # 2 rows of 16
    with pytest.raises(ValueError):
        ServeConfig(**{**base, "max_len": 18})          # not page-aligned
    with pytest.raises(ValueError):
        ServeConfig(**{**base, "prompt_buckets": (6,)})  # bucket unaligned
    with pytest.raises(ValueError):
        ServeConfig(**{**base, "prefill_chunk_tokens": 6})  # chunk unaligned
    with pytest.raises(ValueError):
        # bucket 16 > chunk 12 but not a multiple: no static chunk shape
        ServeConfig(max_slots=2, max_len=32, prompt_buckets=(16,),
                    kv_cache="paged", page_size=4, prefill_chunk_tokens=12)
    # whether a pool holds one full-length request is the page manager's
    # check since PR 36 (what such a request demands is its family's to say)
    with pytest.raises(ValueError, match="full-length request \\(4 pages\\)"):
        PagedKVCache(LlamaConfig.tiny(), 2, 16, 4, 3)   # < one full request
    with pytest.raises(ValueError):
        ServeConfig(**{**base, "kv_quant": "int4"})
    with pytest.raises(ValueError):
        ServeConfig(max_slots=2, max_len=16, prompt_buckets=(8,),
                    kv_cache="rowed")


# -- the fp parity grid: paged == generate(), bit for bit --------------------


def test_paged_token_parity_vs_generate(setup):
    """Staggered mixed-config requests through 2 slots: every served
    stream must equal the independent generate() call token-for-token (fp
    pages are a residency change, not an arithmetic one), with decode
    writes crossing page boundaries and pages recycled across requests."""
    cfg, params = setup
    rs = np.random.RandomState(0)
    gens = [GenerationConfig(max_new_tokens=6),                       # greedy
            GenerationConfig(max_new_tokens=4, temperature=0.8, top_k=5),
            GenerationConfig(max_new_tokens=6, temperature=0.7, top_p=0.9),
            GenerationConfig(max_new_tokens=5, temperature=1.1)]
    prompts = [rs.randint(3, cfg.vocab_size, (n,)).tolist()
               for n in (5, 8, 3, 7)]

    engine = make_engine(cfg, params)
    handles = [engine.submit(ServeRequest(input_ids=p, gen=g, seed=i))
               for i, (p, g) in enumerate(zip(prompts[:2], gens[:2]))]
    engine.step()
    engine.step()
    handles += [engine.submit(ServeRequest(input_ids=p, gen=g, seed=i + 2))
                for i, (p, g) in enumerate(zip(prompts[2:], gens[2:]))]
    engine.drain(timeout_s=120)
    streams = [h.result(timeout=1) for h in handles]
    # slot AND page reuse: one pool allocation, pages recycled
    assert engine.slots.allocations == 1
    assert engine.slots.reused_slot_count() >= 1
    assert engine.slots.pages_free == engine.slots.num_pages
    assert engine.slots.pages_reserved == 0
    assert engine.slots.page_allocations > max(
        engine.slots.demand_pages(BUCKET, g.max_new_tokens)
        for g in gens)        # reuse, not one giant reservation
    snap = engine.metrics_snapshot()
    assert snap["kv_cache"] == "paged"
    assert snap["pages_total"] == 16
    assert snap["requests_completed"] == 4

    for i, (p, g) in enumerate(zip(prompts, gens)):
        assert streams[i] == reference_tokens(params, cfg, p, g, i), \
            f"request {i} diverged from its independent generate() call"


# -- page by page against the gathered rows ------------------------------------
#
# Since ops/paged_attention.py the fp tick sums its softmax page by page and
# rounds its weights before normalizing them (tests/test_paged_attention.py:
# float32 to 1e-5, bfloat16 to two ulps of a layer's attention output). What
# that leaves in the tick's logits, the tolerances below: float32 1e-4 after
# 4 layers; bfloat16 2^-7, four ulps of a logit under 1/2 (the tiny model's
# are all under 0.45). A token can only move where two logits lie that
# close: the streams are held token for token up to such a tie, and the tie
# itself is pinned on the logits.

LOGIT_TOL = {jnp.float32: 1e-4, jnp.bfloat16: 2 ** -7}


def _gathered_attention(q, k_pool, v_pool, layer, page_table, live_pages,
                        kv_mask):
    """What the fp tick ran before the kernel, under the kernel's
    signature: the slots' logical rows gathered whole, then `attention`."""
    del live_pages
    gk, gv = decode._gather_pages({"k": k_pool, "v": v_pool}, layer,
                                  page_table, q.dtype)
    return attention(q[:, None], gk, gv, kv_mask, causal=False)[:, 0]


def _next_tick_logits(engine, monkeypatch):
    """The logits of the engine's NEXT decode tick for its present
    occupants, from its stores as they stand (nothing donated, nothing
    advanced): {slot: (through the kernel, through the gathered rows)}."""
    slots = engine.serve_cfg.max_slots
    token, pos, write_pos, active = (np.zeros(slots, np.int32)
                                     for _ in range(4))
    for slot, r in engine._occupants.items():
        engine.slots.ensure_capacity(slot, r.write_pos + 1)
        token[slot], pos[slot], write_pos[slot] = r.token, r.pos, r.write_pos
        active[slot] = 1
    args = (engine.params, jnp.asarray(token), engine.slots.pool,
            jnp.asarray(engine.slots.page_table), jnp.asarray(pos),
            jnp.asarray(write_pos), engine.slots.kv_mask, jnp.asarray(active),
            engine.cfg)
    kernel = np.asarray(decode.tick_logits(*args)[0], np.float32)
    with monkeypatch.context() as m:
        m.setattr(decode, "paged_decode_attention", _gathered_attention)
        gathered = np.asarray(decode.tick_logits(*args)[0], np.float32)
    return {slot: (kernel[slot], gathered[slot])
            for slot in engine._occupants}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_fp_ticks_logits_are_the_gathered_rows_logits(monkeypatch, dtype):
    """Two requests of different lengths decoding side by side, a page
    boundary crossed: every tick's logits through the kernel are the
    gathered rows' within LOGIT_TOL, for every occupant."""
    cfg = LlamaConfig.tiny(dtype=dtype)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(1)
    engine = make_engine(cfg, params)
    for i, n in enumerate((3, 7)):
        engine.submit(ServeRequest(
            input_ids=rs.randint(3, cfg.vocab_size, (n,)).tolist(),
            gen=GenerationConfig(max_new_tokens=6), seed=i))
    seen = 0
    for _ in range(4):
        engine.step()
        for kernel, gathered in _next_tick_logits(engine, monkeypatch).values():
            np.testing.assert_allclose(kernel, gathered, rtol=0,
                                       atol=LOGIT_TOL[dtype])
            seen += 1
    assert seen == 8


@pytest.mark.slow  # funds the Request trace tier-1 rows: this is the fp32
# parity grid above re-run in bf16 — a dtype variant of an identical
# contract, not a new one; it stays pinned in the slow/round gate.
def test_paged_token_parity_vs_generate_bf16(monkeypatch):
    """The parity contract in the serving compute dtype: bf16 served streams
    equal the bf16 generate() reference token for token (greedy + sampled)
    wherever the logits do not tie. RandomState(4)'s greedy request does
    tie, exactly, at its third token (0.3828125 twice in the gathered rows'
    logits): there the kernel's logits are held to the gathered rows'
    within LOGIT_TOL and its token to one of the tied pair; a second greedy
    request without a tie is held token for token to its end."""
    dtype = jnp.bfloat16
    cfg = LlamaConfig.tiny(dtype=dtype)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    rs = np.random.RandomState(4)
    gens = [GenerationConfig(max_new_tokens=5),
            GenerationConfig(max_new_tokens=4, temperature=0.9, top_k=6),
            GenerationConfig(max_new_tokens=5)]
    prompts = [rs.randint(3, cfg.vocab_size, (n,)).tolist()
               for n in (5, 8, 6)]

    engine = make_engine(cfg, params)
    handles = [engine.submit(ServeRequest(input_ids=p, gen=g, seed=i))
               for i, (p, g) in enumerate(zip(prompts, gens))]
    by_id = {h.request.request_id: i for i, h in enumerate(handles)}
    tied_at = {}                 # request -> index of its first tied token
    while engine.step():
        for slot, (kernel, gathered) in _next_tick_logits(
                engine, monkeypatch).items():
            np.testing.assert_allclose(kernel, gathered, rtol=0,
                                       atol=LOGIT_TOL[dtype])
            r = engine._occupants[slot]
            i = by_id[r.request.request_id]
            best, second = np.sort(gathered)[::-1][:2]
            if (gens[i].temperature == 0 and i not in tied_at
                    and best - second <= LOGIT_TOL[dtype]):
                tied_at[i] = r.emitted
                assert gathered[kernel.argmax()] >= best - LOGIT_TOL[dtype]
    assert tied_at == {0: 2}, tied_at
    for i, (h, p, g) in enumerate(zip(handles, prompts, gens)):
        upto = tied_at.get(i, g.max_new_tokens)
        served = h.result(timeout=1)
        assert len(served) == g.max_new_tokens
        assert served[:upto] == reference_tokens(params, cfg, p, g, i)[:upto]


def test_paged_eos_finishes_row_early_and_frees_pages(setup):
    """eos frees the slot AND its pages before the budget."""
    cfg, params = setup
    engine = make_engine(cfg, params, max_slots=1)
    prompt = np.random.RandomState(2).randint(3, cfg.vocab_size, (4,)).tolist()

    free = engine.submit(ServeRequest(
        input_ids=prompt, gen=GenerationConfig(max_new_tokens=8), seed=0))
    engine.drain(timeout_s=60)
    eos = free.result(timeout=1)[0]  # force eos on the very first token

    gen = GenerationConfig(max_new_tokens=8, eos_token_id=eos, pad_token_id=17)
    h = engine.submit(ServeRequest(input_ids=prompt, gen=gen, seed=0))
    engine.drain(timeout_s=60)
    assert h.result(timeout=1) == [eos]
    assert engine.slots.free_count == 1
    assert engine.slots.pages_free == engine.slots.num_pages
    assert engine.slots.pages_reserved == 0
    ref = reference_tokens(params, cfg, prompt, gen, 0)
    assert ref[0] == eos and all(t == 17 for t in ref[1:])


@pytest.mark.parametrize("kv_quant", ["fp", "int8"])
def test_an_overruns_write_lands_inside_the_rows_own_reservation(setup,
                                                                 kv_quant):
    """A row's eos is read one tick late, and the tick already enqueued
    writes the row's cache once more. Here that write is the FIRST of a new
    page, in a pool that holds exactly the three requests' worst cases: the
    page is backed from the row's own reservation (no accounting error), all
    of it is free again at once, and the row decoding beside it
    and the request that takes the slot next emit their generate() calls'
    tokens (fp) or an undisturbed int8 engine's."""
    cfg, params = setup
    rs = np.random.RandomState(3)
    prompts = [rs.randint(3, cfg.vocab_size, (n,)).tolist() for n in (5, 7, 6)]
    plain = GenerationConfig(max_new_tokens=8)
    drawn = GenerationConfig(max_new_tokens=8, temperature=1.1)  # no repeats

    def serve_all(gens, **kw):
        demand = page_demand(BUCKET, 8, PAGE)
        engine = make_engine(cfg, params, num_pages=3 * demand,
                             kv_quant=kv_quant, **kw)
        handles = [engine.submit(ServeRequest(input_ids=p, gen=g, seed=i))
                   for i, (p, g) in enumerate(zip(prompts[:2], gens))]
        engine.step()
        engine.step()
        handles.append(engine.submit(ServeRequest(
            input_ids=prompts[2], gen=plain, seed=2)))
        engine.drain(timeout_s=120)
        return engine, [h.result(timeout=1) for h in handles]

    _, free = serve_all([drawn, plain])
    # token 4 comes from the tick that writes place BUCKET + 3; the overrun
    # writes place BUCKET + 4, the first of the row's fourth page
    assert (BUCKET + 4) % PAGE == 0
    assert free[0][4] not in free[0][:4]
    gen = GenerationConfig(max_new_tokens=8, temperature=1.1,
                           eos_token_id=free[0][4])
    spans = []
    listener = lambda rec: spans.append(dict(rec))
    from llama_pipeline_parallel_tpu.utils import trace

    trace.recorder().add_listener(listener)
    try:
        engine, got = serve_all([gen, plain], decode_span_every=64)
        assert engine.step() is False
    finally:
        trace.recorder().remove_listener(listener)
    assert got == [free[0][:5], free[1], free[2]]
    if kv_quant == "fp":
        assert got[1] == reference_tokens(params, cfg, prompts[1], plain, 1)
        assert got[2] == reference_tokens(params, cfg, prompts[2], plain, 2)
    ticks = [s for s in spans if s["name"] == "serve_decode_step"]
    assert sum(s["rows_overrun"] for s in ticks) == 1
    # the overrun's page was allocated, from the reservation, and released
    assert engine.slots.page_allocations == 4 + 2 * page_demand(BUCKET, 8, PAGE)  # noqa: E501
    assert engine.slots.pages_free == engine.slots.num_pages
    assert engine.slots.pages_reserved == 0


# -- chunked batched prefill: no full-prefill stall ---------------------------


def chunked_engine(cfg, params, **kw):
    """The chunked-prefill shape (shared with tests/test_serve_traffic.py
    so the chunk/decode programs compile once): buckets 8 and 32, 8-token
    per-tick budget — a bucket-32 prompt takes 4 interleaved chunks."""
    defaults = dict(max_slots=2, max_len=48, prompt_buckets=(8, 32),
                    page_size=4, kv_cache="paged", num_pages=24,
                    prefill_chunk_tokens=8, max_queue=32, metrics_every=1,
                    decode_span_every=1)
    defaults.update(kw)
    return ServeEngine(params, cfg, ServeConfig(**defaults))


def test_chunked_prefill_no_stall_and_token_parity(setup):
    """THE no-stall acceptance: a long-prompt admission during active
    decode runs as bounded chunks — the in-flight stream gains exactly one
    token EVERY tick of the prefill window — and the chunked request's
    tokens still match its independent generate() reference (greedy and
    sampled)."""
    cfg, params = setup
    engine = chunked_engine(cfg, params)
    rs = np.random.RandomState(1)
    short = rs.randint(3, cfg.vocab_size, (5,)).tolist()
    long_p = rs.randint(3, cfg.vocab_size, (20,)).tolist()

    ga = GenerationConfig(max_new_tokens=20)
    a = engine.submit(ServeRequest(input_ids=short, gen=ga, seed=0))
    engine.step()                      # bucket 8 <= chunk 8: one-shot admit
    engine.step()
    assert len(a.tokens_out) >= 2      # actively decoding

    gb = GenerationConfig(max_new_tokens=6)
    b = engine.submit(ServeRequest(input_ids=long_p, gen=gb, seed=7))
    # bucket 32 / chunk 8 = 4 chunks, the first of them nothing but pads and
    # never run: 3 interleaved chunks; A must advance EVERY tick
    for tick in range(3):
        n_a = len(a.tokens_out)
        engine.step()
        assert len(a.tokens_out) == n_a + 1, \
            f"in-flight stream stalled at prefill tick {tick}"
        assert engine.prefill_chunks_last_tick == 1
        if tick < 2:
            assert len(b.tokens_out) == 0   # still prefilling
            # the decode tick must not touch the mid-prefill row: B's
            # position 0 is a LEFT PAD (20-token prompt in a 32 bucket)
            # and must stay unmasked while its slot rides the tick
            slot_b = engine._prefilling[0].slot
            assert int(np.asarray(engine.slots.kv_mask)[slot_b, 0]) == 0, \
                "decode tick polluted the mid-prefill slot's kv mask"
    assert len(b.tokens_out) >= 1           # joined at its final chunk
    snap = engine.metrics_snapshot()
    # A's one-shot + B's four, of which one was skipped
    assert snap["prefill_chunks_total"] >= 4
    assert snap["prefill_chunks_skipped_total"] == 1
    assert snap["prefill_tokens_total"] >= 8 + 24

    # a SAMPLED request whose chunked prefill interleaves with A's still-
    # running decode — the regression shape for the mid-prefill pollution
    # bug (a tick writing garbage kv + a spurious mask bit into the
    # prefilling row flipped exactly this temperature-0.9/seed-1 stream):
    # B's slot frees after its 6 tokens while A (20-token budget) is still
    # decoding, so D's 3 chunks run against live decode ticks
    while not b.done:
        engine.step()
    assert not a.done                      # A still mid-decode
    gd = GenerationConfig(max_new_tokens=6, temperature=0.9)
    d = engine.submit(ServeRequest(input_ids=long_p, gen=gd, seed=1))
    for _ in range(4):                     # D's whole prefill window
        n_a = len(a.tokens_out)
        engine.step()
        assert len(a.tokens_out) == n_a + 1
    engine.drain(timeout_s=120)
    assert d.result(timeout=1) == reference_tokens(params, cfg, long_p, gd,
                                                   1, bucket=32)
    assert a.result(timeout=1) == reference_tokens(params, cfg, short, ga, 0)
    assert b.result(timeout=1) == reference_tokens(params, cfg, long_p, gb,
                                                   7, bucket=32)
    # a sampled chunked admission reproduces its reference too
    gc = GenerationConfig(max_new_tokens=6, temperature=0.8, top_k=7)
    c = engine.submit(ServeRequest(input_ids=long_p, gen=gc, seed=3))
    engine.drain(timeout_s=120)
    assert c.result(timeout=1) == reference_tokens(params, cfg, long_p, gc,
                                                   3, bucket=32)


# -- backpressure: worst-case page demand refused up front --------------------


def test_page_exhaustion_refusal_and_retry_after_release(setup):
    """Admission control: a submit whose worst-case page demand cannot be
    covered is refused NOW (ServePagesExhausted with a retry hint) instead
    of being admitted and failing mid-decode; the same request succeeds
    after a release frees the pool."""
    cfg, params = setup
    engine = make_engine(cfg, params)      # 16 pages; 4 pages/request below
    gen = GenerationConfig(max_new_tokens=8)
    assert engine.slots.demand_pages(BUCKET, 8) == 4
    prompt = [5, 6, 7]
    handles = [engine.submit(ServeRequest(input_ids=prompt, gen=gen, seed=i))
               for i in range(4)]          # 16/16 pages reserved (2 queued)
    with pytest.raises(ServePagesExhausted) as exc:
        engine.submit(ServeRequest(input_ids=prompt, gen=gen, seed=9))
    assert exc.value.retry_after_s > 0
    snap = engine.metrics_snapshot()
    assert snap["requests_page_refused"] == 1
    assert snap["requests_rejected"] == 1  # counted in the headline too
    assert snap["pages_reserved"] == 16

    # a demand the pool can NEVER cover is a 400-class rejection instead
    with pytest.raises(RequestRejected):
        engine.submit(ServeRequest(
            input_ids=prompt, gen=GenerationConfig(max_new_tokens=9)))

    engine.drain(timeout_s=120)            # completions release pages
    retry = engine.submit(ServeRequest(input_ids=prompt, gen=gen, seed=9))
    engine.drain(timeout_s=120)
    assert retry.result(timeout=1) == reference_tokens(params, cfg, prompt,
                                                       gen, 9)
    for h in handles:
        assert len(h.result(timeout=1)) == 8


@pytest.mark.slow  # funds the Prefix cache tier-1 rows: the unit-level
# refusal/retry contract stays fast above, and tests/test_prefix_cache.py
# re-pins the 429 math under page sharing — this HTTP re-run of the same
# mapping (server thread + full drain) stays pinned in the round gate.
def test_page_exhaustion_maps_to_http_429_with_retry_after(setup):
    """The frontend maps ServePagesExhausted to HTTP 429 + Retry-After;
    the client's retry succeeds once the pool drains."""
    import threading
    import urllib.error
    import urllib.request

    from llama_pipeline_parallel_tpu.serve import ServeLoop
    from llama_pipeline_parallel_tpu.serve.frontend import make_server

    cfg, params = setup
    engine = make_engine(cfg, params)
    server = make_server(engine)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()

    def post(body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=60)

    gen = dict(max_new_tokens=8)
    try:
        # fill the pool in-process (reservations are immediate; no stepping)
        fillers = [engine.submit(ServeRequest(
            input_ids=[5, 6], gen=GenerationConfig(max_new_tokens=8),
            seed=i)) for i in range(4)]
        with pytest.raises(urllib.error.HTTPError) as err:
            post({"input_ids": [5, 6], "seed": 9, **gen})
        assert err.value.code == 429
        # a shed client can still name its trace (docs/SERVING.md
        # "Request tracing"): correlation ids ride the 429 too
        assert err.value.headers["X-Request-Id"]
        assert err.value.headers["X-Trace-Id"]
        body_429 = json.loads(err.value.read())
        assert body_429["trace_id"] == err.value.headers["X-Trace-Id"]
        assert int(err.value.headers["Retry-After"]) >= 1
        with ServeLoop(engine, idle_wait_s=0.005):
            for h in fillers:
                h.result(timeout=120)      # pool drains
            out = json.load(post({"input_ids": [5, 6], "seed": 9, **gen}))
            assert out["tokens"] == reference_tokens(
                params, cfg, [5, 6], GenerationConfig(max_new_tokens=8), 9)
    finally:
        server.shutdown()


# -- int8 pages: tolerance gate + capacity ------------------------------------


def test_int8_quant_roundtrip_bound():
    """Per-page scale quantization error bound: |roundtrip - x| <=
    scale / 127 / 2 when the scale is the block absmax (no saturation)."""
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(4, 8, 2, 16).astype(np.float32))
    scale = jnp.max(jnp.abs(x), axis=(1, 3))[:, None, :, None]
    q = decode.quant_page_block(x, scale)
    rt = np.asarray(decode.dequant_page_block(q, scale, jnp.float32))
    assert np.all(np.abs(rt - np.asarray(x))
                  <= np.asarray(scale) / 127.0 * 0.5000001)


def test_int8_pages_tolerance_gate_vs_dequantized_reference(setup):
    """The int8 parity gate: feed BOTH an fp and an int8 paged cache the
    SAME token stream (the fp path's) and assert the int8 pool's
    dequantized prompt pages sit within the per-page quantization bound of
    the fp values, and that the greedy tokens agree along the gated
    horizon."""
    cfg, params = setup
    rs = np.random.RandomState(2)
    prompt = rs.randint(3, cfg.vocab_size, (6,)).tolist()
    pad = BUCKET - len(prompt)
    ids = np.zeros((1, BUCKET), np.int32)
    ids[0, pad:] = prompt
    mask = np.zeros((1, BUCKET), np.int32)
    mask[0, pad:] = 1

    caches = {}
    for quant in ("fp", "int8"):
        c = PagedKVCache(cfg, 2, 16, PAGE, 16, quant)
        c.acquire("r", c.demand_pages(BUCKET, 8))
        out = decode.prefill_prompt(params, jnp.asarray(ids),
                                    jnp.asarray(mask), cfg, BUCKET)
        c.admit(0, out)
        caches[quant] = (c, out)

    fp_c, fp_out = caches["fp"]
    q_c, _ = caches["int8"]
    n = BUCKET // PAGE
    fp_k = np.asarray(fp_c.pool["k"][:, fp_c.page_table[0, :n]],
                      dtype=np.float32)
    qk = np.asarray(q_c.pool["k"][:, q_c.page_table[0, :n]], np.float32)
    sk = np.asarray(q_c.pool["k_scale"][:, q_c.page_table[0, :n]])
    deq = qk * (sk[:, :, None, :, None] / 127.0)
    bound = sk[:, :, None, :, None] / 127.0 * 0.5000001 + 1e-7
    # the bound only holds where the fp value is real prompt kv; padded
    # positions are garbage in both pools and excluded by the kv mask
    valid = np.asarray(fp_c.kv_mask[0, :BUCKET]).reshape(n, PAGE).astype(bool)
    assert np.all((np.abs(deq - fp_k) <= bound)[:, valid[None].repeat(
        fp_k.shape[0], 0)[0]])

    # forced-same-stream decode: 6 greedy ticks, int8 fed the fp tokens
    def tick(c, tok, pos, wp):
        out = decode.paged_decode_step(
            params, jnp.asarray([tok, 0], jnp.int32), c.pool,
            jnp.asarray(c.page_table), jnp.asarray([pos, 0], jnp.int32),
            jnp.asarray([wp, 0], jnp.int32), c.kv_mask,
            jnp.asarray([1, 0], jnp.int32), jnp.zeros((2, 2), jnp.uint32),
            jnp.zeros(2, jnp.float32), jnp.zeros(2, jnp.int32),
            jnp.ones(2, jnp.float32), cfg)
        c.update_from_step(out)
        return int(np.asarray(out["token"])[0])

    tok = int(np.argmax(np.asarray(fp_out["logits"])[0]))
    pos, wp = int(np.asarray(fp_out["next_pos"])[0]), BUCKET
    fp_toks, q_toks = [], []
    for _ in range(6):
        fp_c.ensure_capacity(0, wp + 1)
        q_c.ensure_capacity(0, wp + 1)
        nf = tick(fp_c, tok, pos, wp)
        q_toks.append(tick(q_c, tok, pos, wp))
        fp_toks.append(nf)
        tok, pos, wp = nf, pos + 1, wp + 1
    assert q_toks == fp_toks, \
        f"int8 greedy tokens drifted past the gate: {q_toks} vs {fp_toks}"


@pytest.mark.slow  # funds the Prefix cache tier-1 rows: first-token
# equality and greedy agreement are already clauses of the tolerance gate
# above — this two-full-engine e2e re-run of the same contract stays
# pinned in the round gate.
def test_int8_engine_first_token_matches_fp(setup):
    """Prefill logits are computed unquantized, so the FIRST token of an
    int8-paged request always equals the fp path's; the rest of the stream
    completes under the tolerance regime."""
    cfg, params = setup
    prompt = np.random.RandomState(3).randint(3, 250, (6,)).tolist()
    gen = GenerationConfig(max_new_tokens=5)
    outs = {}
    for quant in ("fp", "int8"):
        engine = make_engine(cfg, params, kv_quant=quant)
        h = engine.submit(ServeRequest(input_ids=prompt, gen=gen, seed=0))
        engine.drain(timeout_s=60)
        outs[quant] = h.result(timeout=1)
    assert len(outs["int8"]) == 5
    assert outs["int8"][0] == outs["fp"][0]


def test_paged_capacity_2x_and_int8_4x_at_dense_hbm_budget(setup):
    """THE capacity assertion: at the dense cache's resident HBM budget
    (2 slots x 64 tokens), the paged pool admits >= 2x the dense cache's
    concurrent requests, and int8 pages >= 4x — because demand is charged
    per request (prompt + budget), not one worst case per slot."""
    cfg, params = setup
    dense_slots, dense_len, page = 2, 64, 8
    budget_bytes = dense_kv_cache_bytes(cfg, dense_slots, dense_len)
    gen = GenerationConfig(max_new_tokens=9)   # bucket 8 + 8 writes: 2 pages
    prompt = [5, 6, 7]

    active = {}
    for quant, factor in (("fp", 2), ("int8", 4)):
        num_pages = 1
        while paged_pool_bytes(cfg, num_pages + 1, page, quant) \
                <= budget_bytes:
            num_pages += 1
        assert paged_pool_bytes(cfg, num_pages, page, quant) <= budget_bytes
        engine = make_engine(
            cfg, params, max_slots=4 * dense_slots * dense_len // 16,
            max_len=dense_len, page_size=page, num_pages=num_pages,
            kv_quant=quant, max_queue=64)
        admitted = 0
        while True:
            try:
                engine.submit(ServeRequest(input_ids=prompt, gen=gen,
                                           seed=admitted))
            except ServePagesExhausted:
                break
            admitted += 1
        engine._advance_prefill()     # place them all into live slots
        active[quant] = engine.slots.active_count
        assert engine.slots.active_count == admitted
        assert admitted >= factor * dense_slots, \
            (f"{quant} pool at the dense budget admitted {admitted} < "
             f"{factor}x dense's {dense_slots}")
        engine.shutdown()
    assert active["int8"] >= 2 * active["fp"]


# -- telemetry ---------------------------------------------------------------


def test_serving_report_renders_page_gauges(tmp_path, capsys):
    import serving_report  # tools/ on sys.path via conftest

    line = {"step": 3, "serving": 1, "requests_completed": 3,
            "requests_rejected": 1, "requests_page_refused": 1,
            "ttft_p50_ms": 12.0, "active_slots": 1, "queue_depth": 0,
            "slot_allocations": 1, "kv_cache": "paged", "kv_quant": "int8",
            "page_size": 4, "pages_total": 16, "pages_used": 3,
            "pages_free": 13, "pages_reserved": 4, "page_allocations": 9,
            "prefill_chunks_last_tick": 1, "prefill_chunks_total": 7,
            "prefill_tokens_total": 88, "prefilling": 0}
    with open(tmp_path / "metrics.jsonl", "w") as f:
        f.write(json.dumps(line) + "\n")
    with open(tmp_path / "spans.jsonl", "w") as f:
        f.write(json.dumps({"name": "serve_request", "ts": 1.0, "end": 2.0,
                            "dur": 1.0, "ttft": 0.1, "tpot": 0.01,
                            "queue_wait": 0.0, "tokens": 4}) + "\n")
    assert serving_report.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "pages_used=3" in out and "pages_reserved=4" in out
    assert "requests_page_refused=1" in out
    assert "prefill_chunks_last_tick=1" in out
