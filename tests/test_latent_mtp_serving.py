"""A model that drafts, served through the normal path: `ServeEngine` takes
the verify tick and the first draft from `models/family.py` because the
configuration states a multi-token-prediction module, and for no other
reason. The stream with drafting is the stream without it, greedy and sampled,
with a tick in flight and in the serial order; a row emits one or two tokens a
tick; an eos or the end of the budget on the first of two drops the second; a
budget that runs out inside the tick in flight overruns once; the spans and
the snapshot count what the device did. float32 on the CPU at a tiny size and
a 16-id vocabulary, where chance accepts some drafts."""

import numpy as np
import pytest

import glm_mtp_tiny as tiny
import tick_ahead
from llama_pipeline_parallel_tpu import serve
from llama_pipeline_parallel_tpu.models import family as families
from llama_pipeline_parallel_tpu.models import tick_io

MODEL = tiny.SMALL_VOCAB
PLAIN = {**MODEL, "num_nextn_predict_layers": 0}
SHAPE = dict(max_slots=3, max_queue=16, max_len=64, prompt_buckets=(8, 16, 32),
             page_size=4, num_pages=64, prefill_chunk_tokens=8,
             decode_span_every=4)
# prompts of 11 ids whose greedy streams accept a draft early (seed 30: at the
# row's first tick), beside a longer and a shorter one
PROMPTS = [np.random.default_rng(s).integers(0, 16, n).tolist()
           for s, n in ((30, 11), (26, 11), (5, 27), (31, 11), (39, 11), (6, 3))]
BUDGETS = [12, 9, 7, 10, 6, 11]
KNOBS = {"greedy": {}, "sampled": {"temperature": 0.9},
         "filtered": {"temperature": 0.8, "top_k": 5, "top_p": 0.9}}
_PARAMS = {}


def engine(model=MODEL, **knobs):
    if "params" not in _PARAMS:
        _PARAMS["params"] = tiny.both_sides(MODEL)[0]
    return serve.ServeEngine(_PARAMS["params"], tiny.config(model),
                             serve.ServeConfig(**{**SHAPE, **knobs}))


def requests(knobs, budgets=BUDGETS, eos=None, prompts=PROMPTS):
    return tick_ahead.requests_of(prompts, budgets, [knobs] * len(prompts),
                                  eos)


def check_the_drafting_spans(result, serially=False):
    """`tick_ahead.check_the_spans` for a family whose tick makes one or two
    tokens a row: what the ticks made, less what was discarded, is what the
    handles received after their first tokens."""
    sums = result["sums"]
    spans = result["spans"]
    for s in spans:
        assert s["h2d_copies"] == s["d2h_copies"] == s["ticks"]
        assert 0 <= s["ticks_ahead"] <= s["ticks"]
        assert s["row_ticks"] <= s["tokens"] <= 2 * s["row_ticks"]
        assert s["tokens"] == s["spec_tokens"]
        assert s["spec_offered"] == s["row_ticks"]
        assert s["tokens"] == s["row_ticks"] + s["spec_accepted"]
        assert s["rows_overrun"] <= s["tokens_discarded"] <= s["tokens"]
        rows = [row for ticks in s["verify_rows"].values() for row in ticks]
        assert len(rows) == s["row_ticks"]
        assert sum(made for made, _, _ in rows) == s["tokens"]
        assert sum(made == 2 for made, _, _ in rows) == s["spec_accepted"]
    assert sums["ticks_ahead"] == (
        0 if serially else sums["ticks"] - result["restarts"])
    delivered = sum(max(len(t) - 1, 0) for t in result["tokens"])
    discarded = sum(s["tokens_discarded"] for s in spans)
    assert sums["tokens"] - discarded == delivered
    return {"accepted": sums["spec_accepted"], "discarded": discarded,
            "row_ticks": sum(s["row_ticks"] for s in spans)}


# -- the stream with drafting is the stream without it -----------------------------

@pytest.mark.parametrize("serially", [False, True], ids=["ahead", "serial"])
@pytest.mark.parametrize("knobs", list(KNOBS))
def test_the_stream_with_drafting_is_the_stream_without_it(knobs, serially):
    plain = tick_ahead.run(engine(PLAIN), requests(KNOBS[knobs]),
                           serially=serially, spread=2)
    drafted = tick_ahead.run(engine(), requests(KNOBS[knobs]),
                             serially=serially, spread=2)
    assert drafted["tokens"] == plain["tokens"]
    assert [len(t) for t in drafted["tokens"]] == BUDGETS
    tick_ahead.check_the_spans(plain, serially)
    seen = check_the_drafting_spans(drafted, serially)
    # chance accepted some drafts: rows emitted two tokens in one tick, and
    # the run took fewer row-ticks than tokens
    assert seen["accepted"] >= 1
    assert seen["row_ticks"] < plain["sums"]["tokens"] + seen["discarded"]
    assert "row_ticks" not in plain["spans"][0]
    assert "spec_offered" not in plain["spans"][0]


def test_the_greedy_stream_is_the_references_self_drafting_loop():
    _, top, layer_fn = tiny.both_sides(MODEL)
    result = tick_ahead.run(engine(), requests({}, prompts=PROMPTS[:2],
                                               budgets=BUDGETS[:2]))
    for prompt, budget, tokens in zip(PROMPTS, BUDGETS, result["tokens"]):
        loop = tiny.reference.self_draft(top, layer_fn, prompt, budget, MODEL)
        assert tokens == loop["tokens"]
    accepted = sum(sum(tiny.reference.self_draft(
        top, layer_fn, p, b, MODEL)["accepted"]) for p, b in
        zip(PROMPTS[:2], BUDGETS[:2]))
    # a draft the loop accepts on a budget's last token is one the engine's
    # row accepted too and dropped
    assert result["sums"]["spec_accepted"] >= accepted >= 1


def verify_rows(result: dict) -> list:
    """Every request's row-ticks as the spans recorded them, in tick order:
    [(tokens made, the draft verified, the second query's first choice)]."""
    by_request = {}
    for s in result["spans"]:
        for request, rows in s["verify_rows"].items():
            by_request.setdefault(request, []).extend(rows)
    return [by_request.get(h.request.request_id, [])
            for h in result["handles"]]


def test_the_spans_record_what_the_module_and_the_second_query_produced():
    """What the RUN's own ticks drafted and what their second queries put
    first, three rows decoding together with a tick in flight, is what the
    plain self-drafting loop drafts and what the reference's trunk puts
    first behind the draft, accepted or not."""
    _, top, layer_fn = tiny.both_sides(MODEL)
    result = tick_ahead.run(engine(), requests({}, prompts=PROMPTS[:3],
                                               budgets=BUDGETS[:3]))
    accepted = refused = 0
    for prompt, budget, tokens, rows in zip(PROMPTS, BUDGETS, result["tokens"],
                                            verify_rows(result)):
        loop = tiny.reference.self_draft(top, layer_fn, prompt, budget, MODEL)
        steps = len(loop["drafts"])
        # at most one tick more: an overrun behind a budget's last token
        assert steps <= len(rows) <= steps + 1
        at = 0
        for (made, drafted, second), draft, ok in zip(rows, loop["drafts"],
                                                      loop["accepted"]):
            assert drafted == draft and (made == 2) == ok
            seq = list(prompt) + tokens[:at + 1] + [drafted]
            best = int(np.argmax(tiny.reference.logits_fn(
                top, layer_fn, [seq], MODEL)[0, -1]))
            assert second == best
            if ok and at + 2 < budget:
                assert tokens[at + 2] == second
            accepted += ok
            refused += not ok
            at += made
    assert accepted >= 1 and refused >= 1


def test_a_whole_bucket_and_a_chunked_one_serve_the_same_stream():
    whole = tick_ahead.run(engine(prefill_chunk_tokens=0), requests({}))
    chunked = tick_ahead.run(engine(), requests({}))
    assert whole["tokens"] == chunked["tokens"]
    units = lambda r: sum("mtp_positions" in u for u in r["units"])
    assert units(whole) == len(whole["units"]) == len(PROMPTS)
    assert units(chunked) == len(chunked["units"]) > len(PROMPTS)
    # every prompt position but the last is the module's in some unit
    for result in (whole, chunked):
        assert sum(u["mtp_positions"] for u in result["units"]) == sum(
            len(p) - 1 for p in PROMPTS)


# -- one or two tokens a tick: where a row ends ------------------------------------

def _first_stream():
    return tick_ahead.run(engine(), requests({}, prompts=PROMPTS[:1],
                                             budgets=[6]))["tokens"][0]


def test_an_eos_on_the_first_of_two_tokens_drops_the_second():
    stream = _first_stream()            # its first tick emits two tokens
    assert stream[1] not in stream[:1]
    result = tick_ahead.run(
        engine(), requests({}, prompts=PROMPTS[:1], budgets=[6],
                           eos={0: stream[1]}), serially=True)
    assert result["tokens"][0] == stream[:2]
    seen = check_the_drafting_spans(result, serially=True)
    assert seen["accepted"] == 1 and seen["discarded"] == 1
    assert result["sums"]["rows_overrun"] == 0


def test_a_budget_that_ends_on_the_first_of_two_tokens_drops_the_second():
    stream = _first_stream()
    result = tick_ahead.run(
        engine(), requests({}, prompts=PROMPTS[:1], budgets=[2]),
        serially=True)
    assert result["tokens"][0] == stream[:2]
    seen = check_the_drafting_spans(result, serially=True)
    assert (seen["accepted"], seen["discarded"], seen["row_ticks"]) == (1, 1, 1)


def test_a_budget_that_runs_out_inside_the_tick_in_flight_overruns_once():
    """Three tokens: the first tick makes the second and the third, which
    the host learns a tick late; the tick it had enqueued meanwhile ran the
    row once more, inside the places its reservation covers, for nothing."""
    stream = _first_stream()
    result = tick_ahead.run(
        engine(), requests({}, prompts=PROMPTS[:1], budgets=[3]))
    assert result["tokens"][0] == stream[:3]
    seen = check_the_drafting_spans(result)
    assert result["sums"]["rows_overrun"] == 1 and seen["row_ticks"] == 2
    assert seen["discarded"] >= 1
    serial = tick_ahead.run(
        engine(), requests({}, prompts=PROMPTS[:1], budgets=[3]),
        serially=True)
    assert serial["tokens"][0] == stream[:3]
    assert serial["sums"]["rows_overrun"] == 0


def test_a_row_fills_its_row_to_the_last_place_its_reservation_covers():
    """bucket + budget + 2 == max_len: the last tick's draft sits on the
    row's last place but one; one token more is refused at the door."""
    eng = engine()
    budget = SHAPE["max_len"] - 32 - 2
    prompt = PROMPTS[2]
    ok = tick_ahead.run(eng, requests({}, prompts=[prompt], budgets=[budget]))
    assert len(ok["tokens"][0]) == budget
    assert eng.slots.pages_used == 0 and eng.slots.pages_reserved == 0
    with pytest.raises(serve.RequestRejected, match="places past"):
        engine().submit(requests({}, prompts=[prompt],
                                 budgets=[budget + 1])[0])
    # the family that does not draft keeps its two places
    plain = engine(PLAIN)
    assert plain.pick_bucket(len(prompt), budget + 2) == 32


# -- what the host stages and reads ---------------------------------------------------

def test_a_row_in_flight_is_fed_its_position_on_the_device():
    """With a tick in flight the staged `pos` and `write_pos` of its rows are
    upper bounds (two past what the host has read) and the rows are staged
    `FED_ALL`; the stream is right all the same, so the program took them
    from the tick before."""
    eng = engine()
    staged_rows = []
    real = tick_io.stage

    def spy(slots, pages):
        staged = real(slots, pages)
        staged_rows.append(staged)
        return staged

    tick_io.stage = spy
    try:
        result = tick_ahead.run(eng, requests({}, prompts=PROMPTS[:1],
                                              budgets=[8]))
    finally:
        tick_io.stage = real
    assert result["tokens"][0] == _first_stream()[:6] + result["tokens"][0][6:]
    fed = [int(s.fed[0]) for s in staged_rows if s.active[0]]
    assert fed[0] == tick_io.FED_TOKEN and set(fed[1:]) == {tick_io.FED_ALL}
    write = [int(s.write_pos[0]) for s in staged_rows if s.active[0]]
    # the first tick emitted two tokens: the second tick's bound (16 + 2) was
    # the truth; every later bound stands two past what the host had read
    assert write[:3] == [16, 18, 20]
    assert all(b - a in (1, 2) for a, b in zip(write[1:], write[2:]))


def test_the_snapshot_counts_drafts_offered_and_accepted():
    eng = engine()
    result = tick_ahead.run(eng, requests({}))
    snap = eng.metrics_snapshot()
    assert snap["spec_offered_total"] == result["sums"]["spec_offered"] > 0
    assert snap["spec_accepted_total"] == result["sums"]["spec_accepted"] >= 1
    assert "spec_offered_total" not in engine(PLAIN).metrics_snapshot()


def test_a_shutdown_collects_the_verify_tick_in_flight():
    eng = engine()
    handles = [eng.submit(r) for r in requests({}, prompts=PROMPTS[:2],
                                               budgets=[20, 20])]
    for _ in range(4):
        eng.step()
    assert eng._in_flight is not None
    before = [len(h.tokens_out) for h in handles]
    eng.shutdown()
    after = [len(h.tokens_out) for h in handles]
    assert all(a > b for a, b in zip(after, before))
    for h in handles:
        with pytest.raises(serve.EngineShutdown):
            h.result(timeout=1)


def test_the_family_that_does_not_draft_runs_the_programs_it_ran():
    fam = families.family_of(tiny.config(PLAIN))
    assert fam.decode_tick is tick_io.packed(fam.paged_decode_step)
    assert fam.first_draft is None and not fam.recurrent
    drafting = families.family_of(tiny.config())
    assert drafting.decode_tick is tick_io.packed_drafting(
        drafting.paged_decode_step)
    assert drafting.first_draft is not None


def test_the_trace_summary_reads_the_drafting_counters(tmp_path):
    import json
    import sys

    sys.path.insert(0, tiny.REPO)
    from tools import trace_summary

    result = tick_ahead.run(engine(), requests({}))
    path = tmp_path / "spans.jsonl"
    with open(path, "w") as f:
        for record in result["spans"] + result["units"]:
            f.write(json.dumps(record) + "\n")
    found = trace_summary.drafting_counters(str(path))
    assert found["spec_accepted"] == result["sums"]["spec_accepted"] >= 1
    assert found["spec_offered"] == found["row_ticks"]
    assert found["tokens"] == found["row_ticks"] + found["spec_accepted"]
    assert found["unit_positions"] == sum(len(p) - 1 for p in PROMPTS)
    plain = tick_ahead.run(engine(PLAIN), requests({}))
    other = tmp_path / "plain.jsonl"
    with open(other, "w") as f:
        for record in plain["spans"] + plain["units"]:
            f.write(json.dumps(record) + "\n")
    assert trace_summary.drafting_counters(str(other)) is None
