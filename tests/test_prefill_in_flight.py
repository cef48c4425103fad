"""The engine's one prefill unit in flight (`serve/engine.py`,
`models/tick_io.first_token`): a unit is handed to the device and its result
is read only after the next hand-over (the next unit of the step's burst, or
the step's decode tick) is enqueued behind it; a row's first token is drawn
on the device and fed to that tick there. The streams are those of the
serial order kept in `tests/tick_ahead.py` (every unit read at once, every
tick collected before the next is staged), token for token, on every family,
greedy and sampled, a step apart and in bursts; the first token's program
gives the bits the host's eager calls gave; the host's own `next_pos` is the
programs'; a unit makes one read; a first token that is the eos overruns
once; a budget of one token never joins a tick; a cancellation, `shutdown()`,
`drain()` and the idle boundary find no unit unread; a read that raises fails
its own request only. float32 on the CPU at tiny sizes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import serving_tiny
import tick_ahead
from llama_pipeline_parallel_tpu import serve
from llama_pipeline_parallel_tpu.models import family as families
from llama_pipeline_parallel_tpu.models import tick_io
from llama_pipeline_parallel_tpu.serve import engine as engine_module
from llama_pipeline_parallel_tpu.utils import trace

# the five families (the latent one in both its kinds), and the dense one's
# other prefill paths: chunks, and the prefix cache's span
ENGINES = {**{name: (name, {}) for name in serving_tiny.FAMILIES},
           "llama.chunked": ("llama", dict(prefill_chunk_tokens=8)),
           "llama.prefix_cache": ("llama", dict(prefix_cache=True))}
FAMILIES = list(serving_tiny.FAMILIES)


def _engine(which: str, **knobs):
    family, own = ENGINES[which]
    return serving_tiny.engine(family, decode_span_every=4, **own, **knobs)


VOCAB = {"eva": 48}            # every other tiny model's is 128 or more


KNOBS = [dict(), dict(temperature=0.7), dict(temperature=0.7, top_k=5),
         dict(temperature=1.3, top_p=0.9),
         dict(temperature=0.9, top_k=7, top_p=0.8), dict()]


def _requests(which: str, n: int = 7, seed: int = 5, eos=None,
              budgets=None) -> list:
    """A seeded mix: greedy, temperature alone, a top-k, a top-p and both
    filters; prompts of 3 to 14 tokens, budgets of 2 to 11 (or `budgets`);
    under the prefix cache every other prompt shares a prefix."""
    rng = np.random.default_rng(seed)
    vocab = VOCAB.get(which, 128)
    made = []
    for i in range(n):
        prompt = rng.integers(0, vocab, int(rng.integers(3, 15))).tolist()
        made.append(serve.ServeRequest(
            input_ids=prompt, seed=int(rng.integers(0, 2 ** 31)),
            gen=families.GenerationConfig(
                max_new_tokens=int(rng.integers(2, 12)),
                eos_token_id=(eos or {}).get(i), **KNOBS[i % len(KNOBS)])))
    if budgets is not None:
        for r, b in zip(made, budgets):
            r.gen = dataclasses.replace(r.gen, max_new_tokens=b)
    if "prefix" in which:
        for r in made[1::2]:
            r.input_ids = made[0].input_ids[:9] + r.input_ids[:3]
    return made


_engines_made: dict = {}


def _engine_of(which: str):
    """One engine a kind, kept for what only its configuration says."""
    if which not in _engines_made:
        _engines_made[which] = _engine(which)
    return _engines_made[which]


def _listen():
    spans = []
    listener = lambda rec: spans.append(dict(rec))
    trace.recorder().add_listener(listener)
    return spans, lambda: trace.recorder().remove_listener(listener)


# -- (a) the first token's program ---------------------------------------------

@pytest.mark.parametrize("seed", [0, 5, 2 ** 31 - 1, 2 ** 31 + 7, 2 ** 32 - 1,
                                  2 ** 32 + 3, -1, 3000000019])
@pytest.mark.parametrize("knobs", KNOBS[:5],
                         ids=["greedy", "temperature", "top_k", "top_p", "both"])
def test_the_first_token_on_the_device_is_the_hosts_bit_for_bit(seed, knobs):
    """`PRNGKey(seed)` -> `split` -> `sample_rowwise` inside one program,
    from one staged vector, against the eager calls the engine made one by
    one: the same token and the same chain, for seeds beyond 32 signed bits
    too; the counters ride behind them; `prev` comes back with the row's slot
    (and no other place) holding token and chain in `pack_result`'s layout."""
    S, slot, V = 5, 3, 97
    gen = families.GenerationConfig(max_new_tokens=4, **knobs)
    logits = jax.random.normal(jax.random.PRNGKey(11), (1, V)) * 3.0
    chain, first_key = jax.random.split(jax.random.PRNGKey(seed))
    want = jax.jit(families.sample_rowwise)(
        logits, jnp.asarray([gen.temperature], jnp.float32),
        jnp.asarray([gen.top_k], jnp.int32),
        jnp.asarray([gen.top_p], jnp.float32), first_key[None])
    rng = np.random.default_rng(3)
    for counters in (None, jnp.asarray([7, 0, 2 ** 31 - 1], jnp.int32)):
        n = 0 if counters is None else 3
        prev = rng.integers(-2 ** 31, 2 ** 31, 3 * S + n).astype(np.int32)
        staged = tick_io.stage_first(seed, slot, gen.temperature, gen.top_k,
                                     gen.top_p)
        assert staged.dtype == np.int32
        assert staged.shape == (tick_io.FIRST_COLUMNS,)
        read, fed = tick_io.first_token(families.sample_rowwise, S)(
            logits, jnp.asarray(staged), jnp.asarray(prev), counters)
        token, words, rest = tick_io.split_first(np.asarray(read))
        assert token == int(want[0])
        np.testing.assert_array_equal(words, np.asarray(chain))
        assert rest.tolist() == ([] if counters is None
                                 else np.asarray(counters).tolist())
        tokens, keys, tail = tick_io.split_result(np.asarray(fed), S)
        want_prev = tick_io.split_result(prev.copy(), S)
        want_prev[0][slot] = token
        want_prev[1][slot] = np.asarray(chain)
        np.testing.assert_array_equal(tokens, want_prev[0])
        np.testing.assert_array_equal(keys, want_prev[1])
        np.testing.assert_array_equal(tail, want_prev[2])
    # one program an engine shape, whoever asks
    assert tick_io.first_token(families.sample_rowwise, S) is \
        tick_io.first_token(families.sample_rowwise, S)


# -- (b) the host's next_pos is the programs' ----------------------------------

@pytest.mark.parametrize("length", [1, 5, 8], ids=["one", "padded", "full"])
@pytest.mark.parametrize("which", FAMILIES)
def test_the_hosts_next_pos_is_the_programs(which, length):
    """The whole-bucket path no longer reads `out["next_pos"]`: the rope
    position of the first generated token is the last prompt position + 1 of
    the host's own `positions`, as the chunk and span paths always took it."""
    engine = _engine_of(which)
    bucket = engine.serve_cfg.prompt_buckets[0]
    request = serve.ServeRequest(
        input_ids=list(range(1, min(length, bucket) + 1)),
        gen=families.GenerationConfig(max_new_tokens=2))
    pf = engine._start_prefill(request, serve.RequestHandle(request), 0, 0)
    out = engine._family.prefill_prompt(
        engine.params, jnp.asarray(pf.ids), jnp.asarray(pf.mask), engine.cfg,
        pf.bucket)
    assert int(pf.positions[0, -1]) + 1 == int(out["next_pos"][0]) \
        == min(length, bucket)


# -- (c) the streams are the serial order's ------------------------------------

@pytest.mark.parametrize("spread", [0, 1], ids=["bursts", "a_step_apart"])
@pytest.mark.parametrize("which", sorted(ENGINES))
def test_the_streams_with_units_deferred_are_the_serial_orders(which, spread):
    """Greedy and sampled rows (`temperature`, `top_k`, `top_p` set) on every
    family and on the dense one's chunk and span paths, admitted a step apart
    and all at once (bursts of as many units as slots are free): what every
    handle receives is what the serial order gives it, token for token; every
    unit makes at most one read; every unit but a step's last in front of no
    tick is read after the next hand-over; nothing overruns."""
    serial, ahead = tick_ahead.both_orders(
        lambda: _engine(which), lambda: _requests(which), spread=spread)
    budgets = [r.gen.max_new_tokens for r in _requests(which)]
    assert [len(t) for t in ahead["tokens"]] == budgets
    assert all(h.done and h.error is None for h in ahead["handles"])
    assert ahead["sums"]["rows_overrun"] == 0
    units = ahead["units"]
    counters = _engine_of(which)._family.counters
    last = [u for u in units if u["offset"] + u["chunk"] >= u["bucket"]]
    assert len(last) == len(budgets)
    # a request's last unit has the step's tick behind it (a chunk in front
    # of no tick, in a step that only prefills, is read where the step ends)
    assert all(u["ahead"] == 1 and u["reads"] == 1 for u in last)
    assert all(u["reads"] == int(bool(counters)) for u in units
               if u not in last)
    assert sum(u["ahead"] for u in units) >= 0.8 * len(units)
    # a step's last unit hands its row to the tick on the device
    assert 1 <= ahead["sums"]["rows_joined_fed"] <= len(budgets)
    if spread:
        assert ahead["sums"]["rows_joined_fed"] >= 3
    if "prefix" not in which:
        # the same units either way, and the same counts on them (what the
        # prefix cache serves depends on who was admitted a step earlier)
        names = ("bucket", "chunk") + tuple(counters)

        def listed(result):
            index = {h.request.request_id: i
                     for i, h in enumerate(result["handles"])}
            return sorted([index[u["request"]], u["offset"]]
                          + [u[k] for k in names] for u in result["units"])

        assert listed(ahead) == listed(serial)


# -- (d) one read a unit, none at its hand-over --------------------------------

@pytest.mark.parametrize("which", ["llama", "hybrid_moe", "latent_moe.a.x-k1"],
                         ids=["whole_no_counters", "whole_counters", "chunks"])
def test_a_unit_makes_one_read_and_none_at_its_hand_over(which, monkeypatch):
    """Any transfer to the host at a unit's hand-over raises (the
    whole-bucket path read `next_pos` and then the first token there); at its
    collection the engine's one `np.asarray` of the unit's vector is counted:
    one a unit that has something to read, of [token, chain, counters] or of
    the counters alone, and the span says so (`reads`)."""
    tick_ahead.run(_engine(which), _requests(which, 2))     # compile first
    engine = _engine(which)
    # numpy as the engine sees it: its `asarray` of a device array counted
    counting = tick_ahead.Counting(np, jax.Array, [True])
    monkeypatch.setattr(engine_module, "np", counting)
    log = []
    hand_over, collect = engine._hand_over_unit, engine._collect_unit

    def guarded_hand_over(pf, cost):
        with jax.transfer_guard_device_to_host("disallow_explicit"):
            before = len(counting.seen)
            unit = hand_over(pf, cost)
            assert len(counting.seen) == before
        log.append(("hand_over", unit))
        return unit

    def guarded_collect(ahead=False):
        unit, before = engine._unit, len(counting.seen)
        with jax.transfer_guard_device_to_host("disallow_explicit"):
            collect(ahead)
        if unit is not None:
            log.append(("collect", unit, len(counting.seen) - before))

    engine._hand_over_unit, engine._collect_unit = (guarded_hand_over,
                                                    guarded_collect)
    # ticks read their own vector: count the units' reads alone
    real_tick = engine._collect_tick

    def tick(t):
        before = len(counting.seen)
        real_tick(t)
        del counting.seen[before:]

    engine._collect_tick = tick
    result = tick_ahead.run(engine, _requests(which))
    handed = [entry[1] for entry in log if entry[0] == "hand_over"]
    collected = [entry for entry in log if entry[0] == "collect"]
    assert [entry[1] for entry in collected] == handed      # each once, in order
    assert len(handed) == len(result["units"]) >= 7
    n = len(engine._family.counters)
    for (_, unit, reads), span in zip(collected, result["units"]):
        assert reads == span["reads"] == int(unit.vector is not None)
        if unit.vector is not None:
            assert unit.vector.dtype == jnp.int32
            assert unit.vector.shape == ((3 if unit.row is not None else 0) + n,)
    assert len(counting.seen) == sum(s["reads"] for s in result["units"])
    # a unit is read after the hand-over that follows it, never a second later
    at = {(kind, id(entry[0])): i for i, (kind, *entry) in enumerate(log)}
    for a, b in zip(handed, handed[1:]):
        assert at["collect", id(a)] < at["collect", id(b)]
        assert at["collect", id(a)] < at["hand_over", id(b)] + 2


# -- (e) eos at the first token, a budget of one -------------------------------

@pytest.mark.parametrize("which", ["llama", "ssm_moe"])
def test_a_first_token_that_is_the_eos_overruns_one_tick_and_no_more(which):
    """The first token is read a hand-over late: a row whose first token is
    its eos has joined the tick enqueued meanwhile, once (`rows_overrun` 1 a
    row, its token of that tick reaches nobody), the stream is the eos alone, slot
    and pages are free at once, and the request that takes the slot next is
    served as the serial order serves it."""
    plain = tick_ahead.run(_engine(which), _requests(which),
                           serially=True)["tokens"]
    ends = {1: plain[1][0], 4: plain[4][0]}
    spans, stop = _listen()
    try:
        serial, ahead = tick_ahead.both_orders(
            lambda: _engine(which), lambda: _requests(which, eos=ends))
    finally:
        stop()
    for i, tokens in enumerate(ahead["tokens"]):
        cut = next((j for j, t in enumerate(plain[i]) if t == ends.get(i)),
                   len(plain[i]) - 1)
        assert tokens == plain[i][:cut + 1]
    assert ahead["tokens"][1] == [ends[1]] and ahead["tokens"][4] == [ends[4]]
    # once a row that was its step's last unit (one read inside a burst, in
    # front of another unit, never joined)
    overran = ahead["sums"]["rows_overrun"]
    assert 1 <= overran <= 2 and serial["sums"]["rows_overrun"] == 0
    assert serial["sums"]["tokens"] + overran == ahead["sums"]["tokens"]
    emitted = {s["request"]: s["tokens"] for s in spans
               if s["name"] == "serve_request"}
    for result in (serial, ahead):
        for handle in result["handles"]:
            assert emitted[handle.request.request_id] == len(handle.tokens_out)


def test_the_eos_row_alone_is_collected_and_its_slot_reused_cleanly():
    """One request whose first token is its eos, alone: the tick it overran
    into holds no other row and is collected at the idle boundary; nothing is
    left in flight, no page is held; the same engine then serves another
    request in the same slot as a fresh engine does."""
    plain = tick_ahead.run(_engine("hybrid_moe"), _requests("hybrid_moe", 2),
                           serially=True)["tokens"]
    engine = _engine("hybrid_moe")
    got = tick_ahead.run(engine, _requests("hybrid_moe", 1,
                                           eos={0: plain[0][0]}))
    assert got["tokens"] == [[plain[0][0]]]
    assert got["sums"]["rows_overrun"] == got["sums"]["tokens"] == 1
    assert got["sums"]["rows_joined_fed"] == got["sums"]["ticks"] == 1
    assert engine._in_flight is None and engine._unit is None
    assert engine.slots.pages_used == 0 and not engine._occupants
    again = tick_ahead.run(engine, _requests("hybrid_moe", 2)[1:])
    assert again["tokens"] == [plain[1]]
    assert engine.slots.assignments[0][0] == engine.slots.assignments[1][0]


def test_a_budget_of_one_token_never_joins_a_tick():
    """`max_new_tokens == 1` is known to the host: the row joins no tick (a
    lone such request runs none at all), is finished at its unit's read and
    frees its slot; beside others, every row-tick is still a delivered
    token."""
    engine = _engine("llama")
    alone = tick_ahead.run(engine, _requests("llama", 1, budgets=[1]))
    assert [len(t) for t in alone["tokens"]] == [1]
    assert alone["spans"] == [] and alone["handles"][0].done
    assert [(u["ahead"], u["reads"]) for u in alone["units"]] == [(0, 1)]
    assert engine._unit is None and engine.slots.pages_used == 0
    budgets = [1, 4, 1, 1, 6, 1, 3]
    serial, ahead = tick_ahead.both_orders(
        lambda: _engine("llama"),
        lambda: _requests("llama", budgets=budgets), spread=0)
    assert [len(t) for t in ahead["tokens"]] == budgets
    assert ahead["sums"]["tokens"] == sum(n - 1 for n in budgets)
    assert ahead["sums"]["rows_overrun"] == 0
    assert ahead["sums"]["rows_joined_fed"] <= sum(n > 1 for n in budgets)


# -- (f) cancellation, shutdown, drain, the idle boundary ----------------------

def test_a_request_cancelled_while_its_unit_is_in_flight():
    """`note_abandoned` between a unit's hand-over and its read: the unit is
    read where the step reads it (the first token reaches the handle), the
    request is cancelled at the next boundary after the tick in flight is
    collected, its slot and pages are freed, and the rows beside it are
    served as if it had never been."""
    plain = tick_ahead.run(_engine("llama"), _requests("llama"),
                           serially=True)["tokens"]
    engine = _engine("llama")
    requests = _requests("llama")
    doomed, seen = requests[2], {}
    hand_over = engine._hand_over_unit

    def cancelling(pf, cost):
        unit = hand_over(pf, cost)
        if pf.request is doomed:
            seen["unread"] = unit.row.first_unread
            engine.note_abandoned(doomed)
        return unit

    engine._hand_over_unit = cancelling
    got = tick_ahead.run(engine, requests)
    assert seen == {"unread": True}
    # its first token, and the one of the tick it had joined meanwhile
    assert got["tokens"][2] == plain[2][:2]
    assert got["handles"][2].done and got["handles"][2].error is None
    for i, tokens in enumerate(got["tokens"]):
        if i != 2:
            assert tokens == plain[i]
    tick_ahead.check_the_spans(got)
    assert got["sums"]["rows_overrun"] == 0
    assert engine.slots.pages_used == 0 and engine._unit is None


@pytest.mark.parametrize("end", ["shutdown", "drain", "idle_boundary"])
def test_no_unit_is_left_unread(end):
    """Units handed over and not read: `shutdown()` reads the one in flight
    before it fails the handles (each first token reached its handle),
    `drain()` steps until nothing is in flight, and a step that finds no row
    to decode (every budget one token) reads its unit before it parks."""
    engine = _engine("hybrid_moe")
    budgets = [1, 1] if end == "idle_boundary" else [5, 7]
    requests = _requests("hybrid_moe", 2, budgets=budgets)
    handles = [engine.submit(r) for r in requests]
    spans, stop = _listen()
    try:
        if end == "shutdown":
            engine._advance_prefill()               # a burst of two units
            assert engine._unit is not None and engine._unit.row.first_unread
            assert [len(h.tokens_out) for h in handles] == [1, 0]
            engine.shutdown()
            assert [len(h.tokens_out) for h in handles] == [1, 1]
            assert all(isinstance(h.error, serve.EngineShutdown)
                       for h in handles)
        elif end == "drain":
            engine.drain(timeout_s=300)
            assert [len(h.tokens_out) for h in handles] == budgets
            engine.shutdown()
        else:
            assert engine.step() is True
            assert [len(h.tokens_out) for h in handles] == [1, 1]
            assert all(h.done and h.error is None for h in handles)
            assert engine.step() is False           # parked
    finally:
        stop()
    assert engine._unit is None and engine._in_flight is None
    assert all(h.done for h in handles) and engine.slots.pages_used == 0
    units = [s for s in spans if s["name"] == "serve_prefill"]
    assert len(units) == 2 and all(u["reads"] == 1 for u in units)
    assert [u["ahead"] for u in units] == [1, 0 if end != "drain" else 1]
    assert sum(s["routed_total"] for s in units) > 0


# -- (g) a unit that fails at its deferred read --------------------------------

class _Unreadable:
    """Stands for a unit's vector whose transfer to the host fails."""

    def __array__(self, *args, **kwargs):
        raise RuntimeError("the device lost this unit")


@pytest.mark.parametrize("which,victim", [("llama", 1), ("llama", 4),
                                          ("latent_moe.a.x-k1", 3)])
def test_a_unit_that_fails_at_its_deferred_read_fails_only_its_request(
        which, victim):
    """The read of one unit raises, a hand-over after the unit was enqueued
    (for the latent engine: of a chunk that is not the prompt's last): that
    request fails with the error, its slot and pages are freed, a row it had
    already made leaves the batch (its row of the tick enqueued meanwhile is
    an overrun), and every other request is served as if nothing happened."""
    plain = tick_ahead.run(_engine(which), _requests(which),
                           serially=True)["tokens"]
    engine = _engine(which)
    requests = _requests(which)
    hand_over, failed = engine._hand_over_unit, []

    def failing(pf, cost):
        unit = hand_over(pf, cost)
        if pf.request is requests[victim] and not failed:
            failed.append(unit)
            unit.vector = _Unreadable()
        return unit

    engine._hand_over_unit = failing
    got = tick_ahead.run(engine, requests)
    assert len(failed) == 1
    handle = got["handles"][victim]
    assert handle.done and isinstance(handle.error, RuntimeError)
    assert "lost this unit" in str(handle.error) and got["tokens"][victim] == []
    for i, (h, tokens) in enumerate(zip(got["handles"], got["tokens"])):
        if i != victim:
            assert h.error is None and tokens == plain[i]
    joined = failed[0].row is not None
    assert got["sums"]["rows_overrun"] == int(joined)
    assert engine.stats.snapshot()["requests_failed"] == 1
    assert engine.slots.pages_used == 0 and not engine._occupants
    assert not engine._prefilling and engine._unit is None
    # the unit that failed was its request's first: no span of it was closed
    assert joined == (which == "llama")
    assert not [u for u in got["units"]
                if u["request"] == requests[victim].request_id]
