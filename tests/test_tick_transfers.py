"""A decode tick's transfers between host and device: ONE staged buffer in,
one fetched vector out (`models/tick_io.py`, `serve/engine.py`), and the
engine's one tick in flight. Every field crosses bit for bit; the engine's
program is the thirteen-argument `paged_decode_step`'s body under its name,
and gives its tokens, keys, counters and stores on every family; the
engine's thread makes exactly one transfer each way a tick and says so on the
span; no buffer it has handed to the device is written again. With a tick in
flight (tick k enqueued before tick k-1 is read, its token and key fed back
on the device) the streams are those of the serial order, kept in
`tests/tick_ahead.py`: greedy and sampled, for rows that join mid-flight, end
by length, end by eos (one overrun), are cancelled, and under `drain()` and
`shutdown()`. float32 on the CPU at tiny sizes."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_tiny
import latent_tiny
import mla_tiny
import tick_ahead
from llama_pipeline_parallel_tpu import serve
from llama_pipeline_parallel_tpu.models import family as families
from llama_pipeline_parallel_tpu.models import tick_io
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.serve import engine as engine_module
from llama_pipeline_parallel_tpu.utils import trace

SLOTS = 3


def _dense():
    cfg = LlamaConfig.tiny()
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg), dict(
        max_len=32, prompt_buckets=(8, 16), page_size=8, num_pages=32)


def _hybrid():
    return hybrid_tiny.config(), hybrid_tiny.both_sides()[0], dict(
        max_len=48, prompt_buckets=(8, 16), page_size=8, num_pages=32)


def _latent(tiny):
    return tiny.config(), tiny.both_sides()[0], dict(
        max_len=64, prompt_buckets=(8, 16, 32), page_size=4, num_pages=64,
        prefill_chunk_tokens=8)


FAMILIES = {"llama": _dense, "hybrid_moe": _hybrid,
            "latent_moe.dots3": lambda: _latent(latent_tiny),
            "latent_moe.a.x-k1": lambda: _latent(mla_tiny)}


def _engine(family: str, **knobs):
    cfg, params, shape = FAMILIES[family]()
    return serve.ServeEngine(params, cfg, serve.ServeConfig(
        max_slots=SLOTS, max_queue=16, **{**shape, **knobs}))


def _mix(n: int = 6, seed: int = 11) -> list:
    """A seeded mix of requests: greedy, temperature alone, a top-k, a
    top-p and both filters, prompts of 3 to 14 tokens, 4 to 9 new ones."""
    rng = np.random.default_rng(seed)
    knobs = [dict(), dict(temperature=0.7), dict(temperature=0.7, top_k=5),
             dict(temperature=1.3, top_p=0.9),
             dict(temperature=0.9, top_k=7, top_p=0.8), dict()]
    return [serve.ServeRequest(
        input_ids=rng.integers(0, 128, int(rng.integers(3, 15))).tolist(),
        seed=int(rng.integers(0, 2 ** 31)),
        gen=families.GenerationConfig(
            max_new_tokens=int(rng.integers(4, 10)), **knobs[i % len(knobs)]))
        for i in range(n)]


def _serve(engine, requests) -> list:
    """Admissions spread over the first steps, then to the end."""
    handles = []
    for request in requests:
        handles.append(engine.submit(request))
        engine.step()
    engine.drain(timeout_s=300)
    engine.shutdown()
    return [h.result(timeout=1) for h in handles]


def _listen():
    spans = []
    listener = lambda rec: spans.append(dict(rec))
    trace.recorder().add_listener(listener)
    return spans, lambda: trace.recorder().remove_listener(listener)


# -- (a) every field, bit for bit ----------------------------------------------

def _bits(a) -> np.ndarray:
    a = np.ascontiguousarray(a)
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


def test_the_staged_buffer_carries_every_field_bit_for_bit():
    """Floats with no short decimal (and -0.0, an infinity, a NaN with a
    payload), keys above 2^31, negative integers and a page table that holds
    the garbage page: what the program unpacks is what the host wrote."""
    S, pages, garbage = 5, 7, 640
    rng = np.random.default_rng(0)
    want = {
        "token": rng.integers(0, 102400, S), "pos": rng.integers(0, 2560, S),
        "write_pos": rng.integers(0, 2560, S),
        "active": np.asarray([1, 0, 1, 1, 0]),
        "top_k": np.asarray([0, 50, -1, 2 ** 31 - 1, 7]),
        "fed": np.asarray([0, 0, 1, 0, 1]),
        "keys": rng.integers(2 ** 31, 2 ** 32, (S, 2), dtype=np.uint64),
        "temperature": np.asarray([0.7, 0.0, -0.0, np.inf, 1.3]),
        "top_p": np.asarray([0.9, 1.0, 0.1, 0.95, 0.8]),
        "page_table": rng.integers(0, garbage, (S, pages))}
    want["page_table"][1, :] = garbage
    want["page_table"][3, 4:] = garbage
    kinds = {"keys": np.uint32, "temperature": np.float32,
             "top_p": np.float32}
    want = {k: v.astype(kinds.get(k, np.int32)) for k, v in want.items()}
    want["temperature"][1:2].view(np.uint32)[:] = 0x7FC12345     # a NaN
    assert want["keys"].min() >= 2 ** 31

    staged = tick_io.stage(S, pages)
    assert staged.buffer.shape == (S, tick_io.COLUMNS + pages) == (S, 17)
    assert staged.buffer.dtype == np.int32
    # an unoccupied slot's row: greedy, writes nothing, fed from nowhere
    assert not staged.temperature.any() and not staged.active.any()
    assert not staged.fed.any()
    assert (staged.top_p == 1.0).all() and not staged.top_k.any()
    for name, value in want.items():
        getattr(staged, name)[...] = value
        assert np.shares_memory(getattr(staged, name), staged.buffer)

    # the tick before's fetched vector, two counters behind its keys: a fed
    # row's token and key words are ITS, every other row's the buffer's
    prev_token = rng.integers(0, 102400, S).astype(np.int32)
    prev_keys = rng.integers(2 ** 31, 2 ** 32, (S, 2), dtype=np.uint64).astype(
        np.uint32)
    prev = jax.jit(tick_io.pack_result)(
        jnp.asarray(prev_token), jnp.asarray(prev_keys),
        jnp.asarray([9, 4], jnp.int32))
    fed = want.pop("fed").astype(bool)
    want["token"] = np.where(fed, prev_token, want["token"])
    want["keys"] = np.where(fed[:, None], prev_keys, want["keys"])
    assert (want["keys"][fed] != staged.keys[fed]).any()

    got = jax.jit(tick_io.unpack)(jnp.asarray(staged.buffer), prev)
    order = ("token", "page_table", "pos", "write_pos", "active", "keys",
             "temperature", "top_k", "top_p")       # `paged_decode_step`'s
    for name, value in zip(order, got):
        assert value.dtype == want[name].dtype, name
        assert value.shape == want[name].shape, name
        np.testing.assert_array_equal(_bits(value), _bits(want[name]),
                                      err_msg=name)
    assert np.isnan(np.asarray(got[6])[1]) and np.signbit(np.asarray(got[6])[2])


@pytest.mark.parametrize("counters", [None, [3, 0, 2 ** 31 - 1, 17, 5]],
                         ids=["no_counters", "counters"])
def test_the_fetched_vector_carries_token_keys_and_counters(counters):
    S = 4
    rng = np.random.default_rng(1)
    token = rng.integers(0, 102400, S).astype(np.int32)
    keys = rng.integers(2 ** 31, 2 ** 32, (S, 2), dtype=np.uint64).astype(
        np.uint32)
    given = None if counters is None else jnp.asarray(counters, jnp.int32)
    fetched = np.asarray(jax.jit(tick_io.pack_result)(
        jnp.asarray(token), jnp.asarray(keys), given))
    assert fetched.dtype == np.int32
    assert fetched.shape == (3 * S + len(counters or ()),)
    got_token, got_keys, got_counters = tick_io.split_result(fetched, S)
    np.testing.assert_array_equal(got_token, token)
    assert got_keys.dtype == np.uint32
    np.testing.assert_array_equal(got_keys, keys)
    assert got_counters.tolist() == (counters or [])


# -- (b) the engine's tokens are the thirteen-argument program's ---------------

def _host_fields(staged: np.ndarray, prev: np.ndarray) -> dict:
    """The staged buffer taken apart on the host, by the test's own slices
    (the layout in `tick_io`'s docstring), as the thirteen arguments; a fed
    row's token and key from the tick before's fetched vector."""
    f32 = lambda col: np.ascontiguousarray(col).view(np.float32)
    S = len(staged)
    fed = staged[:, 5] != 0
    return dict(
        token=np.where(fed, prev[:S], staged[:, 0]), pos=staged[:, 1],
        write_pos=staged[:, 2], active=staged[:, 3], top_k=staged[:, 4],
        keys=np.ascontiguousarray(np.where(
            fed[:, None], prev[S:3 * S].reshape(S, 2),
            staged[:, 6:8])).view(np.uint32),
        temperature=f32(staged[:, 8]), top_p=f32(staged[:, 9]),
        page_table=staged[:, 10:])


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_engine_emits_what_the_thirteen_argument_step_gives(family):
    """A seeded mix of greedy, temperature-only and filtered rows through
    the engine. Every tick, the thirteen-argument `paged_decode_step` runs
    by hand on copies of the stores with the rows the engine staged (a row
    the tick in flight holds: with that tick's token and key): its tokens
    and keys on the decoding rows, its counters and both stores are the
    engine's program's, bit for bit; the streams the clients get are its
    tokens, and the spans' counters its counters' sums."""
    engine = _engine(family, decode_span_every=4)
    step = engine._family.paged_decode_step
    names = engine._family.counters
    real = engine._tick_program
    streams: dict = {}
    sums = dict.fromkeys(names, 0)
    branches = set()

    def checked(params, staged, prev, pool, kv_mask, cfg):
        f = {k: jnp.asarray(v) for k, v in
             _host_fields(np.asarray(staged), np.asarray(prev)).items()}
        want = step(params, f["token"], jax.tree.map(jnp.copy, pool),
                    f["page_table"], f["pos"], f["write_pos"],
                    jnp.copy(kv_mask), f["active"], f["keys"],
                    f["temperature"], f["top_k"], f["top_p"], cfg)
        got = real(params, staged, prev, pool, kv_mask, cfg)
        assert sorted(got) == ["fetch", "kv_mask", "pool"]
        token, keys, counters = tick_io.split_result(
            np.asarray(got["fetch"]), SLOTS)
        # every occupant but the ones whose last token is in flight
        rows = np.flatnonzero(np.asarray(f["active"])).tolist()
        assert set(rows) <= set(engine._occupants) and rows
        np.testing.assert_array_equal(token[rows],
                                      np.asarray(want["token"])[rows])
        np.testing.assert_array_equal(keys[rows],
                                      np.asarray(want["keys"])[rows])
        assert counters.tolist() == (
            np.asarray(want["counters"]).tolist() if names else [])
        for a, b in zip(jax.tree.leaves((got["pool"], got["kv_mask"])),
                        jax.tree.leaves((want["pool"], want["kv_mask"]))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for slot in rows:
            streams.setdefault(engine._occupants[slot].request.request_id,
                               []).append(int(want["token"][slot]))
        for name, n in zip(names, counters.tolist()):
            sums[name] += n
        branches.add(int(families.sampler_branch(
            np.asarray(f["temperature"]), np.asarray(f["top_k"]),
            np.asarray(f["top_p"]))))
        return got

    engine._tick_program = checked
    requests = _mix()
    spans, stop = _listen()
    try:
        served = _serve(engine, requests)
    finally:
        stop()
    assert branches == {0, 1, 2}             # the mix reached every sampler
    for request, tokens in zip(requests, served):
        assert len(tokens) == request.gen.max_new_tokens
        # the first token is the prefill's, the rest are ticks'
        assert tokens[1:] == streams[request.request_id]
    ticks = [s for s in spans if s["name"] == "serve_decode_step"]
    assert {name: sum(s[name] for s in ticks) for name in names} == sums
    if names:
        assert sums["routed_total"] > 0


# -- (c) one transfer each way a tick ------------------------------------------

def _guarded(engine, monkeypatch):
    """Run every dispatch and every collection of a tick of `engine` with
    explicit AND implicit transfers refused
    (`transfer_guard("disallow_explicit")` holds on the CPU backend too),
    but for the engine's own `jnp.asarray` of a numpy array and `np.asarray`
    of a device array, which are counted. Returns the two counting modules
    and the log, in the order the calls ran: ("dispatch" | "collect", the
    tick's number, copies in, copies out)."""
    ticking = []
    to_device = tick_ahead.Counting(jnp, np.ndarray, ticking)
    to_host = tick_ahead.Counting(np, jax.Array, ticking)
    monkeypatch.setattr(engine_module, "jnp", to_device)
    monkeypatch.setattr(engine_module, "np", to_host)
    log, dispatched = [], []        # the ticks, kept: a number is an index

    def guarded(real, kind):
        def call(arg):
            before = len(to_device.seen), len(to_host.seen)
            ticking.append(True)
            try:
                with jax.transfer_guard("disallow_explicit"):
                    out = real(arg)
            finally:
                ticking.clear()
            if kind == "dispatch" and out is not None:
                dispatched.append(out)
            tick = out if kind == "dispatch" else arg
            if tick is not None:
                number = next(i for i, t in enumerate(dispatched) if t is tick)
                log.append((kind, number, len(to_device.seen) - before[0],
                            len(to_host.seen) - before[1]))
            return out
        return call

    engine._dispatch_tick = guarded(engine._dispatch_tick, "dispatch")
    engine._collect_tick = guarded(engine._collect_tick, "collect")
    return to_device, to_host, log


@pytest.mark.parametrize("family", ["llama", "hybrid_moe",
                                    "latent_moe.a.x-k1"],
                         ids=["no_counters", "counters", "counters_chunked"])
def test_a_tick_makes_one_transfer_each_way(family, monkeypatch):
    """Any transfer the engine's thread makes for a tick other than the one
    copy in when it is dispatched and the one copy out when it is collected
    raises; those two are counted here and by the engine (`h2d_copies`,
    `d2h_copies` on `serve_decode_step`), with and without counters in the
    fetched vector. The tick before's fetched vector is CONSUMED ON THE
    DEVICE: a tick that was enqueued behind one in flight was enqueued
    before that one's vector was read (`ticks_ahead` counts them: every tick
    but the restarts)."""
    engine = _engine(family, decode_span_every=3)
    # compile outside the guard (a program's constants are transfers)
    _serve(_engine(family), _mix(2))
    to_device, to_host, log = _guarded(engine, monkeypatch)
    spans, stop = _listen()
    try:
        _serve(engine, _mix(4))
    finally:
        stop()
    ticks = sorted(k for kind, k, _, _ in log if kind == "dispatch")
    assert ticks == list(range(len(ticks))) and len(ticks) >= 8
    assert sorted(k for kind, k, _, _ in log if kind == "collect") == ticks
    assert {entry[2:] for entry in log if entry[0] == "dispatch"} == {(1, 0)}
    assert {entry[2:] for entry in log if entry[0] == "collect"} == {(0, 1)}
    assert all(a.dtype == np.int32 and a.ndim == 2 for a in to_device.seen)
    assert all(a.dtype == jnp.int32 and a.ndim == 1 for a in to_host.seen)
    width = 3 * SLOTS + len(engine._family.counters)
    assert {a.shape for a in to_host.seen} == {(width,)}
    # ticks are collected in the order they were dispatched, each after the
    # NEXT one's dispatch unless the pipeline had run dry
    at = {entry[:2]: i for i, entry in enumerate(log)}
    assert [k for kind, k, _, _ in log if kind == "collect"] == ticks
    ahead = [k for k in ticks[1:]
             if at["dispatch", k] < at["collect", k - 1]]
    assert all(at["dispatch", k] < at["collect", k] for k in ticks)
    decode_spans = [s for s in spans if s["name"] == "serve_decode_step"]
    assert sum(s["ticks"] for s in decode_spans) == len(ticks)
    assert sum(s["ticks_ahead"] for s in decode_spans) == len(ahead)
    assert len(ahead) >= len(ticks) - 2      # `_serve` never runs dry
    for s in decode_spans:
        assert s["h2d_copies"] == s["d2h_copies"] == s["ticks"]
    # plain attributes beside `ticks_sampled`, not sums of seconds
    assert not {"h2d_copies", "d2h_copies", "ticks_ahead",
                "rows_overrun"} & set(engine_module.TICK_SUMS)


# -- (d) the program's name -----------------------------------------------------

def _paths(lowered) -> set:
    text = lowered.as_text(debug_info=True)
    return set(re.findall(r'^#loc\d+ = loc\("(jit\([^"]*)"', text, re.M))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_engines_tick_lowers_under_the_name_the_readers_find(family):
    """The benchmark tells the tick from the prefills by
    `jit(paged_decode_step)` and reads scopes under it: the engine's program
    carries the name, and every operation of the thirteen-argument program
    keeps its path (no second `jit(...)` level under the first)."""
    engine = _engine(family)
    S, pages = engine.slots.page_table.shape
    staged = jnp.asarray(tick_io.stage(S, pages).buffer)
    packed = engine._tick_program.lower(
        engine.params, staged, engine._no_fetch, engine.slots.pool,
        engine.slots.kv_mask, engine.cfg)
    assert "module @jit_paged_decode_step" in packed.as_text()
    f = tick_io.unpack(staged, engine._no_fetch)
    plain = engine._family.paged_decode_step.lower(
        engine.params, f[0], engine.slots.pool, *f[1:4],
        engine.slots.kv_mask, *f[4:], engine.cfg)
    ours, theirs = _paths(packed), _paths(plain)
    theirs = {p for p in theirs if p.startswith("jit(paged_decode_step)/")}
    assert len(theirs) > 20
    # every path under a scope a reader looks for is still there (what goes
    # is the stacking of the latent tick's `selection`, which is not the
    # engine's to read: a few unscoped operations of the layer loop)
    scoped = set(trace.SCOPES + trace.HYBRID_SCOPES + trace.LATENT_SCOPES)
    under_a_scope = {p for p in theirs if scoped & set(p.split("/"))}
    assert len(under_a_scope) > 20
    assert under_a_scope <= ours, sorted(under_a_scope - ours)[:5]
    assert len(theirs - ours) <= 4, sorted(theirs - ours)
    assert not any("/jit(paged_decode_step)" in p for p in ours)
    # one program a family, whichever engine asks
    assert _engine(family)._tick_program is engine._tick_program


# -- (e) a buffer handed to the device is not written again ---------------------

@pytest.mark.parametrize("after", ["left_alone", "scribbled"])
def test_no_write_reaches_a_staging_buffer_the_device_was_given(
        after, monkeypatch):
    """On the CPU backend `jnp.asarray` of an aligned numpy array SHARES its
    memory, so a staging buffer written again would change under the program
    it was handed to, and with a tick in flight the NEXT buffer is staged
    while that program may still run. The engine stages every tick in a
    fresh buffer: `left_alone`, every buffer handed over is kept alive with
    a copy of what it held, and holds the same when the engine is done
    (nothing wrote it again, through any view); `scribbled`, every buffer is
    overwritten with garbage once its tick has been collected, and nothing
    the engine emits afterwards changes. Either way the tokens are an
    undisturbed engine's, and no buffer shares memory with an earlier one or
    with the page table the engine keeps editing."""
    want = _serve(_engine("llama"), _mix())
    engine = _engine("llama")
    handed, held, ticking = [], [], []

    class Recording(tick_ahead.Counting):
        def asarray(self, a, *args, **kwargs):
            if ticking and isinstance(a, np.ndarray):
                assert not np.shares_memory(a, engine.slots.page_table)
                assert not any(np.shares_memory(a, b) for b in handed)
                handed.append(a)
                held.append(a.copy())
            return jnp.asarray(a, *args, **kwargs)

    monkeypatch.setattr(engine_module, "jnp", Recording(jnp, np.ndarray, ()))
    real_dispatch, real_collect = engine._dispatch_tick, engine._collect_tick

    def dispatch(before):
        ticking.append(True)
        try:
            tick = real_dispatch(before)
        finally:
            ticking.pop()
        dispatched.extend([] if tick is None else [tick])
        assert len(handed) == len(dispatched)
        return tick

    def collect(tick):
        waited = real_collect(tick)
        assert tick is dispatched[len(collected)]       # in order
        if after == "scribbled":
            handed[len(collected)][...] = -7
        collected.append(tick)
        return waited

    dispatched, collected = [], []
    engine._dispatch_tick, engine._collect_tick = dispatch, collect
    assert _serve(engine, _mix()) == want
    assert len(handed) == len(collected) >= 8
    if after == "left_alone":
        for a, b in zip(handed, held):
            np.testing.assert_array_equal(a, b)


# -- (f) one tick in flight: the streams are the serial order's -----------------

def _requests(n: int = 7, seed: int = 5, eos=None) -> list:
    """`_mix` with budgets of 2 to 11 tokens (a row of two ends by length at
    its first tick) and, for the requests `eos` names, an `eos_token_id`."""
    requests = _mix(n, seed)
    rng = np.random.default_rng(seed + 1)
    for i, request in enumerate(requests):
        request.gen = dataclasses.replace(
            request.gen, max_new_tokens=int(rng.integers(2, 12)),
            eos_token_id=(eos or {}).get(i))
    return requests


IN_FLIGHT = {**{name: (name, {}) for name in FAMILIES},
             "llama.int8": ("llama", dict(kv_quant="int8")),
             "llama.chunked": ("llama", dict(prefill_chunk_tokens=8)),
             "llama.prefix_cache": ("llama", dict(prefix_cache=True))}


@pytest.mark.parametrize("spread", [1, 3], ids=["a_step_apart", "three_apart"])
@pytest.mark.parametrize("which", sorted(IN_FLIGHT))
def test_the_streams_with_a_tick_in_flight_are_the_serial_orders(which, spread):
    """Greedy and sampled rows, rows that join while others decode and rows
    that end by length, on every family and, on the dense one, with the int8
    pool, chunked prefill and the prefix cache: what every handle receives is
    what the serial order (stage, dispatch, wait, emit, in turn) gives it, bit
    for bit; every tick but the restarts is dispatched ahead, nothing
    overruns, and every row-tick is a delivered token."""
    family, knobs = IN_FLIGHT[which]
    if knobs.get("prefix_cache"):
        # shared prefixes, so the cache serves some of them
        def requests():
            made = _requests()
            for r in made[1::2]:
                r.input_ids = made[0].input_ids[:9] + r.input_ids[:3]
            return made
    else:
        requests = _requests
    serial, ahead = tick_ahead.both_orders(
        lambda: _engine(family, decode_span_every=4, **knobs), requests,
        spread=spread)
    budgets = [r.gen.max_new_tokens for r in requests()]
    assert [len(t) for t in ahead["tokens"]] == budgets
    assert all(h.done and h.error is None for h in ahead["handles"])
    assert ahead["sums"]["rows_overrun"] == 0
    assert ahead["sums"]["ticks_ahead"] >= ahead["sums"]["ticks"] - 3
    assert ahead["sums"]["tokens"] == sum(n - 1 for n in budgets)


@pytest.mark.parametrize("family", ["llama", "hybrid_moe"])
def test_a_row_that_ends_by_eos_overruns_one_tick_and_no_more(family):
    """A row's eos is seen one tick late: the tick already enqueued runs its
    row once more (one `rows_overrun` a row), that token reaches nobody, `emitted`
    and the `serve_request` span count what was delivered, the slot and its
    pages are free at once, and the request that takes the slot next (its
    prefill and its ticks follow the overrun's write on the device) is
    served as the serial order serves it, as is every other row."""
    make = lambda: _engine(family, decode_span_every=4)
    plain = tick_ahead.run(make(), _requests(), serially=True)["tokens"]
    # two rows end early: one a long way from its budget, one a tick from it
    # (the tick that overruns is the last its budget allowed)
    long_one = max(range(len(plain)), key=lambda i: len(plain[i]))
    cut, eos = tick_ahead.eos_of(plain[long_one])
    near = next(i for i, t in enumerate(plain) if i != long_one
                and len(t) >= 4 and t[-2] not in t[:-2])
    ends = {long_one: eos, near: plain[near][-2]}
    spans, stop = _listen()
    try:
        serial, ahead = tick_ahead.both_orders(
            make, lambda: _requests(eos=ends))
    finally:
        stop()
    assert ahead["tokens"][long_one] == plain[long_one][:cut + 1]
    assert ahead["tokens"][near] == plain[near][:-1]
    for i, tokens in enumerate(ahead["tokens"]):
        if i not in ends:
            assert tokens == plain[i]
    # each was in the tick enqueued before its eos was read, once
    assert ahead["sums"]["rows_overrun"] == 2
    assert serial["sums"]["tokens"] + 2 == ahead["sums"]["tokens"]
    emitted = {s["request"]: s["tokens"] for s in spans
               if s["name"] == "serve_request"}
    for result in (serial, ahead):
        for handle in result["handles"]:
            assert emitted[handle.request.request_id] == len(handle.tokens_out)


def test_the_overrun_row_alone_in_flight_is_collected_at_the_idle_boundary():
    """One request, ended by its eos: the tick that overran holds no other
    row, and no occupant is left to step for. The idle boundary collects it
    before it parks: its counters and the overrun are on the span."""
    plain = tick_ahead.run(_engine("hybrid_moe"), _requests(1),
                           serially=True)["tokens"]
    cut, eos = tick_ahead.eos_of(plain[0], least=1)
    engine = _engine("hybrid_moe")
    got = tick_ahead.run(engine, _requests(1, eos={0: eos}))
    assert got["tokens"] == [plain[0][:cut + 1]]
    tick_ahead.check_the_spans(got)
    assert got["sums"]["rows_overrun"] == 1
    assert got["sums"]["ticks"] == got["sums"]["tokens"] == cut + 1
    # 4 experts a token in each of 8 layers, the overrun's among them
    assert got["sums"]["routed_total"] == (cut + 1) * 4 * 8
    assert engine._in_flight is None and engine.slots.pages_used == 0


def test_a_cancelled_row_still_gets_the_token_of_the_tick_in_flight():
    """`note_abandoned` while the row decodes: at the next boundary the tick
    in flight is collected first (the row's token of it is delivered, so
    every row-tick the device ran is a token a handle received), then the row
    is out of the next tick; the other rows' streams are untouched, nothing
    overruns, and the tick after the cancellation restarts the pipeline."""
    make = lambda: _engine("llama", decode_span_every=4)
    plain = tick_ahead.run(make(), _requests(), serially=True)["tokens"]
    victim = max(range(3), key=lambda i: len(plain[i]))
    assert len(plain[victim]) >= 6
    seen = {}

    def cancel(engine, step):
        r = next((r for r in engine._occupants.values()
                  if r.request.seed == doomed.seed), None)
        if r is not None and r.emitted == 3 and not seen:
            seen["in_flight"] = r.in_flight
            engine.note_abandoned(r.request)

    requests = _requests()
    doomed = requests[victim]
    got = tick_ahead.run(make(), requests, during=cancel)
    assert seen == {"in_flight": 1}
    # three delivered when it was cancelled, and the one in flight
    assert got["tokens"][victim] == plain[victim][:4]
    assert got["handles"][victim].done
    for i, tokens in enumerate(got["tokens"]):
        if i != victim:
            assert tokens == plain[i]
    tick_ahead.check_the_spans(got)
    assert got["sums"]["rows_overrun"] == 0 and got["restarts"] >= 2


@pytest.mark.parametrize("end", ["shutdown", "drain"])
def test_shutdown_and_drain_first_collect_the_tick_in_flight(end):
    """Stop a hybrid engine between two steps, a tick in flight. `shutdown()`
    collects it before it fails the handles: every row-tick the device ran
    is a token a handle holds, and the device's counters summed over all
    spans are the host's count of those tokens. `drain()` steps until no
    tick is in flight either."""
    engine = _engine("hybrid_moe", decode_span_every=4)
    requests = _requests(5)
    spans, stop = _listen()
    try:
        handles = []
        for request in requests:
            handles.append(engine.submit(request))
            engine.step()
        assert engine._in_flight is not None and engine._occupants
        in_flight = len(engine._in_flight.rows)
        before = sum(len(h.tokens_out) for h in handles)
        if end == "shutdown":
            engine.shutdown()
            assert sum(len(h.tokens_out) for h in handles) == before + in_flight
            cut = [h for h in handles if h.error is not None]
            assert cut and all(isinstance(h.error, serve.EngineShutdown)
                               for h in cut)
        else:
            engine.drain(timeout_s=300)
            assert [len(h.tokens_out) for h in handles] == [
                r.gen.max_new_tokens for r in requests]
            engine.shutdown()
    finally:
        stop()
    assert engine._in_flight is None and all(h.done for h in handles)
    assert engine.slots.pages_used == 0
    ticks = [s for s in spans if s["name"] == "serve_decode_step"]
    decoded = sum(len(h.tokens_out) - 1 for h in handles if h.tokens_out)
    assert sum(s["tokens"] for s in ticks) == decoded
    assert sum(s["rows_overrun"] for s in ticks) == 0
    assert sum(s["routed_total"] for s in ticks) == decoded * 4 * 8
    for s in ticks:
        assert s["h2d_copies"] == s["d2h_copies"] == s["ticks"]
