"""A decode tick's transfers between host and device: ONE staged buffer in,
one fetched vector out (`models/tick_io.py`, `serve/engine.py::_decode_tick`).
Every field crosses bit for bit; the engine's program is the
thirteen-argument `paged_decode_step`'s body under its name, and gives its
tokens, keys, counters and stores on every family; the engine's thread makes
exactly one transfer each way a tick and says so on the span; no buffer it
has handed to the device is written again. float32 on the CPU at tiny sizes."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_tiny
import latent_tiny
import mla_tiny
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
    assert staged.buffer.shape == (S, tick_io.COLUMNS + pages) == (S, 16)
    assert staged.buffer.dtype == np.int32
    # an unoccupied slot's row: greedy, writes nothing
    assert not staged.temperature.any() and not staged.active.any()
    assert (staged.top_p == 1.0).all() and not staged.top_k.any()
    for name, value in want.items():
        getattr(staged, name)[...] = value
        assert np.shares_memory(getattr(staged, name), staged.buffer)

    got = jax.jit(tick_io.unpack)(jnp.asarray(staged.buffer))
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

def _host_fields(staged: np.ndarray) -> dict:
    """The staged buffer taken apart on the host, by the test's own slices
    (the layout in `tick_io`'s docstring), as the thirteen arguments."""
    f32 = lambda col: np.ascontiguousarray(col).view(np.float32)
    return dict(
        token=staged[:, 0], pos=staged[:, 1], write_pos=staged[:, 2],
        active=staged[:, 3], top_k=staged[:, 4],
        keys=np.ascontiguousarray(staged[:, 5:7]).view(np.uint32),
        temperature=f32(staged[:, 7]), top_p=f32(staged[:, 8]),
        page_table=staged[:, 9:])


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_engine_emits_what_the_thirteen_argument_step_gives(family):
    """A seeded mix of greedy, temperature-only and filtered rows through
    the engine. Every tick, the thirteen-argument `paged_decode_step` runs
    by hand on copies of the stores with the rows the engine staged: its
    tokens and keys on the decoding rows, its counters and both stores are
    the engine's program's, bit for bit; the streams the clients get are its
    tokens, and the spans' counters its counters' sums."""
    engine = _engine(family, decode_span_every=4)
    step = engine._family.paged_decode_step
    names = engine._family.counters
    real = engine._tick_program
    streams: dict = {}
    sums = dict.fromkeys(names, 0)
    branches = set()

    def checked(params, staged, pool, kv_mask, cfg):
        f = {k: jnp.asarray(v) for k, v in
             _host_fields(np.asarray(staged)).items()}
        want = step(params, f["token"], jax.tree.map(jnp.copy, pool),
                    f["page_table"], f["pos"], f["write_pos"],
                    jnp.copy(kv_mask), f["active"], f["keys"],
                    f["temperature"], f["top_k"], f["top_p"], cfg)
        got = real(params, staged, pool, kv_mask, cfg)
        assert sorted(got) == ["fetch", "kv_mask", "pool"]
        token, keys, counters = tick_io.split_result(
            np.asarray(got["fetch"]), SLOTS)
        rows = sorted(engine._occupants)
        assert rows == np.flatnonzero(np.asarray(f["active"])).tolist()
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

class _Counting:
    """A module as the engine sees it, whose `asarray` counts and lets
    through the transfers of `kind` made inside a tick; every other
    attribute is the module's own."""

    def __init__(self, module, kind, ticking):
        self._module, self._kind, self._ticking = module, kind, ticking
        self.seen = []

    def __getattr__(self, name):
        return getattr(self._module, name)

    def asarray(self, a, *args, **kwargs):
        if self._ticking and isinstance(a, self._kind):
            self.seen.append(a)
            with jax.transfer_guard("allow"):
                return self._module.asarray(a, *args, **kwargs)
        return self._module.asarray(a, *args, **kwargs)


def _guarded(engine, monkeypatch):
    """Run every `_decode_tick` of `engine` with explicit AND implicit
    transfers refused (`transfer_guard("disallow_explicit")` holds on the
    CPU backend too), but for the engine's own `jnp.asarray` of a numpy
    array and `np.asarray` of a device array, which are counted. Returns the
    two counting modules and the list of ticks' counts."""
    ticking = []
    to_device = _Counting(jnp, np.ndarray, ticking)
    to_host = _Counting(np, jax.Array, ticking)
    monkeypatch.setattr(engine_module, "jnp", to_device)
    monkeypatch.setattr(engine_module, "np", to_host)
    real_tick = engine._decode_tick
    per_tick = []

    def guarded_tick():
        before = len(to_device.seen), len(to_host.seen)
        ticking.append(True)
        try:
            with jax.transfer_guard("disallow_explicit"):
                real_tick()
        finally:
            ticking.clear()
        per_tick.append((len(to_device.seen) - before[0],
                         len(to_host.seen) - before[1]))

    engine._decode_tick = guarded_tick
    return to_device, to_host, per_tick


@pytest.mark.parametrize("family", ["llama", "hybrid_moe",
                                    "latent_moe.a.x-k1"],
                         ids=["no_counters", "counters", "counters_chunked"])
def test_a_tick_makes_one_transfer_each_way(family, monkeypatch):
    """Any transfer the engine's thread makes in a tick other than the one
    copy in and the one copy out raises; those two are counted here and by
    the engine (`h2d_copies`, `d2h_copies` on `serve_decode_step`), with and
    without counters in the fetched vector."""
    engine = _engine(family, decode_span_every=3)
    # compile outside the guard (a program's constants are transfers)
    _serve(_engine(family), _mix(2))
    to_device, to_host, per_tick = _guarded(engine, monkeypatch)
    spans, stop = _listen()
    try:
        _serve(engine, _mix(4))
    finally:
        stop()
    assert len(per_tick) >= 8 and set(per_tick) == {(1, 1)}
    assert all(a.dtype == np.int32 and a.ndim == 2 for a in to_device.seen)
    assert all(a.dtype == jnp.int32 and a.ndim == 1 for a in to_host.seen)
    width = 3 * SLOTS + len(engine._family.counters)
    assert {a.shape for a in to_host.seen} == {(width,)}
    ticks = [s for s in spans if s["name"] == "serve_decode_step"]
    assert sum(s["ticks"] for s in ticks) == len(per_tick)
    for s in ticks:
        assert s["h2d_copies"] == s["d2h_copies"] == s["ticks"]
    # plain attributes beside `ticks_sampled`, not sums of seconds
    assert not {"h2d_copies", "d2h_copies"} & set(engine_module.TICK_SUMS)


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
        engine.params, staged, engine.slots.pool, engine.slots.kv_mask,
        engine.cfg)
    assert "module @jit_paged_decode_step" in packed.as_text()
    f = tick_io.unpack(staged)
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
    it was handed to. The engine stages every tick in a fresh buffer:
    `left_alone`, every buffer handed over is kept alive with a copy of what
    it held, and holds the same when the engine is done (nothing wrote it
    again, through any view); `scribbled`, every buffer is overwritten with
    garbage once its tick has returned, and nothing the engine emits
    afterwards changes. Either way the tokens are an undisturbed engine's,
    and no buffer shares memory with an earlier one or with the page table
    the engine keeps editing."""
    want = _serve(_engine("llama"), _mix())
    engine = _engine("llama")
    handed, held, ticking = [], [], []

    class Recording(_Counting):
        def asarray(self, a, *args, **kwargs):
            if ticking and isinstance(a, np.ndarray):
                assert not np.shares_memory(a, engine.slots.page_table)
                assert not any(np.shares_memory(a, b) for b in handed)
                handed.append(a)
                held.append(a.copy())
            return jnp.asarray(a, *args, **kwargs)

    monkeypatch.setattr(engine_module, "jnp", Recording(jnp, np.ndarray, ()))
    real_tick = engine._decode_tick

    def tick():
        ticking.append(True)
        try:
            real_tick()
        finally:
            ticking.pop()
        assert len(handed) == len(ticking_done) + 1
        ticking_done.append(True)
        if after == "scribbled":
            handed[-1][...] = -7

    ticking_done = []
    engine._decode_tick = tick
    assert _serve(engine, _mix()) == want
    assert len(handed) == len(ticking_done) >= 8
    if after == "left_alone":
        for a, b in zip(handed, held):
            np.testing.assert_array_equal(a, b)
