"""The decode tick's nested profiler events, the sums beside them on
`serve_decode_step` and the wall-clock anchor, on a tiny engine under a real
`jax.profiler` capture (a CPU capture has the host plane, which is all these
read), and the one lookup of `TraceAnnotation`; the engine thread's account
of its own seconds beside them (`HOST_SUMS`, `HOST_COUNTS`) and the stall
records, with stalls made on purpose."""

import gc
import os
import sys
import threading
import time

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import tick_gap, xplane  # noqa: E402
from llama_pipeline_parallel_tpu.models.llama import decode  # noqa: E402
from llama_pipeline_parallel_tpu.models.llama import model as llama  # noqa: E402
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig  # noqa: E402
from llama_pipeline_parallel_tpu.serve import (  # noqa: E402
    ServeConfig,
    ServeEngine,
    ServeRequest,
)
from llama_pipeline_parallel_tpu.serve.engine import (  # noqa: E402
    HOST_COUNTS,
    HOST_SUMS,
    TICK_SUMS,
)
from llama_pipeline_parallel_tpu.models import tick_io  # noqa: E402
from llama_pipeline_parallel_tpu.utils import trace  # noqa: E402

OLD = (trace.TICK_STAGE, trace.TICK_DISPATCH, trace.TICK_WAIT, trace.TICK_EMIT)
PARENT_OF = {
    trace.TICK_GROW: trace.TICK_DISPATCH, trace.TICK_H2D: trace.TICK_DISPATCH,
    trace.TICK_ENQUEUE: trace.TICK_DISPATCH,
    trace.TICK_BLOCK: trace.TICK_WAIT, trace.TICK_FETCH: trace.TICK_WAIT,
    trace.PREFILL_ENQUEUE: "serve_prefill"}


def _serve(capture_dir=None):
    """Two requests, one of them sampling, through a fresh engine; returns
    (their tokens, the spans heard, the capture's host events or None)."""
    cfg = LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(params, cfg, ServeConfig(
        max_slots=2, max_len=24, prompt_buckets=(16,), page_size=8,
        max_queue=8, decode_span_every=3))
    spans = []
    listener = lambda rec: spans.append(dict(rec))
    trace.recorder().add_listener(listener)
    if capture_dir is not None:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(capture_dir, profiler_options=options)
    try:
        handles = [engine.submit(ServeRequest(
            input_ids=[5, 6, 7 + i], seed=i,
            gen=decode.GenerationConfig(max_new_tokens=7,
                                        temperature=0.8 * i)))
            for i in range(2)]
        engine.drain(timeout_s=120)
        engine.shutdown()
    finally:
        if capture_dir is not None:
            jax.profiler.stop_trace()
        trace.recorder().remove_listener(listener)
    tokens = [h.result(timeout=1) for h in handles]
    host = None
    if capture_dir is not None:
        host = xplane.read(xplane.find_xplane(capture_dir))["host"]
    return tokens, spans, host


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    return _serve(str(tmp_path_factory.mktemp("capture")))


def _named(host, name):
    return sorted((s, e) for n, s, e in host if n == name)


def test_old_events_once_a_tick_and_new_events_inside_their_parents(captured):
    _, spans, host = captured
    ticks = sum(s["ticks"] for s in spans if s["name"] == "serve_decode_step")
    assert ticks == 6                     # 7 tokens: the first is prefill's
    for name in OLD + (trace.TICK_GROW, trace.TICK_H2D, trace.TICK_ENQUEUE,
                       trace.TICK_BLOCK, trace.TICK_FETCH):
        assert len(_named(host, name)) == ticks, name
    assert len(_named(host, trace.SERVE_ADMIT)) >= ticks   # one a step
    prefills = _named(host, "serve_prefill")
    assert len(prefills) == 2
    assert len(_named(host, trace.PREFILL_ENQUEUE)) == 2
    assert len(_named(host, trace.PREFILL_FIRST)) == 2
    for child, parent in PARENT_OF.items():
        parents = _named(host, parent)
        for s, e in _named(host, child):
            assert any(ps <= s and e <= pe for ps, pe in parents), child
    # a tick is enqueued before the tick before it is waited for
    dispatches, waits = (_named(host, name) for name in (
        trace.TICK_DISPATCH, trace.TICK_WAIT))
    assert all(dispatches[i + 1][1] <= waits[i][0] for i in range(ticks - 1))
    # the four phases of a tick follow one another; the parts of a phase too
    for i in range(ticks):
        phase = [_named(host, name)[i] for name in OLD]
        assert all(a[1] <= b[0] for a, b in zip(phase, phase[1:]))
        for parts in ((trace.TICK_GROW, trace.TICK_H2D, trace.TICK_ENQUEUE),
                      (trace.TICK_BLOCK, trace.TICK_FETCH)):
            inner = [_named(host, name)[i] for name in parts]
            assert all(a[1] <= b[0] for a, b in zip(inner, inner[1:]))
    # `serve_prefill` still nests in `serve_admit`
    admits = _named(host, trace.SERVE_ADMIT)
    assert all(any(a <= s and e <= b for a, b in admits) for s, e in prefills)
    # a unit's result is read one hand-over late: `serve_prefill_first` is
    # round the deferred read, outside the unit's `serve_prefill` event and
    # after the next hand-over, the second unit's and then the tick's
    reads = _named(host, trace.PREFILL_FIRST)
    enqueues = _named(host, trace.TICK_ENQUEUE)
    assert prefills[0][1] <= prefills[1][1] <= reads[0][0]
    assert reads[0][1] <= enqueues[0][0] <= enqueues[0][1] <= reads[1][0]
    assert reads[1][1] <= enqueues[1][0]        # never a second one late


def test_the_sums_beside_the_phases(captured):
    _, spans, _ = captured
    decode_spans = [s for s in spans if s["name"] == "serve_decode_step"]
    assert [s["ticks"] for s in decode_spans] == [3, 3]
    for s in decode_spans:
        assert all(s[k] >= 0.0 for k in TICK_SUMS)
        assert s["h2d_s"] + s["enqueue_s"] <= s["dispatch_s"]
        assert s["dur"] == pytest.approx(s["dispatch_s"] + s["wait_s"])
    # every sum has a reader, so none is carried for its own sake
    assert set(TICK_SUMS) == {"stage_s", "dispatch_s", "wait_s", "emit_s",
                              "h2d_s", "enqueue_s"}


def test_one_anchor_a_span_line_and_a_clock_offset_from_them(captured):
    _, spans, host = captured
    lines = sum(1 for s in spans
                if s["name"] in ("serve_decode_step", "serve_prefill"))
    anchors = [n for n, _, _ in host if n.startswith(trace.WALLCLOCK_PREFIX)]
    assert len(anchors) == lines == 2 + 2     # two flushes, two prefill units
    clock = tick_gap.clock_offset({"host": host})
    assert clock["anchors"] == 4 and clock["spread_us"] is not None
    # both anchors give the one offset, to within what a CPU test can hold:
    # an anchor is entered within a millisecond of its stamp
    assert clock["range_us"] < 5e3
    # the spans' own wall-clock stamps land inside the capture with it
    events = [(s, e) for n, s, e in host if n in OLD]
    lo, hi = min(s for s, _ in events), max(e for _, e in events)
    for span in (s for s in spans if s["name"] == "serve_decode_step"):
        at_ns = (span["ts"] * 1e6 + clock["offset_us"]) * 1e3
        assert lo - 5e6 <= at_ns <= hi + 5e6


def test_tokens_are_bit_equal_with_and_without_a_capture(captured):
    tokens, spans, _ = captured
    plain_tokens, plain_spans, _ = _serve()
    assert plain_tokens == tokens and all(len(t) == 7 for t in tokens)
    keys = lambda ss: [sorted(s) for s in ss if s["name"] == "serve_decode_step"]
    assert keys(plain_spans) == keys(spans)


def test_the_annotation_class_is_resolved_once():
    trace._annotation_class.cache_clear()
    for _ in range(5):
        with trace.annotate(trace.TICK_GROW):
            pass
    trace.wallclock_anchor()                # no trace runs: one annotate
    info = trace._annotation_class.cache_info()
    assert info.misses == 1 and info.hits == 5
    assert trace._annotation_class() is jax.profiler.TraceAnnotation


# -- the engine thread's own account (PR 50) -----------------------------------

PHASES = ("admit_s", "stage_s", "dispatch_s", "wait_s", "unit_wait_s",
          "emit_s", "loop_s")


def _engine(**overrides):
    cfg = LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return ServeEngine(params, cfg, ServeConfig(**{**dict(
        max_slots=2, max_len=24, prompt_buckets=(16,), page_size=8,
        max_queue=8, decode_span_every=3), **overrides}))


def _request(i, tokens=7):
    return ServeRequest(input_ids=[5, 6, 7 + i], seed=i,
                        gen=decode.GenerationConfig(max_new_tokens=tokens))


def _listen():
    """(spans heard so far, the undo)."""
    spans = []
    listener = lambda rec: spans.append(dict(rec))
    trace.recorder().add_listener(listener)
    return spans, lambda: trace.recorder().remove_listener(listener)


def _decode_spans(spans):
    return [s for s in spans if s["name"] == "serve_decode_step"]


def _records(spans):
    return [r for s in _decode_spans(spans) for r in s["stalls"]]


def test_the_threads_seconds_partition_and_fold_with_the_ticks(captured):
    # `captured` ran these shapes: nothing compiles here
    engine = _engine()
    spans, undo = _listen()
    try:
        for round_ in range(3):
            handles = [engine.submit(_request(i)) for i in range(2)]
            engine.drain(timeout_s=120)
            assert engine.step() is False       # the idle boundary flushes
            # an idle wait is not the loop's time, nor the steps'
            time.sleep(0.3)
        engine.shutdown()
    finally:
        undo()
    assert all(len(h.result(timeout=1)) == 7 for h in handles)
    ticks = _decode_spans(spans)
    assert [s["ticks"] for s in ticks] == [3, 3] * 3
    for s in ticks:                 # at the same flushes as the tick's sums
        assert set(HOST_SUMS + HOST_COUNTS + TICK_SUMS) <= set(s)
        assert all(s[k] >= 0 for k in HOST_SUMS + HOST_COUNTS)
        assert s["block_s"] <= s["wait_s"] + 1e-9
        assert isinstance(s["stalls"], list) and s["stalls_dropped"] >= 0
        assert s["gc_longest_s"] >= 0.0
    total = lambda k: sum(s[k] for s in ticks)
    named = sum(total(k) for k in PHASES)
    assert named == pytest.approx(total("step_s"), rel=0.01)
    assert total("loop_s") < 0.3 and total("step_s") < 0.6   # 0.9 s slept
    # a step that ends after its span's flush is the next span's
    assert total("steps") + engine._host.steps == engine.steps
    assert total("ticks_found_ready") <= total("ticks")
    snap = engine.metrics_snapshot()
    assert {"host_stalls", "host_stall_s", "gc_s", "compiles",
            "ticks_found_ready"} <= set(snap)
    assert snap["ticks_found_ready"] == total("ticks_found_ready")
    # every sum and count of the thread is named once
    assert len(set(HOST_SUMS + HOST_COUNTS)) == len(HOST_SUMS + HOST_COUNTS) == 13


def test_a_slow_emit_is_one_record_that_names_its_phase(captured):
    engine = _engine()
    spans, undo = _listen()
    try:
        handle = engine.submit(_request(0))
        push = handle._push

        def slow(token):
            if len(handle.tokens_out) == 2:     # in a tick's emit, once
                time.sleep(0.05)
            push(token)

        handle._push = slow
        engine.drain(timeout_s=120)
        engine.shutdown()
    finally:
        undo()
    found = [r for r in _records(spans) if r["phase"] == trace.TICK_EMIT]
    assert len(found) == 1
    rec = found[0]
    assert 0.05 <= rec["dur"] < 0.2 and "in_wait" not in rec
    assert rec["other_s"] >= 0.045      # asleep: no cause the watch names
    named = rec["gc_s"] + rec["compile_s"] + rec["other_s"]
    assert named >= rec["dur"] - 1e-6 and rec["other_s"] >= 0.0
    # found at the next device wait: in the step after the one it lay in
    assert rec["step"] in (2, 3) and (rec["active"], rec["units"]) == (1, 0)
    assert abs(rec["ts"] - time.time()) < 120.0         # the wall clock
    # emitted where it happened too, the same record
    lines = [s for s in spans if s["name"] == "serve_host_stall"
             and s["phase"] == trace.TICK_EMIT]
    assert len(lines) == 1 and lines[0]["ts"] == rec["ts"]
    assert lines[0]["dur"] == rec["dur"]
    snap = engine.metrics_snapshot()
    assert snap["host_stalls"] >= 1 and snap["host_stall_s"] >= 0.05


def test_a_record_carries_what_held_its_own_phase_alone(captured,
                                                        monkeypatch):
    """A sleep in one phase and a fresh program compiled in another phase of
    the SAME stretch of host work: each record's causes are its own."""
    engine = _engine()
    spans, undo = _listen()
    fresh = jax.jit(lambda x: (x * 7.0 - 2.0).sum())
    real = tick_io.stage
    try:
        handle = engine.submit(_request(0))
        push = handle._push

        def slow(token):
            if len(handle.tokens_out) == 2:     # in a tick's emit, once
                time.sleep(0.05)
            push(token)

        def compiling(*args, **kwargs):
            if len(handle.tokens_out) == 3 and not fresh._cache_size():
                t = time.perf_counter()         # the next step's stage, once
                jax.block_until_ready(fresh(jax.numpy.ones((5,))))
                time.sleep(max(0.0, 0.03 - (time.perf_counter() - t)))
            return real(*args, **kwargs)

        handle._push = slow
        monkeypatch.setattr(tick_io, "stage", compiling)
        engine.drain(timeout_s=120)
        engine.shutdown()
    finally:
        undo()
    records = _records(spans)
    (emit,) = [r for r in records if r["phase"] == trace.TICK_EMIT]
    (staged,) = [r for r in records if r["phase"] == trace.TICK_STAGE]
    assert 0.0 < staged["compile_s"] <= staged["dur"]
    # the sleep's record names no compiler, though its stretch held one
    assert emit["compile_s"] == 0.0 and emit["other_s"] >= 0.045
    for r in records:
        assert r["gc_s"] + r["compile_s"] + r["other_s"] >= r["dur"] - 1e-6


def test_a_tick_found_ready_behind_a_unit_is_not_the_hosts_pace(captured):
    engine = _engine()
    host = engine._host
    t = time.perf_counter()
    host.tick_blocked(t, t, t + 1e-6, behind=True)      # the device had work
    assert host.ticks_found_ready == 0
    host.tick_blocked(t, t, t + 1e-6, behind=False)
    host.tick_blocked(t, t, t + 1e-3, behind=False)     # it was waited for
    assert (host.ticks_found_ready, host.found_ready) == (1, 1)
    assert host.block_s == pytest.approx(1e-3 + 2e-6)
    engine.shutdown()


def test_a_cancellation_is_admissions_seconds_outside_its_annotation(
        tmp_path):
    engine = _engine()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    spans, undo = _listen()
    real = engine._cancel_abandoned
    slept = []

    def slow_cancel():
        if not slept and engine.steps == 2:
            slept.append((time.perf_counter_ns(), time.sleep(0.03),
                          time.perf_counter_ns()))
        real()

    engine._cancel_abandoned = slow_cancel
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        engine.submit(_request(0))
        engine.drain(timeout_s=120)
        engine.shutdown()
    finally:
        jax.profiler.stop_trace()
        undo()
    events = xplane.read(xplane.find_xplane(str(tmp_path)))["host"]
    admits = _named(events, trace.SERVE_ADMIT)
    assert len(slept) == 1 and admits
    # the annotation is where it was: no `serve_admit` event holds the sleep
    assert all(e - s < 0.03e9 for s, e in admits)
    # and the seconds are admission's all the same, the record under no event
    assert sum(s["admit_s"] for s in _decode_spans(spans)) >= 0.03
    (rec,) = [r for r in _records(spans) if r["phase"] == "loop"]
    assert 0.03 <= rec["dur"] < 0.2 and rec["other_s"] >= 0.025


def test_a_collection_by_a_client_thread_is_one_record_that_names_it(captured):
    engine = _engine(num_pages=32)
    spans, undo = _listen()
    gc.collect()
    gc.disable()                # the one collection is the client's
    go, done = threading.Event(), threading.Event()

    def client():
        go.wait(timeout=60)
        gc.collect()
        done.set()

    thread = threading.Thread(target=client)
    try:
        heap = [[] for _ in range(1_000_000)]
        thread.start()
        for i in range(8):
            engine.submit(_request(i))
        for _ in range(3):
            engine.step()
        go.set()
        engine.drain(timeout_s=120)
        assert done.wait(timeout=60)
        engine.shutdown()
    finally:
        gc.enable()
        undo()
        thread.join(timeout=60)
    del heap
    assert not thread.is_alive()
    held = [r for r in _records(spans)
            if max(r.get("gc_s", 0.0), r.get("wait_gc_s", 0.0)) >= 0.02]
    assert len(held) == 1
    rec = held[0]
    collecting = rec["wait_gc_s"] if rec.get("in_wait") else rec["gc_s"]
    assert collecting >= 0.9 * rec["dur"] and rec["other_s"] >= 0.0
    ticks = _decode_spans(spans)
    assert sum(s["gc_s"] + s["wait_gc_s"] for s in ticks) >= collecting - 1e-6
    assert sum(s["gc_gen2"] for s in ticks) >= 1


def test_a_device_wait_that_is_merely_long_is_no_record(captured, monkeypatch):
    engine = _engine()
    real = jax.block_until_ready

    def long_wait(x):
        time.sleep(0.05)                # the device's work: proves nothing
        return real(x)

    spans, undo = _listen()
    monkeypatch.setattr(jax, "block_until_ready", long_wait)
    try:
        engine.submit(_request(0))
        engine.drain(timeout_s=120)
        engine.shutdown()
    finally:
        undo()
        monkeypatch.undo()
    ticks = _decode_spans(spans)
    assert sum(s["block_s"] for s in ticks) >= 6 * 0.05
    assert sum(s["ticks_found_ready"] for s in ticks) == 0
    assert not [r for r in _records(spans)
                if r.get("in_wait") or r["phase"] == trace.TICK_BLOCK]


def test_a_span_carries_sixteen_records_and_counts_the_rest(captured):
    engine = _engine(max_slots=1, num_pages=16, decode_span_every=1000)
    spans, undo = _listen()
    slept = [0]
    try:
        for i in range(3):
            handle = engine.submit(_request(i))
            push = handle._push

            def slow(token, push=push):
                if slept[0] < 17:               # 17 phases of 25 ms
                    slept[0] += 1
                    time.sleep(0.025)
                push(token)

            handle._push = slow
        engine.drain(timeout_s=120)
        assert engine.step() is False
        engine.shutdown()
    finally:
        undo()
    (span,) = _decode_spans(spans)
    assert span["ticks"] == 18 and len(span["stalls"]) == 16
    assert span["stalls_dropped"] >= 1
    made = 16 + span["stalls_dropped"]
    assert sum(s["name"] == "serve_host_stall" for s in spans) == made >= 17
    assert engine.metrics_snapshot()["host_stalls"] == made
    assert {r["phase"] for r in span["stalls"]} <= {
        trace.TICK_EMIT, trace.PREFILL_RESULT, trace.PREFILL_ENQUEUE,
        trace.TICK_ENQUEUE, "serve_prefill"}
