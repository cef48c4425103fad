"""The decode tick's nested profiler events, the sums beside them on
`serve_decode_step` and the wall-clock anchor, on a tiny engine under a real
`jax.profiler` capture (a CPU capture has the host plane, which is all these
read), and the one lookup of `TraceAnnotation`."""

import os
import sys

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
from llama_pipeline_parallel_tpu.serve.engine import TICK_SUMS  # noqa: E402
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
