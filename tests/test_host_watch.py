"""The process's one watch of what can hold a Python thread
(`utils/trace.HostWatch`): the collector's pauses, the compiler's seconds,
and what of a stretch of time the two held."""

import gc
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import xplane  # noqa: E402
from llama_pipeline_parallel_tpu.models.llama import decode  # noqa: E402
from llama_pipeline_parallel_tpu.models.llama import model as llama  # noqa: E402
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig  # noqa: E402
from llama_pipeline_parallel_tpu.serve import (  # noqa: E402
    ServeConfig,
    ServeEngine,
    ServeRequest,
)
from llama_pipeline_parallel_tpu.utils import trace  # noqa: E402

@pytest.fixture
def no_automatic_collection():
    """The collector runs only where a test calls it."""
    gc.collect()
    gc.disable()
    yield
    gc.enable()


def _totals(watch):
    return (watch.gc_s, watch.gc_collections, watch.gc_gen2,
            watch.gc_longest_s)


def test_a_forced_collection_is_counted_with_its_generation_and_seconds(
        no_automatic_collection):
    watch = trace.host_watch()
    cycles = []
    for _ in range(20000):
        a, b = [], []
        a.append(b)
        b.append(a)
        cycles.append(a)
    del cycles, a, b
    s0, n0, full0, _ = _totals(watch)
    t = time.perf_counter()
    assert gc.collect() >= 40000
    took = time.perf_counter() - t
    s1, n1, full1, longest = _totals(watch)
    assert (n1 - n0, full1 - full0) == (1, 1)
    assert 0.0 < s1 - s0 <= took and longest >= s1 - s0
    gc.collect(0)                       # a young one is counted, not full
    s2, n2, full2, _ = _totals(watch)
    assert (n2 - n1, full2 - full1) == (1, 0) and s2 >= s1


def test_a_collection_of_generation_1_or_2_is_an_annotation_of_its_thread(
        tmp_path, no_automatic_collection):
    trace.host_watch()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        gc.collect(0)
        gc.collect(1)
        gc.collect(2)
    finally:
        jax.profiler.stop_trace()
    host = xplane.read(xplane.find_xplane(str(tmp_path)))["host"]
    pauses = sorted(n for n, _, _ in host if n.startswith(trace.GC_PREFIX))
    assert pauses == ["py_gc gen=1", "py_gc gen=2"]   # generation 0: counted


def test_a_fresh_jit_is_counted_and_a_second_call_is_not():
    watch = trace.host_watch()
    lines = []
    listener = lambda rec: lines.append(dict(rec))
    trace.recorder().add_listener(listener)
    try:
        fresh = jax.jit(lambda x: jnp.tanh(x * 3.0 + 1.0).sum())
        # made first: an array's own making compiles too
        x, other = (jax.block_until_ready(jnp.ones(shape))
                    for shape in ((17, 3), (5,)))
        n0, s0, lines0 = watch.compiles, watch.compile_s, len(lines)
        jax.block_until_ready(fresh(x))
        n1, s1 = watch.compiles, watch.compile_s
        jax.block_until_ready(fresh(x))
        n2, s2 = watch.compiles, watch.compile_s
    finally:
        trace.recorder().remove_listener(listener)
    assert n1 - n0 == 1 and s1 > s0
    assert (n2, s2) == (n1, s1)
    compiled = [r for r in lines[lines0:] if r["name"] == "jit_compile"
                and r["event"] == trace.COMPILE_EVENT]
    assert len(compiled) == 1 and 0.0 < compiled[0]["dur"] <= s1 - s0
    jax.block_until_ready(fresh(other))             # a new shape is
    assert watch.compiles == n2 + 1


# (the watch's kept events as (is the compiler's, start, end), the interval
# asked about, then what held it: gc_s, compile_s, other_s)
HELD = {
    "nothing_kept": ([], (10.0, 10.5), (0.0, 0.0, 0.5)),
    "a_collection_inside": (
        [(False, 10.1, 10.3)], (10.0, 10.5), (0.2, 0.0, 0.3)),
    "clipped_at_both_ends": (
        [(False, 9.0, 10.1), (True, 10.4, 11.0)], (10.0, 10.5),
        (0.1, 0.1, 0.3)),
    "nested_tracing_counts_once": (
        [(True, 10.1, 10.2), (True, 10.05, 10.3), (True, 10.3, 10.45)],
        (10.0, 10.5), (0.0, 0.4, 0.1)),
    "a_collection_inside_a_compile": (
        [(True, 10.0, 10.5), (False, 10.2, 10.3)], (10.0, 10.5),
        (0.1, 0.5, 0.0)),
    "events_elsewhere": (
        [(False, 9.0, 10.0), (True, 10.5, 11.0)], (10.0, 10.5),
        (0.0, 0.0, 0.5)),
}


@pytest.mark.parametrize("case", sorted(HELD))
def test_held_sets_an_interval_against_the_events_kept(case):
    events, (start, end), (gc_s, compile_s, other_s) = HELD[case]
    watch = trace.HostWatch()
    watch._recent.extend(events)
    held = watch.held(start, end)
    assert held == pytest.approx(
        {"gc_s": gc_s, "compile_s": compile_s, "other_s": other_s})
    # never negative, and the three cover the interval
    assert min(held.values()) >= 0.0
    assert sum(held.values()) >= end - start - 1e-9


def test_the_watch_keeps_where_a_collection_and_a_compile_lay(
        no_automatic_collection):
    watch = trace.host_watch()
    t0 = time.perf_counter()
    gc.collect()
    jax.block_until_ready(jax.jit(lambda x: jnp.cos(x).sum() * 5.0)(
        jnp.ones((3, 11))))
    t1 = time.perf_counter()
    held = watch.held(t0, t1)
    assert 0.0 < held["gc_s"] < t1 - t0 and 0.0 < held["compile_s"] < t1 - t0
    assert held["other_s"] == pytest.approx(
        t1 - t0 - held["gc_s"] - held["compile_s"], abs=1e-6)
    # no more than the newest are kept, and of the compiler's events (one
    # for every jitted helper a program's tracing meets) those long enough
    assert watch._recent.maxlen == trace.RECENT_EVENTS
    kept = [e - s for compiler, s, e in watch._recent if compiler]
    assert kept and min(kept) >= trace.KEPT_EVENT_S
    before = len(watch._recent)
    watch._on_duration(trace.COMPILE_SECONDS_EVENTS[1], 1e-5)
    assert len(watch._recent) == before
    assert watch.held(t1 + 1.0, t1 + 2.0) == {
        "gc_s": 0.0, "compile_s": 0.0, "other_s": 1.0}


def test_the_module_imports_no_jax_and_the_watch_counts_without_it(
        monkeypatch):
    import ast

    with open(trace.__file__) as f:
        tree = ast.parse(f.read())
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    names = {a.name.split(".")[0] for n in top for a in n.names} | {
        (n.module or "").split(".")[0] for n in top
        if isinstance(n, ast.ImportFrom)}
    assert "jax" not in names           # resolved on first use, where needed
    # a process without jax: no annotation class, and no compiler to hear
    monkeypatch.setattr(trace, "_annotation_class", lambda: None)
    watch = trace.HostWatch()
    watch._annotation_class = trace._annotation_class()
    watch._on_gc("stop", {"generation": 2})         # hooked mid-collection
    assert watch.gc_collections == 0
    watch._on_gc("start", {"generation": 2})
    watch._on_gc("stop", {"generation": 2})
    assert (watch.gc_collections, watch.gc_gen2) == (1, 1)
    assert watch.gc_s == watch.gc_longest_s > 0.0 and watch.compiles == 0
    assert watch.held(0.0, time.perf_counter())["gc_s"] == watch.gc_s


def _serve():
    cfg = LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(params, cfg, ServeConfig(
        max_slots=2, max_len=24, prompt_buckets=(16,), page_size=8,
        max_queue=8, decode_span_every=3))
    spans = []
    listener = lambda rec: spans.append(dict(rec))
    trace.recorder().add_listener(listener)
    try:
        handles = [engine.submit(ServeRequest(
            input_ids=[5, 6, 7 + i], seed=i,
            gen=decode.GenerationConfig(max_new_tokens=7)))
            for i in range(2)]
        engine.drain(timeout_s=120)
        engine.shutdown()
    finally:
        trace.recorder().remove_listener(listener)
    return [h.result(timeout=1) for h in handles], spans


def test_one_watch_a_process_however_many_engines():
    watch = trace.host_watch()
    _serve()
    _serve()
    assert trace.host_watch() is watch
    assert gc.callbacks.count(watch._on_gc) == 1
