"""Request observatory (serve/reqtrace.py + tools/request_report.py —
docs/SERVING.md "Request tracing").

The acceptance contracts live here:
- W3C traceparent handling: valid headers join the caller's trace,
  malformed ones mint a fresh context instead of rejecting.
- the span tree is INTERNALLY CONSISTENT: queue-wait span == the recorded
  queue_wait_s, a request's own prefill chunks sum to prefill_s <= TTFT,
  child spans never exceed the request wall, decode ticks are contiguous.
- tracing OFF is structurally free (no builder dict entries, no page-pool
  listener, no stream) and tracing ON changes NO tokens (the OFF-twin
  parity run is bit-identical).
- the tail-exemplar ring keeps the slowest-K in eviction order, and the
  offline report degrades on torn/garbage/missing trace files.
- THE e2e acceptance: a deliberately slow long-prompt chunked-prefill
  request is named the p99-TTFT exemplar, its waterfall attributes TTFT
  to prefill chunks, and the SLO-breach capture's meta names the same
  trace id.
"""

import json
import os
import time

import jax
import numpy as np
import pytest

import request_report  # tools/ on sys.path via conftest
import serve_traffic
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.models.llama.decode import (
    GenerationConfig,
    generate,
)
from llama_pipeline_parallel_tpu.serve import (
    RequestTraceRecorder,
    ServeConfig,
    ServeEngine,
    ServeRequest,
    TraceContext,
)
from llama_pipeline_parallel_tpu.serve.reqtrace import (
    EXEMPLARS_NAME,
    REQUEST_TRACE_NAME,
    ExemplarRing,
)
from llama_pipeline_parallel_tpu.utils.trace import (
    format_traceparent,
    mint_span_id,
    mint_trace_id,
    parse_traceparent,
)


@pytest.fixture(scope="module")
def setup():
    cfg = LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def chunked_engine(cfg, params, **kw):
    """The chunked-prefill shape of tests/test_paged_serving.py: buckets 8
    and 32, 8-token chunk budget — a bucket-32 prompt takes 4 interleaved
    chunks, the slow-request shape the waterfall must attribute."""
    engine_kw = {k: kw.pop(k) for k in ("reqtrace", "profiler", "slo")
                 if k in kw}
    defaults = dict(max_slots=2, max_len=48, prompt_buckets=(8, 32),
                    page_size=4, kv_cache="paged", num_pages=24,
                    prefill_chunk_tokens=8, max_queue=32, metrics_every=1,
                    decode_span_every=1)
    defaults.update(kw)
    return ServeEngine(params, cfg, ServeConfig(**defaults), **engine_kw)


def reference_tokens(params, cfg, prompt, gen, seed, bucket):
    import jax.numpy as jnp

    pad = bucket - len(prompt)
    ids = np.concatenate([np.zeros(pad, np.int32),
                          np.asarray(prompt, np.int32)])[None]
    mask = np.asarray([[0] * pad + [1] * len(prompt)], np.int32)
    out = generate(params, jnp.asarray(ids), jnp.asarray(mask), cfg, gen,
                   rng=jax.random.PRNGKey(seed))
    return np.asarray(out["tokens"])[0].tolist()


def load_records(d: str) -> list[dict]:
    with open(os.path.join(d, REQUEST_TRACE_NAME)) as f:
        return [json.loads(line) for line in f]


# -- W3C trace context --------------------------------------------------------


def test_traceparent_parse_format_grid():
    tid, sid = "ab" * 16, "cd" * 8
    assert parse_traceparent(f"00-{tid}-{sid}-01") == (tid, sid)
    assert parse_traceparent(f"00-{tid}-{sid}-00") == (tid, sid)
    # a future version is parseable as long as the fields are sound
    assert parse_traceparent(f"01-{tid}-{sid}-01") == (tid, sid)
    for bad in (None, "", "garbage", f"ff-{tid}-{sid}-01",
                f"00-{tid[:-2]}-{sid}-01", f"00-{tid}-{sid[:-2]}-01",
                f"00-{'zz' * 16}-{sid}-01", f"00-{'00' * 16}-{sid}-01",
                f"00-{tid}-{'00' * 8}-01", f"00-{tid}-{sid}",
                f"00-{tid.upper()}-{sid}-01"):
        assert parse_traceparent(bad) is None, bad
    assert format_traceparent(tid, sid) == f"00-{tid}-{sid}-01"
    assert parse_traceparent(format_traceparent(tid, sid)) == (tid, sid)

    minted = {mint_trace_id() for _ in range(32)}
    assert len(minted) == 32 and all(len(t) == 32 for t in minted)
    assert all(len(mint_span_id()) == 16 for _ in range(4))


def test_trace_context_adopts_or_mints():
    ctx = TraceContext.from_traceparent("00-" + "ab" * 16 + "-"
                                        + "cd" * 8 + "-01")
    assert ctx.trace_id == "ab" * 16
    assert ctx.parent_span == "cd" * 8
    assert ctx.span_id not in ("cd" * 8, "00" * 8)  # OUR span, fresh
    # the outgoing header continues OUR span, not the caller's
    assert ctx.traceparent() == format_traceparent(ctx.trace_id, ctx.span_id)

    fresh = TraceContext.from_traceparent("not-a-header")
    assert fresh.parent_span is None and len(fresh.trace_id) == 32
    assert TraceContext.mint().trace_id != TraceContext.mint().trace_id


def test_submit_mints_trace_when_absent(setup):
    cfg, params = setup
    engine = chunked_engine(cfg, params)
    try:
        r = ServeRequest(input_ids=[5, 6],
                         gen=GenerationConfig(max_new_tokens=1))
        assert r.trace is None
        engine.submit(r)
        assert r.trace is not None and len(r.trace.trace_id) == 32
        ctx = TraceContext.mint()
        r2 = ServeRequest(input_ids=[5, 6],
                          gen=GenerationConfig(max_new_tokens=1), trace=ctx)
        engine.submit(r2)
        assert r2.trace is ctx            # a provided context is kept
    finally:
        engine.shutdown()


# -- exemplar ring ------------------------------------------------------------


def test_exemplar_ring_keeps_slowest_k_in_order():
    ring = ExemplarRing(3)
    for v in (0.3, 0.1, 0.9, 0.2):
        assert ring.offer(v, {"v": v})
    # full ring: 0.1 was evicted (always the LEAST slow), order slowest-first
    assert [r["v"] for r in ring.records()] == [0.9, 0.3, 0.2]
    assert not ring.offer(0.15, {"v": 0.15})      # below the floor: rejected
    assert ring.offer(0.5, {"v": 0.5})
    assert [r["v"] for r in ring.records()] == [0.9, 0.5, 0.3]
    with pytest.raises(ValueError):
        ExemplarRing(0)


def test_recorder_writes_shed_and_exemplars(tmp_path):
    rec = RequestTraceRecorder(str(tmp_path), exemplar_k=2)
    shed = ServeRequest(input_ids=[1], tenant="free",
                        trace=TraceContext.mint())
    rec.record_shed(shed, "queue_full", retry_after_s=1.5)
    for i, ttft in enumerate((0.2, 0.9, 0.5)):
        rec.write({"request_id": f"r{i}", "outcome": "completed",
                   "ttft_s": ttft, "tpot_s": 0.01 * (i + 1)})
    rec.close()
    rec.close()                                    # idempotent

    rows = load_records(str(tmp_path))
    assert rows[0]["outcome"] == "shed"
    assert rows[0]["reason"] == "queue_full"
    assert rows[0]["retry_after_s"] == 1.5
    assert rows[0]["trace_id"] == shed.trace.trace_id
    assert len(rows) == 4
    with open(tmp_path / EXEMPLARS_NAME) as f:
        snap = json.load(f)
    assert [r["request_id"] for r in snap["ttft"]] == ["r1", "r2"]
    assert [r["request_id"] for r in snap["tpot"]] == ["r2", "r1"]


# -- offline report: math + degrade grid --------------------------------------


def test_ttft_breakdown_and_tail_attribution():
    rec = {"ttft_s": 1.0, "queue_wait_s": 0.12, "prefill_s": 0.71,
           "wall_s": 1.5}
    bd = request_report.ttft_breakdown(rec)
    assert bd["queue_pct"] == 12.0 and bd["prefill_pct"] == 71.0
    assert bd["interleave_pct"] == pytest.approx(17.0)
    assert bd["decode_s"] == pytest.approx(0.5)
    assert request_report.ttft_breakdown({"outcome": "shed"}) is None

    tail = request_report.tail_attribution([rec] * 4, quantile=99.0)
    assert tail["requests"] >= 1 and tail["queue_pct"] == 12.0
    assert request_report.tail_attribution([]) == {}


@pytest.mark.parametrize("damage", ["missing", "torn", "garbage"])
def test_report_degrades_on_damaged_trace(tmp_path, damage, capsys):
    good = {"schema": 1, "request_id": "r0", "trace_id": "t" * 32,
            "tenant": "paid", "outcome": "completed", "arrival": 100.0,
            "wall_s": 1.0, "tokens": 4, "ttft_s": 0.5, "tpot_s": 0.01,
            "queue_wait_s": 0.1, "prefill_s": 0.2, "spans": []}
    if damage != "missing":
        with open(tmp_path / REQUEST_TRACE_NAME, "w") as f:
            f.write(json.dumps(good) + "\n")
            if damage == "garbage":
                f.write("not json at all\n")
                f.write(json.dumps(good | {"request_id": "r1"}) + "\n")
            else:
                f.write('{"torn tail')
        with open(tmp_path / EXEMPLARS_NAME, "w") as f:
            f.write("{also torn")               # must not kill the report
    rc = request_report.main([str(tmp_path)])
    out = capsys.readouterr().out
    if damage == "missing":
        assert rc == 1 and "no request_trace.jsonl records" in out
    else:
        assert rc == 0
        rep = request_report.build_report(str(tmp_path))
        assert rep["completed"] == (2 if damage == "garbage" else 1)
        assert rep["tenants"]["paid"]["completed"] == rep["completed"]
        assert rep["exemplars"] == {}           # torn snapshot: degraded


# -- e2e: span-tree invariants + ON/OFF parity --------------------------------


def test_span_tree_invariants_and_on_off_token_parity(setup, tmp_path):
    """One seeded Poisson trace replayed twice — tracing ON and the OFF
    twin — must produce bit-identical tokens; the ON run's records must
    satisfy the span-tree invariants. The pool is sized so nothing sheds
    (shedding is wall-clock-dependent and would make the twin runs
    incomparable); the shed-record path is pinned separately below."""
    from llama_pipeline_parallel_tpu.serve import RequestRejected

    cfg, params = setup
    trace_reqs = serve_traffic.poisson_trace(
        3, 200.0, 6, serve_traffic.parse_mix("6:0.5,20:0.5"),
        serve_traffic.parse_mix("3:0.5,6:0.5"),
        tenant_mix=serve_traffic.parse_tenant_mix("free:0.7,paid:0.3"))

    tokens = {}
    for mode in ("on", "off"):
        rec = (RequestTraceRecorder(str(tmp_path), exemplar_k=4)
               if mode == "on" else None)
        engine = chunked_engine(cfg, params, num_pages=64, reqtrace=rec)
        summary = serve_traffic.run_trace(engine, trace_reqs,
                                          time_scale=0.02,
                                          collect_tokens=True)
        if mode == "on":
            # a synchronous rejection leaves a shed record (the request
            # never reaches the loop, so the terminal event IS its trace)
            with pytest.raises(RequestRejected):
                engine.submit(ServeRequest(
                    input_ids=[3] * 40,
                    gen=GenerationConfig(max_new_tokens=4)))
        engine.shutdown()
        if rec is not None:
            rec.close()
        tokens[mode] = summary["tokens"]
        if mode == "off":
            # OFF is structurally free: no recorder, no listener, no
            # builder dict entries ever created
            assert engine._reqtrace is None
            assert engine._rt == {}
            assert engine.slots.alloc_listener is None
    assert None not in tokens["on"]             # nothing shed
    assert tokens["on"] == tokens["off"]        # THE parity pin

    records = load_records(str(tmp_path))
    completed = [r for r in records if r["outcome"] == "completed"]
    assert len(completed) == 6                  # every request completed
    shed = [r for r in records if r["outcome"] == "shed"]
    assert [r["reason"] for r in shed] == ["rejected"]
    assert len(shed[0]["trace_id"]) == 32       # shed requests traced too
    for r in completed:
        names = [s["name"] for s in r["spans"]]
        assert names[0] == "queue_wait" and names[1] == "admission"
        assert "prefill_chunk" in names and "first_token" in names
        assert len(r["trace_id"]) == 32 and len(r["span_id"]) == 16
        assert r["tenant"] in ("free", "paid")
        # queue-wait span == the retroactive queue_wait_s measurement
        qspan = next(s for s in r["spans"] if s["name"] == "queue_wait")
        assert qspan["dur"] == pytest.approx(r["queue_wait_s"], abs=1e-5)
        # a request's own chunks can't exceed its TTFT, TTFT its wall
        assert r["prefill_s"] <= r["ttft_s"] + 1e-6
        assert r["ttft_s"] <= r["wall_s"] + 1e-6
        # timed child spans sum within the request wall
        assert sum(s.get("dur", 0.0) for s in r["spans"]) \
            <= r["wall_s"] + 1e-6
        # chunk offsets advance monotonically to the bucket
        chunks = [s for s in r["spans"] if s["name"] == "prefill_chunk"]
        offs = [c["offset"] for c in chunks]
        assert offs == sorted(offs)
        assert sum(c["tokens"] for c in chunks) == r["bucket"]
        d = r.get("decode")
        if d:                                   # ticks are contiguous
            assert d["ticks"] == d["last_tick"] - d["first_tick"] + 1
            assert sum(d["shared_with"].values()) == d["ticks"]
        assert r.get("pages_reserved", 0) >= r.get("pages_allocated", 0)


def test_note_abandoned_live_and_late(setup, tmp_path):
    cfg, params = setup
    rec = RequestTraceRecorder(str(tmp_path))
    engine = chunked_engine(cfg, params, reqtrace=rec)
    try:
        r = ServeRequest(input_ids=[4, 5, 6], tenant="free",
                         gen=GenerationConfig(max_new_tokens=4))
        h = engine.submit(r)
        engine.step()                          # admitted: builder is live
        engine.note_abandoned(r)               # disconnect mid-stream
        engine.drain(timeout_s=120)
        # cancelled at the next step boundary: the stream ends early and
        # the slot/pages were reclaimed instead of decoding for nobody
        assert len(h.result(timeout=1)) < 4

        done = ServeRequest(input_ids=[4, 5], tenant="paid",
                            gen=GenerationConfig(max_new_tokens=1))
        h2 = engine.submit(done)
        engine.drain(timeout_s=120)
        h2.result(timeout=1)
        engine.note_abandoned(done)            # disconnect AFTER completion
        snap = engine.stats.snapshot()
        assert snap["requests_abandoned"] == 2
        assert snap["tenants"]["free"]["requests_abandoned"] == 1
        assert snap["tenants"]["paid"]["requests_abandoned"] == 1
    finally:
        engine.shutdown()
        rec.close()
    records = load_records(str(tmp_path))
    live = next(x for x in records if x["request_id"] == r.request_id)
    assert live["outcome"] == "abandoned" and live["abandoned"] is True
    assert live["tokens_discarded"] == live["tokens"]
    assert any(s["name"] == "abandoned" for s in live["spans"])
    late = [x for x in records if x["request_id"] == done.request_id]
    assert [x["outcome"] for x in late] == ["completed", "abandoned"]
    assert late[1]["event"] == "late_disconnect"
    assert late[1]["trace_id"] == late[0]["trace_id"]


# -- THE e2e acceptance -------------------------------------------------------


def test_slow_chunked_request_is_p99_exemplar_with_capture(setup, tmp_path,
                                                           capsys):
    """Mixed-tenant run with one deliberately slow long-prompt chunked
    request B: B's waterfall attributes its TTFT to prefill chunks, the
    report names B the slowest-TTFT exemplar with per-tenant tables, and
    the SLO-breach capture meta carries B's trace id."""
    from llama_pipeline_parallel_tpu.serve.telemetry import SLOThresholds
    from llama_pipeline_parallel_tpu.utils.profiler import (
        CaptureConfig,
        TriggeredProfiler,
    )

    cfg, params = setup
    rs = np.random.RandomState(5)
    short = rs.randint(3, cfg.vocab_size, (5,)).tolist()
    long_p = rs.randint(3, cfg.vocab_size, (20,)).tolist()
    # warm both program shapes on a throwaway engine so compile time
    # skews neither TTFT (it would otherwise dwarf the chunk phases and
    # hand the warming request both the capture and the p99)
    warm = chunked_engine(cfg, params)
    for prompt in (short, long_p):
        warm.submit(ServeRequest(input_ids=prompt,
                                 gen=GenerationConfig(max_new_tokens=2)))
    warm.drain(timeout_s=300)
    warm.shutdown()

    rec = RequestTraceRecorder(str(tmp_path), exemplar_k=4)
    prof = TriggeredProfiler(
        CaptureConfig(zscore=0.0, window_steps=2, max_captures=1),
        str(tmp_path))
    engine = chunked_engine(cfg, params, reqtrace=rec, profiler=prof,
                            slo=SLOThresholds(ttft_s=0.0))
    try:
        ga = GenerationConfig(max_new_tokens=20)
        a = engine.submit(ServeRequest(input_ids=short, gen=ga, seed=1,
                                       tenant="paid"))
        engine.step()                      # A's one-shot prefill: TTFT ~1 tick
        gb = GenerationConfig(max_new_tokens=2)
        b_req = ServeRequest(input_ids=long_p, gen=gb, seed=2, tenant="free")
        b = engine.submit(b_req)           # 3 chunks behind A's live decode
        engine.drain(timeout_s=300)
        # parity under tracing ON: B bit-matches its generate() reference
        assert b.result(timeout=1) == reference_tokens(
            params, cfg, long_p, gb, 2, bucket=32)
        a.result(timeout=1)
    finally:
        engine.shutdown()
        rec.close()

    records = load_records(str(tmp_path))
    by_id = {x["request_id"]: x for x in records}
    rb = by_id[b_req.request_id]
    # B finished first (budget 2 vs A's 20), so the single capture is B's
    assert rb["slo_breach"] == ["ttft"]
    assert rb["capture"]
    with open(os.path.join(rb["capture"], "capture_meta.json")) as f:
        meta = json.load(f)
    assert meta["trace_id"] == rb["trace_id"] == b_req.trace.trace_id
    assert meta["tenant"] == "free"
    assert meta["request_id"] == b_req.request_id
    # the waterfall attributes B's TTFT to its interleaved chunks (3 run of
    # the bucket's 4: the first is nothing but pads and skipped), not queue
    # wait (B was admitted immediately)
    chunks = [s for s in rb["spans"] if s["name"] == "prefill_chunk"]
    assert [s["offset"] for s in chunks] == [8, 16, 24]
    bd = request_report.ttft_breakdown(rb)
    assert bd["prefill_pct"] + bd["interleave_pct"] > bd["queue_pct"]

    rep = request_report.build_report(str(tmp_path))
    assert rep["p99_exemplar"]["request_id"] == b_req.request_id
    assert set(rep["tenants"]) == {"paid", "free"}
    assert rep["exemplars"]["ttft"][0] == b_req.request_id
    assert request_report.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert b_req.request_id in out and "per-tenant" in out
    assert "prefill-behind-chunked-neighbor" in out
