"""Continuous-batching serving subsystem (serve/, tools/serve.py,
docs/SERVING.md).

The two acceptance contracts live here:
- e2e: staggered requests through the scheduler return TOKEN-IDENTICAL
  outputs to independent generate() calls with the same per-request seeds,
  with slot reuse (one pool allocation, a slot serving two requests) and
  TTFT/TPOT/queue-wait records in the spans + metrics streams.
- multi-replica: two serve processes under tools/supervisor.py, one
  SIGKILLed mid-decode, restarted from the same checkpoint by the
  watchdog, serving again; the incarnation ledger records the restart.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.models.llama.decode import (
    GenerationConfig,
    generate,
)
from llama_pipeline_parallel_tpu.serve import (
    RequestRejected,
    ServeConfig,
    ServeEngine,
    ServeLoop,
    ServeOverloaded,
    ServeRequest,
)
from llama_pipeline_parallel_tpu.serve.telemetry import (
    SLOStats,
    percentile,
    percentiles_ms,
)
from llama_pipeline_parallel_tpu.utils import trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUCKET = 8


@pytest.fixture(scope="module")
def setup():
    cfg = LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_engine(cfg, params, **kw):
    # a page of half the bucket: every row crosses page boundaries; a pool
    # that covers the reservations of a full queue beside the running rows
    # (a request reserves its worst case when it is submitted), so it is
    # the queue's bound these tests meet, not the pool's
    defaults = dict(max_slots=2, max_len=BUCKET + 8, prompt_buckets=(BUCKET,),
                    page_size=BUCKET // 2, num_pages=40, max_queue=8,
                    metrics_every=1, decode_span_every=1)
    defaults.update(kw)
    return ServeEngine(params, cfg, ServeConfig(**defaults))


def reference_tokens(params, cfg, prompt, gen, seed):
    """What the served request must emit: an independent generate() call
    with the prompt left-padded to the engine's bucket."""
    pad = BUCKET - len(prompt)
    ids = np.concatenate([np.zeros(pad, np.int32),
                          np.asarray(prompt, np.int32)])[None]
    mask = np.asarray([[0] * pad + [1] * len(prompt)], np.int32)
    out = generate(params, jnp.asarray(ids), jnp.asarray(mask), cfg, gen,
                   rng=jax.random.PRNGKey(seed))
    return np.asarray(out["tokens"])[0].tolist()


# -- the e2e acceptance test -------------------------------------------------


@pytest.mark.parametrize("page_size", [BUCKET // 2, BUCKET])
def test_continuous_batching_token_parity_and_telemetry(setup, tmp_path,
                                                        page_size):
    """Staggered arrivals through 2 slots: every request's stream matches
    its independent generate() call; slot reuse is proven (one allocation,
    slots serving two requests each); TTFT/TPOT/queue-wait land in both
    telemetry streams. At a page of half the bucket rows cross page
    boundaries while slots are reused; at a page the bucket long a prompt
    is one page and the first decode write claims the second."""
    from llama_pipeline_parallel_tpu.utils.metrics import MetricsWriter

    cfg, params = setup
    trace.configure(str(tmp_path))
    writer = MetricsWriter(str(tmp_path))
    try:
        engine = make_engine(cfg, params, page_size=page_size)
        engine._metrics_writer = writer
        rs = np.random.RandomState(0)
        gens = [GenerationConfig(max_new_tokens=6),                       # greedy
                GenerationConfig(max_new_tokens=4, temperature=0.8, top_k=5),
                GenerationConfig(max_new_tokens=6, temperature=0.7, top_p=0.9),
                GenerationConfig(max_new_tokens=5, temperature=1.1)]
        prompts = [rs.randint(3, cfg.vocab_size, (n,)).tolist()
                   for n in (5, 8, 3, 7)]

        # staggered arrivals: two up front, two more mid-flight (they join
        # the running batch at a later step boundary)
        handles = [engine.submit(ServeRequest(input_ids=p, gen=g, seed=i))
                   for i, (p, g) in enumerate(zip(prompts[:2], gens[:2]))]
        engine.step()
        engine.step()
        handles += [engine.submit(ServeRequest(input_ids=p, gen=g, seed=i + 2))
                    for i, (p, g) in enumerate(zip(prompts[2:], gens[2:]))]
        engine.drain(timeout_s=120)

        for i, (h, p, g) in enumerate(zip(handles, prompts, gens)):
            assert h.result(timeout=1) == reference_tokens(params, cfg, p, g, i), \
                f"request {i} diverged from its independent generate() call"

        # slot reuse: the pool was allocated once and at least one slot
        # served two requests (4 requests > 2 slots force it)
        assert engine.slots.allocations == 1
        assert engine.slots.reused_slot_count() >= 1
        assert len(engine.slots.assignments) == 4
        assert engine.slots.free_count == 2  # all released
        # ... with their pages: each request was handed exactly its worst
        # case (no eos: every budget runs out), lazily, and none is left
        assert engine.slots.page_size == page_size
        assert engine.slots.pages_free == engine.slots.num_pages
        assert engine.slots.page_allocations == sum(
            engine.slots.demand_pages(BUCKET, g.max_new_tokens) for g in gens)

        snap = engine.metrics_snapshot()
        assert snap["requests_completed"] == 4
        assert snap["slot_allocations"] == 1
    finally:
        writer.close()
        trace.configure(None)

    # SLO records in the spans stream
    with open(tmp_path / "spans.jsonl") as f:
        spans = [json.loads(l) for l in f]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    assert "serve_ttft" not in by_name     # `serve_request` carries `ttft`
    assert len(by_name["serve_queue_wait"]) == 4
    assert len(by_name["serve_prefill"]) == 4
    decode_spans = by_name["serve_decode_step"]
    assert sum(s["ticks"] for s in decode_spans) >= 5  # every tick accounted
    requests = by_name["serve_request"]
    assert len(requests) == 4
    for r in requests:
        assert r["ttft"] >= r["queue_wait"] >= 0.0
        assert r["tpot"] > 0.0 and r["tokens"] >= 4

    # ... and in the metrics stream
    with open(tmp_path / "metrics.jsonl") as f:
        lines = [json.loads(l) for l in f if l.strip()]
    serving = [m for m in lines if m.get("serving")]
    assert serving, "no serving metrics line written"
    last = serving[-1]
    for key in ("ttft_p50_ms", "tpot_p50_ms", "queue_wait_p50_ms",
                "ttft_p99_ms"):
        assert key in last, f"metrics line missing {key}"
    assert last["requests_completed"] == 4
    assert last["tokens_generated"] == sum(g.max_new_tokens for g in gens)


@pytest.mark.slow  # test_paged_serving.py::
# test_paged_eos_finishes_row_early_and_frees_pages pins the same early-
# free semantics every tier-1 run; this twin stays in the round gate
def test_eos_finishes_row_early_and_frees_slot(setup):
    """A request hitting eos frees its slot before the budget; the emitted
    stream ends with the eos token, matching generate()'s pre-pad prefix."""
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
    got = h.result(timeout=1)
    assert got == [eos]                      # stream stops AT eos
    assert engine.slots.free_count == 1      # slot freed immediately
    ref = reference_tokens(params, cfg, prompt, gen, 0)
    assert ref[0] == eos and all(t == 17 for t in ref[1:])  # generate pads


def test_an_eos_mid_stream_with_a_tick_in_flight_is_generates_prefix(setup):
    """The engine reads a row's eos one tick late (the next tick is already
    enqueued: `rows_overrun`), and frees its slot at once. What the client
    gets is still generate()'s stream up to and including the eos, and the
    request that takes the one slot next, prefilled and decoded behind the
    overrun's write, emits exactly its own generate() call's tokens."""
    cfg, params = setup
    rs = np.random.RandomState(7)
    prompts = [rs.randint(3, cfg.vocab_size, (n,)).tolist() for n in (4, 6)]
    free = reference_tokens(params, cfg, prompts[0],
                            GenerationConfig(max_new_tokens=8), 0)
    at = next(i for i in range(2, 7) if free[i] not in free[:i])
    gens = [GenerationConfig(max_new_tokens=8, eos_token_id=free[at],
                             pad_token_id=17),
            GenerationConfig(max_new_tokens=7, temperature=0.9, top_k=5)]
    engine = make_engine(cfg, params, max_slots=1)
    spans = []
    listener = lambda rec: spans.append(dict(rec))
    trace.recorder().add_listener(listener)
    try:
        handles = [engine.submit(ServeRequest(input_ids=p, gen=g, seed=i))
                   for i, (p, g) in enumerate(zip(prompts, gens))]
        engine.drain(timeout_s=120)
        assert engine.step() is False
    finally:
        trace.recorder().remove_listener(listener)
    got = [h.result(timeout=1) for h in handles]
    refs = [reference_tokens(params, cfg, p, g, i)
            for i, (p, g) in enumerate(zip(prompts, gens))]
    assert got[0] == free[:at + 1] == refs[0][:at + 1]
    assert all(t == 17 for t in refs[0][at + 1:])        # generate pads
    assert got[1] == refs[1]
    ticks = [s for s in spans if s["name"] == "serve_decode_step"]
    assert sum(s["rows_overrun"] for s in ticks) == 1
    assert sum(s["tokens"] for s in ticks) == at + 1 + 6
    assert engine.slots.free_count == 1
    assert engine.slots.pages_free == engine.slots.num_pages


# -- scheduler / slot units --------------------------------------------------


def test_backpressure_and_rejection(setup):
    cfg, params = setup
    engine = make_engine(cfg, params, max_queue=2)

    # shape that can never be served -> rejected outright
    with pytest.raises(RequestRejected):
        engine.submit(ServeRequest(input_ids=list(range(BUCKET + 1)),
                                   gen=GenerationConfig(max_new_tokens=2)))
    with pytest.raises(RequestRejected):  # budget overflows the slot
        engine.submit(ServeRequest(input_ids=[5],
                                   gen=GenerationConfig(max_new_tokens=100)))
    with pytest.raises(RequestRejected):
        engine.submit(ServeRequest(input_ids=[]))

    # bounded wait queue -> overload is backpressure, not OOM
    small = GenerationConfig(max_new_tokens=2)
    for i in range(2):
        engine.submit(ServeRequest(input_ids=[3 + i], gen=small))
    with pytest.raises(ServeOverloaded):
        engine.submit(ServeRequest(input_ids=[9], gen=small))
    # all 4 refusals count: 3 unservable shapes + 1 overload
    assert engine.stats.snapshot()["requests_rejected"] == 4
    engine.drain(timeout_s=120)  # the queued two still complete
    assert engine.queue_depth() == 0


def test_shutdown_fails_pending_and_blocks_late_submits(setup):
    """shutdown() fails queued handles and flips the engine closed: a late
    submit raises EngineShutdown instead of queueing into a dead engine
    (its handle would otherwise block its caller forever)."""
    from llama_pipeline_parallel_tpu.serve import EngineShutdown

    cfg, params = setup
    engine = make_engine(cfg, params)
    small = GenerationConfig(max_new_tokens=2)
    h = engine.submit(ServeRequest(input_ids=[5], gen=small))
    engine.shutdown()
    with pytest.raises(EngineShutdown):
        h.result(timeout=1)
    with pytest.raises(EngineShutdown):
        engine.submit(ServeRequest(input_ids=[6], gen=small))


def test_serve_config_validation():
    with pytest.raises(ValueError):
        ServeConfig(prompt_buckets=())
    with pytest.raises(ValueError):
        ServeConfig(prompt_buckets=(64, 32))         # not ascending
    with pytest.raises(ValueError):
        ServeConfig(prompt_buckets=(64,), max_len=64)  # no room to generate
    with pytest.raises(ValueError):
        ServeConfig(max_queue=0)


def test_the_paged_pool_is_the_only_kv_store(setup):
    """`ServeConfig()` with no arguments is a page pool of one max_len row
    a slot, and an engine built from a config that names no store runs the
    paged tick; any other `kv_cache` is refused by a sentence that names
    the pool."""
    scfg = ServeConfig()
    assert scfg.kv_cache == "paged"
    assert scfg.resolved_num_pages == (scfg.max_slots * scfg.max_len
                                       // scfg.page_size)
    cfg, params = setup
    engine = ServeEngine(params, cfg, ServeConfig(
        max_slots=2, max_len=16, prompt_buckets=(8,), page_size=4))
    assert type(engine.slots).__name__ == "PagedKVCache"
    assert engine.metrics_snapshot()["kv_cache"] == "paged"
    assert set(engine.slots.pool) == {"k", "v"}
    for other in ("dense", "rowed"):
        with pytest.raises(ValueError, match="paged pool"):
            ServeConfig(kv_cache=other)


@pytest.mark.parametrize("tool", ["serve", "serve_traffic"])
def test_the_tools_have_no_flag_that_chooses_a_store(tool, capsys):
    """`--kv_cache` is gone from both command lines: argparse refuses it
    before anything is loaded."""
    import importlib

    cli = importlib.import_module(tool)    # tools/ on sys.path via conftest
    assert os.path.samefile(cli.__file__,
                            os.path.join(REPO, "tools", f"{tool}.py"))
    with pytest.raises(SystemExit) as exit_:
        cli.main(["--checkpoint_dir", "unused", "--output_dir", "unused",
                  "--page_size", "8", "--kv_cache", "paged"])
    assert exit_.value.code == 2
    assert ("unrecognized arguments: --kv_cache paged"
            in capsys.readouterr().err)


def test_page_alignment_is_checked_for_every_engine():
    """No engine has a row that is not whole pages: `max_len` and every
    bucket are multiples of `page_size`, whether or not a caller names
    the store."""
    with pytest.raises(ValueError, match="max_len 40 must be a multiple"):
        ServeConfig(max_len=40, prompt_buckets=(8, 16, 32), page_size=16)
    with pytest.raises(ValueError, match="prompt bucket 8 must be a "
                                         "multiple"):
        ServeConfig(max_len=320, prompt_buckets=(8,))   # page_size 64
    ServeConfig(max_len=320, prompt_buckets=(8,), page_size=8)


def test_pick_bucket_prefers_smallest_fitting(setup):
    cfg, params = setup
    engine = ServeEngine(params, cfg, ServeConfig(
        max_slots=1, max_len=40, prompt_buckets=(8, 16, 32), page_size=8))
    assert engine.pick_bucket(5, 4) == 8
    assert engine.pick_bucket(9, 4) == 16
    # 8-token budget pushes a 30-prompt past max_len on bucket 32 -> reject
    with pytest.raises(RequestRejected):
        engine.pick_bucket(30, 16)


def test_decode_span_aggregation(setup, tmp_path):
    """Decode-tick spans aggregate (decode_span_every) so a long-lived
    replica doesn't grow spans.jsonl at token rate; the aggregate's dur is
    the exact sum of its ticks and the idle boundary flushes the tail."""
    cfg, params = setup
    trace.configure(str(tmp_path))
    try:
        engine = make_engine(cfg, params, decode_span_every=1000)
        engine.submit(ServeRequest(
            input_ids=[5, 6], gen=GenerationConfig(max_new_tokens=5)))
        engine.drain(timeout_s=60)
        assert engine.step() is False  # idle boundary flushes the aggregate
    finally:
        trace.configure(None)
    with open(tmp_path / "spans.jsonl") as f:
        spans = [json.loads(l) for l in f]
    decode_spans = [s for s in spans if s["name"] == "serve_decode_step"]
    assert len(decode_spans) == 1              # 4 ticks, ONE line
    assert decode_spans[0]["ticks"] == 4       # max_new 5 -> 4 decode ticks
    assert decode_spans[0]["dur"] > 0.0


def test_percentile_helpers():
    assert percentile([], 50) is None
    assert percentile([3.0], 99) == 3.0
    vals = list(range(1, 102))       # 1..101: median unambiguous
    assert percentile(vals, 50) == 51
    assert percentile(vals, 100) == 101
    assert percentile(vals, 0) == 1
    out = percentiles_ms([0.1, 0.2], "ttft")
    assert set(out) == {"ttft_p50_ms", "ttft_p95_ms", "ttft_p99_ms"}
    assert percentiles_ms([], "x") == {}
    stats = SLOStats()
    stats.record(ttft=0.5, tpot=None, queue_wait=0.1, tokens=1)
    snap = stats.snapshot()
    assert snap["requests_completed"] == 1
    assert "tpot_p50_ms" not in snap  # single-token request: TPOT undefined


# -- in-process loop + HTTP front-end ---------------------------------------


@pytest.mark.slow  # ServeLoop streaming now runs every tier-1 lane under
# real load via test_serve_traffic.py::test_run_trace_against_chunked_paged_
# engine (plus the HTTP test below); this focused dense rep joins the round
# gate
def test_serve_loop_streams_tokens(setup):
    """ServeLoop drives the engine in the background; the handle streams
    tokens as they are produced and the stream matches the result."""
    cfg, params = setup
    engine = make_engine(cfg, params)
    with ServeLoop(engine, idle_wait_s=0.005):
        h = engine.submit(ServeRequest(
            input_ids=[5, 6, 7],
            gen=GenerationConfig(max_new_tokens=5, temperature=0.9), seed=4))
        streamed = list(h.tokens(timeout=60))
    assert len(streamed) == 5
    assert streamed == h.result(timeout=1)
    assert streamed == reference_tokens(
        params, cfg, [5, 6, 7],
        GenerationConfig(max_new_tokens=5, temperature=0.9), 4)


def test_http_frontend_inprocess(setup):
    from llama_pipeline_parallel_tpu.serve.frontend import make_server

    cfg, params = setup
    engine = make_engine(cfg, params)
    server = make_server(engine)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        with ServeLoop(engine, idle_wait_s=0.005):
            def post(body, headers=None):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/generate",
                    data=json.dumps(body).encode(),
                    headers={"Content-Type": "application/json",
                             **(headers or {})})
                return urllib.request.urlopen(req, timeout=60)

            resp = post({"input_ids": [5, 6], "max_new_tokens": 3,
                         "seed": 1})
            # correlation contract (docs/SERVING.md "Request tracing"):
            # ids in the body AND the response headers, joined by the
            # incoming W3C traceparent when the caller sent one
            assert resp.headers["X-Request-Id"]
            assert resp.headers["X-Trace-Id"]
            assert resp.headers["traceparent"].startswith("00-")
            out = json.load(resp)
            assert out["request_id"] == resp.headers["X-Request-Id"]
            assert out["trace_id"] == resp.headers["X-Trace-Id"]
            assert out["tokens"] == reference_tokens(
                params, cfg, [5, 6], GenerationConfig(max_new_tokens=3), 1)

            parent = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
            joined = post({"input_ids": [5, 6], "max_new_tokens": 1},
                          headers={"traceparent": parent})
            assert joined.headers["X-Trace-Id"] == "ab" * 16  # adopted
            assert joined.headers["traceparent"] != parent    # our span id

            stream = post({"input_ids": [4, 5], "max_new_tokens": 4,
                           "temperature": 0.8, "top_p": 0.9, "seed": 2,
                           "stream": True})
            assert stream.headers["X-Trace-Id"]
            lines = [json.loads(l) for l in stream.read().decode().splitlines()]
            assert [l["token"] for l in lines[:-1]] == lines[-1]["tokens"]
            assert lines[-1]["done"] is True
            # the FIRST streamed line carries the correlation ids (a client
            # can join a waterfall without waiting for the tail line);
            # later token lines stay minimal
            assert lines[0]["request_id"] == stream.headers["X-Request-Id"]
            assert lines[0]["trace_id"] == stream.headers["X-Trace-Id"]
            assert all(set(l) == {"token"} for l in lines[1:-1])
            assert lines[-1]["trace_id"] == stream.headers["X-Trace-Id"]

            health = json.load(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10))
            assert health["serving"] == 1 and health["requests_completed"] == 3

            with pytest.raises(urllib.error.HTTPError) as err:
                post({"input_ids": "nope"})
            assert err.value.code == 400
            with pytest.raises(urllib.error.HTTPError) as err:
                post({"input_ids": list(range(BUCKET + 1))})
            assert err.value.code == 400
    finally:
        server.shutdown()


def test_serving_report_builds_from_run_dir(tmp_path):
    import serving_report  # tools/ on sys.path via conftest

    spans = [
        {"name": "serve_request", "ts": 100.0, "end": 101.0, "dur": 1.0,
         "ttft": 0.3, "tpot": 0.05, "queue_wait": 0.1, "tokens": 15},
        {"name": "serve_request", "ts": 100.5, "end": 102.0, "dur": 1.5,
         "ttft": 0.6, "tpot": 0.07, "queue_wait": 0.2, "tokens": 5},
        {"name": "serve_decode_step", "ts": 100.0, "dur": 0.01},
    ]
    with open(tmp_path / "spans.jsonl", "w") as f:
        for s in spans:
            f.write(json.dumps(s) + "\n")
        f.write("{torn")  # torn tail must not kill the report
    with open(tmp_path / "metrics.jsonl", "w") as f:
        f.write(json.dumps({"step": 2, "serving": 1, "requests_completed": 2,
                            "ttft_p50_ms": 300.0, "active_slots": 0,
                            "slot_allocations": 1}) + "\n")

    rep = serving_report.build_report(str(tmp_path))
    assert rep["requests"] == 2 and rep["tokens"] == 20
    assert rep["ttft"]["ttft_p50_ms"] == 300.0
    assert rep["tpot"]["tpot_p99_ms"] == 70.0
    assert rep["tokens_per_sec"] == pytest.approx(20 / 2.0)
    assert rep["last_metrics"]["slot_allocations"] == 1
    assert serving_report.main([str(tmp_path)]) == 0
    # empty dir degrades, nonzero exit, no traceback
    empty = tmp_path / "empty"
    empty.mkdir()
    assert serving_report.main([str(empty)]) == 1


# -- multi-replica serving under the supervisor ------------------------------


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_for_replica(out_dir: str, old_pid: int | None = None,
                      timeout_s: float = 120.0) -> dict:
    """Poll serve.json until a (new) replica is up and /healthz answers."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            with open(os.path.join(out_dir, "serve.json")) as f:
                info = json.load(f)
            if old_pid is not None and info["pid"] == old_pid:
                raise OSError("still the old incarnation")
            urllib.request.urlopen(
                f"http://127.0.0.1:{info['port']}/healthz", timeout=5)
            return info
        except Exception:
            time.sleep(0.25)
    raise TimeoutError(f"no live replica in {out_dir} within {timeout_s}s")


def _post(port: int, body: dict, timeout: float = 120.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    return json.load(urllib.request.urlopen(req, timeout=timeout))


@pytest.mark.slow  # ~40 s of real process spawns/kills — the heavyweight
# chaos leg the CI gate note already earmarks for the round gate; its
# machinery (supervisor restart, serve.json discovery, role ledger) is
# untouched by the paged-cache work that funds this rebalance
def test_multi_replica_supervised_restart(setup, tmp_path):
    """Two serve replicas under tools/supervisor.py from ONE checkpoint;
    replica A is SIGKILLed mid-decode, the watchdog restarts it from the
    same checkpoint, and it serves again — the incarnation ledger records
    the crash, the restart, and the serve role."""
    import supervisor  # tools/ on sys.path via conftest
    from llama_pipeline_parallel_tpu.ckpt.checkpoint import CheckpointManager
    from llama_pipeline_parallel_tpu.models.llama.manifest import StageManifest
    from llama_pipeline_parallel_tpu.parallel.pipeline import stack_stages

    cfg, params = setup
    ckpt = str(tmp_path / "ckpt")
    manifest = StageManifest.for_config(cfg, 1)
    CheckpointManager(ckpt).save(0, stack_stages(params, manifest), manifest,
                                 cfg)

    replicas, sups, threads = {}, {}, {}
    try:
        for name in ("a", "b"):
            out = str(tmp_path / name)
            port = _free_port()
            cmd = [sys.executable, os.path.join(REPO, "tools", "serve.py"),
                   "--checkpoint_dir", ckpt, "--output_dir", out,
                   "--host", "127.0.0.1", "--port", str(port),
                   "--platform", "cpu", "--max_slots", "2",
                   "--max_len", "320", "--buckets", "8",
                   "--page_size", "8", "--metrics_every", "1"]
            env = dict(os.environ)
            # stretch decode steps so the kill lands mid-decode deterministically
            env["LPT_SERVE_STEP_DELAY_S"] = "0.05" if name == "a" else "0"
            sup = supervisor.Supervisor(cmd, supervisor.SupervisorConfig(
                output_dir=out, max_restarts=3, hang_timeout_s=300.0,
                grace_s=5.0, crash_loop_threshold=3, crash_loop_window_s=0.0,
                poll_s=0.1), env=env)
            t = threading.Thread(target=sup.run, daemon=True)
            t.start()
            replicas[name], sups[name], threads[name] = out, sup, t

        info = {n: _wait_for_replica(replicas[n]) for n in ("a", "b")}

        # both replicas serve, and token-identically: same checkpoint,
        # same seed -> same stream, whichever replica handles it
        body = {"input_ids": [5, 6, 7], "max_new_tokens": 4, "seed": 3}
        out_a = _post(info["a"]["port"], body)["tokens"]
        out_b = _post(info["b"]["port"], body)["tokens"]
        assert out_a == out_b
        assert out_a == reference_tokens(params, cfg, [5, 6, 7],
                                         GenerationConfig(max_new_tokens=4), 3)

        # a long streaming request on A, killed mid-decode
        def doomed():
            try:
                urllib.request.urlopen(urllib.request.Request(
                    f"http://127.0.0.1:{info['a']['port']}/v1/generate",
                    data=json.dumps({"input_ids": [9, 10],
                                     "max_new_tokens": 300,
                                     "stream": True}).encode()),
                    timeout=300).read()
            except Exception:
                pass  # the point: the replica dies under it

        t_doomed = threading.Thread(target=doomed, daemon=True)
        t_doomed.start()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:  # wait until decode is underway
            health = supervisor.read_health(replicas["a"]) or {}
            if (health.get("last_step") or 0) >= 3:
                break
            time.sleep(0.1)
        else:
            pytest.fail("replica a never started decoding the doomed request")
        os.kill(info["a"]["pid"], signal.SIGKILL)

        # the watchdog relaunches from the same checkpoint; it serves again
        new_info = _wait_for_replica(replicas["a"], old_pid=info["a"]["pid"])
        assert new_info["checkpoint_step"] == 0
        out_a2 = _post(new_info["port"], body)["tokens"]
        assert out_a2 == out_a  # same checkpoint, same seed, same tokens

        # the goodput ledger recorded the crash + serve role
        with open(os.path.join(replicas["a"], "incarnations.jsonl")) as f:
            rows = [json.loads(l) for l in f]
        assert rows[0]["outcome"] == "crash" and rows[0]["exit_code"] != 0
        assert rows[0]["role"] == "serve"
        assert rows[0]["incarnation"] == 0
    finally:
        # clean stop: SIGTERM the children -> serve exits 0 -> supervisors
        # return; anything still alive gets killed so the test never leaks
        for name, out in replicas.items():
            try:
                with open(os.path.join(out, "serve.json")) as f:
                    os.kill(json.load(f)["pid"], signal.SIGTERM)
            except (OSError, ValueError):
                pass
        for name, t in threads.items():
            t.join(timeout=60)
        for name, out in replicas.items():
            try:
                with open(os.path.join(out, "serve.json")) as f:
                    os.kill(json.load(f)["pid"], signal.SIGKILL)
            except (OSError, ValueError):
                pass

    # B was never restarted; its supervisor saw a clean exit
    with open(os.path.join(replicas["b"], "incarnations.jsonl")) as f:
        rows_b = [json.loads(l) for l in f]
    assert [r["outcome"] for r in rows_b] == ["clean"]
    assert rows_b[0]["role"] == "serve"


# -- degraded-mode admission (docs/RESILIENCE.md "Actuation") ----------------


def test_degraded_mode_sheds_and_recovers(setup):
    """A degraded engine (draining / mid-resize) refuses NEW admissions
    with an honest retry hint, keeps decoding what it already admitted,
    advertises the reason in its metrics, and recovers the moment the
    degradation clears."""
    cfg, params = setup
    engine = make_engine(cfg, params)
    gen = GenerationConfig(max_new_tokens=3)
    h = engine.submit(ServeRequest(input_ids=[5, 6], gen=gen, seed=1))
    engine.set_degraded("draining")
    with pytest.raises(ServeOverloaded) as exc:
        engine.submit(ServeRequest(input_ids=[7, 8], gen=gen))
    assert "degraded (draining)" in str(exc.value)
    assert exc.value.retry_after_s > 0
    assert engine.metrics_snapshot()["degraded"] == "draining"
    # the admitted request still decodes through the degraded window
    engine.drain(timeout_s=120)
    assert h.result(timeout=1) == reference_tokens(params, cfg, [5, 6],
                                                   gen, 1)
    engine.clear_degraded()
    assert "degraded" not in engine.metrics_snapshot()
    h2 = engine.submit(ServeRequest(input_ids=[7, 8], gen=gen, seed=2))
    engine.drain(timeout_s=120)
    assert h2.result(timeout=1) == reference_tokens(params, cfg, [7, 8],
                                                    gen, 2)


def test_degraded_maps_to_429_with_pinned_retry_after(setup):
    """HTTP contract pin: a degraded replica answers 429 with a
    Retry-After measured from its OWN backlog and drain rate. 2 queued
    requests draining at 1 completion / 30 s window -> 90 s, clamped to
    the 60 s cap — jitter cannot move a clamped value, so the header is
    exactly "60" for any request id."""
    from llama_pipeline_parallel_tpu.serve.frontend import make_server

    cfg, params = setup
    engine = make_engine(cfg, params)
    server = make_server(engine)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    gen = GenerationConfig(max_new_tokens=2)
    try:
        for i in range(2):
            engine.submit(ServeRequest(input_ids=[5, 6], gen=gen, seed=i))
        engine.stats.finished_at.append(time.monotonic())
        engine.set_degraded("draining")
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/generate",
                data=json.dumps({"input_ids": [3, 4],
                                 "max_new_tokens": 2}).encode(),
                headers={"Content-Type": "application/json"}), timeout=60)
        assert err.value.code == 429
        assert err.value.headers["Retry-After"] == "60"
        assert "degraded (draining)" in json.loads(err.value.read())["error"]
    finally:
        engine.clear_degraded()
        engine.drain(timeout_s=120)  # the queued admissions still finish
        server.shutdown()


def test_shutdown_maps_to_503_with_pinned_retry_after(setup):
    """HTTP contract pin for the gateway's failover signal: a shut-down
    replica answers 503 + Retry-After so the gateway reroutes instead of
    hot-retrying a dying process. With no measured completions the hint
    is the 1.0 s fallback, and "rid-301" has zero deterministic jitter
    (crc32 % 1000 == 0) — the header is exactly "1"."""
    from llama_pipeline_parallel_tpu.serve.frontend import make_server

    cfg, params = setup
    engine = make_engine(cfg, params)
    server = make_server(engine)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        engine.shutdown()
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/generate",
                data=json.dumps({"input_ids": [3, 4], "max_new_tokens": 2,
                                 "request_id": "rid-301"}).encode(),
                headers={"Content-Type": "application/json"}), timeout=60)
        assert err.value.code == 503
        assert err.value.headers["Retry-After"] == "1"
        payload = json.loads(err.value.read())
        assert "shut down" in payload["error"]
        assert payload["request_id"] == "rid-301"
    finally:
        server.shutdown()
