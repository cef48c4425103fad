"""Gateway tier (serve/gateway.py, tools/gateway.py — docs/SERVING.md
"Gateway & failover").

The acceptance contracts live here:
- WAL discipline: every accepted request journalled before dispatch,
  exactly ONE terminal row per gid (duplicates rejected at write, first
  wins at load), torn tails tolerated, orphans reconciled at restart.
- bit-exact replay failover: a replica killed mid-stream -> the gateway
  re-submits the journalled request (same seed/config) to a survivor,
  verifies + skips the delivered-token watermark, and splices — the
  client's stream is TOKEN-IDENTICAL to an uninterrupted independent
  generate() call.
- health-aware routing + bounded retry honoring Retry-After, hedged
  dispatch with first-token-wins, and the one-way import pin: the
  direct-to-replica path never pays for the gateway.

Protocol-level legs (retry/hedge/splice-divergence) run against scripted
FakeReplica servers — the front-end's wire shape without an engine — so
they are fast; the determinism legs run real engines.
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
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

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
    ServeConfig,
    ServeEngine,
    ServeLoop,
)
from llama_pipeline_parallel_tpu.serve.frontend import make_server
from llama_pipeline_parallel_tpu.serve.gateway import (
    Gateway,
    GatewayJournal,
    GatewayOverloaded,
    GatewayRejected,
    JOURNAL_NAME,
    ReplicaDirectory,
    SpliceDiverged,
    make_gateway_server,
)
from llama_pipeline_parallel_tpu.utils import fleet
from llama_pipeline_parallel_tpu.utils.retry import RetryPolicy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUCKET = 8

FAST_POLICY = RetryPolicy(max_attempts=4, base_delay_s=0.01,
                          max_delay_s=0.05)


@pytest.fixture(scope="module")
def setup():
    cfg = LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def reference_tokens(params, cfg, prompt, gen, seed):
    """What any replica must emit for (prompt, seed, gen) — and therefore
    what the gateway's spliced stream must equal across a failover."""
    pad = BUCKET - len(prompt)
    ids = np.concatenate([np.zeros(pad, np.int32),
                          np.asarray(prompt, np.int32)])[None]
    mask = np.asarray([[0] * pad + [1] * len(prompt)], np.int32)
    out = generate(params, jnp.asarray(ids), jnp.asarray(mask), cfg, gen,
                   rng=jax.random.PRNGKey(seed))
    return np.asarray(out["tokens"])[0].tolist()


def write_replica_files(outdir: str, port: int | None,
                        hb_time: float | None = None) -> None:
    """The discovery surface a live replica maintains: serve.json
    (endpoint) + health.json (heartbeat)."""
    os.makedirs(outdir, exist_ok=True)
    if port is not None:
        fleet.write_json_atomic(os.path.join(outdir, "serve.json"),
                                {"pid": os.getpid(), "host": "127.0.0.1",
                                 "port": port, "started": time.time()})
    fleet.write_json_atomic(
        os.path.join(outdir, fleet.HEALTH_NAME),
        {"time": time.time() if hb_time is None else hb_time,
         "role": "serve"})


def journal_rows(gw_dir: str) -> list[dict]:
    with open(os.path.join(gw_dir, JOURNAL_NAME)) as f:
        return [json.loads(line) for line in f if line.strip()]


# -- a scripted stand-in replica ---------------------------------------------


class _FakeHandler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.0"

    def log_message(self, fmt, *args):
        pass

    def do_GET(self):
        body = json.dumps({"serving": 1, "queue_depth": 0,
                           "queue_wait_p95_ms": 0.0}).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_POST(self):
        length = int(self.headers.get("Content-Length", 0))
        body = json.loads(self.rfile.read(length) or b"{}")
        server = self.server
        with server.lock:  # type: ignore[attr-defined]
            server.requests.append(body)  # type: ignore[attr-defined]
            n = len(server.requests)  # type: ignore[attr-defined]
        plan = server.script(body, n)  # type: ignore[attr-defined]
        code = plan.get("code", 200)
        if code != 200:
            payload = json.dumps({"error": plan.get("error", "no")}).encode()
            self.send_response(code)
            if plan.get("retry_after") is not None:
                self.send_header("Retry-After", str(plan["retry_after"]))
            self.send_header("Content-Length", str(len(payload)))
            self.end_headers()
            self.wfile.write(payload)
            return
        self.send_response(200)
        self.send_header("Content-Type", "application/jsonlines")
        self.end_headers()
        tokens = plan.get("tokens", [])
        die_after = plan.get("die_after")
        delay = plan.get("token_delay", 0.0)
        try:
            for i, tok in enumerate(tokens):
                if die_after is not None and i >= die_after:
                    return  # crash: close without the done line
                if delay:
                    time.sleep(delay)
                line = ({"token": tok, "request_id": body.get("request_id"),
                         "trace_id": "t"} if i == 0 else {"token": tok})
                self.wfile.write((json.dumps(line) + "\n").encode())
                self.wfile.flush()
            if die_after is not None and die_after >= len(tokens):
                return
            self.wfile.write((json.dumps(
                {"done": True, "request_id": body.get("request_id"),
                 "tokens": tokens}) + "\n").encode())
        except OSError:
            with server.lock:  # type: ignore[attr-defined]
                server.disconnects += 1  # type: ignore[attr-defined]


class FakeReplica:
    """Scripted replica speaking the front-end's wire protocol.
    `script(body, n)` -> {"tokens": [...], "die_after": k,
    "token_delay": s} or {"code": 429, "retry_after": 0.05} — the
    protocol legs (backoff, hedge, divergence) without an engine."""

    def __init__(self, outdir: str, script):
        self.output_dir = outdir
        self.server = ThreadingHTTPServer(("127.0.0.1", 0), _FakeHandler)
        self.server.script = script  # type: ignore[attr-defined]
        self.server.requests = []  # type: ignore[attr-defined]
        self.server.disconnects = 0  # type: ignore[attr-defined]
        self.server.lock = threading.Lock()  # type: ignore[attr-defined]
        self.server.daemon_threads = True  # type: ignore[attr-defined]
        self.port = self.server.server_address[1]
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()
        write_replica_files(outdir, self.port)

    @property
    def requests(self):
        return self.server.requests  # type: ignore[attr-defined]

    @property
    def disconnects(self):
        return self.server.disconnects  # type: ignore[attr-defined]

    def close(self):
        self.server.shutdown()


def make_gateway(tmp_path, *replicas, name="gw", **kw):
    directory = ReplicaDirectory(
        replica_dirs=tuple(r.output_dir for r in replicas),
        stale_s=60.0, probe_every_s=0.05, probe_timeout_s=1.0)
    kw.setdefault("policy", FAST_POLICY)
    kw.setdefault("route_wait_s", 5.0)
    return Gateway(str(tmp_path / name), directory, **kw)


# -- WAL discipline -----------------------------------------------------------


def test_journal_exactly_once_and_duplicate_rejected(tmp_path):
    """The writer enforces one terminal per gid; state survives reload."""
    gw_dir = str(tmp_path / "gw")
    j = GatewayJournal(gw_dir)
    j.intent("g1", "t1", {"input_ids": [1], "seed": 0})
    j.routed("g1", "a", 1)
    j.watermark("g1", 4)
    j.watermark("g1", 2)          # stale watermark can't move it back
    j.terminal("g1", "completed", tokens=8, replays=1)
    with pytest.raises(ValueError):
        j.terminal("g1", "failed")
    assert j.has_terminal("g1") and j.orphans() == []
    j.close()

    j2 = GatewayJournal(gw_dir)   # restart: rebuild from the file
    st = j2.state["g1"]
    assert st["watermark"] == 4
    assert st["terminal"]["outcome"] == "completed"
    assert st["terminal"]["replays"] == 1
    assert [r["replica"] for r in st["routed"]] == ["a"]
    with pytest.raises(ValueError):  # the exactly-once rule survives too
        j2.terminal("g1", "failed")
    j2.close()


def test_journal_torn_tail_orphans_and_first_terminal_wins(tmp_path):
    """A torn tail (the crash case) is skipped, not fatal; intents without
    terminals come back as orphans in intent order; a duplicated terminal
    in the file (crash between write and flush) keeps the FIRST."""
    gw_dir = str(tmp_path / "gw")
    j = GatewayJournal(gw_dir)
    j.intent("g2", "t2", {"input_ids": [2], "seed": 0})
    time.sleep(0.01)  # intent-ts order must be observable
    j.intent("g1", "t1", {"input_ids": [1], "seed": 0})
    j.intent("g3", "t3", {"input_ids": [3], "seed": 0})
    j.terminal("g3", "completed", tokens=2)
    j.close()
    with open(os.path.join(gw_dir, JOURNAL_NAME), "a") as f:
        # a crashed twin's duplicate terminal + a torn tail
        f.write(json.dumps({"kind": "terminal", "gid": "g3",
                            "outcome": "failed", "tokens": 0,
                            "ts": time.time()}) + "\n")
        f.write('{"kind": "intent", "gid": "g4", "tr')

    j2 = GatewayJournal(gw_dir)
    assert j2.orphans() == ["g2", "g1"]          # intent order, no g3/g4
    assert j2.state["g3"]["terminal"]["outcome"] == "completed"
    assert "g4" not in j2.state
    j2.close()


# -- discovery + health-aware routing ----------------------------------------


def test_directory_candidates_health_gates(tmp_path):
    """candidates() drops replicas without an endpoint, with a stale
    heartbeat, or cooling from a Retry-After — and orders the rest by
    load (inflight + probed queue depth)."""
    dirs = {n: str(tmp_path / n) for n in ("a", "b", "c", "d")}
    write_replica_files(dirs["a"], port=1)
    write_replica_files(dirs["b"], port=2)
    write_replica_files(dirs["c"], port=None)            # no endpoint yet
    write_replica_files(dirs["d"], port=4,
                        hb_time=time.time() - 120)       # stale heartbeat
    d = ReplicaDirectory(replica_dirs=tuple(dirs.values()), stale_s=30.0)
    d.poll(probe=False)
    assert [r.name for r in d.candidates()] == ["a", "b"]

    a, b = d.candidates()
    d.acquire(a)                                         # a now loaded
    assert [r.name for r in d.candidates()] == ["b", "a"]
    d.release(a)
    b.queue_depth = 3                                    # probed gauge
    assert [r.name for r in d.candidates()] == ["a", "b"]

    d.note_backoff(a, retry_after=30.0)                  # cooling
    assert [r.name for r in d.candidates()] == ["b"]
    assert [r.name for r in d.candidates(exclude=("b",))] == []
    snap = d.snapshot()
    assert snap["a"]["cooling_s"] > 0 and not snap["a"]["healthy"]
    assert snap["d"]["heartbeat_age_s"] > 30


def test_directory_ingests_fleet_registry(tmp_path):
    """role="serve" registry rows (PR 15) name replicas live — the
    gateway needs no restart to see a new one."""
    root = str(tmp_path / "fleet")
    os.makedirs(root)
    d = ReplicaDirectory(fleet_root=root, stale_s=60.0)
    d.poll(probe=False)
    assert d.all() == []
    out = str(tmp_path / "r0")
    write_replica_files(out, port=7)
    fleet.register_member(root, output_dir=out, role="serve", replica="r0",
                          pid=os.getpid())
    fleet.register_member(root, output_dir=str(tmp_path / "tr"),
                          role="trainer", pid=os.getpid())
    d.poll(probe=False)
    assert [r.name for r in d.all()] == ["r0"]          # serve rows only
    assert [r.name for r in d.candidates()] == ["r0"]


# -- protocol legs against scripted replicas ---------------------------------


def test_retry_honors_retry_after_and_cools_replica(tmp_path):
    """A 429 with Retry-After moves the request to another replica, cools
    the refusing one for the hinted window, and counts the retry."""
    a = FakeReplica(str(tmp_path / "a"),
                    lambda body, n: {"code": 429, "retry_after": 5.0,
                                     "error": "full"})
    b = FakeReplica(str(tmp_path / "b"),
                    lambda body, n: {"tokens": [7, 8, 9]})
    try:
        gw = make_gateway(tmp_path, a, b)
        handle = gw.submit({"input_ids": [1, 2], "max_new_tokens": 3,
                            "seed": 0})
        assert handle.result() == [7, 8, 9]
        assert handle.info["attempts"] == 2
        snap = gw.healthz()
        assert snap["requests_retried"] == 1
        assert snap["requests_completed"] == 1
        # the refuser is cooling for ~the hinted 5 s, so it is not healthy
        assert not snap["replicas"]["a"]["healthy"]
        assert snap["replicas"]["a"]["cooling_s"] > 3
        term = [r for r in journal_rows(str(tmp_path / "gw"))
                if r["kind"] == "terminal"]
        assert [t["outcome"] for t in term] == ["completed"]
        gw.close()
    finally:
        a.close(), b.close()


def test_backoff_budget_spent_sheds_with_retry_after(tmp_path):
    """Every replica refusing -> the gateway sheds honestly (429 class +
    Retry-After) instead of hot-looping; the WAL outcome is `shed`."""
    a = FakeReplica(str(tmp_path / "a"),
                    lambda body, n: {"code": 429, "retry_after": 0.01,
                                     "error": "full"})
    try:
        gw = make_gateway(tmp_path, a, policy=RetryPolicy(
            max_attempts=2, base_delay_s=0.01, max_delay_s=0.02))
        handle = gw.submit({"input_ids": [1], "seed": 0})
        with pytest.raises(GatewayOverloaded) as exc:
            handle.result()
        assert exc.value.code == 429
        assert exc.value.retry_after_s > 0
        snap = gw.healthz()
        assert snap["requests_shed"] == 1
        term = [r for r in journal_rows(str(tmp_path / "gw"))
                if r["kind"] == "terminal"]
        assert [t["outcome"] for t in term] == ["shed"]
        gw.close()
    finally:
        a.close()


def test_replica_400_is_terminal_not_retried(tmp_path):
    """A deterministic 400 must not burn retries on other replicas."""
    a = FakeReplica(str(tmp_path / "a"),
                    lambda body, n: {"code": 400, "error": "bad shape"})
    b = FakeReplica(str(tmp_path / "b"),
                    lambda body, n: {"tokens": [1]})
    try:
        gw = make_gateway(tmp_path, a, b)
        with pytest.raises(GatewayRejected, match="bad shape"):
            gw.submit({"input_ids": [1], "seed": 0}).result()
        assert gw.healthz()["requests_rejected"] == 1
        assert b.requests == []                 # never dispatched to b
        gw.close()
    finally:
        a.close(), b.close()


def test_splice_divergence_fails_loudly(tmp_path):
    """A replayed stream that disagrees with the already-delivered prefix
    is a broken determinism contract — the gateway must fail the request,
    never serve a franken-stream."""
    a = FakeReplica(str(tmp_path / "a"),
                    lambda body, n: {"tokens": [1, 2, 3, 4],
                                     "die_after": 2})
    b = FakeReplica(str(tmp_path / "b"),
                    lambda body, n: {"tokens": [1, 9, 3, 4]})
    try:
        gw = make_gateway(tmp_path, a, b)
        handle = gw.submit({"input_ids": [5], "seed": 0})
        it = handle.tokens()
        assert [next(it), next(it)] == [1, 2]   # delivered prefix from a
        with pytest.raises(SpliceDiverged):
            list(it)                            # b's replay diverges at 1
        term = [r for r in journal_rows(str(tmp_path / "gw"))
                if r["kind"] == "terminal"]
        assert term[0]["outcome"] == "failed"
        assert term[0]["reason"] == "splice"
        gw.close()
    finally:
        a.close(), b.close()


def test_watermark_ahead_blocks_splice_until_caught_up(tmp_path):
    """A replayed replica slower than the original: the splice stays
    BLOCKED while the replay re-streams the already-delivered prefix —
    the client sees a gap, never a duplicate — and resumes exactly at
    the watermark once the replay catches up."""
    a = FakeReplica(str(tmp_path / "a"),
                    lambda body, n: {"tokens": [1, 2, 3, 4, 5, 6],
                                     "die_after": 3})
    b = FakeReplica(str(tmp_path / "b"),
                    lambda body, n: {"tokens": [1, 2, 3, 4, 5, 6],
                                     "token_delay": 0.15})
    try:
        gw = make_gateway(tmp_path, a, b, watermark_every=1)
        handle = gw.submit({"input_ids": [5], "seed": 0})
        stream = [(tok, time.monotonic()) for tok in handle.tokens()]
        assert [tok for tok, _ in stream] == [1, 2, 3, 4, 5, 6]
        # the catch-up gap: b re-streamed the 3 suppressed tokens (plus
        # its own token 4) at 0.15 s each before anything new could be
        # delivered — a's instant prefix shows no such stall
        assert stream[3][1] - stream[2][1] >= 0.4
        assert stream[2][1] - stream[0][1] < 0.2
        assert handle.info == {"attempts": 2, "replays": 1, "hedges": 0}
        assert gw.healthz()["replay_skipped_tokens"] == 3
        rows = journal_rows(str(tmp_path / "gw"))
        marks = [r["delivered"] for r in rows if r["kind"] == "watermark"]
        assert marks == sorted(marks) and marks[-1] == 6
        assert [r for r in rows if r["kind"] == "terminal"][0][
            "outcome"] == "completed"
        gw.close()
    finally:
        a.close(), b.close()


def test_hedged_dispatch_first_token_wins_loser_cancelled(tmp_path):
    """With a fixed hedge delay, a stalled primary gets a second attempt
    on another replica; the first token decides the winner and the loser
    is cancelled (its socket closed — the replica-side disconnect)."""
    a = FakeReplica(str(tmp_path / "a"),
                    lambda body, n: {"tokens": [1, 2, 3],
                                     "token_delay": 1.5})
    b = FakeReplica(str(tmp_path / "b"),
                    lambda body, n: {"tokens": [1, 2, 3]})
    try:
        # name order routes the primary to the slow replica a; the hedge
        # fires after 0.1 s and b's instant first token wins the race
        gw = make_gateway(tmp_path, a, b, hedge=0.1)
        handle = gw.submit({"input_ids": [5], "seed": 0})
        t0 = time.monotonic()
        assert handle.result() == [1, 2, 3]
        assert time.monotonic() - t0 < 1.5      # did not wait out a
        assert handle.info == {"attempts": 2, "replays": 0, "hedges": 1}
        snap = gw.healthz()
        assert snap["requests_hedged"] == 1 and snap["hedge_wins"] == 1
        routed = [r for r in journal_rows(str(tmp_path / "gw"))
                  if r["kind"] == "routed"]
        assert [r["hedge"] for r in routed] == [False, True]
        assert {r["replica"] for r in routed} == {"a", "b"}
        gw.close()
    finally:
        a.close(), b.close()


def test_zero_token_stream_completes_empty(tmp_path):
    """The done line decides a zero-token stream — a valid completion,
    not a death."""
    a = FakeReplica(str(tmp_path / "a"), lambda body, n: {"tokens": []})
    try:
        gw = make_gateway(tmp_path, a)
        assert gw.submit({"input_ids": [1], "seed": 0}).result() == []
        assert gw.healthz()["requests_completed"] == 1
        gw.close()
    finally:
        a.close()


def test_draining_gateway_sheds_new_submits(tmp_path):
    a = FakeReplica(str(tmp_path / "a"), lambda body, n: {"tokens": [1]})
    try:
        gw = make_gateway(tmp_path, a)
        gw.draining = True
        with pytest.raises(GatewayOverloaded) as exc:
            gw.submit({"input_ids": [1], "seed": 0})
        assert exc.value.code == 503
        assert gw.healthz()["draining"] == 1
        gw.close()
    finally:
        a.close()


# -- reconciliation (gateway restart) ----------------------------------------


def test_reconcile_adopts_replica_trace_else_replays(tmp_path):
    """Orphaned intents left by a crashed gateway: one finished on its
    replica while the gateway was down (adopted from request_trace.jsonl
    by trace_id), one never ran (replayed headless) — both get exactly
    one terminal row."""
    a = FakeReplica(str(tmp_path / "a"),
                    lambda body, n: {"tokens": [4, 5]})
    try:
        gw_dir = str(tmp_path / "gw")
        j = GatewayJournal(gw_dir)
        j.intent("gone-1", "trace-done", {"input_ids": [1], "seed": 0})
        j.intent("gone-2", "trace-lost", {"input_ids": [2], "seed": 0})
        j.close()
        # replica-side evidence that gone-1 completed without us
        with open(os.path.join(a.output_dir, "request_trace.jsonl"),
                  "w") as f:
            f.write(json.dumps({"request_id": "gone-1.a1",
                                "trace_id": "trace-done",
                                "outcome": "completed", "tokens": 6}) + "\n")

        gw = make_gateway(tmp_path, a)
        results = {r["gid"]: r["outcome"] for r in gw.reconcile()}
        assert results == {"gone-1": "reconciled", "gone-2": "replayed"}
        term = {r["gid"]: r for r in journal_rows(gw_dir)
                if r["kind"] == "terminal"}
        assert term["gone-1"]["via"] == "replica_trace"
        assert term["gone-1"]["tokens"] == 6
        assert term["gone-2"]["tokens"] == 2    # the headless replay ran
        assert gw.journal.orphans() == []
        gw.close()
    finally:
        a.close()


def test_reconcile_no_replay_marks_lost(tmp_path):
    gw_dir = str(tmp_path / "gw")
    j = GatewayJournal(gw_dir)
    j.intent("gx", "tx", {"input_ids": [1], "seed": 0})
    j.close()
    gw = Gateway(gw_dir, ReplicaDirectory(stale_s=60.0),
                 policy=FAST_POLICY)
    assert [r["outcome"] for r in gw.reconcile(replay=False)] == ["lost"]
    assert gw.journal.state["gx"]["terminal"]["via"] == "no_replay"
    gw.close()


# -- the one-way import pin ---------------------------------------------------


def test_direct_path_never_imports_gateway():
    """The acceptance pin: serve/__init__ and tools/serve.py must not
    import the gateway — the single-replica direct path pays zero gateway
    import cost and stays byte-identical with the gateway absent."""
    for rel in (os.path.join("llama_pipeline_parallel_tpu", "serve",
                             "__init__.py"),
                os.path.join("tools", "serve.py")):
        with open(os.path.join(REPO, rel)) as f:
            assert "gateway" not in f.read(), \
                f"{rel} must stay gateway-free (one-way import contract)"


# -- real engines: parity, HTTP, replay splice -------------------------------


class LiveReplica:
    """An in-process real replica: engine + HTTP front-end + discovery
    files, with a pausable step loop so a test can freeze decode and kill
    it at an exact stream position."""

    def __init__(self, cfg, params, outdir: str, reqtrace=None,
                 **engine_kw):
        os.makedirs(outdir, exist_ok=True)
        self.output_dir = outdir
        # a pool that covers a full queue's reservations: the queue's
        # bound is the one these tests meet
        defaults = dict(max_slots=2, max_len=BUCKET + 8,
                        prompt_buckets=(BUCKET,), page_size=BUCKET // 2,
                        num_pages=40, max_queue=8)
        defaults.update(engine_kw)
        extra = {"reqtrace": reqtrace} if reqtrace is not None else {}
        self.engine = ServeEngine(params, cfg, ServeConfig(**defaults),
                                  **extra)
        self.server = make_server(self.engine)
        self.port = self.server.server_address[1]
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()
        self.paused = threading.Event()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        write_replica_files(outdir, self.port)

    def _loop(self):
        while not self._stop.is_set():
            if self.paused.is_set() or not self.engine.step():
                time.sleep(0.002)

    def kill(self):
        """The crash: stop stepping, fail in-flight requests (their
        streams end with the engine-shutdown error — replayable), close
        the socket."""
        self._stop.set()
        self.paused.clear()
        self._thread.join(timeout=10)
        self.engine.shutdown()
        self.server.shutdown()

    def close(self):
        self.kill()


def test_gateway_token_parity_and_wal(setup, tmp_path):
    """Requests through the gateway are TOKEN-IDENTICAL to independent
    generate() calls — greedy and seeded sampling — and the WAL records
    intent -> routed -> terminal for each."""
    cfg, params = setup
    rep = LiveReplica(cfg, params, str(tmp_path / "r0"))
    try:
        gw = make_gateway(tmp_path, rep)
        cases = [([5, 6, 7], GenerationConfig(max_new_tokens=5), 3),
                 ([9, 4], GenerationConfig(max_new_tokens=4,
                                           temperature=0.8, top_k=5), 11)]
        for prompt, gen, seed in cases:
            body = {"input_ids": prompt, "seed": seed,
                    "max_new_tokens": gen.max_new_tokens}
            if gen.temperature != 1.0 or gen.top_k:
                body.update(temperature=gen.temperature, top_k=gen.top_k)
            handle = gw.submit(body)
            assert handle.result() == reference_tokens(params, cfg, prompt,
                                                       gen, seed)
            assert handle.info == {"attempts": 1, "replays": 0,
                                   "hedges": 0}
        rows = journal_rows(str(tmp_path / "gw"))
        by_kind = {}
        for r in rows:
            by_kind.setdefault(r["kind"], []).append(r)
        assert len(by_kind["intent"]) == 2
        assert len(by_kind["routed"]) == 2
        assert [t["outcome"] for t in by_kind["terminal"]] == [
            "completed", "completed"]
        snap = gw.healthz()
        assert snap["requests_completed"] == 2
        assert snap["replicas_healthy"] == 1
        gw.close()
    finally:
        rep.close()


def test_gateway_http_stream_ids_and_errors(setup, tmp_path):
    """The gateway's own HTTP surface: streamed token lines with
    correlation ids on the first line, attempt accounting on the tail
    line, /healthz + /replicas, 400 on malformed bodies."""
    cfg, params = setup
    rep = LiveReplica(cfg, params, str(tmp_path / "r0"))
    server = None
    try:
        gw = make_gateway(tmp_path, rep)
        server = make_gateway_server(gw)
        port = server.server_address[1]
        threading.Thread(target=server.serve_forever, daemon=True).start()

        gen = GenerationConfig(max_new_tokens=4)
        resp = urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/generate",
            data=json.dumps({"input_ids": [5, 6, 7], "seed": 3,
                             "max_new_tokens": 4,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"}), timeout=120)
        assert resp.headers["X-Request-Id"].startswith("gw-")
        lines = [json.loads(l) for l in resp.read().splitlines()]
        assert lines[0]["request_id"] == resp.headers["X-Request-Id"]
        assert lines[0]["trace_id"] == resp.headers["X-Trace-Id"]
        tail = lines[-1]
        assert tail["done"] and tail["attempts"] == 1
        assert [l["token"] for l in lines[:-1]] == tail["tokens"]
        assert tail["tokens"] == reference_tokens(params, cfg, [5, 6, 7],
                                                  gen, 3)

        # non-stream: one JSON body, same parity
        body = json.load(urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/generate",
            data=json.dumps({"input_ids": [5, 6, 7], "seed": 3,
                             "max_new_tokens": 4}).encode()), timeout=120))
        assert body["tokens"] == tail["tokens"]

        snap = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10))
        assert snap["gateway"] == 1 and snap["requests_completed"] == 2
        reps = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/replicas", timeout=10))
        assert reps["r0"]["healthy"]

        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/generate",
                data=json.dumps({"input_ids": "nope"}).encode()),
                timeout=10)
        assert err.value.code == 400
        gw.close()
    finally:
        if server is not None:
            server.shutdown()
        rep.close()


def test_replay_splice_bitexact_after_midstream_kill(setup, tmp_path):
    """THE headline: a replica killed mid-stream -> the gateway replays
    the journalled request on the survivor, skips the delivered-token
    watermark, and the client's spliced stream is bit-identical to an
    uninterrupted generate(). Deterministic: replica a's loop is PAUSED
    after 3 tokens are delivered, then killed."""
    cfg, params = setup
    a = LiveReplica(cfg, params, str(tmp_path / "a"))
    b = LiveReplica(cfg, params, str(tmp_path / "b"))
    try:
        gw = make_gateway(tmp_path, a, b, watermark_every=2)
        gen = GenerationConfig(max_new_tokens=8)
        expected = reference_tokens(params, cfg, [5, 6, 7], gen, 3)

        handle = gw.submit({"input_ids": [5, 6, 7], "seed": 3,
                            "max_new_tokens": 8})
        it = handle.tokens()
        got = [next(it) for _ in range(3)]       # 3 tokens delivered...
        routed_to = [r["replica"] for r in
                     journal_rows(str(tmp_path / "gw"))
                     if r["kind"] == "routed"]
        victim = a if routed_to[0] == "a" else b
        victim.paused.set()                      # freeze mid-stream
        victim.kill()                            # ...then the crash
        got += list(it)                          # splice from the survivor

        assert got == expected, \
            "spliced stream diverged from the uninterrupted reference"
        assert handle.info["attempts"] == 2
        assert handle.info["replays"] == 1
        snap = gw.healthz()
        assert snap["requests_replayed"] == 1
        assert snap["requests_completed"] == 1
        # the survivor re-decoded the delivered prefix; the gateway
        # verified and suppressed those 3 tokens instead of duplicating
        assert snap["replay_skipped_tokens"] >= 3

        rows = journal_rows(str(tmp_path / "gw"))
        routed = [r for r in rows if r["kind"] == "routed"]
        assert len(routed) == 2 and len({r["replica"]
                                         for r in routed}) == 2
        marks = [r["delivered"] for r in rows if r["kind"] == "watermark"]
        assert marks and max(marks) >= 2         # watermark_every=2 rows
        term = [r for r in rows if r["kind"] == "terminal"]
        assert len(term) == 1                    # exactly-once outcome
        assert term[0]["outcome"] == "completed"
        assert term[0]["tokens"] == len(expected)
        assert term[0]["replays"] == 1
        gw.close()
    finally:
        a.close(), b.close()


def test_replay_attribution_lands_in_replica_trace(setup, tmp_path):
    """One trace_id joins the gateway WAL and both replicas' trace
    records; the survivor's record carries the gateway replay marker."""
    from llama_pipeline_parallel_tpu.serve.reqtrace import (
        RequestTraceRecorder,
    )

    cfg, params = setup
    outdir = str(tmp_path / "r0")
    rec = RequestTraceRecorder(outdir)
    rep = LiveReplica(cfg, params, outdir, reqtrace=rec)
    try:
        gw = make_gateway(tmp_path, rep)
        handle = gw.submit({"input_ids": [5, 6], "seed": 1,
                            "max_new_tokens": 3})
        handle.result()
        rep.engine.drain(timeout_s=60)
        rec.close()
        with open(os.path.join(outdir, "request_trace.jsonl")) as f:
            traces = [json.loads(l) for l in f]
        match = [t for t in traces
                 if t["trace_id"] == handle.trace.trace_id]
        assert match, "replica trace did not join the gateway trace id"
        assert match[0]["request_id"] == f"{handle.gid}.a1"
        assert match[0]["gateway"] == {"attempt": 1, "replay": False,
                                       "hedge": False}
        gw.close()
    finally:
        rep.close()


# -- fleet rollup + reports ---------------------------------------------------


def test_fleet_rollup_and_report_surface_gateway(tmp_path, capsys):
    """A gateway member's `"gateway": 1` metrics lines roll up into the
    fleet status (utils/fleet._GATEWAY_FIELDS) and render in
    fleet_report's gateway-tier table."""
    import fleet_report  # tools/ on sys.path via conftest
    from llama_pipeline_parallel_tpu.utils.fleet import FleetAggregator

    root = str(tmp_path / "fleet")
    os.makedirs(root)
    out = str(tmp_path / "gw")
    os.makedirs(out)
    fleet.register_member(root, output_dir=out, role="gateway",
                          replica="gw0", pid=os.getpid())
    with open(os.path.join(out, "health.json"), "w") as f:
        json.dump({"time": time.time(), "role": "gateway"}, f)
    with open(os.path.join(out, "metrics.jsonl"), "w") as f:
        f.write(json.dumps({"step": 1, "gateway": 1, "requests_routed": 9,
                            "requests_replayed": 2, "requests_hedged": 1,
                            "hedge_wins": 1, "wasted_hedge_tokens": 4,
                            "ttft_p95_ms": 12.5, "replicas_known": 2,
                            "replicas_healthy": 2,
                            "inflight_total": 0}) + "\n")
    status = FleetAggregator(root).refresh()
    m = status["members"]["gateway:gw0"]
    assert m["requests_routed"] == 9
    assert m["requests_replayed"] == 2
    assert m["ttft_p95_ms"] == 12.5

    rep = fleet_report.build_report(root)
    assert rep["gateway_table"][0]["requests_routed"] == 9
    fleet_report.print_report(rep)
    printed = capsys.readouterr().out
    assert "gateway tier" in printed
    assert "requests_replayed=2" in printed
    assert "replicas=2/2 healthy" in printed


def test_request_report_joins_gateway_wal(tmp_path, capsys):
    """request_report --gateway joins WAL rows to replica trace records
    by trace_id and renders the dispatch waterfall with the replay
    attempt marked."""
    import request_report  # tools/ on sys.path via conftest

    gw_dir = str(tmp_path / "gw")
    j = GatewayJournal(gw_dir)
    j.intent("g1", "tr-1", {"input_ids": [1], "seed": 0})
    j.routed("g1", "a", 1)
    j.watermark("g1", 3)
    j.routed("g1", "b", 2)
    j.terminal("g1", "completed", tokens=6, replays=1, hedges=0)
    j.intent("g2", "tr-2", {"input_ids": [2], "seed": 0})
    j.close()
    replica_dir = str(tmp_path / "replica")
    os.makedirs(replica_dir)
    with open(os.path.join(replica_dir, "request_trace.jsonl"), "w") as f:
        f.write(json.dumps({"request_id": "g1.a1", "trace_id": "tr-1",
                            "outcome": "failed", "tokens": 3,
                            "gateway": {"attempt": 1, "replay": False,
                                        "hedge": False}}) + "\n")
        f.write(json.dumps({"request_id": "g1.a2", "trace_id": "tr-1",
                            "outcome": "completed", "tokens": 6,
                            "ttft_s": 0.02,
                            "gateway": {"attempt": 2, "replay": True,
                                        "hedge": False}}) + "\n")

    rep = request_report.build_report(replica_dir, gateway_dir=gw_dir)
    gw = rep["gateway"]
    assert gw["requests"] == 2
    assert gw["outcomes"] == {"completed": 1}
    assert gw["replayed"] == 1 and gw["orphans"] == 1
    assert gw["joined"] == 1
    lines = request_report.gateway_waterfall(gw["exemplar"]["wal"],
                                             gw["exemplar"]["records"])
    text = "\n".join(lines)
    assert "attempt 2 replay -> b" in text
    assert "replica outcome=completed" in text
    request_report.main([replica_dir, "--gateway", gw_dir])
    printed = capsys.readouterr().out
    assert "gateway join (2 journalled request(s))" in printed
    assert "1 replayed" in printed


def test_serve_traffic_gateway_mode(tmp_path):
    """serve_traffic --gateway replays the SAME poisson trace over HTTP
    (no new RNG draws) and reports attempt/replay counts; parse_chaos and
    kill_replica degrade sanely."""
    import serve_traffic  # tools/ on sys.path via conftest

    a = FakeReplica(str(tmp_path / "a"),
                    lambda body, n: {"tokens": [1, 2]})
    try:
        gw = make_gateway(tmp_path, a)
        server = make_gateway_server(gw)
        port = server.server_address[1]
        threading.Thread(target=server.serve_forever, daemon=True).start()

        trace_reqs = serve_traffic.poisson_trace(
            0, 50.0, 4, serve_traffic.parse_mix("4"),
            serve_traffic.parse_mix("2"))
        summary = serve_traffic.run_trace_gateway(
            f"http://127.0.0.1:{port}", trace_reqs, vocab=32,
            collect_tokens=True)
        assert summary["requests"] == 4 and summary["completed"] == 4
        assert summary["attempts_total"] == 4
        assert summary["replayed"] == 0
        assert summary["tokens"] == [[1, 2]] * 4
        assert summary["gateway"]["requests_routed"] == 4
        # the fake replica got the trace's own seeds — same stream as the
        # in-process mode would submit
        seeds = sorted(r["seed"] for r in a.requests)
        assert seeds == sorted(tr.seed for tr in trace_reqs)

        assert serve_traffic.parse_chaos("kill:2.5") == ("kill", 2.5)
        with pytest.raises(ValueError):
            serve_traffic.parse_chaos("explode:1")
        assert serve_traffic.kill_replica(str(tmp_path / "nope")) is None
        server.shutdown()
        gw.close()
    finally:
        a.close()


# -- the chaos acceptance drill ----------------------------------------------


@pytest.mark.slow  # ~60 s of real process spawns/kills — the heavyweight
# failover leg: supervised subprocess replicas, a gateway process tier,
# Poisson load and a SIGKILL racing the watchdog relaunch
def test_chaos_acceptance_sigkill_vs_replay(setup, tmp_path):
    """2 supervised serve replicas behind a gateway; Poisson traffic via
    serve_traffic --gateway; one replica SIGKILLed mid-run while the
    watchdog relaunch races the gateway's replay. Every accepted request
    gets exactly one WAL terminal, nothing is dropped or duplicated, and
    every completed stream is token-identical to its reference.

    The references are collected from an UNINTERRUPTED replica before the
    chaos run (which also warms both replicas' compile caches so the
    SIGKILL lands mid-stream, not mid-compile). A replica process is the
    right oracle for the cross-process contract: XLA compiles the serve
    path and a driver-side generate() differently, and on this tiny
    random-init model the float drift is enough to flip greedy argmax
    near-ties — engine==generate() parity is pinned in-process by
    test_gateway_token_parity_and_wal instead."""
    import serve_traffic
    import supervisor  # tools/ on sys.path via conftest
    from llama_pipeline_parallel_tpu.ckpt.checkpoint import CheckpointManager
    from llama_pipeline_parallel_tpu.models.llama.manifest import (
        StageManifest,
    )
    from llama_pipeline_parallel_tpu.parallel.pipeline import stack_stages

    cfg, params = setup
    ckpt = str(tmp_path / "ckpt")
    manifest = StageManifest.for_config(cfg, 1)
    CheckpointManager(ckpt).save(0, stack_stages(params, manifest),
                                 manifest, cfg)

    replicas, sups, threads = {}, {}, {}
    gw = None
    gw_server = None
    try:
        for name in ("a", "b"):
            out = str(tmp_path / name)
            cmd = [sys.executable, os.path.join(REPO, "tools", "serve.py"),
                   "--checkpoint_dir", ckpt, "--output_dir", out,
                   "--host", "127.0.0.1", "--port", "0",
                   "--platform", "cpu", "--max_slots", "2",
                   "--max_len", "320", "--buckets", "8",
                   "--page_size", "8", "--metrics_every", "1"]
            env = dict(os.environ)
            # stretch decode so the SIGKILL lands mid-stream
            env["LPT_SERVE_STEP_DELAY_S"] = "0.05"
            sup = supervisor.Supervisor(cmd, supervisor.SupervisorConfig(
                output_dir=out, max_restarts=3, hang_timeout_s=300.0,
                grace_s=5.0, crash_loop_threshold=3,
                crash_loop_window_s=0.0, poll_s=0.1), env=env)
            t = threading.Thread(target=sup.run, daemon=True)
            t.start()
            replicas[name], sups[name], threads[name] = out, sup, t
        info = {name: _wait_for_replica(replicas[name])
                for name in ("a", "b")}

        # Reference pass: serve every trace request once, uninterrupted,
        # straight to replica b — its streams are the oracle the chaos
        # run must reproduce. One request also goes to replica a so both
        # compile caches are warm before the kill timer starts (a cold
        # replica spends the first seconds compiling and the SIGKILL
        # would land mid-compile, producing retries instead of
        # mid-stream replays) and so replica equivalence is pinned.
        trace_reqs = serve_traffic.poisson_trace(
            7, 4.0, 10, serve_traffic.parse_mix("5"),
            serve_traffic.parse_mix("24"))
        bodies = []
        for tr in trace_reqs:
            prompt = np.random.RandomState(tr.seed).randint(
                3, cfg.vocab_size, size=tr.prompt_len).tolist()
            bodies.append({"input_ids": prompt, "seed": tr.seed,
                          "max_new_tokens": tr.max_new_tokens})
        refs = [_post_replica(info["b"]["port"], body)
                for body in bodies]
        assert all(len(r) == 24 for r in refs)
        assert _post_replica(info["a"]["port"], bodies[0]) == refs[0], \
            "replicas a and b disagree on an uninterrupted stream"

        gw = Gateway(str(tmp_path / "gw"), ReplicaDirectory(
            replica_dirs=(replicas["a"], replicas["b"]), stale_s=60.0,
            probe_every_s=0.2),
            policy=RetryPolicy(max_attempts=6, base_delay_s=0.05,
                               max_delay_s=0.5),
            route_wait_s=60.0, request_timeout_s=300.0)
        gw_server = make_gateway_server(gw)
        port = gw_server.server_address[1]
        threading.Thread(target=gw_server.serve_forever,
                         daemon=True).start()

        victim = replicas["a"]
        summary = serve_traffic.run_trace_gateway(
            f"http://127.0.0.1:{port}", trace_reqs,
            vocab=cfg.vocab_size, collect_tokens=True,
            result_timeout_s=240.0, chaos=("kill", 1.0),
            chaos_target=victim)

        # exactly-once: every request got a 200 and exactly one terminal
        assert summary["completed"] == 10, summary
        assert summary["failed"] == 0
        rows = journal_rows(str(tmp_path / "gw"))
        terms = [r for r in rows if r["kind"] == "terminal"]
        intents = [r for r in rows if r["kind"] == "intent"]
        assert len(intents) == 10
        assert sorted(t["gid"] for t in terms) == sorted(
            i["gid"] for i in intents)         # one terminal per intent
        assert all(t["outcome"] == "completed" for t in terms)

        # bit-exact: every chaos-run stream — including the spliced ones
        # that crossed a replica death — equals the uninterrupted serve
        # of the same request
        for tr, ref, tokens in zip(trace_reqs, refs, summary["tokens"]):
            assert tokens == ref, \
                f"request seed={tr.seed} diverged after the chaos kill"

        # the kill actually produced a mid-stream replay: replicas are
        # warm, request 0 lands on a at t=0 and streams 24 tokens over
        # ~1.3 s, so the SIGKILL at 1.0 s catches it with a non-empty
        # delivered watermark — the summary must report a replay, not
        # just a pre-first-token retry
        assert summary["replayed"] >= 1, summary
        assert summary["attempts_total"] > summary["requests"], summary
    finally:
        if gw_server is not None:
            gw_server.shutdown()
        if gw is not None:
            gw.close()
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


def _post_replica(port: int, body: dict, timeout_s: float = 120.0) -> list:
    """Non-stream POST straight to a replica frontend; returns tokens."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps(dict(body, stream=False)).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout_s) as resp:
        return json.loads(resp.read())["tokens"]


def _wait_for_replica(out_dir: str, timeout_s: float = 120.0) -> dict:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            with open(os.path.join(out_dir, "serve.json")) as f:
                info = json.load(f)
            urllib.request.urlopen(
                f"http://127.0.0.1:{info['port']}/healthz", timeout=5)
            return info
        except Exception:
            time.sleep(0.25)
    raise TimeoutError(f"no live replica in {out_dir} within {timeout_s}s")
