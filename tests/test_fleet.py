"""Fleet observatory (utils/fleet.py + tools/fleetd.py +
tools/fleet_report.py — docs/OBSERVABILITY.md "Fleet").

Fast lanes: the registry contract, the incremental tailer's read-bytes
bound (no full-file re-reads — the aggregator scales with bytes WRITTEN,
not bytes accumulated), alert firing/resolved edges + the cross-process
capture trigger, atomic fleet_status.json, the live HTTP endpoint, the
supervisor's own heartbeat + registration, and the offline report's
degrade grid. The kill-a-replica chaos e2e lives in test_fleet_e2e.py."""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from llama_pipeline_parallel_tpu.utils import fleet
from llama_pipeline_parallel_tpu.utils.fleet import (
    AlertRules,
    FileWatcher,
    FleetAggregator,
    JsonlTailer,
    latest_verified_step,
    load_registry,
    read_alerts,
    register_member,
)


def write_lines(path, rows, mode="a"):
    with open(path, mode) as f:
        for row in rows:
            f.write((row if isinstance(row, str) else json.dumps(row)) + "\n")


def make_member(fleet_root, out_root, name, role=None, health=None,
                metrics=(), incarnations=(), reg_ts=None,
                health_file="health.json"):
    """One fake fleet member: a registry row + its run-dir artifacts."""
    out = os.path.join(str(out_root), name)
    os.makedirs(out, exist_ok=True)
    row = {"ts": reg_ts if reg_ts is not None else time.time(), "role": role,
           "replica": name, "output_dir": os.path.abspath(out), "pid": 1234,
           "incarnation": 0, "health_file": health_file}
    write_lines(os.path.join(str(fleet_root), fleet.REGISTRY_NAME), [row])
    if health is not None:
        with open(os.path.join(out, health_file), "w") as f:
            json.dump(health, f)
    if metrics:
        write_lines(os.path.join(out, "metrics.jsonl"), list(metrics))
    if incarnations:
        write_lines(os.path.join(out, "incarnations.jsonl"),
                    list(incarnations))
    return out


def write_ckpt(out, step, complete=True):
    d = os.path.join(out, f"checkpoint-{step}")
    os.makedirs(d, exist_ok=True)
    if complete:
        with open(os.path.join(d, "meta.json"), "w") as f:
            json.dump({"step": step}, f)


# ---------------------------------------------------------------------------
# registry contract
# ---------------------------------------------------------------------------

def test_register_member_appends_and_loads(tmp_path):
    row = register_member(str(tmp_path), output_dir=str(tmp_path / "a"),
                          role="serve", pid=42, incarnation=1)
    assert row["replica"] == "a" and row["health_file"] == "health.json"
    register_member(str(tmp_path), output_dir=str(tmp_path / "a"),
                    role="serve", pid=43, incarnation=2, layout="dp1")
    # a torn tail degrades, never tracebacks
    with open(tmp_path / fleet.REGISTRY_NAME, "a") as f:
        f.write('{"output_dir": "/torn')
    rows = load_registry(str(tmp_path))
    assert len(rows) == 2
    assert rows[1]["pid"] == 43 and rows[1]["layout"] == "dp1"


def test_latest_verified_step_requires_meta(tmp_path):
    out = str(tmp_path)
    assert latest_verified_step(out) is None
    write_ckpt(out, 2)
    write_ckpt(out, 6, complete=False)  # arrays landed, no meta commit yet
    assert latest_verified_step(out) == 2
    write_ckpt(out, 6)
    assert latest_verified_step(out) == 6
    assert latest_verified_step(str(tmp_path / "missing")) is None


# ---------------------------------------------------------------------------
# incremental readers: the read-bytes bound
# ---------------------------------------------------------------------------

def test_tailer_reads_each_byte_exactly_once(tmp_path):
    """THE incremental contract: across any number of polls, the tailer
    reads exactly the bytes ever written — never the file again from the
    start. This is what keeps a fleetd refresh O(new data) while
    metrics.jsonl grows without bound."""
    path = str(tmp_path / "m.jsonl")
    t = JsonlTailer(path)
    assert t.poll() == []                       # missing file: no read
    write_lines(path, [{"step": i} for i in range(50)])
    size1 = os.path.getsize(path)
    assert [r["step"] for r in t.poll()] == list(range(50))
    assert t.bytes_read == size1
    assert t.poll() == [] and t.bytes_read == size1   # idle poll: 0 bytes
    write_lines(path, [{"step": 50}])
    size2 = os.path.getsize(path)
    assert [r["step"] for r in t.poll()] == [50]
    # the bound the ISSUE pins: total bytes read == total bytes written
    assert t.bytes_read == size2


def test_tailer_carries_torn_tail_until_completed(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with open(path, "w") as f:
        f.write('{"a": 1}\n{"b": 2')           # writer mid-append
    t = JsonlTailer(path)
    assert t.poll() == [{"a": 1}]              # the tear is carried, not lost
    with open(path, "a") as f:
        f.write('2}\n')                        # writer finishes the line
    assert t.poll() == [{"b": 22}]
    # garbage lines skip without losing later rows (read_jsonl semantics)
    write_lines(path, ["not json", '{"c": 3}'])
    assert t.poll() == [{"c": 3}]


def test_tailer_resets_on_truncation(tmp_path):
    path = str(tmp_path / "m.jsonl")
    write_lines(path, [{"a": 1}, {"a": 2}])
    t = JsonlTailer(path)
    assert len(t.poll()) == 2
    write_lines(path, [{"b": 1}], mode="w")    # rotated/truncated under us
    assert t.poll() == [{"b": 1}]


def test_filewatcher_rereads_only_on_change(tmp_path):
    path = str(tmp_path / "health.json")
    w = FileWatcher(path)
    assert w.poll() is None and w.status == "missing"
    with open(path, "w") as f:
        json.dump({"time": 1.0}, f)
    assert w.poll() == {"time": 1.0} and w.status == "ok"
    n = w.bytes_read
    assert w.poll() == {"time": 1.0}
    assert w.bytes_read == n                   # unchanged stat: zero reads
    # a torn rewrite keeps the last good value, flags corrupt
    with open(path, "w") as f:
        f.write('{"time": 2')
    assert w.poll() == {"time": 1.0} and w.status == "corrupt"


# ---------------------------------------------------------------------------
# alert rules
# ---------------------------------------------------------------------------

def test_alert_rules_reject_unknown_keys():
    with pytest.raises(ValueError, match="unknown alerts"):
        AlertRules.from_cfg({"heartbeat_stale": 3})
    with pytest.raises(ValueError, match="mapping"):
        AlertRules.from_cfg(7)
    rules = AlertRules.from_cfg({"heartbeat_stale_s": 30,
                                 "checkpoint_lag_steps": 4})
    assert rules.heartbeat_stale_s == 30.0
    assert rules.checkpoint_lag_steps == 4
    assert rules.ttft_p95_ms is None
    assert AlertRules.from_cfg(None) == AlertRules()


def test_alert_rules_evaluate_role_and_absence():
    rules = AlertRules(heartbeat_stale_s=10, goodput_floor=0.5,
                       ttft_p95_ms=200, checkpoint_lag_steps=2,
                       nonfinite_steps=0, step_time_p95_s=1.0)
    # a rule whose input is absent is NOT evaluated (no fabricated edges)
    out = rules.evaluate({"role": "serve", "heartbeat_age_s": 3})
    assert out == [("heartbeat_stale", 3.0, 10.0, False)]
    fired = dict((r[0], r[3]) for r in rules.evaluate(
        {"role": "serve", "heartbeat_age_s": 30, "goodput": 0.2,
         "ttft_p95_ms": 500, "checkpoint_lag": 5}))
    assert fired == {"heartbeat_stale": True, "goodput_floor": True,
                     "ttft_p95": True, "checkpoint_lag": True}
    trainer = dict((r[0], r[3]) for r in rules.evaluate(
        {"role": "trainer", "heartbeat_age_s": 1, "goodput": 0.9,
         "step_time_p95": 2.0, "nonfinite_steps": 1}))
    assert trainer == {"heartbeat_stale": False, "goodput_floor": False,
                       "step_time_p95": True, "nonfinite_steps": True}
    # the supervisor's goodput (it has none) is never judged
    assert rules.evaluate({"role": "supervisor", "heartbeat_age_s": 1,
                           "goodput": None}) == \
        [("heartbeat_stale", 1.0, 10.0, False)]


def test_alert_rules_per_tenant_ttft_fanout():
    """One configured `tenant_ttft_p95_ms` threshold fans out to a rule
    INSTANCE per tenant in the member's serving snapshot
    (`tenant_ttft_p95:<tenant>`), all sharing the base rule's threshold
    and damping — the ':' suffix is instance identity, not config."""
    rules = AlertRules(tenant_ttft_p95_ms=100.0,
                       damping={"tenant_ttft_p95": (5.0, 10.0)})
    out = rules.evaluate({"role": "serve", "tenants": {
        "free": {"ttft_p95_ms": 250.0},
        "paid": {"ttft_p95_ms": 40.0},
        "torn": "not a snapshot",          # tolerated, not evaluated
        "silent": {"requests_completed": 3}}})   # no ttft yet: absent
    assert dict((r[0], r[3]) for r in out) == \
        {"tenant_ttft_p95:free": True, "tenant_ttft_p95:paid": False}
    assert all(r[2] == 100.0 for r in out)
    assert rules.damping_for("tenant_ttft_p95:free") == (5.0, 10.0)
    # no threshold configured -> the tenants map is never judged
    assert AlertRules().evaluate(
        {"role": "serve", "tenants": {"free": {"ttft_p95_ms": 9e9}}}) == []


# ---------------------------------------------------------------------------
# the aggregator
# ---------------------------------------------------------------------------

def make_fleet(tmp_path, trainer_step_time=0.1):
    """One trainer (2 checkpoints, metrics, incarnations) + one serve
    replica (serving metrics, checkpoint_step) + its supervisor member."""
    root = str(tmp_path / "fleet")
    os.makedirs(root, exist_ok=True)
    now = time.time()
    trainer = make_member(
        root, tmp_path, "trainer0",
        health={"time": now, "last_step": 8, "goodput": 0.9,
                "clock": {"elapsed": 100.0},
                "topology": {"layout": "pp2dp2"}},
        metrics=[{"step": s, "loss": 2.0, "step_time": trainer_step_time,
                  "bubble_fraction": 0.05,
                  "nonfinite_steps": 0, "anomaly_count": 1}
                 for s in range(1, 9)],
        incarnations=[{"incarnation": 0, "outcome": "crash",
                       "duration_s": 5.0, "start": now - 60, "end": now - 55},
                      {"incarnation": 1, "outcome": None, "start": now - 50}])
    write_ckpt(trainer, 4)
    write_ckpt(trainer, 8)
    serve = make_member(
        root, tmp_path, "serve0", role="serve",
        health={"time": now, "last_step": 30, "goodput": 0.6, "role": "serve",
                "checkpoint_step": 4, "clock": {"elapsed": 50.0}},
        metrics=[{"step": 16, "serving": 1, "requests_completed": 16,
                  "ttft_p95_ms": 120.0, "tpot_p50_ms": 30.0,
                  "queue_wait_p95_ms": 15.0, "slo_breaches": 2,
                  "requests_page_refused": 3, "pages_used": 5,
                  "pages_free": 11, "prefill_chunks_total": 7,
                  "prefill_tokens_total": 448}],
        incarnations=[{"incarnation": 0, "outcome": "crash",
                       "duration_s": 3.0, "start": now - 40,
                       "end": now - 37}])
    make_member(root, tmp_path, "serve0", role="supervisor",
                health={"time": now, "role": "supervisor", "restarts": 1,
                        "consecutive_failures": 0, "child_pid": 777},
                health_file="supervisor_health.json")
    return root, trainer, serve


def test_aggregator_composes_fleet_status(tmp_path):
    root, trainer_dir, serve_dir = make_fleet(tmp_path)
    agg = FleetAggregator(root)
    status = agg.refresh()

    assert set(status["members"]) == {"trainer:trainer0", "serve:serve0",
                                      "supervisor:serve0"}
    tr = status["members"]["trainer:trainer0"]
    assert tr["last_step"] == 8 and tr["goodput"] == 0.9
    assert tr["latest_verified_step"] == 8
    # the percentiles are the tailed metrics step_time stream's
    assert tr["step_time_p50"] == pytest.approx(0.1)
    assert tr["step_time_p95"] == pytest.approx(0.1)
    assert tr["bubble_fraction_analytic"] == 0.05
    assert "bubble_fraction_measured" not in tr
    assert tr["anomaly_count"] == 1 and tr["nonfinite_steps"] == 0
    assert tr["incarnations"] == 2 and tr["restarts"] == 1
    assert tr["failed_incarnations"] == 1
    assert tr["heartbeat_age_s"] < 5

    sv = status["members"]["serve:serve0"]
    assert sv["checkpoint_step"] == 4
    assert sv["checkpoint_lag"] == 4          # trainer verified 8, loaded 4
    assert sv["ttft_p95_ms"] == 120.0 and sv["slo_breaches"] == 2
    assert sv["requests_page_refused"] == 3 and sv["pages_free"] == 11
    assert sv["prefill_chunks_total"] == 7

    sup = status["members"]["supervisor:serve0"]
    assert sup["role"] == "supervisor" and sup["restarts"] == 1
    assert sup["child_pid"] == 777
    # the watchdog shares its child's dir but must NOT mirror the child's
    # streams: no serve SLO fields re-attributed to it (a ttft rule would
    # otherwise fire twice), no ledger rows double-counted
    assert "ttft_p95_ms" not in sup and "slo_breaches" not in sup
    assert "incarnations" not in sup

    pod = status["pod"]
    assert pod["trainer_step"] == 8 and pod["members"] == 3
    # elapsed-weighted: (0.9*100 + 0.6*50) / 150
    assert pod["goodput"] == pytest.approx(0.8)
    assert pod["alerts_firing"] == []

    # the status file landed atomically and parses
    with open(os.path.join(root, fleet.STATUS_NAME)) as f:
        on_disk = json.load(f)
    assert on_disk["refresh_count"] == 1
    assert on_disk["members"]["serve:serve0"]["checkpoint_lag"] == 4


def test_aggregator_refreshes_are_incremental(tmp_path):
    """The acceptance bound: a refresh against an IDLE fleet reads zero
    stream bytes, and a refresh after appends reads only the appended
    bytes — pinned via the aggregator's own byte counter."""
    root, trainer_dir, _ = make_fleet(tmp_path)
    agg = FleetAggregator(root)
    agg.refresh()
    first = agg.bytes_read
    status = agg.refresh()
    assert status["bytes_read_last_refresh"] == 0   # idle: stats only
    appended = [{"step": 9, "loss": 1.9, "step_time": 0.2}]
    before = os.path.getsize(os.path.join(trainer_dir, "metrics.jsonl"))
    write_lines(os.path.join(trainer_dir, "metrics.jsonl"), appended)
    after = os.path.getsize(os.path.join(trainer_dir, "metrics.jsonl"))
    status = agg.refresh()
    assert status["bytes_read_last_refresh"] == after - before
    assert agg.bytes_read == first + (after - before)


def test_alert_edges_fire_resolve_and_drop_one_trigger(tmp_path):
    root = str(tmp_path / "fleet")
    os.makedirs(root)
    now = time.time()
    out = make_member(root, tmp_path, "serveA", role="serve",
                      health={"time": now - 100, "role": "serve"},
                      reg_ts=now - 100)
    rules = AlertRules(heartbeat_stale_s=30.0)
    agg = FleetAggregator(root, rules)

    status = agg.refresh()
    assert status["pod"]["alerts_firing"] == ["heartbeat_stale:serve:serveA"]
    trigger = os.path.join(out, fleet.CAPTURE_TRIGGER_NAME)
    assert os.path.exists(trigger)
    with open(trigger) as f:
        payload = json.load(f)
    assert payload["alert"] == "heartbeat_stale"

    # still firing: NO second edge, and an unconsumed trigger not re-dropped
    os_stat = os.stat(trigger).st_mtime_ns
    status = agg.refresh()
    assert status["alert_edges_last_refresh"] == []
    assert os.stat(trigger).st_mtime_ns == os_stat

    # the member comes back: resolved edge, exactly two edges on disk
    with open(os.path.join(out, "health.json"), "w") as f:
        json.dump({"time": time.time(), "role": "serve"}, f)
    status = agg.refresh()
    edges = read_alerts(root)
    assert [e["state"] for e in edges] == ["firing", "resolved"]
    assert edges[0]["member"] == "serve:serveA"
    assert status["pod"]["alerts_firing"] == []
    assert status["alerts"]["heartbeat_stale:serve:serveA"]["state"] == \
        "resolved"


def test_checkpoint_lag_alert_fires_and_resolves(tmp_path):
    root = str(tmp_path / "fleet")
    os.makedirs(root)
    now = time.time()
    trainer = make_member(root, tmp_path, "t0",
                          health={"time": now, "last_step": 10})
    write_ckpt(trainer, 10)
    serve = make_member(root, tmp_path, "s0", role="serve",
                        health={"time": now, "role": "serve",
                                "checkpoint_step": 2})
    agg = FleetAggregator(root, AlertRules(checkpoint_lag_steps=4))
    status = agg.refresh()
    assert status["members"]["serve:s0"]["checkpoint_lag"] == 8
    assert status["pod"]["alerts_firing"] == ["checkpoint_lag:serve:s0"]
    # the serve tier tails the newer verified checkpoint -> resolved
    with open(os.path.join(serve, "health.json"), "w") as f:
        json.dump({"time": time.time(), "role": "serve",
                   "checkpoint_step": 10}, f)
    agg.refresh()
    assert [e["state"] for e in read_alerts(root)] == ["firing", "resolved"]


def test_garbage_registry_row_skipped_not_fatal(tmp_path):
    """A parseable-but-wrong registry line (no output_dir) must degrade
    like a torn one — never a KeyError out of the daemon's refresh."""
    root = str(tmp_path / "fleet")
    os.makedirs(root)
    make_member(root, tmp_path, "ok", health={"time": time.time()})
    write_lines(os.path.join(root, fleet.REGISTRY_NAME),
                [{"note": "not a member"}, "plain garbage"])
    status = FleetAggregator(root).refresh()
    assert sorted(status["members"]) == ["trainer:ok"]


def test_replica_name_collision_keeps_alerts_distinct(tmp_path):
    """Two dirs with the same basename and no --replica label: member ids
    disambiguate ONCE (status map, alert rollup, and edge rows all agree),
    so one replica's resolution can never mask the other's firing."""
    root = str(tmp_path / "fleet")
    os.makedirs(root)
    old = time.time() - 100
    for sub in ("x", "y"):
        out = os.path.join(str(tmp_path), sub, "serve")
        os.makedirs(out)
        write_lines(os.path.join(root, fleet.REGISTRY_NAME),
                    [{"ts": old, "role": "serve", "replica": "serve",
                      "output_dir": out, "health_file": "health.json"}])
        with open(os.path.join(out, "health.json"), "w") as f:
            json.dump({"time": old, "role": "serve"}, f)
    agg = FleetAggregator(root, AlertRules(heartbeat_stale_s=30.0))
    status = agg.refresh()
    assert sorted(status["members"]) == ["serve:serve", "serve:serve+"]
    assert sorted(status["pod"]["alerts_firing"]) == [
        "heartbeat_stale:serve:serve", "heartbeat_stale:serve:serve+"]
    assert sorted(e["member"] for e in read_alerts(root)) == [
        "serve:serve", "serve:serve+"]


def test_registration_vouches_liveness_before_first_health(tmp_path):
    """A just-launched member with a STALE health.json from its previous
    incarnation must not be declared stale: the fresh registry row vouches
    for it, the supervisor's own staleness rule."""
    root = str(tmp_path / "fleet")
    os.makedirs(root)
    make_member(root, tmp_path, "m0",
                health={"time": time.time() - 1000},  # dead incarnation's
                reg_ts=time.time())                   # fresh relaunch
    agg = FleetAggregator(root, AlertRules(heartbeat_stale_s=30.0))
    status = agg.refresh()
    assert status["pod"]["alerts_firing"] == []
    assert status["members"]["trainer:m0"]["heartbeat_age_s"] < 5


# ---------------------------------------------------------------------------
# cross-process capture trigger (the profiler side)
# ---------------------------------------------------------------------------

def test_trigger_file_starts_exactly_one_capture(tmp_path):
    import glob

    from llama_pipeline_parallel_tpu.utils.profiler import (
        CaptureConfig,
        TriggeredProfiler,
    )

    out = str(tmp_path)
    prof = TriggeredProfiler(
        CaptureConfig(zscore=0.0, window_steps=1, trigger_poll_s=0.0), out)
    prof.observe_step(1, 0.01)
    assert prof.captures_taken == 0            # no trigger file: no capture
    fleet.write_json_atomic(os.path.join(out, fleet.CAPTURE_TRIGGER_NAME),
                            {"alert": "heartbeat_stale", "member": "x"})
    prof.observe_step(2, 0.01)
    assert prof.capturing and prof.captures_taken == 1
    assert not os.path.exists(
        os.path.join(out, fleet.CAPTURE_TRIGGER_NAME))  # consumed
    prof.observe_step(3, 0.01)                 # window closes
    prof.observe_step(4, 0.01)
    assert not prof.capturing and prof.captures_taken == 1  # exactly one
    dirs = glob.glob(os.path.join(out, "captures", "*"))
    assert len(dirs) == 1 and "fleet_heartbeat_stale" in dirs[0]
    prof.close()


def test_trigger_file_respects_retention_cap_and_garbage(tmp_path):
    from llama_pipeline_parallel_tpu.utils.profiler import (
        CaptureConfig,
        TriggeredProfiler,
    )

    out = str(tmp_path)
    prof = TriggeredProfiler(
        CaptureConfig(zscore=0.0, window_steps=1, max_captures=1,
                      trigger_poll_s=0.0), out)
    prof.captures_taken = 1                    # cap already reached
    path = os.path.join(out, fleet.CAPTURE_TRIGGER_NAME)
    with open(path, "w") as f:
        f.write("not json")                    # garbage trigger: still consumed
    prof.observe_step(1, 0.01)
    assert not prof.capturing and not os.path.exists(path)
    prof.close()


# ---------------------------------------------------------------------------
# the supervisor's registration + own heartbeat
# ---------------------------------------------------------------------------

def test_supervisor_registers_and_heartbeats(tmp_path):
    import sys

    import supervisor  # tools/ on sys.path via conftest

    out = str(tmp_path / "run")
    root = str(tmp_path / "fleet")
    sup = supervisor.Supervisor(
        [sys.executable, "-c", "pass"],
        supervisor.SupervisorConfig(output_dir=out, max_restarts=1,
                                    poll_s=0.05, fleet_root=root,
                                    role="serve", replica="r0"))
    assert sup.run() == 0
    rows = load_registry(root)
    # the supervisor member + incarnation 0's launch row
    roles = [(r["role"], r["health_file"]) for r in rows]
    assert (("supervisor", fleet.SUPERVISOR_HEALTH_NAME) in roles)
    launch = [r for r in rows if r["role"] == "serve"]
    assert len(launch) == 1 and launch[0]["incarnation"] == 0
    assert launch[0]["replica"] == "r0" and launch[0]["pid"]
    with open(os.path.join(out, fleet.SUPERVISOR_HEALTH_NAME)) as f:
        health = json.load(f)
    assert health["role"] == "supervisor"
    assert health["last_outcome"] == "clean"
    assert health["restarts"] == 0 and health["consecutive_failures"] == 0


def test_supervisor_heartbeat_without_fleet_root(tmp_path):
    """The watchdog heartbeat is unconditional (its staleness is fleet
    business, but labeling the dir is the goodput report's too)."""
    import sys

    import goodput_report
    import supervisor

    out = str(tmp_path)
    sup = supervisor.Supervisor(
        [sys.executable, "-c", "import sys; sys.exit(3)"],
        supervisor.SupervisorConfig(output_dir=out, max_restarts=0,
                                    poll_s=0.05, crash_loop_threshold=9))
    assert sup.run() == 2
    with open(os.path.join(out, fleet.SUPERVISOR_HEALTH_NAME)) as f:
        health = json.load(f)
    assert health["last_outcome"] == "crash"
    assert health["consecutive_failures"] == 1
    summary = goodput_report.supervisor_summary(out)
    assert summary["last_outcome"] == "crash"
    assert summary["consecutive_failures"] == 1


# ---------------------------------------------------------------------------
# fleetd: the live endpoint
# ---------------------------------------------------------------------------

def test_fleetd_http_endpoint(tmp_path):
    import fleetd  # tools/ on sys.path via conftest

    root, _, _ = make_fleet(tmp_path)
    agg = FleetAggregator(root)
    server = fleetd.make_server(agg)
    port = server.server_address[1]
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()

    def get(path):
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=5) as r:
                return r.status, json.load(r)
        except urllib.error.HTTPError as e:
            return e.code, json.load(e)

    try:
        # before the first refresh /fleet is 503, /healthz still answers
        code, _ = get("/fleet")
        assert code == 503
        code, hz = get("/healthz")
        assert code == 200 and hz["refresh_count"] == 0
        agg.refresh()
        code, status = get("/fleet")
        assert code == 200
        assert status["members"]["serve:serve0"]["ttft_p95_ms"] == 120.0
        code, hz = get("/healthz")
        assert code == 200
        assert hz["members"] == 3 and hz["refresh_count"] == 1
        code, _ = get("/nope")
        assert code == 404
    finally:
        server.shutdown()


def test_fleetd_once_cli_and_bad_alerts(tmp_path, capsys):
    import fleetd

    root, _, _ = make_fleet(tmp_path)
    assert fleetd.main(["--fleet-root", root, "--once"]) == 0
    status = json.loads(capsys.readouterr().out)
    assert status["pod"]["trainer_step"] == 8
    with pytest.raises(SystemExit, match="bad --alerts"):
        fleetd.main(["--fleet-root", root, "--once",
                     "--alerts", '{"nope": 1}'])


# ---------------------------------------------------------------------------
# the offline report
# ---------------------------------------------------------------------------

def test_fleet_report_tables_and_degrade(tmp_path, capsys):
    import fleet_report

    root, trainer_dir, serve_dir = make_fleet(tmp_path)
    # an alert timeline for the report to draw
    agg = FleetAggregator(root, AlertRules(checkpoint_lag_steps=2))
    agg.refresh()
    rep = fleet_report.build_report(root)
    assert rep["registered_members"] == 3
    assert rep["checkpoint_lag"]["trainer_step"] == 8
    assert rep["checkpoint_lag"]["replicas"][0]["checkpoint_lag"] == 4
    # serve0's dir hosts BOTH the serve member and its supervisor member:
    # the shared ledger must appear once, labeled as the child
    timeline = [(e["member"], e["incarnation"])
                for e in rep["incarnation_timeline"]]
    assert timeline == [("trainer:trainer0", 0), ("trainer:trainer0", 1),
                        ("serve:serve0", 0)]
    assert rep["alert_timeline"][0]["alert"] == "checkpoint_lag"
    assert rep["slo_table"][0]["slo_breaches"] == 2
    assert fleet_report.main([root]) == 0
    out = capsys.readouterr().out
    assert "incarnation timeline" in out and "alert timeline" in out
    assert "checkpoint lag" in out and "slo_breaches=2" in out

    # empty/garbage fleet root degrades, never tracebacks
    empty = str(tmp_path / "empty")
    os.makedirs(empty)
    assert fleet_report.main([empty]) == 0
    assert "no members registered" in capsys.readouterr().out
    write_lines(os.path.join(empty, fleet.REGISTRY_NAME), ["garbage"])
    assert fleet_report.main([empty]) == 0


# ---------------------------------------------------------------------------
# report satellites (serving counters + role labeling)
# ---------------------------------------------------------------------------

def test_goodput_report_surfaces_serve_counters_and_role(tmp_path, capsys):
    import goodput_report

    out = str(tmp_path)
    now = time.time()
    write_lines(os.path.join(out, "spans.jsonl"),
                [{"name": "serve_decode_step", "ts": now, "dur": 1.0,
                  "end": now + 1.0, "depth": 0, "main_thread": True}])
    write_lines(os.path.join(out, "metrics.jsonl"),
                [{"step": 4, "serving": 1, "requests_completed": 4,
                  "slo_breaches": 1, "requests_page_refused": 2,
                  "requests_failed": 0, "prefill_chunks_total": 3,
                  "prefill_tokens_total": 192, "ttft_p95_ms": 99.0}])
    with open(os.path.join(out, "health.json"), "w") as f:
        json.dump({"time": now, "role": "serve", "goodput": 0.5}, f)
    rep = goodput_report.build_report(out)
    assert rep["role"] == "serve"
    assert rep["serve_counters"]["slo_breaches"] == 1
    assert rep["serve_counters"]["requests_page_refused"] == 2
    assert rep["serve_counters"]["prefill_tokens_total"] == 192
    goodput_report.print_report(rep)
    text = capsys.readouterr().out
    assert "role serve" in text
    assert "slo_breaches=1" in text and "requests_page_refused=2" in text


def test_serving_report_surfaces_breach_and_refusal_counters(tmp_path,
                                                             capsys):
    import serving_report

    write_lines(os.path.join(str(tmp_path), "metrics.jsonl"),
                [{"step": 8, "serving": 1, "requests_completed": 8,
                  "requests_failed": 1, "requests_page_refused": 5,
                  "slo_breaches": 3, "tokens_generated": 64,
                  "active_slots": 2, "kv_cache": "paged", "pages_used": 4,
                  "prefill_chunks_total": 2, "prefill_tokens_total": 128}])
    assert serving_report.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "slo_breaches=3" in out and "requests_page_refused=5" in out
    assert "requests_failed=1" in out and "prefill_chunks_total=2" in out


# ---------------------------------------------------------------------------
# alert damping (for_s / cooldown_s) — independent of any actuator
# ---------------------------------------------------------------------------

def test_alert_damping_config_parse_and_reject():
    rules = AlertRules.from_cfg({
        "ttft_p95_ms": {"threshold": 500, "for_s": 10, "cooldown_s": 30},
        "heartbeat_stale_s": 30})
    assert rules.ttft_p95_ms == 500.0
    assert rules.damping_for("ttft_p95") == (10.0, 30.0)
    assert rules.damping_for("heartbeat_stale") == (0.0, 0.0)
    # scalar spelling == dict spelling with zero damping
    assert AlertRules.from_cfg({"ttft_p95_ms": 500}) == \
        AlertRules.from_cfg({"ttft_p95_ms": {"threshold": 500}})
    with pytest.raises(ValueError, match="unknown alerts.ttft_p95_ms"):
        AlertRules.from_cfg({"ttft_p95_ms": {"threshold": 500, "hold_s": 9}})
    with pytest.raises(ValueError, match="threshold"):
        AlertRules.from_cfg({"ttft_p95_ms": {"for_s": 10}})
    with pytest.raises(ValueError, match=">= 0"):
        AlertRules.from_cfg({"ttft_p95_ms": {"threshold": 5, "for_s": -1}})


def _eval_once(agg, value, now):
    """One damped-evaluator pass over a single synthetic serve member."""
    key = ("serve", "/runs/s0")
    member = {"role": "serve", "replica": "s0", "output_dir": "/runs/s0",
              "ttft_p95_ms": value}
    return agg._evaluate_alerts({key: member}, {key: "serve:s0"}, now,
                                write=False)


def test_alert_for_s_delays_the_rising_edge(tmp_path):
    root = str(tmp_path / "fleet")
    os.makedirs(root)
    rules = AlertRules.from_cfg({"ttft_p95_ms": {"threshold": 500,
                                                 "for_s": 10}})
    agg = FleetAggregator(root, rules)
    t0 = time.time()
    # breaching, but not sustained -> no edge yet
    alerts, edges = _eval_once(agg, 900, t0)
    assert edges == [] and alerts == {}
    alerts, edges = _eval_once(agg, 900, t0 + 5)
    assert edges == []
    # a dip resets the continuity clock
    _eval_once(agg, 100, t0 + 6)
    alerts, edges = _eval_once(agg, 900, t0 + 7)
    assert edges == []
    alerts, edges = _eval_once(agg, 900, t0 + 16)   # held 9s < 10s
    assert edges == []
    alerts, edges = _eval_once(agg, 900, t0 + 17.5)  # held 10.5s -> FIRES
    assert [e["state"] for e in edges] == ["firing"]
    assert alerts["ttft_p95:serve:s0"]["state"] == "firing"


def test_alert_cooldown_suppresses_the_refire(tmp_path):
    root = str(tmp_path / "fleet")
    os.makedirs(root)
    rules = AlertRules.from_cfg({"ttft_p95_ms": {"threshold": 500,
                                                 "cooldown_s": 30}})
    agg = FleetAggregator(root, rules)
    t0 = time.time()
    _, edges = _eval_once(agg, 900, t0)              # for_s=0: fires at once
    assert [e["state"] for e in edges] == ["firing"]
    _, edges = _eval_once(agg, 100, t0 + 1)          # resolves
    assert [e["state"] for e in edges] == ["resolved"]
    _, edges = _eval_once(agg, 900, t0 + 5)          # flap inside cooldown
    assert edges == []
    _, edges = _eval_once(agg, 900, t0 + 29)
    assert edges == []
    _, edges = _eval_once(agg, 900, t0 + 32)         # cooled -> re-fires
    assert [e["state"] for e in edges] == ["firing"]


def test_zero_damping_is_bit_identical_to_undamped(tmp_path):
    """{threshold: x} with no for_s/cooldown_s must produce the exact
    edge sequence the scalar spelling always did."""
    t0 = time.time()
    seqs = []
    for spec in (500, {"threshold": 500}):
        root = str(tmp_path / f"fleet-{len(seqs)}")
        os.makedirs(root)
        agg = FleetAggregator(root, AlertRules.from_cfg(
            {"ttft_p95_ms": spec}))
        seq = []
        for dt, val in ((0, 900), (1, 900), (2, 100), (3, 900)):
            _, edges = _eval_once(agg, val, t0 + dt)
            seq.extend((round(e["ts"] - t0, 3), e["state"]) for e in edges)
        seqs.append(seq)
    assert seqs[0] == seqs[1]
    assert [s for _, s in seqs[0]] == ["firing", "resolved", "firing"]


def test_queue_wait_p95_rule_fires(tmp_path):
    root = str(tmp_path / "fleet")
    os.makedirs(root)
    now = time.time()
    make_member(root, tmp_path, "s0", role="serve",
                health={"time": now, "role": "serve"},
                metrics=[{"step": 1, "serving": 1,
                          "queue_wait_p95_ms": 850.0}])
    agg = FleetAggregator(root, AlertRules.from_cfg(
        {"queue_wait_p95_ms": 500}))
    status = agg.refresh()
    assert status["members"]["serve:s0"]["queue_wait_p95_ms"] == 850.0
    assert status["pod"]["alerts_firing"] == ["queue_wait_p95:serve:s0"]


def test_terminal_registry_row_fires_stale_immediately(tmp_path):
    """A supervisor that gave up writes outcome=aborted registry rows;
    the member must alert NOW — a fresh-looking abort row must not vouch
    liveness for the whole staleness window."""
    root = str(tmp_path / "fleet")
    os.makedirs(root)
    now = time.time()
    out = make_member(root, tmp_path, "t0", role="trainer",
                      health={"time": now}, reg_ts=now)
    agg = FleetAggregator(root, AlertRules(heartbeat_stale_s=30.0))
    assert agg.refresh()["pod"]["alerts_firing"] == []
    register_member(root, output_dir=out, role="trainer", pid=99,
                    incarnation=3, outcome="aborted", reason="crash_loop")
    status = agg.refresh()
    assert status["members"]["trainer:t0"]["terminal_outcome"] == "aborted"
    assert status["pod"]["alerts_firing"] == ["heartbeat_stale:trainer:t0"]
    # a relaunch re-registers WITHOUT an outcome -> fresh again, resolves
    register_member(root, output_dir=out, role="trainer", pid=100,
                    incarnation=4)
    status = agg.refresh()
    assert status["pod"]["alerts_firing"] == []
    assert "terminal_outcome" not in status["members"]["trainer:t0"]
