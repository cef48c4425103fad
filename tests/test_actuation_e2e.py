"""Actuation chaos e2e (docs/RESILIENCE.md "Actuation").

The self-driving-fleet acceptance scenarios, run against real processes:

- **Autoscale borrow/handback**: a sustained serve-SLO breach makes
  tools/fleetctl.py borrow training devices — the trainer's supervisor
  (--actuate) pins the smaller ladder rung, the trainer checkpoints at a
  step boundary and relaunches on it, `scale_up_cmd` fires — and
  sustained quiet hands the devices back. Chaos: the ACTUATOR is
  SIGKILLed between its intent and the request write (the next start
  voids the orphan and re-acts), and the TRAINER is SIGKILLed mid-borrow
  (the relaunch keeps the pinned rung). The per-sample-id ledger proves
  zero dropped and zero duplicated samples across the whole
  borrow -> crash -> handback ride.
- **Continuous deployment + rollback**: a serve replica tails the
  trainer's latest verified checkpoint via the same action RPC; the
  REPLICA is SIGKILLed (the relaunch keeps serving the pinned step); a
  regressed eval on the deployed checkpoint rolls it back to the
  previous verified step, token-identically.

Process-spawn heavy, slow-marked for the round gate like the other
chaos e2es; the fast actuator state-machine lanes live in
tests/test_actions.py."""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from llama_pipeline_parallel_tpu.utils import faults
from llama_pipeline_parallel_tpu.utils.actions import (
    ACTION_ACK_NAME,
    RESIZE_ACK_NAME,
    read_actions,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _read_json(path):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _wait_for(cond, what: str, timeout_s: float = 180.0,
              every_s: float = 0.25):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        out = cond()
        if out:
            return out
        time.sleep(every_s)
    pytest.fail(f"never reached: {what}")


def _fleetctl_once(fleet_root: str, actions_cfg: dict,
                   env: dict | None = None) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "tools/fleetctl.py", "--fleet-root", fleet_root,
         "--actions", json.dumps(actions_cfg), "--once"],
        cwd=REPO, env=env or os.environ.copy(),
        capture_output=True, text=True, timeout=120)


def _write_status(fleet_root: str, alerts: dict) -> None:
    """Stand-in for one fleetd refresh: the aggregator's own alert-edge
    e2e lives in tests/test_fleet_e2e.py; here the snapshot is the
    actuator's INPUT, so the test pins it exactly."""
    from llama_pipeline_parallel_tpu.utils.fleet import STATUS_NAME

    tmp = os.path.join(fleet_root, STATUS_NAME + ".tmp")
    with open(tmp, "w") as f:
        json.dump({"time": time.time(), "alerts": alerts}, f)
    os.replace(tmp, os.path.join(fleet_root, STATUS_NAME))


@pytest.mark.slow  # a long-running supervised trainer + three actuator
# runs + two kills: round-gate material like the other chaos e2es
def test_autoscale_borrow_handback_chaos_zero_sample_loss(tmp_path):
    import supervisor  # tools/ on sys.path via conftest

    root = str(tmp_path / "fleet")
    out = str(tmp_path / "trainer")
    os.makedirs(root, exist_ok=True)
    up_marker = str(tmp_path / "scaled_up")
    down_marker = str(tmp_path / "scaled_down")

    ladder = [
        {"name": "dp2", "devices": 8, "overrides": []},
        {"name": "dp1", "devices": 4,
         "overrides": ["mesh.dp=1", "gradient_accumulation_steps=4"]}]
    actions_cfg = {"autoscale": {
        "trainer_dir": out, "borrow_rung": "dp1", "restore_rung": "dp2",
        "for_s": 60.0, "idle_for_s": 0.0, "cooldown_s": 0.0,
        "scale_up_cmd": f"touch {up_marker}",
        "scale_down_cmd": f"touch {down_marker}"}}
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "LPT_DEVICE_COUNT": "8",
           # stretch steps so the choreography happens mid-run
           faults.ENV_PLAN: json.dumps({"faults": [
               {"site": "step", "op": "slow", "seconds": 0.1}]})}
    sup = subprocess.Popen(
        [sys.executable, "tools/supervisor.py", "--output-dir", out,
         "--max-restarts", "6", "--hang-timeout-s", "600",
         "--poll-s", "0.2", "--fleet-root", root,
         "--role", "trainer", "--replica", "trainer", "--actuate",
         "--layout-ladder", json.dumps(ladder),
         "--", sys.executable, "train.py", "--config",
         "conf/tiny_smoke.yaml", "--platform", "cpu", f"output_dir={out}",
         "max_steps=2000", "total_steps=2000", "save_steps=5",
         "save_final=true", "logging_steps=1", "attention=exact",
         "data.log_sample_ids=true", "actions.resize_on_request=true",
         "health_interval=0.5"],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    try:
        # ---- phase 0: the trainer is stepping on the full rung -----------
        _wait_for(lambda: os.path.exists(os.path.join(out, "metrics.jsonl")),
                  "first trainer metrics line", timeout_s=240)
        _wait_for(lambda: (supervisor.read_health(out) or {}).get(
            "topology", {}).get("dp") == 2, "trainer heartbeat on dp2")

        # ---- phase 1: sustained breach; the actuator dies MID-ACTION -----
        _write_status(root, {"ttft_p95:serve:r0": {
            "state": "firing", "since": time.time() - 300}})
        r = _fleetctl_once(root, actions_cfg, env={
            **os.environ, faults.ENV_PLAN: json.dumps({"faults": [
                {"site": "action_execute", "op": "die"}]})})
        assert r.returncode != 0  # SIGKILLed between intent and request
        rows = read_actions(root)
        assert [(x["kind"], x["phase"]) for x in rows] == \
            [("borrow", "intent")]  # the orphan: intent row, no outcome
        assert not os.path.exists(os.path.join(out, "action.request"))

        # ---- phase 2: restart voids the orphan, then borrows for real ----
        r = _fleetctl_once(root, actions_cfg)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "reconciled action-000000 (borrow): voided" in r.stdout
        taken = json.loads(r.stdout.strip().splitlines()[-1])["actions"]
        assert taken == ["action-000001"]

        # supervisor consumes: ack + pinned rung; trainer checkpoints at a
        # boundary, acks the resize, relaunches on dp1; scale_up_cmd ran
        _wait_for(lambda: (_read_json(os.path.join(out, ACTION_ACK_NAME))
                           or {}).get("id") == "action-000001",
                  "supervisor acked the borrow")
        _wait_for(lambda: os.path.exists(os.path.join(out, RESIZE_ACK_NAME)),
                  "trainer acked the resize at a step boundary")
        _wait_for(lambda: (supervisor.read_health(out) or {}).get(
            "topology", {}).get("dp") == 1, "trainer relaunched on dp1",
            timeout_s=240)
        _wait_for(lambda: os.path.exists(up_marker), "scale_up_cmd fired")
        state = _read_json(os.path.join(out, "action_state.json"))
        assert state["rung"] == "dp1" and state["last_id"] == "action-000001"

        # ---- phase 3: SIGKILL the trainer mid-borrow ---------------------
        # let the dp1 leg train PAST a save boundary (save_steps=5) first,
        # so the kill genuinely discards optimizer steps that have to be
        # retrained — that's what the sample-ledger audit is for
        _wait_for(lambda: ((supervisor.read_health(out) or {}).get(
            "last_step") or 0) >= 8, "dp1 leg trained past step 8",
            timeout_s=240)
        ledger_path = os.path.join(out, "incarnations.jsonl")
        n_rows = len(open(ledger_path).readlines())
        child = _wait_for(
            lambda: (_read_json(os.path.join(
                out, "supervisor_health.json")) or {}).get("child_pid"),
            "supervisor heartbeat names the dp1 child")
        kill_time = time.time()
        os.kill(child, signal.SIGKILL)
        _wait_for(lambda: len(open(ledger_path).readlines()) > n_rows,
                  "the crash landed in the incarnation ledger")
        # the relaunch STAYS on the pinned rung (availability is 8 devices;
        # best-fit would wrongly re-promote to dp2)
        health = _wait_for(
            lambda: ((supervisor.read_health(out) or {}).get("time", 0)
                     > kill_time) and supervisor.read_health(out),
            "relaunched trainer heartbeating", timeout_s=240)
        assert health["topology"]["dp"] == 1

        # ---- phase 4: sustained quiet hands the devices back -------------
        _write_status(root, {})
        r = _fleetctl_once(root, actions_cfg)
        assert r.returncode == 0, r.stdout + r.stderr
        handback = json.loads(r.stdout.strip().splitlines()[-1])["actions"]
        assert handback == ["action-000002"]
        _wait_for(lambda: (_read_json(os.path.join(out, ACTION_ACK_NAME))
                           or {}).get("id") == "action-000002",
                  "supervisor acked the handback")
        _wait_for(lambda: (supervisor.read_health(out) or {}).get(
            "topology", {}).get("dp") == 2, "trainer restored to dp2",
            timeout_s=240)
        _wait_for(lambda: os.path.exists(down_marker), "scale_down_cmd fired")

        # ---- phase 5: graceful end (pod preemption of the supervisor) ----
        # a few more steps on the restored rung, so the audit window spans
        # borrow AND handback training
        _wait_for(lambda: ((supervisor.read_health(out) or {}).get(
            "last_step") or 0) >= 12, "restored dp2 leg trained past 12",
            timeout_s=240)
        sup.send_signal(signal.SIGTERM)
        sup.wait(timeout=180)
        assert sup.returncode == 0
    finally:
        if sup.poll() is None:
            sup.kill()
        tail = sup.stdout.read() if sup.stdout else ""
        if sup.returncode != 0:
            print(tail[-4000:])

    # ---- audits ----------------------------------------------------------
    # journal: the orphan voided, borrow + handback done, every row paired
    rows = read_actions(root)
    by_id = {}
    for row in rows:
        by_id.setdefault(row["id"], []).append(row)
    assert [r.get("outcome") for r in by_id["action-000000"]
            if r["phase"] == "outcome"] == ["voided"]
    for action_id in ("action-000001", "action-000002"):
        phases = [r["phase"] for r in by_id[action_id]]
        assert phases == ["intent", "outcome"], (action_id, phases)
        assert by_id[action_id][1]["outcome"] == "done"

    # ledger: both actions attributed, one crash, layouts walked
    # dp2 -> dp1 -> dp2, and the pod ended by OUR stop, not a fault
    ledger = [json.loads(l)
              for l in open(os.path.join(out, "incarnations.jsonl"))]
    acted = [r["action"]["id"] for r in ledger if r.get("action")]
    assert acted == ["action-000001", "action-000002"]
    assert [r["outcome"] for r in ledger].count("crash") == 1
    layouts = [r["layout"] for r in ledger]
    assert layouts[0] == "dp2" and layouts[-1] == "dp2"
    assert "dp1" in layouts
    assert ledger[-1]["outcome"] == "supervisor_stopped"

    # zero dropped, zero duplicated samples across the whole ride: the
    # per-sample ledger's epoch-0 batches (last attempt wins — retrained
    # post-crash batches overwrite the discarded ones) are exactly
    # 0..K-1 with pairwise-disjoint sample ids
    final_step = max(r.get("last_step") or 0 for r in ledger)
    assert final_step >= 12
    sample_rows = [json.loads(l)
                   for l in open(os.path.join(out, "samples.jsonl"))]
    steps_per_epoch = 32  # 256 examples / (2 batch x 2 accum x dp2) = 32
    k = min(final_step, steps_per_epoch)
    trained = {}
    for row in sample_rows:
        if row["epoch"] == 0 and row["batch"] < k:
            trained[row["batch"]] = sorted(row["indices"])
    assert sorted(trained) == list(range(k)), \
        f"dropped batches: {sorted(set(range(k)) - set(trained))}"
    seen: set = set()
    for batch, ids in trained.items():
        dup = seen & set(ids)
        assert not dup, f"samples {sorted(dup)} trained twice (batch {batch})"
        seen.update(ids)

    # the story renders: paired action rows on the fleet_report timeline
    import fleet_report

    rep = fleet_report.build_report(root)
    kinds = [(r["kind"], r["phase"]) for r in rep["action_timeline"]]
    assert ("borrow", "intent") in kinds and ("handback", "outcome") in kinds


@pytest.mark.slow  # four serve incarnations under a supervisor + a kill
def test_deploy_rollback_chaos_replica_kill(tmp_path):
    import jax

    import supervisor  # tools/ on sys.path via conftest
    from llama_pipeline_parallel_tpu.ckpt.checkpoint import CheckpointManager
    from llama_pipeline_parallel_tpu.models.llama import model as llama
    from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
    from llama_pipeline_parallel_tpu.models.llama.manifest import StageManifest
    from llama_pipeline_parallel_tpu.parallel.pipeline import stack_stages

    root = str(tmp_path / "fleet")
    trainer_out = str(tmp_path / "trainer")
    replica_out = str(tmp_path / "replica")
    os.makedirs(root, exist_ok=True)

    # two verified checkpoints with DIFFERENT weights (the rollback's
    # token-identity check must be able to tell them apart) and recorded
    # eval quality: step 2 @ 1.0, step 4 @ 0.9 (an improvement — until a
    # later re-score says otherwise)
    cfg = LlamaConfig.tiny()
    manifest = StageManifest.for_config(cfg, 1)
    mgr = CheckpointManager(trainer_out)
    mgr.save(2, stack_stages(
        llama.init_params(jax.random.PRNGKey(0), cfg), manifest),
        manifest, cfg, extra_meta={"eval_loss": 1.0, "eval_step": 2})

    actions_cfg = {"deploy": {
        "trainer_dir": trainer_out, "replica_dirs": [replica_out],
        "eval_regression": 0.05, "cooldown_s": 0.0}}
    cmd = [sys.executable, os.path.join(REPO, "tools", "serve.py"),
           "--checkpoint_dir", trainer_out, "--output_dir", replica_out,
           "--host", "127.0.0.1", "--port", str(_free_port()),
           "--platform", "cpu", "--max_slots", "2", "--max_len", "320",
           "--buckets", "8", "--page_size", "8", "--metrics_every", "1",
           "--health_interval", "0.5", "--drain_s", "10"]
    sup = supervisor.Supervisor(cmd, supervisor.SupervisorConfig(
        output_dir=replica_out, max_restarts=6, hang_timeout_s=600.0,
        grace_s=15.0, crash_loop_threshold=3, crash_loop_window_s=0.0,
        poll_s=0.2, fleet_root=root, role="serve", replica="r0",
        actuate=True))
    t = threading.Thread(target=sup.run, daemon=True)
    t.start()

    def wait_replica(step: int, old_pid: int | None = None) -> dict:
        def up():
            info = _read_json(os.path.join(replica_out, "serve.json")) or {}
            if info.get("checkpoint_step") != step:
                return None
            if old_pid is not None and info.get("pid") == old_pid:
                return None
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{info['port']}/healthz", timeout=5)
            except Exception:
                return None
            return info
        return _wait_for(up, f"replica serving step {step}", timeout_s=240)

    def tokens(port: int) -> list:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/generate",
            data=json.dumps({"input_ids": [5, 6, 7], "max_new_tokens": 4,
                             "seed": 3}).encode(),
            headers={"Content-Type": "application/json"})
        return json.load(urllib.request.urlopen(req, timeout=180))["tokens"]

    try:
        # ---- phase 0: serving the only verified checkpoint ---------------
        info = wait_replica(2)
        baseline = tokens(info["port"])
        # converged pod: the deployer has nothing to do
        r = _fleetctl_once(root, actions_cfg)
        assert json.loads(r.stdout.strip().splitlines()[-1]) == \
            {"actions": []}

        # ---- phase 1: a newer, better checkpoint lands -> deploy ---------
        mgr.save(4, stack_stages(
            llama.init_params(jax.random.PRNGKey(1), cfg), manifest),
            manifest, cfg, extra_meta={"eval_loss": 0.9, "eval_step": 4})
        r = _fleetctl_once(root, actions_cfg)
        deployed = json.loads(r.stdout.strip().splitlines()[-1])["actions"]
        assert deployed == ["action-000000"]
        info4 = wait_replica(4, old_pid=info["pid"])
        new_tokens = tokens(info4["port"])
        assert new_tokens != baseline  # genuinely different weights

        # ---- phase 2: SIGKILL the replica; the pin survives the crash ----
        os.kill(info4["pid"], signal.SIGKILL)
        info4b = wait_replica(4, old_pid=info4["pid"])
        assert tokens(info4b["port"]) == new_tokens

        # ---- phase 3: the deployed checkpoint re-scores WORSE -> rollback
        meta_path = os.path.join(trainer_out, "checkpoint-4", "meta.json")
        meta = json.load(open(meta_path))
        meta["eval_loss"] = 2.0
        with open(meta_path + ".tmp", "w") as f:
            json.dump(meta, f)
        os.replace(meta_path + ".tmp", meta_path)
        r = _fleetctl_once(root, actions_cfg)
        rolled = json.loads(r.stdout.strip().splitlines()[-1])["actions"]
        assert rolled == ["action-000001"]
        info2 = wait_replica(2, old_pid=info4b["pid"])
        assert tokens(info2["port"]) == baseline  # token-identical restore

        # the regressed candidate is NOT immediately re-deployed: the next
        # tick holds it (journaled once), the replica stays on step 2
        r = _fleetctl_once(root, actions_cfg)
        assert json.loads(r.stdout.strip().splitlines()[-1]) == \
            {"actions": []}
        assert (_read_json(os.path.join(replica_out, "serve.json"))
                or {}).get("checkpoint_step") == 2
    finally:
        try:
            with open(os.path.join(replica_out, "serve.json")) as f:
                os.kill(json.load(f)["pid"], signal.SIGTERM)
        except (OSError, ValueError):
            pass
        t.join(timeout=120)
        try:
            with open(os.path.join(replica_out, "serve.json")) as f:
                os.kill(json.load(f)["pid"], signal.SIGKILL)
        except (OSError, ValueError):
            pass

    rows = read_actions(root)
    by_kind = {}
    for row in rows:
        if row["phase"] == "outcome":
            by_kind.setdefault(row["kind"], []).append(row["outcome"])
    assert by_kind["deploy"] == ["done"]
    assert by_kind["rollback"] == ["done"]
    assert by_kind["hold"] == ["done"]  # the vetoed re-deploy, exactly once
    # the replica's ledger tells the same story: two action-attributed
    # clean exits (deploy, rollback) and one crash between them
    ledger = [json.loads(l)
              for l in open(os.path.join(replica_out, "incarnations.jsonl"))]
    acted = [r["action"]["action"] for r in ledger if r.get("action")]
    assert acted == ["deploy", "deploy"]  # rollback delivers a deploy pin
    assert [r["outcome"] for r in ledger].count("crash") == 1
