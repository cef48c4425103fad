"""Fleet observatory chaos e2e (docs/OBSERVABILITY.md "Fleet").

The acceptance scenario: one pod — a supervised trainer plus two
supervised serve replicas serving from the trainer's checkpoint dir —
aggregated by an in-process FleetAggregator with alert rules armed.

- SIGKILL replica A mid-decode: the heartbeat-stale alert FIRES within
  the window, the firing edge drops a capture trigger into A's dir,
  the watchdog relaunches A, the relaunched process consumes the trigger
  (EXACTLY one capture lands in that member), the alert RESOLVES, and A
  serves token-identically again.
- Checkpoint lag: a second training leg writes a newer VERIFIED
  checkpoint while replica B still serves the old step — the
  checkpoint-lag alert fires; B's relaunch tails the newer checkpoint
  and the alert resolves.

Process-spawn heavy (two serve replicas + two training legs on CPU), so
slow-marked for the round gate like the other chaos e2es; the fast
aggregation/alert/tailer lanes live in tests/test_fleet.py."""

import glob
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

from llama_pipeline_parallel_tpu.utils import fleet
from llama_pipeline_parallel_tpu.utils.fleet import (
    AlertRules,
    FleetAggregator,
    read_alerts,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_for_replica(out_dir: str, old_pid: int | None = None,
                      timeout_s: float = 180.0) -> dict:
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


def _post(port: int, body: dict, timeout: float = 180.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    return json.load(urllib.request.urlopen(req, timeout=timeout))


def _refresh_until(agg, cond, what: str, timeout_s: float = 120.0,
                   every_s: float = 0.25) -> dict:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        status = agg.refresh()
        if cond(status):
            return status
        time.sleep(every_s)
    pytest.fail(f"fleet never reached: {what}")


def _train_leg(trainer_out: str, fleet_root: str, max_steps: int) -> None:
    """One supervised training leg via the CLI (--fleet-root coverage):
    writes checkpoint-<max_steps> into trainer_out and registers the
    trainer member; a later leg resumes from the earlier checkpoint."""
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    r = subprocess.run(
        [sys.executable, "tools/supervisor.py", "--output-dir", trainer_out,
         "--max-restarts", "1", "--hang-timeout-s", "600",
         "--poll-s", "0.2", "--fleet-root", fleet_root,
         "--role", "trainer", "--replica", "trainer",
         "--", sys.executable, "train.py", "--config",
         "conf/tiny_smoke.yaml", "--platform", "cpu",
         f"output_dir={trainer_out}", f"max_steps={max_steps}",
         "total_steps=4", "save_steps=0", "save_final=true",
         "logging_steps=1", "attention=exact"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, \
        f"training leg failed:\n{r.stdout[-3000:]}\n{r.stderr[-3000:]}"


@pytest.mark.slow  # two training legs + two serve replicas + kills: the
# heavyweight acceptance run, round-gate material like the other chaos e2es
def test_fleet_chaos_stale_alert_capture_and_checkpoint_lag(tmp_path):
    import supervisor  # tools/ on sys.path via conftest

    root = str(tmp_path / "fleet")
    trainer_out = str(tmp_path / "trainer")
    os.makedirs(root, exist_ok=True)

    # ---- phase 0: first training leg -> checkpoint-2 ---------------------
    _train_leg(trainer_out, root, max_steps=2)
    assert fleet.latest_verified_step(trainer_out) == 2

    replicas, sups, threads = {}, {}, {}
    agg = None
    try:
        # ---- phase 1: two supervised serve replicas off checkpoint-2 -----
        for name in ("a", "b"):
            out = str(tmp_path / name)
            cmd = [sys.executable, os.path.join(REPO, "tools", "serve.py"),
                   "--checkpoint_dir", trainer_out, "--output_dir", out,
                   "--host", "127.0.0.1", "--port", str(_free_port()),
                   "--platform", "cpu", "--max_slots", "2",
                   "--max_len", "320", "--buckets", "8",
                   "--page_size", "8", "--metrics_every", "1",
                   "--health_interval", "0.5"]
            env = dict(os.environ)
            # stretch decode steps so the kill lands mid-decode
            env["LPT_SERVE_STEP_DELAY_S"] = "0.05" if name == "a" else "0"
            sup = supervisor.Supervisor(cmd, supervisor.SupervisorConfig(
                output_dir=out, max_restarts=3, hang_timeout_s=600.0,
                grace_s=5.0, crash_loop_threshold=3, crash_loop_window_s=0.0,
                poll_s=0.2, fleet_root=root, role="serve", replica=name),
                env=env)
            t = threading.Thread(target=sup.run, daemon=True)
            t.start()
            replicas[name], sups[name], threads[name] = out, sup, t
        info = {n: _wait_for_replica(replicas[n]) for n in ("a", "b")}
        assert info["a"]["checkpoint_step"] == 2

        # the aggregator arms its rules only against a HEALTHY baseline
        # (a replica's own startup window must not pre-fire the alert
        # whose exactly-one-capture count the kill is about)
        agg = FleetAggregator(root, AlertRules(heartbeat_stale_s=2.0,
                                               checkpoint_lag_steps=1))
        status = agg.refresh()
        for member_id in ("serve:a", "serve:b", "trainer:trainer",
                          "supervisor:a", "supervisor:b"):
            assert member_id in status["members"], \
                f"{member_id} not discovered: {sorted(status['members'])}"
        assert status["members"]["serve:a"]["checkpoint_step"] == 2
        assert status["members"]["serve:a"]["checkpoint_lag"] == 0
        assert "heartbeat_stale:serve:a" not in \
            status["pod"]["alerts_firing"]

        # both replicas serve token-identically off the shared checkpoint
        body = {"input_ids": [5, 6, 7], "max_new_tokens": 4, "seed": 3}
        baseline = _post(info["a"]["port"], body)["tokens"]
        assert _post(info["b"]["port"], body)["tokens"] == baseline

        # ---- phase 2: SIGKILL replica A mid-decode -----------------------
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
        threading.Thread(target=doomed, daemon=True).start()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            health = supervisor.read_health(replicas["a"]) or {}
            if (health.get("last_step") or 0) >= 3:
                break
            time.sleep(0.1)
        else:
            pytest.fail("replica a never started decoding")
        os.kill(info["a"]["pid"], signal.SIGKILL)

        # the stale alert fires within the window and drops the trigger
        _refresh_until(
            agg, lambda s: "heartbeat_stale:serve:a"
            in s["pod"]["alerts_firing"],
            "heartbeat_stale firing for serve:a", timeout_s=60)
        trigger = os.path.join(replicas["a"], fleet.CAPTURE_TRIGGER_NAME)
        captures = os.path.join(replicas["a"], "captures", "*")
        assert os.path.exists(trigger) or glob.glob(captures)

        # the watchdog relaunches; once the new incarnation heartbeats,
        # the alert resolves and A serves token-identically again
        new_info = _wait_for_replica(replicas["a"], old_pid=info["a"]["pid"])
        _refresh_until(
            agg, lambda s: "heartbeat_stale:serve:a"
            not in s["pod"]["alerts_firing"],
            "heartbeat_stale resolved after relaunch", timeout_s=60)
        assert _post(new_info["port"], body)["tokens"] == baseline

        # the relaunched member consumed the trigger: EXACTLY one capture
        deadline = time.monotonic() + 30
        while not glob.glob(captures) and time.monotonic() < deadline:
            time.sleep(0.25)
        assert len(glob.glob(captures)) == 1, glob.glob(captures)
        assert not os.path.exists(trigger)
        edges = [e for e in read_alerts(root)
                 if e["alert"] == "heartbeat_stale"
                 and e["member"] == "serve:a"]
        assert edges[0]["state"] == "firing"
        assert edges[-1]["state"] == "resolved"

        # ---- phase 3: checkpoint lag fires and resolves ------------------
        _train_leg(trainer_out, root, max_steps=4)  # resumes 2 -> ckpt-4
        assert fleet.latest_verified_step(trainer_out) == 4
        status = _refresh_until(
            agg, lambda s: "checkpoint_lag:serve:b"
            in s["pod"]["alerts_firing"],
            "checkpoint_lag firing for serve:b", timeout_s=60)
        assert status["members"]["serve:b"]["checkpoint_lag"] == 2
        assert status["pod"]["trainer_step"] == 4

        # B's relaunch tails the newer verified checkpoint -> resolved
        os.kill(info["b"]["pid"], signal.SIGKILL)
        status = _refresh_until(
            agg, lambda s:
            s["members"]["serve:b"].get("checkpoint_step") == 4
            and "checkpoint_lag:serve:b" not in s["pod"]["alerts_firing"],
            "checkpoint_lag resolved on the newer checkpoint",
            timeout_s=180)
        lag_edges = [e for e in read_alerts(root)
                     if e["alert"] == "checkpoint_lag"
                     and e["member"] == "serve:b"]
        assert lag_edges[0]["state"] == "firing"
        assert lag_edges[-1]["state"] == "resolved"

        # the atomic rollup on disk matches the acceptance picture
        with open(os.path.join(root, fleet.STATUS_NAME)) as f:
            on_disk = json.load(f)
        assert on_disk["members"]["serve:b"]["checkpoint_lag"] == 0
        assert on_disk["members"]["trainer:trainer"][
            "latest_verified_step"] == 4
    finally:
        for name, out in replicas.items():
            try:
                with open(os.path.join(out, "serve.json")) as f:
                    os.kill(json.load(f)["pid"], signal.SIGTERM)
            except (OSError, ValueError):
                pass
        for name, t in threads.items():
            t.join(timeout=90)
        for name, out in replicas.items():
            try:
                with open(os.path.join(out, "serve.json")) as f:
                    os.kill(json.load(f)["pid"], signal.SIGKILL)
            except (OSError, ValueError):
                pass

    # the offline story renders from the same root (degrade contract
    # exercised live: every stream has torn/append history by now)
    import fleet_report

    rep = fleet_report.build_report(root)
    assert rep["checkpoint_lag"]["trainer_step"] == 4
    members = {e["member"] for e in rep["incarnation_timeline"]}
    assert "serve:a" in members and "serve:b" in members
