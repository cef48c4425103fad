"""Triggered profiler capture (utils/profiler.py —
docs/OBSERVABILITY.md "Triggered capture").

Unit level: config parsing/rejection, the at_step / z-score / span
triggers, the bounded window, and the retention cap. E2E level: a
fault-plan `slow` rule at the step site fires the z-score trigger during
a real tiny training run — exactly once under a cap of 1 even though a
second slow step follows — and the written capture is readable by the
reader tools/trace_summary.py uses (benchmark/xplane.py: on the CPU a
capture holds host events only); the serving SLO-breach trigger does the
same under the synthetic traffic generator."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import trace_summary  # tools/ on sys.path via conftest

from llama_pipeline_parallel_tpu.utils.profiler import (
    CaptureConfig,
    TriggeredProfiler,
)


def _burn():
    x = jnp.ones((128, 128))
    jax.block_until_ready(jnp.tanh(x @ x))


def _capture_dirs(output_dir) -> list[str]:
    return sorted(glob.glob(os.path.join(str(output_dir), "captures", "*")))


def _host_events(capture_dir: str) -> list:
    """The capture's host events, through the reader trace_summary uses."""
    path = trace_summary.xplane.find_xplane(capture_dir)
    assert path is not None, f"no .xplane.pb under {capture_dir}"
    return trace_summary.xplane.read(path)["host"]


# ---------------------------------------------------------------------------
# Config + unit triggers
# ---------------------------------------------------------------------------

def test_capture_config_parse():
    assert CaptureConfig.from_cfg(None) is None
    c = CaptureConfig.from_cfg({"at_step": 5, "window_steps": 1,
                                "max_captures": 2})
    assert c.at_step == (5,) and c.window_steps == 1 and c.max_captures == 2
    assert CaptureConfig.from_cfg({}).zscore == 4.0
    with pytest.raises(ValueError, match="unknown profiler"):
        CaptureConfig.from_cfg({"atstep": [1]})
    with pytest.raises(ValueError, match="mapping"):
        CaptureConfig.from_cfg(7)
    with pytest.raises(ValueError, match="max_captures"):
        CaptureConfig.from_cfg({"max_captures": 0})


def test_at_step_deferred_semantics(tmp_path, monkeypatch):
    """at_step 4 lands INSIDE the step-3 capture window: it must fire at
    the first free boundary after the window closes, not silently drop.
    Capture start/stop are stubbed — the state machine is the contract
    here; the real-trace rep below stays in the round gate."""
    prof = TriggeredProfiler(
        CaptureConfig(at_step=(3, 4), window_steps=2, zscore=0.0),
        str(tmp_path))
    started = []

    def fake_start(path, reason, step=None, meta=None):
        prof._active_dir = path
        prof._remaining = prof.cfg.window_steps
        prof.captures_taken += 1
        started.append((reason, path))
        return True

    monkeypatch.setattr(prof, "_start", fake_start)
    monkeypatch.setattr(prof, "_stop",
                        lambda: setattr(prof, "_active_dir", None))
    for step in range(1, 9):
        prof.observe_step(step, 0.01)
    assert not prof.capturing  # windows closed
    assert prof.captures_taken == 2
    # step 3 fired at 3; step 4's landed inside that window and fired at
    # the first free boundary (step 5), never dropped
    assert [r for r, _ in started] == ["at_step", "at_step"]
    assert "step3" in started[0][1] and "step5" in started[1][1]


@pytest.mark.slow  # two real jax trace captures (~20 s); the deferral
# state machine is pinned fast above, and capture-dir/trace readability
# fast by the zscore test — this rep funds the fleet fast lanes
def test_at_step_trigger_bounded_window(tmp_path):
    prof = TriggeredProfiler(
        CaptureConfig(at_step=(3, 4), window_steps=2, zscore=0.0),
        str(tmp_path))
    for step in range(1, 9):
        prof.observe_step(step, 0.01)
        if prof.capturing:
            _burn()  # give the open window device work to record
    assert not prof.capturing  # windows closed
    assert prof.captures_taken == 2
    dirs = _capture_dirs(tmp_path)
    assert len(dirs) == 2 and all("at_step" in d for d in dirs)
    assert _host_events(dirs[0])


def test_zscore_trigger_and_retention_cap(tmp_path):
    prof = TriggeredProfiler(
        CaptureConfig(zscore=4.0, zscore_min_history=8, window_steps=1,
                      max_captures=1), str(tmp_path))
    for step in range(1, 11):
        prof.observe_step(step, 0.01 + 0.0001 * (step % 3))
    assert prof.captures_taken == 0  # steady walls: no trigger
    prof.observe_step(11, 1.0)  # the outlier
    assert prof.capturing and prof.captures_taken == 1
    _burn()
    prof.observe_step(12, 0.01)  # closes the 1-step window
    assert not prof.capturing
    # a second outlier is dropped by the retention cap
    assert prof.trigger("zscore-again", step=13) is False
    assert len(_capture_dirs(tmp_path)) == 1


def test_numerics_anomaly_span_listener(tmp_path):
    prof = TriggeredProfiler(CaptureConfig(window_steps=1, zscore=0.0),
                             str(tmp_path))
    prof.on_span({"name": "data_wait", "dur": 1.0})
    assert not prof.capturing
    prof.on_span({"name": "numerics_anomaly", "step": 7})
    assert prof.capturing
    prof.close()
    dirs = _capture_dirs(tmp_path)
    assert len(dirs) == 1 and "numerics_anomaly" in dirs[0]


# ---------------------------------------------------------------------------
# E2E: the fault-plan leg
# ---------------------------------------------------------------------------

def test_slow_step_fault_fires_zscore_capture_once(tmp_path):
    """A `slow` fault at the step site inflates one iteration's wall; the
    z-score trigger captures a bounded window EXACTLY once (a second slow
    step at step 12 is dropped by max_captures=1), and the trace is
    readable by trace_summary."""
    from llama_pipeline_parallel_tpu.train import run_training

    out = tmp_path / "run"
    cfg = {
        "output_dir": str(out),
        "mesh": {"pp": 2},
        "model": {"preset": "tiny", "dtype": "float32"},
        "dataset": {"synthetic": True, "seq_length": 32,
                    "pseudo_dataset_len": 64},
        "seed": 0, "per_device_train_batch_size": 2,
        "gradient_accumulation_steps": 2, "max_steps": 14,
        "logging_steps": 7, "save_steps": 0, "save_final": False,
        "attention": "exact", "numerics": {"enabled": False},
        "profiler": {"zscore": 4.0, "zscore_min_history": 6,
                     "window_steps": 2, "max_captures": 1},
        "fault_plan": {"faults": [
            {"site": "step", "op": "slow", "seconds": 2.0, "at_step": 10},
            {"site": "step", "op": "slow", "seconds": 2.0, "at_step": 12},
        ]},
    }
    summary = run_training(cfg)
    assert summary["final_step"] == 14
    dirs = _capture_dirs(out)
    assert len(dirs) == 1, dirs  # exactly once; cap honored
    assert "zscore" in os.path.basename(dirs[0])
    assert _host_events(dirs[0])


# ---------------------------------------------------------------------------
# E2E: serving SLO breach under the traffic generator
# ---------------------------------------------------------------------------

def test_serve_slo_breach_capture_under_traffic(tmp_path):
    import serve_traffic

    from llama_pipeline_parallel_tpu.models.llama import model as llama
    from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
    from llama_pipeline_parallel_tpu.serve import ServeConfig, ServeEngine
    from llama_pipeline_parallel_tpu.serve.telemetry import SLOThresholds

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    prof = TriggeredProfiler(
        CaptureConfig(zscore=0.0, window_steps=2, max_captures=1),
        str(tmp_path))
    eng = ServeEngine(
        params, cfg,
        ServeConfig(max_slots=2, max_len=64, prompt_buckets=(16,),
                    page_size=16, num_pages=32, max_queue=32),
        profiler=prof,
        slo=SLOThresholds(ttft_s=0.0))  # every completion breaches
    trace_reqs = serve_traffic.poisson_trace(
        0, 50.0, 6, serve_traffic.parse_mix("8:1.0"),
        serve_traffic.parse_mix("3:1.0"))
    summary = serve_traffic.run_trace(eng, trace_reqs)
    eng.shutdown()
    assert summary["requests_completed"] == 6
    snap = eng.stats.snapshot()
    assert snap["slo_breaches"] >= 1
    dirs = _capture_dirs(tmp_path)
    assert len(dirs) == 1, dirs  # cap of 1 despite 6 breaching requests
    assert "serve_slo_ttft" in os.path.basename(dirs[0])
