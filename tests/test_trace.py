"""Span layer, RunClock goodput accounting, heartbeat/health.json, and the
trainer's end-to-end telemetry contract (docs/OBSERVABILITY.md)."""

import json
import os
import threading
import time

import pytest

from llama_pipeline_parallel_tpu.parallel.pipeline import (
    PipelineConfig,
    bubble_fraction,
)
from llama_pipeline_parallel_tpu.utils import trace


@pytest.fixture
def recorder(tmp_path):
    rec = trace.configure(str(tmp_path))
    yield rec
    trace.configure(None)


def read_spans(tmp_path):
    with open(tmp_path / "spans.jsonl") as f:
        return [json.loads(l) for l in f if l.strip()]


# ---- spans -----------------------------------------------------------------

def test_span_nesting_ordering_and_roundtrip(tmp_path, recorder):
    with trace.span("outer", step=3):
        time.sleep(0.01)
        with trace.span("inner"):
            time.sleep(0.005)
    recs = read_spans(tmp_path)
    # inner finishes first (jsonl is completion-ordered), nesting is explicit
    assert [r["name"] for r in recs] == ["inner", "outer"]
    inner, outer = recs
    assert inner["depth"] == 1 and inner["parent"] == "outer"
    assert outer["depth"] == 0 and outer["parent"] is None
    assert outer["step"] == 3
    assert outer["dur"] >= inner["dur"] > 0
    assert outer["ts"] <= inner["ts"] and inner["end"] <= outer["end"] + 1e-6
    assert outer["main_thread"] is True


def test_span_records_on_exception(tmp_path, recorder):
    with pytest.raises(RuntimeError):
        with trace.span("doomed"):
            raise RuntimeError("boom")
    (rec,) = read_spans(tmp_path)
    assert rec["name"] == "doomed" and rec["dur"] >= 0


def test_retroactive_emit_and_unconfigured_noop(tmp_path):
    trace.configure(None)
    with trace.span("nobody-listening"):  # must not raise, nothing persisted
        pass
    rec = trace.configure(str(tmp_path))
    rec.emit("init", ts=123.0, dur=4.5)
    (r,) = read_spans(tmp_path)
    assert (r["name"], r["ts"], r["dur"], r["end"]) == ("init", 123.0, 4.5, 127.5)
    trace.configure(None)


def test_spans_threadsafe_and_thread_tagged(tmp_path, recorder):
    def worker():
        with trace.span("bg"):
            time.sleep(0.002)

    t = threading.Thread(target=worker)
    with trace.span("fg"):
        t.start()
        t.join()
    recs = {r["name"]: r for r in read_spans(tmp_path)}
    assert recs["bg"]["main_thread"] is False
    # the worker's span must not see the main thread's stack as its parent
    assert recs["bg"]["depth"] == 0 and recs["bg"]["parent"] is None
    assert recs["fg"]["main_thread"] is True


# ---- RunClock --------------------------------------------------------------

def test_runclock_buckets_goodput_and_untracked(recorder):
    clock = trace.RunClock()
    recorder.add_listener(clock.on_span)
    with trace.span("step_dispatch"):
        time.sleep(0.02)
    with trace.span("data_wait"):
        time.sleep(0.01)
        with trace.span("prefetch_stall"):  # nested: must NOT double-count
            time.sleep(0.005)
    time.sleep(0.01)  # untracked gap
    snap = clock.snapshot()
    b = snap["buckets"]
    assert b["train"] >= 0.02
    assert 0.015 <= b["data_stall"] <= snap["elapsed"]  # outer span only
    assert b["untracked"] >= 0.005
    # snapshot is internally consistent: goodput vs its own elapsed sample
    assert snap["goodput"] == b["train"] / snap["elapsed"]
    # buckets partition elapsed wall time
    assert sum(b.values()) == pytest.approx(snap["elapsed"], rel=0.05)


def test_runclock_ignores_background_thread_spans(recorder):
    clock = trace.RunClock()
    recorder.add_listener(clock.on_span)

    def worker():
        with trace.span("ckpt_save"):  # async commit analogue
            time.sleep(0.01)

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert clock.snapshot()["buckets"]["ckpt"] == 0.0


def test_runclock_resume_accumulates_prior():
    prior = {"elapsed": 100.0,
             "buckets": {"train": 60.0, "init": 10.0, "untracked": 30.0}}
    clock = trace.RunClock(prior=prior, already_elapsed=5.0)
    clock.add("init", 5.0)
    clock.add("train", 20.0)
    snap = clock.snapshot()
    # elapsed: prior 100 + pre-clock 5 + (own ticking, ~0)
    assert snap["elapsed"] == pytest.approx(105.0, abs=1.0)
    assert snap["buckets"]["train"] == pytest.approx(80.0)
    assert snap["buckets"]["init"] == pytest.approx(15.0)
    # prior `untracked` is recomputed against the new elapsed, never summed
    assert snap["buckets"]["untracked"] == pytest.approx(
        snap["elapsed"] - 95.0, abs=1.0)
    assert snap["goodput"] == pytest.approx(80.0 / snap["elapsed"])


def test_runclock_prior_badput_depresses_goodput():
    """Wall time a preemption threw away (elapsed without train seconds)
    must keep depressing the cumulative goodput after resume."""
    # prior incarnation: 100s elapsed, only 50s of it training (50s lost)
    lossy = {"elapsed": 100.0, "buckets": {"train": 50.0}}
    clock = trace.RunClock(prior=lossy)
    assert clock.goodput() == pytest.approx(0.5, abs=0.01)
    # vs a clean prior of the same train seconds in half the wall
    clean = trace.RunClock(prior={"elapsed": 50.0, "buckets": {"train": 50.0}})
    assert clean.goodput() > clock.goodput()


# ---- device memory ---------------------------------------------------------

def test_device_peak_bytes_always_reports(devices):
    val, src = trace.device_peak_bytes()
    # CPU backend has no memory_stats -> host RSS stands in; either way the
    # metrics field exists and is a sane positive byte count
    assert src in ("device", "host_rss")
    assert val > 1 << 20


# ---- bubble fraction -------------------------------------------------------

def test_bubble_fraction_hand_computed():
    mk = lambda **kw: PipelineConfig(**{"num_stages": 4, "num_microbatches": 8,
                                        **kw})
    # 1f1b: c(S-1) / (M + c(S-1)) = 3 / 11 (6 / 14 until PR 38: the
    # warmup and drain ticks no longer run the half every stage masks)
    assert bubble_fraction(mk()) == pytest.approx(3 / 11)
    # gpipe: c(S-1) / (M + c(S-1)) = 3 / 11
    assert bubble_fraction(mk(schedule="gpipe")) == pytest.approx(3 / 11)
    # chunks multiply the flush bubble: c=2 -> 6 / 14 for both
    assert bubble_fraction(mk(accum_chunks=2)) == pytest.approx(6 / 14)
    assert bubble_fraction(mk(schedule="gpipe", accum_chunks=2)) \
        == pytest.approx(6 / 14)
    # no pipeline, no bubble; more microbatches amortize it monotonically
    assert bubble_fraction(mk(num_stages=1)) == 0.0
    assert bubble_fraction(mk(num_microbatches=64)) < bubble_fraction(mk())


# ---- heartbeat / health.json ----------------------------------------------

def test_heartbeat_atomic_rewrite_and_fields(tmp_path):
    clock = trace.RunClock()
    clock.add("train", 1.0)
    hb = trace.Heartbeat(str(tmp_path), clock, interval=30.0,
                         min_write_interval=0.0)
    path = tmp_path / "health.json"
    assert path.exists()  # file exists from construction
    first = json.load(open(path))
    assert first["last_step"] is None and first["pid"] == os.getpid()

    hb.beat(7, step_dur=0.25)
    mid = json.load(open(path))
    assert mid["last_step"] == 7 and mid["last_step_dur"] == 0.25
    # top-level goodput mirrors the embedded clock snapshot exactly
    assert mid["goodput"] == mid["clock"]["goodput"]
    assert mid["clock"]["buckets"]["train"] == pytest.approx(1.0)

    hb.stop()
    final = json.load(open(path))
    assert final["time"] >= mid["time"]
    # atomic contract: no torn temp files left behind
    assert [p.name for p in tmp_path.iterdir()] == ["health.json"]


def test_heartbeat_thread_refreshes_time(tmp_path):
    hb = trace.Heartbeat(str(tmp_path), clock=None, interval=0.05)
    t0 = json.load(open(tmp_path / "health.json"))["time"]
    deadline = time.time() + 5.0
    while time.time() < deadline:
        if json.load(open(tmp_path / "health.json"))["time"] > t0:
            break
        time.sleep(0.02)
    else:
        pytest.fail("heartbeat thread never rewrote health.json")
    hb.stop()


def test_load_health_roundtrip_and_missing(tmp_path):
    assert trace.load_health(str(tmp_path)) is None
    hb = trace.Heartbeat(str(tmp_path), trace.RunClock(), interval=30.0)
    hb.beat(3, 0.1)
    hb.stop()
    health = trace.load_health(str(tmp_path))
    assert health["last_step"] == 3
    assert "clock" in health  # the RunClock resume seed


# ---- trainer end-to-end ----------------------------------------------------

@pytest.mark.slow
def test_trainer_emits_observability_surface(tmp_path, devices):
    """The acceptance contract: a toy run writes nested spans, goodput +
    device_peak_bytes on every metrics line, and a live health.json whose
    bucket sum matches wall-clock (tools/goodput_report.py checks the 5%)."""
    from llama_pipeline_parallel_tpu.train import run_training

    out = tmp_path / "run"
    run_training({
        "output_dir": str(out),
        "mesh": {"pp": 2, "dp": 2},
        "model": {"preset": "tiny", "dtype": "float32"},
        "dataset": {"synthetic": True, "seq_length": 16,
                    "pseudo_dataset_len": 128},
        "seed": 7, "per_device_train_batch_size": 2,
        "gradient_accumulation_steps": 2, "max_steps": 4,
        "learning_rate": 1e-3, "warmup_steps": 1, "logging_steps": 2,
        "save_steps": 0, "save_final": True,
    })

    spans = [json.loads(l) for l in open(out / "spans.jsonl")]
    names = {s["name"] for s in spans}
    assert {"init", "compile_block", "data_wait", "step_dispatch",
            "device_step", "ckpt_save"} <= names

    for line in [json.loads(l) for l in open(out / "metrics.jsonl")]:
        assert 0.0 <= line["goodput"] <= 1.0
        assert line["device_peak_bytes"] > 0
        assert line["bubble_fraction"] == pytest.approx(1 / 3)  # S=2, M=2

    health = json.load(open(out / "health.json"))
    assert health["last_step"] == 4
    buckets = health["clock"]["buckets"]
    assert sum(buckets.values()) == pytest.approx(health["clock"]["elapsed"],
                                                  rel=0.05)

    import goodput_report  # tools/ on sys.path via conftest

    rep = goodput_report.build_report(str(out))
    assert sum(rep["buckets"].values()) == pytest.approx(rep["wall_seconds"],
                                                         rel=0.05)
