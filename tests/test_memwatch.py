"""Memory observatory (utils/memwatch.py + `preflight --memory-audit` +
the OOM forensics path — docs/OBSERVABILITY.md "Memory",
docs/PREFLIGHT.md "Memory audit" / "Calibration").

Pins, in order: the `memory.*` config contract; the compiled-analysis
capture (memory_analysis aggregates + top-N HLO buffer attribution,
degrading to None/[] where a backend hides them); the sampler's cadence,
bounded forensics ring, and perf-ledger pairing; the reader degrade
grid (memory.jsonl and oom/ snapshots); the OOM snapshot's atomicity +
retention and the RESOURCE_EXHAUSTED matcher; THE calibration
acceptance pin — a measured live/model peak ratio distills into
`mem_scale` and re-ranks the 65B-shape frontier from the in-HBM zb1
winner to its wgrad-offload twin; the page-pool fragmentation gauges
(serve/pages.py) and their metrics-snapshot surface; the trainer e2e
(memory ON is bit-equal to OFF — the zero-cost contract — while writing
memory.jsonl + mem_peak_gib ledger rows); the OOM chaos e2e (fault op
`oom` -> snapshot -> supervisor `oom` outcome -> fleet `oom_recent`
alert firing and resolving);
`inspect_ckpt --sizes`; and the slow-marked anchored-estimate evidence
(the 2^31-element XLA-CPU stash over-count the audit localizes)."""

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import preflight  # tools/ on sys.path via conftest
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.parallel import pipeline as pl
from llama_pipeline_parallel_tpu.utils import memwatch, perf


# ---------------------------------------------------------------------------
# config block
# ---------------------------------------------------------------------------

def test_memory_config_parse():
    assert not memwatch.MemoryConfig.from_cfg(None).enabled
    c = memwatch.MemoryConfig.from_cfg(
        {"enabled": True, "every": 4, "top_buffers": 2})
    assert c.enabled and c.every == 4 and c.top_buffers == 2
    with pytest.raises(ValueError, match="unknown memory"):
        memwatch.MemoryConfig.from_cfg({"enalbed": True})
    with pytest.raises(ValueError, match="mapping"):
        memwatch.MemoryConfig.from_cfg("yes")
    with pytest.raises(ValueError, match="every must be >= 1"):
        memwatch.MemoryConfig.from_cfg({"every": 0})
    # an empty yaml key (None) IS the default, not an error
    assert memwatch.MemoryConfig.from_cfg({"every": None}).every == 1
    with pytest.raises(ValueError, match="top_buffers must be >= 0"):
        memwatch.MemoryConfig.from_cfg({"top_buffers": -1})


# ---------------------------------------------------------------------------
# compiled-program analysis
# ---------------------------------------------------------------------------

_HLO_SAMPLE = """\
ENTRY %main.42 {
  %big.1 = f32[4,4,8]{2,1,0} fusion(...)
  %fusion.3 = bf16[8,16]{1,0} fusion(...)
  %fusion.3 = bf16[2]{0} slice(...)
  %mystery = q128[8]{0} custom-call(...)
  %scalar = f32[] constant(0)
}
"""


def test_top_hlo_buffers_ranks_and_degrades():
    bufs = memwatch._top_hlo_buffers(_HLO_SAMPLE, 8)
    assert [b["name"] for b in bufs] == ["big.1", "fusion.3", "scalar"]
    assert bufs[0] == {"name": "big.1", "dtype": "f32", "shape": [4, 4, 8],
                       "bytes": 512}
    # per-name dedup keeps the LARGER value; unknown dtypes are skipped
    assert bufs[1]["bytes"] == 8 * 16 * 2
    assert bufs[2]["shape"] == [] and bufs[2]["bytes"] == 4
    assert memwatch._top_hlo_buffers(_HLO_SAMPLE, 1) == bufs[:1]
    assert memwatch._top_hlo_buffers(_HLO_SAMPLE, 0) == []
    assert memwatch._top_hlo_buffers("not hlo at all", 4) == []
    assert memwatch._top_hlo_buffers(None, 4) == []  # degrade, not raise


class _FakeMA:
    argument_size_in_bytes = 100
    output_size_in_bytes = 50
    temp_size_in_bytes = 30
    alias_size_in_bytes = 20
    generated_code_size_in_bytes = 7


class _FakeCompiled:
    def memory_analysis(self):
        return _FakeMA()

    def as_text(self):
        return _HLO_SAMPLE


def test_compiled_memory_aggregates_and_degrade():
    rec = memwatch.compiled_memory(_FakeCompiled(), top_buffers=2,
                                   label="fake")
    assert rec["label"] == "fake"
    assert rec["peak_bytes"] == 100 + 50 + 30 - 20
    assert rec["generated_bytes"] == 7
    assert [b["name"] for b in rec["top_buffers"]] == ["big.1", "fusion.3"]
    assert "top_buffers" not in memwatch.compiled_memory(_FakeCompiled(),
                                                         top_buffers=0)

    class NoAnalysis:
        def memory_analysis(self):
            raise NotImplementedError("backend hides it")

    class NoneAnalysis:
        def memory_analysis(self):
            return None

    class GarbageAttrs:
        def memory_analysis(self):
            return object()

    assert memwatch.compiled_memory(NoAnalysis()) is None
    assert memwatch.compiled_memory(NoneAnalysis()) is None
    assert memwatch.compiled_memory(GarbageAttrs()) is None


def test_compiled_memory_on_real_jit():
    """XLA-CPU exposes memory_analysis: the aggregates are real ints and
    the identity peak = arg + out + temp - alias holds on an actual
    Compiled, not just the stub."""
    compiled = jax.jit(lambda x: (x @ x).sum()).lower(
        jnp.ones((64, 64), jnp.float32)).compile()
    rec = memwatch.compiled_memory(compiled, top_buffers=4, label="real")
    if rec is None:  # a backend without the analysis: degrade documented
        pytest.skip("backend exposes no memory_analysis")
    assert rec["argument_bytes"] >= 64 * 64 * 4
    assert rec["peak_bytes"] == (rec["argument_bytes"] + rec["output_bytes"]
                                 + rec["temp_bytes"] - rec["alias_bytes"])
    assert isinstance(rec.get("top_buffers"), list)


def test_live_sample_and_device_peak_exist_on_cpu():
    """The live sources never raise; on the CPU backend the host RSS
    stands in (tagged, so it is never compared against a device peak)."""
    peak, src = memwatch.device_peak_bytes()
    assert src in ("device", "host_rss", "unavailable")
    if src != "unavailable":
        assert peak > 0
    row = memwatch.live_sample()
    assert row.get("host_rss_bytes", 0) > 0


# ---------------------------------------------------------------------------
# the run-side watch: cadence, ring, ledger pairing, reader degrade
# ---------------------------------------------------------------------------

def test_memwatch_cadence_ring_and_perf_rows(tmp_path):
    w = memwatch.MemoryWatch(str(tmp_path), every=2, top_buffers=2,
                             stash_bytes=4096)
    assert w.sample(1) is None          # off-cadence: skipped entirely
    row = w.sample(2)
    assert row["step"] == 2 and row["host_stash_bytes"] == 4096
    assert w.health_gauges().get("host_rss_bytes", 0) > 0

    rec = w.note_compiled("train_step", _FakeCompiled())
    assert rec["peak_bytes"] == 160
    # first call per label wins; a re-compile never duplicates the record
    class Other(_FakeCompiled):
        pass
    assert w.note_compiled("train_step", Other()) is rec

    for step in range(4, 4 + 2 * (memwatch.OOM_KEEP_ROWS + 5), 2):
        w.sample(step)
    snap = w.snapshot()
    assert len(snap["recent"]) == memwatch.OOM_KEEP_ROWS
    assert snap["compiled"]["train_step"]["label"] == "train_step"
    w.close()

    rows = memwatch.read_memory(str(tmp_path / "memory.jsonl"))
    kinds = {r["kind"] for r in rows}
    assert kinds == {"sample", "compiled"}
    assert all(r["step"] % 2 == 0 for r in rows if r["kind"] == "sample")

    ledger = {r["metric"]: r for r in w.perf_rows(run="r1")}
    assert ledger["compiled_peak_gib:train_step"]["model"] == round(
        160 / memwatch.GIB, 3)
    pair = ledger["mem_peak_gib"]
    assert pair["model"] == round(160 / memwatch.GIB, 3)
    # on CPU there is no device peak: the measured half stays empty rather
    # than smuggling host RSS into a device calibration
    if pair["context"].get("measured_source") != "device":
        assert pair["measured"] is None


def test_memwatch_write_failure_degrades(tmp_path):
    blocked = tmp_path / "file"
    blocked.write_text("")
    w = memwatch.MemoryWatch(str(blocked / "sub"))  # open fails under a file
    assert w.sample(1) is not None      # sampling continues unwritten
    w.close()


def test_read_memory_degrades(tmp_path):
    assert memwatch.read_memory(str(tmp_path / "absent.jsonl")) == []
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert memwatch.read_memory(str(empty)) == []
    torn = tmp_path / "torn.jsonl"
    torn.write_text('{"kind": "sample", "step": 1}\n{"kind": "sam')
    assert memwatch.read_memory(str(torn)) == [{"kind": "sample", "step": 1}]
    garbage = tmp_path / "garbage.jsonl"
    garbage.write_text('nope\n[1]\n{"kind": "compiled"}\n\x00\x01\n')
    assert memwatch.read_memory(str(garbage)) == [{"kind": "compiled"}]


# ---------------------------------------------------------------------------
# OOM forensics: matcher, snapshot atomicity + retention, readers
# ---------------------------------------------------------------------------

def test_is_resource_exhausted_matrix():
    assert memwatch.is_resource_exhausted(
        RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating ..."))
    assert memwatch.is_resource_exhausted(RuntimeError("ran Out of Memory"))

    class ResourceExhaustedError(Exception):
        pass

    assert memwatch.is_resource_exhausted(ResourceExhaustedError("boom"))
    assert not memwatch.is_resource_exhausted(ValueError("shape mismatch"))
    assert not memwatch.is_resource_exhausted(KeyboardInterrupt())


class _FakeClock:
    """Advancing stand-in for memwatch's `time` module: distinct snapshot
    filenames without sleeping through real seconds."""

    def __init__(self, t0):
        self._t = t0

    def time(self):
        self._t += 2.0
        return self._t

    def __getattr__(self, name):  # strftime/gmtime delegate to the real one
        return getattr(time, name)


def test_oom_snapshot_retention_atomicity_and_readers(tmp_path, monkeypatch):
    monkeypatch.setattr(memwatch, "time", _FakeClock(time.time()))
    w = memwatch.MemoryWatch(str(tmp_path), write=False)
    w.note_compiled("train_step", _FakeCompiled())
    w.sample(1)
    for i in range(memwatch.OOM_KEEP_SNAPSHOTS + 4):
        path = memwatch.dump_oom_snapshot(
            str(tmp_path), step=i, error=RuntimeError("RESOURCE_EXHAUSTED: x"
                                                      * 3000),
            memwatch=w, page_table={"pages_used": 3})
        assert path is not None and os.path.exists(path)
    names = os.listdir(memwatch.oom_dir(str(tmp_path)))
    assert not [n for n in names if n.endswith(".tmp")]  # atomic rename
    assert len(names) == memwatch.OOM_KEEP_SNAPSHOTS     # bounded retention

    snaps = memwatch.read_oom_snapshots(str(tmp_path))
    assert len(snaps) == memwatch.OOM_KEEP_SNAPSHOTS
    assert [s["_file"] for s in snaps] == sorted(
        (s["_file"] for s in snaps), reverse=True)        # newest first
    newest = snaps[0]
    assert newest["step"] == memwatch.OOM_KEEP_SNAPSHOTS + 3
    assert len(newest["error"]) == 2000                   # bounded payload
    assert newest["error_type"] == "RuntimeError"
    assert newest["memwatch"]["compiled"]["train_step"]["peak_bytes"] == 160
    assert newest["page_table"] == {"pages_used": 3}
    assert memwatch.latest_oom_mtime(str(tmp_path)) is not None

    # forensics never turn an abort into a second crash
    blocked = tmp_path / "plainfile"
    blocked.write_text("")
    assert memwatch.dump_oom_snapshot(str(blocked / "x"), 0, "e") is None


def test_read_oom_snapshots_degrades(tmp_path):
    assert memwatch.read_oom_snapshots(str(tmp_path)) == []
    assert memwatch.latest_oom_mtime(str(tmp_path)) is None
    d = memwatch.oom_dir(str(tmp_path))
    os.makedirs(d)
    with open(os.path.join(d, "oom-20260101-000000-1.json"), "w") as f:
        f.write('{"step": 3, "error": "RESOURCE_EXHAUSTED"}')
    with open(os.path.join(d, "oom-20260101-000001-1.json"), "w") as f:
        f.write('{"torn": ')
    with open(os.path.join(d, "oom-20260101-000002-1.json"), "w") as f:
        f.write('[1, 2]')  # parseable but not a dict: skipped
    with open(os.path.join(d, "unrelated.txt"), "w") as f:
        f.write("x")
    snaps = memwatch.read_oom_snapshots(str(tmp_path))
    assert len(snaps) == 1 and snaps[0]["step"] == 3


# ---------------------------------------------------------------------------
# THE calibration acceptance pin: measured mem constant re-ranks the frontier
# ---------------------------------------------------------------------------

def test_mem_scale_rerank_pinned(tmp_path):
    """At the 65B pp8 shape with a roomy 140 GiB budget, the byte model
    keeps the zb1 v=2 in-HBM candidate feasible and it wins (same bubble
    as its offload twin, no bytes moved). A ledger whose live device peak
    ran 15% over the compiled model distills into `mem_scale` 1.15, flows
    through --calibration, and flips the SAME frontier to the
    wgrad-offload twin — the budget cut re-ranked from MEASUREMENT
    (docs/PREFLIGHT.md "Calibration")."""
    dims = pl.stash_dims(8, 512, 1, 8192, "bfloat16")
    cands = preflight.enumerate_candidates(8, 256, 80)
    compute = lambda pcfg: 60.0

    def pick(scale):
        winner, _ = preflight.select_schedule(cands, 70.0, dims, 140.0, 30.0,
                                              compute, mem_scale=scale)
        return winner

    # the measured ratio lands in the ledger: model 100 GiB, live 115 GiB.
    # A cpu-stamped row with an absurd ratio and a lone measurement must
    # not pollute the constant (derive_calibration's exclusion rules).
    ledger = tmp_path / "perf.jsonl"
    perf.append_rows(str(ledger), [
        perf.make_row("mem_peak_gib", model=100.0, measured=115.0,
                      unit="GiB", source="memwatch", run="r1"),
        perf.make_row("mem_peak_gib", model=1.0, measured=50.0, unit="GiB",
                      source="bench", run="cpu-smoke", backend="cpu"),
        perf.make_row("mem_peak_gib", measured=80.0, unit="GiB",
                      source="train", run="r2")])
    calib = perf.derive_calibration(perf.read_ledger(str(ledger)))
    assert calib["mem_scale"] == 1.15
    calib_path = tmp_path / "calib.json"
    calib_path.write_text(json.dumps(calib))

    args = argparse.Namespace(mfu=0.45, host_bw_gibps=30.0,
                              ici_bw_gibps=90.0, mem_scale=1.0)
    applied = preflight.apply_calibration(args, str(calib_path))
    assert applied == {"mem_scale": 1.15}
    assert args.mem_scale == 1.15 and args.mfu == 0.45  # absent keys kept

    uncalibrated = pick(1.0)
    calibrated = pick(args.mem_scale)
    assert (uncalibrated["schedule"], uncalibrated["virtual_stages"]) == \
        ("zb1", 2)
    assert not uncalibrated["offload_wgrad"]   # fits: no bytes moved
    assert (calibrated["schedule"], calibrated["virtual_stages"]) == \
        ("zb1", 2)
    assert calibrated["offload_wgrad"]         # the measured cut flips it
    assert calibrated["bubble_fraction"] == uncalibrated["bubble_fraction"]


def test_bench_mem_rows_map_into_ledger():
    """bench.py's `extra:mem-peak` / `extra:mem-pagepool` rows convert to
    the `mem_peak_gib` pairing and the fragmentation gauge row."""
    summary = {"metric": "tok/s", "mfu": 0.3, "all_configs": {
        "extra:mem-peak": {"ms": 10.0, "detail": {
            "backend": "cpu", "compiled_peak_gib": 1.5, "live_peak_gib": 1.8,
            "temp_gib": 0.7}},
        "extra:mem-pagepool": {"ms": 0.0, "detail": {
            "backend": "cpu", "fragmentation": 0.25, "pages_reserved": 8,
            "pages_used": 6, "reserved_gap_gib": 0.01}},
    }}
    by = {}
    for row in perf.rows_from_bench_summary(summary, run="rX"):
        by.setdefault(row["metric"], row)
    assert by["mem_peak_gib"]["model"] == 1.5
    assert by["mem_peak_gib"]["measured"] == 1.8
    assert by["page_fragmentation"]["measured"] == 0.25
    assert by["page_fragmentation"]["context"]["pages_reserved"] == 8
    # cpu-stamped: measured on the wrong hardware, never calibrates
    calib = perf.derive_calibration(list(by.values()))
    assert "mem_scale" not in calib


# ---------------------------------------------------------------------------
# page-pool fragmentation gauges (serve/pages.py -> engine surfaces)
# ---------------------------------------------------------------------------

def test_pages_fragmentation_gauges():
    from llama_pipeline_parallel_tpu.serve.pages import (
        PagedKVCache,
        paged_pool_bytes,
    )

    cfg = LlamaConfig.tiny()
    cache = PagedKVCache(cfg, max_slots=2, max_len=16, page_size=4,
                         num_pages=8)
    assert cache.fragmentation == 0.0          # empty pool: defined, not NaN
    assert cache.reserved_unbacked == 0
    assert cache.page_bytes() == (paged_pool_bytes(cfg, 1, 4)
                                  - paged_pool_bytes(cfg, 0, 4))
    assert cache.page_bytes() > 0

    assert cache.reserve(4)                    # promised, nothing backed yet
    g = cache.fragmentation_gauges()
    assert g == {"pages_free": 8, "pages_used": 0, "pages_reserved": 4,
                 "reserved_unbacked": 4, "fragmentation": 1.0,
                 "reserved_gap_bytes": 4 * cache.page_bytes()}

    slot = cache.acquire("req-a", 4)
    cache.ensure_capacity(slot, 6)             # 2 pages back 6 tokens
    g = cache.fragmentation_gauges()
    assert g["pages_used"] == 2 and g["pages_reserved"] == 4
    assert g["reserved_unbacked"] == 2 and g["fragmentation"] == 0.5
    assert g["reserved_gap_bytes"] == 2 * cache.page_bytes()

    cache.ensure_capacity(slot, 16)            # fully backed: gap closes
    assert cache.fragmentation == 0.0
    cache.release(slot)
    assert cache.fragmentation_gauges()["pages_reserved"] == 0


def test_serve_engine_publishes_fragmentation():
    """The paged engine's metrics snapshot (the /healthz payload) carries
    the occupancy gauges."""
    from llama_pipeline_parallel_tpu.models.llama import model as llama
    from llama_pipeline_parallel_tpu.models.llama.decode import (
        GenerationConfig,
    )
    from llama_pipeline_parallel_tpu.serve import (
        ServeConfig,
        ServeEngine,
        ServeRequest,
    )

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(params, cfg,
                      ServeConfig(max_slots=2, max_len=32,
                                  prompt_buckets=(16,), kv_cache="paged",
                                  page_size=4))
    rs = np.random.RandomState(0)
    prompt = rs.randint(3, cfg.vocab_size, (12,)).tolist()
    for _ in range(2):
        eng.submit(ServeRequest(input_ids=prompt,
                                gen=GenerationConfig(max_new_tokens=4)))
    eng.drain(timeout_s=300)
    snap = eng.metrics_snapshot()
    eng.shutdown()

    assert snap["reserved_unbacked"] >= 0
    assert 0.0 <= snap["page_fragmentation"] <= 1.0
    assert snap["reserved_gap_bytes"] == \
        snap["reserved_unbacked"] * eng.slots.page_bytes()


# ---------------------------------------------------------------------------
# trainer e2e: zero-cost OFF, artifacts ON, and the OOM chaos path
# ---------------------------------------------------------------------------

def _trainer_cfg(out, **kw):
    cfg = {
        "output_dir": str(out),
        "mesh": {"pp": 2, "dp": 2},
        "model": {"preset": "tiny", "dtype": "float32"},
        "dataset": {"synthetic": True, "seq_length": 16,
                    "pseudo_dataset_len": 128},
        "seed": 7, "per_device_train_batch_size": 2,
        "gradient_accumulation_steps": 2, "max_steps": 3,
        "logging_steps": 1, "save_steps": 0, "save_final": False,
        "attention": "exact", "numerics": {"enabled": False},
    }
    cfg.update(kw)
    return cfg


def _metric_losses(out):
    with open(os.path.join(str(out), "metrics.jsonl")) as f:
        lines = [json.loads(l) for l in f if l.strip()]
    return [(l["step"], l["loss"]) for l in lines[1:] if "loss" in l]


def test_trainer_memory_on_bit_equal_and_artifacts(tmp_path):
    """The zero-cost contract: the sampler is host-side only, so every
    step's loss is BIT-equal ON vs OFF — while ON writes memory.jsonl (one
    compiled record for the train step + per-step samples) and closes into
    the perf ledger with the compiled-vs-live `mem_peak_gib` pairing."""
    from llama_pipeline_parallel_tpu.train import run_training

    off_dir, on_dir = tmp_path / "off", tmp_path / "on"
    off = run_training(_trainer_cfg(off_dir))
    on = run_training(_trainer_cfg(
        on_dir, memory={"enabled": True, "every": 1, "top_buffers": 4}))
    assert float(off["final_loss"]) == float(on["final_loss"])
    assert _metric_losses(off_dir) == _metric_losses(on_dir)

    assert not os.path.exists(off_dir / "memory.jsonl")  # OFF writes nothing
    rows = memwatch.read_memory(str(on_dir / "memory.jsonl"))
    compiled = [r for r in rows if r["kind"] == "compiled"]
    samples = [r for r in rows if r["kind"] == "sample"]
    assert [c["label"] for c in compiled] == ["train_step"]
    assert compiled[0]["peak_bytes"] > 0
    assert [s["step"] for s in samples] == [1, 2, 3]
    assert all(s.get("host_rss_bytes", 0) > 0 for s in samples)

    ledger = perf.read_ledger(str(on_dir / "perf.jsonl"))
    by = {r["metric"]: r for r in ledger}
    assert by["compiled_peak_gib:train_step"]["model"] > 0
    assert by["mem_peak_gib"]["model"] > 0
    assert not any(r["metric"].startswith("mem_") for r in
                   perf.read_ledger(str(off_dir / "perf.jsonl")))


def test_oom_chaos_e2e(tmp_path):
    """Chaos op `oom` at the step site drives the REAL forensics path:
    the trainer raises a synthetic RESOURCE_EXHAUSTED, the handler writes
    a bounded snapshot (live rows + compiled analyses riding along) and
    re-raises — no final save: the device state is not trustworthy."""
    from llama_pipeline_parallel_tpu.train import run_training

    out = tmp_path / "run"
    cfg = _trainer_cfg(
        out, max_steps=4,
        memory={"enabled": True},
        fault_plan={"faults": [{"site": "step", "op": "oom", "at_step": 2}]})
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        run_training(cfg)

    snaps = memwatch.read_oom_snapshots(str(out))
    assert len(snaps) == 1
    snap = snaps[0]
    assert snap["step"] == 2                     # steps 0,1 completed
    assert "RESOURCE_EXHAUSTED" in snap["error"]
    assert snap["error_type"] == "RuntimeError"
    assert snap["live"].get("host_rss_bytes", 0) > 0
    assert "train_step" in snap["memwatch"]["compiled"]
    assert snap["memwatch"]["recent"]            # the sampler's ring rode in
    # no checkpoint was attempted after the allocation failure
    assert not [d for d in os.listdir(out) if d.startswith("checkpoint-")]


# ---------------------------------------------------------------------------
# supervisor outcome + fleet alert + goodput section
# ---------------------------------------------------------------------------

def _super_cfg(out, **kw):
    import supervisor

    defaults = dict(output_dir=str(out), max_restarts=0, hang_timeout_s=5.0,
                    grace_s=1.0, crash_loop_threshold=3,
                    crash_loop_window_s=0.0, poll_s=0.05)
    defaults.update(kw)
    return supervisor.SupervisorConfig(**defaults)


def _super_ledger(out):
    import supervisor

    with open(os.path.join(str(out), supervisor.LEDGER_NAME)) as f:
        return [json.loads(l) for l in f]


def test_supervisor_labels_oom_outcome(tmp_path):
    """A crash whose OOM snapshot postdates the incarnation start is an
    `oom` outcome; a plain crash, or one with only a STALE snapshot from
    a previous life, stays `crash` (capacity problem vs transient).

    hang_timeout_s is far above the default 5 s here: the oom child imports
    the package (jax and all, 2-4.5 s on a loaded box), and a 5 s watchdog
    racing that import labelled the incarnation `hang` before the snapshot
    existed — a load-dependent flake, not a jax 0.9 behaviour change."""
    import sys

    import supervisor

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(memwatch.__file__))))
    oom_child = (
        "import sys; sys.path.insert(0, {root!r})\n"
        "from llama_pipeline_parallel_tpu.utils import memwatch\n"
        "memwatch.dump_oom_snapshot({out!r}, 3, "
        "'RESOURCE_EXHAUSTED: oom')\n"
        "sys.exit(9)\n")
    out = tmp_path / "oomed"
    cmd = [sys.executable, "-c",
           oom_child.format(root=root, out=str(out))]
    patient = dict(hang_timeout_s=60.0)
    rc = supervisor.Supervisor(cmd, _super_cfg(out, **patient)).run()
    assert rc == 2
    assert [r["outcome"] for r in _super_ledger(out)] == ["oom"]

    plain = tmp_path / "plain"
    rc = supervisor.Supervisor([sys.executable, "-c", "import sys; "
                                "sys.exit(9)"],
                               _super_cfg(plain, **patient)).run()
    assert rc == 2
    assert [r["outcome"] for r in _super_ledger(plain)] == ["crash"]

    stale = tmp_path / "stale"
    memwatch.dump_oom_snapshot(str(stale), 1, "RESOURCE_EXHAUSTED: old")
    old = time.time() - 3600
    d = memwatch.oom_dir(str(stale))
    for name in os.listdir(d):
        os.utime(os.path.join(d, name), (old, old))
    rc = supervisor.Supervisor([sys.executable, "-c", "import sys; "
                                "sys.exit(9)"],
                               _super_cfg(stale, **patient)).run()
    assert rc == 2
    assert [r["outcome"] for r in _super_ledger(stale)] == ["crash"]


def test_fleet_oom_recent_alert_fires_and_resolves(tmp_path):
    """The fleet surface: a snapshot newer than the member's registration
    sets `oom_recent` and fires the alert; the supervisor's relaunch
    re-registers with a newer ts and the alert resolves deterministically
    — recovery, not data loss, clears it."""
    from llama_pipeline_parallel_tpu.utils import fleet

    root = tmp_path / "fleet"
    os.makedirs(root)
    out = tmp_path / "trainer0"
    os.makedirs(out)
    now = time.time()

    def register(ts):
        with open(os.path.join(str(root), fleet.REGISTRY_NAME), "a") as f:
            f.write(json.dumps({
                "ts": ts, "role": None, "replica": "trainer0",
                "output_dir": os.path.abspath(str(out)), "pid": 1,
                "incarnation": 0, "health_file": "health.json"}) + "\n")

    def heartbeat():
        with open(os.path.join(str(out), "health.json"), "w") as f:
            json.dump({"time": time.time(), "last_step": 4}, f)

    register(now - 50)
    heartbeat()
    memwatch.dump_oom_snapshot(str(out), 4, "RESOURCE_EXHAUSTED: hbm")

    agg = fleet.FleetAggregator(str(root), fleet.AlertRules(oom_recent=0))
    status = agg.refresh()
    member = status["members"]["trainer:trainer0"]
    assert member["oom_snapshots"] == 1 and member["oom_recent"] == 1
    assert "oom_recent:trainer:trainer0" in status["pod"]["alerts_firing"]

    register(time.time() + 5)        # the relaunch re-registers
    heartbeat()
    status = agg.refresh()
    assert status["members"]["trainer:trainer0"]["oom_recent"] == 0
    assert status["pod"]["alerts_firing"] == []
    edges = fleet.read_alerts(str(root))
    assert [e["state"] for e in edges
            if e["alert"] == "oom_recent"] == ["firing", "resolved"]


def test_goodput_report_oom_section_and_degrade(tmp_path, capsys):
    import goodput_report

    out = tmp_path / "run"
    os.makedirs(out)
    with open(out / "spans.jsonl", "w") as f:
        for s in ({"name": "init", "ts": 0.0, "dur": 1.0, "end": 1.0,
                   "depth": 0, "parent": None, "main_thread": True},
                  {"name": "device_step", "ts": 1.0, "dur": 4.0, "end": 5.0,
                   "depth": 0, "parent": None, "main_thread": True,
                   "step": 2, "steps": 2}):
            f.write(json.dumps(s) + "\n")
    with open(out / "incarnations.jsonl", "w") as f:
        for r in ({"incarnation": 0, "outcome": "oom", "duration_s": 5.0},
                  {"incarnation": 1, "outcome": "crash", "duration_s": 2.0},
                  {"incarnation": 2, "outcome": "clean", "duration_s": 9.0}):
            f.write(json.dumps(r) + "\n")
    memwatch.dump_oom_snapshot(
        str(out), 7, "RESOURCE_EXHAUSTED: while allocating",
        extra={"live": {"device_peak_bytes": 3 << 30}})
    # a torn snapshot next to it contributes nothing, breaks nothing
    with open(os.path.join(memwatch.oom_dir(str(out)),
                           "oom-19990101-000000-1.json"), "w") as f:
        f.write('{"torn": ')

    rep = goodput_report.build_report(str(out))
    assert rep["incarnations"]["ooms"] == 1
    assert rep["oom"]["snapshots"] == 1
    event = rep["oom"]["events"][0]
    assert event["step"] == 7 and event["device_peak_gib"] == 3.0
    assert "RESOURCE_EXHAUSTED" in event["error"]
    goodput_report.print_report(rep)
    printed = capsys.readouterr().out
    assert "oom forensics" in printed and "1 oom(s)" in printed

    # no oom/ dir: the section is simply absent
    bare = tmp_path / "bare"
    os.makedirs(bare)
    with open(bare / "spans.jsonl", "w") as f:
        f.write(json.dumps({"name": "init", "ts": 0.0, "dur": 1.0,
                            "end": 1.0, "depth": 0, "parent": None,
                            "main_thread": True}) + "\n")
    rep = goodput_report.build_report(str(bare))
    assert rep["oom"] is None
    goodput_report.print_report(rep)
    assert "oom forensics" not in capsys.readouterr().out


# ---------------------------------------------------------------------------
# inspect_ckpt --sizes
# ---------------------------------------------------------------------------

def test_inspect_ckpt_sizes_and_degrade(tmp_path, capsys):
    import inspect_ckpt
    from llama_pipeline_parallel_tpu.ckpt.checkpoint import CheckpointManager
    from llama_pipeline_parallel_tpu.models.llama import model as llama
    from llama_pipeline_parallel_tpu.models.llama.manifest import (
        StageManifest,
    )
    from llama_pipeline_parallel_tpu.utils.metrics import param_count

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    man = StageManifest.for_config(cfg, 2)
    stacked = pl.stack_stages(llama.init_params(jax.random.PRNGKey(0), cfg),
                              man)
    root = tmp_path / "ckpt"
    mgr = CheckpointManager(str(root))
    mgr.save(1, stacked, man, cfg)

    out = inspect_ckpt.sizes(str(root), 1)
    assert out["total_gib"] >= 0 and out["trees"]
    assert sum(t["files"] for t in out["trees"].values()) > 0
    model = out["model"]
    assert model["param_count"] == param_count(cfg)
    assert model["params_gib"] == round(param_count(cfg) * 4 / (1 << 30), 3)
    assert "opt_state_gib" not in model          # module-only checkpoint
    if "stage_weight_gib" in model:
        assert len(model["stage_weight_gib"]) == 2

    rc = inspect_ckpt.main([str(root), "--sizes"])
    assert rc == 0
    assert '"sizes"' in capsys.readouterr().out

    # pre-elastic meta (no model_config): measured bytes only, with a verdict
    meta_path = os.path.join(mgr.step_dir(1), "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    del meta["model_config"]
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    out = inspect_ckpt.sizes(str(root), 1)
    assert isinstance(out["model"], str) and "unavailable" in out["model"]
    assert out["total_gib"] >= 0

    # no complete checkpoint: --sizes reports, exit code unaffected
    empty = tmp_path / "none"
    os.makedirs(empty)
    assert inspect_ckpt.main([str(empty), "--sizes"]) == 0
    assert "NO_CHECKPOINT" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# the anchored-estimate evidence, pinned (slow lane)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_memory_audit_anchored_evidence_pinned():
    """The per-buffer receipt behind preflight's anchored-estimate mode,
    at a reduced shape that still crosses the XLA-CPU cliff: the zb1
    stash store is exactly 2^31 elements at the as-written M=8 (flagged,
    residual jumps) while the anchor rung M=2 stays under it (no flags,
    residual tracks the closed-form terms) — the same evidence committed
    for the 65B shape in docs/PREFLIGHT.md "Memory audit"."""
    cfg = {
        "mesh": {"pp": 2},
        "model": {"vocab_size": 512, "hidden_size": 8192,
                  "intermediate_size": 1024, "num_hidden_layers": 2,
                  "num_attention_heads": 64, "max_position_embeddings": 512,
                  "dtype": "bfloat16"},
        "dataset": {"synthetic": True, "seq_length": 512},
        "per_device_train_batch_size": 64,
        "gradient_accumulation_steps": 8,
        "pipeline_schedule": "zb1",
        "attention": "exact",
        "seed": 0,
    }
    audit = preflight.memory_audit(cfg, top_buffers=4)
    assert audit["schedule"] == "zb1"
    rungs = {r["microbatches"]: r for r in audit["rungs"]}
    assert set(rungs) == {2, 4, 8}
    assert rungs[2]["anchor_rung"] and rungs[8]["as_written"]

    # the model's stash term scales closed-form with M...
    assert rungs[4]["stash_gib"] == 2 * rungs[2]["stash_gib"]
    assert rungs[8]["stash_gib"] == 2 * rungs[4]["stash_gib"]
    # ...and under 2^31 elements the compile tracks it: no flags, and the
    # residual moves far less than the stash term it subtracted
    for m in (2, 4):
        assert not any(b["over_2^31_elements"]
                       for b in rungs[m]["top_buffers"]), m
    small_drift = rungs[4]["residual_gib"] - rungs[2]["residual_gib"]
    assert abs(small_drift) < 2.0

    # the cliff: at M=8 the [M, mb, seq, hidden] stash store hits 2^31
    # elements, XLA-CPU materializes it f32 (the model charges bf16), the
    # attribution flags it, and the residual jumps past the small rungs'
    # drift — micro-2 matches the model, micro-8 over-counts
    flagged = [b for b in rungs[8]["top_buffers"] if b["over_2^31_elements"]]
    assert flagged
    assert flagged[0]["shape"] == [8, 64, 512, 8192]
    assert flagged[0]["dtype"] == "f32"
    jump = rungs[8]["residual_gib"] - rungs[4]["residual_gib"]
    assert jump > small_drift + 1.0
    # the printer renders the table + flag without tracebacks
    preflight.print_memory_audit(audit)
