"""The perf ledger + calibration loop (utils/perf.py,
tools/perf_report.py, preflight --calibration —
docs/OBSERVABILITY.md "Perf ledger & calibration").

Pins: the bench-summary -> rows conversion (model-vs-measured pairs,
probe-failure rounds as reason-tagged rows, the repo's own BENCH_r0*
history summarizing as "N rounds unreachable"); the reader's
degrade-don't-traceback contract; the report CLI (table + failure
summary + --emit-calibration); and the acceptance pin — a calibration
file distilled from a measured starved host link makes
`preflight --select --calibration` re-rank the schedule frontier away
from the offload winner the uncalibrated CLI defaults pick."""

import argparse
import json

import pytest

import perf_report  # tools/ on sys.path via conftest
import preflight

from llama_pipeline_parallel_tpu.parallel import pipeline as pl
from llama_pipeline_parallel_tpu.utils import perf

BENCH_SUMMARY = {
    "metric": "tokens_per_sec_per_chip", "value": 1234.5, "mfu": 0.31,
    "best_config": "remat=0,attn=exact,bs=32",
    "all_configs": {
        "remat=0,attn=exact,bs=32": {"ms": 100.0, "tok_s": 1234.5},
        "extra:sched-zb1,pp=4": {
            "ms": 250.0, "tok_s": 90.0,
            "detail": {"schedule": "zb1", "bubble_fraction_analytic": 0.009}},
        "extra:layout-pp4tp2dp1sp1": {
            "ms": 300.0, "tok_s": 80.0,
            "detail": {"layout": "pp4tp2dp1sp1", "score_s_model": 0.28}},
        "extra:offload-bw": {
            "ms": 50.0, "tok_s": 0.0,
            "detail": {"d2h_gibps": 21.0, "h2d_gibps": 24.0,
                       "probe_mib": 256, "pinned_host": True}},
        "extra:offload-wgrad-stash,pp=4": {
            "ms": 260.0, "tok_s": 88.0,
            "detail": {"transfer_ms_model": 12.0,
                       "transfer_stall_ms": 15.5}},
        "extra:kernel-ce,bs=32": {
            "ms": 90.0, "tok_s": 1300.0,
            "detail": {"bytes_model_gib": 2.0, "saved_ms": 10.0,
                       "achieved_gibps": 200.0}},
    },
}


# ---------------------------------------------------------------------------
# rows + readers
# ---------------------------------------------------------------------------

def test_rows_from_bench_summary_pairs():
    rows = perf.rows_from_bench_summary(BENCH_SUMMARY, run="r1")
    by_metric = {r["metric"]: r for r in rows}
    assert by_metric["mfu"]["measured"] == 0.31
    # headline sweep rows contribute nothing; extras all do
    assert "step_s:remat=0,attn=exact,bs=32" not in by_metric
    lay = by_metric["step_s:extra:layout-pp4tp2dp1sp1"]
    assert lay["model"] == 0.28 and lay["measured"] == pytest.approx(0.3)
    assert by_metric["host_bw_gibps"]["measured"] == 21.0  # min(d2h, h2d)
    tr = by_metric["transfer_ms:extra:offload-wgrad-stash,pp=4"]
    assert tr["model"] == 12.0 and tr["measured"] == 15.5
    assert by_metric["bubble_fraction:extra:sched-zb1,pp=4"]["model"] == 0.009
    assert by_metric["kernel_bw_gibps:extra:kernel-ce,bs=32"][
        "measured"] == 200.0


def test_error_round_becomes_failure_row():
    rows = perf.rows_from_bench_summary(
        {"metric": "tokens_per_sec_per_chip", "value": 0.0,
         "error": "no usable accelerator: device probe did not respond"},
        run="BENCH_r05")
    assert len(rows) == 1 and rows[0]["reason"].startswith("no usable")


def test_read_ledger_degrades(tmp_path):
    assert perf.read_ledger(str(tmp_path / "absent.jsonl")) == []
    p = tmp_path / "perf.jsonl"
    p.write_text("")
    assert perf.read_ledger(str(p)) == []
    p.write_text('garbage\n{"metric": "mfu", "measured": 0.3}\n'
                 '{"not_a_row": 1}\n{"metric": "x", "mea')
    rows = perf.read_ledger(str(p))
    assert len(rows) == 1 and rows[0]["metric"] == "mfu"


def test_append_and_report_roundtrip(tmp_path, capsys):
    path = tmp_path / "perf.jsonl"
    n = perf.append_rows(str(path), perf.rows_from_bench_summary(
        BENCH_SUMMARY, run="r1"))
    assert n > 0
    calib_path = tmp_path / "calib.json"
    perf_report.main([str(path), "--emit-calibration", str(calib_path)])
    out = capsys.readouterr().out
    assert "host_bw_gibps" in out and "mfu" in out
    calib = json.loads(calib_path.read_text())
    assert calib["host_bw_gibps"] == 21.0 and calib["mfu"] == 0.31
    # run-dir spelling reads <dir>/perf.jsonl
    perf_report.main([str(tmp_path)])
    assert "host_bw_gibps" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# preflight --calibration
# ---------------------------------------------------------------------------

def test_load_calibration_degrades(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{torn")
    with pytest.raises(SystemExit, match="not readable JSON"):
        preflight.load_calibration(str(bad))
    not_obj = tmp_path / "list.json"
    not_obj.write_text("[1, 2]")
    with pytest.raises(SystemExit, match="not a JSON object"):
        preflight.load_calibration(str(not_obj))
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"generated_at": 1.0, "rows_used": 0,
                                 "mfu": None, "host_bw_gibps": "n/a"}))
    assert preflight.load_calibration(str(empty)) == {}


def test_calibration_rerank_pinned(tmp_path):
    """THE acceptance pin: at the 65B pp8 shape with the CLI defaults
    (30 GiB/s host link) --select picks zb1 + wgrad offload; a ledger
    whose measured host bandwidth is a starved 0.5 GiB/s distills into a
    calibration file that re-ranks the SAME frontier to interleaved —
    offload refused analytically from the MEASUREMENT, not the guess."""
    dims = pl.stash_dims(8, 512, 1, 8192, "bfloat16")
    cands = preflight.enumerate_candidates(8, 256, 80)
    compute = lambda pcfg: 60.0

    def pick(bw):
        winner, _ = preflight.select_schedule(cands, 70.0, dims, 95.0, bw,
                                              compute)
        return winner

    # a measured starved link lands in the ledger...
    ledger = tmp_path / "perf.jsonl"
    perf.append_rows(str(ledger), [
        perf.make_row("host_bw_gibps", measured=0.5, unit="GiB/s",
                      source="bench", run="r1")])
    calib = perf.derive_calibration(perf.read_ledger(str(ledger)))
    calib_path = tmp_path / "calib.json"
    calib_path.write_text(json.dumps(calib))

    # ...and flows through the --calibration arg surface
    args = argparse.Namespace(mfu=0.45, host_bw_gibps=30.0,
                              ici_bw_gibps=90.0)
    applied = preflight.apply_calibration(args, str(calib_path))
    assert applied == {"host_bw_gibps": 0.5}
    assert args.host_bw_gibps == 0.5 and args.mfu == 0.45  # absent key kept

    uncalibrated = pick(30.0)
    calibrated = pick(args.host_bw_gibps)
    assert uncalibrated["schedule"] == "zb1" and uncalibrated["offload_wgrad"]
    assert calibrated["schedule"] == "interleaved_1f1b"
    assert not calibrated["offload_wgrad"]


def test_bench_ledger_writer(tmp_path, monkeypatch):
    """bench.py's _write_ledger: summary -> rows; budget skips -> reason
    rows."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(os.path.dirname(preflight.__file__),
                                  os.pardir, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    path = tmp_path / "perf.jsonl"
    monkeypatch.setenv("BENCH_RUN_LABEL", "round-x")
    bench._write_ledger(str(path), BENCH_SUMMARY, ["serve"])
    rows = perf.read_ledger(str(path))
    assert any(r["metric"] == "mfu" for r in rows)
    skip = [r for r in rows if r["metric"] == "bench_row_family"]
    assert len(skip) == 1 and "serve" in skip[0]["reason"]
    assert all(r["run"] == "round-x" for r in rows)
    # the rows are stamped with THIS process's backend (cpu under the test
    # mesh) — and cpu-stamped measurements must never calibrate preflight's
    # TPU model constants (a CPU smoke's mfu/host-bw are about the wrong
    # hardware)
    mfu_row = next(r for r in rows if r["metric"] == "mfu")
    assert mfu_row["context"]["backend"] == "cpu"
    calib = perf.derive_calibration(rows)
    assert "mfu" not in calib and "host_bw_gibps" not in calib
    # an unstamped mfu below the 0.01 sanity floor is dropped too
    assert "mfu" not in perf.derive_calibration(
        [perf.make_row("mfu", measured=1e-4)])

    # a None path is a no-op, never an error
    bench._write_ledger(None, BENCH_SUMMARY, [])


