"""The three readers of the serving engine's host thread (PR 50) and the
arithmetic they share (benchmark/host_stall.py), on synthetic observations
whose values are known: each ratio, the thread's partition, the split of the
stall records by cause and by phase, the join of a record against a capture's
idle time with the idle gaps no record covers, and None where there is
nothing to read."""

import json
import os

import pytest
from conftest import REPO

from benchmark import host_stall, registry

READERS = {"host_stall_share.serve": "program_span",
           "gc_pause_share.serve": "program_counter",
           "host_bound_tick_share.serve": "program_counter"}
CELLS = ["serve-closed-16.deepseek", "serve-closed-64.solar-open2",
         "serve-reason-64.nemotron3-super", "serve-mixed-64.mimo-v2-flash"]
LAYER = "serving engine host thread"
WALL = 1_790_000_000.0          # the window's start on the wall clock
OFFSET_US = 250.0 - WALL * 1e6  # profiler clock - wall clock


def _read(name, obs):
    return registry.load_layer_metric(REPO, name).read(obs)


def _span(ts, **fields):
    base = {"name": "serve_decode_step", "ts": WALL + ts, "ticks": 32,
            "admit_s": 0.010, "stage_s": 0.004, "dispatch_s": 0.020,
            "wait_s": 0.080, "unit_wait_s": 0.002, "emit_s": 0.008,
            "loop_s": 0.015, "step_s": 0.140, "block_s": 0.070, "steps": 32,
            "gc_s": 0.0, "compile_s": 0.0, "wait_gc_s": 0.0,
            "gc_collections": 5, "gc_gen2": 0, "compiles": 0,
            "ticks_found_ready": 1, "gc_longest_s": 0.0004,
            "stalls": [], "stalls_dropped": 0}
    return {**base, **fields}


def _record(at, dur, phase, **fields):
    return {"phase": phase, "ts": WALL + at, "dur": dur, "step": 7,
            "active": 16, "units": 0, **fields}


HOST_STALL = _record(
    1.0, 0.100, "serve_prefill_enqueue", gc_s=0.005, compile_s=0.080,
    other_s=0.015)
WAIT_STALL = _record(
    2.0, 0.050, "serve_tick_block", in_wait=1, wait_gc_s=0.050, other_s=0.0)


def _obs(spans, xplane=None):
    return {"kind": "serve", "spans": spans, "window": (WALL, WALL + 10.0),
            "xplane": xplane}


@pytest.fixture
def spans():
    return [
        _span(0.5, stalls=[HOST_STALL], compile_s=0.080, compiles=1),
        _span(1.5, stalls=[WAIT_STALL], wait_gc_s=0.045, gc_s=0.005,
              gc_gen2=1, gc_longest_s=0.045, stalls_dropped=2),
        {"name": "serve_prefill", "ts": WALL + 1.0, "dur": 0.1},
        # a line of a build before PR 50 is passed over
        {"name": "serve_decode_step", "ts": WALL + 3.0, "ticks": 32,
         "wait_s": 0.1}]


def test_each_reader_agrees_with_its_benchmark_entry_by_membership():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for name, source in READERS.items():
        assert name in entries                  # wherever in the list it is
        entry, reader = entries[name], registry.load_layer_metric(REPO, name)
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            entry["layer"], entry["unit"], entry["moves"], entry["source"])
        assert (entry["layer"], entry["source"]) == (LAYER, source)
        assert (entry["unit"], entry["better"]) == ("%", "lower")
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        # the cells that report the metric it moves, every one of them
        assert entry["moves"] == "serve_tokens_per_s"
        assert sorted(entry["workloads"]) == sorted(CELLS) == sorted(
            e2e["serve_tokens_per_s"]["workloads"])
        for cell in CELLS:
            assert name in registry.load_cell(REPO, cell).per_layer
        for cell in ("serve-long-32.dots3", "serve-longdoc-32.a.x-k1",
                     "serve-bytes-16.evabyte", "train-sft-4k.mistral-d2"):
            assert name not in registry.load_cell(REPO, cell).per_layer
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    assert LAYER in perf and all(f"`{n}`" in perf for n in READERS)


@pytest.mark.parametrize("name", sorted(READERS))
def test_nothing_to_read_is_none(name):
    assert _read(name, {"kind": "none"}) is None
    assert _read(name, {"kind": "train", "spans": [], "window": (0, 1)}) is None
    # a program before PR 50: spans without the thread's account
    old = _obs([{"name": "serve_decode_step", "ts": WALL + 1.0, "ticks": 32,
                 "stage_s": 0.1, "dispatch_s": 0.1, "wait_s": 0.1,
                 "emit_s": 0.1}])
    assert _read(name, old) is None
    assert _read(name, _obs([])) is None


def test_the_three_values(spans, capsys):
    obs = _obs(spans)
    assert _read("host_stall_share.serve", obs) == pytest.approx(
        100.0 * (0.100 + 0.050) / 10.0)
    out = capsys.readouterr().out
    assert "2 record(s), 0.1500 s of 10.000 (2 more dropped" in out
    assert "collector 0.0550, compiler 0.0800, other 0.0150" in out
    assert "serve_prefill_enqueue 0.1000, serve_tick_block 0.0500" in out
    assert "100.0 ms under serve_prefill_enqueue at" in out
    assert "50.0 ms under serve_tick_block (in a device wait)" in out
    assert "unaccounted=0.71%" in out            # 0.280 - 0.278 of 0.280
    assert "the host works 48.57% of its thread" in out
    assert " traced: " not in out               # no capture, no join
    assert "a stop of the process while the thread slept" in out
    assert ("outside the waits: gc_s 0.0050 in 10 collections (1 full); "
            "inside them: wait_gc_s 0.0450; compile_s 0.0800 in 1 programs"
            ) in out
    assert _read("gc_pause_share.serve", obs) == pytest.approx(
        100.0 * (0.005 + 0.045) / 10.0)
    out = capsys.readouterr().out
    assert "outside the device waits 0.0050 s" in out
    assert "1 of generation 2" in out and "longest pause so far 45.00 ms" in out
    assert _read("host_bound_tick_share.serve", obs) == pytest.approx(
        100.0 * 2 / 64)


def test_no_record_reads_zero_and_a_missing_name_is_not_known():
    quiet = _obs([_span(0.5), _span(1.5)])
    assert _read("host_stall_share.serve", quiet) == 0.0
    assert _read("gc_pause_share.serve", quiet) == 0.0
    assert _read("host_bound_tick_share.serve", quiet) == pytest.approx(
        100.0 / 32)
    # a name no span carries is not known, never 0
    bare = {k: v for k, v in _span(0.5).items() if k != "wait_gc_s"}
    assert "wait_gc_s" not in host_stall.account([bare])
    assert host_stall.share_of_window(_obs([bare]), "gc_s", "wait_gc_s") is None
    assert host_stall.share_of_window(_obs([bare]), "gc_s") == 0.0
    assert host_stall.found_ready_share(_obs([dict(bare, ticks=0)])) is None


def test_the_account_and_the_split(spans):
    acc = host_stall.account(host_stall.account_spans(_obs(spans)))
    assert acc["spans"] == 2 and acc["step_s"] == pytest.approx(0.280)
    assert host_stall.unaccounted_s(acc) == pytest.approx(0.002)
    assert host_stall.host_share(acc) == pytest.approx(
        100.0 * (0.280 - 0.140 - 0.004) / 0.280)
    assert acc["gc_longest_s"] == 0.045 and acc["stalls_dropped"] == 2
    records = host_stall.stalls_of(host_stall.account_spans(_obs(spans)))
    assert [r["phase"] for r in records] == ["serve_prefill_enqueue",
                                             "serve_tick_block"]
    by_cause, by_phase = host_stall.split(records)
    assert by_cause == pytest.approx({"collector": 0.055, "compiler": 0.080,
                                      "other": 0.015})
    assert by_phase == pytest.approx({"serve_prefill_enqueue": 0.100,
                                      "serve_tick_block": 0.050})
    assert host_stall.account([]) is None


def _capture(stopped=False):
    """One device plane busy but for [1.0, 1.1) s and [2.0, 2.045) s of the
    window (and, with `stopped`, [3.2, 3.9) s under a tick's wait, which no
    record covers), on a profiler clock 250 us ahead of (wall clock - WALL);
    the runtime's own events over the first gap."""
    at = lambda seconds: int(seconds * 1e9 + 250_000)
    ops = [("fusion.1", at(0.5), at(1.0)), ("fusion.2", at(1.1), at(2.0)),
           ("fusion.3", at(2.045), at(3.2 if stopped else 3.9)),
           ("fusion.4", at(3.9), at(4.5))]
    host = [("serve_prefill_enqueue", at(0.9995), at(1.0995)),
            ("DeferredTpuAllocator::Allocate", at(1.01), at(1.08)),
            ("ReadSyncFlag", at(1.001), at(1.021)),
            ("ReadSyncFlag", at(1.05), at(1.06)),
            ("ExecuteHelperOnSingleDevice", at(3.0), at(3.1)),
            ("py_gc gen=2", at(1.955), at(2.0)),
            ("serve_tick_block", at(1.9), at(2.0451))]
    if stopped:
        host += [("serve_tick_wait", at(3.19), at(3.93)),
                 ("serve_tick_block", at(3.191), at(3.9)),
                 ("serve_tick_fetch", at(3.9), at(3.93))]
    host += [(f"wallclock_us={int((WALL + s) * 1e6)}", at(s), at(s))
             for s in (0.6, 1.6, 2.6, 3.6)]
    return {"devices": {"/device:TPU:0": ops}, "host": host}


def test_a_record_against_the_capture(spans, capsys):
    # the wait's record as the engine places it: at the END of the wait
    waited = dict(WAIT_STALL, ts=WALL + 2.045 - 0.050)
    spans[1] = dict(spans[1], stalls=[waited])
    obs = _obs(spans, _capture())
    records = host_stall.stalls_of(host_stall.account_spans(obs))
    joined = host_stall.join(obs, records)
    assert joined["clock"]["offset_us"] == pytest.approx(OFFSET_US, abs=1.0)
    assert joined["idle_ns"] == pytest.approx(0.145e9, abs=2e3)
    assert joined["idle_in_stalls_ns"] == pytest.approx(0.145e9, abs=2e3)
    first, second = joined["records"]
    assert first["idle_ns"] == pytest.approx(0.100e9, abs=2e3)
    assert first["gap_ns"] == pytest.approx(0.100e9, abs=2e3)
    assert abs(first["gap_starts_ns"]) < 2e3    # the gap starts where it does
    assert abs(first["gap_ends_ns"]) < 2e3      # and ends where it does
    assert [n for n, _ in first["runtime"]] == [
        "DeferredTpuAllocator::Allocate", "ReadSyncFlag"]
    assert first["runtime"][1][1] == pytest.approx(0.030e9, abs=2e3)
    assert second["idle_ns"] == pytest.approx(0.045e9, abs=2e3)
    assert abs(second["gap_ends_ns"]) < 2e3     # it ends where the wait does
    assert second["runtime"] == []              # `py_gc`, `serve_*` are our own
    # three events began in the first record's 0.1 s; the capture's eleven
    # over its 3.0 s of host events would put 0.37 there
    assert first["begun"] == (3, pytest.approx(11 * 0.1 / 3.0, rel=1e-3))
    assert second["begun"][0] == 0
    assert _read("host_stall_share.serve", obs) == pytest.approx(1.5)
    out = capsys.readouterr().out
    assert "traced: 2 record(s) inside the capture" in out
    assert "idle ms lie inside one (100.0%)" in out
    assert "DeferredTpuAllocator::Allocate 70.000, ReadSyncFlag 30.000" in out
    assert "began inside it: 3 (the capture's mean rate would give 0)" in out
    assert joined["uncovered"] == [] and "NO RECORD" not in out
    # a record outside the capture is not joined; no anchor, no join
    late = dict(HOST_STALL, ts=WALL + 8.0)
    assert host_stall.join(obs, [late])["records"] == []
    no_anchor = dict(_capture(), host=[])
    assert host_stall.join(_obs(spans, no_anchor), records) is None
    assert host_stall.join(_obs(spans), records) is None


def test_an_idle_gap_no_record_covers_is_printed_beside_them(spans, capsys):
    """The process stopped while the thread slept in a tick's wait: 0.7 s of
    idle device and no record of its own, only one of the 30 ms that follow
    it on the host. The share cannot see it; the traced join says so."""
    after = _record(3.9, 0.030, "serve_tick_fetch", gc_s=0.0, compile_s=0.0,
                    other_s=0.030)
    waited = dict(WAIT_STALL, ts=WALL + 2.045 - 0.050)
    spans[1] = dict(spans[1], stalls=[waited, after])
    obs = _obs(spans, _capture(stopped=True))
    records = host_stall.stalls_of(host_stall.account_spans(obs))
    joined = host_stall.join(obs, records)
    (gap,) = joined["uncovered"]               # the two short ones: records
    assert gap["gap_ns"] == pytest.approx(0.7e9, abs=2e3)
    assert gap["start_ns"] == pytest.approx(2.7e9, abs=2e3)
    assert gap["under"] == "serve_tick_block"   # the innermost of the two
    assert gap["begun"][0] == 1                 # the anchor at 3.6 s
    # the 45 ms gap has no record now either
    assert host_stall.join(obs, [HOST_STALL])["uncovered"][1]["gap_ns"] == (
        pytest.approx(0.045e9, abs=2e3))
    # a record over most of a gap covers it
    whole = _record(3.25, 0.68, "serve_tick_emit", other_s=0.68)
    assert host_stall.join(obs, records + [whole])["uncovered"] == []
    assert _read("host_stall_share.serve", obs) == pytest.approx(1.8)
    out = capsys.readouterr().out
    assert ("traced: NO RECORD covers an idle gap of 700.000 ms at 2.700 s "
            "of the capture, under serve_tick_block") in out
