"""tools/trace_summary.py: the operator's command over a `profile_steps` or
`tools/serve.py` capture, on a synthetic `.xplane.pb` (the benchmark's own
reduction does the reading: tests/benchmark_harness)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmark_harness"))

import synthetic_xplane as sx  # noqa: E402
import trace_summary  # noqa: E402  (tools/ is on the path: tests/conftest.py)

TICK = "jit(paged_decode_step)/while/body/closed_call/"


def _op(name, path, start, dur):
    return (sx.instruction(name), path, start, dur)


@pytest.fixture
def capture(tmp_path):
    """One chip, 200 ns: two ticks with a host-owned gap between them."""
    ops = [_op("fusion.1", TICK + "kv_gather/gather", 0, 50),
           _op("fusion.2", TICK + "decode_mlp/cast_weights/convert", 50, 10),
           _op("fusion.1", TICK + "kv_gather/gather", 100, 60),
           _op("copy.9", None, 160, 40)]
    host = {"python": [("serve_tick_wait", None, 0, 62),
                       ("serve_tick_stage", None, 62, 33),
                       ("serve_tick_wait", None, 95, 100)]}
    run = tmp_path / "plugins" / "profile" / "2026_01_01"
    run.mkdir(parents=True)
    sx.write(run / "host.xplane.pb", {"/device:TPU:0": {"XLA Ops": ops},
                                      "/host:CPU": host})
    return str(tmp_path)


def test_summary_has_busy_idle_and_both_tables(capture):
    s = trace_summary.summarize(
        trace_summary.xplane.find_xplane(capture), top=3)
    assert s["chips"] == 1
    assert s["window_s"] == pytest.approx(200e-9)
    assert s["idle_percent"] == pytest.approx(20.0)
    assert s["by_class"] == pytest.approx({"forward": 75.0, "other": 25.0})
    assert s["by_scope"] == pytest.approx(
        {"kv_gather": 68.75, "cast_weights": 6.25, "(no scope)": 25.0})
    assert s["scoped_percent"] == pytest.approx(75.0)


def test_top_operations_and_gap_owners(capture):
    s = trace_summary.summarize(
        trace_summary.xplane.find_xplane(capture), top=2)
    assert [name for name, _ in s["top_ops"]] == ["fusion.1", "copy.9"]
    assert s["top_ops"][0][1] == pytest.approx(110e-9)
    assert s["idle_gaps"][0] == ["serve_tick_stage", pytest.approx(40e-9)]


def test_command_prints_every_section(capture, capsys):
    trace_summary.main([capture, "--top", "2"])
    out = capsys.readouterr().out
    assert "idle 20.000%" in out and "75.0% of busy time under a named scope" in out
    for heading in ("by class", "by scope", "most device time",
                    "longest idle gaps"):
        assert heading in out
    assert "kv_gather" in out and "serve_tick_stage" in out
    # a capture of a build without the nested events: what lies under
    # `serve_tick_wait` is one part, and there is no anchor to read
    assert "wait_other" in out and "no wall-clock anchor" in out


def test_a_serving_capture_prints_the_tick_gap_and_the_clock(tmp_path, capsys):
    """Two ticks whose dispatch and wait hold the nested events, two
    anchors: the partition a tick, launch apart from wake, and the offset."""
    ms = 1_000_000
    ops = [_op("fusion.1", TICK + "kv_gather/gather", 0 * ms, 1 * ms),
           _op("fusion.1", TICK + "kv_gather/gather", 6 * ms, 4 * ms),
           _op("fusion.1", TICK + "kv_gather/gather", 16 * ms, 4 * ms)]
    host = {"python": [
        (name, None, int((at + lo) * ms), int((hi - lo) * ms))
        for at in (0, 10)
        for name, lo, hi in (("serve_tick_stage", 1, 2),
                             ("serve_tick_dispatch", 2, 5),
                             ("serve_tick_h2d", 2, 3),
                             ("serve_tick_enqueue", 3, 5),
                             ("serve_tick_wait", 5, 11),
                             ("serve_tick_block", 5, 10.5),
                             ("serve_tick_fetch", 10.5, 11))] + [
        (f"wallclock_us={1_790_000_000_000_000 + at}", None, at * 1000 + 250, 0)
        for at in (1_500, 11_500)]}
    sx.write(tmp_path / "serve.xplane.pb", {"/device:TPU:0": {"XLA Ops": ops},
                                            "/host:CPU": host})
    s = trace_summary.summarize(trace_summary.xplane.find_xplane(str(tmp_path)))
    part = s["tick_gap"]
    assert part["ticks"] == 2 and part["idle_ns"] == 11 * ms
    assert {k: v / ms for k, v in part["parts_ns"].items() if v} == {
        "stage": 2, "h2d": 2, "enqueue": 4, "launch": 2, "wake": 0.5,
        "fetch": 0.5}
    assert s["clock"]["offset_us"] == pytest.approx(-1.79e15 + 0.25, abs=1.0)
    trace_summary.main([str(tmp_path)])
    out = capsys.readouterr().out
    assert "idle time a serving tick" in out and "5.500 ms a tick" in out
    assert "1.000 ms  launch" in out and "0.250 ms  wake" in out
    assert "over 2 anchors, spread 0.0 us" in out


def test_newest_capture_wins(capture, tmp_path):
    later = tmp_path / "plugins" / "profile" / "2026_01_02"
    later.mkdir()
    path = sx.write(later / "host.xplane.pb", {"/device:TPU:0": {"XLA Ops": [
        _op("fusion.7", "jit(train_step)/optimizer/mul", 0, 10)]}})
    os.utime(path, (2e9, 2e9))
    assert trace_summary.xplane.find_xplane(capture) == path
    assert trace_summary.summarize(path)["by_class"] == {"optimizer": 100.0}


def test_a_directory_without_a_capture_is_a_readable_verdict(tmp_path):
    with pytest.raises(SystemExit, match="no .xplane.pb under"):
        trace_summary.main([str(tmp_path)])


def test_a_capture_with_no_device_operation_says_so(tmp_path):
    """A CPU run's capture has host events only."""
    sx.write(tmp_path / "cpu.xplane.pb", {"/host:CPU": {"python": [
        ("device_step", None, 0, 10)]}})
    with pytest.raises(SystemExit, match="holds no device operation"):
        trace_summary.main([str(tmp_path)])


def test_the_tick_in_flight_is_summed_from_the_runs_spans(capture, tmp_path,
                                                          capsys):
    """`ticks_ahead`, `rows_overrun` and `rows_joined_fed` come from
    `spans.jsonl`, found beside or above the capture (or named), the two
    ratios of the tick's paged attention from the lines that carry
    `kv_steps_visited`, and the
    prefill units' `ahead` and `reads` from its `serve_prefill` lines, with
    the chunks never run (`chunks_skipped` on a request's first unit); lines
    of other spans and of a build that does not carry them are passed over,
    and a capture with no such file prints no such section."""
    import json

    trace_summary.main([capture])
    assert "tick in flight" not in capsys.readouterr().out
    lines = [{"name": "serve_decode_step", "ticks": 32, "ticks_ahead": 31,
              "tokens": 500, "rows_overrun": 0},
             {"name": "serve_prefill", "ticks": 7},
             {"name": "serve_decode_step", "ticks": 5, "tokens": 80},
             {"name": "serve_decode_step", "ticks": 8, "ticks_ahead": 8,
              "tokens": 100, "rows_overrun": 2, "rows_joined_fed": 3,
              # rows of 536 pages walked 5 a step: 108 steps a whole table
              "kv_pages_live": 9800, "kv_pages_table": 53600,
              "kv_steps_visited": 2000, "kv_pages_per_step": 5},
             {"name": "serve_prefill", "ahead": 1, "reads": 1,
              "chunks_skipped": 2},
             {"name": "serve_prefill", "ahead": 1, "reads": 0},
             {"name": "serve_prefill", "ahead": 0, "reads": 1,
              "chunks_skipped": 0},
             {"name": "serve_request", "ahead": 1, "reads": 1,
              "chunks_skipped": 9}]
    spans = tmp_path / "spans.jsonl"
    spans.write_text("".join(json.dumps(r) + "\n" for r in lines))
    assert trace_summary.find_spans(
        str(tmp_path / "plugins" / "profile")) == str(spans)
    assert trace_summary.tick_pipeline(str(spans)) == {
        "ticks": 40, "ticks_ahead": 39, "tokens": 600, "rows_overrun": 2,
        "rows_joined_fed": 3}
    assert trace_summary.unit_pipeline(str(spans)) == {
        "units": 3, "ahead": 2, "reads": 2, "skipped": 2}
    assert trace_summary.kv_steps(str(spans)) == {
        "pages_live": 9800, "steps_visited": 2000, "steps_table": 10800}
    trace_summary.main([capture])
    out = capsys.readouterr().out
    assert "ticks_ahead 39 of 40 ticks (97.50%)" in out
    assert "rows_overrun 2 of 600 row-ticks" in out
    assert "rows_joined_fed 3" in out
    assert "kv_pages_live / kv_steps_visited 4.90 pages a step" in out
    assert "kv_steps_visited 2000 of 10800 steps" in out and "(0.185)" in out
    assert "units ahead 2 of 3 (66.67%)" in out
    assert "reads 2 (0.67 a unit)" in out
    assert "units run 3 / skipped 2 (40.00% of both" in out
    elsewhere = tmp_path / "elsewhere.jsonl"
    elsewhere.write_text(json.dumps(lines[1]) + "\n")
    trace_summary.main([capture, "--spans", str(elsewhere)])
    assert "tick in flight" not in capsys.readouterr().out


def test_the_engine_threads_account_and_its_stall_records(tmp_path, capsys):
    """From the run's `spans.jsonl` alone: the partition, what held the
    thread, the ratios as the benchmark's readers compute them and the
    records longest first; with a capture (a device idle from 1 to 6 ms, two
    anchors), the record that lies over the gap beside the idle time in it;
    a gap no record covers is short of 20 ms there and is not printed."""
    import json

    wall = 1_790_000_000.0
    base = {"name": "serve_decode_step", "ticks": 32, "steps": 32,
            "admit_s": 0.001, "stage_s": 0.001, "dispatch_s": 0.002,
            "wait_s": 0.004, "unit_wait_s": 0.0, "emit_s": 0.001,
            "loop_s": 0.0009, "step_s": 0.010, "block_s": 0.003,
            "gc_s": 0.0002, "compile_s": 0.0, "wait_gc_s": 0.0,
            "gc_collections": 3, "gc_gen2": 0, "compiles": 0,
            "ticks_found_ready": 4, "gc_longest_s": 0.0001,
            "stalls": [], "stalls_dropped": 0}
    # on the profiler's clock of the capture below: [1.0, 6.0) ms
    stall = {"phase": "serve_tick_emit", "ts": wall + 0.001 - 0.25e-6,
             "dur": 0.005, "gc_s": 0.004, "compile_s": 0.0,
             "other_s": 0.001, "step": 3, "active": 2, "units": 0}
    short = dict(stall, phase="loop", ts=wall + 0.5, dur=0.002, gc_s=0.0,
                 other_s=0.002)
    lines = [dict(base, ts=wall, stalls=[stall]),
             dict(base, ts=wall + 0.010, stalls=[short]),
             {"name": "serve_decode_step", "ts": wall + 5.0, "ticks": 3}]
    spans = tmp_path / "spans.jsonl"
    spans.write_text("".join(json.dumps(r) + "\n" for r in lines))
    found = trace_summary.host_thread(str(spans))
    assert found["account"]["spans"] == 2
    assert found["window_s"] == pytest.approx(0.020)
    assert found["ratios"] == pytest.approx({
        "host_stall_share.serve": 100.0 * 0.007 / 0.020,
        "gc_pause_share.serve": 100.0 * 0.0004 / 0.020,
        "host_bound_tick_share.serve": 100.0 * 8 / 64})
    assert [r["phase"] for r in found["records"]] == ["serve_tick_emit",
                                                      "loop"]
    assert found["joined"] is None
    trace_summary.main([str(tmp_path)])         # spans alone: no capture
    out = capsys.readouterr().out
    assert "no capture under" in out and "the engine's thread" in out
    assert "unaccounted=1.00%" in out and "host_stall_share.serve 35.0000%" in out
    assert "5.0 ms under serve_tick_emit at" in out
    assert "ms by cause: collector 4.0, other 1.0" in out
    assert out.index("under serve_tick_emit") < out.index("under loop")
    # the capture of the test above: idle from 1 to 6 ms and from 10 to 16
    ms = 1_000_000
    ops = [_op("fusion.1", TICK + "kv_gather/gather", 0 * ms, 1 * ms),
           _op("fusion.1", TICK + "kv_gather/gather", 6 * ms, 4 * ms),
           _op("fusion.1", TICK + "kv_gather/gather", 16 * ms, 4 * ms)]
    host = {"python": [("serve_tick_wait", None, 5 * ms, 6 * ms),
                       ("serve_tick_emit", None, 1 * ms, 5 * ms)] + [
        (f"wallclock_us={1_790_000_000_000_000 + at}", None, at * 1000 + 250, 0)
        for at in (1_500, 11_500)]}
    sx.write(tmp_path / "serve.xplane.pb", {"/device:TPU:0": {"XLA Ops": ops},
                                            "/host:CPU": host})
    trace_summary.main([str(tmp_path)])
    out = capsys.readouterr().out
    assert "1 record(s) inside the capture" in out
    assert "device idle inside it 5.000 ms" in out
    assert "5.000 of the first device plane's 11.000 idle ms" in out
    assert "NO RECORD" not in out               # the other gap is 6 ms
