"""The tick-gap partition (benchmark/tick_gap.py), the wall-clock anchor and
the four readers of PR 34, on synthetic traces whose layout is known: every
part exact, `launch` / `between_ops` / `wake` in a wait with one program, with
two, and with none, parts that sum to the value; the clock offset recovered
from anchors; None where there is nothing to read."""

import json
import os
import time

import benchmark_tiny
import jax
import pytest
import synthetic_xplane as sx
from conftest import REPO
from test_benchmark_layer_readers import (  # noqa: F401  (fixtures)
    _observe,
    runs,
    serve_obs,
    train_obs,
)

from benchmark import harness, registry, tick_gap, xplane
from llama_pipeline_parallel_tpu.utils import trace as program_trace

READERS = ["tick_gap_ms.serve", "tick_gap_launch_wake_ms.serve",
           "tick_h2d_ms.serve", "tick_enqueue_ms.serve"]
CELLS = ["serve-closed-16.deepseek", "serve-long-32.dots3"]
# serving cells whose per-layer lists are pinned by accepted tests, or whose
# `serve_tpot_ms_p90` is a note: a `benchmark` PR's to add (PERF.md section 7)
OTHER_CELLS = ["serve-longdoc-32.a.x-k1", "serve-closed-64.solar-open2"]
TICK = "jit(paged_decode_step)/while/body/closed_call/"
CHUNK = "jit(paged_prefill_chunk)/while/body/closed_call/"


K = 10_000         # nanoseconds a unit below: a window of 4.1 ms


def _op(name, path, start, end, k=K):
    return (sx.instruction(name), path, k * start, k * (end - start))


def _ev(name, start, end, k=K):
    return (name, None, k * start, k * (end - start))


# Three ticks on one chip, window [0, 410) units, 168 of them busy.
#  tick 1, one program in the wait: the device idles from 4 through stage,
#    the whole dispatch and 9 ns of the block (launch), runs [60,80) and
#    [82,100) with 2 ns between the operations, then idles to the end of the
#    block (wake), the fetch and the emit;
#  then 5 ns under no event (an anchor of no length in them), an admission
#    whose middle chunk runs [185,260);
#  tick 2, two programs in the wait: the chunk still runs when the block
#    begins (no launch), 5 ns between it and the tick's program [265,300);
#  tick 3, a wait the device is never busy in: its program ran inside the
#    enqueue [352,358), so the whole block is wake.
OPS = [
    _op("fusion.9", CHUNK + "mlp/dot_general", 0, 4),
    _op("fusion.1", TICK + "decode_attn/dot_general", 60, 80),
    _op("fusion.2", TICK + "decode_mlp/dot_general", 82, 100),
    _op("fusion.9", CHUNK + "mlp/dot_general", 185, 260),
    _op("while.1", TICK + "while", 265, 300),
    _op("fusion.1", TICK + "decode_attn/dot_general", 265, 280),  # nested
    _op("fusion.3", TICK + "lm_head/dot_general", 352, 358),
    _op("fusion.9", CHUNK + "mlp/dot_general", 400, 410),
]
HOST = [
    _ev("serve_tick_stage", 0, 10),
    _ev("serve_tick_dispatch", 10, 50), _ev("serve_tick_grow", 10, 16),
    _ev("serve_tick_h2d", 16, 30), _ev("serve_tick_enqueue", 30, 44),
    _ev("serve_tick_wait", 50, 130), _ev("serve_tick_block", 51, 120),
    _ev("serve_tick_fetch", 120, 129),
    _ev("serve_tick_emit", 130, 150),
    _ev("wallclock_us=1790000000000000", 152, 152),     # no length
    _ev("serve_admit", 155, 200), _ev("serve_prefill", 160, 195),
    _ev("serve_prefill_enqueue", 162, 180),
    _ev("serve_tick_stage", 200, 210),
    _ev("serve_tick_dispatch", 210, 240), _ev("serve_tick_grow", 210, 214),
    _ev("serve_tick_h2d", 214, 224), _ev("serve_tick_enqueue", 224, 236),
    _ev("serve_tick_wait", 240, 330), _ev("serve_tick_block", 241, 320),
    _ev("serve_tick_fetch", 320, 328),
    _ev("serve_tick_emit", 330, 345),
    _ev("serve_tick_stage", 345, 350),
    _ev("serve_tick_dispatch", 350, 360), _ev("serve_tick_enqueue", 352, 358),
    _ev("serve_tick_wait", 360, 400), _ev("serve_tick_block", 361, 395),
    _ev("serve_tick_fetch", 395, 399),
    # another thread's event is no event of the engine
    _ev("PjitFunction(paged_decode_step)", 30, 44),
]
# the runtime's hand-overs, on a thread of its own: the chunk's, the second
# tick's and the third's (where its program starts), each inside the call
# that asked for it
DOORBELLS = (178, 234, 352)
EXPECTED = {
    "stage": 6 + 5, "grow": 6, "h2d": 14, "enqueue": 14,
    "dispatch_other": 6 + 2 + 2, "launch": 9, "between_ops": 2 + 5,
    "wake": 20 + 20 + 34, "fetch": 9 + 8 + 4,
    "wait_other": 1 + 1 + 2 + 1 + 1, "emit": 20 + 15, "loop": 5, "admit": 5,
    "prefill_host": 25}
EXPECTED_NS = {part: K * units for part, units in EXPECTED.items()}
IDLE_NS = K * (410 - 168)


def _reader(name):
    return registry.load_layer_metric(REPO, name)


def _doorbells(at_units):
    return [_ev(tick_gap.DOORBELL, at, at + 1) for at in at_units]


@pytest.fixture
def gap_obs(runs):
    spans = [
        {"name": "serve_decode_step", "ts": 1.0, "dur": 0.9, "ticks": 10,
         "stage_s": 0.02, "dispatch_s": 0.05, "wait_s": 0.85, "emit_s": 0.03,
         "h2d_s": 0.02, "enqueue_s": 0.015},
        {"name": "serve_decode_step", "ts": 2.0, "dur": 0.5, "ticks": 5,
         "stage_s": 0.01, "dispatch_s": 0.02, "wait_s": 0.48, "emit_s": 0.04,
         "h2d_s": 0.01, "enqueue_s": 0.006}]
    return _observe(runs, "serve", {
        "/device:TPU:0": {"XLA Ops": OPS},
        "/host:CPU": {"python": HOST + _doorbells(DOORBELLS)}}, spans)


# -- the partition --------------------------------------------------------------

def test_every_part_of_the_partition_is_exact(gap_obs):
    part = tick_gap.partition(gap_obs["xplane"])
    assert part["ticks"] == 3 and part["window_ns"] == K * 410
    assert part["idle_ns"] == IDLE_NS
    assert part["parts_ns"] == EXPECTED_NS
    assert set(tick_gap.PARTS) == set(EXPECTED_NS)


@pytest.mark.parametrize("tick,window,launch,between,wake", [
    ("one program", (50, 130), 9, 2, 20),
    ("two programs", (240, 330), 0, 5, 20),
    ("never busy", (360, 400), 0, 0, 34),
])
def test_launch_between_and_wake_of_one_wait(gap_obs, tick, window, launch,
                                             between, wake):
    """The same trace cut to one `serve_tick_wait`: what lies under its
    block goes to the three parts by where the device's busy instants lie."""
    parts = tick_gap.partition(
        gap_obs["xplane"], (K * window[0], K * window[1]))["parts_ns"]
    assert (parts["launch"], parts["between_ops"], parts["wake"]) == (
        K * launch, K * between, K * wake), tick


def test_parts_sum_to_the_value_and_the_value_is_the_idle_share(gap_obs,
                                                                capsys):
    value = _reader("tick_gap_ms.serve").read(gap_obs)
    assert value == pytest.approx(1e-6 * IDLE_NS / 3)
    part = tick_gap.partition(gap_obs["xplane"])
    assert sum(tick_gap.ms_a_tick(part, p) for p in tick_gap.PARTS) == \
        pytest.approx(value)
    # value x ticks / window: the same trace's idle share
    share = _reader("device_idle_share.serve").read(gap_obs)
    assert 100.0 * value * 3 / (1e-6 * K * 410) == pytest.approx(share)
    out = capsys.readouterr().out
    assert "wake=" in out and "launch=" in out and "over 3 ticks" in out
    assert "tick_gap_ms.serve clock:" in out and "1 anchors" in out
    printed = dict(kv.split("=") for kv in out.split("\n")[0].split(
        "): ")[1].split(";")[0].split(", "))
    assert set(printed) == set(EXPECTED)
    assert sum(map(float, printed.values())) == pytest.approx(value, rel=0.01)


@pytest.mark.parametrize("name,expected", [
    ("tick_gap_launch_wake_ms.serve", 1e-6 * K * (9 + 74) / 3),
    # one anchor: its spread is not known, every span of the window
    ("tick_h2d_ms.serve", 1e3 * 0.03 / 15),
    ("tick_enqueue_ms.serve", 1e3 * 0.021 / 15),
])
def test_reader_on_the_synthetic_observation(gap_obs, name, expected):
    assert _reader(name).read(gap_obs) == pytest.approx(expected)


# -- the device's clock against the host's -----------------------------------

def _early(runs, by, doorbells=DOORBELLS):
    """The same three ticks with the device plane `by` units early against
    the host plane (every host event that much later), as the profiler places
    it on the v5e."""
    host = [(name, path, start + K * by, dur)
            for name, path, start, dur in HOST + _doorbells(doorbells)]
    return _observe(runs, "serve", {"/device:TPU:0": {"XLA Ops": OPS},
                                    "/host:CPU": {"python": host}})


def test_the_clocks_of_the_synthetic_trace_agree(gap_obs):
    """Zones without work: block end 120 to the chunk's hand-over 178, block
    end 320 to the third tick's 352, where its program starts. No shift is
    needed; 20 units later the second program would end at its
    block's return."""
    shift = tick_gap.device_clock_shift(gap_obs["xplane"])
    assert shift == {"shift_ns": 0, "slack_ns": K * 20, "zones": 2}
    part, found = tick_gap.shifted_partition(gap_obs["xplane"])
    assert found == shift and part["parts_ns"] == EXPECTED_NS


def test_a_device_plane_that_lies_early_is_moved_back(runs, capsys):
    obs = _early(runs, 30)
    # as placed: the third program runs before its enqueue begins, half of
    # every wake is really a launch
    assert tick_gap.partition(obs["xplane"])["parts_ns"] != EXPECTED_NS
    shift = tick_gap.device_clock_shift(obs["xplane"])
    assert shift == {"shift_ns": K * 30, "slack_ns": K * 20, "zones": 2}
    part, _ = tick_gap.shifted_partition(obs["xplane"])
    assert part["parts_ns"] == EXPECTED_NS and part["ticks"] == 3
    assert _reader("tick_gap_ms.serve").read(obs) == pytest.approx(
        1e-6 * IDLE_NS / 3)
    assert _reader("tick_gap_launch_wake_ms.serve").read(obs) == \
        pytest.approx(1e-6 * K * (9 + 74) / 3)
    assert "device clock moved by +300.0 us" in capsys.readouterr().out


def test_without_the_runtimes_hand_overs_the_clock_is_left_alone(runs, capsys):
    """The engine's own events begin too early to bound the shift: a trace
    of a runtime that does not name its hand-overs is partitioned as placed,
    says so, and the one reader that needs the placement reads nothing."""
    obs = _early(runs, 30, doorbells=())
    assert tick_gap.device_clock_shift(obs["xplane"]) is None
    assert _reader("tick_gap_ms.serve").read(obs) == pytest.approx(
        1e-6 * IDLE_NS / 3)
    assert "as the profiler placed it" in capsys.readouterr().out
    assert _reader("tick_gap_launch_wake_ms.serve").read(obs) is None


def test_no_zone_leaves_the_clock_alone(serve_obs):
    assert tick_gap.device_clock_shift(serve_obs["xplane"]) is None
    assert "as the profiler placed it" in tick_gap.describe_shift(None)
    part, shift = tick_gap.shifted_partition(serve_obs["xplane"])
    assert shift is None and part == tick_gap.partition(serve_obs["xplane"])


def test_no_causal_shift_leaves_the_clock_alone(runs, monkeypatch):
    """An operation across a whole zone, longer than any shift may move it."""
    ops = OPS + [_op("fusion.7", TICK + "decode_mlp/dot_general", 100, 400)]
    obs = _observe(runs, "serve", {
        "/device:TPU:0": {"XLA Ops": ops},
        "/host:CPU": {"python": HOST + _doorbells(DOORBELLS)}})
    monkeypatch.setattr(tick_gap, "MAX_SHIFT_NS", K * 50)
    assert tick_gap.device_clock_shift(obs["xplane"]) is None
    part, shift = tick_gap.shifted_partition(obs["xplane"])
    assert shift is None and part == tick_gap.partition(obs["xplane"])


def test_innermost_names_every_instant_by_the_event_that_began_last():
    events = [("a", 0, 100), ("b", 10, 40), ("c", 20, 30), ("d", 60, 70),
              ("e", 200, 210)]
    assert tick_gap.innermost(events) == [
        (0, 10, "a"), (10, 20, "b"), (20, 30, "c"), (30, 40, "b"),
        (40, 60, "a"), (60, 70, "d"), (70, 100, "a"), (200, 210, "e")]
    # a child that outlives its parent keeps the instants it began in
    assert tick_gap.innermost([("a", 0, 50), ("b", 40, 80)]) == [
        (0, 40, "a"), (40, 80, "b")]


def test_a_capture_of_a_build_without_the_nested_events(serve_obs):
    """The parent's trace: the partition still sums (what lies under
    `serve_tick_wait` is `wait_other`), the readers read nothing."""
    part = tick_gap.partition(serve_obs["xplane"])
    assert part["ticks"] == 2 and part["idle_ns"] == 200 - 125
    assert sum(part["parts_ns"].values()) == part["idle_ns"]
    assert part["parts_ns"]["stage"] == 8
    assert part["parts_ns"]["dispatch_other"] == 25
    assert part["parts_ns"]["wait_other"] == 2 + 5 + 2
    assert part["parts_ns"]["admit"] == 2
    assert part["parts_ns"]["prefill_host"] == 13
    assert part["parts_ns"]["launch"] == part["parts_ns"]["wake"] == 0
    for name in READERS:
        assert _reader(name).read(serve_obs) is None, name


# -- nothing to read ----------------------------------------------------------

@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_where_there_is_nothing_to_read(name, gap_obs,
                                                       train_obs):
    reader = _reader(name)
    assert reader.read(train_obs) is None
    assert reader.read({"kind": "none"}) is None
    assert reader.read(dict(gap_obs, xplane=None)) is None      # untraced
    # a program without PR 34's names: no nested event, no new attribute
    old = ("name", "ts", "dur", "ticks", "stage_s", "dispatch_s", "wait_s",
           "emit_s")
    bare = dict(gap_obs, spans=[{k: s[k] for k in old}
                                for s in gap_obs["spans"]])
    bare["xplane"] = dict(gap_obs["xplane"], host=[
        ev for ev in gap_obs["xplane"]["host"]
        if ev[0] in ("serve_tick_stage", "serve_tick_dispatch",
                     "serve_tick_wait", "serve_tick_emit", "serve_admit",
                     "serve_prefill")])
    assert reader.read(bare) is None


def test_every_reader_agrees_with_its_benchmark_entry():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        reader, entry = _reader(name), entries[name]
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            entry["layer"], entry["unit"], entry["moves"], entry["source"])
        assert entry["better"] == "lower" and entry["workloads"] == CELLS
        for cell in CELLS:
            assert name in registry.load_cell(REPO, cell).per_layer


@pytest.mark.parametrize("cell", OTHER_CELLS)
def test_the_cells_that_do_not_list_the_readers_read_none(cell):
    assert not set(READERS) & set(registry.load_cell(REPO, cell).per_layer)


def test_the_benchmark_and_the_program_name_the_same_events():
    """benchmark/ imports nothing of the program: its names are held equal
    to `utils/trace.py`'s here."""
    t = program_trace
    assert set(tick_gap.PART_OF) | {tick_gap.BLOCK} == {
        t.TICK_STAGE, t.TICK_DISPATCH, t.TICK_WAIT, t.TICK_EMIT,
        t.SERVE_ADMIT, t.TICK_GROW, t.TICK_H2D, t.TICK_ENQUEUE, t.TICK_BLOCK,
        t.TICK_FETCH, t.PREFILL_ENQUEUE, t.PREFILL_FIRST, "serve_prefill"}
    assert (tick_gap.WAIT, tick_gap.BLOCK) == (t.TICK_WAIT, t.TICK_BLOCK)
    assert tick_gap.ANCHOR_PREFIX == t.WALLCLOCK_PREFIX


# -- the anchor -----------------------------------------------------------------

EPOCH_US = 1_790_000_000_000_000      # the wall clock where the profiler's is 0


def _anchored(runs, errors_us, spans):
    """A trace of 2 s, [1 s, 3 s) on the profiler's clock, with one anchor
    for each of `errors_us`: an anchor entered `error` late reads
    profiler - wall = -EPOCH_US + error."""
    at_ns = [1_200_000_000 + 500_000_000 * i for i in range(len(errors_us))]
    host = [_ev("serve_tick_wait", 1_000_000_000, 3_000_000_000, k=1)] + [
        _ev(f"wallclock_us={EPOCH_US + ns // 1000 - err}", ns, ns, k=1)
        for ns, err in zip(at_ns, errors_us)]
    ops = [_op("fusion.1", TICK + "decode_mlp/dot_general",
               1_000_000_000, 3_000_000_000, k=1)]
    return _observe(runs, "serve", {"/device:TPU:0": {"XLA Ops": ops},
                                    "/host:CPU": {"python": host}}, spans)


def _span(ts_s, ticks, h2d_s):
    return {"name": "serve_decode_step", "ts": EPOCH_US * 1e-6 + ts_s,
            "dur": 0.4, "ticks": ticks, "h2d_s": h2d_s, "enqueue_s": h2d_s,
            "stage_s": 0.01, "emit_s": 0.01}


SPANS = [_span(0.5, 32, 0.32), _span(1.5, 32, 0.032), _span(2.2, 32, 0.064),
         _span(3.5, 32, 0.32)]


def test_the_offset_is_recovered_within_a_microsecond(runs):
    obs = _anchored(runs, [3, 0, 40, -2, 1], SPANS)
    clock = tick_gap.clock_offset(obs["xplane"])
    assert clock["anchors"] == 5
    assert clock["offset_us"] == pytest.approx(-EPOCH_US + 1, abs=1.0)
    assert clock["range_us"] == pytest.approx(42.0, abs=1.0)
    assert clock["spread_us"] < 25.0          # one late anchor moves neither
    assert "5 anchors" in tick_gap.describe_clock(clock)
    assert tick_gap.clock_offset({"host": []}) is None
    assert "no wall-clock anchor" in tick_gap.describe_clock(None)


def test_host_clock_readers_take_the_spans_of_the_traced_window(runs, capsys):
    obs = _anchored(runs, [3, 0, -2], SPANS)
    spans, how = tick_gap.spans_of_trace(obs, "h2d_s")
    assert spans == SPANS[1:3] and "2 of 4 spans" in how
    assert _reader("tick_h2d_ms.serve").read(obs) == pytest.approx(
        1e3 * 0.096 / 64)
    assert "2 of 4 spans" in capsys.readouterr().out


@pytest.mark.parametrize("errors_us,said", [
    ([], "no wall-clock anchor"),
    ([7], "spread not known"),
    ([0, 400, 900, 50], "spread"),          # anchors that disagree
])
def test_host_clock_readers_fall_back_to_the_whole_window(runs, capsys,
                                                          errors_us, said):
    obs = _anchored(runs, errors_us, SPANS)
    spans, how = tick_gap.spans_of_trace(obs, "h2d_s")
    assert spans == SPANS and "every span of the window" in how and said in how
    assert _reader("tick_h2d_ms.serve").read(obs) == pytest.approx(
        1e3 * 0.736 / 128)
    assert "every span of the window" in capsys.readouterr().out


def test_no_span_began_in_the_trace(runs):
    obs = _anchored(runs, [0, 1, 2], [SPANS[0], SPANS[3]])
    spans, how = tick_gap.spans_of_trace(obs, "h2d_s")
    assert spans == [SPANS[0], SPANS[3]] and "none began in the trace" in how


def test_anchors_of_no_length_change_no_accepted_reading(serve_obs):
    """On the observation the accepted readers' tests use: an anchor in the
    middle of a gap, one at its edge and one under no event name no gap and
    own no idle time."""
    trace = serve_obs["xplane"]
    reader = _reader("host_idle_ms_per_tick.serve")
    gaps, owned = xplane.idle_gaps(trace, 5), reader.read(serve_obs)
    anchored = dict(serve_obs, xplane=dict(trace, host=trace["host"] + [
        (f"wallclock_us={EPOCH_US + at}", at, at) for at in (60, 75, 100, 190)]))
    assert xplane.idle_gaps(anchored["xplane"], 5) == gaps
    assert reader.read(anchored) == owned
    assert tick_gap.clock_offset(anchored["xplane"])["anchors"] == 4
    assert tick_gap.partition(anchored["xplane"]) == tick_gap.partition(trace)


# -- through the harness, on a real capture ----------------------------------

ANCHORS_READER = '''from benchmark import tick_gap

LAYER, UNIT, SOURCE = "device", "anchors", "program_span"


def read(obs):
    clock = tick_gap.clock_offset(obs.get("xplane") or {})
    if clock is None:
        return None
    # the spans' own stamps, moved by the offset, lie inside the capture
    host = obs["xplane"]["host"]
    lo, hi = min(s for _, s, _ in host), max(e for _, _, e in host)
    placed = sum(1 for s in obs["spans"]
                 if lo <= (s["ts"] * 1e6 + clock["offset_us"]) * 1e3 <= hi)
    print(f"anchors_in_trace: {clock['anchors']} anchors, {placed} spans "
          f"placed inside the capture", flush=True)
    return float(clock["anchors"]) if placed else None
'''


@pytest.mark.parametrize("cell,moves", [
    ("train-tiny.tiny", "train_tokens_per_s"),
    ("serve-tiny.tiny", "serve_tpot_ms_p90")])
def test_a_capture_of_either_program_is_anchored(tmp_path, cell, moves):
    """The trainer's `profile_steps` capture and the serving job's, through
    the harness at a tiny size: each holds anchors that place the run's own
    spans inside it, and on a capture with no device plane (the CPU's) the
    four readers read nothing and raise nothing."""
    root = benchmark_tiny.make_root(str(tmp_path))
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "anchors_in_trace.py"), "w") as f:
        f.write(ANCHORS_READER + f"MOVES = {moves!r}\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    bench["per_layer"] += [dict(real[name], workloads=["serve-tiny.tiny"])
                           for name in READERS]
    bench["per_layer"].append({
        "name": "anchors_in_trace", "unit": "anchors", "better": "higher",
        "source": "program_span", "layer": "device", "moves": moves,
        "workloads": [cell]})
    benchmark_tiny._dump(path, bench)
    res = harness.run_cell(root, cell, seed=34, seconds=1.0, trace=True,
                           devices=jax.devices()[:1], t_start=time.time())
    assert res["correct"] is True
    assert res["metrics"]["anchors_in_trace"]["value"] >= 1
    assert not set(READERS) & set(res["metrics"])
