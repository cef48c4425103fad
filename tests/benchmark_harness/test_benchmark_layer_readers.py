"""The ten per-layer readers PR 24 added: each returns None for the other
kind of cell and where what it reads is absent (the parent program has no
such scope, span attribute or annotation), and the hand-computed number on a
synthetic observation."""

import json
import os
import types

import pytest
import synthetic_xplane as sx
from conftest import REPO

from benchmark import registry, scopes, xplane

TRAIN_READERS = ["recompute_share.train", "head_loss_share.train",
                 "flash_fwd_roofline.train", "optimizer_share.train",
                 "bubble_share.train", "handoff_exposed_share.train",
                 "compiled_hbm_gb.train"]
SERVE_READERS = ["tick_host_share.serve", "host_idle_ms_per_tick.serve",
                 "kv_pool_share.serve"]

STEP = "jit(train_step)/shard_map/while/body/closed_call/"
TICK = "jit(paged_decode_step)/while/body/closed_call/"

MODEL = {"hidden_size": 4096, "num_attention_heads": 32,
         "num_key_value_heads": 8}
SCHEDULE = [{"stage": 0, "devices": [0], "f": 22, "f_masked": 6, "b": 22,
             "b_masked": 6, "w": 0, "w_masked": 0}]
MEMORY = {"label": "train_step", "argument_bytes": 9_000_000_000,
          "output_bytes": 8_500_000_000, "temp_bytes": 4_000_000_000,
          "alias_bytes": 8_400_000_000, "generated_bytes": 1_000_000,
          "peak_bytes": 13_100_000_000, "compiler_peak_bytes": 12_000_000_000}
PAGES = "bf16[4,40,16,8,128]"     # whole pages: (page 16, 8 KV heads, 128)


def _op(name, path, start, dur, result="bf16[8,128]"):
    return (sx.instruction(name, result), path, start, dur)


def _reader(name):
    return registry.load_layer_metric(REPO, name)


@pytest.fixture
def runs(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "RUNS_DIR", str(tmp_path))
    return tmp_path


def _observe(runs, kind, planes, spans=()):
    """A traced run's observations, its trace written where the run's own
    directory would hold it."""
    cell = types.SimpleNamespace(
        name=f"{kind}-cell.tiny", model=MODEL, mix={"rows_per_microbatch": 1},
        params={"engine": {"page_size": 16}})
    run = runs / f"{cell.name}.42.1"
    run.mkdir()
    path = sx.write(run / "t.xplane.pb", planes)
    return {"kind": kind, "cell": cell, "spans": list(spans),
            "xplane": xplane.read(path), "seq_length": 4096,
            "devices": [types.SimpleNamespace(device_kind="TPU v5 lite")]}


@pytest.fixture
def train_obs(runs):
    # one chip, window [0, 1000): forward 200, the schedule's recompute 100,
    # backward 300 of which 50 are a remat recompute, the head 150 (forward
    # 50 + backward 100), a hand-off 40, clip 10 + AdamW 90, 10 unnamed; the
    # flash forward runs three times (50, 60, 70 us ... in ns here), nested
    ops = [
        _op("fusion.1", STEP + "pp_fwd/mlp/dot_general", 0, 200),
        _op("flash_fwd.1", STEP + "pp_fwd/attn_core/flash_fwd/pallas_call",
            10, 50),
        _op("fusion.2", STEP + "pp_recompute/jvp()/mlp/dot_general", 200, 100),
        _op("flash_fwd.2", STEP + "pp_recompute/jvp()/attn_core/flash_fwd/"
            "pallas_call", 210, 60),
        _op("fusion.3", STEP + "pp_bwd/transpose(jvp())/checkpoint/mlp/"
            "dot_general", 300, 250),
        _op("flash_fwd.3", STEP + "pp_bwd/transpose(jvp())/checkpoint/"
            "rematted_computation/attn_core/flash_fwd/pallas_call", 310, 70),
        _op("fusion.4", STEP + "pp_fwd/cond/branch_1_fun/lm_head_loss/"
            "lm_head/dot_general", 550, 50),
        _op("fusion.5", STEP + "pp_bwd/transpose(jvp())/cond/branch_1_fun/"
            "lm_head_loss/lm_head/dot_general", 600, 100),
        _op("collective-permute-start.1", STEP + "pp_handoff/ppermute",
            700, 40),
        _op("fusion.6", "jit(train_step)/grad_clip/reduce_sum", 740, 10),
        _op("fusion.7", "jit(train_step)/optimizer/mul", 750, 90),
        _op("copy.1", None, 840, 10),
        _op("copy.2", None, 990, 10)]
    spans = [{"name": "profile_window", "ts": 1.0, "dur": 2.0, "steps": 2,
              "schedule": SCHEDULE, "compiled_memory": MEMORY},
             {"name": "data_wait", "ts": 1.5, "dur": 0.001}]
    return _observe(runs, "train", {"/device:TPU:0": {"XLA Ops": ops}}, spans)


BUSY = 860.0      # [0, 850) and [990, 1000)


@pytest.mark.parametrize("name,expected", [
    ("recompute_share.train", 100.0 * (40 + 60 + 70) / BUSY),
    ("head_loss_share.train", 100.0 * 150 / BUSY),
    ("optimizer_share.train", 100.0 * 100 / BUSY),
    # masked F: (200 + 50) * 6/22; masked B: (100 + 250) * 6/22, without the
    # 100 under `lm_head_loss`, which a masked B slot skips
    ("bubble_share.train", 100.0 * (250 + 350) * 6 / 22 / BUSY),
    ("handoff_exposed_share.train", 100.0 * 40 / 1000),
    ("compiled_hbm_gb.train", 12.0),       # the compiler's own peak
    # 2 * 4096^2 * 128 * 32 FLOPs at 197e12 / s over the median 60 ns
    ("flash_fwd_roofline.train",
     100.0 * (2 * 4096 ** 2 * 128 * 32 / 197e12) / 60e-9),
])
def test_training_reader_on_a_synthetic_observation(train_obs, name, expected):
    assert _reader(name).read(train_obs) == pytest.approx(expected)


@pytest.fixture
def serve_obs(runs):
    # ticks: device busy [0,60) and [100,160); between them the host stages
    # [60,70), dispatches [70,95), and the device idles [60,100). The second
    # tick's emit [160,180) and an admission [180,200) cover the tail gap up
    # to the last operation at [195,200). The scan writes the pool back [40,50) and slices a layer's weights
    # [100,105); the compiler copies the whole pool with no path [105,115)
    scan = "jit(paged_decode_step)/while/body/"
    ops = [
        _op("fusion.1", TICK + "kv_gather/gather", 0, 30),
        _op("fusion.2", TICK + "kv_write/scatter", 30, 10),
        _op("bitcast_dynamic-update-slice_fusion.4",
            scan + "dynamic_update_slice", 40, 10, PAGES),
        _op("fusion.3", TICK + "decode_mlp/dot_general", 50, 10),
        _op("dynamic-slice_fusion.9", scan + "dynamic_slice", 100, 5,
            "bf16[1,4096,4096]"),
        _op("copy.91", None, 105, 10, PAGES),
        _op("fusion.1", TICK + "kv_gather/gather", 115, 45),
        _op("fusion.4", "jit(prefill_prompt)/lm_head/dot_general", 195, 5)]
    host = {"python": [
        ("serve_tick_wait", None, 0, 62), ("serve_tick_stage", None, 62, 8),
        ("serve_tick_dispatch", None, 70, 25),
        ("serve_tick_wait", None, 95, 67), ("serve_tick_emit", None, 162, 18),
        ("serve_admit", None, 180, 20), ("serve_prefill", None, 182, 16)]}
    spans = [
        {"name": "serve_decode_step", "ts": 1.0, "dur": 0.9, "ticks": 10,
         "stage_s": 0.02, "dispatch_s": 0.05, "wait_s": 0.85, "emit_s": 0.03},
        {"name": "serve_decode_step", "ts": 2.0, "dur": 0.5, "ticks": 5,
         "stage_s": 0.01, "dispatch_s": 0.02, "wait_s": 0.48, "emit_s": 0.02},
        {"name": "serve_queue_wait", "ts": 1.2, "dur": 0.1}]
    return _observe(runs, "serve", {"/device:TPU:0": {"XLA Ops": ops},
                                    "/host:CPU": host}, spans)


@pytest.mark.parametrize("name,expected", [
    ("tick_host_share.serve", 100.0 * 0.15 / (0.15 + 1.33)),
    # idle [60,100): 8 under stage + 25 under dispatch; idle [160,195): 18
    # under emit... up to 180, then 15 under the admission; over two ticks
    ("host_idle_ms_per_tick.serve", 1e-6 * (8 + 25 + 18 + 15) / 2),
    # under the two scopes 30 + 10 + 45, the scan's pool write 10; not the
    # weights' slice (5) nor the pool copy with no path (10)
    ("kv_pool_share.serve", 100.0 * (30 + 10 + 45 + 10) / 125),
])
def test_serving_reader_on_a_synthetic_observation(serve_obs, name, expected):
    assert _reader(name).read(serve_obs) == pytest.approx(expected)


@pytest.mark.parametrize("name", TRAIN_READERS + SERVE_READERS)
def test_reader_is_none_for_the_other_kind_and_without_its_input(
        name, train_obs, serve_obs):
    reader = _reader(name)
    mine, other = ((train_obs, serve_obs) if name.endswith(".train")
                   else (serve_obs, train_obs))
    assert reader.read(other) is None
    # what a program without PR 24's names gives: spans with no new
    # attribute, a trace with no scope, annotation or kernel name in it
    bare = dict(mine, xplane=None, spans=[
        {k: v for k, v in s.items()
         if k in ("name", "ts", "dur", "ticks", "steps")}
        for s in mine["spans"] if s["name"] != "profile_window"])
    assert reader.read(bare) is None


def test_pipeline_readers_are_none_on_one_stage(runs):
    """pp = 1: no `schedule` on the span and no hand-off in the trace."""
    obs = _observe(runs, "train", {"/device:TPU:0": {"XLA Ops": [
        _op("fusion.1", "jit(train_step)/while/body/closed_call/pp_fwd/mlp/"
            "dot_general", 0, 10)]}},
        [{"name": "profile_window", "ts": 1.0, "dur": 1.0, "steps": 3,
          "compiled_memory": dict(MEMORY, compiler_peak_bytes=None)}])
    assert _reader("bubble_share.train").read(obs) is None
    assert _reader("handoff_exposed_share.train").read(obs) is None
    assert _reader("flash_fwd_roofline.train").read(obs) is None
    # a backend that gives no peak of its own: argument + output + temp - alias
    assert _reader("compiled_hbm_gb.train").read(obs) == pytest.approx(13.1)


@pytest.mark.parametrize("path,result,part", [
    (TICK + "kv_gather/gather", "bf16[40,16,8,128]", "scoped"),
    (TICK + "kv_write/scatter", "s32[16]", "scoped"),
    ("jit(paged_decode_step)/while/body/squeeze", "bf16[41,16,8,128]", "scan"),
    ("jit(paged_decode_step)/while/body/dynamic_update_slice", PAGES, "scan"),
    ("jit(paged_decode_step)/while/body/dynamic_slice", "bf16[1,4096,4096]",
     "scan_other"),
    ("jit(prefill_prompt)/while/body/dynamic_update_slice",
     "bf16[4,1,1024,8,128]", "scan_other"),      # the dense prefill cache
    ("", PAGES, "unnamed"),
    ("", "f32[16,800,128]", None),
    (TICK + "decode_attn/dot_general", "bf16[16,16,8,128]", None),
])
def test_kv_pool_parts_tell_the_pool_from_the_weights(path, result, part):
    reader = _reader("kv_pool_share.serve")
    block = reader.page_block(types.SimpleNamespace(
        model=MODEL, params={"engine": {"page_size": 16}}))
    assert block == ["16", "8", "128"]
    assert reader.part_of(scopes.Op("x.1", path, 0.0, 1.0, result), block) == part


def test_every_new_reader_agrees_with_its_benchmark_entry():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in TRAIN_READERS + SERVE_READERS:
        reader, entry = _reader(name), entries[name]
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            entry["layer"], entry["unit"], entry["moves"], entry["source"])
