"""The schedule observatory's measured-timeline layer
(utils/timeline.py + the interpreter's boundary marks —
docs/OBSERVABILITY.md "Timelines").

Pins, in order: the segment decomposition shared by the interpreter and
the accounting (schedule.segments / segment_stats reproducing
bubble_stats exactly); the structural contract — timeline OFF compiles
NO callback (jaxpr-identical to the pre-observatory interpreter) while
ON compiles marks and stays loss/grad BIT-exact; the collector's record
(measured bubble next to analytic, straggler z-scores, segment labels);
the trainer e2e acceptance (per-segment durations sum to within 10% of
the measured step wall on a CPU tiny conf, bubble_fraction_measured on
the metrics line + health.json, step_time_p50/p95); the serving per-tick
records; and the degrade-don't-traceback reader contract."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.models.llama.manifest import StageManifest
from llama_pipeline_parallel_tpu.parallel import pipeline as pl
from llama_pipeline_parallel_tpu.parallel import schedule as usched
from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig, make_mesh
from llama_pipeline_parallel_tpu.utils import timeline as tl


# ---------------------------------------------------------------------------
# Segment decomposition (parallel/schedule.py)
# ---------------------------------------------------------------------------

def test_segments_labels_and_grouping():
    us = usched.canonical_schedule("zb1", 4, 2, 2)
    segs = usched.segments(us)
    assert [s.label for s in segs] == ["F", "F+B", "B", "W"]
    # contiguous, exhaustive cover of the tick axis
    assert segs[0].t0 == 0 and segs[-1].t1 == us.num_ticks
    for a, b in zip(segs, segs[1:]):
        assert a.t1 == b.t0
    flat = usched.segments(usched.canonical_schedule("1f1b", 8, 4))
    assert [s.label for s in flat] == ["F", "F+B", "B"]
    assert [s.num_ticks for s in flat] == [3, 8, 3]
    drain_w = usched.segments(usched.list_schedule(8, 2, 2,
                                                   w_placement="drain"))
    assert "B+W" in [s.label for s in drain_w]


def test_segment_stats_reproduce_bubble_stats():
    for sched, m, s, v in (("1f1b", 8, 4, 1), ("interleaved_1f1b", 8, 4, 2),
                           ("zb1", 8, 4, 2), ("zb1", 4, 2, 1)):
        us = usched.canonical_schedule(sched, m, s, v)
        stats = usched.segment_stats(us)
        idle, wall = usched.bubble_stats(us)
        seg_wall = sum(st["wall_units"] for st in stats) * us.num_stages
        seg_useful = sum(sum(st["useful_units"]) for st in stats)
        assert seg_wall == wall
        assert seg_wall - seg_useful == idle


def test_segment_stats_unequal_costs_and_offload():
    us = usched.canonical_schedule("zb1", 4, 2, 1, offload_wgrad=True,
                                   stage_costs=(3, 1))
    stats = usched.segment_stats(us)
    idle, wall = usched.bubble_stats(us)
    assert sum(st["wall_units"] for st in stats) * 2 == wall
    assert wall - sum(sum(st["useful_units"]) for st in stats) == idle
    w_only = [st for st in stats if st["label"] == "W"]
    assert w_only and w_only[0]["offloaded_w_units"] == us.n_units


# ---------------------------------------------------------------------------
# Structural + parity contract (the jaxpr pin)
# ---------------------------------------------------------------------------

def _tiny_setup(schedule="zb1", v=2):
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    mesh = make_mesh(MeshConfig(pp=2))
    man = StageManifest.for_config(cfg, 2, virtual_stages=v)
    params = pl.stack_stages(llama.init_params(jax.random.PRNGKey(0), cfg),
                             man)
    pcfg = pl.PipelineConfig(num_stages=2, num_microbatches=2,
                             schedule=schedule, virtual_stages=v)
    rs = np.random.RandomState(0)
    L = 32
    batch = {"input_ids": jnp.asarray(rs.randint(3, cfg.vocab_size, (2, L)),
                                      jnp.int32),
             "attention_mask": jnp.ones((2, L), jnp.int32),
             "position_ids": jnp.broadcast_to(
                 jnp.arange(L, dtype=jnp.int32), (2, L)),
             "labels": jnp.asarray(rs.randint(3, cfg.vocab_size, (2, L)),
                                   jnp.int32)}
    return cfg, mesh, params, pcfg, batch


def test_timeline_on_bit_exact_and_record_fields():
    """The structural pin + the value pin in one build: OFF compiles no
    callback primitive (no timing residue in the program) while ON marks
    every segment boundary; loss and every grad leaf are bit-equal ON vs
    OFF; and the collector's record carries the measured bubble NEXT to
    the analytic one, per-segment durations for every plan label, and
    per-stage straggler z-scores."""
    cfg, mesh, params, pcfg, batch = _tiny_setup()
    off = pl.make_pipeline_loss_and_grad(mesh, cfg, pcfg, params)
    on = pl.make_pipeline_loss_and_grad(mesh, cfg, pcfg, params,
                                        timeline_segments=True)
    assert "callback" not in str(jax.make_jaxpr(off)(params, batch))
    assert "callback" in str(jax.make_jaxpr(on)(params, batch))
    off, on = jax.jit(off), jax.jit(on)
    l0, g0 = off(params, batch)
    plan = tl.SegmentPlan(pcfg)
    assert [s["label"] for s in plan.stats] == ["F", "F+B", "B", "W"]
    coll = tl.TimelineCollector(plan)
    tl.install(coll)
    try:
        coll.begin_step(1)
        l1, g1 = on(params, batch)
        jax.block_until_ready(l1)
        rec = coll.end_step(1)
    finally:
        tl.install(None)
    assert float(l0) == float(l1)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        assert bool((a == b).all())
    assert set(rec["segments"]) == {"F", "F+B", "B", "W"}
    assert rec["bubble_fraction_analytic"] == round(
        usched.analytic_bubble(pl.flush_unit_schedule(pcfg)), 6)
    assert 0.0 <= rec["bubble_fraction_measured"] < 1.0
    assert rec["pipeline_s"] == pytest.approx(
        sum(s["dur_s"] for s in rec["segments"].values()), abs=1e-5)
    assert len(rec["stage_z"]) == 2 and rec["straggler_stage"] in (0, 1)
    # marks after detach are dropped, not crashed
    tl.mark_callback(np.int32(0), np.int32(0), np.float32(0.0))


def test_timeline_rejects_gpipe():
    cfg, mesh, params, pcfg, batch = _tiny_setup(schedule="1f1b", v=1)
    import dataclasses

    gp = dataclasses.replace(pcfg, schedule="gpipe")
    with pytest.raises(ValueError, match="unit-sequence"):
        pl.make_pipeline_loss_and_grad(mesh, cfg, gp, params,
                                       timeline_segments=True)


# ---------------------------------------------------------------------------
# Config block
# ---------------------------------------------------------------------------

def test_timeline_config_parse():
    assert not tl.TimelineConfig.from_cfg(None).enabled
    c = tl.TimelineConfig.from_cfg({"enabled": True, "window": 8})
    assert c.enabled and c.window == 8
    with pytest.raises(ValueError, match="unknown timeline"):
        tl.TimelineConfig.from_cfg({"enalbed": True})
    with pytest.raises(ValueError, match="mapping"):
        tl.TimelineConfig.from_cfg("yes")
    # an explicit bad window is rejected, not silently defaulted; an empty
    # `window:` yaml key (None) IS the default
    with pytest.raises(ValueError, match="window must be >= 2"):
        tl.TimelineConfig.from_cfg({"window": 0})
    assert tl.TimelineConfig.from_cfg({"window": None}).window == 64


def test_gpipe_degrades_to_step_wall_records(tmp_path):
    """The trainer keeps timelines ON for gpipe but without marks
    (StepTimeline.segmented False): records carry the step wall only —
    the documented degrade, while building marks directly still raises
    (test_timeline_rejects_gpipe)."""
    import dataclasses

    _, _, _, pcfg, _ = _tiny_setup(schedule="1f1b", v=1)
    gp = dataclasses.replace(pcfg, schedule="gpipe")
    st = tl.StepTimeline(gp, str(tmp_path), window=4)
    assert not st.segmented
    st.pre_step(1)
    rec = st.post_step(1, jnp.float32(0.0))
    st.close()
    assert "wall_s" in rec and "segments" not in rec
    assert "step_time_p50" in st.scalars()
    assert "bubble_fraction_measured" not in st.scalars()


# ---------------------------------------------------------------------------
# Trainer e2e: the acceptance pin
# ---------------------------------------------------------------------------

def test_trainer_timeline_e2e(tmp_path):
    """CPU tiny conf with `timeline.enabled: true`: per-segment durations
    (+ the optimizer mark) sum to within 10% of the measured step wall,
    `bubble_fraction_measured` appears NEXT to `bubble_fraction` on the
    metrics line, and health.json carries the rolling percentiles."""
    from llama_pipeline_parallel_tpu.train import run_training

    out = tmp_path / "run"
    cfg = {
        "output_dir": str(out),
        "mesh": {"pp": 2},
        "model": {"preset": "tiny", "dtype": "float32"},
        "dataset": {"synthetic": True, "seq_length": 128,
                    "pseudo_dataset_len": 64},
        "seed": 0, "per_device_train_batch_size": 4,
        "gradient_accumulation_steps": 2, "max_steps": 4,
        "pipeline_schedule": "zb1", "virtual_stages": 2,
        "logging_steps": 2, "save_steps": 0, "save_final": False,
        "attention": "exact", "numerics": {"enabled": False},
        "timeline": {"enabled": True, "window": 8},
    }
    summary = run_training(cfg)
    assert summary["final_step"] == 4

    records = tl.read_timeline(str(out / "timeline.jsonl"))
    assert [r["step"] for r in records] == [1, 2, 3, 4]
    steady = records[1:]  # step 1 pays compile inside its wall
    for rec in steady:
        assert set(rec["segments"]) == {"F", "F+B", "B", "W"}
        assert rec["bubble_fraction_measured"] is not None
    # the acceptance bound: attributed time (segments + optimizer) within
    # 10% of the blocked step wall, on the median steady step (median, not
    # every step: a CI scheduler hiccup in ONE step must not flake this)
    ratios = [(rec["pipeline_s"] + rec.get("optimizer_s", 0.0))
              / rec["wall_s"] for rec in steady]
    # (slightly above 1.0 is possible: per-segment maxes across straggling
    # stages can overlap — still "within 10% of the step wall")
    assert 0.9 <= sorted(ratios)[len(ratios) // 2] <= 1.1, ratios

    metrics = [json.loads(l) for l in open(out / "metrics.jsonl")
               if l.strip()][1:]  # line 0 is the config snapshot
    line = metrics[-1]
    assert "bubble_fraction" in line and "bubble_fraction_measured" in line
    assert "step_time_p50" in line and "step_time_p95" in line
    health = json.loads((out / "health.json").read_text())
    assert "bubble_fraction_measured" in health
    assert "step_time_p50" in health and "step_time_p95" in health
    # the run closed into the perf ledger: analytic bubble paired with the
    # timeline-measured one
    from llama_pipeline_parallel_tpu.utils import perf

    rows = perf.read_ledger(str(out / "perf.jsonl"))
    bub = next(r for r in rows if r["metric"] == "bubble_fraction")
    assert bub["model"] is not None and bub["measured"] is not None


# ---------------------------------------------------------------------------
# Serving per-tick records
# ---------------------------------------------------------------------------

def test_serve_timeline_ticks(tmp_path):
    from llama_pipeline_parallel_tpu.models.llama.decode import (
        GenerationConfig,
    )
    from llama_pipeline_parallel_tpu.serve import (
        ServeConfig,
        ServeEngine,
        ServeRequest,
    )

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    man = StageManifest.for_config(cfg, 1)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    path = tmp_path / "timeline.jsonl"
    writer = tl.TimelineWriter(str(path))
    eng = ServeEngine(params, cfg,
                      ServeConfig(max_slots=2, max_len=96,
                                  prompt_buckets=(16,), page_size=16),
                      timeline=writer)
    rs = np.random.RandomState(0)
    prompt = rs.randint(3, cfg.vocab_size, (12,)).tolist()
    for _ in range(2):
        eng.submit(ServeRequest(input_ids=prompt,
                                gen=GenerationConfig(max_new_tokens=4)))
    eng.drain(timeout_s=300)
    eng.shutdown()
    writer.close()
    ticks = tl.read_timeline(str(path))
    assert ticks and all("decode_s" in t and "prefill_s" in t for t in ticks)
    assert any(t["decode_s"] > 0 for t in ticks)
    assert any(t["active"] for t in ticks)
    # the pool's occupancy rides every tick record: two 12-token prompts in
    # a bucket of one page each
    assert all({"pages_used", "pages_reserved", "fragmentation"} <= set(t)
               for t in ticks)
    assert max(t["pages_used"] for t in ticks) == 4


# ---------------------------------------------------------------------------
# Reader degrade contract (the goodput_report house rule)
# ---------------------------------------------------------------------------

def test_read_timeline_degrades(tmp_path):
    assert tl.read_timeline(str(tmp_path / "absent.jsonl")) == []
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert tl.read_timeline(str(empty)) == []
    torn = tmp_path / "torn.jsonl"
    torn.write_text('{"step": 1, "wall_s": 0.5}\n{"step": 2, "wal')
    assert tl.read_timeline(str(torn)) == [{"step": 1, "wall_s": 0.5}]
    garbage = tmp_path / "garbage.jsonl"
    garbage.write_text('not json\n[1, 2]\n{"step": 3}\n\x00\x01\n')
    assert tl.read_timeline(str(garbage)) == [{"step": 3}]
