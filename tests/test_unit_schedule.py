"""Solver-generated pipeline schedules: the unit-sequence representation.

The CI `Schedule parity` gate's solver lane (docs/SCHEDULES.md "Solver
schedules"): the canonical generators must re-emit the three deleted
hand-written phase scans exactly (idle-unit counts reproduce the closed
bubble formulas bit-for-bit), the validator must reject broken sequences
(W-before-B, ring overflow, torn transport = cyclic dependencies), the
interpreter must replay a loaded/mutated sequence bit-exactly against the
canonical schedules (same assertion style as tests/test_zero_bubble.py),
and selective per-unit offload must reproduce the `offload.wgrad_stash`
on/off extremes as boundary points of its decision space."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.models.llama.manifest import StageManifest
from llama_pipeline_parallel_tpu.parallel import pipeline as pl
from llama_pipeline_parallel_tpu.parallel import schedule as us
from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig, make_mesh


@pytest.fixture(scope="module")
def cfg():
    return LlamaConfig.tiny(num_hidden_layers=8)


@pytest.fixture(scope="module")
def params(cfg):
    return llama.init_params(jax.random.PRNGKey(0), cfg)


def make_batch(cfg, batch_size=8, seqlen=16, seed=42):
    rng = np.random.RandomState(seed)
    ids = rng.randint(3, cfg.vocab_size, size=(batch_size, seqlen)).astype(np.int32)
    mask = np.ones((batch_size, seqlen), np.int32)
    mask[:, -3:] = 0
    labels = ids.copy()
    labels[mask == 0] = llama.IGNORE_INDEX
    labels[:, :2] = llama.IGNORE_INDEX
    pos = np.broadcast_to(np.arange(seqlen, dtype=np.int32),
                          (batch_size, seqlen)).copy()
    return {"input_ids": jnp.asarray(ids), "attention_mask": jnp.asarray(mask),
            "position_ids": jnp.asarray(pos), "labels": jnp.asarray(labels)}


def run_schedule(params, batch, cfg, pp, schedule, v=1, microbatches=4,
                 chunks=1, seq=None, counts=None):
    mesh = make_mesh(MeshConfig(pp=pp))
    if counts is None:
        manifest = StageManifest.for_config(cfg, pp, virtual_stages=v)
    else:
        manifest = StageManifest(num_layers=cfg.num_hidden_layers,
                                 num_stages=pp, layer_counts=tuple(counts))
    stacked = pl.stack_stages(params, manifest)
    pcfg = pl.PipelineConfig(num_stages=pp, num_microbatches=microbatches,
                             schedule=schedule, virtual_stages=v,
                             accum_chunks=chunks, unit_schedule=seq,
                             layer_counts=counts)
    fn = jax.jit(pl.make_pipeline_loss_and_grad(mesh, cfg, pcfg, stacked))
    out = fn(stacked, batch)
    return out[0], pl.unstack_stages(out[1], manifest)


def assert_tree_bitexact(a, b):
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a, b)


# ---------------------------------------------------------------------------
# Generators: idle-unit counting reproduces the deleted closed formulas
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("schedule,m,s,v,closed", [
    # flat counts as interleaved at v=1 since PR 38: c(S-1) / (M + c(S-1))
    ("1f1b", 4, 2, 1, 1 / (4 + 1)),
    ("1f1b", 8, 4, 1, 3 / (8 + 3)),
    ("1f1b", 1, 4, 1, 3 / 4),
    ("interleaved_1f1b", 4, 2, 2, 1 / (8 + 1)),
    ("interleaved_1f1b", 8, 4, 2, 3 / (16 + 3)),
    ("interleaved_1f1b", 1, 4, 1, 3 / 4),
    ("zb1", 4, 2, 2, 2 / (24 + 2)),
    ("zb1", 8, 4, 2, 6 / (48 + 6)),
    ("zb1", 1, 4, 1, 6 / 9),
])
def test_canonical_bubble_matches_closed_forms(schedule, m, s, v, closed):
    """The emitted sequence's (idle, wall) integer pair reduces to the
    exact rational the deleted per-schedule formulas computed — so the
    bubble_fraction floats stay bit-identical across the refactor."""
    seq = us.canonical_schedule(schedule, m, s, v)
    us.validate(seq)
    idle, wall = us.bubble_stats(seq)
    assert idle / wall == closed
    pcfg = pl.PipelineConfig(num_stages=s, num_microbatches=m,
                             schedule=schedule, virtual_stages=v)
    assert pl.bubble_fraction(pcfg) == closed


def test_canonical_zb1_65b_shape_idle_count():
    """The 65B pp8/M=256/v=2 derivation pinned in test_zero_bubble now
    falls out of COUNTING the sequence: 14 idle units per stage over a
    1550-unit wall = 0.90%."""
    seq = us.canonical_schedule("zb1", 256, 8, 2)
    assert us.bubble_stats(seq) == (8 * 14, 8 * 1550)


def test_solver_bubble_fraction_via_sequence():
    """schedule: solver resolves bubble_fraction through its sequence —
    a canonical zb1 sequence scores exactly the zb1 number."""
    seq = us.canonical_schedule("zb1", 4, 2, 2)
    sv = pl.PipelineConfig(num_stages=2, num_microbatches=4,
                           schedule="solver", virtual_stages=2,
                           unit_schedule=seq)
    zb = pl.PipelineConfig(num_stages=2, num_microbatches=4, schedule="zb1",
                           virtual_stages=2)
    assert pl.bubble_fraction(sv) == pl.bubble_fraction(zb)


def test_flat_s1_degenerate_sequence():
    """S=1 flat has no forward half (the fused backward re-embeds under
    its stage-0 cond) — the generator emits a B-only grid and the
    validator accepts exactly this one forward-less form."""
    seq = us.generate_1f1b(4, 1)
    us.validate(seq)
    assert not seq.has_f.any() and seq.num_ticks == 4
    assert us.analytic_bubble(seq) == 0.0


# ---------------------------------------------------------------------------
# Flat 1f1b = interleaved at v=1 (PR 38): no half that every stage masks
# ---------------------------------------------------------------------------

FLAT_SHAPES = [(16, 4), (8, 4), (4, 2), (1, 4), (3, 4)]


def all_fb_flat_grid(m, s, stage_costs=None):
    """The flat 1f1b sequence as it stood until PR 38, written out by hand:
    forward unit t-s and backward unit t-(2S-2-s) over m + 2(S-1) ticks,
    EVERY tick structurally F+B with both ring directions."""
    ticks = m + 2 * (s - 1)
    t = np.arange(ticks)[:, None]
    st = np.arange(s)[None, :]
    fu, bu = t - st, t - (2 * (s - 1) - st)
    on = np.ones(ticks, bool)
    return us.UnitSchedule(
        num_stages=s, virtual_stages=1, num_microbatches=m,
        split_backward=False,
        f_unit=np.where((fu >= 0) & (fu < m), fu, -1).astype(np.int32),
        b_unit=np.where((bu >= 0) & (bu < m), bu, -1).astype(np.int32),
        w_unit=np.full((ticks, s), -1, np.int32),
        has_f=on, has_b=on.copy(), has_w=np.zeros(ticks, bool),
        ring_fwd=on.copy(), ring_bwd=on.copy(), ring_slots=min(2 * s - 1, m),
        offload_units=np.zeros(0, bool), wq_slot=np.zeros(0, np.int32),
        wq_hbm_slots=0, wq_host_slots=0, label="flat/all-F+B",
        stage_costs=stage_costs)


@pytest.mark.parametrize("m,s", FLAT_SHAPES)
def test_flat_is_interleaved_at_v1(m, s):
    """One sequence under two names: grids, per-tick flags, ring depth, the
    segments and the idle accounting are equal element for element, and
    the grids are the old all-F+B grid's (the units never moved)."""
    flat = us.canonical_schedule("1f1b", m, s)
    inter = us.canonical_schedule("interleaved_1f1b", m, s, 1)
    us.validate(flat)
    for name in ("f_unit", "b_unit", "w_unit", "has_f", "has_b", "has_w",
                 "ring_fwd", "ring_bwd"):
        np.testing.assert_array_equal(getattr(flat, name),
                                      getattr(inter, name), err_msg=name)
    assert flat.ring_slots == inter.ring_slots
    assert flat.label == "1f1b" and inter.label == "interleaved_1f1b"
    assert us.segments(flat) == us.segments(inter)
    assert us.bubble_stats(flat) == us.bubble_stats(inter)
    old = all_fb_flat_grid(m, s)
    us.validate(old)
    np.testing.assert_array_equal(flat.f_unit, old.f_unit)
    np.testing.assert_array_equal(flat.b_unit, old.b_unit)
    assert flat.ring_slots == old.ring_slots
    # the flags are the grid's own: a half is present iff some stage runs it
    np.testing.assert_array_equal(flat.has_f, (flat.f_unit >= 0).any(axis=1))
    np.testing.assert_array_equal(flat.has_b, (flat.b_unit >= 0).any(axis=1))
    assert [(g.label, g.num_ticks) for g in us.segments(flat)] == [
        ("F", s - 1), ("F+B", m), ("B", s - 1)]


def test_segments_labels_and_grouping():
    """The interpreter's compile units: maximal equal-flag tick runs that
    cover the tick axis once, named by their active halves."""
    zb1 = us.canonical_schedule("zb1", 4, 2, 2)
    segs = us.segments(zb1)
    assert [s.label for s in segs] == ["F", "F+B", "B", "W"]
    assert segs[0].t0 == 0 and segs[-1].t1 == zb1.num_ticks
    for a, b in zip(segs, segs[1:]):
        assert a.t1 == b.t0
    flat = us.segments(us.canonical_schedule("1f1b", 8, 4))
    assert [s.label for s in flat] == ["F", "F+B", "B"]
    assert [s.num_ticks for s in flat] == [3, 8, 3]
    drain_w = us.segments(us.list_schedule(8, 2, 2, w_placement="drain"))
    assert "B+W" in [s.label for s in drain_w]


@pytest.mark.parametrize("pp,chunks,counts", [
    (2, 1, None), (2, 2, None), (4, 1, None), (4, 2, None), (2, 1, (5, 3)),
])
def test_flat_bitexact_vs_all_fb_grid(cfg, params, devices, pp, chunks,
                                      counts):
    """The removed halves added exact zeros: loss and every gradient leaf
    of `1f1b` are bit-equal to the old all-F+B grid replayed under
    `schedule: solver` — per flush under accum_chunks, and on an unequal
    partition (padded-slot skipping included)."""
    batch = make_batch(cfg)
    seq = all_fb_flat_grid(4 // chunks, pp, stage_costs=counts)
    l_new, g_new = run_schedule(params, batch, cfg, pp, "1f1b",
                                chunks=chunks, counts=counts)
    l_old, g_old = run_schedule(params, batch, cfg, pp, "solver",
                                chunks=chunks, seq=seq, counts=counts)
    np.testing.assert_array_equal(np.asarray(l_new), np.asarray(l_old))
    assert_tree_bitexact(g_new, g_old)


# ---------------------------------------------------------------------------
# Validator negatives: cyclic deps, ring overflow, W-before-B, torn streams
# ---------------------------------------------------------------------------

def test_validator_rejects_w_before_b():
    seq = us.canonical_schedule("zb1", 4, 2, 1)
    # move unit 3's W replay into a steady tick before its B retires
    w = seq.w_unit.copy()
    has_w = seq.has_w.copy()
    w[w == 3] = -1
    w[2, :] = 3  # tick 2 is warm/steady — unit 3's B runs later
    has_w[2] = True
    bad = dataclasses.replace(seq, w_unit=w, has_w=has_w)
    with pytest.raises(us.ScheduleError, match="W before B"):
        us.validate(bad)


def test_validator_rejects_ring_overflow():
    seq = us.canonical_schedule("interleaved_1f1b", 8, 2, 2)
    bad = dataclasses.replace(seq, ring_slots=2)
    with pytest.raises(us.ScheduleError, match="ring overflow"):
        us.validate(bad)


def test_validator_rejects_broken_transport():
    """Swapping two forward rows makes a stage consume a unit its ring
    predecessor never produced — the data-level form of a cyclic
    dependency in the transport graph."""
    seq = us.canonical_schedule("1f1b", 4, 2)
    f = seq.f_unit.copy()
    f[[1, 2], :] = f[[2, 1], :]
    bad = dataclasses.replace(seq, f_unit=f)
    with pytest.raises(us.ScheduleError,
                       match="transport broken|cyclic dependency"):
        us.validate(bad)


def test_validator_rejects_incomplete_stream():
    seq = us.canonical_schedule("1f1b", 4, 2)
    b = seq.b_unit.copy()
    b[b == 2] = -1  # drop unit 2's backward everywhere
    bad = dataclasses.replace(seq, b_unit=b)
    with pytest.raises(us.ScheduleError, match="not each unit exactly once"):
        us.validate(bad)


def test_validator_rejects_unit_outside_flags():
    seq = us.canonical_schedule("interleaved_1f1b", 4, 2, 2)
    has_f = seq.has_f.copy()
    has_f[0] = False  # tick 0 schedules F0 on stage 0
    bad = dataclasses.replace(seq, has_f=has_f)
    with pytest.raises(us.ScheduleError, match="has_f"):
        us.validate(bad)


# ---------------------------------------------------------------------------
# Serialization: per-stage typed sequences round-trip exactly
# ---------------------------------------------------------------------------

def test_json_roundtrip_exact():
    seq = us.with_offload(us.canonical_schedule("zb1", 4, 2, 2),
                          np.array([True, False] * 4))
    rt = us.from_json(us.to_json(seq))
    for f in ("f_unit", "b_unit", "w_unit", "offload_units", "wq_slot",
              "has_f", "has_b", "has_w", "ring_fwd", "ring_bwd"):
        np.testing.assert_array_equal(getattr(seq, f), getattr(rt, f))
    assert (seq.ring_slots, seq.wq_hbm_slots, seq.wq_host_slots) == \
           (rt.ring_slots, rt.wq_hbm_slots, rt.wq_host_slots)
    doc = json.loads(us.to_json(seq))
    # the serialized form is per-stage sequences of typed units
    assert doc["stages"][1][1].startswith("F0")


def test_from_json_rejects_garbage():
    with pytest.raises(us.ScheduleError, match="format"):
        us.from_json(json.dumps({"format": "something-else"}))
    doc = json.loads(us.to_json(us.canonical_schedule("1f1b", 2, 2)))
    doc["stages"][0][0] = "Q7"
    with pytest.raises(us.ScheduleError, match="bad unit token"):
        us.from_json(json.dumps(doc))
    # a structurally valid document with broken transport fails validate()
    doc2 = json.loads(us.to_json(us.canonical_schedule("1f1b", 2, 2)))
    doc2["stages"][0][0], doc2["stages"][0][1] = (doc2["stages"][0][1],
                                                  doc2["stages"][0][0])
    with pytest.raises(us.ScheduleError):
        us.from_json(json.dumps(doc2))


def test_ascii_timeline_smoke():
    text = us.ascii_timeline(us.canonical_schedule("zb1", 4, 2, 2))
    assert "stage  0" in text and "stage  1" in text
    assert "F0" in text and "W7" in text and "ring" in text


# ---------------------------------------------------------------------------
# The search space beyond the canonical three
# ---------------------------------------------------------------------------

def test_drain_w_placement_same_bubble_smaller_queue():
    """The list scheduler's drain-interleaved W placement: wall clock and
    bubble IDENTICAL to canonical zb1 (each drain tick's W replaces one
    trailing W tick), W-queue slots strictly fewer after liveness reuse."""
    trailing = us.canonical_schedule("zb1", 8, 4, 2)
    drain = us.list_schedule(8, 4, 2, w_placement="drain")
    assert us.bubble_stats(drain) == us.bubble_stats(trailing)
    assert drain.wq_hbm_slots < trailing.wq_hbm_slots


def test_offload_vector_boundary_points_match_boolean_byte_models():
    """All-True/all-False decision vectors reproduce the legacy boolean's
    byte models EXACTLY — `offload.wgrad_stash` on/off are boundary points
    of the solver's per-unit decision space."""
    dims = (2, 16, 64, 2)
    seq = us.canonical_schedule("zb1", 4, 2, 2)
    for flag, vector in ((False, np.zeros(8, bool)), (True, np.ones(8, bool))):
        zb = pl.PipelineConfig(num_stages=2, num_microbatches=4,
                               schedule="zb1", virtual_stages=2,
                               offload_wgrad=flag)
        sv = pl.PipelineConfig(num_stages=2, num_microbatches=4,
                               schedule="solver", virtual_stages=2,
                               unit_schedule=us.with_offload(seq, vector))
        assert pl.wgrad_partition(sv) == pl.wgrad_partition(zb)
        assert pl.wgrad_queue_peak(sv) == pl.wgrad_queue_peak(zb)
        assert pl.wgrad_offloaded_units(sv) == pl.wgrad_offloaded_units(zb)
        assert pl.wgrad_stash_bytes(sv, *dims) == pl.wgrad_stash_bytes(zb, *dims)
        assert pl.host_stash_bytes(sv, *dims) == pl.host_stash_bytes(zb, *dims)


def test_mixed_offload_vector_partitions():
    seq = us.with_offload(us.canonical_schedule("zb1", 4, 2, 2),
                          np.array([True] * 3 + [False] * 5))
    sv = pl.PipelineConfig(num_stages=2, num_microbatches=4,
                           schedule="solver", virtual_stages=2,
                           unit_schedule=seq)
    assert pl.wgrad_partition(sv) == (5, 3)
    assert pl.wgrad_offloaded_units(sv) == 3
    slot = 2 * 16 * 64 * 2
    # host bytes: 2 buffers x 3 slots + the two garbage slots
    assert pl.host_stash_bytes(sv, 2, 16, 64, 2) == 2 * 3 * slot + 2 * slot


# ---------------------------------------------------------------------------
# PipelineConfig plumbing
# ---------------------------------------------------------------------------

def test_pipeline_config_solver_validation():
    seq = us.canonical_schedule("zb1", 4, 2, 2)
    kw = dict(num_stages=2, num_microbatches=4, schedule="solver",
              virtual_stages=2)
    pl.PipelineConfig(unit_schedule=seq, **kw)  # fits
    with pytest.raises(ValueError, match="needs a unit sequence"):
        pl.PipelineConfig(**kw)
    with pytest.raises(ValueError, match="does not fit"):
        pl.PipelineConfig(unit_schedule=seq, num_stages=4,
                          num_microbatches=4, schedule="solver",
                          virtual_stages=2)
    with pytest.raises(ValueError, match="does not fit"):
        pl.PipelineConfig(unit_schedule=seq, num_stages=2,
                          num_microbatches=8, schedule="solver",
                          virtual_stages=2)
    with pytest.raises(ValueError, match="per-unit offload"):
        pl.PipelineConfig(unit_schedule=seq, offload_wgrad=True, **kw)
    with pytest.raises(ValueError, match="only meaningful"):
        pl.PipelineConfig(num_stages=2, num_microbatches=4, schedule="zb1",
                          virtual_stages=2, unit_schedule=seq)
    # accum_chunks: the sequence is PER FLUSH
    pl.PipelineConfig(unit_schedule=seq, num_stages=2, num_microbatches=8,
                      schedule="solver", virtual_stages=2, accum_chunks=2)


# ---------------------------------------------------------------------------
# Interpreter replay: loaded sequences run bit-exact on the parity grid
# ---------------------------------------------------------------------------

def test_solver_mixed_offload_bitexact_vs_flat(cfg, params, devices):
    """The acceptance-grade replay proof in the test_zero_bubble assertion
    style: a solver sequence (canonical zb1 placement, MIXED per-unit
    offload vector, round-tripped through JSON) produces losses AND
    unstacked gradients bit-identical to the flat fused-backward schedule
    — transfers are copies and the fold order is unchanged, so selective
    offload can never move the numbers."""
    batch = make_batch(cfg)
    seq = us.with_offload(us.canonical_schedule("zb1", 4, 2, 2),
                          np.array([True, False, True, False,
                                    False, True, False, True]))
    seq = us.from_json(us.to_json(seq))  # exercise the loader path too
    l_flat, g_flat = run_schedule(params, batch, cfg, 2, "1f1b")
    l_sv, g_sv = run_schedule(params, batch, cfg, 2, "solver", v=2, seq=seq)
    assert float(l_sv) == float(l_flat)
    assert_tree_bitexact(g_sv, g_flat)


@pytest.mark.slow
def test_solver_drain_w_reordered_folds_allclose(cfg, params, devices):
    """The drain-interleaved W placement reorders the fp32 weight-grad
    folds (that is the point — earlier retirement), so parity is allclose,
    not bit-exact; the loss (no fold reorder) stays bit-equal."""
    batch = make_batch(cfg)
    l_flat, g_flat = run_schedule(params, batch, cfg, 2, "1f1b")
    drain = us.list_schedule(4, 2, 2, w_placement="drain")
    l_dr, g_dr = run_schedule(params, batch, cfg, 2, "solver", v=2, seq=drain)
    assert float(l_dr) == float(l_flat)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a, np.float64), np.asarray(b, np.float64),
        rtol=2e-5, atol=1e-6), g_dr, g_flat)


@pytest.mark.slow
def test_solver_accum_chunks_bitexact(cfg, params, devices):
    """A per-flush sequence replayed over accum_chunks flushes matches the
    chunked flat schedule bit-for-bit."""
    batch = make_batch(cfg)
    seq = us.canonical_schedule("zb1", 2, 2, 2)
    l_flat, g_flat = run_schedule(params, batch, cfg, 2, "1f1b",
                                  microbatches=4, chunks=2)
    l_sv, g_sv = run_schedule(params, batch, cfg, 2, "solver", v=2,
                              microbatches=4, chunks=2, seq=seq)
    assert float(l_sv) == float(l_flat)
    assert_tree_bitexact(g_sv, g_flat)


@pytest.mark.slow
def test_trainer_runs_solver_schedule_file(tmp_path, devices):
    """schedule_file plumbs through train.py the way zb1's knob did: a
    tiny run under `pipeline_schedule: solver` + an emitted sequence file
    trains end-to-end and the metrics line carries the solver schedule
    name, its sequence-derived bubble, and the selective-offload tier."""
    import json as _json
    import os

    from llama_pipeline_parallel_tpu.train import run_training

    seq = us.with_offload(us.canonical_schedule("zb1", 2, 2, 2),
                          np.array([True, False, False, True]))
    sched_path = tmp_path / "sched.json"
    sched_path.write_text(us.to_json(seq))
    out = tmp_path / "run"
    run_training({
        "output_dir": str(out),
        "mesh": {"pp": 2},
        "model": {"preset": "tiny", "dtype": "float32"},
        "dataset": {"synthetic": True, "seq_length": 16,
                    "pseudo_dataset_len": 64},
        "seed": 7,
        "per_device_train_batch_size": 2,
        "gradient_accumulation_steps": 2,
        "pipeline_schedule": "solver",
        "virtual_stages": 2,
        "schedule_file": str(sched_path),
        "max_steps": 2,
        "learning_rate": 1e-3,
        "warmup_steps": 1,
        "logging_steps": 1,
        "save_steps": 0,
        "save_final": False,
    })
    lines = [_json.loads(ln) for ln in
             open(os.path.join(str(out), "metrics.jsonl"))]
    assert lines and lines[0]["schedule"] == "solver"
    assert lines[0]["wgrad_queue_depth"] == pl.wgrad_queue_peak(
        pl.PipelineConfig(num_stages=2, num_microbatches=2,
                          schedule="solver", virtual_stages=2,
                          unit_schedule=seq)) == 4
    assert lines[0]["bubble_fraction"] == round(us.analytic_bubble(seq), 4)
    assert lines[0]["offload_stash"] == "wgrad_stash[2/4]"


def test_generator_and_validator_reject_partial_unit_groups():
    """m not divisible by S at v > 1 breaks the round-robin unit-group
    layout — the generator refuses, and a hand-built sequence with a
    partial group is a named ScheduleError, not an IndexError."""
    with pytest.raises(us.ScheduleError, match="divisible"):
        us.list_schedule(3, 2, 2, w_placement="drain")
    good = us.canonical_schedule("interleaved_1f1b", 4, 2, 2)
    bad = dataclasses.replace(good, num_microbatches=3)
    with pytest.raises(us.ScheduleError, match="round-robin unit groups"):
        us.validate(bad)


def test_validator_rejects_degenerate_slot_metadata():
    """ring_slots < 1 (numpy's `% 0` degenerates to a warning, not an
    error) and negative wq_slot entries (the interpreter's clip would
    alias residuals) are named rejections, not downstream trace bugs."""
    seq = us.canonical_schedule("1f1b", 4, 2)
    with pytest.raises(us.ScheduleError, match="ring_slots"):
        us.validate(dataclasses.replace(seq, ring_slots=0))
    zb = us.canonical_schedule("zb1", 4, 2, 2)
    wq = zb.wq_slot.copy()
    wq[3] = -1
    with pytest.raises(us.ScheduleError, match="negative wq_slot"):
        us.validate(dataclasses.replace(zb, wq_slot=wq))


# ---------------------------------------------------------------------------
# Per-stage unit costs: unequal partitions in the bubble accounting
# ---------------------------------------------------------------------------

def test_stage_costs_bubble_weighting_by_hand():
    """The costed accounting at a shape small enough to count by hand:
    flat fused 1f1b, m=4, S=2, costs (2,1). Of the 6 ticks one is F-only,
    four F+B and one B-only: 5 F halves + 5 B halves, wall per stage =
    (5*1 + 5*2) * cmax(2) = 30, total 60; useful = F (4 units * cost per
    stage: 4*2 + 4*1 = 12) + B (twice that, fused cost 2) = 36 -> bubble
    24/60 = 2/5, vs the even 1/5."""
    seq = us.generate_1f1b(4, 2, stage_costs=(2, 1))
    idle, wall = us.bubble_stats(seq)
    assert (idle, wall) == (24, 60)
    assert us.analytic_bubble(seq) == 0.4
    assert us.analytic_bubble(us.generate_1f1b(4, 2)) == pytest.approx(1 / 5)


def test_uniform_stage_costs_bit_identical_to_uncosted():
    """A uniform cost vector (an even partition's k) must reduce to the
    identical rational — floats bit-equal, the canonical-parity
    contract."""
    for sched, v in (("1f1b", 1), ("interleaved_1f1b", 2), ("zb1", 2)):
        a = us.analytic_bubble(us.canonical_schedule(sched, 8, 4, v))
        b = us.analytic_bubble(us.canonical_schedule(sched, 8, 4, v,
                                                     stage_costs=(10,) * 4))
        assert a == b  # bit-equal, not approx


def test_stage_costs_json_roundtrip_and_validation():
    seq = us.canonical_schedule("zb1", 4, 4, stage_costs=(4, 4, 4, 1))
    seq2 = us.from_json(us.to_json(seq))
    assert seq2.stage_costs == (4, 4, 4, 1)
    assert us.bubble_stats(seq2) == us.bubble_stats(seq)
    # costless documents still round-trip (no stage_costs key)
    plain = us.from_json(us.to_json(us.canonical_schedule("zb1", 4, 4)))
    assert plain.stage_costs is None
    with pytest.raises(us.ScheduleError, match="entries for"):
        us.generate_1f1b(4, 2, stage_costs=(2, 1, 1))
    with pytest.raises(us.ScheduleError, match=">= 1"):
        us.generate_1f1b(4, 2, stage_costs=(2, 0))
    with pytest.raises(us.ScheduleError, match="no uneven form"):
        us.generate_interleaved(4, 2, 2, stage_costs=(2, 1))
    bad = dataclasses.replace(us.canonical_schedule("1f1b", 4, 2),
                              stage_costs=(1, 2, 3))
    with pytest.raises(us.ScheduleError, match="entries for"):
        us.validate(bad)


def test_pipeline_bubble_fraction_counts_uneven_costs():
    """pipeline.bubble_fraction threads layer_counts into the sequence's
    cost accounting: the uneven zb1 bubble is the costed sequence's
    number, strictly above its even twin at the same shape."""
    uneven = pl.PipelineConfig(num_stages=4, num_microbatches=8,
                               schedule="zb1", layer_counts=(4, 4, 4, 1))
    even = pl.PipelineConfig(num_stages=4, num_microbatches=8,
                             schedule="zb1")
    seq = us.canonical_schedule("zb1", 8, 4, stage_costs=(4, 4, 4, 1))
    assert pl.bubble_fraction(uneven) == us.analytic_bubble(seq)
    assert pl.bubble_fraction(uneven) > pl.bubble_fraction(even)
    assert "layers/stage=[4, 4, 4, 1]" in us.ascii_timeline(seq)


def test_uniform_cost_sequence_on_uneven_run_gets_run_costs():
    """A sequence carrying UNIFORM stage costs is the same accounting as a
    costless one: run on an unequal partition, the run's real layer counts
    are attached (never the uniform vector's k), so the reported bubble is
    the honest costed number — the uniform-costs bypass of the
    partition-mismatch check cannot pin wrong accounting."""
    uniform = us.canonical_schedule("zb1", 4, 2, stage_costs=(2, 2))
    pcfg = pl.PipelineConfig(num_stages=2, num_microbatches=4,
                             schedule="solver", unit_schedule=uniform,
                             layer_counts=(3, 1))
    costed = us.canonical_schedule("zb1", 4, 2, stage_costs=(3, 1))
    assert pl.bubble_fraction(pcfg) == us.analytic_bubble(costed)
    # genuinely uneven sequence costs still refuse a mismatched run
    with pytest.raises(ValueError, match="stage layer counts"):
        pl.PipelineConfig(num_stages=2, num_microbatches=4,
                          schedule="solver",
                          unit_schedule=us.canonical_schedule(
                              "zb1", 4, 2, stage_costs=(3, 1)),
                          layer_counts=(1, 3))
