"""Interleaved 1F1B (virtual pipeline stages) correctness.

The schedule-parity suite the CI `schedule-parity` step runs: interleaved
loss AND gradients must match the flat 1f1b schedule BIT-exactly on the
dryrun grid topologies (pp=2 v=2, pp=4 v=2) — the two schedules reorder
only zero-padded accumulation, so any drift is a scheduling bug, not
float noise. Plus: the round-robin stacked layout's bit-exact round trip
(PR-2 checkpoints and the HF converter ride on it), the [S, v] activation
stats, the eval path, the full-trainer plumbing, and every new validation
error."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.models.llama.manifest import StageManifest
from llama_pipeline_parallel_tpu.parallel import pipeline as pl
from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig, make_mesh


@pytest.fixture(scope="module")
def cfg():
    return LlamaConfig.tiny(num_hidden_layers=8)  # 8 layers: pp*v up to 8


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
    pos = np.broadcast_to(np.arange(seqlen, dtype=np.int32), (batch_size, seqlen)).copy()
    return {
        "input_ids": jnp.asarray(ids),
        "attention_mask": jnp.asarray(mask),
        "position_ids": jnp.asarray(pos),
        "labels": jnp.asarray(labels),
    }


def run_schedule(params, batch, cfg, pp, schedule, v=1, dp=1, tp=1, sp=1,
                 microbatches=4, chunks=1, collect_stats=False):
    mesh = make_mesh(MeshConfig(pp=pp, dp=dp, tp=tp, sp=sp))
    manifest = StageManifest.for_config(cfg, pp, virtual_stages=v)
    stacked = pl.stack_stages(params, manifest)
    pcfg = pl.PipelineConfig(num_stages=pp, num_microbatches=microbatches,
                             schedule=schedule, virtual_stages=v,
                             accum_chunks=chunks)
    fn = jax.jit(pl.make_pipeline_loss_and_grad(mesh, cfg, pcfg, stacked,
                                                collect_stats=collect_stats))
    out = fn(stacked, batch)
    loss, grads = out[0], pl.unstack_stages(out[1], manifest)
    return (loss, grads, out[2]) if collect_stats else (loss, grads, None)


def assert_tree_bitexact(a, b):
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a, b)


# ---------------------------------------------------------------------------
# Schedule parity: interleaved == flat, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pp,v,microbatches", [
    (2, 2, 4),                  # the dryrun_multichip acceptance grid
    # one fast representative is enough for the tier-1 budget (PR 10
    # rebalance); the deeper rings / v=4 / bigger-M rows are round-gate
    pytest.param(4, 2, 4, marks=pytest.mark.slow),
    pytest.param(2, 4, 4, marks=pytest.mark.slow),   # deeper interleaving
    pytest.param(2, 2, 8, marks=pytest.mark.slow),
    pytest.param(4, 2, 8, marks=pytest.mark.slow),
])
def test_interleaved_matches_flat_bitexact(cfg, params, devices, pp, v,
                                           microbatches):
    """Loss AND unstacked gradients identical to the flat schedule: both
    accumulate each layer's per-microbatch gradients in the same order, and
    the only extra terms are exact zeros (masked vjp cotangents, the
    dynamic-slice scatter's untouched chunks)."""
    batch = make_batch(cfg, batch_size=microbatches * 2)
    l_flat, g_flat, _ = run_schedule(params, batch, cfg, pp, "1f1b",
                                     microbatches=microbatches)
    l_int, g_int, _ = run_schedule(params, batch, cfg, pp, "interleaved_1f1b",
                                   v=v, microbatches=microbatches)
    assert float(l_int) == float(l_flat)
    assert_tree_bitexact(g_int, g_flat)


@pytest.mark.parametrize("dp,tp,sp,chunks", [
    (2, 1, 1, 1),               # one fast hybrid rep (PR 10 rebalance)
    pytest.param(1, 2, 1, 1, marks=pytest.mark.slow),
    pytest.param(1, 1, 2, 1, marks=pytest.mark.slow),
    pytest.param(1, 1, 1, 2, marks=pytest.mark.slow),
])
def test_interleaved_hybrid_grids_bitexact(cfg, params, devices, dp, tp, sp,
                                           chunks):
    """Interleaving composes with dp/tp/sp sharding and chunked
    accumulation without losing the bit-exact flat equivalence (the tp head
    gating, sp label shift, and accum fold are all shared code paths)."""
    m = 4
    batch = make_batch(cfg, batch_size=dp * m * 2)
    l_flat, g_flat, _ = run_schedule(params, batch, cfg, 2, "1f1b", dp=dp,
                                     tp=tp, sp=sp, microbatches=m, chunks=chunks)
    l_int, g_int, _ = run_schedule(params, batch, cfg, 2, "interleaved_1f1b",
                                   v=2, dp=dp, tp=tp, sp=sp, microbatches=m,
                                   chunks=chunks)
    assert float(l_int) == float(l_flat)
    assert_tree_bitexact(g_int, g_flat)


@pytest.mark.slow  # PR 11: under the one interpreter this follows from the
# fast interleaved-vs-flat rep + test_pipeline's flat-vs-single-device
# anchor by transitivity; runs in the round gate
def test_interleaved_matches_single_device_reference(cfg, params, devices):
    """And the flat schedule itself is pinned to the plain forward, so the
    interleaved grads are the true ones, not merely self-consistent."""
    batch = make_batch(cfg)

    def loss(p):
        logits = llama.forward(p, batch["input_ids"], batch["attention_mask"],
                               batch["position_ids"], cfg=cfg)
        return llama.loss_fn(logits, batch["labels"])

    ref_loss, ref_grads = jax.value_and_grad(loss)(params)
    l_int, g_int, _ = run_schedule(params, batch, cfg, 4, "interleaved_1f1b",
                                   v=2, microbatches=4)
    np.testing.assert_allclose(float(l_int), float(ref_loss), rtol=1e-5)
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y), rtol=2e-5, atol=1e-6), g_int, ref_grads)


@pytest.mark.parametrize("pp,microbatches", [
    # (2,4) slow since PR 11: same v1-degenerate segment structure as the
    # fast (4,2) M<S row under the one interpreter — its fast-lane slot
    # funds the solver-sequence tests (test_unit_schedule.py)
    pytest.param(2, 4, marks=pytest.mark.slow),
    (4, 2),   # M < S: the pipe never fills — pure warmup+drain masking
    pytest.param(4, 1, marks=pytest.mark.slow),   # M == 1 (sub-case of M<S)
])
def test_interleaved_v1_degenerates_to_flat(cfg, params, devices, pp,
                                            microbatches):
    """virtual_stages=1 runs the interleaved code path on the flat stacked
    layout and must still be bit-identical — the degenerate case that keeps
    the two schedules mutually testable (including M < S, where the steady
    phase shrinks to nothing and masking carries the whole schedule)."""
    batch = make_batch(cfg, batch_size=max(microbatches * 2, 2))
    l_flat, g_flat, _ = run_schedule(params, batch, cfg, pp, "1f1b",
                                     microbatches=microbatches)
    l_int, g_int, _ = run_schedule(params, batch, cfg, pp, "interleaved_1f1b",
                                   v=1, microbatches=microbatches)
    assert float(l_int) == float(l_flat)
    assert_tree_bitexact(g_int, g_flat)


def test_interleaved_eval_matches(cfg, params, devices):
    """make_pipeline_eval_fn understands the interleaved layout (the
    forward-only loop walks the v*S virtual ring)."""
    batch = make_batch(cfg)
    mesh = make_mesh(MeshConfig(pp=2))
    manifest = StageManifest.for_config(cfg, 2, virtual_stages=2)
    stacked = pl.stack_stages(params, manifest)
    pcfg = pl.PipelineConfig(num_stages=2, num_microbatches=4,
                             schedule="interleaved_1f1b", virtual_stages=2)
    loss_sum, count = jax.jit(pl.make_pipeline_eval_fn(
        mesh, cfg, pcfg, stacked))(stacked, batch)
    l_flat, _, _ = run_schedule(params, batch, cfg, 2, "1f1b")
    np.testing.assert_allclose(float(loss_sum) / float(count), float(l_flat),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# Stacked layout: round-robin assignment + bit-exact round trip
# ---------------------------------------------------------------------------

def test_interleaved_stack_roundtrip_bitexact(cfg, params):
    man = StageManifest.for_config(cfg, 2, virtual_stages=2)
    rt = pl.unstack_stages(pl.stack_stages(params, man), man)
    assert_tree_bitexact(rt, params)


def test_interleaved_stack_is_round_robin(cfg, params):
    """stacked[s, j] holds exactly the layers manifest.layers_of_chunk(s, j)
    names — the layout and the manifest's layer->(stage, chunk) map agree."""
    man = StageManifest.for_config(cfg, 2, virtual_stages=2)
    stacked = pl.stack_stages(params, man)
    wq_c = np.asarray(params["layers"]["attn"]["wq"])  # [n, d, d]
    wq_s = np.asarray(stacked["layers"]["attn"]["wq"])  # [S, v, k, d, d]
    assert wq_s.shape[:3] == (2, 2, man.layers_per_chunk)
    for s in range(man.num_stages):
        for j in range(man.virtual_stages):
            layers = list(man.layers_of_chunk(s, j))
            np.testing.assert_array_equal(wq_s[s, j], wq_c[layers])
    # and the inverse maps agree with it
    for layer in range(man.num_layers):
        s, j = man.chunk_of_layer(layer)
        assert layer in list(man.layers_of_chunk(s, j))
        assert man.stage_of_layer(layer) == s
    # per-stage view: sorted union of the stage's chunks
    assert list(man.layers_of_stage(0)) == [0, 1, 4, 5]
    assert list(man.layers_of_stage(1)) == [2, 3, 6, 7]


def test_interleaved_manifest_json_roundtrip(cfg):
    man = StageManifest.for_config(cfg, 2, virtual_stages=2)
    assert StageManifest.from_json(man.to_json()) == man
    # pre-interleaving metadata (no virtual_stages key) still deserializes
    legacy = json.loads(StageManifest.for_config(cfg, 2).to_json())
    del legacy["virtual_stages"]
    assert StageManifest(**legacy).virtual_stages == 1


def test_checkpoint_roundtrips_across_schedules(cfg, params, tmp_path, devices):
    """A checkpoint written under the INTERLEAVED layout restores into the
    flat layout (and vice versa) unchanged: the canonical [num_layers, ...]
    on-disk layout is the interchange, so PR-2 checkpoints and the HF
    converter keep working with no migration."""
    from llama_pipeline_parallel_tpu.ckpt.checkpoint import CheckpointManager
    from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig as LC

    man_i = StageManifest.for_config(cfg, 2, virtual_stages=2)
    man_f = StageManifest.for_config(cfg, 4)
    stacked_i = pl.stack_stages(params, man_i)

    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save(3, stacked_i, man_i, cfg)

    # restore the interleaved-written checkpoint into a flat pp=4 topology
    restored_f = mgr.load_params(3, pl.stack_stages(params, man_f), man_f)
    assert_tree_bitexact(pl.unstack_stages(restored_f, man_f), params)
    # and back into the interleaved layout itself
    restored_i = mgr.load_params(3, stacked_i, man_i)
    assert_tree_bitexact(restored_i, stacked_i)
    # meta carries the virtual manifest
    assert StageManifest(**mgr.load_meta(3)["manifest"]) == man_i


# ---------------------------------------------------------------------------
# Stats: [S, v] activation reductions
# ---------------------------------------------------------------------------

def test_interleaved_collect_stats_shapes(cfg, params, devices):
    _, _, stats = run_schedule(params, make_batch(cfg), cfg, 2,
                               "interleaved_1f1b", v=2, collect_stats=True)
    assert np.asarray(stats["act_absmax_per_chunk"]).shape == (2, 2)
    assert np.asarray(stats["act_rms_per_chunk"]).shape == (2, 2)
    assert np.asarray(stats["act_absmax_per_stage"]).shape == (2,)
    assert np.asarray(stats["act_rms_per_stage"]).shape == (2,)
    for v in stats.values():
        assert np.all(np.isfinite(np.asarray(v)))
        assert np.all(np.asarray(v) > 0)
    # the per-stage view is the chunk view reduced over the chunk axis
    np.testing.assert_allclose(
        np.asarray(stats["act_absmax_per_stage"]),
        np.asarray(stats["act_absmax_per_chunk"]).max(axis=1), rtol=1e-6)


def test_step_stats_flatten_chunk_axis(cfg, params, devices):
    """numerics.step_stats on the interleaved [S, v, k, ...] layout: the
    per-stage vectors keep length S and the per-layer grid flattens the
    chunk axis to [S, v*k] chunk-major slots."""
    from llama_pipeline_parallel_tpu.utils import numerics

    man = StageManifest.for_config(cfg, 2, virtual_stages=2)
    stacked = pl.stack_stages(params, man)
    stats = jax.jit(lambda p: numerics.step_stats(p, p, virtual_stages=2))(stacked)
    assert np.asarray(stats["grad_norm_per_stage"]).shape == (2,)
    assert np.asarray(stats["grad_absmax_per_layer"]).shape == (2, 4)
    assert not bool(stats["nonfinite"])
    # flat vs interleaved layouts agree on the per-stage norm (same layers
    # per stage, different slot order)
    man_f = StageManifest.for_config(cfg, 2)
    flat = jax.jit(lambda p: numerics.step_stats(p, p))(
        pl.stack_stages(params, man_f))
    # stage 0 holds layers {0,1,4,5} interleaved vs {0,1,2,3} flat — norms
    # differ; the TOTAL over stages must match exactly either way
    np.testing.assert_allclose(
        float(jnp.sum(jnp.square(stats["grad_norm_per_stage"]))),
        float(jnp.sum(jnp.square(flat["grad_norm_per_stage"]))), rtol=1e-5)


# ---------------------------------------------------------------------------
# Validation
# ---------------------------------------------------------------------------

def test_virtual_stages_require_interleaved_schedule():
    with pytest.raises(ValueError, match="interleaved_1f1b"):
        pl.PipelineConfig(num_stages=2, num_microbatches=4, virtual_stages=2)


def test_interleaved_requires_divisible_microbatches():
    with pytest.raises(ValueError, match="divisible by num_stages"):
        pl.PipelineConfig(num_stages=4, num_microbatches=6,
                          schedule="interleaved_1f1b", virtual_stages=2)
    # ...per FLUSH: chunking can break divisibility even when M satisfies it
    with pytest.raises(ValueError, match="divisible by num_stages"):
        pl.PipelineConfig(num_stages=4, num_microbatches=8, accum_chunks=4,
                          schedule="interleaved_1f1b", virtual_stages=2)


def test_interleaved_rejects_uneven_partition(cfg):
    with pytest.raises(ValueError, match="even"):
        pl.PipelineConfig(num_stages=2, num_microbatches=4,
                          schedule="interleaved_1f1b", virtual_stages=2,
                          layer_counts=(5, 3))
    with pytest.raises(ValueError, match="even partition"):
        StageManifest(num_layers=8, num_stages=2, virtual_stages=2,
                      layer_counts=(5, 3))
    with pytest.raises(ValueError, match="not divisible"):
        StageManifest(num_layers=6, num_stages=2, virtual_stages=2)


def test_layout_schedule_mismatch_fails_at_build(cfg, params, devices):
    """Flat-stacked params with an interleaved pcfg (and the converse) fail
    loudly at build time, not as a shape error inside shard_map."""
    mesh = make_mesh(MeshConfig(pp=2))
    flat = pl.stack_stages(params, StageManifest.for_config(cfg, 2))
    inter = pl.stack_stages(params,
                            StageManifest.for_config(cfg, 2, virtual_stages=2))
    pcfg_i = pl.PipelineConfig(num_stages=2, num_microbatches=4,
                               schedule="interleaved_1f1b", virtual_stages=2)
    pcfg_f = pl.PipelineConfig(num_stages=2, num_microbatches=4)
    with pytest.raises(ValueError, match="stack_stages"):
        pl.make_pipeline_loss_and_grad(mesh, cfg, pcfg_i, flat)
    with pytest.raises(ValueError, match="virtual_stages manifest"):
        pl.make_pipeline_loss_and_grad(mesh, cfg, pcfg_f, inter)


def test_trainer_rejects_virtual_stages_without_schedule(cfg):
    from llama_pipeline_parallel_tpu.train import build_manifest

    with pytest.raises(ValueError, match="pipeline_schedule"):
        build_manifest({"virtual_stages": 2}, cfg, 2)
    with pytest.raises(ValueError, match="round-robin"):
        build_manifest({"virtual_stages": 2,
                        "pipeline_schedule": "interleaved_1f1b",
                        "stage_balance": "cost"}, cfg, 2)


# ---------------------------------------------------------------------------
# bubble_fraction: schedule x accum_chunks x virtual_stages grid
# ---------------------------------------------------------------------------

def _pcfg(schedule, s, m, c=1, v=1):
    return pl.PipelineConfig(num_stages=s, num_microbatches=m, accum_chunks=c,
                             schedule=schedule, virtual_stages=v)


@pytest.mark.parametrize("schedule,s,m,c,v,expected", [
    # flat 1f1b (= interleaved at v=1 since PR 38): c(S-1) / (M + c(S-1))
    ("1f1b", 4, 8, 1, 1, 3 / 11),
    ("1f1b", 8, 256, 1, 1, 7 / 263),
    ("1f1b", 4, 8, 2, 1, 6 / 14),
    # m per flush == 1 (m == accum_chunks): every flush is pure fill+drain
    ("1f1b", 4, 4, 4, 1, 12 / 16),
    # gpipe: c(S-1) / (M + c(S-1))
    ("gpipe", 4, 8, 1, 1, 3 / 11),
    ("gpipe", 4, 8, 4, 1, 12 / 20),
    ("gpipe", 4, 4, 4, 1, 12 / 16),
    # interleaved: c(S-1) / (Mv + c(S-1))
    ("interleaved_1f1b", 4, 8, 1, 2, 3 / 19),
    ("interleaved_1f1b", 8, 256, 1, 2, 7 / 519),
    ("interleaved_1f1b", 4, 8, 2, 2, 6 / 22),
    ("interleaved_1f1b", 4, 8, 1, 1, 3 / 11),
    ("interleaved_1f1b", 2, 8, 4, 4, 4 / 36),
    # m per flush == accum chunks degenerate under interleaving: flush m=S
    ("interleaved_1f1b", 2, 4, 2, 2, 2 / 10),
    # zb1 (split B/W backward): 2c(S-1) / (3Mv + 2c(S-1)) — unit terms,
    # F=B=W (docs/SCHEDULES.md; test_zero_bubble.py pins the derivation
    # and the zb1 <= interleaved <= flat ordering across the grid)
    ("zb1", 4, 8, 1, 2, 6 / 54),
    ("zb1", 8, 256, 1, 2, 14 / 1550),   # the 65B shape: 0.90% vs 1.35%
    ("zb1", 4, 8, 2, 2, 12 / 60),
    ("zb1", 4, 8, 1, 1, 6 / 30),        # flat zero-bubble form
    ("zb1", 2, 4, 2, 2, 4 / 28),        # m per flush == accum chunks
    ("zb1", 4, 2, 1, 1, 6 / 12),        # M < S
    # S=1: no pipeline, no bubble, any schedule/chunking/interleaving
    ("1f1b", 1, 8, 1, 1, 0.0),
    ("1f1b", 1, 8, 8, 1, 0.0),
    ("gpipe", 1, 8, 2, 1, 0.0),
    ("interleaved_1f1b", 1, 8, 1, 4, 0.0),
    ("zb1", 1, 8, 1, 4, 0.0),
])
def test_bubble_fraction_grid(schedule, s, m, c, v, expected):
    assert pl.bubble_fraction(_pcfg(schedule, s, m, c, v)) == pytest.approx(expected)


def test_bubble_fraction_interleaved_reduction():
    """At the same (S, m), interleaving with v chunks cuts the reported
    bubble by (Mv + S-1) / (M + S-1): just under v, ~v for m >> S (the
    shorter fill). The second factor of 2 this test once claimed was flat
    1f1b's own masked halves, which flat no longer runs (PR 38): at v=1
    the two schedules are one sequence and report one number."""
    for s, m in [(2, 4), (4, 8), (8, 256)]:
        flat = pl.bubble_fraction(_pcfg("1f1b", s, m))
        assert flat == pl.bubble_fraction(_pcfg("interleaved_1f1b", s, m))
        for v in (2, 4):
            if m % s:
                continue
            inter = pl.bubble_fraction(
                _pcfg("interleaved_1f1b", s, m, v=v))
            assert flat / v < inter < flat, (s, m, v, flat, inter)
            assert flat / inter == pytest.approx(
                (m * v + s - 1) / (m + s - 1)), (s, m, v, flat, inter)


def test_bubble_fraction_monotone_in_v():
    vals = [pl.bubble_fraction(_pcfg("interleaved_1f1b", 4, 8, v=v))
            for v in (1, 2, 4, 8)]
    assert vals == sorted(vals, reverse=True)
    assert all(0.0 < b < 1.0 for b in vals)


# ---------------------------------------------------------------------------
# Full-trainer plumbing (the CI schedule-parity gate's artifact producer)
# ---------------------------------------------------------------------------

@pytest.mark.slow  # since PR 11 the trainer runs every schedule through ONE
# unit interpreter, and tests/test_zero_bubble.py's trainer e2e exercises
# that trainer path (zb1-v2) every fast run — the interleaved parity reps
# above keep this schedule's fast coverage
def test_trainer_interleaved_end_to_end(tmp_path, devices):
    """run_training with schedule: interleaved_1f1b + virtual_stages: 2 —
    metrics carry the interleaved bubble_fraction, numerics.jsonl resolves
    activations per [S, v] chunk, and the final loss matches the flat
    schedule bit-for-bit.

    Both runs warm-start from ONE canonical-layout checkpoint (the PR-2
    format; written here with a flat manifest, restored into both layouts):
    fresh inits go through `init_params_sharded`, whose in-jit RNG draws are
    sharding-LAYOUT-dependent (a pre-existing quirk of partitioned threefry,
    not a schedule property), so identical weights — the real 65B warm-start
    situation — are the honest baseline for schedule equality."""
    from llama_pipeline_parallel_tpu.ckpt.checkpoint import CheckpointManager
    from llama_pipeline_parallel_tpu.train import run_training

    model_cfg = LlamaConfig.tiny(dtype=jnp.float32)
    man = StageManifest.for_config(model_cfg, 2)
    warm_dir = str(tmp_path / "warm")
    CheckpointManager(warm_dir).save(
        0, pl.stack_stages(llama.init_params(jax.random.PRNGKey(7), model_cfg),
                           man), man, model_cfg)

    def cfg_for(out, **kw):
        base = {
            "output_dir": str(tmp_path / out),
            "mesh": {"pp": 2, "dp": 2},
            "model": {"preset": "tiny", "dtype": "float32"},
            "model_name_or_path": warm_dir,
            "dataset": {"synthetic": True, "seq_length": 16,
                        "pseudo_dataset_len": 128},
            "seed": 7,
            "per_device_train_batch_size": 2,
            "gradient_accumulation_steps": 2,
            "max_steps": 3,
            "learning_rate": 1e-3,
            "warmup_steps": 1,
            "logging_steps": 1,
            "save_steps": 0,
            "save_final": False,
        }
        base.update(kw)
        return base

    flat = run_training(cfg_for("flat"))
    inter = run_training(cfg_for("inter", pipeline_schedule="interleaved_1f1b",
                                 virtual_stages=2))
    assert inter["final_loss"] == flat["final_loss"]

    lines = [json.loads(l) for l in
             open(os.path.join(str(tmp_path / "inter"), "metrics.jsonl"))]
    pcfg = pl.PipelineConfig(num_stages=2, num_microbatches=2,
                             schedule="interleaved_1f1b", virtual_stages=2)
    assert lines[0]["bubble_fraction"] == round(pl.bubble_fraction(pcfg), 4)
    flat_lines = [json.loads(l) for l in
                  open(os.path.join(str(tmp_path / "flat"), "metrics.jsonl"))]
    assert lines[0]["bubble_fraction"] < flat_lines[0]["bubble_fraction"]

    nrec = [json.loads(l) for l in
            open(os.path.join(str(tmp_path / "inter"), "numerics.jsonl"))]
    per_chunk = np.asarray(nrec[0]["act_rms_per_chunk"])
    assert per_chunk.shape == (2, 2) and np.all(per_chunk > 0)


@pytest.mark.slow
def test_trainer_interleaved_offload_zero2(tmp_path, devices):
    """The 65B run-of-record combination (conf/llama_65b_pp8_v2_tp2_dp2.yaml):
    interleaved 1F1B under the ZeRO-2 host-offloaded optimizer — the
    [S, v, k, ...] layout must stream through host masters/moments, the
    dp-sharded grad outputs, and the numerics stats dispatch. Slow-marked
    (PR 10 rebalance): the plain interleaved trainer e2e stays fast, and
    test_trainer/test_offload keep the zero2 machinery's own fast gates."""
    from llama_pipeline_parallel_tpu.train import run_training

    summary = run_training({
        "output_dir": str(tmp_path / "out"),
        "mesh": {"pp": 2, "dp": 2},
        "model": {"preset": "tiny", "dtype": "float32"},
        "dataset": {"synthetic": True, "seq_length": 16,
                    "pseudo_dataset_len": 128},
        "seed": 7,
        "per_device_train_batch_size": 2,
        "gradient_accumulation_steps": 2,
        "pipeline_schedule": "interleaved_1f1b",
        "virtual_stages": 2,
        "optimizer_offload": True,
        "optimizer_offload_zero2": True,
        "max_steps": 2,
        "learning_rate": 1e-3,
        "warmup_steps": 1,
        "logging_steps": 1,
        "save_steps": 0,
        "save_final": True,
    })
    assert summary["final_step"] == 2
    assert np.isfinite(summary["final_loss"])
    # the offload checkpoint wrote the canonical layout via the interleaved
    # manifest (save_offload -> unstack_stages)
    meta = json.load(open(os.path.join(str(tmp_path / "out"),
                                       "checkpoint-2", "meta.json")))
    assert meta["manifest"]["virtual_stages"] == 2
    assert meta["opt_layout"] == "offload_parts"
