"""Zero-bubble (zb1) schedule correctness: the split B/W backward.

The CI `schedule-parity` gate's zb1 lane: the decomposed backward — B
(input-grad only) units on the critical-path tick clock, W (weight-grad
only) units replayed from stashed residuals in the collective-free fourth
phase — must match the flat 1f1b schedule BIT-exactly on the parity grid
(the decomposition changes when weight grads materialize, never what is
summed; docs/SCHEDULES.md "Zero-bubble 1F1B"). Plus: the analytic
`bubble_fraction` derivation at the 65B shape and the
zb1 <= interleaved <= flat ordering across the degenerate grid, the
W-queue/stash accounting preflight consumes, checkpoint restores across
schedules in both directions, [S, v] activation stats, the eval path, the
trainer/offload plumbing with the new metrics/health keys, and every new
validation error."""

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
# Schedule parity: zb1 == flat == interleaved, bit for bit
# ---------------------------------------------------------------------------

# The fast lane keeps one case per structural regime (flat form, chunked
# form, M < S masking) to fit the tier-1 time budget; the rest of the grid
# is slow-marked and runs in the round gate.
@pytest.mark.parametrize("pp,v,microbatches", [
    # flat zero-bubble (v=1): slow since PR 11 — the v1 split form shares
    # the interpreter's segment machinery with the fast (4,1,2) M<S row,
    # and the solver lane (test_unit_schedule.py) took its fast-lane slot
    pytest.param(2, 1, 4, marks=pytest.mark.slow),
    (2, 2, 4),                  # the dryrun_multichip acceptance grid
    pytest.param(4, 2, 4, marks=pytest.mark.slow),
    pytest.param(2, 4, 4, marks=pytest.mark.slow),   # deeper interleaving
    # M < S masking: slow since PR 17 (actuation rebalance) — the regime
    # keeps a fast rep in test_interleaved_v1_degenerates_to_flat[4-2]
    # through the same unit interpreter; the zb1-specific B/W split stays
    # gated fast by the (2, 2, 4) row above
    pytest.param(4, 1, 2, marks=pytest.mark.slow),
    pytest.param(4, 1, 1, marks=pytest.mark.slow),   # M == 1
    pytest.param(4, 2, 8, marks=pytest.mark.slow),
])
def test_zb1_matches_flat_bitexact(cfg, params, devices, pp, v, microbatches):
    """Loss AND unstacked gradients identical to the flat fused-backward
    schedule: every B unit's dx and every W unit's dparams replay the same
    chunk recompute + cotangent chain the fused vjp ran, and the W drain
    folds in the fused backward's unit order — the only difference is WHEN
    dparams materialize."""
    batch = make_batch(cfg, batch_size=max(microbatches * 2, 2))
    l_flat, g_flat, _ = run_schedule(params, batch, cfg, pp, "1f1b",
                                     microbatches=microbatches)
    l_zb, g_zb, _ = run_schedule(params, batch, cfg, pp, "zb1", v=v,
                                 microbatches=microbatches)
    assert float(l_zb) == float(l_flat)
    assert_tree_bitexact(g_zb, g_flat)


@pytest.mark.slow
def test_zb1_matches_interleaved_bitexact(cfg, params, devices):
    """zb1 is the interleaved tick clock with the backward split — at the
    same (pp, v, m) the two must agree bit-for-bit, not just via flat."""
    batch = make_batch(cfg)
    l_int, g_int, _ = run_schedule(params, batch, cfg, 2, "interleaved_1f1b",
                                   v=2)
    l_zb, g_zb, _ = run_schedule(params, batch, cfg, 2, "zb1", v=2)
    assert float(l_zb) == float(l_int)
    assert_tree_bitexact(g_zb, g_int)


@pytest.mark.parametrize("dp,tp,sp,chunks", [
    pytest.param(2, 1, 1, 1, marks=pytest.mark.slow),
    (1, 2, 1, 1),   # tp fast: the split head's vocab-parallel grads are
                    # the hybrid most likely to break independently
    pytest.param(1, 1, 2, 1, marks=pytest.mark.slow),
    pytest.param(1, 1, 1, 2, marks=pytest.mark.slow),
])
def test_zb1_hybrid_grids_bitexact(cfg, params, devices, dp, tp, sp, chunks):
    """The split backward composes with dp/tp/sp sharding and chunked
    accumulation without losing the bit-exact flat equivalence — the W
    replay re-runs the SAME stage-uniform tp/sp collectives the fused
    backward ran (they sit inside chunk_fwd, shared by both paths)."""
    m = 4
    batch = make_batch(cfg, batch_size=dp * m * 2)
    l_flat, g_flat, _ = run_schedule(params, batch, cfg, 2, "1f1b", dp=dp,
                                     tp=tp, sp=sp, microbatches=m, chunks=chunks)
    l_zb, g_zb, _ = run_schedule(params, batch, cfg, 2, "zb1", v=2, dp=dp,
                                 tp=tp, sp=sp, microbatches=m, chunks=chunks)
    assert float(l_zb) == float(l_flat)
    assert_tree_bitexact(g_zb, g_flat)


@pytest.mark.slow
def test_zb1_matches_single_device_reference(cfg, params, devices):
    """And pinned to the plain unpipelined forward, so the zb1 grads are
    the true ones, not merely self-consistent."""
    batch = make_batch(cfg)

    def loss(p):
        logits = llama.forward(p, batch["input_ids"], batch["attention_mask"],
                               batch["position_ids"], cfg=cfg)
        return llama.loss_fn(logits, batch["labels"])

    ref_loss, ref_grads = jax.value_and_grad(loss)(params)
    l_zb, g_zb, _ = run_schedule(params, batch, cfg, 4, "zb1", v=2,
                                 microbatches=4)
    np.testing.assert_allclose(float(l_zb), float(ref_loss), rtol=1e-5)
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y), rtol=2e-5, atol=1e-6), g_zb, ref_grads)


@pytest.mark.slow  # PR 11: eval is the untouched forward-only loop (not
# the unit interpreter); the interleaved eval rep stays fast
def test_zb1_eval_matches(cfg, params, devices):
    """make_pipeline_eval_fn under a zb1 pcfg (the forward-only loop walks
    the same v*S virtual ring; B/W only exist in training)."""
    batch = make_batch(cfg)
    mesh = make_mesh(MeshConfig(pp=2))
    manifest = StageManifest.for_config(cfg, 2, virtual_stages=2)
    stacked = pl.stack_stages(params, manifest)
    pcfg = pl.PipelineConfig(num_stages=2, num_microbatches=4,
                             schedule="zb1", virtual_stages=2)
    loss_sum, count = jax.jit(pl.make_pipeline_eval_fn(
        mesh, cfg, pcfg, stacked))(stacked, batch)
    l_flat, _, _ = run_schedule(params, batch, cfg, 2, "1f1b")
    np.testing.assert_allclose(float(loss_sum) / float(count), float(l_flat),
                               rtol=1e-6)


@pytest.mark.slow  # round gate; the trainer e2e below keeps the flat->zb1
# restore direction in the fast lane, and test_interleaved.py keeps the
# manager-level v2<->flat mechanics there too
def test_zb1_checkpoint_roundtrips_across_schedules(cfg, params, tmp_path,
                                                    devices):
    """A checkpoint written under the zb1 (chunked) layout restores into the
    flat layout and vice versa, unchanged: the canonical [num_layers, ...]
    on-disk layout is the interchange — PR-2/PR-5 checkpoints restore into
    the new schedule with no migration, in both directions."""
    from llama_pipeline_parallel_tpu.ckpt.checkpoint import CheckpointManager

    man_zb = StageManifest.for_config(cfg, 2, virtual_stages=2)  # zb1 v=2
    man_f = StageManifest.for_config(cfg, 4)                     # flat pp=4
    stacked_zb = pl.stack_stages(params, man_zb)
    stacked_f = pl.stack_stages(params, man_f)

    # zb1 -> flat
    mgr = CheckpointManager(str(tmp_path / "from_zb1"))
    mgr.save(3, stacked_zb, man_zb, cfg)
    restored_f = mgr.load_params(3, stacked_f, man_f)
    assert_tree_bitexact(pl.unstack_stages(restored_f, man_f), params)
    # flat -> zb1
    mgr2 = CheckpointManager(str(tmp_path / "from_flat"))
    mgr2.save(5, stacked_f, man_f, cfg)
    restored_zb = mgr2.load_params(5, stacked_zb, man_zb)
    assert_tree_bitexact(restored_zb, stacked_zb)


# ---------------------------------------------------------------------------
# Stats: [S, v] activation reductions under the split backward
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_zb1_collect_stats_shapes(cfg, params, devices):
    """Per-stage numerics telemetry resolves under zb1: the B ticks fold
    the same chunk-boundary activation stats the fused backward folded, so
    [S, v] and [S] keys exist, finite and positive — and match the
    interleaved schedule's EXACTLY (same primals, same fold order).
    Slow-marked (PR 10 rebalance): the interleaved stats test is the fast
    [S, v]-stats gate, and zb1 rides the identical fold path it pins."""
    batch = make_batch(cfg)
    _, _, stats = run_schedule(params, batch, cfg, 2, "zb1", v=2,
                               collect_stats=True)
    assert np.asarray(stats["act_absmax_per_chunk"]).shape == (2, 2)
    assert np.asarray(stats["act_rms_per_chunk"]).shape == (2, 2)
    assert np.asarray(stats["act_absmax_per_stage"]).shape == (2,)
    assert np.asarray(stats["act_rms_per_stage"]).shape == (2,)
    for val in stats.values():
        assert np.all(np.isfinite(np.asarray(val)))
        assert np.all(np.asarray(val) > 0)
    _, _, stats_int = run_schedule(params, batch, cfg, 2, "interleaved_1f1b",
                                   v=2, collect_stats=True)
    assert_tree_bitexact(stats, stats_int)


@pytest.mark.slow
def test_zb1_collect_stats_v1(cfg, params, devices):
    """The v=1 (flat zero-bubble) degenerate still emits the chunked stat
    keys, with the chunk axis of size 1 agreeing with the per-stage view."""
    _, _, stats = run_schedule(params, make_batch(cfg), cfg, 2, "zb1", v=1,
                               collect_stats=True)
    assert np.asarray(stats["act_absmax_per_chunk"]).shape == (2, 1)
    np.testing.assert_allclose(
        np.asarray(stats["act_absmax_per_stage"]),
        np.asarray(stats["act_absmax_per_chunk"])[:, 0], rtol=1e-6)


# ---------------------------------------------------------------------------
# bubble_fraction: the zb1 derivation + the full schedule ordering
# ---------------------------------------------------------------------------

def _pcfg(schedule, s, m, c=1, v=1):
    return pl.PipelineConfig(num_stages=s, num_microbatches=m, accum_chunks=c,
                             schedule=schedule, virtual_stages=v)


def test_bubble_fraction_zb1_derivation_at_65b_shape():
    """Pin the derivation at the config-of-record shape (S=8, M=256, v=2,
    c=1), in unit terms with F = B = W = 1 (docs/SCHEDULES.md):

        warmup   vS-1 = 15 ticks x {F}      =   15 units
        steady   Mv+S-vS = 504 ticks x {F,B} = 1008 units
        drain    vS-1 = 15 ticks x {B}      =   15 units
        w-drain  Mv = 512 ticks x {W}       =  512 units
        total 1550 units, useful 3*Mv = 1536
        -> bubble = 2(S-1) / (3Mv + 2(S-1)) = 14/1550

    strictly below interleaved's 7/519 (~1.35%) and flat's 7/263 (2.66%;
    14/270 until PR 38 took the all-masked halves out of flat's flush)
    — the acceptance number of this PR."""
    zb = pl.bubble_fraction(_pcfg("zb1", 8, 256, v=2))
    inter = pl.bubble_fraction(_pcfg("interleaved_1f1b", 8, 256, v=2))
    flat = pl.bubble_fraction(_pcfg("1f1b", 8, 256))
    assert zb == pytest.approx(14 / 1550)
    assert inter == pytest.approx(7 / 519)
    assert flat == pytest.approx(7 / 263)
    assert zb < inter < flat


@pytest.mark.parametrize("s,m,c,v,expected", [
    # zb1: 2c(S-1) / (3Mv + 2c(S-1))
    (4, 8, 1, 2, 6 / 54),
    (8, 256, 1, 2, 14 / 1550),
    (4, 8, 2, 2, 12 / 60),
    (4, 8, 1, 1, 6 / 30),          # flat zero-bubble form
    (2, 4, 2, 2, 4 / 28),          # m per flush == accum chunks degenerate
    (4, 2, 1, 1, 6 / 12),          # M < S: fill dominates
    (1, 8, 1, 4, 0.0),             # S=1: no pipeline, no bubble
    (1, 8, 8, 1, 0.0),
])
def test_bubble_fraction_zb1_grid(s, m, c, v, expected):
    assert pl.bubble_fraction(_pcfg("zb1", s, m, c, v)) == pytest.approx(expected)


def test_bubble_fraction_ordering_zb1_interleaved_flat():
    """zb1 <= interleaved <= flat at EVERY grid point — including S=1,
    M < S, and m == accum_chunks degenerates (strict once S > 1)."""
    grid = [(s, m, c, v)
            for s in (1, 2, 4, 8)
            for m in (1, 2, 4, 8, 256)
            for c in (1, 2, m)
            for v in (1, 2, 4)
            # valid PipelineConfigs only: c | m, and v > 1 needs the
            # round-robin constraint (m per flush divisible by S)
            if m % c == 0 and (v == 1 or (m // c) % s == 0)]
    assert len(grid) > 60        # S=1, M<S, m==c degenerates all present
    assert any(m < s for s, m, c, v in grid)
    assert any(m == c and m > 1 for s, m, c, v in grid)
    for s, m, c, v in grid:
        zb = pl.bubble_fraction(_pcfg("zb1", s, m, c, v))
        inter = pl.bubble_fraction(_pcfg("interleaved_1f1b", s, m, c, v))
        flat = pl.bubble_fraction(_pcfg("1f1b", s, m, c))
        if s == 1:
            assert zb == inter == flat == 0.0
        else:
            assert zb < inter, (s, m, c, v, zb, inter)
            # an EQUALITY at v = 1, where flat and interleaved are one
            # sequence under two names (PR 38); strict for v > 1
            if v == 1:
                assert inter == flat, (s, m, c, v, inter, flat)
            else:
                assert inter < flat, (s, m, c, v, inter, flat)
            assert 0.0 < zb < 1.0


# ---------------------------------------------------------------------------
# W-queue / stash accounting (the preflight memory-model term)
# ---------------------------------------------------------------------------

def test_wgrad_queue_peak_and_stash_bytes():
    # fused-backward schedules queue nothing
    assert pl.wgrad_queue_peak(_pcfg("1f1b", 8, 256)) == 0
    assert pl.wgrad_queue_peak(_pcfg("interleaved_1f1b", 8, 256, v=2)) == 0
    # zb1: Mv / accum_chunks per-flush units
    assert pl.wgrad_queue_peak(_pcfg("zb1", 8, 256, v=2)) == 512
    assert pl.wgrad_queue_peak(_pcfg("zb1", 8, 256, c=4, v=2)) == 128
    assert pl.wgrad_queue_peak(_pcfg("zb1", 2, 4, v=1)) == 4
    # stash = 2 residuals x queue x [mb, L, d] x dtype: the 65B shape's
    # 64 GiB (mb=8, seq 512, d 8192, bf16) — the number the config's
    # header and docs/SCHEDULES.md quote
    stash = pl.wgrad_stash_bytes(_pcfg("zb1", 8, 256, v=2), mb_rows=8,
                                 local_seqlen=512, hidden_size=8192,
                                 dtype_bytes=2)
    assert stash == 2 * 512 * 8 * 512 * 8192 * 2
    assert round(stash / (1 << 30)) == 64
    assert pl.wgrad_stash_bytes(_pcfg("1f1b", 8, 256), 8, 512, 8192) == 0


def test_preflight_resume_block_names_schedule_change(tmp_path):
    """The elastic-resume preflight names a schedule change like it names
    topology changes: restoring a flat-schedule checkpoint into a zb1
    config reports `schedule_changed` with both names."""
    import preflight  # tools/ on sys.path via conftest

    ckpt = tmp_path / "out" / "checkpoint-7"
    ckpt.mkdir(parents=True)
    (ckpt / "meta.json").write_text(json.dumps({
        "topology": {"pp": 2, "dp": 2, "tp": 1, "sp": 1, "layout": "pp2xdp2",
                     "schedule": "1f1b", "virtual_stages": 1,
                     "process_count": 1}}))
    report = preflight.resume_compat({
        "output_dir": str(tmp_path / "out"),
        "mesh": {"pp": 2, "dp": 2},
        "pipeline_schedule": "zb1", "virtual_stages": 2})
    assert report["resume_step"] == 7
    assert "schedule" in report["topology_changed"]
    assert "1f1b -> zb1" in report["schedule_changed"]


@pytest.mark.slow
def test_preflight_reports_wgrad_stash_for_zb1():
    """tools/preflight.py compiles a zb1 config (the conf-sweep contract for
    conf/llama_65b_pp8_zb1_tp2_dp2.yaml at tiny scale) and reports the
    W-stash term; on a blown budget the FAIL message names the
    accum_chunks dial — the actionable rejection the acceptance requires."""
    import subprocess
    import sys as _sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def run(*args):
        return subprocess.run(
            [_sys.executable, os.path.join(repo, "tools", "preflight.py"),
             *args], capture_output=True, text=True, cwd=repo, timeout=600,
            env={**os.environ, "PYTHONPATH": repo})

    ok = run("--config", "conf/tiny_smoke.yaml", "pipeline_schedule=zb1",
             "virtual_stages=2")
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "schedule: zb1" in ok.stdout
    assert "wgrad_queue_depth: 4" in ok.stdout  # M=2 microbatches x v=2
    assert "wgrad_stash_gib" in ok.stdout

    fail = run("--config", "conf/tiny_smoke.yaml", "pipeline_schedule=zb1",
               "virtual_stages=2", "--hbm-gb", "0.000001")
    assert fail.returncode == 1
    assert "preflight FAIL" in fail.stdout
    assert "gradient_accumulation_chunks" in fail.stdout
    assert "interleaved_1f1b" in fail.stdout


# ---------------------------------------------------------------------------
# Validation
# ---------------------------------------------------------------------------

def test_zb1_accepts_virtual_stages():
    pcfg = pl.PipelineConfig(num_stages=2, num_microbatches=4,
                             schedule="zb1", virtual_stages=2)
    assert pcfg.virtual_stages == 2


def test_zb1_requires_divisible_microbatches():
    with pytest.raises(ValueError, match="divisible by num_stages"):
        pl.PipelineConfig(num_stages=4, num_microbatches=6,
                          schedule="zb1", virtual_stages=2)
    with pytest.raises(ValueError, match="divisible by num_stages"):
        pl.PipelineConfig(num_stages=4, num_microbatches=8, accum_chunks=4,
                          schedule="zb1", virtual_stages=2)


def test_zb1_uneven_partition_needs_v1():
    """Since the auto-layout PR, zb1 at v=1 RUNS unequal partitions
    through the unit interpreter (tests/test_uneven_stages.py has the
    parity grid); the round-robin chunk layout (any v>1) still has no
    uneven form and keeps the rejection."""
    pcfg = pl.PipelineConfig(num_stages=2, num_microbatches=4,
                             schedule="zb1", layer_counts=(5, 3))
    assert pcfg.layer_counts == (5, 3)
    with pytest.raises(ValueError, match="no uneven form"):
        pl.PipelineConfig(num_stages=2, num_microbatches=4,
                          schedule="zb1", virtual_stages=2,
                          layer_counts=(5, 3))


def test_zb1_layout_schedule_mismatch_fails_at_build(cfg, params, devices):
    mesh = make_mesh(MeshConfig(pp=2))
    flat = pl.stack_stages(params, StageManifest.for_config(cfg, 2))
    pcfg_zb = pl.PipelineConfig(num_stages=2, num_microbatches=4,
                                schedule="zb1", virtual_stages=2)
    with pytest.raises(ValueError, match="stack_stages"):
        pl.make_pipeline_loss_and_grad(mesh, cfg, pcfg_zb, flat)


def test_trainer_accepts_zb1_virtual_stages(cfg):
    from llama_pipeline_parallel_tpu.train import build_manifest

    man = build_manifest({"virtual_stages": 2, "pipeline_schedule": "zb1"},
                         cfg, 2)
    assert man.virtual_stages == 2
    with pytest.raises(ValueError, match="interleaved_1f1b, zb1, or solver"):
        build_manifest({"virtual_stages": 2, "pipeline_schedule": "1f1b"},
                       cfg, 2)


# ---------------------------------------------------------------------------
# Full-trainer plumbing (the CI schedule-parity gate's artifact producer)
# ---------------------------------------------------------------------------

# fast: the one run_training over the zb1-v2 interpreter path in every fast
# run (tests/test_interleaved.py's trainer e2e is slow-marked against it)
def test_trainer_zb1_end_to_end(tmp_path, devices):
    """run_training with schedule: zb1 + virtual_stages: 2 — the metrics
    line carries schedule/bubble_fraction/wgrad_queue_depth, health.json
    carries the queue depth + the zb1 topology, numerics.jsonl resolves
    activations per [S, v] chunk, and the final loss matches the flat
    schedule bit-for-bit.

    Both runs warm-start from ONE canonical-layout checkpoint (the PR-2
    format, written with a flat manifest and restored into both layouts —
    the flat->zb1 restore direction through the trainer), because fresh
    `init_params_sharded` RNG draws are sharding-layout-dependent (the
    pre-existing partitioned-threefry quirk, see test_interleaved.py)."""
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
    zb = run_training(cfg_for("zb", pipeline_schedule="zb1",
                              virtual_stages=2))
    assert zb["final_loss"] == flat["final_loss"]

    lines = [json.loads(l) for l in
             open(os.path.join(str(tmp_path / "zb"), "metrics.jsonl"))]
    pcfg = pl.PipelineConfig(num_stages=2, num_microbatches=2,
                             schedule="zb1", virtual_stages=2)
    assert lines[0]["schedule"] == "zb1"
    assert lines[0]["wgrad_queue_depth"] == pl.wgrad_queue_peak(pcfg) == 4
    assert lines[0]["bubble_fraction"] == round(pl.bubble_fraction(pcfg), 4)
    flat_lines = [json.loads(l) for l in
                  open(os.path.join(str(tmp_path / "flat"), "metrics.jsonl"))]
    assert flat_lines[0]["schedule"] == "1f1b"
    assert "wgrad_queue_depth" not in flat_lines[0]  # no always-zero column
    assert lines[0]["bubble_fraction"] < flat_lines[0]["bubble_fraction"]

    health = json.load(open(os.path.join(str(tmp_path / "zb"), "health.json")))
    assert health["topology"]["schedule"] == "zb1"
    assert health["wgrad_queue_depth"] == 4

    nrec = [json.loads(l) for l in
            open(os.path.join(str(tmp_path / "zb"), "numerics.jsonl"))]
    per_chunk = np.asarray(nrec[0]["act_rms_per_chunk"])
    assert per_chunk.shape == (2, 2) and np.all(per_chunk > 0)


@pytest.mark.slow
def test_trainer_zb1_offload_zero2(tmp_path, devices):
    """The zb1 run-of-record combination (conf/llama_65b_pp8_zb1_tp2_dp2
    .yaml at tiny scale): the split backward under the ZeRO-2
    host-offloaded optimizer — the W-drain's incremental grad folds must
    stream through dp-sharded grad outputs and host masters unchanged."""
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
        "pipeline_schedule": "zb1",
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
    meta = json.load(open(os.path.join(str(tmp_path / "out"),
                                       "checkpoint-2", "meta.json")))
    assert meta["manifest"]["virtual_stages"] == 2
    assert meta["topology"]["schedule"] == "zb1"
