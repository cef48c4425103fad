"""Checkpoint round-trips, topology-change restore, resume detection, converter."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llama_pipeline_parallel_tpu.ckpt import checkpoint
from llama_pipeline_parallel_tpu.ckpt.checkpoint import CheckpointManager, find_resume_checkpoint
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.models.llama.manifest import StageManifest
from llama_pipeline_parallel_tpu.optim import OptimizerConfig, make_optimizer
from llama_pipeline_parallel_tpu.parallel import pipeline as pl
from llama_pipeline_parallel_tpu.parallel import train_step as ts
from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig, make_mesh


def tree_equal(a, b, atol=0.0):
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y), atol=atol), a, b)


@pytest.fixture()
def cfg():
    return LlamaConfig.tiny()


def _trained_state(cfg, pp, dp, steps=2):
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh(MeshConfig(pp=pp, dp=dp))
    manifest = StageManifest.for_config(cfg, pp)
    stacked = pl.stack_stages(params, manifest)
    pcfg = pl.PipelineConfig(num_stages=pp, num_microbatches=2)
    tx, sched = make_optimizer(OptimizerConfig(learning_rate=1e-3, total_steps=50,
                                               warmup_steps=5))
    state = ts.init_train_state(stacked, tx, mesh)
    step = ts.make_train_step(mesh, cfg, pcfg, tx, sched, stacked)
    rng = np.random.RandomState(0)
    B = dp * 2 * 2
    ids = rng.randint(3, cfg.vocab_size, size=(B, 16)).astype(np.int32)
    batch = {"input_ids": jnp.asarray(ids),
             "attention_mask": jnp.ones((B, 16), jnp.int32),
             "position_ids": jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (B, 16)),
             "labels": jnp.asarray(ids)}
    for _ in range(steps):
        state, _ = step(state, batch)
    return state, manifest, tx


@pytest.mark.slow
def test_full_roundtrip_same_topology(tmp_path, cfg, devices):
    state, manifest, tx = _trained_state(cfg, pp=2, dp=2)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(2, state.params, manifest, cfg, opt_state=state.opt_state)

    params2, opt2, step = mgr.load(2, state.params, state.opt_state, manifest)
    assert step == 2
    tree_equal(params2, state.params)
    tree_equal(opt2, state.opt_state)


def test_data_files_stay_under_the_size_cap(tmp_path, cfg, devices, monkeypatch):
    """No checkpoint file outgrows 2 x DATA_FILE_TARGET_BYTES (a host with a
    per-file size limit refused Orbax's default, up-to-2-GiB data files with
    EFBIG), and the chunked arrays restore bit-equal."""
    target = 16 << 10
    monkeypatch.setattr(checkpoint, "DATA_FILE_TARGET_BYTES", target)
    manifest = StageManifest.for_config(cfg, 1)
    params = pl.stack_stages(llama.init_params(jax.random.PRNGKey(0), cfg), manifest)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, params, manifest, cfg)

    sizes = [os.path.getsize(os.path.join(d, name))
             for d, _, names in os.walk(mgr.step_dir(1)) for name in names]
    assert sum(sizes) > 8 * target  # the cap had something to split
    assert max(sizes) < 2 * target
    tree_equal(mgr.load_params(1, params, manifest), params)


@pytest.mark.slow
def test_async_save_finalize_and_roundtrip(tmp_path, cfg, devices):
    """blocking=False: commit (meta/tag/on_complete) lands after finalize();
    back-to-back async saves serialize; the result round-trips bit-exactly."""
    state, manifest, tx = _trained_state(cfg, pp=2, dp=2)
    mgr = CheckpointManager(str(tmp_path))
    seen = []
    mgr.save(2, state.params, manifest, cfg, opt_state=state.opt_state,
             blocking=False, on_complete=seen.append)
    mgr.finalize()
    assert seen == [mgr.step_dir(2)]
    assert mgr.is_complete(2) and mgr.latest_step() == 2
    params2, opt2, step = mgr.load(2, state.params, state.opt_state, manifest)
    assert step == 2
    tree_equal(params2, state.params)
    tree_equal(opt2, state.opt_state)

    mgr.save(3, state.params, manifest, cfg, blocking=False)
    mgr.save(4, state.params, manifest, cfg, blocking=False)  # joins save(3)
    mgr.finalize()
    assert mgr.is_complete(3) and mgr.is_complete(4)
    assert mgr.latest_step() == 4


def test_prune_keeps_newest_and_ignores_incomplete(tmp_path, cfg, devices):
    """save_total_limit semantics: oldest COMPLETE checkpoints beyond the
    limit are deleted (incl. async path via keep_last=); incomplete dirs and
    the latest tag are untouched."""
    manifest = StageManifest.for_config(cfg, 1)
    stacked = pl.stack_stages(llama.init_params(jax.random.PRNGKey(0), cfg),
                              manifest)
    mgr = CheckpointManager(str(tmp_path))
    for s in (2, 3):
        mgr.save(s, stacked, manifest, cfg)
    os.makedirs(mgr.step_dir(0))  # incomplete (no meta.json): never pruned
    # complete checkpoint under a NON-canonical dirname (hand-copied style):
    # pruning must delete the actual directory, not a step_dir() respelling
    odd = str(tmp_path / "checkpoint-001")
    os.makedirs(odd)
    open(os.path.join(odd, "meta.json"), "w").write("{}")
    mgr.save(4, stacked, manifest, cfg, blocking=False, keep_last=2)
    mgr.finalize()
    assert mgr.list_steps(complete_only=True) == [3, 4]
    assert not os.path.isdir(odd)
    assert os.path.isdir(mgr.step_dir(0))
    assert mgr.latest_step() == 4


def test_async_save_surfaces_commit_failure(tmp_path, cfg, devices):
    """A background-commit failure must fail the run at finalize(), exactly
    as a blocking save would — not vanish into a daemon-thread traceback."""
    manifest = StageManifest.for_config(cfg, 1)
    stacked = pl.stack_stages(llama.init_params(jax.random.PRNGKey(0), cfg),
                              manifest)
    mgr = CheckpointManager(str(tmp_path))

    def boom(*a, **k):
        raise OSError("disk full")

    mgr._commit = boom
    mgr.save(2, stacked, manifest, cfg, blocking=False)
    with pytest.raises(RuntimeError, match="async checkpoint commit failed"):
        mgr.finalize()
    mgr.finalize()  # error is consumed; manager stays usable


@pytest.mark.slow
def test_topology_change_restore(tmp_path, cfg, devices):
    """Save at PP=2, restore at PP=4 — forbidden by the reference's filename
    arithmetic, enabled by the canonical layout + manifest design."""
    state, manifest2, tx = _trained_state(cfg, pp=2, dp=2)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(2, state.params, manifest2, cfg, opt_state=state.opt_state)

    manifest4 = StageManifest.for_config(cfg, 4)
    params4_tmpl = pl.stack_stages(pl.unstack_stages(state.params, manifest2), manifest4)
    mesh4 = make_mesh(MeshConfig(pp=4, dp=1))
    state4 = ts.init_train_state(params4_tmpl, tx, mesh4)
    params4, opt4, step = mgr.load(2, state4.params, state4.opt_state, manifest4)

    # canonical views must agree exactly
    tree_equal(pl.unstack_stages(params4, manifest4),
               pl.unstack_stages(state.params, manifest2))
    assert np.asarray(params4["layers"]["attn"]["wq"]).shape[:2] == (4, 1)


@pytest.mark.slow
def test_module_only_warm_start_from_full_ckpt(tmp_path, cfg, devices):
    state, manifest, tx = _trained_state(cfg, pp=2, dp=2)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(2, state.params, manifest, cfg, opt_state=state.opt_state)
    params = mgr.load_params(2, state.params, manifest)
    tree_equal(params, state.params)


@pytest.mark.slow
def test_params_only_ckpt_refuses_full_resume(tmp_path, cfg, devices):
    state, manifest, tx = _trained_state(cfg, pp=2, dp=1, steps=1)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(0, state.params, manifest, cfg, opt_state=None)
    with pytest.raises(ValueError, match="no optimizer state"):
        mgr.load(0, state.params, state.opt_state, manifest)
    # but warm start works
    params = mgr.load_params(0, state.params, manifest)
    tree_equal(params, state.params)


@pytest.mark.slow
def test_latest_tag_and_resume_detection(tmp_path, cfg, devices):
    assert find_resume_checkpoint(str(tmp_path / "nope")) is None
    state, manifest, tx = _trained_state(cfg, pp=2, dp=1, steps=1)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, state.params, manifest, cfg)
    mgr.save(5, state.params, manifest, cfg)
    step, path = find_resume_checkpoint(str(tmp_path))
    assert step == 5 and path.endswith("checkpoint-5")
    # corrupt the tag -> directory-scan fallback
    with open(tmp_path / "latest", "w") as f:
        f.write("checkpoint-999")
    assert find_resume_checkpoint(str(tmp_path))[0] == 5


def test_resume_edge_cases_tag_meta_and_quarantine(tmp_path, cfg, devices):
    """Resume-path edge cases (docs/RESILIENCE.md): a corrupt/stale `latest`
    tag falls back to the directory scan; a checkpoint-N dir with no
    meta.json is invisible to every reader; find_resume_checkpoint skips a
    quarantined checkpoint."""
    manifest = StageManifest.for_config(cfg, 1)
    stacked = pl.stack_stages(llama.init_params(jax.random.PRNGKey(0), cfg),
                              manifest)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(2, stacked, manifest, cfg)
    mgr.save(5, stacked, manifest, cfg)

    # tag holding garbage (not even a checkpoint-N name)
    with open(tmp_path / "latest", "w") as f:
        f.write("!!torn write garbage")
    assert mgr.latest_step() == 5

    # tag pointing at a checkpoint that never completed (dir, no meta.json)
    os.makedirs(mgr.step_dir(9))
    with open(tmp_path / "latest", "w") as f:
        f.write("checkpoint-9")
    assert mgr.latest_step() == 5
    assert mgr.list_steps(complete_only=True) == [2, 5]
    assert not mgr.is_complete(9)

    # quarantined newest checkpoint: resume detection falls back past it
    os.rename(mgr.step_dir(5), mgr.step_dir(5) + ".corrupt")
    with open(tmp_path / "latest", "w") as f:
        f.write("checkpoint-5")
    step, path = find_resume_checkpoint(str(tmp_path))
    assert step == 2 and path.endswith("checkpoint-2")


@pytest.mark.slow
def test_hf_export_round_trip(tmp_path, cfg, devices):
    """native ckpt -> HF (tools/export_hf) -> logits parity with our forward."""
    torch = pytest.importorskip("torch")
    state, manifest, tx = _trained_state(cfg, pp=2, dp=1, steps=1)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, state.params, manifest, cfg)

    from tools.export_hf import export
    out = str(tmp_path / "hf")
    export(str(tmp_path), out)

    from transformers import LlamaForCausalLM
    hf_model = LlamaForCausalLM.from_pretrained(out).eval()
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, size=(1, 12))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = np.asarray(llama.forward(
        pl.unstack_stages(jax.device_get(state.params), manifest),
        jnp.asarray(ids), cfg=cfg))
    np.testing.assert_allclose(ours, hf_logits, rtol=2e-4, atol=2e-4)


def test_hf_converter_end_to_end(tmp_path, devices):
    """convert2ckpt.py equivalent: HF model -> native ckpt -> logits parity."""
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig as HFLlamaConfig
    from transformers import LlamaForCausalLM

    hf_dir = str(tmp_path / "hf")
    hf_cfg = HFLlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=2,
        max_position_embeddings=64, attn_implementation="eager",
        tie_word_embeddings=False)
    torch.manual_seed(0)
    hf_model = LlamaForCausalLM(hf_cfg).eval()
    hf_model.save_pretrained(hf_dir)

    from tools.convert_hf import convert
    out_dir = str(tmp_path / "native")
    convert(hf_dir, out_dir, expand_vocab=False)

    # load it back through the normal warm-start path, at PP=2
    cfg = LlamaConfig.from_hf_config(hf_cfg, dtype=jnp.float32)
    manifest = StageManifest.for_config(cfg, 2)
    template = pl.stack_stages(llama.init_params(jax.random.PRNGKey(1), cfg), manifest)
    mgr = CheckpointManager(out_dir)
    assert mgr.latest_step() == 0
    params = pl.unstack_stages(mgr.load_params(0, template, manifest), manifest)

    ids = np.random.RandomState(0).randint(0, 128, size=(1, 10))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = np.asarray(llama.forward(params, jnp.asarray(ids), cfg=cfg))
    np.testing.assert_allclose(ours, hf_logits, rtol=2e-4, atol=2e-4)
