"""Trainer e2e: smoke run, checkpoint/resume continuity, warm start."""

import json
import os

import jax

import numpy as np
import pytest

from llama_pipeline_parallel_tpu.train import run_training
from llama_pipeline_parallel_tpu.utils.config import load_config


def base_cfg(tmp_path, **kw):
    cfg = {
        "output_dir": str(tmp_path / "out"),
        "mesh": {"pp": 2, "dp": 2},
        "model": {"preset": "tiny", "dtype": "float32"},
        "dataset": {"synthetic": True, "seq_length": 16, "pseudo_dataset_len": 128},
        "seed": 7,
        "per_device_train_batch_size": 2,
        "gradient_accumulation_steps": 2,
        "max_steps": 4,
        "learning_rate": 1e-3,
        "warmup_steps": 1,
        "logging_steps": 2,
        "save_steps": 0,
        "save_final": True,
    }
    cfg.update(kw)
    return cfg


def test_smoke_run_writes_metrics_and_ckpt(tmp_path, devices):
    summary = run_training(base_cfg(tmp_path))
    assert summary["final_step"] == 4
    out = summary["output_dir"]
    lines = [json.loads(l) for l in open(os.path.join(out, "metrics.jsonl"))]
    assert lines and {"loss", "lr", "tokens_per_sec"} <= set(lines[0])
    assert os.path.isdir(os.path.join(out, "checkpoint-4"))
    assert os.path.exists(os.path.join(out, "training_config.json"))


def test_compilation_cache_dir_key_is_rejected(tmp_path, devices):
    """The old config key must not be silently ignored: the cache is placed
    by JAX_COMPILATION_CACHE_DIR or the fixed in-checkout default
    (utils/compile_cache.py; tests/test_bring_up.py)."""
    with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
        run_training(base_cfg(tmp_path, compilation_cache_dir=str(tmp_path)))


@pytest.mark.slow
def test_schedule_knob_equivalence(tmp_path, devices):
    """pipeline_schedule: gpipe (+ chunks) through the FULL trainer produces
    the same losses as the default 1f1b — the knob is plumbed end to end and
    the schedules are numerically interchangeable."""
    ref = run_training(base_cfg(tmp_path, output_dir=str(tmp_path / "s1")))
    gp = run_training(base_cfg(tmp_path, output_dir=str(tmp_path / "s2"),
                               pipeline_schedule="gpipe",
                               gradient_accumulation_chunks=2))
    np.testing.assert_allclose(gp["final_loss"], ref["final_loss"], rtol=1e-5)


@pytest.mark.slow
def test_resume_continues_identically(tmp_path, devices):
    """Interrupted-at-4 + resume-to-8 must equal straight-through-to-8
    (the reference's resume fast-forward contract, trainer_base_ds_mp:345-351)."""
    cfg_a = base_cfg(tmp_path, output_dir=str(tmp_path / "a"), max_steps=8)
    straight = run_training(cfg_a)

    cfg_b = base_cfg(tmp_path, output_dir=str(tmp_path / "b"), max_steps=4,
                     total_steps=8)  # schedule horizon stays 8 across the interruption
    run_training(cfg_b)
    cfg_b2 = base_cfg(tmp_path, output_dir=str(tmp_path / "b"), max_steps=8)
    resumed = run_training(cfg_b2)

    np.testing.assert_allclose(resumed["final_loss"], straight["final_loss"], rtol=1e-6)


@pytest.mark.slow
def test_async_save_loop_durable_and_resumable(tmp_path, devices):
    """async_save: periodic checkpoints commit in the background but are
    durable by loop exit, and a resumed run picks the latest one up."""
    from llama_pipeline_parallel_tpu.ckpt.checkpoint import CheckpointManager

    cfg = base_cfg(tmp_path, save_steps=2, async_save=True, max_steps=4,
                   total_steps=8)
    out = run_training(cfg)["output_dir"]
    mgr = CheckpointManager(out)
    assert mgr.list_steps(complete_only=True) == [2, 4]
    assert mgr.latest_step() == 4

    resumed = run_training(base_cfg(tmp_path, save_steps=2, async_save=True,
                                    max_steps=8))
    assert resumed["final_step"] == 8
    assert CheckpointManager(out).latest_step() == 8


def test_warm_start_requires_checkpoint(tmp_path, devices):
    cfg = base_cfg(tmp_path, model_name_or_path=str(tmp_path / "missing"), resume=False)
    with pytest.raises(FileNotFoundError, match="convert_hf"):
        run_training(cfg)


@pytest.mark.slow
def test_offload_loop_runs_and_resumes(tmp_path, devices):
    """Host-offloaded optimizer path: loss decreases on a fixed-seed synthetic
    set; interrupted + resumed equals straight-through."""
    base = dict(base_cfg(tmp_path, output_dir=str(tmp_path / "o"), max_steps=8,
                         total_steps=8, optimizer_offload=True, learning_rate=1e-2))
    straight = run_training(dict(base, output_dir=str(tmp_path / "oa")))
    run_training(dict(base, output_dir=str(tmp_path / "ob"), max_steps=4))
    resumed = run_training(dict(base, output_dir=str(tmp_path / "ob"), max_steps=8))
    np.testing.assert_allclose(resumed["final_loss"], straight["final_loss"], rtol=1e-5)


@pytest.mark.slow
def test_offload_zero2_matches_plain_offload(tmp_path, devices):
    """optimizer_offload_zero2 (dp-sharded masters/moments + reduce-scattered
    grads + per-step dp re-gather of the bf16 working copy) is numerically
    identical to the plain offload layout — and each host stores only 1/dp
    of the dp-shardable leaves."""
    base = dict(base_cfg(tmp_path, optimizer_offload=True, learning_rate=1e-2,
                         max_steps=4, total_steps=4))
    plain = run_training(dict(base, output_dir=str(tmp_path / "p")))
    z2 = run_training(dict(base, output_dir=str(tmp_path / "z"),
                           optimizer_offload_zero2=True))
    np.testing.assert_allclose(z2["final_loss"], plain["final_loss"],
                               rtol=1e-6)


@pytest.mark.slow
def test_offload_zero2_resumes_identically(tmp_path, devices):
    """z2 interrupted-at-2 + resume-to-4 equals straight z2: the dp-sharded
    master/moment templates round-trip through the checkpoint (the canonical
    reshape preserves trailing-dim dp shardings)."""
    base = dict(base_cfg(tmp_path, optimizer_offload=True,
                         optimizer_offload_zero2=True, learning_rate=1e-2,
                         max_steps=4, total_steps=4))
    straight = run_training(dict(base, output_dir=str(tmp_path / "s")))
    run_training(dict(base, output_dir=str(tmp_path / "r"), max_steps=2))
    resumed = run_training(dict(base, output_dir=str(tmp_path / "r")))
    assert resumed["final_step"] == 4
    np.testing.assert_allclose(resumed["final_loss"], straight["final_loss"],
                               rtol=1e-6)


@pytest.mark.slow
def test_offload_zero2_uneven_partition_resumes(tmp_path, devices):
    """z2 composed with an uneven stage partition (5 layers on pp=2): the
    abstract unstack now carries trailing-dim (dp) shardings through the
    uneven gather, so the resume templates stay dp-sharded and the
    interrupted run continues identically."""
    model = {"preset": "tiny", "dtype": "float32", "num_hidden_layers": 5}
    base = dict(base_cfg(tmp_path, optimizer_offload=True,
                         optimizer_offload_zero2=True, learning_rate=1e-2,
                         model=model, max_steps=4, total_steps=4))
    straight = run_training(dict(base, output_dir=str(tmp_path / "us")))
    run_training(dict(base, output_dir=str(tmp_path / "ur"), max_steps=2))
    resumed = run_training(dict(base, output_dir=str(tmp_path / "ur")))
    np.testing.assert_allclose(resumed["final_loss"], straight["final_loss"],
                               rtol=1e-6)


def test_offload_zero2_requires_offload(tmp_path, devices):
    with pytest.raises(ValueError, match="requires optimizer_offload"):
        run_training(base_cfg(tmp_path, optimizer_offload_zero2=True))


def test_zero2_param_specs_shard_over_dp(devices):
    """The z2 spec rule: every dp-shardable leaf gains AXIS_DP on its
    rightmost free dim; indivisible leaves keep their plain spec."""
    import jax
    from jax.sharding import PartitionSpec as P

    from llama_pipeline_parallel_tpu.models.llama import model as llama
    from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
    from llama_pipeline_parallel_tpu.models.llama.manifest import StageManifest
    from llama_pipeline_parallel_tpu.parallel import pipeline as pl
    from llama_pipeline_parallel_tpu.parallel import train_step as ts
    from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(pp=2, dp=2))
    cfg = LlamaConfig.tiny()
    stacked = pl.stack_stages(
        jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0), cfg)),
        StageManifest.for_config(cfg, 2))
    specs = ts.zero2_param_specs(stacked, mesh)
    # stacked layer matmul leaf [pp, k, d, d]: dp lands on the last dim
    assert specs["layers"]["attn"]["wq"] == P("pp", None, None, "dp")
    flat = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert all(isinstance(s, P) for s in flat)
    # every leaf of this model is dp-shardable (all dims are multiples of 2)
    assert all("dp" in s for s in flat), flat


def test_offload_save_total_limit(tmp_path, devices):
    """The retention knob covers the offload save path too: only the newest
    checkpoint survives at save_total_limit=1."""
    from llama_pipeline_parallel_tpu.ckpt.checkpoint import CheckpointManager

    cfg = base_cfg(tmp_path, optimizer_offload=True, save_steps=2,
                   save_total_limit=1, max_steps=4, total_steps=4)
    out = run_training(cfg)["output_dir"]
    mgr = CheckpointManager(out)
    assert mgr.list_steps(complete_only=True) == [4]
    assert mgr.latest_step() == 4


@pytest.mark.slow
def test_offload_with_uneven_stages(tmp_path, devices):
    """Host-offloaded optimizer composed with an auto-balanced uneven
    partition (5 layers on pp=2): the padded stacked layout must survive the
    host round-trip (shard-keyed masters, f32 working copy) unchanged —
    pinned by matching the fused-optimizer path's losses on the identical
    run (the offload kernel mirrors optax numerics)."""
    model = {"preset": "tiny", "dtype": "float32", "num_hidden_layers": 5}
    fused = run_training(base_cfg(tmp_path, output_dir=str(tmp_path / "f"),
                                  learning_rate=1e-2, model=model))
    off = run_training(base_cfg(tmp_path, output_dir=str(tmp_path / "o"),
                                optimizer_offload=True, learning_rate=1e-2,
                                model=model))
    assert off["final_step"] == 4
    np.testing.assert_allclose(off["final_loss"], fused["final_loss"], rtol=2e-5)


@pytest.mark.slow
def test_eval_loop(tmp_path, devices):
    cfg = base_cfg(tmp_path, eval_steps=2,
                   eval_dataset={"synthetic": True, "seq_length": 16,
                                 "pseudo_dataset_len": 16})
    summary = run_training(cfg)
    lines = [json.loads(l) for l in
             open(os.path.join(summary["output_dir"], "metrics.jsonl"))]
    evals = [l for l in lines if "eval_loss" in l]
    assert len(evals) == 2 and all(np.isfinite(l["eval_loss"]) for l in evals)
    # the LAST eval lands in the final checkpoint's meta.json — the quality
    # signal the continuous-deployment gate (utils/actions.Deployer) reads
    from llama_pipeline_parallel_tpu.utils.actions import checkpoint_eval_loss

    meta = json.load(open(os.path.join(summary["output_dir"],
                                       "checkpoint-4", "meta.json")))
    assert meta["eval_loss"] == evals[-1]["eval_loss"]
    assert meta["eval_step"] == 4
    assert checkpoint_eval_loss(summary["output_dir"], 4) == meta["eval_loss"]


def test_shipped_configs_parse():
    """EVERY shipped config must parse, build its model config, and satisfy
    the mesh divisibility rules the runtime enforces (tp over heads/kv/ffn/
    vocab, sp over the sequence) — a new yaml cannot ship broken."""
    import glob

    from llama_pipeline_parallel_tpu.train import build_model_config

    conf_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "conf")
    paths = sorted(glob.glob(os.path.join(conf_dir, "*.yaml")))
    assert len(paths) >= 5
    for path in paths:
        cfg = load_config(path)
        assert isinstance(cfg["learning_rate"], float), path
        mesh = cfg.get("mesh", {})
        assert mesh.get("pp", 1) >= 1, path
        mc = build_model_config(cfg["model"])
        tp, sp = mesh.get("tp", 1), mesh.get("sp", 1)
        assert mc.num_attention_heads % tp == 0, path
        assert mc.kv_heads % tp == 0, path
        assert mc.intermediate_size % tp == 0, path
        assert mc.vocab_size % tp == 0, path
        assert cfg.get("max_seq_length", 512) % sp == 0, path
        assert mc.num_hidden_layers >= mesh.get("pp", 1), path


def test_resize_request_checkpoints_acks_and_exits(tmp_path, devices):
    """actions.resize_on_request: a `resize.request` dropped into
    output_dir (the supervisor's actuation RPC) stops the loop at the next
    step boundary — checkpoint saved, THEN the request renamed to
    `resize.request.ack` (ack-after-save: a crash mid-save leaves the
    request for the next incarnation), clean exit."""
    import threading
    import time as _time

    from llama_pipeline_parallel_tpu.utils.actions import (
        RESIZE_ACK_NAME,
        RESIZE_REQUEST_NAME,
    )

    out = str(tmp_path / "out")
    req = os.path.join(out, RESIZE_REQUEST_NAME)

    def drop_once_running():
        deadline = _time.time() + 120
        metrics = os.path.join(out, "metrics.jsonl")
        while _time.time() < deadline and not os.path.exists(metrics):
            _time.sleep(0.05)
        with open(req + ".tmp", "w") as f:
            json.dump({"rung": "half", "id": "action-000000"}, f)
        os.replace(req + ".tmp", req)

    t = threading.Thread(target=drop_once_running)
    t.start()
    try:
        summary = run_training(base_cfg(
            tmp_path, max_steps=60, logging_steps=1,
            actions={"resize_on_request": True}))
    finally:
        t.join()
    assert summary["preempted_at"] is not None
    assert summary["preempted_at"] < 60
    step = summary["final_step"]
    assert os.path.isdir(os.path.join(out, f"checkpoint-{step}"))
    assert not os.path.exists(req)
    ack = json.load(open(os.path.join(out, RESIZE_ACK_NAME)))
    assert ack["rung"] == "half"


def test_resize_request_inert_without_actions_block(tmp_path, devices):
    """No `actions` config -> the trainer never reads resize.request: the
    run completes untouched and the file survives (actuation is opt-in at
    every layer)."""
    from llama_pipeline_parallel_tpu.utils.actions import RESIZE_REQUEST_NAME

    out = tmp_path / "out"
    out.mkdir()
    req = os.path.join(str(out), RESIZE_REQUEST_NAME)
    with open(req, "w") as f:
        json.dump({"rung": "half"}, f)
    summary = run_training(base_cfg(tmp_path))
    assert summary["preempted_at"] is None and summary["final_step"] == 4
    assert os.path.exists(req)  # nobody consumed it
