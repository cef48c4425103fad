"""Host-offloaded AdamW (C++ kernel, shard-aware) vs optax numerics."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from llama_pipeline_parallel_tpu.optim import OptimizerConfig, make_optimizer
from llama_pipeline_parallel_tpu.optim import offload as off
from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig, make_mesh


@pytest.fixture(scope="module")
def tree():
    rng = np.random.RandomState(0)
    return {"a": jnp.asarray(rng.randn(64, 32), jnp.float32),
            "b": {"c": jnp.asarray(rng.randn(128), jnp.float32)}}


def grads_like(tree, seed):
    rng = np.random.RandomState(seed)
    return jax.tree.map(lambda x: jnp.asarray(rng.randn(*x.shape) * 2, jnp.float32), tree)


def optax_reference(tree, cfg, n_steps):
    tx, _ = make_optimizer(cfg)
    opt_state = tx.init(tree)
    params = tree
    import optax

    for step in range(n_steps):
        updates, opt_state = tx.update(grads_like(tree, step), opt_state, params)
        params = optax.apply_updates(params, updates)
    return params


def test_native_kernel_builds_into_checkout_keyed_by_source_hash():
    """The build rule: the .so lands in the git-ignored <checkout>/.lpt_native
    under the sha256 of csrc/host_adamw.cpp as committed — never a shared
    /tmp, never reused by mtime."""
    import hashlib

    assert off._load_native() is not None
    path = off.native_lib_path()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.dirname(path) == os.path.join(repo, ".lpt_native")
    with open(off._CSRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    assert os.path.basename(path) == f"host_adamw-{digest}.so"
    assert os.path.exists(path)


def test_native_build_failure_raises(tmp_path, monkeypatch):
    """No numpy fallback: a source that does not compile is an error."""
    bad = tmp_path / "host_adamw.cpp"
    bad.write_text("this is not C++")
    monkeypatch.setattr(off, "_CSRC", str(bad))
    monkeypatch.setattr(off, "_BUILD_DIR", str(tmp_path / "build"))
    monkeypatch.setattr(off, "_lib", None)
    with pytest.raises(RuntimeError, match="could not build the host AdamW"):
        off._load_native()


def test_matches_optax(tree):
    cfg = OptimizerConfig(learning_rate=1e-2, weight_decay=0.1, beta1=0.9,
                          beta2=0.95, max_grad_norm=1.0, total_steps=100,
                          warmup_steps=10)
    params_ref = optax_reference(tree, cfg, 5)

    host = off.HostOffloadAdamW(cfg)
    host.init(tree)
    for step in range(5):
        host.update(grads_like(tree, step))

    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6),
        params_ref, host.masters_tree())
    assert host.last_grad_norm > 0
    assert host.last_timings["update_ms"] >= 0


def test_sharded_masters_match_optax(tree, devices):
    """Masters stored per mesh shard (pp x dp sharded + replicated leaves)
    must step to the same values as the unsharded optax chain."""
    mesh = make_mesh(MeshConfig(pp=2, dp=2))
    shard_specs = {"a": P("pp"), "b": {"c": P()}}  # sharded + replicated leaf
    put = lambda t: jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), t, shard_specs)
    cfg = OptimizerConfig(learning_rate=1e-2, weight_decay=0.1,
                          max_grad_norm=1.0, total_steps=100, warmup_steps=10)
    params_ref = optax_reference(tree, cfg, 3)

    host = off.HostOffloadAdamW(cfg)
    host.init(put(tree))
    assert len(host._leaves[0].shards) == 2   # "a" split over pp
    assert len(host._leaves[1].shards) == 1   # replicated "c": one distinct shard
    for step in range(3):
        host.update(put(grads_like(tree, step)))

    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-6),
        params_ref, host.masters_tree())

    # the bf16 working copy keeps the mesh sharding and the master values
    dev = host.device_params(jnp.bfloat16)
    assert dev["a"].sharding.spec == NamedSharding(mesh, P("pp")).spec
    np.testing.assert_allclose(np.asarray(dev["a"], np.float32),
                               np.asarray(host.masters_tree()["a"]),
                               rtol=8e-3, atol=1e-5)


def test_update_and_refresh_matches_separate_phases(tree, devices):
    """The fused per-leaf AdamW + cast + H2D pipeline (update_and_refresh,
    the trainer's hot path) is bit-identical to the separate
    update() + device_params() phases — same kernels, same order — while
    returning the same sharded working copy."""
    mesh = make_mesh(MeshConfig(pp=2, dp=2))
    shard_specs = {"a": P("pp"), "b": {"c": P()}}
    put = lambda t: jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), t, shard_specs)
    cfg = OptimizerConfig(learning_rate=1e-2, weight_decay=0.1,
                          max_grad_norm=1.0, total_steps=100, warmup_steps=10)

    h_sep = off.HostOffloadAdamW(cfg)
    h_sep.init(put(tree))
    h_fused = off.HostOffloadAdamW(cfg)
    h_fused.init(put(tree))

    for step in range(3):
        g = put(grads_like(tree, step))
        h_sep.update(g)
        dev_sep = h_sep.device_params(jnp.bfloat16)
        dev_fused = h_fused.update_and_refresh(g, jnp.bfloat16)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)),
            dev_sep, dev_fused)
        assert dev_fused["a"].sharding.spec == NamedSharding(mesh, P("pp")).spec
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)),
        h_sep.masters_tree(), h_fused.masters_tree())
    assert h_fused.last_timings["update_h2d_ms"] >= 0
    assert h_fused.last_grad_norm == h_sep.last_grad_norm


def test_state_dict_roundtrip(tree):
    cfg = OptimizerConfig(learning_rate=1e-2, total_steps=50, warmup_steps=2)
    h1 = off.HostOffloadAdamW(cfg)
    h1.init(tree)
    h1.update(grads_like(tree, 0))

    h2 = off.HostOffloadAdamW(cfg)
    h2.init(tree)
    h2.load_state_dict(h1.state_dict())
    h2.load_masters(h1.masters_tree())

    h1.update(grads_like(tree, 1))
    h2.update(grads_like(tree, 1))
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y), rtol=0, atol=0),
        h1.masters_tree(), h2.masters_tree())


def test_bf16_host_cast_matches_device_cast(tree):
    """The native round-to-nearest-even f32->bf16 must agree with XLA's."""
    cfg = OptimizerConfig(total_steps=10, warmup_steps=1)
    host = off.HostOffloadAdamW(cfg)
    host.init(tree)
    dev = host.device_params(jnp.bfloat16)
    expected = jax.tree.map(lambda x: jnp.asarray(x).astype(jnp.bfloat16), tree)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b, np.float32)), dev, expected)


def test_offload_checkpoint_restores_sharded(tmp_path, devices):
    """save_offload -> load with the host's sharded abstract template: the
    restored params keep the pp sharding end to end (at 65B an unsharded
    restore would funnel whole canonical leaves through one device)."""
    from llama_pipeline_parallel_tpu.ckpt.checkpoint import CheckpointManager
    from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
    from llama_pipeline_parallel_tpu.models.llama.manifest import StageManifest
    from llama_pipeline_parallel_tpu.parallel import train_step as ts

    mcfg = LlamaConfig.tiny()
    mesh = make_mesh(MeshConfig(pp=4))
    man = StageManifest.for_config(mcfg, 4)
    stacked = ts.init_params_sharded(jax.random.PRNGKey(0), mcfg, mesh, man)

    cfg = OptimizerConfig(learning_rate=1e-2, total_steps=10, warmup_steps=1)
    host = off.HostOffloadAdamW(cfg)
    host.init(stacked)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save_offload(3, host, man, mcfg)

    template = host.abstract_tree()
    assert tuple(template["layers"]["attn"]["wq"].sharding.spec)[0] == "pp"
    restored = mgr.load_params(3, template, man)
    wq = restored["layers"]["attn"]["wq"]
    assert tuple(wq.sharding.spec)[0] == "pp"  # never funneled to one device
    np.testing.assert_array_equal(
        np.asarray(wq), np.asarray(stacked["layers"]["attn"]["wq"]))
    m, v, step_count = mgr.load_offload_moments(3, template, man)
    assert step_count == 0
    np.testing.assert_array_equal(np.asarray(m["norm"]), 0.0)


def test_mismatched_tree_raises(tree):
    cfg = OptimizerConfig(total_steps=10, warmup_steps=1)
    h = off.HostOffloadAdamW(cfg)
    h.init(tree)
    with pytest.raises(ValueError, match="does not match"):
        h.update({"a": jnp.zeros((64, 32))})


def test_device_norm_streaming_matches_host_norm(tree, devices):
    """The streaming fused step (device-side global norm, the trainer's
    default) matches the host-norm path within fp32-vs-fp64 norm-accumulation
    tolerance — WITH clipping active (grads_like's *2 against clip 1.0), so
    the grad_scale actually depends on the norm under test."""
    mesh = make_mesh(MeshConfig(pp=2, dp=2))
    shard_specs = {"a": P("pp"), "b": {"c": P()}}
    put = lambda t: jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), t, shard_specs)
    cfg = OptimizerConfig(learning_rate=1e-2, weight_decay=0.1,
                          max_grad_norm=1.0, total_steps=100, warmup_steps=10)

    h_host = off.HostOffloadAdamW(cfg)
    h_host.init(put(tree))
    h_dev = off.HostOffloadAdamW(cfg, device_norm=True)
    h_dev.init(put(tree))

    for step in range(3):
        g = put(grads_like(tree, step))
        if step == 2:  # gpipe can hand the optimizer bf16 grads: the device
            # norm must cast to fp32 before accumulating (8 mantissa bits
            # would move the clip factor ~0.4%)
            g = jax.tree.map(lambda x: x.astype(jnp.bfloat16), g)
        dev_a = h_host.update_and_refresh(g, jnp.float32)
        dev_b = h_dev.update_and_refresh(g, jnp.float32)
        assert "stream_d2h_update_h2d_ms" in h_dev.last_timings
        assert "d2h_norm_ms" in h_host.last_timings
        np.testing.assert_allclose(h_dev.last_grad_norm, h_host.last_grad_norm,
                                   rtol=1e-6)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-6, atol=1e-7),
            dev_a, dev_b)
