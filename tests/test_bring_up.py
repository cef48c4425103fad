"""What the bring-up PR added to start-up: where the compile cache lives,
and the measurement paths' refusal to run without a chip they know."""

import os
import subprocess
import sys

import jax
import pytest

from llama_pipeline_parallel_tpu.utils import compile_cache, metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


METADATA_IN_KEY = "jax_compilation_cache_include_metadata_in_key"


def test_compile_cache_env_set_leaves_the_directory_alone(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX already honours it; the helper
    must not place the cache (whoever owns the machine does). The one
    option it does write is the key's metadata rule."""
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: updates.append(a))
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "cache"))
    assert compile_cache.setup() == str(tmp_path / "cache")
    assert updates == [(METADATA_IN_KEY, True)]


def test_compile_cache_default_is_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.setup()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.parametrize("env_set", [False, True])
def test_compile_cache_key_includes_scope_metadata(monkeypatch, tmp_path,
                                                   env_set):
    """Scope names are HLO metadata: left out of the key, a cache warmed
    before a scope changed returns an executable with the old names."""
    if env_set:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "cache"))
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    prev = (jax.config.jax_compilation_cache_dir,
            getattr(jax.config, METADATA_IN_KEY))
    try:
        jax.config.update(METADATA_IN_KEY, False)
        compile_cache.setup()
        assert getattr(jax.config, METADATA_IN_KEY) is True
    finally:
        jax.config.update("jax_compilation_cache_dir", prev[0])
        jax.config.update(METADATA_IN_KEY, prev[1])


def test_compile_cache_entry_count(tmp_path):
    assert compile_cache.entry_count(str(tmp_path / "absent")) == 0
    (tmp_path / "jit_f-abc-cache").write_bytes(b"x")
    (tmp_path / "jit_f-abc-atime").write_bytes(b"x")
    assert compile_cache.entry_count(str(tmp_path)) == 1


def test_unknown_device_kind_is_an_error():
    """A measurement against a guessed peak is wrong: the CPU backend's
    device_kind is not in the table, so the strict lookup raises and names
    what it found (the trainer's meter just omits `mfu` there)."""
    assert metrics.detect_chip_peak_flops() is None
    with pytest.raises(RuntimeError, match="unknown device_kind 'cpu'"):
        metrics.require_chip_peak_flops()


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_measurement_entry_points_refuse_without_a_tpu(script):
    """No CPU fallback: exit nonzero, name the backend found, print no
    result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, script)], cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout and '"metric"' not in proc.stdout
