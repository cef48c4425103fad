"""Force an 8-device virtual CPU mesh before jax is imported anywhere.

This is the TPU-world analogue of a fake NCCL backend: multi-chip PP/DP/TP/SP
paths run on one host (SURVEY.md §4 test strategy)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

import sys  # noqa: E402

import pytest  # noqa: E402

# repo tools/ are plain scripts, not a package: make them importable once
# for every test that drives one (inspect_ckpt, trace_summary, ...)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "tools"))


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs
