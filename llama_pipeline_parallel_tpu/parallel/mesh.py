"""Device-mesh construction and topology queries.

TPU-native replacement for the DeepSpeed process-grid the reference relies on:
`PipelineModule.grid` / `ProcessTopology` (reference trainer_base_ds_mp.py:245,313
computes `dp_degree = world_size // num_stages` and queries
`model.grid.get_data_parallel_id()`).  Here the topology is an explicit
`jax.sharding.Mesh` over four named axes:

    pp  pipeline stages           (activation handoff rides `lax.ppermute`)
    dp  data-parallel replicas    (gradient psum / ZeRO-1 opt-state sharding)
    tp  tensor parallel           (head/ffn sharding, psum at block outputs)
    sp  sequence/context parallel (ring attention KV rotation)

Axis order is chosen so the model axes (tp, sp) are innermost (fastest-varying
-> contiguous ICI neighbours on real TPU slices), dp next, and pp outermost —
pipeline handoff is the least bandwidth-hungry collective so it can ride the
outer links / DCN on multi-slice topologies.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from llama_pipeline_parallel_tpu.utils.logging import get_logger

AXIS_PP = "pp"
AXIS_DP = "dp"
AXIS_TP = "tp"
AXIS_SP = "sp"
# Expert-parallel axis hook (SURVEY.md §2.2: MoE is out of the reference's
# scope — dense LLaMA only — but the axis NAME is reserved so an expert
# router can shard over it without renaming the mesh). MeshConfig accepts
# `ep` and rejects >1 until a MoE block exists; while inert, ep is
# deliberately EXCLUDED from ALL_AXES / world_size / axis_sizes /
# from_world — whoever adds MoE must wire it into all four.
AXIS_EP = "ep"
ALL_AXES = (AXIS_PP, AXIS_DP, AXIS_SP, AXIS_TP)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Degrees of each parallelism axis.

    Replaces the reference's implicit rule `dp_degree = world // num_stages`
    (trainer_base_ds_mp.py:245): here every axis is explicit and validated
    against the device count.
    """

    pp: int = 1
    dp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1  # reserved (AXIS_EP): expert parallelism for a future MoE block

    def __post_init__(self) -> None:
        for axis in ("pp", "dp", "tp", "sp", "ep"):
            if getattr(self, axis) < 1:
                raise ValueError(f"axis {axis} must be >= 1, got {getattr(self, axis)}")
        if self.ep > 1:
            raise NotImplementedError(
                "expert parallelism (ep) is an axis-name hook only: the model "
                "family is dense LLaMA (SURVEY.md §2.2) — add a MoE block "
                "before sharding over AXIS_EP")

    @property
    def world_size(self) -> int:
        return self.pp * self.dp * self.tp * self.sp

    def axis_sizes(self) -> dict[str, int]:
        return {AXIS_PP: self.pp, AXIS_DP: self.dp, AXIS_SP: self.sp, AXIS_TP: self.tp}

    def describe(self) -> str:
        """Compact layout label ("pp2xdp4xtp1xsp1") for logs, checkpoint
        topology metadata, and the supervisor's incarnation ledger."""
        return f"pp{self.pp}xdp{self.dp}xtp{self.tp}xsp{self.sp}"

    @staticmethod
    def from_world(world_size: int, pp: int = 1, tp: int = 1, sp: int = 1) -> "MeshConfig":
        """Infer dp from the device count, reference-style (world // pp)."""
        if min(pp, tp, sp) < 1:
            raise ValueError(f"axis degrees must be >= 1, got pp={pp} tp={tp} sp={sp}")
        denom = pp * tp * sp
        if world_size % denom:
            raise ValueError(f"world_size={world_size} not divisible by pp*tp*sp={denom}")
        return MeshConfig(pp=pp, dp=world_size // denom, tp=tp, sp=sp)


# Layouts already warned about as under-using the device pool: one warning
# per distinct (world_size, available, axes) layout per process — test
# suites and dryrun sweeps build the same small mesh dozens of times, and
# repeating the line every build buries real output (MULTICHIP_r05).
_UNDERUSE_WARNED: set = set()


def make_mesh(config: MeshConfig, devices: Sequence[jax.Device] | None = None) -> Mesh:
    """Build the `(pp, dp, sp, tp)` mesh over the available devices."""
    if devices is None:
        devices = jax.devices()
    if config.world_size > len(devices):
        raise ValueError(
            f"mesh needs {config.world_size} devices "
            f"(pp={config.pp} dp={config.dp} sp={config.sp} tp={config.tp}) "
            f"but only {len(devices)} available"
        )
    if config.world_size < len(devices):
        layout = (config.world_size, len(devices),
                  config.pp, config.dp, config.sp, config.tp)
        if layout not in _UNDERUSE_WARNED:
            _UNDERUSE_WARNED.add(layout)
            get_logger(__name__).warning(
                "mesh uses %d of %d available devices (pp=%d dp=%d sp=%d tp=%d); "
                "the rest stay idle (warned once per layout)",
                config.world_size, len(devices), config.pp, config.dp, config.sp,
                config.tp,
            )
    devices = list(devices)[: config.world_size]
    shape = (config.pp, config.dp, config.sp, config.tp)
    if len(devices) > 1 and devices[0].platform == "tpu":
        from jax.experimental import mesh_utils

        try:
            dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
        except ValueError:
            get_logger(__name__).warning(
                "mesh_utils.create_device_mesh failed for shape %s; falling back to "
                "naive device order — ICI placement may be suboptimal", shape,
            )
            dev_array = np.asarray(devices).reshape(shape)
    else:
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, ALL_AXES)


# ---------------------------------------------------------------------------
# In-SPMD topology queries (valid inside shard_map only)
# ---------------------------------------------------------------------------

def stage_index() -> jax.Array:
    """This device's pipeline-stage id (replaces grid.get_pipe_parallel_rank)."""
    return jax.lax.axis_index(AXIS_PP)


def dp_index() -> jax.Array:
    """Data-parallel replica id (replaces grid.get_data_parallel_id,
    reference trainer_base_ds_mp.py:313)."""
    return jax.lax.axis_index(AXIS_DP)


def is_first_stage() -> jax.Array:
    return stage_index() == 0


def is_last_stage() -> jax.Array:
    return stage_index() == jax.lax.axis_size(AXIS_PP) - 1
