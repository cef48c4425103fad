import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from llama_pipeline_parallel_tpu.parallel import mesh as mesh_lib
from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig, make_mesh


def test_mesh_shapes(devices):
    m = make_mesh(MeshConfig(pp=4, dp=2))
    assert m.shape == {"pp": 4, "dp": 2, "sp": 1, "tp": 1}
    m2 = make_mesh(MeshConfig(pp=2, dp=2, tp=2))
    assert m2.shape["tp"] == 2


def test_from_world():
    cfg = MeshConfig.from_world(8, pp=4)
    assert cfg.dp == 2 and cfg.world_size == 8
    with pytest.raises(ValueError):
        MeshConfig.from_world(6, pp=4)


def test_too_many_devices(devices):
    with pytest.raises(ValueError):
        make_mesh(MeshConfig(pp=16))


def test_ep_axis_is_a_reserved_hook():
    """SURVEY §2.2: the expert-parallel axis NAME exists for a future MoE
    block, but sharding over it is rejected until one does."""
    assert mesh_lib.AXIS_EP == "ep"
    assert MeshConfig(ep=1).world_size == 1  # accepted, inert
    with pytest.raises(NotImplementedError, match="expert parallelism"):
        MeshConfig(ep=2)


def test_stage_index_inside_shard_map(devices):
    m = make_mesh(MeshConfig(pp=4, dp=2))

    def f():
        return (
            mesh_lib.stage_index()[None],
            mesh_lib.dp_index()[None],
            mesh_lib.is_last_stage()[None],
        )

    sm = shard_map(
        f, mesh=m, in_specs=(), out_specs=(P("pp"), P("dp"), P("pp")), check_vma=False
    )
    stages, dps, last = jax.jit(sm)()
    np.testing.assert_array_equal(np.asarray(stages), [0, 1, 2, 3])
    np.testing.assert_array_equal(np.asarray(dps), [0, 1])
    np.testing.assert_array_equal(np.asarray(last), [False, False, False, True])


def test_underuse_warning_once_per_layout(devices):
    """The 'mesh uses N of M devices' warning fires once per distinct
    layout, not on every mesh build (it used to repeat dozens of times in a
    dryrun sweep — MULTICHIP_r05)."""
    import logging

    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    layout_a = MeshConfig(pp=3)
    layout_b = MeshConfig(dp=3)
    # hermetic: an earlier build of these layouts (or an in-process re-run
    # of this test) must not pre-latch the warn-once set
    mesh_lib._UNDERUSE_WARNED.discard((3, 8, 3, 1, 1, 1))
    mesh_lib._UNDERUSE_WARNED.discard((3, 8, 1, 3, 1, 1))

    handler = Capture(level=logging.WARNING)
    logger = logging.getLogger("llama_pipeline_parallel_tpu.parallel.mesh")
    logger.addHandler(handler)
    try:
        def warnings_for(cfg):
            records.clear()
            make_mesh(cfg)
            return [m for m in records if "available devices" in m]

        assert len(warnings_for(layout_a)) == 1
        assert len(warnings_for(layout_a)) == 0   # repeat build: silent
        assert len(warnings_for(layout_b)) == 1   # a NEW layout still warns
        assert len(warnings_for(layout_b)) == 0
    finally:
        logger.removeHandler(handler)
