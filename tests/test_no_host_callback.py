"""No compiled program of this package calls the host. The train step under
every schedule name and the decode tick of every served family contain no
callback primitive: a step's and a tick's time is read from the device trace
(the `pp_*` scopes, `profile_window`, `serve_decode_step`), never from a
clock compiled into the program. A config that still asks for the removed
`timeline:` block is refused by name. float32 on the CPU at tiny sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import eva_tiny
import hybrid_tiny
import latent_tiny
import ssm_tiny
import window_tiny
from llama_pipeline_parallel_tpu import serve
from llama_pipeline_parallel_tpu.models import family as families
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.models.llama.manifest import StageManifest
from llama_pipeline_parallel_tpu.optim import OptimizerConfig, make_optimizer
from llama_pipeline_parallel_tpu.parallel import pipeline as pl
from llama_pipeline_parallel_tpu.parallel import schedule as usched
from llama_pipeline_parallel_tpu.parallel import train_step as ts
from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig, make_mesh


def _primitives(jaxpr) -> set:
    """The name of every primitive in a jaxpr, its sub-jaxprs' included."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


def _host_calls(closed_jaxpr) -> set:
    return {p for p in _primitives(closed_jaxpr.jaxpr) if "callback" in p}


def test_the_search_finds_a_callback_under_jit_and_scan():
    """The check has teeth: a host callback two programs deep is found."""
    def body(carry, x):
        y = jax.pure_callback(lambda v: v, jax.ShapeDtypeStruct((), x.dtype),
                              x)
        return carry + y, y

    program = jax.jit(lambda xs: jax.lax.scan(body, jnp.float32(0), xs))
    found = _host_calls(jax.make_jaxpr(program)(jnp.ones(3)))
    assert found == {"pure_callback"}


# -- (a) the train step, under every schedule name ------------------------------

PP, MICROBATCHES = 2, 2
# what the tiny conf needs to build each: chunks per stage, a loaded sequence
SCHEDULE_NEEDS = {
    "1f1b": {},
    "interleaved_1f1b": {"virtual_stages": 2},
    "zb1": {"virtual_stages": 2},
    "solver": {"virtual_stages": 2,
               "unit_schedule": lambda: usched.canonical_schedule(
                   "zb1", MICROBATCHES, PP, 2)},
    "gpipe": {},
}


@pytest.mark.parametrize("schedule", pl.SCHEDULES)
def test_no_host_callback_in_any_train_program(schedule, devices):
    needs = {k: v() if callable(v) else v
             for k, v in SCHEDULE_NEEDS[schedule].items()}
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    mesh = make_mesh(MeshConfig(pp=PP))
    manifest = StageManifest.for_config(
        cfg, PP, virtual_stages=needs.get("virtual_stages", 1))
    stacked = pl.stack_stages(llama.init_params(jax.random.PRNGKey(0), cfg),
                              manifest)
    pcfg = pl.PipelineConfig(num_stages=PP, num_microbatches=MICROBATCHES,
                             schedule=schedule, **needs)
    tx, lr = make_optimizer(OptimizerConfig(
        learning_rate=1e-3, total_steps=50, warmup_steps=5))
    state = ts.init_train_state(stacked, tx, mesh)
    step = ts.make_train_step(mesh, cfg, pcfg, tx, lr, stacked)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        3, cfg.vocab_size, size=(MICROBATCHES, 16)), jnp.int32)
    batch = {"input_ids": ids, "labels": ids,
             "attention_mask": jnp.ones_like(ids),
             "position_ids": jnp.broadcast_to(
                 jnp.arange(16, dtype=jnp.int32), ids.shape)}
    jaxpr = jax.make_jaxpr(step)(state, batch)
    # the walk went into the pipeline's program, not round it
    assert "shard_map" in _primitives(jaxpr.jaxpr)
    assert _host_calls(jaxpr) == set()


# -- (b) the decode tick, of every served family --------------------------------

def _dense():
    cfg = LlamaConfig.tiny()
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg), dict(
        max_len=32, prompt_buckets=(8, 16), page_size=8, num_pages=32)


def _hybrid():
    return hybrid_tiny.config(), hybrid_tiny.both_sides()[0], dict(
        max_len=48, prompt_buckets=(8, 16), page_size=8, num_pages=32)


def _latent():
    return latent_tiny.config(), latent_tiny.both_sides()[0], dict(
        max_len=64, prompt_buckets=(8, 16, 32), page_size=4, num_pages=64,
        prefill_chunk_tokens=8)


def _eva():
    cfg = eva_tiny.tiny_config()
    return cfg, eva_tiny.tiny_params(cfg), dict(
        max_len=160, prompt_buckets=(16, 32), page_size=eva_tiny.PAGE,
        num_pages=40, prefill_chunk_tokens=32)


def _ssm():
    return ssm_tiny.config(), ssm_tiny.both_sides()[0], dict(
        max_len=48, prompt_buckets=(8, 16), page_size=8, num_pages=32)


def _window():
    return window_tiny.config(), window_tiny.both_sides()[0], dict(
        max_len=48, prompt_buckets=(8, 16), page_size=window_tiny.PAGE,
        num_pages=48, prefill_chunk_tokens=8)


FAMILIES = {"llama": _dense, "hybrid_moe": _hybrid, "latent_moe": _latent,
            "eva": _eva, "ssm_moe": _ssm, "window_moe": _window}


# a family added to `models/family.py` fails here until it has a tiny conf
@pytest.mark.parametrize("family", sorted(families._FAMILIES))
def test_no_host_callback_in_any_decode_tick(family):
    """The program the ENGINE enqueues each tick (the family's
    `paged_decode_step` body behind one staged buffer), traced with the
    arguments of a real first tick."""
    cfg, params, shape = FAMILIES[family]()
    engine = serve.ServeEngine(params, cfg, serve.ServeConfig(
        max_slots=2, max_queue=4, **shape))
    assert engine._family.name == family
    real = engine._tick_program
    ticks = []

    def traced(params, staged, prev, pool, kv_mask, cfg):
        if not ticks:           # before the call: it donates the stores
            ticks.append(jax.make_jaxpr(real, static_argnums=5)(
                params, staged, prev, pool, kv_mask, cfg))
        return real(params, staged, prev, pool, kv_mask, cfg)

    engine._tick_program = traced
    handle = engine.submit(serve.ServeRequest(
        input_ids=list(range(3, 12)),
        gen=families.GenerationConfig(max_new_tokens=3)))
    engine.drain(timeout_s=300)
    engine.shutdown()
    assert len(handle.result(timeout=1)) == 3
    (jaxpr,) = ticks
    assert _host_calls(jaxpr) == set()


# -- (c) the removed knob ---------------------------------------------------------

def test_timeline_block_is_refused_by_name(tmp_path):
    """Top-level config keys are not validated, so a `timeline:` block left
    in a config would be silently ignored: it is refused instead, on or
    off, with where the numbers are read now."""
    from llama_pipeline_parallel_tpu.train import run_training

    for block in ({"enabled": True, "window": 8}, {"enabled": False}, None):
        with pytest.raises(ValueError, match="timeline config block") as e:
            run_training({"output_dir": str(tmp_path / "run"),
                          "timeline": block})
        assert "tools/trace_summary.py" in str(e.value)
        assert "bubble_share.train" in str(e.value)
    assert not (tmp_path / "run").exists()     # refused before any work
