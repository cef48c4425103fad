"""The jitted train step: pipeline grads + ZeRO-1-sharded optimizer update.

One call of the returned function does everything the reference's
`engine.train_batch(data_iter)` does (reference trainer_base_ds_mp.py:354):
runs `num_microbatches` microbatches through the pipeline (fwd+bwd), reduces
gradients across DP, clips, steps AdamW + LR schedule, and returns the mean
loss — except here it is one XLA program with no Python in the hot loop.

Gradient-accumulation contract across schedules: the pipeline hands this
module ONE fully-accumulated fp32 gradient tree per step, whatever the
schedule's internal unit decomposition — fused per-tick vjp grads (1f1b /
interleaved), AD-of-the-scan (gpipe), or the zb1 split backward, whose
W units fold their weight-grad outputs incrementally into the same fp32
accumulators during the W-drain phase in fused-identical unit order
(parallel/pipeline.py). Nothing downstream of `make_pipeline_loss_and_grad`
branches on the schedule, which is what lets one optimizer/numerics path
serve all four. The host-stash offload knobs (PipelineConfig.offload_wgrad
/ offload_activations, utils/host_stash.py) change only WHERE the
schedules' residual stores live (host DRAM vs HBM), never the gradient
values or fold order — so they too are invisible downstream, and offload
on/off stays bit-exact through this module's update unchanged.

ZeRO-1 (reference conf yaml `zero_optimization: stage 1` + reduce-scatter):
optimizer moments are sharded over the `dp` axis via GSPMD sharding
annotations — each dp replica owns a 1/dp slice of mu/nu, XLA inserts the
reduce-scatter/all-gather traffic around the (sharded) update. Params remain
dp-replicated fp32 masters.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llama_pipeline_parallel_tpu.models.llama import model as llama_model
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.parallel.mesh import AXIS_DP, AXIS_PP
from llama_pipeline_parallel_tpu.parallel.pipeline import (
    PipelineConfig,
    batch_specs,
    make_pipeline_loss_and_grad,
    stack_stages,
    stage_param_specs,
)

Params = dict


class TrainState(NamedTuple):
    step: jax.Array
    params: Params  # stage-stacked, fp32 master, dp-replicated
    opt_state: Any  # ZeRO-1: dp-sharded moments


# ---------------------------------------------------------------------------
# ZeRO-1 sharding-spec construction
# ---------------------------------------------------------------------------

def _zero1_leaf_spec(param_spec: P, shape: tuple[int, ...], dp_size: int) -> P:
    """Extend a param's spec with dp sharding on its rightmost free dim.

    Scans from the trailing (feature) dim backwards so tp-sharded weights
    (whose last dim already carries 'tp') still get their moments dp-sharded
    on another dim — otherwise a pp x tp x dp run would silently keep the
    column-parallel moments (most of the bytes) dp-replicated. Dim 0 is a
    valid fallback for NON-stacked leaves (embed/lm_head have no leading
    stage axis — without it the vocab-parallel lm_head [d, V/tp] moments,
    the largest non-stacked leaves, would stay fully dp-replicated); for
    stage-stacked layer leaves dim 0 carries 'pp' and is never touched.
    """
    if not shape or dp_size == 1:
        return param_spec
    spec = list(param_spec) + [None] * (len(shape) - len(param_spec))
    lowest_dim = 1 if spec[0] == AXIS_PP else 0
    for dim in range(len(shape) - 1, lowest_dim - 1, -1):
        if spec[dim] is None and shape[dim] % dp_size == 0:
            spec[dim] = AXIS_DP
            return P(*spec)
    return param_spec


def zero1_opt_state_specs(
    tx: optax.GradientTransformation,
    params: Params,
    param_specs: Params,
    dp_size: int,
) -> Any:
    """PartitionSpec tree for `tx.init(params)`.

    Moment leaves mirror param leaves (same tree paths under mu/nu), so specs
    are matched by path suffix; scalar state (step counts) is replicated.
    """
    flat_param_specs = {
        jax.tree_util.keystr(path): (spec, leaf.shape)
        for (path, spec), leaf in zip(
            jax.tree_util.tree_flatten_with_path(param_specs)[0],
            jax.tree.leaves(params),
        )
    }
    opt_shapes = jax.eval_shape(tx.init, params)

    def spec_for(path, leaf):
        ks = jax.tree_util.keystr(path)
        for pks, (pspec, pshape) in flat_param_specs.items():
            if ks.endswith(pks) and tuple(leaf.shape) == tuple(pshape):
                return _zero1_leaf_spec(pspec, leaf.shape, dp_size)
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, opt_shapes)


def specs_to_shardings(mesh: Mesh, specs: Any) -> Any:
    """PartitionSpec tree -> NamedSharding tree. The is_leaf guard is load-
    bearing (P is a tuple pytree; without it tree.map descends INTO each
    spec) — keep every caller on this helper instead of re-writing the map."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def zero2_param_specs(params_like: Params, mesh: Mesh) -> Params:
    """ZeRO-2-flavored spec tree for PARAMS/GRADS: every leaf additionally
    dp-sharded on its rightmost free dim (the same placement rule as the
    ZeRO-1 moments, `_zero1_leaf_spec`). The offload path uses it to keep
    fp32 masters + host moments + the reduce-scattered gradient outputs at
    1/dp per host — the reference's ZeRO-2 'reduce_scatter: True' story
    (reference conf yaml:152-159) taken to the host tier. Leaves no dim of
    which divides dp stay on their plain spec (replicated over dp)."""
    param_specs = stage_param_specs(params_like, tp=mesh.shape["tp"] > 1)
    dp = mesh.shape[AXIS_DP]
    return jax.tree.map(
        lambda leaf, spec: _zero1_leaf_spec(spec, leaf.shape, dp),
        params_like, param_specs)


def state_shardings(mesh: Mesh, tx: optax.GradientTransformation, params_like: Params
                    ) -> TrainState:
    """NamedSharding tree for the full TrainState."""
    param_specs = stage_param_specs(params_like, tp=mesh.shape["tp"] > 1)
    opt_specs = zero1_opt_state_specs(tx, params_like, param_specs, mesh.shape[AXIS_DP])
    to_sharding = lambda spec: NamedSharding(mesh, spec)
    return TrainState(
        step=to_sharding(P()),
        params=jax.tree.map(to_sharding, param_specs),
        opt_state=jax.tree.map(to_sharding, opt_specs,
                               is_leaf=lambda x: isinstance(x, P)),
    )


# ---------------------------------------------------------------------------
# State init / step
# ---------------------------------------------------------------------------

def init_params_sharded(
    rng: jax.Array,
    cfg: LlamaConfig,
    mesh: Mesh,
    manifest,
) -> Params:
    """Initialize params DIRECTLY into their mesh sharding: each device
    materializes only its stage/tp shard, never the full model.

    This is the analogue of the reference's `LayerSpec` deferred construction
    (models/llama_ds_mp_wrap.py:214-219, README.md:21-22 — avoiding the
    65B x world_size host-RAM blowup): under jit with out_shardings, XLA
    allocates every leaf sharded from the start.
    """

    def build(rng):
        return stack_stages(llama_model.init_params(rng, cfg), manifest)

    shapes = jax.eval_shape(build, rng)
    specs = stage_param_specs(shapes, tp=mesh.shape["tp"] > 1)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.jit(build, out_shardings=shardings)(rng)


def init_train_state(
    params_stacked: Params,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    donate_params: bool = False,
) -> TrainState:
    """Place params and freshly initialized optimizer state onto the mesh with
    ZeRO-1 shardings.

    `donate_params=True` consumes the caller's buffers (no copy) — use when
    the init output is not needed afterwards (a full fp32 param copy is real
    HBM at 65B scale). Default copies: a bare device_put can alias the
    caller's arrays when shardings are compatible, and the donated train step
    would then delete the caller's copies out from under it."""
    shardings = state_shardings(mesh, tx, params_stacked)
    params = jax.jit(lambda p: p, out_shardings=shardings.params,
                     donate_argnums=(0,) if donate_params else ())(params_stacked)
    opt_state = jax.jit(tx.init, out_shardings=shardings.opt_state)(params)
    step = jax.device_put(jnp.zeros((), jnp.int32), shardings.step)
    return TrainState(step=step, params=params, opt_state=opt_state)


def make_train_step(
    mesh: Mesh,
    cfg: LlamaConfig,
    pcfg: PipelineConfig,
    tx: optax.GradientTransformation,
    schedule: optax.Schedule,
    params_like: Params,
    attn_fn: Callable | None = None,
    collect_stats: bool = False,
    poison: bool = False,
) -> Callable[..., tuple[TrainState, dict]]:
    """Build the donated, fully-sharded jitted train step.

    `collect_stats` (the numerics observatory, utils/numerics.py) adds
    in-graph per-stage/per-layer-group statistics under `metrics["numerics"]`
    AND arms the nonfinite guard: when any gradient leaf is nonfinite, the
    parameter/optimizer update is `where`-skipped the same step (fp16
    loss-scaler skip semantics; the step counter still advances so the LR
    schedule stays aligned with the loop). Off (the default), the step is
    bit-identical to the pre-observatory one.

    `poison` (chaos only — the `grad_nonfinite` fault op) extends the jitted
    signature with a third `poison_stage` scalar that multiplies one stage's
    layer gradients by +inf (-1 = no-op). Steady-state runs never pass it,
    so the per-step host->device traffic is unchanged.
    """
    from llama_pipeline_parallel_tpu.ops.attention import attention
    from llama_pipeline_parallel_tpu.utils import numerics, trace

    loss_grad_fn = make_pipeline_loss_and_grad(
        mesh, cfg, pcfg, params_like, attn_fn=attn_fn or attention,
        collect_stats=collect_stats)
    shardings = state_shardings(mesh, tx, params_like)

    def _step(state: TrainState, batch: dict, poison_stage
              ) -> tuple[TrainState, dict]:
        if collect_stats:
            loss, grads, act_stats = loss_grad_fn(state.params, batch)
        else:
            loss, grads = loss_grad_fn(state.params, batch)
        if poison_stage is not None:
            grads = numerics.poison_grads(grads, poison_stage)
        # `tx` names its own clip and AdamW (optim/optimizer.py); the norm
        # for the metrics line is the clip's, which XLA computes once
        updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
        with jax.named_scope(trace.SCOPE_OPTIMIZER):
            new_params = optax.apply_updates(state.params, updates)
        with jax.named_scope(trace.SCOPE_GRAD_CLIP):
            grad_norm = optax.global_norm(grads)
        metrics = {
            "loss": loss,
            "grad_norm": grad_norm,
            "lr": schedule(state.step),
            "step": state.step + 1,
        }
        if collect_stats:
            with jax.named_scope(trace.SCOPE_NUMERICS):
                stats = numerics.step_stats(
                    state.params, grads, updates,
                    virtual_stages=pcfg.virtual_stages)
            stats.update(act_stats)
            # replicate the stat vectors (a few hundred floats): the host
            # monitor reads them with np.asarray, which on a pod requires
            # every process to hold the full value — without this the
            # pp-sharded [S] outputs are not fully addressable off-host
            stats = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, P())), stats)
            # nonfinite guard: keep the old params/opt-state when any grad
            # leaf is nonfinite — the skip happens in-graph, the same step
            # (named as the update it guards: XLA fuses each leaf's AdamW
            # into this select, and a fusion carries its root's name)
            finite = ~stats["nonfinite"]
            with jax.named_scope(trace.SCOPE_OPTIMIZER):
                new_params = jax.tree.map(
                    lambda new, old: jnp.where(finite, new, old),
                    new_params, state.params)
                new_opt_state = jax.tree.map(
                    lambda new, old: jnp.where(finite, new, old),
                    new_opt_state, state.opt_state)
            metrics["numerics"] = stats
        return TrainState(state.step + 1, new_params, new_opt_state), metrics

    batch_shardings = {k: NamedSharding(mesh, s)
                       for k, s in batch_specs(mesh).items()}
    # `train_step` is the name the compiled module carries (`jit_train_step`
    # in a trace and in the compile cache), whatever wraps it here
    if poison:
        def train_step(state, batch, poison_stage):
            return _step(state, batch, poison_stage)

        in_shardings = (shardings, batch_shardings, None)
    else:
        def train_step(state, batch):
            return _step(state, batch, None)

        in_shardings = (shardings, batch_shardings)
    return jax.jit(
        train_step,
        in_shardings=in_shardings,
        out_shardings=(shardings, None),
        donate_argnums=(0,),
    )
