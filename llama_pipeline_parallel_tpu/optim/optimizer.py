"""Optimizer + LR schedule.

Rebuilds the engine-side optimizer surface the reference configures through
its DeepSpeed config dict (reference conf yaml:119-136): AdamW with weight
decay/betas/eps, global-norm gradient clipping, and a WarmupDecayLR schedule
whose total/warmup step counts are injected at runtime by the trainer
(reference trainer_base_ds_mp.py:263-275).

Precision model: params are fp32 master weights (cast to bf16 at use inside
the forward — see models/llama/model.py), gradients arrive fp32, and the
optimizer steps in fp32.  This replaces the reference's fp16 loss-scaling
state machine (conf yaml:137-143) entirely: bf16 on TPU needs no loss scale.
"""

from __future__ import annotations

import dataclasses

import jax
import optax

from llama_pipeline_parallel_tpu.utils import trace


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Hyperparameters of record (reference conf yaml:77-86,122-136)."""

    learning_rate: float = 1e-6
    weight_decay: float = 0.001
    beta1: float = 0.9
    beta2: float = 0.99
    eps: float = 1e-8
    max_grad_norm: float = 5.0
    total_steps: int = 1000
    warmup_steps: int = 50


def warmup_decay_schedule(peak_lr: float, total_steps: int, warmup_steps: int
                          ) -> optax.Schedule:
    """Linear warmup to peak, then linear decay to 0 at total_steps — the
    behavior of DeepSpeed's WarmupDecayLR the reference selects
    (conf yaml:129-135)."""
    if warmup_steps >= total_steps:
        raise ValueError(f"warmup_steps ({warmup_steps}) must be < total_steps ({total_steps})")
    return optax.join_schedules(
        [
            optax.linear_schedule(0.0, peak_lr, max(warmup_steps, 1)),
            optax.linear_schedule(peak_lr, 0.0, total_steps - warmup_steps),
        ],
        boundaries=[warmup_steps],
    )


def _scoped(name: str, tx: optax.GradientTransformation
            ) -> optax.GradientTransformation:
    """`tx` with its update's operations named `name` in a device trace
    (utils/trace.py scope vocabulary); state and values are `tx`'s own."""
    def update(updates, state, params=None):
        with jax.named_scope(name):
            return tx.update(updates, state, params)

    return optax.GradientTransformation(tx.init, update)


def make_optimizer(cfg: OptimizerConfig) -> tuple[optax.GradientTransformation, optax.Schedule]:
    """AdamW + clip + schedule. Returns (transform, schedule) — the schedule is
    also returned standalone so the trainer can log lr (the reference queries
    `scheduler.get_lr()[0]`, trainer_base_ds_mp.py:362)."""
    schedule = warmup_decay_schedule(cfg.learning_rate, cfg.total_steps, cfg.warmup_steps)
    tx = optax.chain(
        _scoped(trace.SCOPE_GRAD_CLIP,
                optax.clip_by_global_norm(cfg.max_grad_norm)),
        _scoped(trace.SCOPE_OPTIMIZER, optax.adamw(
            learning_rate=schedule,
            b1=cfg.beta1,
            b2=cfg.beta2,
            eps=cfg.eps,
            weight_decay=cfg.weight_decay,
        )),
    )
    return tx, schedule
