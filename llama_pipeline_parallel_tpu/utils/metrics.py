"""Observability: throughput/MFU accounting and the metrics writer.

Fills the reference's §5.1/§5.5 surface: rank-0 scalar logging of lr and
windowed mean loss every `logging_steps` (reference
trainer_base_ds_mp.py:360-374 to wandb) plus the per-step throughput DeepSpeed
printed via `steps_per_print` — extended with tokens/sec/chip and MFU, the
BASELINE.md north-star metrics the reference never measured.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any

from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# bf16 peak TFLOP/s per chip by TPU generation (public figures)
TPU_PEAK_FLOPS = {
    "v4": 275e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
}


def param_count(cfg: LlamaConfig) -> int:
    d, f, L, V = (cfg.hidden_size, cfg.intermediate_size,
                  cfg.num_hidden_layers, cfg.vocab_size)
    kv_dim = cfg.kv_heads * cfg.head_dim
    per_layer = d * d * 2 + d * kv_dim * 2 + 3 * d * f + 2 * d
    return V * d * 2 + L * per_layer + d


def train_flops_per_token(cfg: LlamaConfig, seq_length: int) -> float:
    """PaLM-style accounting: 6*N + 12*L*d*S per trained token (fwd+bwd,
    attention quadratic term included)."""
    return 6.0 * param_count(cfg) + 12.0 * cfg.num_hidden_layers * cfg.hidden_size * seq_length


_PEAK_FLOPS_LOGGED: set[str] = set()  # one verdict line per device kind


def detect_chip_peak_flops() -> float | None:
    """Peak bf16 FLOP/s for the local chip generation, or None (MFU off).

    The match verdict is logged once per device kind: before this, an
    unknown/CPU device made `mfu` silently vanish from the metrics line and
    the operator couldn't tell a meter bug from an unlisted chip."""
    import jax

    kind = jax.devices()[0].device_kind
    first_time = kind not in _PEAK_FLOPS_LOGGED
    _PEAK_FLOPS_LOGGED.add(kind)
    for key, flops in TPU_PEAK_FLOPS.items():
        if key in kind.lower():
            if first_time:
                logger.info("MFU accounting on: device_kind %r matched "
                            "TPU_PEAK_FLOPS[%r] = %.0f bf16 TFLOP/s/chip",
                            kind, key, flops / 1e12)
            return flops
    if first_time:
        logger.info("MFU disabled: device_kind %r matches no TPU_PEAK_FLOPS "
                    "entry (%s) — metrics lines will carry no `mfu` field; "
                    "add the chip's peak to utils/metrics.py to enable it",
                    kind, ", ".join(sorted(TPU_PEAK_FLOPS)))
    return None


def require_chip_peak_flops() -> float:
    """`detect_chip_peak_flops` for a measurement path: a device that is not
    in the table is an error, not a default (the trainer's meter merely
    omits `mfu` there; a benchmark number against a guessed peak is wrong)."""
    import jax

    peak = detect_chip_peak_flops()
    if peak is None:
        raise RuntimeError(
            f"unknown device_kind {jax.devices()[0].device_kind!r} (backend "
            f"{jax.default_backend()!r}): a measurement needs a chip listed "
            f"in utils/metrics.TPU_PEAK_FLOPS "
            f"({', '.join(sorted(TPU_PEAK_FLOPS))}) — there is no default "
            f"peak and no CPU fallback")
    return peak


@dataclasses.dataclass
class Throughput:
    """Rolling tokens/sec + MFU meter.

    `global_scale`: multiplier from the counts `update()` sees to the global
    batch. A pod host only observes its own dp shards' tokens while `n_chips`
    is the GLOBAL chip count — without the scale, tokens/sec and MFU
    under-report by the process count. The trainer passes
    dp_global / dp_local; real-token counts scale by the same factor (exact
    for the pad-free case, an even-padding approximation otherwise — an
    allgather per step just to meter would sync the hot loop)."""

    cfg: LlamaConfig
    seq_length: int
    n_chips: int
    peak_flops_per_chip: float | None = None
    global_scale: float = 1.0

    def __post_init__(self) -> None:
        self._t0 = time.perf_counter()
        self._tokens = 0
        self._real_tokens = 0
        if self.peak_flops_per_chip is None:
            self.peak_flops_per_chip = detect_chip_peak_flops()

    def update(self, tokens: int, real_tokens: int | None = None) -> None:
        """`tokens` = THIS host's batch positions (pad included — the compute
        actually spent, and what MFU is against). `real_tokens` = non-pad
        positions: the useful-throughput number, where sequence packing's win
        shows (a padded-to-512 baseline inflates tokens_per_sec with pad
        work)."""
        self._tokens += tokens
        self._real_tokens += tokens if real_tokens is None else real_tokens

    def read_and_reset(self) -> dict[str, float]:
        dt = max(time.perf_counter() - self._t0, 1e-9)
        tps = self._tokens * self.global_scale / dt
        out = {"tokens_per_sec": tps, "tokens_per_sec_per_chip": tps / self.n_chips}
        if self._real_tokens != self._tokens:
            out["real_tokens_per_sec"] = self._real_tokens * self.global_scale / dt
        if self.peak_flops_per_chip:
            flops = train_flops_per_token(self.cfg, self.seq_length) * tps
            out["mfu"] = flops / (self.peak_flops_per_chip * self.n_chips)
        self._t0 = time.perf_counter()
        self._tokens = 0
        self._real_tokens = 0
        return out


class NullMetricsWriter:
    """The sink for non-zero pod processes: the scalars are replicated across
    processes, so only process 0 writes (concurrent appenders would interleave
    duplicate lines into the shared metrics.jsonl, and per-process wandb inits
    would each register a run)."""

    def log(self, step: int, scalars: dict[str, Any]) -> None:
        pass

    def close(self) -> None:
        pass


class MetricsWriter:
    """Scalars -> stdout log + metrics.jsonl, plus wandb (`use_wandb`) and
    tensorboard (`use_tensorboard`) sinks when their packages are present.

    The thin interface SURVEY.md §5.5 calls for; replaces the reference's
    hardcoded wandb calls (trainer_base_ds_mp.py:441-447,373-374) and its
    absent `WandbWriter` helper."""

    def __init__(self, output_dir: str, config_snapshot: dict | None = None,
                 use_wandb: bool = False, use_tensorboard: bool = False,
                 project: str = "llama-pipeline-tpu",
                 summary_metrics: dict[str, str] | None = None):
        # wandb summary direction per metric (reference
        # trainer_base_ds_mp.py:447 `wandb.define_metric` driven by
        # prediction_cfg's metric/measure pair, conf yaml:108-112): the run
        # summary shows best-so-far, not last-logged. name -> "min"|"max".
        if summary_metrics is None:
            summary_metrics = {"loss": "min", "eval_loss": "min"}
        self._summary_metrics = summary_metrics
        os.makedirs(output_dir, exist_ok=True)
        self._f = open(os.path.join(output_dir, "metrics.jsonl"), "a", buffering=1)
        self._wandb = None
        self._tb = None
        if config_snapshot is not None:
            # run provenance: resolved config snapshot next to the checkpoints
            # (reference trainer_base_ds_mp.py:439 saves training_config.yaml)
            with open(os.path.join(output_dir, "training_config.json"), "w") as f:
                json.dump(config_snapshot, f, indent=2, default=str)
        if use_wandb:
            try:
                import wandb

                self._wandb = wandb.init(project=project, config=config_snapshot)
            except Exception as e:  # wandb not installed / offline
                logger.warning("wandb unavailable (%r); falling back to jsonl only", e)
            if self._wandb is not None:
                try:
                    for name, direction in self._summary_metrics.items():
                        wandb.define_metric(name, summary=direction)
                except Exception as e:  # run stays live; only best-so-far lost
                    logger.warning("wandb.define_metric failed (%r); summary "
                                   "shows last value, not best", e)
        if use_tensorboard:
            try:
                from torch.utils.tensorboard import SummaryWriter

                self._tb = SummaryWriter(os.path.join(output_dir, "tensorboard"))
            except Exception as e:
                logger.warning("tensorboard unavailable (%r); falling back to "
                               "jsonl only", e)

    def log(self, step: int, scalars: dict[str, Any]) -> None:
        record = {"step": step, **{k: _to_py(v) for k, v in scalars.items()}}
        self._f.write(json.dumps(record) + "\n")
        pretty = " ".join(f"{k}={record[k]:.5g}" if isinstance(record[k], float)
                          else f"{k}={record[k]}" for k in record)
        logger.info(pretty)
        if self._wandb is not None:
            self._wandb.log(scalars, step=step)
        if self._tb is not None:
            for k, v in record.items():
                if k != "step" and isinstance(v, (int, float)):
                    self._tb.add_scalar(k, v, global_step=step)

    def close(self) -> None:
        self._f.close()
        if self._wandb is not None:
            self._wandb.finish()
        if self._tb is not None:
            self._tb.close()


def _to_py(v: Any) -> Any:
    if hasattr(v, "item"):
        return v.item()
    return v
