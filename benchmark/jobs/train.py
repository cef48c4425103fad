"""Job `train`: a training cell, through `train.py`'s own entry point.

`run_training` stops at `max_steps`, not at a time, so the entry point is
called twice in this process: a warm-up run that compiles (or hits the cache)
and gives a step time from the trainer's own logging boundaries, then the
measured run with `max_steps = W + N`. That second call is the one object the
check and the window share: its first steps are compared with the plain
reference, and its steps W+1 .. W+N are the window, between two logging
boundaries (the `device_step` spans the trainer wrote, which end where it
fetched the loss). Loader, prefetch, logging and the metrics writer all run.

What is compared (limits in the cell's file, `checks`): for each of the first
`follow_steps` optimizer steps the loss, the global gradient norm before
clipping, and by pipeline stage the norm of the layers' gradient and of the
update applied, read from the trainer's own `numerics.jsonl`; against the
reference's float32 AdamW over the same rows from the same seeded weights.
The reference runs after the trainer's state is freed.
"""

from __future__ import annotations

import gc
import json
import math
import os
import time

from benchmark import traffic, weights
from benchmark.harness import Check

TRAINER_SEED_MODULUS = 32749   # the trainer's loader needs seed * 131071 < 2**32


def _jsonl(path: str) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def trainer_config(ctx, output_dir: str, max_steps: int) -> dict:
    """The YAML a user would write for this cell, from its three files."""
    cell, mix, t = ctx.cell, ctx.cell.mix, ctx.cell.params["trainer"]
    model = dict(cell.llama_config_sizes(),
                 dtype=cell.config["compute_dtype"])
    rows_per_step = mix["rows_per_microbatch"] * mix["microbatches"]
    cfg = {
        "output_dir": output_dir,
        "seed": ctx.seed % TRAINER_SEED_MODULUS,
        "mesh": dict(t["mesh"]),
        "model": model,
        "dataset": {"_target_": "benchmark.traffic.SeededRows",
                    "seed": ctx.seed, "vocab_size": model["vocab_size"],
                    "seq_length": mix["seq_length"],
                    # fresh rows every step: no row repeats inside a run
                    "length": rows_per_step * t["dataset_steps"]},
        "collator": {"_target_": "llama_pipeline_parallel_tpu.data.collator."
                                 "PretokenizedCollator"},
        "data": {"log_sample_ids": True},
        "max_seq_length": mix["seq_length"],
        "per_device_train_batch_size": mix["rows_per_microbatch"],
        "gradient_accumulation_steps": mix["microbatches"],
        "max_steps": max_steps,
        "save_steps": 0, "save_final": False, "resume": False,
    }
    cfg.update(t["overrides"])
    return cfg


def _call_trainer(cfg: dict, path: str) -> None:
    import yaml

    from llama_pipeline_parallel_tpu.cli import main as trainer_main

    with open(path, "w") as f:
        yaml.safe_dump(cfg, f)
    trainer_main(["--config", path])
    gc.collect()


def _boundaries(output_dir: str) -> dict:
    """{step: time.time() at which the trainer had fetched that step's loss}."""
    return {s["step"]: s["ts"] + s["dur"]
            for s in _jsonl(os.path.join(output_dir, "spans.jsonl"))
            if s["name"] == "device_step"}


def _rel_gap(program, reference) -> float:
    """Worst |program - reference| / |reference| over paired numbers."""
    prog = program if isinstance(program, list) else [program]
    ref = reference if isinstance(reference, list) else [reference]
    if len(prog) != len(ref):
        return math.inf
    return max(abs(float(p) - r) / max(abs(r), 1e-30)
               for p, r in zip(prog, ref))


def reference_readings(ctx, step_ids: list, precision: str = "float32") -> list:
    """The plain reference over the same rows, from the same seeded weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark.reference import dense_decoder

    cell, mix, t = ctx.cell, ctx.cell.mix, ctx.cell.params["trainer"]
    model = cell.model
    mesh = Mesh(np.asarray(ctx.devices), ("x",))
    n_dev = len(ctx.devices)

    def shard(shape):
        # the reference's weights and optimizer state are split over the
        # cell's chips on the last axis that divides (it is one program of
        # plain jax.numpy either way; XLA places the collectives)
        for axis in range(len(shape) - 1, -1, -1):
            if n_dev > 1 and shape[axis] % n_dev == 0 and shape[axis] >= 1024:
                spec = [None] * len(shape)
                spec[axis] = "x"
                return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    shapes = weights.abstract(model)
    shardings = jax.tree.map(lambda s: shard(s.shape), shapes)
    params = weights.make_weights(ctx.seed % TRAINER_SEED_MODULUS, model,
                                  jnp.float32, shardings)
    rows_sharding = NamedSharding(mesh, P("x" if n_dev > 1 else None, None))
    step_rows = [jax.device_put(np.stack([
        traffic.row_ids(ctx.seed, i, model["vocab_size"], mix["seq_length"])
        for i in ids]), rows_sharding) for ids in step_ids]
    hp = {k: float(t["overrides"][k]) for k in (
        "learning_rate", "weight_decay", "adam_beta1", "adam_beta2",
        "adam_eps", "max_grad_norm", "total_steps")}
    return dense_decoder.follow_training(
        params, step_rows, model, hp, groups=t["mesh"]["pp"],
        precision=precision)


def compare(program: list, reference: list, limits: dict) -> list:
    """Checks of the program's first steps against the reference's."""
    checks = []
    for i, (prog, ref) in enumerate(zip(program, reference), start=1):
        checks.append(Check(f"loss_abs_gap.step{i}",
                            abs(prog["loss"] - ref["loss"]),
                            limits["loss_abs_gap"]))
        checks.append(Check(f"grad_norm_rel_gap.step{i}",
                            _rel_gap(prog["grad_norm"], ref["grad_norm"]),
                            limits["grad_norm_rel_gap"]))
        checks.append(Check(
            f"stage_grad_norm_rel_gap.step{i}",
            _rel_gap(prog["grad_norm_per_stage"], ref["grad_norm_per_stage"]),
            limits["stage_grad_norm_rel_gap"]))
        checks.append(Check(
            f"stage_update_norm_rel_gap.step{i}",
            _rel_gap(prog["update_norm_per_stage"],
                     ref["update_norm_per_stage"]),
            limits["stage_update_norm_rel_gap"]))
    return checks


def program_readings(output_dir: str, follow_steps: int) -> tuple:
    """(per-step readings from the trainer's numerics.jsonl, row ids by step
    from its sample ledger) for the first `follow_steps` steps."""
    by_step = {r["step"]: r for r in
               _jsonl(os.path.join(output_dir, "numerics.jsonl"))}
    readings = [by_step[s] for s in range(1, follow_steps + 1)]
    ledger = _jsonl(os.path.join(output_dir, "samples.jsonl"))
    ids = [row["indices"] for row in ledger
           if row["epoch"] == 0 and row["batch"] < follow_steps]
    return readings, ids


def run(ctx) -> dict:
    from benchmark import device

    cell, mix, t = ctx.cell, ctx.cell.mix, ctx.cell.params["trainer"]
    interval = int(t["overrides"]["logging_steps"])
    follow = int(t["follow_steps"])
    tokens_per_step = (mix["seq_length"] * mix["rows_per_microbatch"]
                       * mix["microbatches"] * t["mesh"].get("dp", 1))

    # 1. warm-up: compiles or hits the cache, and times a step
    warm_dir = os.path.join(ctx.run_dir, "warmup")
    _call_trainer(trainer_config(ctx, warm_dir, 2 * interval),
                  os.path.join(ctx.run_dir, "warmup.yaml"))
    b = _boundaries(warm_dir)
    step_time = (b[2 * interval] - b[interval]) / interval

    # 2. the measured run: W steps of set-up (the first `follow` of them are
    # what the reference follows), then N steps between logging boundaries
    w = interval * max(1, math.ceil(follow / interval))
    n = interval * max(1, round(ctx.seconds / step_time / interval))
    cfg = trainer_config(ctx, os.path.join(ctx.run_dir, "measured"), w + n)
    if ctx.trace:
        first = w + interval
        cfg["profile_steps"] = [first, min(first + t["trace_steps"], w + n)]
    _call_trainer(cfg, os.path.join(ctx.run_dir, "measured.yaml"))
    out_dir = cfg["output_dir"]
    b = _boundaries(out_dir)
    t0, t1 = b[w], b[w + n]
    metrics_rows = [r for r in _jsonl(os.path.join(out_dir, "metrics.jsonl"))
                    if "loss" in r and w < r.get("step", 0) <= w + n]
    finite_steps = interval * sum(1 for r in metrics_rows
                                  if math.isfinite(r["loss"]))
    tokens_per_s = n * tokens_per_step / (t1 - t0)
    spans = [s for s in _jsonl(os.path.join(out_dir, "spans.jsonl"))
             if t0 <= s["ts"] <= t1]
    print(f"train: step_time(warm-up)={step_time:.4f}s W={w} N={n} "
          f"window={t1 - t0:.3f}s tokens/step={tokens_per_step}", flush=True)

    # 3. the program's state is gone; read the peak, then run the reference
    memory_peak = device.memory_peak_bytes(ctx.devices)
    program, ids = program_readings(out_dir, follow)
    t_ref = time.time()
    reference = reference_readings(ctx, ids)
    print(f"train: reference followed {follow} steps in "
          f"{time.time() - t_ref:.1f}s (not in setup_s)", flush=True)
    for i, (p, r) in enumerate(zip(program, reference), start=1):
        print(f"train: step {i} program loss={p['loss']} grad_norm="
              f"{p['grad_norm']} | reference loss={r['loss']} grad_norm="
              f"{r['grad_norm']}", flush=True)
    checks = compare(program, reference, cell.params["checks"])
    checks.append(Check("window_steps_not_finite", float(n - finite_steps), 0.0))

    xplane_trace = None
    if ctx.trace:
        from benchmark import xplane

        path = xplane.find_xplane(os.path.join(out_dir, "profile"))
        xplane_trace = xplane.read(path) if path else None
    return {
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": t0 - ctx.t_start},
        "attempted": n, "failed": n - finite_steps,
        "checks": checks, "window": (t0, t1),
        "memory_peak_bytes": memory_peak,
        "observations": {
            "kind": "train", "cell": cell, "devices": ctx.devices,
            "tokens_per_s": tokens_per_s, "window": (t0, t1),
            "seq_length": mix["seq_length"], "spans": spans,
            "xplane": xplane_trace},
    }
