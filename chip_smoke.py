#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

One process drives every visible TPU chip through the entry points a user
calls, at LLaMA-7B width (depth cut only, weights random from a seed), and
checks what comes out. It refuses to run on anything but a TPU, catches
nothing, and prints as its last stdout line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Phases (any failed gate raises, so the exit code is nonzero):

A  one chip: `python train.py --config conf/llama_7b_smoke.yaml` (sharded
   init, synthetic loader, attention=auto, 1f1b train step, metrics line,
   final checkpoint), then a restore of that checkpoint into a fresh
   sharded state and a save -> restore round trip that must be bit-equal;
   no checkpoint file may outgrow the manager's cap (hosts limit file size).
B  four chips (when >= 4 are visible): the same entry point at pp=4 and at
   pp=2 x dp=2; step-1 loss against a one-device run of the same seed,
   depth and global batch, and parameter bytes on every chip.
C  the flash-attention kernel compiled by Mosaic (never interpreted):
   unpacked and packed, forward and gradients, 32 heads x 128, seq 512 and
   2048, against ops/attention.py in float32; and `kernels.ce: pallas`
   through the trainer. (`kernels.prologue: pallas` is refused at config
   time at this width — PERF.md "Bring-up".)
D  serving from phase A's checkpoint, built the way tools/serve.py builds
   it (paged KV): a handful of requests over two prompt buckets, and one
   prompt's prefill logits against models/llama/model.py `forward` in
   float32, with the plain bf16 forward as the noise scale.

Heavy files (checkpoints) live under .chip_smoke/ and are removed at exit;
small records go to chiprun_out/chip_smoke/.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import resource
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, ".chip_smoke")
RECORDS = os.path.join(REPO, "chiprun_out", "chip_smoke")
CONF_A = os.path.join(REPO, "conf", "llama_7b_smoke.yaml")
CONF_B = (os.path.join(REPO, "conf", "llama_7b_smoke_pp4.yaml"),
          os.path.join(REPO, "conf", "llama_7b_smoke_pp2_dp2.yaml"))

# Tolerances, each with its reason.
#
# Step-1 loss of a seeded random init. Logits are N(0, s^2) with
# s = 0.02 * sqrt(hidden) (unit-RMS normed hidden x the 0.02-std head), so
# E[loss] = ln V + s^2 / 2; the band covers the sampling noise of 4096
# tokens and bf16 rounding, and excludes an untrained-but-broken model
# (e.g. a head read in the wrong layout shifts s).
INIT_LOSS_BAND = 0.1
# Four chips vs one device, same seed / depth / global batch: every row goes
# through the same bf16 arithmetic in either layout; only the fp32 order of
# the token-mean differs, and `attention: auto` may time its way to the
# flash kernel where the one-device reference uses the exact op (2.5e-3
# relative on attention outputs, ~1e-5 on a 4096-token mean). Measured on a
# v5e 2x2: 3.1e-7 (pp=4) and 3.0e-8 (pp=2 x dp=2). A stage fed the wrong
# microbatch decorrelates hidden states from targets and moves the mean by
# ~1.3 / sqrt(4096) = 2e-2, forty times the gate.
LAYOUT_LOSS_TOL = 5e-4
# Flash kernel (bf16 in and out) vs ops/attention.py in float32 at "highest"
# matmul precision: relative Frobenius error of the output and of dq/dk/dv.
# One bf16 rounding is 2^-9 / sqrt(3) = 1.1e-3 RMS and a v5e MXU pass
# rounds fp32 operands (the scaled q, the probabilities) to bf16 too; on the
# chip the kernel measures 2.3e-3..3.4e-3 and XLA's own bf16 attention
# 2.6e-3..3.8e-3 against the same reference (PERF.md "Bring-up"). The gate
# sits 1.8x above the worst measured value; a wrong mask or a dropped tile
# costs O(1).
FLASH_REL_TOL = 6e-3
# Prefill logits through the cached serving path (bf16) vs the float32
# forward, relative Frobenius error over the vocabulary. The plain bf16
# forward on the same parameters sets the scale: that is what bf16
# activations cost on this checkpoint, whatever it has learned. The cached
# path may be at most twice as far from float32 as the plain forward is
# (same arithmetic, other fusion order), and never further than the cap.
SERVE_NOISE_FACTOR = 2.0
SERVE_REL_CAP = 5e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke gate failed: {what}")


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# ---------------------------------------------------------------------------
# The trainer, through its normal entry point
# ---------------------------------------------------------------------------

def run_trainer(config: str, out: str, overrides: tuple = ()) -> dict:
    """`python train.py --config <config> output_dir=<out> ...` in this
    process; returns the per-step metrics rows and the compile seconds."""
    from llama_pipeline_parallel_tpu import cli
    from llama_pipeline_parallel_tpu.utils.perf import read_jsonl

    t0 = time.time()
    cli.main(["--config", config, f"output_dir={out}", *overrides])
    wall = time.time() - t0
    rows = [r for r in read_jsonl(os.path.join(out, "metrics.jsonl"))
            if "loss" in r]
    spans = read_jsonl(os.path.join(out, "spans.jsonl"))
    compile_s = sum(s["dur"] for s in spans if s["name"] == "compile_block")
    steps = [r["step_time"] for r in rows[1:]]
    gc.collect()  # the run's device state must be gone before the next phase
    return {"rows": rows, "wall_s": round(wall, 1),
            "compile_s": round(compile_s, 1),
            "step_s_median": round(sorted(steps)[len(steps) // 2], 4)}


def resolve(config: str, overrides: tuple = ()) -> tuple:
    """(cfg dict, model config, mesh config) exactly as the trainer reads
    them."""
    from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig
    from llama_pipeline_parallel_tpu.train import build_model_config
    from llama_pipeline_parallel_tpu.utils.config import load_config

    cfg = load_config(config, list(overrides))
    return cfg, build_model_config(cfg["model"]), MeshConfig(**cfg["mesh"])


def gate_training(run: dict, model_cfg, min_steps: int = 6) -> None:
    rows = run["rows"]
    check(len(rows) >= min_steps, f"{len(rows)} metrics rows < {min_steps}")
    for r in rows:
        check(math.isfinite(r["loss"]) and math.isfinite(r["grad_norm"]),
              f"step {r['step']}: loss {r['loss']} grad_norm {r['grad_norm']}")
    expected = (math.log(model_cfg.vocab_size)
                + 0.5 * 0.02 ** 2 * model_cfg.hidden_size)
    check(abs(rows[0]["loss"] - expected) < INIT_LOSS_BAND,
          f"step-1 loss {rows[0]['loss']:.4f} outside {expected:.3f} "
          f"+- {INIT_LOSS_BAND}")
    check(rows[-1]["loss"] < rows[0]["loss"],
          f"loss did not fall: {rows[0]['loss']:.4f} -> {rows[-1]['loss']:.4f}")


def gate_device_metrics(run: dict) -> None:
    """The metrics line carries `mfu` (the device_kind was recognised) and a
    device-sourced `device_peak_bytes`."""
    from llama_pipeline_parallel_tpu.utils import memwatch

    last = run["rows"][-1]
    check(last.get("mfu", 0) > 0, f"no mfu on the metrics line: {last}")
    _, source = memwatch.device_peak_bytes()
    check(source == "device" and last.get("device_peak_bytes"),
          f"device_peak_bytes source {source!r}, value "
          f"{last.get('device_peak_bytes')}")


def gate_checkpoint_files(step_dir: str) -> dict:
    """No file of the checkpoint is larger than a host with a per-file size
    limit accepts: the manager caps Orbax's data files (the first driver-side
    run of this script died with EFBIG on a default-sized one)."""
    from llama_pipeline_parallel_tpu.ckpt.checkpoint import (
        DATA_FILE_TARGET_BYTES,
    )

    sizes = [os.path.getsize(os.path.join(d, name))
             for d, _, names in os.walk(step_dir) for name in names]
    check(max(sizes) < 2 * DATA_FILE_TARGET_BYTES,
          f"checkpoint file of {max(sizes)} B >= 2 x {DATA_FILE_TARGET_BYTES}")
    return {"count": len(sizes), "total_bytes": sum(sizes),
            "largest_bytes": max(sizes)}


def phase_a(config: str = CONF_A, overrides: tuple = ()) -> dict:
    import jax
    import jax.numpy as jnp

    from llama_pipeline_parallel_tpu.ckpt.checkpoint import CheckpointManager
    from llama_pipeline_parallel_tpu.parallel import train_step as ts
    from llama_pipeline_parallel_tpu.parallel.mesh import make_mesh
    from llama_pipeline_parallel_tpu.train import build_manifest

    out = os.path.join(WORK, "phase_a")
    cfg, model_cfg, mesh_cfg = resolve(config, overrides)
    run = run_trainer(config, out, overrides)
    gate_training(run, model_cfg)
    gate_device_metrics(run)

    # restore the trainer's checkpoint into a FRESH sharded state (another
    # seed, so equality cannot come from the init) ...
    mesh = make_mesh(mesh_cfg)
    manifest = build_manifest(cfg, model_cfg, mesh_cfg.pp)
    fresh = ts.init_params_sharded(jax.random.PRNGKey(cfg["seed"] + 1),
                                   model_cfg, mesh, manifest)
    mgr = CheckpointManager(out)
    step = mgr.latest_step()
    check(step == run["rows"][-1]["step"], f"latest checkpoint {step}")
    restored = mgr.load_params(step, fresh, manifest)
    restored = jax.device_put(restored,
                              jax.tree.map(lambda x: x.sharding, fresh))
    differs = any(bool(jnp.any(a != b)) for a, b in
                  zip(jax.tree.leaves(restored), jax.tree.leaves(fresh)))
    check(differs, "restored params equal the fresh init")
    del fresh
    # ... then save -> restore again: every leaf must come back bit-equal
    mgr2 = CheckpointManager(os.path.join(WORK, "phase_a_roundtrip"))
    mgr2.save(step, restored, manifest, model_cfg)
    again = mgr2.load_params(step, restored, manifest)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(restored)[0],
                            jax.tree.leaves(again)):
        check(a.dtype == b.dtype and bool(jnp.array_equal(a, b)),
              f"save->restore changed {jax.tree_util.keystr(path)}")
    del restored, again
    gc.collect()
    shutil.rmtree(mgr2.root)  # 2.7 GB the later phases have no use for
    run["checkpoint_files"] = gate_checkpoint_files(mgr.step_dir(step))
    run["checkpoint_step"] = step
    run["checkpoint_dir"] = out
    return run


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------

def one_device_loss(cfg: dict, model_cfg, mesh_cfg) -> float:
    """Step-1 loss of the same seed, depth and global batch on ONE device
    (the pipeline's pp=1 loss path). Forward only: the 8-layer state with
    optimizer moments does not fit one chip, its fp32 parameters do."""
    import jax

    from llama_pipeline_parallel_tpu.data.loader import DataLoader
    from llama_pipeline_parallel_tpu.models.llama.manifest import StageManifest
    from llama_pipeline_parallel_tpu.parallel import pipeline as pl
    from llama_pipeline_parallel_tpu.parallel import train_step as ts
    from llama_pipeline_parallel_tpu.parallel.distributed import (
        form_global_batch,
    )
    from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig, make_mesh
    from llama_pipeline_parallel_tpu.train import build_dataset_and_collator

    mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
    manifest = StageManifest.for_config(model_cfg, 1)
    params = ts.init_params_sharded(jax.random.PRNGKey(cfg["seed"]),
                                    model_cfg, mesh, manifest)
    microbatches = cfg["gradient_accumulation_steps"] * mesh_cfg.dp
    dataset, collator = build_dataset_and_collator(cfg, model_cfg)
    loader = DataLoader(dataset, collator,
                        per_replica_batch=(cfg["per_device_train_batch_size"]
                                           * microbatches),
                        dp_size=1, seed=cfg["seed"])
    batch = form_global_batch(mesh, next(iter(loader)))
    pcfg = pl.PipelineConfig(num_stages=1, num_microbatches=microbatches)
    eval_fn = jax.jit(pl.make_pipeline_eval_fn(mesh, model_cfg, pcfg, params))
    loss_sum, count = eval_fn(params, batch)
    return float(loss_sum) / int(count)


def gate_stage_placement(cfg: dict, model_cfg, mesh_cfg, out: str) -> list:
    """Every chip holds its stage's parameters: the layer leaves' shardings
    give each pipeline stage its own device set, and the trainer's own
    per-device `bytes_in_use` samples (memory.jsonl, taken while the state
    is live) are at least the fp32 parameter bytes placed there."""
    import jax

    from llama_pipeline_parallel_tpu.models.llama import model as llama
    from llama_pipeline_parallel_tpu.parallel import pipeline as pl
    from llama_pipeline_parallel_tpu.parallel import train_step as ts
    from llama_pipeline_parallel_tpu.parallel.mesh import make_mesh
    from llama_pipeline_parallel_tpu.train import build_manifest
    from llama_pipeline_parallel_tpu.utils.perf import read_jsonl

    mesh = make_mesh(mesh_cfg)
    manifest = build_manifest(cfg, model_cfg, mesh_cfg.pp)
    shapes = jax.eval_shape(
        lambda: pl.stack_stages(
            llama.init_params(jax.random.PRNGKey(0), model_cfg), manifest))
    shardings = ts.specs_to_shardings(
        mesh, pl.stage_param_specs(shapes, tp=mesh_cfg.tp > 1))

    wq = shapes["layers"]["attn"]["wq"]
    stage_devices: dict = {}
    for dev, index in shardings["layers"]["attn"]["wq"].devices_indices_map(
            wq.shape).items():
        stage_devices.setdefault(index[0].start or 0, set()).add(dev.id)
    groups = list(stage_devices.values())
    check(len(groups) == mesh_cfg.pp
          and all(len(g) == mesh.devices.size // mesh_cfg.pp for g in groups)
          and len(set().union(*groups)) == mesh.devices.size,
          f"stage device sets are not distinct: {stage_devices}")

    param_bytes = {d.id: 0 for d in mesh.devices.ravel()}
    for leaf, sharding in zip(jax.tree.leaves(shapes),
                              jax.tree.leaves(shardings)):
        shard = math.prod(sharding.shard_shape(leaf.shape)) * leaf.dtype.itemsize
        for dev in sharding.device_set:
            param_bytes[dev.id] += shard
    samples = [r for r in read_jsonl(os.path.join(out, "memory.jsonl"))
               if r.get("kind") == "sample"]
    check(bool(samples), "no memory.jsonl sample rows")
    in_use = samples[-1]["device_bytes_in_use_each"]
    local_ids = [d.id for d in jax.local_devices()]
    for dev_id, expected in param_bytes.items():
        held = in_use[local_ids.index(dev_id)]
        check(held >= expected,
              f"device {dev_id} holds {held} B < its {expected} B of "
              f"parameters (all devices: {in_use})")
    return in_use


def phase_b(configs: tuple = CONF_B, overrides: tuple = ()) -> dict:
    report = {}
    for config in configs:
        name = os.path.splitext(os.path.basename(config))[0]
        out = os.path.join(WORK, name)
        cfg, model_cfg, mesh_cfg = resolve(config, overrides)
        reference = one_device_loss(cfg, model_cfg, mesh_cfg)
        gc.collect()
        run = run_trainer(config, out, overrides)
        gate_training(run, model_cfg)
        delta = abs(run["rows"][0]["loss"] - reference)
        check(delta < LAYOUT_LOSS_TOL,
              f"{name}: step-1 loss {run['rows'][0]['loss']:.5f} vs "
              f"one-device {reference:.5f} (|d|={delta:.2e} >= "
              f"{LAYOUT_LOSS_TOL})")
        run["one_device_loss"] = reference
        run["loss_delta"] = delta
        run["device_bytes_in_use"] = gate_stage_placement(
            cfg, model_cfg, mesh_cfg, out)
        log(f"B {name}: loss {run['rows'][0]['loss']:.5f} vs one-device "
            f"{reference:.5f} (|d|={delta:.2e}), bytes in use per chip "
            f"{run['device_bytes_in_use']}")
        report[name] = run
    return report


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

def rel_err(got, want) -> float:
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def flash_case(seq: int, packed: bool, heads: int, head_dim: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llama_pipeline_parallel_tpu.ops.attention import attention
    from llama_pipeline_parallel_tpu.ops.flash_attention import flash_attention
    from llama_pipeline_parallel_tpu.train import _measure_segments

    rng = np.random.RandomState(seq + packed)
    shape = (1, seq, heads, head_dim)
    q, k, v, w = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                  for _ in range(4))
    mask = _measure_segments(1, seq) if packed else None
    if packed:  # pad rows: outputs are unspecified, so give them no weight
        w = w * (mask != 0)[:, :, None, None].astype(w.dtype)

    def fwd_and_grads(fn):
        loss = lambda q, k, v: (fn(q, k, v, mask, causal=True)
                                .astype(jnp.float32)
                                * w.astype(jnp.float32)).sum()
        return jax.jit(lambda q, k, v: (
            fn(q, k, v, mask, causal=True),
            jax.grad(loss, argnums=(0, 1, 2))(q, k, v)))

    flash = fwd_and_grads(flash_attention)
    check("tpu_custom_call" in flash.lower(q, k, v).as_text(),
          "the flash kernel did not lower to a Mosaic custom call")
    t0 = time.time()
    out, grads = jax.block_until_ready(flash(q, k, v))
    compile_s = time.time() - t0
    with jax.default_matmul_precision("highest"):
        ref_out, ref_grads = fwd_and_grads(attention)(
            *(x.astype(jnp.float32) for x in (q, k, v)))
    keep = (mask != 0)[:, :, None, None] if packed else True
    errs = {"out": rel_err(jnp.where(keep, out, 0), jnp.where(keep, ref_out, 0)),
            **{name: rel_err(g, r) for name, g, r in
               zip(("dq", "dk", "dv"), grads, ref_grads)}}
    for name, err in errs.items():
        check(math.isfinite(err) and err < FLASH_REL_TOL,
              f"flash seq={seq} packed={packed}: {name} rel err {err:.2e} "
              f">= {FLASH_REL_TOL}")
    return {"seq": seq, "packed": packed, "compile_s": round(compile_s, 1),
            **{k: float(f"{e:.2e}") for k, e in errs.items()}}


def phase_c(heads: int = 32, head_dim: int = 128,
            seqs: tuple = (512, 2048), config: str = CONF_A,
            overrides: tuple = ()) -> dict:
    report = {"flash": [flash_case(seq, packed, heads, head_dim)
                        for seq in seqs for packed in (False, True)]}
    for case in report["flash"]:
        log(f"C flash {case}")
    # kernels.ce through its config key: 128-wide vocab tiles (the [hidden,
    # V/chunks] weight block must fit VMEM; the XLA twin's default is 1)
    _, model_cfg, _ = resolve(config, overrides)
    ce = run_trainer(config, os.path.join(WORK, "phase_c_ce"),
                     ("kernels.ce=pallas",
                      f"loss_vocab_chunks={model_cfg.vocab_size // 128}",
                      "max_steps=3", "save_final=false", *overrides))
    gate_training(ce, model_cfg, min_steps=3)
    log(f"C kernels.ce=pallas: losses "
        f"{[round(r['loss'], 4) for r in ce['rows']]}, compile "
        f"{ce['compile_s']}s")
    report["ce"] = ce
    return report


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def phase_d(checkpoint_dir: str, buckets: tuple = (64, 128),
            page_size: int = 64) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llama_pipeline_parallel_tpu.ckpt.checkpoint import (
        load_module_checkpoint,
    )
    from llama_pipeline_parallel_tpu.models.llama import decode
    from llama_pipeline_parallel_tpu.models.llama import model as llama
    from llama_pipeline_parallel_tpu.serve import (
        ServeConfig,
        ServeEngine,
        ServeRequest,
    )

    params, cfg, _, step = load_module_checkpoint(checkpoint_dir)
    new_tokens = 8
    lengths = [buckets[0] // 2, buckets[0], buckets[0] + 1, buckets[1] - 3,
               buckets[1], 5]
    max_len = max(buckets) + page_size
    # four slots for six requests (two wait in the queue); the pool covers
    # every request's worst-case reservation, so none may be refused
    engine = ServeEngine(params, cfg, ServeConfig(
        max_slots=4, max_len=max_len, prompt_buckets=buckets,
        page_size=page_size, num_pages=len(lengths) * max_len // page_size))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(3, cfg.vocab_size, size=n).tolist()
               for n in lengths]
    t0 = time.time()
    handles = [engine.submit(ServeRequest(
        input_ids=p, gen=decode.GenerationConfig(max_new_tokens=new_tokens),
        seed=i)) for i, p in enumerate(prompts)]
    engine.drain(timeout_s=900)
    wall = time.time() - t0
    outputs = [h.result(timeout=1) for h in handles]
    snap = engine.metrics_snapshot()
    engine.shutdown()
    stats = engine.stats
    check(stats.completed == len(prompts) and not (
        stats.rejected or stats.failed or stats.page_refused
        or stats.abandoned),
        f"serving: completed {stats.completed}/{len(prompts)}, rejected "
        f"{stats.rejected}, failed {stats.failed}, page_refused "
        f"{stats.page_refused}")
    for tokens in outputs:
        check(len(tokens) == new_tokens
              and all(0 <= t < cfg.vocab_size for t in tokens),
              f"serving: bad token ids {tokens}")

    # one prompt's prefill logits (the cached path the engine admits with)
    # against the plain forward in float32
    bucket, prompt = buckets[1], prompts[3]
    pad = bucket - len(prompt)
    ids = np.zeros((1, bucket), np.int32)
    ids[0, pad:] = prompt
    mask = np.zeros((1, bucket), np.int32)
    mask[0, pad:] = 1
    positions = np.clip(np.cumsum(mask, axis=1) - 1, 0, None).astype(np.int32)
    args = (jnp.asarray(ids), jnp.asarray(mask), jnp.asarray(positions))
    got = decode.prefill_prompt(params, args[0], args[1], cfg,
                                bucket)["logits"][0]
    plain = llama.forward(params, *args, cfg=cfg)[0, -1]
    with jax.default_matmul_precision("highest"):
        want = llama.forward(
            params, *args,
            cfg=dataclasses.replace(cfg, dtype=jnp.float32))[0, -1]
    check(got.shape == (cfg.vocab_size,) and bool(jnp.all(jnp.isfinite(got))),
          f"prefill logits shape {got.shape} / non-finite")
    err, noise = rel_err(got, want), rel_err(plain, want)
    log(f"D served {stats.completed} requests ({stats.tokens_generated} "
        f"tokens, buckets {buckets}) from checkpoint step {step} in "
        f"{wall:.1f}s incl. compiles; prefill logits rel err vs float32 "
        f"{err:.2e} (plain bf16 forward {noise:.2e})")
    check(err < SERVE_REL_CAP and err <= SERVE_NOISE_FACTOR * noise,
          f"prefill logits vs float32 forward: rel err {err:.2e} (cap "
          f"{SERVE_REL_CAP}; plain bf16 forward {noise:.2e} x "
          f"{SERVE_NOISE_FACTOR})")
    return {"checkpoint_step": step, "completed": stats.completed,
            "tokens_generated": stats.tokens_generated,
            "wall_s": round(wall, 1), "prefill_logit_rel_err": err,
            "plain_bf16_rel_err": noise,
            "pages_total": snap.get("pages_total")}


# ---------------------------------------------------------------------------

def main() -> int:
    t0 = time.time()
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's default backend here is "
              f"{backend!r} ({jax.devices()[0].device_kind}). Refusing to "
              f"run.", file=sys.stderr)
        return 2
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"device {json.dumps(device)}")
    # what the host lets this process write (-1 = no per-file limit)
    log(f"host: file-size limit {resource.getrlimit(resource.RLIMIT_FSIZE)[0]}"
        f" B, {shutil.disk_usage(REPO).free >> 30} GiB free under {REPO}")

    from llama_pipeline_parallel_tpu.utils import compile_cache

    cache_dir = compile_cache.setup()
    entries_before = compile_cache.entry_count(cache_dir)
    log(f"compile cache {cache_dir} ({entries_before} entries at start)")

    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(RECORDS, exist_ok=True)
    report: dict = {"device": device}
    try:
        a = report["A"] = phase_a()
        log(f"A losses {[round(r['loss'], 4) for r in a['rows']]}, compile "
            f"{a['compile_s']}s, median step {a['step_s_median']}s, mfu "
            f"{a['rows'][-1]['mfu']:.3f}, device peak "
            f"{a['rows'][-1]['device_peak_bytes']} B; checkpoint "
            f"{a['checkpoint_files']}")
        if len(devices) >= 4:
            report["B"] = phase_b()
        else:
            log(f"B skipped: {len(devices)} chip(s) visible, needs 4")
        report["C"] = phase_c()
        report["D"] = phase_d(a["checkpoint_dir"])
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    entries = compile_cache.entry_count(cache_dir)
    report["compile_cache"] = {"dir": cache_dir, "entries": entries,
                               "entries_at_start": entries_before}
    report["wall_s"] = round(time.time() - t0, 1)
    with open(os.path.join(RECORDS, "report.json"), "w") as f:
        json.dump(report, f, indent=1, default=str)
    log(f"compile cache {cache_dir}: {entries} entries "
        f"({entries - entries_before} new); total {report['wall_s']}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
