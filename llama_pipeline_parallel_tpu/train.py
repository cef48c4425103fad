"""The trainer: config -> mesh -> model -> data -> jitted step loop.

Re-implements the reference's `main()` + `train()` orchestration
(reference trainer_base_ds_mp.py:124-459) on the TPU-native stack:

- runtime schedule-total injection (reference :263-275): t_total is computed
  from dataset length x epochs unless `max_steps` is given;
- warm start from a converted checkpoint via `model_name_or_path`
  (reference :284 `load_module_only=True`);
- resume detection from `checkpoint-N` dirs (reference :451-455); the
  reference's dataloader fast-forward replay (:345-351) is replaced by O(1)
  repositioning from the checkpoint's data_state (docs/RESILIENCE.md
  "Elastic resume");
- periodic save every `save_steps` + final save (reference :367-371);
- rank-0 logging of lr / windowed mean loss every `logging_steps`
  (reference :360-374), extended with tokens/sec and MFU.
"""

from __future__ import annotations

import os
import signal
import time
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax._src import distributed as jax_distributed

from llama_pipeline_parallel_tpu.ckpt.checkpoint import (
    CheckpointCorruptError,
    CheckpointManager,
)
from llama_pipeline_parallel_tpu.data.collator import (
    CausalLMCollator,
    PackedCausalLMCollator,
    PretokenizedCollator,
)
from llama_pipeline_parallel_tpu.data.datasets import SyntheticDataset
from llama_pipeline_parallel_tpu.data.loader import (
    DataLoader,
    PrefetchIterator,
    RepeatingLoader,
)
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.models.llama.manifest import StageManifest
from llama_pipeline_parallel_tpu.ops import pallas_common
from llama_pipeline_parallel_tpu.optim import OptimizerConfig, make_optimizer
from llama_pipeline_parallel_tpu.parallel import pipeline as pl
from llama_pipeline_parallel_tpu.parallel import train_step as ts
from llama_pipeline_parallel_tpu.parallel.distributed import (
    barrier,
    form_global_batch,
    host_dp_shard,
    initialize_distributed,
    set_barrier_timeout,
)
from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig, make_mesh
from llama_pipeline_parallel_tpu.utils import (
    faults,
    memwatch as memwatch_mod,
    numerics,
    perf,
    profiler as profiler_mod,
    trace,
)
from llama_pipeline_parallel_tpu.utils.config import instantiate
from llama_pipeline_parallel_tpu.utils.logging import get_logger
from llama_pipeline_parallel_tpu.utils.metrics import (
    MetricsWriter,
    NullMetricsWriter,
    Throughput,
)

logger = get_logger(__name__)

_PRESETS = {
    "tiny": LlamaConfig.tiny,
    "llama_7b": LlamaConfig.llama_7b,
    "llama_13b": LlamaConfig.llama_13b,
    "llama_33b": LlamaConfig.llama_33b,
    "llama_65b": LlamaConfig.llama_65b,
    "llama2_7b": LlamaConfig.llama2_7b,
    "llama2_13b": LlamaConfig.llama2_13b,
    "llama2_70b": LlamaConfig.llama2_70b,
    "codellama_34b_16k": LlamaConfig.codellama_34b_16k,
}


def build_model_config(node: dict) -> LlamaConfig:
    node = dict(node)
    family = node.pop("family", "llama")
    model_cfg = instantiate(node) if "_target_" in node else None
    family = getattr(model_cfg, "family", family)
    if family != "llama":
        # named, not a shape error three modules down: the trainer's step,
        # pipeline and optimizer walk the dense decoder's parameter tree
        raise NotImplementedError(
            f"train.py cannot train the {family!r} family yet: no backward "
            f"pass through its layers (a chunked recurrence, a learned "
            f"top-k selection, grouped expert products, a pooled read of "
            f"chunk summaries), and the pipeline's "
            f"stage split assumes layers of one kind (ROADMAP B2 / B5); it "
            f"is served only (tools/serve.py)")
    if model_cfg is not None:
        return model_cfg
    preset = node.pop("preset", None)
    dtype = node.pop("dtype", None)
    if dtype is not None:
        node["dtype"] = jnp.dtype(dtype).type if isinstance(dtype, str) else dtype
    if preset is not None:
        return _PRESETS[preset](**node)
    return LlamaConfig(**node)


def _packing_factor(cfg: dict) -> int:
    """The one place packing_factor is parsed (train + eval + collator
    construction must agree on it)."""
    return int(cfg.get("packing_factor", 1) or 1)


def _virtual_stages(cfg: dict) -> int:
    """The `virtual_stages` knob (interleaved 1F1B / zb1,
    docs/SCHEDULES.md), parsed in one place so trainer + preflight +
    manifest agree on it."""
    v = int(cfg.get("virtual_stages", 1) or 1)
    if v > 1 and cfg.get("pipeline_schedule", "1f1b") not in (
            "interleaved_1f1b", "zb1", "solver"):
        raise ValueError(
            f"virtual_stages={v} requires pipeline_schedule: "
            f"interleaved_1f1b, zb1, or solver (got "
            f"{cfg.get('pipeline_schedule', '1f1b')!r})")
    return v


def _load_unit_schedule(cfg: dict) -> "Any":
    """The `schedule_file` key under `pipeline_schedule: solver`: a
    parallel/schedule.py unit-sequence JSON (emitted by
    `tools/preflight.py --select --emit-schedule <path>`), loaded and
    validated here so trainer + preflight share one loader. Returns None
    for the named schedules (they generate their canonical sequences
    internally)."""
    if cfg.get("pipeline_schedule", "1f1b") != "solver":
        if cfg.get("schedule_file"):
            raise ValueError(
                "schedule_file only applies under pipeline_schedule: solver "
                f"(got {cfg.get('pipeline_schedule', '1f1b')!r})")
        return None
    path = cfg.get("schedule_file")
    if not path:
        raise ValueError(
            "pipeline_schedule: solver needs schedule_file: <path> — emit "
            "one with `python tools/preflight.py --config ... --select "
            "--emit-schedule <path>` (docs/SCHEDULES.md 'Solver schedules')")
    from llama_pipeline_parallel_tpu.parallel import schedule as usched

    return usched.load(path)


def _offload_flags(cfg: dict) -> tuple[bool, bool]:
    """The `offload.*` config block (host-DRAM residual tiering,
    docs/SCHEDULES.md "Host offload"), parsed in one place so trainer +
    preflight agree: `wgrad_stash` tiers the zb1 W queue, `activations`
    the schedules' stage-input ring buffer (utils/host_stash.py)."""
    node = cfg.get("offload") or {}
    if not isinstance(node, dict):
        raise ValueError(
            f"offload must be a mapping of tier knobs, e.g. "
            f"offload: {{wgrad_stash: true}} — got {node!r}")
    known = {"wgrad_stash", "activations"}
    unknown = set(node) - known
    if unknown:
        raise ValueError(f"unknown offload.* key(s) {sorted(unknown)}; "
                         f"known: {sorted(known)}")
    return (bool(node.get("wgrad_stash", False)),
            bool(node.get("activations", False)))


def _kernel_flags(cfg: dict) -> tuple[bool, bool]:
    """The `kernels.*` config block (fused Pallas TPU kernels,
    docs/KERNELS.md), parsed in one place so trainer + preflight agree:
    `ce` selects the loss head's backend, `prologue` the decoder layers'
    rms_norm->RoPE->QKV prologue. Values are `xla` (default) or `pallas`;
    unknown keys/values are rejected like `offload.*`."""
    node = cfg.get("kernels") or {}
    if not isinstance(node, dict):
        raise ValueError(
            f"kernels must be a mapping of op backends, e.g. "
            f"kernels: {{ce: pallas}} — got {node!r}")
    known = {"ce", "prologue"}
    unknown = set(node) - known
    if unknown:
        raise ValueError(f"unknown kernels.* key(s) {sorted(unknown)}; "
                         f"known: {sorted(known)}")
    flags = []
    for key in ("ce", "prologue"):
        val = node.get(key, "xla")
        if val not in ("xla", "pallas"):
            raise ValueError(f"kernels.{key} must be 'xla' or 'pallas', "
                             f"got {val!r}")
        flags.append(val == "pallas")
    return tuple(flags)


def _offload_static(pcfg: "pl.PipelineConfig", mb_rows: int,
                    local_seqlen: int, hidden_size: int,
                    dtype_bytes: int) -> dict:
    """Run-constant host-stash telemetry for the metrics line AND
    health.json (docs/OBSERVABILITY.md): which residual stores are tiered
    and how many GiB of them are resident in host DRAM. Empty with offload
    off — no always-zero columns, the wgrad_queue_depth policy."""
    wgrad_off = pl.wgrad_offloaded_units(pcfg)
    wgrad_name = "wgrad_stash"
    if pcfg.schedule == "solver" and wgrad_off:
        # selective per-unit offload: name how many of the flush's units
        # tier (the all-True vector reads like the legacy boolean)
        total = pcfg.unit_schedule.n_units
        if wgrad_off < total:
            wgrad_name = f"wgrad_stash[{wgrad_off}/{total}]"
    tiers = [name for name, on in ((wgrad_name, wgrad_off > 0),
                                   ("activations", pcfg.offload_activations))
             if on]
    if not tiers:
        return {}
    resident = pl.host_stash_bytes(pcfg, mb_rows, local_seqlen, hidden_size,
                                   dtype_bytes)
    return {"offload_stash": "+".join(tiers),
            # 6 decimals: KiB resolution, so tiny-model smoke runs still
            # report a nonzero residency
            "offload_stash_resident_gib": round(resident / (1 << 30), 6)}


def _make_observatory(cfg: dict, output_dir: str,
                      stash_bytes: int | None = None) -> tuple:
    """The observatory's run-scoped pieces (docs/OBSERVABILITY.md): the
    triggered profiler (`profiler.*` block — bounded capture windows on
    at_step / step-time z-score / numerics-anomaly triggers) and the memory
    watch (`memory.*` block — opt-in compiled-analysis capture + live
    per-step sampler; OFF compiles and samples nothing). One construction
    for both optimizer paths; `stash_bytes` is the host-stash resident
    estimate the sampler's rows carry next to the device/host polls."""
    pcap = profiler_mod.CaptureConfig.from_cfg(cfg.get("profiler"))
    if pcap is None:
        # no `profiler:` block arms ONLY the fleet trigger-file surface
        # (docs/OBSERVABILITY.md "Fleet"): z-score/at_step captures stay
        # off, but a fleet alert can still reach in for a bounded trace
        pcap = profiler_mod.CaptureConfig(zscore=0.0, on_anomaly=False)
    prof = (profiler_mod.TriggeredProfiler(pcap, output_dir)
            if jax.process_index() == 0 else None)
    mcfg = memwatch_mod.MemoryConfig.from_cfg(cfg.get("memory"))
    mem_watch = None
    if mcfg.enabled:
        mem_watch = memwatch_mod.MemoryWatch(
            output_dir, every=mcfg.every, top_buffers=mcfg.top_buffers,
            write=jax.process_index() == 0,
            stash_bytes=stash_bytes or None)
        logger.info(
            "memory watch enabled: compiled memory_analysis captured per "
            "program, live sampler every %d step(s) (memory.jsonl; "
            "docs/OBSERVABILITY.md 'Memory')", mcfg.every)
    return prof, mem_watch


def _write_perf_rows(output_dir: str, mem_watch) -> None:
    """Close the run into the perf ledger (utils/perf.py): with the memory
    watch on, the compiled-vs-live memory rows (`mem_peak_gib`,
    `compiled_peak_gib:<label>`) — the trainer's contribution to the
    model-vs-measured calibration table tools/perf_report.py renders."""
    if mem_watch is None or jax.process_index() != 0:
        return
    perf.append_rows(os.path.join(output_dir, "perf.jsonl"),
                     mem_watch.perf_rows(run=output_dir))


def _schedule_static_scalars(pcfg: "pl.PipelineConfig") -> dict:
    """Run-constant schedule telemetry repeated on every metrics line
    (docs/OBSERVABILITY.md): the schedule name, its analytic bubble
    fraction, and — under zb1 — the peak W-queue occupancy of the split
    backward (0 elsewhere; omitted rather than an always-zero column)."""
    out = {"schedule": pcfg.schedule,
           "bubble_fraction": round(pl.bubble_fraction(pcfg), 4)}
    if pl.wgrad_queue_peak(pcfg):
        out["wgrad_queue_depth"] = pl.wgrad_queue_peak(pcfg)
    return out


def _schedule_health_static(pcfg: "pl.PipelineConfig", topology: dict) -> dict:
    """The static health.json payload: the topology block (whose `schedule`
    field the elastic-restore contract records) plus, under zb1, the same
    wgrad_queue_depth the metrics line carries — one construction for both
    optimizer paths so the two sinks can never desynchronize."""
    out = {"topology": topology}
    if pl.wgrad_queue_peak(pcfg):
        out["wgrad_queue_depth"] = pl.wgrad_queue_peak(pcfg)
    return out


def _profile_window_facts(cfg: dict, pcfg: "pl.PipelineConfig", mesh) -> dict | None:
    """What a `profile_window` span carries besides its times: facts of the
    compile that a reduction of the trace needs and the trace does not hold.
    None unless `profile_steps` is set, so an untraced run builds nothing.
    `schedule` (pp > 1): per stage the F/B/W slots a step executes, how many
    are masked, and the ids of the stage's devices (the trace's planes are
    named by them). `compiled_memory` is added by the first step
    (`_note_compiled`)."""
    if not cfg.get("profile_steps"):
        return None
    facts: dict = {}
    counts = pl.schedule_slot_counts(pcfg) if pcfg.num_stages > 1 else None
    if counts:
        pp_axis = mesh.axis_names.index("pp")
        for c in counts:
            c["devices"] = sorted(int(d.id) for d in np.take(
                mesh.devices, c["stage"], axis=pp_axis).flat)
        facts["schedule"] = counts
    return facts


def _note_compiled(label: str, lower, mem_watch, profile_facts) -> None:
    """One AOT compile of the step program for whoever wants its
    `memory_analysis()`: the memory watch (docs/OBSERVABILITY.md "Memory")
    and a configured profile window. AOT lowering reads only avals (no
    execution, no donation); the extra compile, or cache hit, lands in the
    first step's compile bucket, before any window. With neither configured
    this returns at once."""
    for_watch = mem_watch is not None and label not in mem_watch.compiled
    for_window = (profile_facts is not None
                  and "compiled_memory" not in profile_facts)
    if not (for_watch or for_window):
        return
    if for_window:
        profile_facts["compiled_memory"] = None   # one attempt, not one a step
    try:
        compiled = lower().compile()
    except Exception as e:
        logger.debug("compiled memory capture failed: %r", e)
        return
    if for_watch:
        mem_watch.note_compiled(label, compiled)
    if for_window:
        profile_facts["compiled_memory"] = memwatch_mod.compiled_memory(
            compiled, top_buffers=0, label=label)


def build_manifest(cfg: dict, model_cfg: LlamaConfig, pp: int) -> StageManifest:
    """Stage partition policy, shared by the trainer and tools/preflight.py
    (the preflight must compile the SAME program the trainer runs): explicit
    per-stage layer_counts > cost-balanced (`stage_balance: cost`, the
    SURVEY §7.3-item-2 MFU lever) > even split. Indivisible layer counts
    fall back to cost-balanced automatically. `virtual_stages` > 1
    (interleaved 1F1B / zb1) switches to the round-robin chunked layout —
    it rejects uneven partitions (manifest.py), so layer_counts/
    stage_balance cannot be combined with it."""
    v = _virtual_stages(cfg)
    if v > 1:
        if cfg.get("layer_counts") or cfg.get("stage_balance", "even") == "cost":
            raise ValueError(
                "virtual_stages > 1 (interleaved 1F1B / zb1) uses the "
                "round-robin even chunk partition; layer_counts/"
                "stage_balance: cost cannot apply — drop them or fall back "
                "to a flat schedule")
        return StageManifest.for_config(model_cfg, pp, virtual_stages=v)
    if cfg.get("layer_counts"):
        return StageManifest(num_layers=model_cfg.num_hidden_layers,
                             num_stages=pp,
                             layer_counts=tuple(cfg["layer_counts"]))
    if (cfg.get("stage_balance", "even") == "cost"
            or model_cfg.num_hidden_layers % pp):
        manifest = StageManifest.balanced(model_cfg, pp)
        logger.info("stage partition (cost-balanced): %s",
                    manifest.stage_layer_counts)
        return manifest
    return StageManifest.for_config(model_cfg, pp)


def build_pipeline_config(cfg: dict, mesh_cfg: Any, manifest: StageManifest
                          ) -> "pl.PipelineConfig":
    """PipelineConfig from the run config — one construction for the trainer
    and tools/preflight.py."""
    offload_wgrad, offload_acts = _offload_flags(cfg)
    kernel_ce, kernel_prologue = _kernel_flags(cfg)
    return pl.PipelineConfig(
        num_stages=mesh_cfg.pp,
        unit_schedule=_load_unit_schedule(cfg),
        num_microbatches=cfg.get("gradient_accumulation_steps", 1),
        remat=cfg.get("activation_checkpointing", True),
        remat_policy=cfg.get("remat_policy", "nothing_saveable"),
        schedule=cfg.get("pipeline_schedule", "1f1b"),
        virtual_stages=manifest.virtual_stages,
        accum_chunks=cfg.get("gradient_accumulation_chunks", 1),
        sequence_parallel=cfg.get("sequence_parallel", "ring"),
        loss_chunks=cfg.get("loss_vocab_chunks", 1),
        layer_counts=None if manifest.is_even else manifest.stage_layer_counts,
        packed=_packing_factor(cfg) > 1,
        offload_wgrad=offload_wgrad,
        offload_activations=offload_acts,
        kernel_ce=kernel_ce,
        kernel_prologue=kernel_prologue)


def build_dataset_and_collator(cfg: dict, model_cfg: LlamaConfig) -> tuple[Any, Any]:
    packing = _packing_factor(cfg)
    data_cfg = cfg.get("dataset")
    if data_cfg is None or data_cfg.get("synthetic"):
        if packing > 1:
            raise ValueError("packing_factor requires a tokenizer-backed "
                             "dataset (the synthetic dataset emits fixed "
                             "full-length rows — nothing to pack)")
        seq = (data_cfg or {}).get("seq_length", cfg.get("max_seq_length", 512))
        ds = SyntheticDataset(
            vocab_size=model_cfg.vocab_size, seq_length=seq,
            pseudo_dataset_len=(data_cfg or {}).get("pseudo_dataset_len", 4096),
            seed=cfg.get("seed", 42),
            pad_fraction=(data_cfg or {}).get("pad_fraction", 0.0))
        return ds, PretokenizedCollator()
    ds = instantiate(data_cfg)
    coll_cfg = cfg.get("collator")
    if coll_cfg is not None and "_target_" in coll_cfg:
        if packing > 1:
            raise ValueError("packing_factor cannot be combined with a "
                             "custom collator _target_; construct "
                             "PackedCausalLMCollator there directly")
        collator = instantiate(coll_cfg)
    else:
        from transformers import AutoTokenizer

        from llama_pipeline_parallel_tpu.data.tokenization import expand_special_tokenizer

        tokenizer = AutoTokenizer.from_pretrained(cfg["tokenizer_path"])
        expand_special_tokenizer(tokenizer)
        if len(tokenizer) > model_cfg.vocab_size:
            raise ValueError(
                f"tokenizer has {len(tokenizer)} tokens but model vocab_size is "
                f"{model_cfg.vocab_size}; re-convert the checkpoint with vocab "
                f"expansion (tools/convert_hf.py resizes embeddings, like "
                f"reference convert2ckpt.py:60-63)")
        if packing > 1:
            collator = PackedCausalLMCollator(
                tokenizer, cfg.get("max_seq_length", 512), pack_factor=packing)
        else:
            collator = CausalLMCollator(tokenizer, cfg.get("max_seq_length", 512))
    return ds, collator


def _flash_without_mask(q, k, v, padding_mask=None, *, causal=True):
    """flash_attention minus the segment-mask input streams (see
    select_attention.finish)."""
    from llama_pipeline_parallel_tpu.ops.flash_attention import flash_attention

    return flash_attention(q, k, v, None, causal=causal)


_AUTO_ATTN_CACHE: dict = {}


def _measure_segments(batch: int, seq_len: int) -> jnp.ndarray:
    """Representative packed-row segment ids for the auto measurement: four
    equal segments covering ~4/5 of the row, then a genuine pad tail — so
    the timing includes the kernels' fully-masked-pad skip path the real
    packed run hits."""
    seg = np.zeros((batch, seq_len), np.int32)
    fifth = max(seq_len // 5, 1)
    for i in range(4):
        seg[:, i * fifth:(i + 1) * fifth] = i + 1
    return jnp.asarray(seg)


def _measure_attention(model_cfg: LlamaConfig, seq_len: int,
                       micro_batch: int = 1, packed: bool = False) -> Any:
    """Time exact vs flash (fwd+bwd, jitted, block_until_ready barrier) at
    this run's ACTUAL (microbatch, seq) shape ON THE DEVICE — with
    segment-id streams when the run packs sequences, since those change the
    flash kernel's work — and return the faster. `auto` picks by
    measurement, not by threshold folklore. Cached per shape. Only reached
    on a TPU (select_attention), where a candidate that fails to compile or
    run is an error, not a vote for the other one."""
    from llama_pipeline_parallel_tpu.ops.attention import attention
    from llama_pipeline_parallel_tpu.ops.flash_attention import flash_attention

    key = (seq_len, micro_batch, packed, model_cfg.num_attention_heads,
           model_cfg.kv_heads, model_cfg.head_dim)
    if key in _AUTO_ATTN_CACHE:
        return _AUTO_ATTN_CACHE[key]

    def measure_locally():
        rng = np.random.RandomState(0)
        h, hkv, hd = (model_cfg.num_attention_heads, model_cfg.kv_heads,
                      model_cfg.head_dim)
        b = max(int(micro_batch), 1)
        q = jnp.asarray(rng.randn(b, seq_len, h, hd), jnp.bfloat16)
        k = jnp.asarray(rng.randn(b, seq_len, hkv, hd), jnp.bfloat16)
        v = jnp.asarray(rng.randn(b, seq_len, hkv, hd), jnp.bfloat16)
        mask = _measure_segments(b, seq_len) if packed else None

        def time_one(fn):
            loss = lambda q, k, v: (fn(q, k, v, mask, causal=True)
                                    .astype(jnp.float32) ** 2).sum()
            step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
            jax.block_until_ready(step(q, k, v))  # compile off the clock
            t0 = time.perf_counter()
            for _ in range(3):
                out = step(q, k, v)
            jax.block_until_ready(out)
            return (time.perf_counter() - t0) / 3

        t_exact, t_flash = time_one(attention), time_one(flash_attention)
        winner = flash_attention if t_flash < t_exact else attention
        logger.info("attention=auto @ batch %d seq %d packed=%s: "
                    "exact %.2fms, flash %.2fms -> %s",
                    b, seq_len, packed, 1e3 * t_exact, 1e3 * t_flash,
                    "flash" if winner is flash_attention else "exact")
        return winner

    if jax.process_count() > 1:
        # Every process must compile the SAME program: near-equal timings (or
        # a one-host measurement failure) must not let hosts pick different
        # kernels — process 0 measures, everyone takes its verdict.
        from jax.experimental import multihost_utils

        choice = 0
        if jax.process_index() == 0:
            choice = 1 if measure_locally() is flash_attention else 0
        choice = int(multihost_utils.broadcast_one_to_all(np.int32(choice)))
        winner = flash_attention if choice else attention
    else:
        winner = measure_locally()
    _AUTO_ATTN_CACHE[key] = winner
    return winner


def select_attention(impl: str, seq_length: int, mesh,
                     sequence_parallel: str = "ring",
                     model_cfg: LlamaConfig | None = None,
                     packed: bool = False,
                     micro_batch: int = 1) -> Any:
    """'exact' | 'flash' | 'auto'. The reference tried and failed to enable
    flash attention (README.md:141-143); here `auto` MEASURES both paths on
    the device at the run's (microbatch, seq) shape — with segment streams
    when packed — and keeps the faster.

    `seq_length` must be the ACTUAL batch sequence length (probe the
    collator), not a config guess. The flash kernel's tiling rule is
    adaptive (ops/flash_attention.py `_auto_block`: the largest 128-multiple
    <= 1024 that divides the length): seq 1536 tiles with 768 blocks, 1280
    with 640; only lengths no 128-multiple divides need the exact path.
    Checked against the length the kernel actually SEES, which under ring
    sequence parallelism is the per-slab seq/sp (Ulysses re-shards to the
    full sequence, so there it stays seq)."""
    from llama_pipeline_parallel_tpu.ops.attention import attention
    from llama_pipeline_parallel_tpu.ops.flash_attention import (
        _auto_block,
        flash_attention,
    )

    def finish(fn):
        """Unpacked single-chip-sequence flash runs skip the kernel's segment
        streams: a 0/1 mask is a documented no-op there, and dropping it
        keeps the non-packed hot path identical to the pre-segments kernel.
        Not applied under sp>1 — make_sp_attention dispatches its ring
        backend by `inner_attn is flash_attention` identity, and ring drops
        the mask itself anyway."""
        if fn is flash_attention and not packed and mesh.shape["sp"] == 1:
            return _flash_without_mask
        return fn

    if impl == "exact":
        return attention
    if impl == "flash":
        return finish(flash_attention)
    if impl == "auto":
        sp = mesh.shape["sp"]
        kernel_len = seq_length // sp if (sp > 1 and sequence_parallel == "ring") \
            else seq_length
        on_tpu = mesh.devices.ravel()[0].platform == "tpu"
        tiles = kernel_len % _auto_block(kernel_len) == 0
        if not on_tpu:
            return attention  # flash interpret mode off-TPU is far slower
        if not tiles:
            logger.warning(
                "attention=auto: kernel sequence length %d (seq %d / sp slab) "
                "is not divisible by any 128-multiple block <= 1024; using "
                "the exact path (pad to a 128 multiple to enable flash)",
                kernel_len, seq_length)
            return attention
        if model_cfg is None:
            return finish(flash_attention) if kernel_len >= 2048 else attention
        return finish(_measure_attention(model_cfg, kernel_len,
                                         micro_batch=micro_batch, packed=packed))
    raise ValueError(f"unknown attention impl {impl!r} (use exact|flash|auto)")


# Preemption state shared between the signal handlers (installed at trainer
# entry, BEFORE jax.distributed.initialize) and the step loop. Module-level so
# a signal landing during the minutes of setup/compile is still seen when the
# loop finally starts. Mutated ONLY from the main thread (install/release
# guard) and the signal handler, which also runs in the main thread.
_STOP_SIGNALS: list[int] = []
_INSTALLED_SIGNALS: list[int] = []
_PREVIOUS_HANDLERS: dict = {}


def _in_main_thread() -> bool:
    import threading

    return threading.current_thread() is threading.main_thread()


def _on_preemption_signal(sig, frame):
    _STOP_SIGNALS.append(sig)
    # async-signal-safe notice — without it a Ctrl+C during minutes of
    # setup/compile looks ignored (the stop only happens at the next step)
    os.write(2, b"\n[trainer] signal received; will checkpoint at the next "
                b"step and exit (signal again to force-quit)\n")
    # restore defaults so a second Ctrl+C force-quits a wedged save — but
    # only for the signals WE still own: SIGTERM passes to the C++ notifier
    # when jax.distributed initializes AFTER the install, and writing its
    # sigaction then would disable the pod-wide preemption protocol
    for s in _INSTALLED_SIGNALS:
        if s == signal.SIGTERM and _cpp_notifier_owns_sigterm():
            continue
        signal.signal(s, signal.SIG_DFL)


def _cpp_notifier_owns_sigterm() -> bool:
    """True iff jax's C++ preemption notifier holds the SIGTERM sigaction.

    The notifier is registered with the preemption SYNC MANAGER, not the
    bare distributed client: `jax.distributed.initialize()` skips it when
    `jax_enable_preemption_service=False`, and then Python must keep owning
    SIGTERM even though a client is active. Reads a jax internal (there is
    no public accessor in the jax this repo is written for, 0.9 —
    pyproject.toml pins it); called from inside signal handlers, where an
    attribute read is safe."""
    return jax_distributed.global_state.preemption_sync_manager is not None


def _install_preemption_handlers() -> None:
    """Record SIGTERM/SIGINT — the TPU-VM maintenance-event notice — from the
    very start of the run. Must run before `jax.distributed.initialize`: on a
    pod the runtime's C++ preemption notifier takes SIGTERM over from Python
    (preemption_notifier.cc registers its own sigaction), after which the
    signal is only observable through the coordination service's sync point
    (`_preemption_notice`); these Python handlers cover the pre-init window
    and all single-process runs.

    If a caller initialized jax.distributed BEFORE calling run_training, the
    notifier already owns SIGTERM and taking it back would silently disable
    the coordination-service protocol pod-wide — leave it alone and own only
    SIGINT there.

    A run on a worker thread (embedded caller) installs nothing and must not
    touch the module state — it may belong to a concurrent main-thread run."""
    if not _in_main_thread():
        return
    signals = [signal.SIGINT] if _cpp_notifier_owns_sigterm() \
        else [signal.SIGTERM, signal.SIGINT]
    _STOP_SIGNALS.clear()  # a stale flag from a prior run must not stop this one
    for sig in signals:
        prev = signal.signal(sig, _on_preemption_signal)
        # a None "previous" is a sigaction installed by non-Python code —
        # signal.signal can't reinstate it; record SIG_DFL so the restore
        # path never leaves OUR handler dangling after the run
        _PREVIOUS_HANDLERS[sig] = signal.SIG_DFL if prev is None else prev
        _INSTALLED_SIGNALS.append(sig)


def _release_preemption_handlers() -> None:
    """Restore the pre-run handlers. Idempotent (second call is a no-op), so
    _train_loop can hand the signals back before the final save — a Ctrl+C
    there must interrupt, not be swallowed by handlers nothing re-checks —
    and run_training's finally stays the backstop for every other exit."""
    if not _in_main_thread():
        return
    for sig, handler in list(_PREVIOUS_HANDLERS.items()):
        # never restore over the C++ notifier's SIGTERM sigaction — it must
        # keep feeding the coordination service for later runs in this process
        if not (sig == signal.SIGTERM and _cpp_notifier_owns_sigterm()):
            signal.signal(sig, handler)
        del _PREVIOUS_HANDLERS[sig]
    _STOP_SIGNALS.clear()
    _INSTALLED_SIGNALS.clear()


def run_training(cfg: dict) -> dict:
    """The full training run; returns a summary dict for programmatic callers."""
    if "timeline" in cfg:
        raise ValueError(
            "the timeline config block is gone with its host callbacks: the "
            "schedule's time is read from the device trace (profiler.at_step "
            "or profile_steps, then tools/trace_summary.py; the benchmark's "
            "bubble_share.train)")
    if "compilation_cache_dir" in cfg:
        raise ValueError(
            "the compilation_cache_dir config key is gone: set "
            "JAX_COMPILATION_CACHE_DIR in the environment, or leave it unset "
            "for <checkout>/.jax_cache (utils/compile_cache.py)")
    _install_preemption_handlers()
    # Fault-tolerance wiring (docs/RESILIENCE.md): the env plan wins over the
    # config node — the supervisor drives chaos runs through LPT_FAULT_PLAN
    # and must be able to override whatever the config ships.
    if os.environ.get(faults.ENV_PLAN):
        faults.configure_from_env()
    else:
        faults.configure(cfg.get("fault_plan"))
    set_barrier_timeout(cfg.get("barrier_timeout_s"))
    try:
        return _run_training(cfg)
    finally:
        trace.configure(None)  # close this run's spans.jsonl writer
        set_barrier_timeout(None)  # later runs must not inherit the timeout
        faults.configure(None)  # ...or this run's fault plan
        _release_preemption_handlers()


def _run_training(cfg: dict) -> dict:
    seed = cfg.get("seed", 42)
    output_dir = cfg["output_dir"]

    initialize_distributed()  # no-op unless a pod coordinator is configured
    # Span stream from here on: everything until the step loop starts is the
    # `init` bucket (model build, checkpoint restore, first-batch probe).
    trace.configure(output_dir, write=jax.process_index() == 0)
    mesh_cfg = MeshConfig(**cfg.get("mesh", {}))
    mesh = make_mesh(mesh_cfg)
    model_cfg = build_model_config(cfg["model"])
    manifest = build_manifest(cfg, model_cfg, mesh_cfg.pp)
    # Packing composes with every parallelism axis: both attention backends
    # handle segment masks at sp=1 (the exact op's pairwise test, the flash
    # kernel's in-tile _seg_tile_mask); under sp>1 Ulysses all-gathers the
    # mask to full length and ring rotates the kv segment slab with its k/v
    # (pcfg.packed switches the ring's segment streams on).
    packing = _packing_factor(cfg)
    pcfg = build_pipeline_config(cfg, mesh_cfg, manifest)
    if (pcfg.offload_wgrad or pcfg.offload_activations
            or pl.wgrad_offloaded_units(pcfg)):
        from llama_pipeline_parallel_tpu.utils import host_stash

        logger.info(
            "host stash enabled (wgrad=%s activations=%s): %s",
            pcfg.offload_wgrad or pl.wgrad_offloaded_units(pcfg),
            pcfg.offload_activations,
            "residuals tier to the pinned_host memory space"
            if host_stash.transfers_enabled() else
            "CPU backend — same schedule, stores stay in regular memory")
    if pcfg.kernel_ce or pcfg.kernel_prologue:
        logger.info(
            "pallas kernels enabled (ce=%s prologue=%s): %s (docs/KERNELS.md)",
            pcfg.kernel_ce, pcfg.kernel_prologue,
            "interpret mode — parity semantics, no kernel speedup off-TPU"
            if pallas_common.interpret_mode() else "Mosaic-compiled")
    topology = _topology_meta(mesh, pcfg, manifest)
    # Numerics observatory (docs/OBSERVABILITY.md "Numerics"): per-stage
    # training-dynamics stats computed in-graph, anomaly detection + the
    # numerics.jsonl stream on the host. On by default — the in-graph
    # reductions are a few hundred floats next to a pipeline step.
    ncfg = numerics.NumericsConfig.from_cfg(cfg.get("numerics"))
    if faults.has_rule("step", "grad_nonfinite"):
        if not ncfg.enabled:
            # the chaos op exists to exercise the observatory; without it
            # the poison would NaN the params with no guard/skip/record
            raise ValueError(
                "fault plan contains a grad_nonfinite rule but "
                "numerics.enabled is false — the nonfinite guard would be "
                "unarmed; enable numerics or drop the rule")
        bad = [s for s in faults.rule_field_values(
                   "step", "grad_nonfinite", "stage")
               if not 0 <= s < pcfg.num_stages]
        if bad:
            # an out-of-range stage would make the poison mask all-ones: the
            # drill "passes" while exercising nothing
            raise ValueError(
                f"grad_nonfinite rule stage(s) {bad} out of range for "
                f"num_stages={pcfg.num_stages}")
    monitor = (numerics.NumericsMonitor(output_dir, ncfg,
                                        write=jax.process_index() == 0,
                                        recorder=trace.recorder())
               if ncfg.enabled else None)

    dataset, collator = build_dataset_and_collator(cfg, model_cfg)
    micro_batch = cfg.get("per_device_train_batch_size", 1)
    # with packing, the loader feeds pack_factor x examples per emitted row
    per_replica_batch = micro_batch * pcfg.num_microbatches * packing
    data_node = cfg.get("data") or {}
    loader = DataLoader(dataset, collator, per_replica_batch=per_replica_batch,
                        dp_size=mesh_cfg.dp, seed=seed,
                        dp_range=host_dp_shard(mesh),
                        quarantine_bad_records=bool(
                            data_node.get("quarantine_bad_shards", False)),
                        # per-sample-id ledger (elastic-resume audits); the
                        # file covers THIS process's dp shards — process 0
                        # only, so a pod doesn't interleave writers
                        sample_ledger=(os.path.join(output_dir, "samples.jsonl")
                                       if data_node.get("log_sample_ids")
                                       and jax.process_index() == 0 else None))
    steps_per_epoch = len(loader)
    if steps_per_epoch == 0:
        raise ValueError(
            f"dataset of {len(dataset)} examples yields 0 steps at "
            f"dp={mesh_cfg.dp} x per_replica_batch={per_replica_batch}")

    # Runtime schedule-total injection (reference trainer_base_ds_mp.py:263-275).
    # `total_steps` (schedule horizon) is separate from `max_steps` (loop end)
    # so an interrupted-then-resumed run sees the same LR curve as an
    # uninterrupted one.
    epochs = cfg.get("num_train_epochs", 1)
    t_total = cfg.get("total_steps") or cfg.get("max_steps") or steps_per_epoch * epochs
    end_step = min(cfg.get("max_steps") or t_total, t_total)
    warmup = cfg.get("warmup_steps")
    if warmup is None:
        warmup = max(int(t_total * cfg.get("warmup_proportion", 0.0)), 1)
    ocfg = OptimizerConfig(
        learning_rate=cfg.get("learning_rate", 1e-6),
        weight_decay=cfg.get("weight_decay", 0.001),
        beta1=cfg.get("adam_beta1", 0.9), beta2=cfg.get("adam_beta2", 0.99),
        eps=cfg.get("adam_eps", 1e-8),
        max_grad_norm=cfg.get("max_grad_norm", 5.0),
        total_steps=t_total, warmup_steps=warmup)
    tx, schedule = make_optimizer(ocfg)

    # ---- params: fresh init, warm start, or resume ------------------------
    # Sharded init: each device materializes only its own stage/tp shard
    # (the reference's LayerSpec lazy construction, README.md:21-22).
    stacked_template = ts.init_params_sharded(
        jax.random.PRNGKey(seed), model_cfg, mesh, manifest)
    mgr = CheckpointManager(output_dir)

    if cfg.get("optimizer_offload"):
        return _run_offload(cfg, mesh, model_cfg, manifest, pcfg, ocfg,
                            dataset, collator, loader, end_step, stacked_template, mgr,
                            ncfg=ncfg, monitor=monitor)
    if cfg.get("optimizer_offload_zero2"):
        raise ValueError("optimizer_offload_zero2 requires optimizer_offload: "
                         "true (it shards the HOST-offloaded masters/grads "
                         "over dp; the fused optimizer already has ZeRO-1 "
                         "sharded moments)")

    resume_step = 0
    # Donate the init output into the train state (no second fp32 copy) and
    # keep only abstract shapes as the structure template from here on.
    template_struct = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                                   stacked_template)
    state = ts.init_train_state(stacked_template, tx, mesh, donate_params=True)
    stacked_template = template_struct
    restored = (_restore_with_fallback(
        mgr, lambda s: mgr.load(s, state.params, state.opt_state, manifest))
        if cfg.get("resume", True) else None)
    if restored is not None:
        p, o, resume_step = restored
        shard_of = lambda tmpl: jax.tree.map(lambda x: x.sharding, tmpl)
        state = ts.TrainState(
            step=jnp.asarray(resume_step, jnp.int32),
            params=jax.device_put(p, shard_of(state.params)),
            opt_state=jax.device_put(o, shard_of(state.opt_state)))
        logger.info("resumed full state from checkpoint-%d", resume_step)
        _note_topology_change(mgr, resume_step, topology)
    elif cfg.get("model_name_or_path"):
        warm = CheckpointManager(cfg["model_name_or_path"])
        warm_step = warm.latest_step()
        if warm_step is None:
            raise FileNotFoundError(
                f"no checkpoint under model_name_or_path={cfg['model_name_or_path']} "
                f"(run tools/convert_hf.py first, like reference convert2ckpt.py)")
        p = warm.load_params(warm_step, state.params, manifest)
        state = ts.TrainState(
            step=state.step,
            params=jax.device_put(p, jax.tree.map(lambda x: x.sharding, state.params)),
            opt_state=state.opt_state)
        logger.info("warm-started module weights from %s", cfg["model_name_or_path"])

    seq_length = int(collator([dataset[0]])["input_ids"].shape[1])
    if seq_length % mesh_cfg.sp:
        raise ValueError(f"sequence length {seq_length} must divide into "
                         f"sp={mesh_cfg.sp} equal slabs")
    attn_fn = select_attention(cfg.get("attention", "auto"), seq_length, mesh,
                               sequence_parallel=cfg.get("sequence_parallel", "ring"),
                               model_cfg=model_cfg,
                               packed=_packing_factor(cfg) > 1,
                               micro_batch=micro_batch)
    # The poison input (the grad_nonfinite chaos op) is only compiled into
    # the step when the active fault plan carries such a rule — steady-state
    # runs keep the two-argument signature (no extra per-step H2D).
    poison_on = faults.has_rule("step", "grad_nonfinite")
    prof, mem_watch = _make_observatory(
        cfg, output_dir,
        stash_bytes=pl.host_stash_bytes(pcfg, *pl.stash_dims(
            micro_batch, seq_length, mesh_cfg.sp, model_cfg.hidden_size,
            model_cfg.dtype)))
    step_fn = ts.make_train_step(mesh, model_cfg, pcfg, tx, schedule,
                                 stacked_template, attn_fn=attn_fn,
                                 collect_stats=ncfg.enabled, poison=poison_on)

    # ---- loop -------------------------------------------------------------
    state_box = [state]
    profile_facts = _profile_window_facts(cfg, pcfg, mesh)

    def do_step(batch, step, fault=None):
        gbatch = form_global_batch(mesh, batch)
        _note_compiled(
            "train_step",
            lambda: step_fn.lower(*(
                (state_box[0], gbatch, numerics.fault_stage(None))
                if poison_on else (state_box[0], gbatch))),
            mem_watch, profile_facts)
        if poison_on:
            new_state, metrics = step_fn(state_box[0], gbatch,
                                         numerics.fault_stage(fault))
        else:
            new_state, metrics = step_fn(state_box[0], gbatch)
        state_box[0] = new_state
        if monitor is not None:
            # async D2H enqueue + lag-1 processing; may raise
            # NonfiniteHaltError (handled by _train_loop's halt path)
            monitor.observe(step, metrics["loss"], metrics["grad_norm"],
                            metrics.get("numerics"))
        return metrics["loss"], lambda: {"lr": float(metrics["lr"]),
                                         "grad_norm": float(metrics["grad_norm"])}

    data_start = (_resume_data_position(mgr, resume_step, loader,
                                        len(dataset), seed)
                  if resume_step else (0, 0))
    # data-stream batches minus step count: nonzero only after a
    # changed-global-batch remap, and every LATER checkpoint must carry the
    # offset forward or a second resume re-trains the remapped span
    data_delta = (data_start[0] * max(len(loader), 1)
                  + data_start[1]) - resume_step

    def do_save(step, final=False):
        # async_save: periodic checkpoints return once Orbax holds host
        # copies; the disk flush + commit + off-node sync overlap the next
        # training steps. Final/preemption saves block — the process exits
        # right after, and a daemon commit thread would die with it.
        barrier("pre-save")
        mgr.save(step, state_box[0].params, manifest, model_cfg,
                 opt_state=state_box[0].opt_state,
                 blocking=final or not cfg.get("async_save", False),
                 on_complete=lambda path: _sync_checkpoint(cfg, path),
                 keep_last=cfg.get("save_total_limit"),
                 extra_meta={"topology": topology,
                             "data_state": _data_state(step, loader,
                                                       len(dataset), seed,
                                                       data_delta),
                             **_eval_meta()})

    do_eval = _make_evaluator(cfg, mesh, model_cfg, pcfg, stacked_template,
                              attn_fn, lambda: state_box[0].params)
    off_static = _offload_static(pcfg, *pl.stash_dims(
        micro_batch, seq_length, mesh_cfg.sp, model_cfg.hidden_size,
        model_cfg.dtype))
    try:
        final_loss, preempted_at = _train_loop(
            cfg, model_cfg, mesh, loader, seq_length,
            resume_step, end_step, do_step, do_save, do_eval,
            extra_scalars=_host_scalars(collator, loader),
            static_scalars={**_schedule_static_scalars(pcfg), **off_static},
            monitor=monitor, data_start=data_start,
            health_static={**_schedule_health_static(pcfg, topology),
                           **off_static},
            profiler=prof, mem_watch=mem_watch,
            profile_facts=profile_facts)
    except BaseException:
        # join the in-flight commit, but never let ITS failure replace the
        # training exception that actually killed the run
        try:
            mgr.finalize()
        except Exception:
            logger.exception("async checkpoint commit also failed while "
                             "unwinding a training error")
        raise
    mgr.finalize()  # surface any async-commit failure on the clean path
    _write_perf_rows(output_dir, mem_watch)
    return _summarize(final_loss, preempted_at, end_step, steps_per_epoch,
                      output_dir)


def _topology_meta(mesh, pcfg: "pl.PipelineConfig",
                   manifest: StageManifest | None = None) -> dict:
    """The run's topology, recorded in every checkpoint's meta.json and in
    health.json — the source half of the elastic-restore contract
    (docs/RESILIENCE.md "Elastic resume"): a later incarnation on a
    different mesh reads it to explain (and log) what changed.

    `layer_counts` names the stage PARTITION — "even/10" or the explicit
    per-stage list — so a partition change (e.g. (4,4,4,1) -> even/2 from a
    generated-ladder resize) is logged like a pp/dp/tp change instead of
    silently resharding through the canonical layout."""
    mc = MeshConfig(pp=mesh.shape["pp"], dp=mesh.shape["dp"],
                    tp=mesh.shape["tp"], sp=mesh.shape["sp"])
    out = {"pp": mc.pp, "dp": mc.dp, "tp": mc.tp, "sp": mc.sp,
           "layout": mc.describe(),
           "schedule": pcfg.schedule, "virtual_stages": pcfg.virtual_stages,
           "process_count": jax.process_count()}
    if manifest is not None:
        out["layer_counts"] = (
            f"even/{manifest.stage_layer_counts[0]}" if manifest.is_even
            else list(manifest.stage_layer_counts))
    return out


def _data_state(step: int, loader: DataLoader, dataset_len: int,
                seed: int, batch_delta: int = 0) -> dict:
    """The sampler position at `step`, in dp-width-independent units: the
    epoch permutation is a function of (seed, epoch) only, and step b
    consumes exactly global-order positions [b*G, (b+1)*G) — so
    consumed_samples, not any per-replica cursor, is the canonical resume
    coordinate that survives a dp resize (docs/RESILIENCE.md).

    `batch_delta`: data-stream batches minus step count, established at
    resume (nonzero only after a changed-global-batch remap, where the step
    counter and the data cursor diverge) — without it, a SECOND resume from
    a checkpoint written after such a remap would reposition from step*G
    and re-train whole spans of data."""
    spe = max(len(loader), 1)
    g = loader.global_batch_examples
    batches = step + batch_delta
    return {"epoch": batches // spe, "offset_batches": batches % spe,
            "consumed_samples": batches * g, "shuffle_seed": seed,
            "global_batch_examples": g, "dataset_len": dataset_len,
            "steps_per_epoch": spe}


def _resume_data_position(mgr: CheckpointManager, resume_step: int,
                          loader: DataLoader, dataset_len: int,
                          seed: int) -> tuple[int, int]:
    """O(1) resume position (start_epoch, start_batch) for the data stream.

    Replaces the seed's O(resume_step) loader replay ("minutes at scale"):
    the checkpoint's data_state pins (seed, dataset_len, consumed samples),
    and index arithmetic alone repositions the samplers. Checkpoints
    without a data_state (pre-elastic format) derive the position from the
    step count — identical to what the old replay computed, still O(1).
    A changed global batch is remapped by consumed-sample count (exact only
    when G is unchanged — re-trains at most one partial batch otherwise,
    and warns); a changed shuffle seed or dataset cannot be remapped and
    falls back to step-count positioning with a warning."""
    spe = max(len(loader), 1)
    g = loader.global_batch_examples
    batches = resume_step
    data_state = None
    try:
        data_state = mgr.load_meta(resume_step).get("data_state")
    except Exception as e:  # meta vanished under us — position by step count
        logger.warning("could not re-read checkpoint-%d meta for data_state "
                       "(%r); positioning the loader by step count",
                       resume_step, e)
    if data_state:
        if (data_state.get("shuffle_seed") != seed
                or data_state.get("dataset_len") != dataset_len):
            logger.warning(
                "checkpoint data_state (seed=%s, dataset_len=%s) does not "
                "match this run (seed=%s, dataset_len=%s); positioning by "
                "step count — the shuffle order differs, sample-exact "
                "continuity is not guaranteed",
                data_state.get("shuffle_seed"), data_state.get("dataset_len"),
                seed, dataset_len)
        else:
            consumed = int(data_state.get("consumed_samples", resume_step * g))
            src_g = data_state.get("global_batch_examples")
            if src_g not in (None, g):
                logger.warning(
                    "global batch changed across resume (%s -> %s examples/"
                    "step); sample-exact continuity only holds for an "
                    "unchanged global batch — remapping by consumed-sample "
                    "count, re-training at most one partial batch "
                    "(docs/RESILIENCE.md)", src_g, g)
            batches = consumed // g
    epoch, offset = divmod(batches, spe)
    logger.info("O(1) data resume: step %d -> epoch %d, batch offset %d "
                "(no loader replay)", resume_step, epoch, offset)
    return epoch, offset


def _note_topology_change(mgr: CheckpointManager, step: int,
                          current: dict) -> None:
    """Log an elastic restore: the checkpoint's recorded source topology vs
    the mesh this incarnation runs. Purely informational — the canonical
    layout + resharded Orbax reads make the restore itself work; what an
    operator needs is the ledger line saying the resize happened."""
    try:
        source = mgr.load_meta(step).get("topology")
    except Exception:
        return
    if not source:
        return  # pre-elastic checkpoint: nothing recorded
    keys = ["pp", "dp", "tp", "sp", "schedule", "virtual_stages"]
    if "layer_counts" in source:
        # the stage PARTITION is restore-relevant like a topology axis (a
        # (4,4,4,1) -> even/2 ladder resize reshards every layer leaf);
        # compared only when the source recorded it, so pre-partition-aware
        # checkpoints don't flag a phantom change on every resume
        keys.append("layer_counts")
    changed = sorted(k for k in keys if source.get(k) != current.get(k))
    if changed:
        logger.warning(
            "elastic restore: checkpoint-%d was written at %s "
            "(schedule=%s, v=%s, layer_counts=%s); restoring onto %s "
            "(schedule=%s, v=%s, layer_counts=%s) — "
            "changed: %s. Keep the global batch unchanged for sample-exact "
            "data continuity (docs/RESILIENCE.md)",
            step, source.get("layout"), source.get("schedule"),
            source.get("virtual_stages"), source.get("layer_counts"),
            current.get("layout"), current.get("schedule"),
            current.get("virtual_stages"), current.get("layer_counts"),
            changed)
    else:
        logger.info("resume topology matches checkpoint-%d (%s)", step,
                    current.get("layout"))


def _restore_with_fallback(mgr: CheckpointManager, restore_fn) -> Any | None:
    """Resume restore with automatic fallback (docs/RESILIENCE.md): when the
    newest checkpoint fails integrity verification, `verify` quarantines it
    to checkpoint-N.corrupt, `latest_step()` then resolves to the previous
    complete one, and the restore simply re-runs — until a checkpoint
    verifies or none remain (fresh start). Only CheckpointCorruptError
    falls back; layout/compat errors (ValueError) stay fatal — they mean a
    misconfigured run, and silently training from an older checkpoint would
    hide that."""
    prev: int | None = None
    while True:
        step = mgr.latest_step()
        if step is None:
            return None
        if step == prev:
            # quarantine could not move the dir (permissions?) — re-raising
            # beats spinning on the same corrupt checkpoint forever
            raise CheckpointCorruptError(
                f"checkpoint-{step} is corrupt and could not be quarantined")
        try:
            return restore_fn(step)
        except CheckpointCorruptError as e:
            logger.error("resume blocked by corrupt checkpoint-%d (%s); "
                         "falling back", step, e)
            prev = step


def _summarize(final_loss, preempted_at, end_step, steps_per_epoch,
               output_dir) -> dict:
    """The run summary contract shared by both optimizer paths: final_step is
    the step the run actually stopped at (a preempted run never reached
    end_step)."""
    return {"final_step": end_step if preempted_at is None else preempted_at,
            "final_loss": final_loss, "preempted_at": preempted_at,
            "steps_per_epoch": steps_per_epoch, "output_dir": output_dir}


def _sync_checkpoint(cfg: dict, path: str) -> None:
    """Off-node durability hook (reference `./s5cmd sync` after each save,
    trainer_base_ds_mp.py:220): run `save_sync_command` with {path}
    substituted, on process 0, after the checkpoint is durably on disk.
    e.g.  save_sync_command: "gsutil -m rsync -r {path} gs://bucket/run/"
    Failures are logged, never fatal — a sync outage must not kill training.
    """
    command = cfg.get("save_sync_command")
    if not command or jax.process_index() != 0:
        return
    import subprocess

    # plain replace (not str.format): the command may contain shell braces
    cmd = command.replace("{path}", path)
    try:
        result = subprocess.run(cmd, shell=True, capture_output=True, text=True,
                                timeout=cfg.get("save_sync_timeout", 1800))
        if result.returncode != 0:
            logger.warning("save_sync_command failed (%d): %s", result.returncode,
                           result.stderr.strip()[-500:])
        else:
            logger.info("checkpoint synced: %s", cmd)
    except Exception as e:  # timeout / spawn failure — never kill training
        logger.warning("save_sync_command error: %r", e)


def _make_evaluator(cfg, mesh, model_cfg, pcfg, stacked_template, attn_fn,
                    get_params):
    """Optional held-out evaluation (cfg `eval_dataset` node + `eval_steps`).

    The reference shipped only dead eval config (`do_eval`, absent evaluator
    classes — SURVEY.md §2.4); this closes that gap with a loss-only pipeline
    pass over an eval loader."""
    eval_cfg = cfg.get("eval_dataset")
    if eval_cfg is None:
        return None
    eval_ds, eval_coll = build_dataset_and_collator(
        {**cfg, "dataset": eval_cfg}, model_cfg)
    mesh_dp = mesh.shape["dp"]
    per_replica = (cfg.get("per_device_eval_batch_size",
                           cfg.get("per_device_train_batch_size", 1))
                   * pcfg.num_microbatches
                   * _packing_factor(cfg))
    eval_loader = DataLoader(eval_ds, eval_coll, per_replica_batch=per_replica,
                             dp_size=mesh_dp, shuffle=False,
                             dp_range=host_dp_shard(mesh))
    if len(eval_loader) == 0:
        raise ValueError("eval dataset too small for one batch")
    eval_fn = jax.jit(pl.make_pipeline_eval_fn(mesh, model_cfg, pcfg,
                                               stacked_template, attn_fn=attn_fn))

    def run_eval():
        total, tokens = 0.0, 0
        for batch in eval_loader:
            loss_sum, count = eval_fn(get_params(), form_global_batch(mesh, batch))
            total += float(loss_sum)
            tokens += int(count)
        return total / max(tokens, 1)  # exact token mean, not mean-of-means

    return run_eval


def _packing_scalars(collator) -> Any:
    """Metrics hook surfacing the packed collator's cumulative drop counters
    (round-3 weak #4: drops warned once per process and never reached the
    metrics stream). Counters are this process's own loader traffic — on a
    pod each host packs its dp shards, so process 0's rate is a same-
    distribution sample, not the global count."""
    if not isinstance(collator, PackedCausalLMCollator):
        return None

    def scalars():
        return {"packing_dropped_total": collator.dropped_total,
                "packing_drop_rate": round(collator.drop_rate(), 4)}

    return scalars


def _host_scalars(collator, loader) -> Any:
    """All host-side per-line counters: the packing drop counters plus the
    loader's record-quarantine count (only when the quarantine is armed —
    an always-zero column on every healthy run would be noise)."""
    packing = _packing_scalars(collator)
    if not loader.quarantine_bad_records:
        return packing

    def scalars():
        out = packing() if packing else {}
        out["data_quarantined_records"] = loader.quarantine_count
        return out

    return scalars


def _train_loop(cfg, model_cfg, mesh, loader, seq_length, resume_step, end_step,
                do_step, do_save, do_eval=None, extra_scalars=None,
                static_scalars=None, monitor=None, data_start=(0, 0),
                health_static=None, profiler=None,
                mem_watch=None, profile_facts=None) -> tuple:
    """The shared step/log/save/profile loop for both optimizer paths.

    `do_step(batch, step, fault=None) -> (loss_scalar, scalars_thunk)`; the
    thunk is only called at logging boundaries so the hot loop never blocks
    on a D2H sync; `fault` forwards the step-site fault verdict (the
    grad_nonfinite chaos op). `do_save(step)` writes a full checkpoint.
    `do_eval() -> float` (optional) runs every `eval_steps`.
    `extra_scalars() -> dict` (optional) contributes host-side counters
    (e.g. packing drop rate) to every metrics line; `static_scalars`
    (optional dict) are run constants (e.g. the schedule's bubble fraction)
    repeated on every line so downstream joins need no second file.
    `monitor` (numerics.NumericsMonitor, optional) feeds the heartbeat's
    numerics fields and the metrics line's counters; its
    `NonfiniteHaltError` is turned into a final checkpoint + re-raise here.
    `data_start` ((epoch, batch), from _resume_data_position) opens the
    repeating loader at the O(1) resume position; `health_static`
    (optional dict, e.g. the run topology) rides on every health.json write.
    `profiler` (profiler.TriggeredProfiler, optional) gets
    each iteration's host wall for the step-time z-score trigger, the
    numerics-anomaly span stream, and a close() on every exit path.
    `profile_facts` (dict, optional, `_profile_window_facts`) rides on the
    `profile_window` span emitted when a `profile_steps` capture closes.
    `mem_watch` (memwatch.MemoryWatch, optional — the memory
    observatory) samples the live memory sources after every step and
    feeds the OOM snapshot; the RESOURCE_EXHAUSTED handler below runs
    with or without it (the snapshot degrades to the live poll alone).
    """
    output_dir = cfg["output_dir"]
    # Scalars are replicated across processes: process 0 writes for the pod
    # (reference rank-0 gating, trainer_base_ds_mp.py:360-374).
    writer = (MetricsWriter(output_dir, config_snapshot=cfg,
                            use_wandb=cfg.get("use_wandb", False),
                            use_tensorboard=cfg.get("use_tensorboard", False))
              if jax.process_index() == 0 else NullMetricsWriter())
    # This host's batches cover only its own dp shards; scale the meter's
    # counts to the global batch (n_chips is the global chip count).
    _, local_dp = host_dp_shard(mesh)
    meter = Throughput(model_cfg, seq_length, n_chips=mesh.devices.size,
                       global_scale=mesh.shape["dp"] / local_dp)
    logging_steps = cfg.get("logging_steps", 10)
    save_steps = cfg.get("save_steps", 0)

    # ---- run-health telemetry (docs/OBSERVABILITY.md) ---------------------
    # Everything since trace.configure() — model build, restore, data probe —
    # is the init bucket; record it retroactively as a span so the offline
    # goodput report's bucket sum matches wall-clock.
    rec = trace.recorder()
    if profiler is not None:
        # numerics-anomaly spans become bounded captures (utils/profiler.py)
        rec.add_listener(profiler.on_span)
    rec.emit("init", rec.configured_at, time.time() - rec.configured_at)
    # Resume carries the previous incarnation's cumulative buckets forward:
    # goodput stays a whole-run number, and the wall time the preemption
    # threw away surfaces as badput instead of vanishing with the restart.
    prior = trace.load_health(output_dir) if resume_step else None
    init_secs = time.time() - rec.configured_at
    clock = trace.RunClock(prior=(prior or {}).get("clock"),
                           already_elapsed=init_secs)
    clock.add("init", init_secs)
    rec.add_listener(clock.on_span)
    # the numerics monitor's health fields are LIVE: it keeps mutating its
    # own dict between health.json writes
    heartbeat = (trace.Heartbeat(output_dir, clock,
                                 interval=cfg.get("health_interval", 10.0),
                                 extra=(monitor.health_fields
                                        if monitor is not None else None),
                                 static=health_static)
                 if jax.process_index() == 0 else None)
    peak_bytes, peak_src = trace.device_peak_bytes()
    logger.info("device memory telemetry: %s (%s)",
                "unavailable" if peak_bytes is None else f"{peak_bytes} B peak",
                peak_src)

    # Optional profiler capture window: profile_steps: [start, stop] writes a
    # tensorboard/Perfetto trace under <output_dir>/profile (SURVEY.md §5.1 —
    # the reference had only DeepSpeed's steps_per_print throughput line).
    # Clamped into [resume_step, end_step] so resume/short runs stay safe.
    profile_window = cfg.get("profile_steps")
    if profile_window:
        lo = max(int(profile_window[0]), resume_step)
        hi = min(int(profile_window[1]), end_step)
        if lo >= hi:
            logger.info("profile_steps %s empty after clamping to [%d, %d); "
                        "skipping trace", list(profile_window), resume_step, end_step)
            profile_window = None
        else:
            profile_window = (lo, hi)
    trace_active = False
    trace_t0 = trace_first = 0

    def close_profile_window(through_step: int) -> None:
        """Stop the capture and emit its one retroactive span: the window on
        the host's clock, the steps it covers, and the compile's facts."""
        jax.profiler.stop_trace()
        rec.emit("profile_window", ts=trace_t0, dur=time.time() - trace_t0,
                 first_step=trace_first + 1,
                 steps=through_step - trace_first, **(profile_facts or {}))

    # O(1) data resume (docs/RESILIENCE.md "Elastic resume"): the loader
    # opens directly at (epoch, batch) by index arithmetic — the reference's
    # batch-by-batch fast-forward replay (reference :345-351, "minutes at
    # scale") and its PR 1 descendant are gone.
    start_epoch, start_batch = data_start
    it: Iterator = iter(RepeatingLoader(loader, start_epoch=start_epoch,
                                        start_batch=start_batch))
    it = PrefetchIterator(it, depth=cfg.get("prefetch_depth", 2))

    # Preemption-aware save (SURVEY.md §5.3): on a preemption notice —
    # Python-handler flag (single-process / pre-init window) or the
    # coordination service's sync point (pod) — finish the current step,
    # checkpoint, exit cleanly so the next run resumes instead of losing the
    # interval. Handlers are installed by run_training before distributed
    # init; see _install_preemption_handlers.
    losses: list = []  # jax scalars; fetched only at logging boundaries
    final_loss = float("nan")
    preempted_at = None  # the step THIS process observed the stop at
    last_saved = -1
    completed = resume_step  # steps whose update the live state reflects
    # Pods agree on preemption via a host collective; running it every step
    # would sync the hot loop, so check on a fixed cadence — the SAME steps on
    # every host (the decision must never depend on a host-local flag, or the
    # allgather call counts diverge and the pod hangs).
    check_every = max(int(cfg.get("preempt_check_every", 10)), 1)
    # actions.resize_on_request (docs/RESILIENCE.md "Actuation"): poll for
    # the autoscaler's resize.request on the same uniform cadence. The
    # config is process-uniform, so the extra _should_stop allgather below
    # is called identically everywhere — collective counts stay aligned.
    from llama_pipeline_parallel_tpu.utils.actions import TrainActions

    resize_watch = TrainActions.from_cfg(cfg.get("actions")).resize_on_request
    _LAST_EVAL.clear()  # a fresh loop must not inherit a prior run's eval
    window_t0 = time.perf_counter()
    window_overhead = 0.0  # compile/eval/ckpt seconds to exclude from step_time

    try:
        for step in range(resume_step, end_step):
            # per-iteration host wall, taken BEFORE the fault hook so a
            # `slow` chaos rule at the step site lands in the measured wall
            # the profiler's z-score trigger watches (docs/OBSERVABILITY.md
            # "Triggered capture")
            iter_t0 = time.perf_counter()
            # chaos hook: a `die`/`stall` rule at a chosen step simulates
            # preemption or a hung pod at an exact, reproducible point; a
            # `grad_nonfinite` verdict rides into do_step to poison the
            # jitted step's gradients (numerics observatory chaos input)
            fault_verdict = faults.fire("step", step=step)
            if fault_verdict == "oom":
                # synthetic allocation failure (chaos op `oom`): raised
                # HERE, inside the loop's try, so it exercises the REAL
                # RESOURCE_EXHAUSTED forensics path below — snapshot,
                # supervisor `oom` outcome, fleet `oom_recent` alert
                raise RuntimeError(
                    f"RESOURCE_EXHAUSTED: Out of memory while running "
                    f"step {step} (injected oom fault)")
            # The sync point must be polled EVERY step with the loop's step id
            # (the protocol computes max-step+1 as the one safe stop step for
            # the whole pod); it returns True on every process at that same
            # step. The allgather vote covers Python-handler signals on its
            # own cadence.
            preempt_notice = _preemption_notice(step)
            check_now = jax.process_count() == 1 or step % check_every == 0
            # Both stop inputs are evaluated into locals BEFORE combining:
            # _should_stop's allgather is a collective, so its call count must
            # be identical on every process every step. Short-circuiting it
            # behind preempt_notice would only be safe because the sync point
            # fires process-uniformly — keep the uniformity structural.
            stop_vote = check_now and _should_stop(bool(_STOP_SIGNALS))
            # the resize vote rides the same cadence and allgather shape:
            # any process seeing the request stops ALL of them at this step
            resize_vote = (resize_watch and check_now
                           and _should_stop(_resize_requested(output_dir)))
            if preempt_notice or stop_vote or resize_vote:
                logger.warning("%s; checkpointing at step %d and "
                               "exiting for clean resume",
                               "resize request" if resize_vote
                               else "preemption signal", step)
                preempted_at = step
                do_save(step, final=True)
                last_saved = end_step  # suppress the save_final duplicate
                if resize_vote and jax.process_index() == 0:
                    # ack AFTER the save commits: the request must outlive
                    # a crash-mid-save so the next incarnation re-honors it
                    _ack_resize_request(output_dir)
                break
            if profile_window and not trace_active and step >= profile_window[0] \
                    and step < profile_window[1]:
                trace_t0, trace_first = time.time(), step
                jax.profiler.start_trace(os.path.join(output_dir, "profile"))
                trace_active = True
                trace.wallclock_anchor()  # every capture holds at least one
            with trace.span("data_wait", step=step):
                batch = next(it)
            try:
                if step == resume_step:
                    # First step: trace+XLA-compile happen synchronously
                    # inside the dispatch, and the value barrier catches the
                    # rest — so the whole first-step wall time lands in the
                    # compile bucket instead of smearing into the first
                    # window's train time.
                    with trace.span("compile_block", step=step) as sp:
                        loss, scalars_thunk = do_step(batch, step + 1,
                                                      fault=fault_verdict)
                        jax.block_until_ready(loss)
                    window_overhead += sp["dur"]  # compile not in step_time
                else:
                    with trace.span("step_dispatch", step=step):
                        loss, scalars_thunk = do_step(batch, step + 1,
                                                      fault=fault_verdict)
            except numerics.NonfiniteHaltError:
                # the monitor raises AFTER do_step committed this step's
                # state — record that so the halt save labels it correctly
                completed = step + 1
                raise
            completed = step + 1
            if mem_watch is not None:
                # host-side poll only (memory_stats + RSS) — never touches
                # the dispatched computation; `memory.every` rate-limits it
                mem_watch.sample(step + 1)
            if profiler is not None:
                # compile step excluded from the z-score baseline (a 100x
                # wall would deflate every later z); it still advances an
                # open capture window
                profiler.observe_step(
                    step + 1, None if step == resume_step
                    else time.perf_counter() - iter_t0)
            if heartbeat is not None:
                heartbeat.beat(step + 1)
            if trace_active and (step + 1 >= profile_window[1] or step + 1 == end_step):
                jax.block_until_ready(loss)
                close_profile_window(step + 1)
                trace_active = False
                logger.info("profiler trace written to %s/profile", output_dir)
            losses.append(loss)
            mask = batch.get("attention_mask")
            meter.update(batch["input_ids"].size,
                         real_tokens=None if mask is None
                         else int((mask != 0).sum()))
            if (step + 1) % logging_steps == 0 or step + 1 == end_step:
                n_window = len(losses)
                # the value fetch is the loop's sync point: its wall time is
                # the device executing the window's steps (minus what the
                # dispatch/data spans already took on the host side)
                with trace.span("device_step", step=step + 1, steps=n_window):
                    final_loss = float(losses[-1])
                # the wall clock on a running capture's clock, so the
                # capture joins spans.jsonl (docs/OBSERVABILITY.md)
                trace.wallclock_anchor()
                # pure stepping time: compile/eval/ckpt wall time inside the
                # window is subtracted, so step_time tracks the train rate
                # (those phases are visible in the goodput buckets instead)
                step_dur = max(time.perf_counter() - window_t0 - window_overhead,
                               0.0) / max(n_window, 1)
                window_t0 = time.perf_counter()
                window_overhead = 0.0
                peak_bytes, _ = trace.device_peak_bytes()
                writer.log(step + 1, {"loss": float(np.mean([float(l) for l in losses])),
                                      **scalars_thunk(), **meter.read_and_reset(),
                                      **(extra_scalars() if extra_scalars else {}),
                                      **(static_scalars or {}),
                                      **(monitor.scalars() if monitor is not None
                                         else {}),
                                      "goodput": round(clock.goodput(), 4),
                                      "step_time": round(step_dur, 4),
                                      "device_peak_bytes": peak_bytes})
                if heartbeat is not None:
                    heartbeat.beat(step + 1, step_dur)
                losses.clear()
            eval_steps = cfg.get("eval_steps", 0)
            if do_eval is not None and eval_steps and (step + 1) % eval_steps == 0:
                with trace.span("eval", step=step + 1) as sp:
                    eval_loss = do_eval()
                writer.log(step + 1, {"eval_loss": eval_loss})
                # later checkpoints carry this as their deployment gate
                _LAST_EVAL.update(step=step + 1, loss=float(eval_loss))
                window_overhead += sp["dur"]
            if save_steps and (step + 1) % save_steps == 0:
                t_save = time.perf_counter()
                do_save(step + 1)
                last_saved = step + 1
                window_overhead += time.perf_counter() - t_save
        if monitor is not None:
            # drain the lag-1 queue: the LAST step's nonfinite verdict must
            # fire (halt included) before the final save decides what state
            # it is committing
            monitor.flush()
    except numerics.NonfiniteHaltError as e:
        # halt_on_nonfinite: the nonfinite update was already where-skipped
        # in-graph, so the live state is finite — commit it through the PR 2
        # checkpoint path, then exit nonzero (the supervisor's crash-loop
        # budget sees a short, clean abort instead of hours of NaN steps).
        # Save under `completed`, NOT e.step: the monitor's lag-1 fetch means
        # the halt surfaces one step after the nonfinite one, and by then the
        # state already reflects that later (clean, or also-skipped) step —
        # labeling it e.step would make a resume re-apply a batch.
        logger.error("halting on nonfinite gradients at step %d; writing a "
                     "final checkpoint at step %d before exiting nonzero",
                     e.step, completed)
        do_save(completed, final=True)
        raise
    except Exception as e:
        if not memwatch_mod.is_resource_exhausted(e):
            raise
        # OOM forensics (docs/OBSERVABILITY.md "Memory"): the process is
        # about to die — write the bounded snapshot FIRST (the supervisor
        # labels the incarnation `oom` off its mtime, the fleet observatory
        # alerts on it), then re-raise the original error. No final save:
        # after a real allocation failure the device state is not
        # trustworthy, and a hung save would turn a crisp abort into a hang.
        logger.error("allocation failure at step %d; writing OOM snapshot "
                     "to %s before exiting", completed,
                     memwatch_mod.oom_dir(output_dir))
        memwatch_mod.dump_oom_snapshot(output_dir, completed, e,
                                       memwatch=mem_watch)
        if profiler is not None:
            profiler.trigger("oom", completed)
        raise
    finally:
        if trace_active:  # preemption break / exception inside the window
            close_profile_window(completed)
            logger.info("profiler trace (early exit) written to %s/profile", output_dir)
        if profiler is not None:
            rec.remove_listener(profiler.on_span)
            profiler.close()  # a capture window open at exit is finalized
        if mem_watch is not None:
            mem_watch.close()
        if monitor is not None:
            monitor.close()
        loader.close_ledger()  # repeated in-process runs must not leak fds
        writer.close()
        if heartbeat is not None:
            heartbeat.stop()  # kills the daemon on every exit path; write()
            # below still works for the final save's post-stop refresh
        # The loop is over on every path out of here: nothing re-checks
        # _STOP_SIGNALS anymore, so holding the graceful handlers would
        # silently swallow a Ctrl+C during the final save or during
        # run_training's async-commit join on the exception path. Hand the
        # signals back (pre-refactor behavior: an interrupt there raises
        # KeyboardInterrupt immediately).
        _release_preemption_handlers()
    if cfg.get("save_final", True) and last_saved != end_step:
        do_save(end_step, final=True)
        if heartbeat is not None:  # clock listener saw the ckpt_save span;
            heartbeat.write()      # fold the final save into health.json
    return final_loss, preempted_at


def _preemption_notice(step: int) -> bool:
    """Poll the JAX coordination service's preemption sync point.

    Once `jax.distributed.initialize()` registers the preemption sync
    manager, its C++ notifier owns SIGTERM (preemption_notifier.cc) — the
    Python handlers never fire, no matter when they were installed. The
    notifier feeds the service, which propagates the notice to every process
    and picks one safe stop step (max current step + 1); this returns True
    on all processes at exactly that step. Without the sync manager
    (single-process, or service disabled by config) it is a no-op and the
    Python-handler path applies."""
    if not _cpp_notifier_owns_sigterm():
        return False
    from jax.experimental import multihost_utils

    return bool(multihost_utils.reached_preemption_sync_point(step))


# the most recent eval_loss, keyed into every later checkpoint's meta.json
# (via do_save's extra_meta) — the continuous-deployment gate's input
# (utils/actions.Deployer): a deploy/rollback decision needs the QUALITY of
# a checkpoint, not just its existence. A module box, like _STOP_SIGNALS:
# the eval happens in _train_loop but the save closures live in its callers.
_LAST_EVAL: dict = {}


def _eval_meta() -> dict:
    """extra_meta contribution: the last eval_loss (and the step it was
    measured at) — empty before the first eval so a never-evaluated run
    writes no fabricated gate value."""
    if "loss" in _LAST_EVAL:
        return {"eval_loss": _LAST_EVAL["loss"],
                "eval_step": _LAST_EVAL["step"]}
    return {}


def _resize_requested(output_dir: str) -> bool:
    """Poll for an actuator's `resize.request` drop (utils/actions): the
    fleet autoscaler asking this trainer to step down/up a ladder rung at
    a step boundary instead of eating a SIGTERM mid-step."""
    from llama_pipeline_parallel_tpu.utils.actions import RESIZE_REQUEST_NAME

    return os.path.exists(os.path.join(output_dir, RESIZE_REQUEST_NAME))


def _ack_resize_request(output_dir: str) -> None:
    """Rename `resize.request` -> `resize.request.ack` (atomic on POSIX):
    the actuator/test sees the trainer honored the request exactly once;
    a crash before the rename leaves the request for the relaunched
    incarnation — at-least-once, and the rename dedups."""
    from llama_pipeline_parallel_tpu.utils.actions import (
        RESIZE_ACK_NAME,
        RESIZE_REQUEST_NAME,
    )

    try:
        os.replace(os.path.join(output_dir, RESIZE_REQUEST_NAME),
                   os.path.join(output_dir, RESIZE_ACK_NAME))
    except OSError:
        pass  # already acked by a peer process, or never landed locally


def _should_stop(local_flag: bool) -> bool:
    """Agree on preemption across hosts: a one-host signal must stop ALL
    processes at the same step, or the save barrier deadlocks against peers
    still running the jitted step's collectives."""
    if jax.process_count() == 1:
        return local_flag
    from jax.experimental import multihost_utils

    flags = multihost_utils.process_allgather(np.asarray(local_flag, np.int32))
    return bool(np.any(flags))


def _run_offload(cfg, mesh, model_cfg, manifest, pcfg, ocfg, dataset, collator,
                 loader, end_step, stacked_template, mgr, ncfg=None,
                 monitor=None) -> dict:
    """Host-offloaded-optimizer training setup (reference ZeRO-offload path,
    conf yaml:160-162): fp32 masters + Adam moments in host DRAM via
    optim/offload.py; the device holds only the bf16 working copy and runs
    loss+grad. Grads stream D2H (async, overlapped with the host kernel),
    fresh bf16 params H2D (host-cast, half the bytes), every step. Masters
    are sharded per process: each host keeps/updates only the shards its
    devices hold (the ZeRO-offload distribution of the reference's 800 GB
    65B state, README.md:70-71).

    `optimizer_offload_zero2: true` (dp>1): masters, moments, AND the
    gradient outputs are additionally dp-sharded on each leaf's rightmost
    free dim (reference ZeRO-2 `reduce_scatter: True`, conf yaml:152-159,
    lifted to the host tier) — host DRAM, grad D2H bytes, and host AdamW
    work all drop to 1/dp per host; the device re-gathers the bf16 working
    copy over the dp axis once per step (ICI all-gather)."""
    from llama_pipeline_parallel_tpu.optim.offload import HostOffloadAdamW

    output_dir = cfg["output_dir"]
    if ncfg is None:
        ncfg = numerics.NumericsConfig.from_cfg(cfg.get("numerics"))
    zero2 = bool(cfg.get("optimizer_offload_zero2"))
    if zero2 and mesh.shape["dp"] == 1:
        logger.info("optimizer_offload_zero2 has no effect at dp=1; "
                    "running the plain offload layout")
        zero2 = False
    if zero2:
        z2_shardings = ts.specs_to_shardings(
            mesh, ts.zero2_param_specs(stacked_template, mesh))
        # reshard the freshly-initialized masters-to-be dp-sharded BEFORE
        # the host copies them out; each host then stores only 1/dp.
        # (No donation: a replicated->sharded reshard can never alias
        # layouts, and the dead donate only emits unusable-buffer warnings.)
        stacked_template = jax.jit(
            lambda p: p, out_shardings=z2_shardings)(stacked_template)
    # device-side grad norm (default): frees the fused step to stream
    # leaf-by-leaf instead of waiting for the full-tree grad D2H before the
    # first AdamW; offload_device_norm: false restores the host fp64 norm
    host = HostOffloadAdamW(ocfg,
                            skip_nonfinite=ncfg.enabled,
                            device_norm=cfg.get("offload_device_norm", True))
    host.init(stacked_template)
    # fp32 masters now live on the host; drop the device fp32 init copy and
    # keep only SHARDED abstract structs as the template (HBM holds just the
    # bf16 working copy; restores place arrays pre-sharded from these)
    stacked_template = host.abstract_tree()

    resume_step = 0

    def _restore_offload(resume: int) -> int:
        meta = mgr.load_meta(resume)
        if not meta.get("has_optimizer_state"):
            raise ValueError(
                f"checkpoint-{resume} has no optimizer state (module-only / "
                f"converter output); point model_name_or_path at it instead")
        layout = meta.get("opt_layout")
        if layout != "offload_parts":
            writer = ("the fused (optax) optimizer" if layout is None
                      else f"an unknown optimizer layout {layout!r}")
            raise ValueError(
                f"checkpoint-{resume}'s optimizer state was written by "
                f"{writer}, not the current offload layout. To continue "
                f"those weights under the offloaded optimizer, point "
                f"model_name_or_path at this checkpoint and use a fresh "
                f"output_dir (module-only warm start; optimizer moments "
                f"restart).")
        # Multi-host restore works end to end: the templates carry mesh
        # shardings (host.abstract_tree + the sharding-preserving canonical
        # reshape), Orbax restores each host's shards locally, and _scatter
        # reads only addressable shards — executed across real processes by
        # tests/test_multiprocess.py::test_offload_trainer_two_process_resume.
        # load_params runs the integrity pass over the WHOLE dir, so the
        # moments restore below skips its own (verify=False — hash once).
        host.load_masters(mgr.load_params(resume, stacked_template, manifest))
        m, v, step_count = mgr.load_offload_moments(resume, stacked_template,
                                                    manifest, verify=False)
        host.load_state_dict({"m": m, "v": v, "step_count": step_count})
        return resume

    topology = _topology_meta(mesh, pcfg, manifest)
    restored = (_restore_with_fallback(mgr, _restore_offload)
                if cfg.get("resume", True) else None)
    if restored is not None:
        resume_step = restored
        logger.info("resumed offloaded state from checkpoint-%d", resume_step)
        _note_topology_change(mgr, resume_step, topology)
    elif cfg.get("model_name_or_path"):
        warm = CheckpointManager(cfg["model_name_or_path"])
        warm_step = warm.latest_step()
        if warm_step is None:
            raise FileNotFoundError(f"no checkpoint under {cfg['model_name_or_path']}")
        host.load_masters(warm.load_params(warm_step, stacked_template, manifest))
        logger.info("warm-started offloaded masters from %s", cfg["model_name_or_path"])

    seq_length = int(collator([dataset[0]])["input_ids"].shape[1])
    if seq_length % mesh.shape["sp"]:
        raise ValueError(f"sequence length {seq_length} must divide into "
                         f"sp={mesh.shape['sp']} equal slabs")
    attn_fn = select_attention(cfg.get("attention", "auto"), seq_length, mesh,
                               sequence_parallel=cfg.get("sequence_parallel", "ring"),
                               model_cfg=model_cfg,
                               packed=_packing_factor(cfg) > 1,
                               micro_batch=cfg.get("per_device_train_batch_size", 1))
    prof, mem_watch = _make_observatory(
        cfg, output_dir,
        stash_bytes=pl.host_stash_bytes(pcfg, *pl.stash_dims(
            cfg.get("per_device_train_batch_size", 1), seq_length,
            mesh.shape["sp"], model_cfg.hidden_size, model_cfg.dtype)))
    loss_and_grad = pl.make_pipeline_loss_and_grad(
        mesh, model_cfg, pcfg, stacked_template, attn_fn=attn_fn,
        collect_stats=ncfg.enabled)
    from jax.sharding import NamedSharding, PartitionSpec

    def _replicate_stats(stats):
        # stat outputs must be replicated (the shard_map leaves act stats
        # pp-sharded): the monitor's host read requires every pod process
        # to hold the full few-hundred-float value
        return jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, PartitionSpec())), stats)

    # The grad_nonfinite chaos op must poison grads BETWEEN loss+grad and
    # the stats, which forces a separate stats dispatch; steady-state runs
    # (no such rule) fold numerics.step_stats into the ONE jitted loss+grad
    # program instead — no second traversal of the gradient tree per step.
    poison_on = faults.has_rule("step", "grad_nonfinite")

    def _grad_with_stats(p, batch):
        loss, grads, act_stats = loss_and_grad(p, batch)
        stats = numerics.step_stats(p, grads,
                                    virtual_stages=pcfg.virtual_stages)
        stats.update(act_stats)
        return loss, grads, _replicate_stats(stats)

    def _grad_chaos(p, batch):
        # chaos mode computes grad/param stats in a separate post-poison
        # dispatch, but the act stats still leave here — replicated, or a
        # pod process couldn't read its non-addressable pp shards
        loss, grads, act_stats = loss_and_grad(p, batch)
        return loss, grads, _replicate_stats(act_stats)

    grad_out = loss_and_grad if not ncfg.enabled else (
        _grad_chaos if poison_on else _grad_with_stats)
    if zero2:
        # grads leave the device dp-SHARDED: GSPMD turns the shard_map's dp
        # psum + the output constraint into a reduce-scatter, and each host
        # then D2H-pulls only its 1/dp of every gradient tree
        out_shardings = ((None, z2_shardings, None) if ncfg.enabled
                         else (None, z2_shardings))
        grad_fn = jax.jit(grad_out, out_shardings=out_shardings)
        # the pipeline consumes dp-REPLICATED bf16 params: re-gather the
        # dp-sharded upload over ICI once per step
        replicated = ts.specs_to_shardings(
            mesh, pl.stage_param_specs(stacked_template,
                                       tp=mesh.shape["tp"] > 1))
        to_replicated = jax.jit(lambda p: p, out_shardings=replicated)
    else:
        grad_fn = jax.jit(grad_out)
        to_replicated = lambda p: p

    device_params_box = [to_replicated(host.device_params(model_cfg.dtype))]
    profile_facts = _profile_window_facts(cfg, pcfg, mesh)
    # chaos-only second dispatch: the stats must see the POISONED grads
    stats_fn = (jax.jit(
        lambda p, g: _replicate_stats(numerics.step_stats(
            p, g, virtual_stages=pcfg.virtual_stages)))
        if ncfg.enabled and poison_on else None)
    poison_fn = jax.jit(numerics.poison_grads)

    def do_step(batch, step, fault=None):
        gbatch = form_global_batch(mesh, batch)
        # the offload path's device program is loss+grad (the optimizer
        # lives on the host): same one-shot AOT capture as the fused path's
        _note_compiled(
            "loss_and_grad",
            lambda: grad_fn.lower(device_params_box[0], gbatch),
            mem_watch, profile_facts)
        stats = None
        if not ncfg.enabled:
            loss, grads = grad_fn(device_params_box[0], gbatch)
        elif not poison_on:
            loss, grads, stats = grad_fn(device_params_box[0], gbatch)
        else:
            loss, grads, act_stats = grad_fn(device_params_box[0], gbatch)
            stage = numerics.fault_stage(fault)
            if stage >= 0:
                grads = poison_fn(grads, stage)
            stats = stats_fn(device_params_box[0], grads)
            stats.update(act_stats)
        # fused step: per-leaf AdamW overlaps the previous leaf's bf16 cast
        # + H2D upload instead of a serial update-all-then-upload-all
        # (a nonfinite global norm skips the masters update, see
        # HostOffloadAdamW.skip_nonfinite)
        device_params_box[0] = to_replicated(
            host.update_and_refresh(grads, model_cfg.dtype))
        if monitor is not None:
            monitor.observe(step, loss, host.last_grad_norm, stats)
        return loss, lambda: {"lr": host.last_lr,
                              "grad_norm": host.last_grad_norm,
                              **{k: round(v, 2)
                                 for k, v in host.last_timings.items()}}

    data_start = (_resume_data_position(mgr, resume_step, loader,
                                        len(dataset), cfg.get("seed", 42))
                  if resume_step else (0, 0))
    data_delta = (data_start[0] * max(len(loader), 1)
                  + data_start[1]) - resume_step

    def do_save(step, final=False):
        # the offload save streams from host masters that the next optimizer
        # step mutates IN PLACE — it must block regardless of async_save
        barrier("pre-save")
        path = mgr.save_offload(step, host, manifest, model_cfg,
                                keep_last=cfg.get("save_total_limit"),
                                extra_meta={"topology": topology,
                                            "data_state": _data_state(
                                                step, loader, len(dataset),
                                                cfg.get("seed", 42),
                                                data_delta),
                                            **_eval_meta()})
        _sync_checkpoint(cfg, path)

    do_eval = _make_evaluator(cfg, mesh, model_cfg, pcfg, stacked_template,
                              attn_fn, lambda: device_params_box[0])
    off_static = _offload_static(pcfg, *pl.stash_dims(
        cfg.get("per_device_train_batch_size", 1), seq_length,
        mesh.shape["sp"], model_cfg.hidden_size, model_cfg.dtype))
    final_loss, preempted_at = _train_loop(
        cfg, model_cfg, mesh, loader, seq_length,
        resume_step, end_step, do_step, do_save, do_eval,
        extra_scalars=_host_scalars(collator, loader),
        static_scalars={**_schedule_static_scalars(pcfg), **off_static},
        monitor=monitor, data_start=data_start,
        health_static={**_schedule_health_static(pcfg, topology),
                       **off_static},
        profiler=prof, mem_watch=mem_watch,
        profile_facts=profile_facts)
    _write_perf_rows(output_dir, mem_watch)
    return _summarize(final_loss, preempted_at, end_step, len(loader),
                      output_dir)
