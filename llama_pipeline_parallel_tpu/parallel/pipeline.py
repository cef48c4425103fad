"""Pipeline-parallel training schedule, TPU-native.

This module replaces the entire DeepSpeed pipeline engine surface the reference
exercises with `engine.train_batch(data_iter)` (reference
trainer_base_ds_mp.py:354): the microbatched pipeline schedule, inter-stage
activation/gradient transport, loss reduction, and data-parallel gradient
reduction — all inside ONE jitted SPMD program.

Design (and why it is not a translation of DeepSpeed):
- Stages live on the `pp` axis of a `jax.sharding.Mesh`. Every decoder layer's
  parameters are stacked on a leading `[num_stages, layers_per_stage, ...]`
  axis and sharded over `pp` — each device holds exactly its stage's slice
  (the analogue of `LayerSpec` lazy per-rank materialization, reference
  models/llama_ds_mp_wrap.py:209-224, but by sharding, not by construction
  order).
- The schedule is DATA, not a code path (since PR 11; docs/SCHEDULES.md
  "Solver schedules"): every hand-written-backward schedule is a typed
  per-stage unit sequence (parallel/schedule.py UnitSchedule) executed by
  ONE interpreter (`_pipeline_units_local`) — skewed microbatch loops where
  activations hop to the next stage via `jax.lax.ppermute` over the ICI
  ring (the analogue of NCCL P2P send/recv):
  * "1f1b" (default) — the schedule DeepSpeed's engine runs: forward and
    backward interleave (F-only warmup, F+B steady, B-only drain scans)
    with a hand-written per-stage `jax.vjp` backward, bounding in-flight
    activations at min(2S-1, M) stage inputs.
  * "interleaved_1f1b" — Megatron-style virtual pipeline stages: each stage
    owns `virtual_stages` round-robin layer chunks, the activation laps the
    ring v times per microbatch, and the flush bubble drops ~vx
    (docs/SCHEDULES.md).
  * "zb1" — the interleaved clock with the backward DECOMPOSED into B
    (input-grad) and W (weight-grad) units, ZB-H1 / 2BP-style: B units
    stay on the critical path, W units replay from stashed residuals in a
    trailing collective-free W segment, dropping the analytic bubble
    another third below interleaved (docs/SCHEDULES.md has the unit
    accounting and the W-stash bound).
  * "solver" — a loaded sequence file (preflight --select --emit-schedule):
    anything the validator accepts, including per-unit selective offload
    of the W residuals and reordered W placements.
  The named three resolve to canonical generated sequences: every live
  unit runs on the tick the deleted hand-written scans ran it.
  * "gpipe" — forward-only scan; JAX autodiff yields the backward pipeline
    automatically (the transpose of `ppermute` is the reverse `ppermute`),
    at the cost of O(M) stored boundary activations. The one non-sequence
    schedule.
  Per-layer remat (`jax.checkpoint`) bounds within-stage activations,
  mirroring `deepspeed.checkpointing.checkpoint`
  (reference models/llama_ds_mp_wrap.py:57,166).
- Embed / final-norm / lm-head params are replicated over `pp`; only the
  first/last stage's contribution survives masking, and their gradients are
  psum'd over `pp` so replicas stay bit-identical (replaces the reference's
  first/last-stage data-feeding special cases, trainer_base_ds_mp.py:309-336).
- The loss is the exact global token-mean: per-shard (sum, count) pairs are
  psum'd over (pp, dp) and divided once — unlike the reference, whose
  microbatch-mean-of-means is only approximate under uneven padding.
- DP gradient reduction: `psum` over `dp` (the analogue of the engine's
  allreduce; ZeRO-1-style opt-state sharding happens in optim/, over the same
  axis the reference shards over, conf yaml zero_optimization block).

Per-tick boundary costs: under both schedules, embed (1f1b only) and the
final-norm/lm-head/loss head run under `lax.cond` on the stage index, so
ONLY the first/last stage pays them (no masked replicated compute). Under
tp>1 the cond moves INSIDE the vocab-parallel CE: the [d, V/tp] matmul and
the exp/gather statistics are stage-gated while the tp collectives
(`tp_copy` backward psum, `tp_max`, `tp_reduce`) stay unconditional — the
no-collectives-in-divergent-branches rule constrains the collectives, not
the matmul feeding them (see _vocab_parallel_token_loss).
"""

from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.models.llama.manifest import StageManifest
from llama_pipeline_parallel_tpu.ops.attention import attention
from llama_pipeline_parallel_tpu.ops.pallas_common import VMEM_LIMIT_BYTES
from llama_pipeline_parallel_tpu.ops.rope import rope_cos_sin
from llama_pipeline_parallel_tpu.parallel.sp import make_sp_attention
from llama_pipeline_parallel_tpu.parallel.mesh import (
    AXIS_DP,
    AXIS_PP,
    AXIS_SP,
    AXIS_TP,
)
from llama_pipeline_parallel_tpu.parallel import schedule as usched
from llama_pipeline_parallel_tpu.utils import host_stash, trace

Params = dict
Batch = dict


SCHEDULES = ("1f1b", "interleaved_1f1b", "zb1", "solver", "gpipe")

# The schedules executed by the unit-sequence INTERPRETER
# (_pipeline_units_local) from a generated/loaded UnitSchedule
# (parallel/schedule.py); "gpipe" stays the AD-of-the-forward-loop path.
UNIT_SCHEDULES = ("1f1b", "interleaved_1f1b", "zb1", "solver")


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Schedule knobs (reference: `num_stages` conf yaml:24,
    `gradient_accumulation_steps` conf yaml:78 = microbatches per step)."""

    num_stages: int
    num_microbatches: int
    # Per-layer jax.checkpoint inside the backward. NOTE: the "1f1b" schedule
    # already checkpoints at STAGE granularity (stage inputs buffered, stage
    # recomputed in backward — DeepSpeed's activation-checkpointing contract),
    # so under 1f1b this knob only bounds the TRANSIENT within-stage
    # activations of the one microbatch being backpropped, at the cost of an
    # extra forward per tick. Worth it for long sequences (16k), wasteful at
    # short ones. Under "gpipe" it is the classic remat and usually required.
    remat: bool = True
    remat_policy: str = "nothing_saveable"
    # "1f1b" (default): one-forward-one-backward with a hand-written backward
    # — in-flight activations bounded at min(2*num_stages-1, M) stage inputs
    # regardless of M, with the single (num_stages-1)-tick flush bubble (the
    # schedule DeepSpeed's engine runs inside the reference's
    # `engine.train_batch`, trainer_base_ds_mp.py:354).
    # "interleaved_1f1b": the same hand-written backward, but each stage owns
    # `virtual_stages` round-robin layer chunks and the activation rides the
    # pp ring v times per microbatch — the flush bubble drops from
    # (S-1) full-stage tick pairs to (S-1) chunk-tick pairs, ~vx smaller
    # (docs/SCHEDULES.md), at the cost of v x the ring hops and a ring
    # buffer of min(2vS-1, Mv) chunk inputs. Requires an even partition
    # with num_layers % (S*v) == 0 and microbatches-per-flush % S == 0.
    # "zb1": ZB-H1-style zero-bubble decomposition of the interleaved
    # schedule's backward tick into two separately schedulable units — B
    # (input-grad only: the cotangent propagation the UPSTREAM stage is
    # waiting on) and W (weight-grad only, replayed later from a stashed
    # (chunk input, output cotangent) residual). B units stay on the
    # critical-path tick clock; W units queue and drain into a fourth,
    # collective-free phase, so the warmup/drain phases stop paying the
    # weight-grad work the fused backward would mask (docs/SCHEDULES.md;
    # 2BP arxiv 2405.18047, the substrate OptPipe-style solver schedules
    # need). Composes with `virtual_stages` (v=1 is the flat form). Costs
    # a W-stash of 2 x (Mv/accum_chunks) hidden-sized buffers per stage
    # (tools/preflight.py models it) and the W unit's chunk recompute.
    # "gpipe": forward-only scan differentiated by AD — simpler graph, but
    # stores one stage-boundary activation per tick, so memory grows with M.
    schedule: str = "1f1b"
    # Virtual pipeline chunks per stage (interleaved_1f1b / zb1; 1 elsewhere).
    virtual_stages: int = 1
    # Split the microbatches into this many sequential pipeline flushes within
    # ONE jitted step, at the price of one extra (num_stages-1)-tick bubble
    # per chunk. Under "gpipe" this is the only memory bound (chunks=8 at
    # M=256 stores 32 microbatches of activations); under "1f1b" memory is
    # already bounded by the schedule and chunks are rarely worth the bubble.
    accum_chunks: int = 1
    # Attention strategy when the mesh's sp axis > 1: "ring" rotates KV slabs
    # around the ICI ring (parallel/ring_attention.py), "ulysses" re-shards
    # head-wise via all-to-all (parallel/ulysses.py). Ignored at sp=1.
    sequence_parallel: str = "ring"
    # Per-stage decoder-layer counts for UNEVEN partitions (from
    # StageManifest.stage_layer_counts). None -> even split. Used to cond-skip
    # the zero-weight padding slots of the stacked layout when the decoder
    # layer is collective-free (tp=1, sp=1); with collectives inside, padded
    # slots still compute (they are exact identities either way).
    layer_counts: tuple | None = None
    # >1: the last stage's lm-head + CE run vocab-chunked with an online
    # logsumexp (ops/cross_entropy.py) — full [tokens, vocab] fp32 logits are
    # never materialized, cutting the loss head's peak HBM by ~this factor.
    # tp>1 already avoids full logits via the vocab-parallel CE; combining
    # the two is rejected at build time.
    loss_chunks: int = 1
    # `kernels.ce: pallas` — the loss head runs the fused Pallas kernel
    # (ops/pallas_ce.py) instead of the XLA vocab-chunked scan: identical
    # chunking (`loss_chunks` is the vocab tile count; 1 = whole vocab per
    # tile), bit-equal loss, but the per-chunk fp32 logits block and the
    # backward's fp32 dh accumulator stay in VMEM instead of round-tripping
    # HBM (loss_head_bytes models the difference for preflight). tp>1 is
    # rejected like loss_chunks>1 — the vocab-parallel CE already owns that
    # regime.
    kernel_ce: bool = False
    # `kernels.prologue: pallas` — every decoder layer's
    # rms_norm -> RoPE -> q/k/v prologue runs as one fused Pallas kernel
    # (ops/pallas_prologue.py, custom VJP; composes with tp — the tp_copy
    # psum moves inside the op's backward). Parity within the pinned
    # tolerance of docs/KERNELS.md; holds each projection's LOCAL weight
    # shard VMEM-resident, so it targets tp-sharded layers or small models.
    kernel_prologue: bool = False
    # Batches carry PACKING segment ids in `attention_mask` (the packed
    # collator's contract, data/collator.py): under sp the ring strategy then
    # rotates the kv segment slab with its k/v so packed examples never
    # attend across pack boundaries; Ulysses all-gathers the mask either way.
    # At sp=1 both attention backends already read segments from the mask,
    # so this knob only affects the sp wrappers.
    packed: bool = False
    # Tier the zb1 W-queue residual pairs to host DRAM (utils/host_stash.py,
    # config key `offload.wgrad_stash`): each B tick pushes its (chunk input,
    # ring cotangent) pair D2H as it retires, and the W-drain phase
    # prefetches pairs back H2D one unit ahead of the replay consuming them
    # — the wgrad_stash_bytes term leaves HBM, which is what lets the 65B
    # zb1 shape keep its batch rows (conf/llama_65b_pp8_zb1_offload_*.yaml)
    # instead of funding the stash from them. Values round-trip bit-exactly;
    # zb1-only (fused-backward schedules have no W queue).
    offload_wgrad: bool = False
    # Tier the schedules' stage-input ring buffer (the min(2vS-1, Mv)
    # buffered boundary activations awaiting their backward recompute) to
    # host DRAM — bounds the ring's HBM term so longer sequences / larger
    # per-flush M fit per chip. 1f1b/interleaved/zb1 only: gpipe's stored
    # activations are AD-internal (no explicit buffer to hook).
    offload_activations: bool = False
    # `schedule: solver` — the per-flush unit sequence the interpreter
    # executes (a parallel/schedule.py UnitSchedule, emitted by
    # `tools/preflight.py --select --emit-schedule` or loaded from a
    # sequence file via train.py's `schedule_file` key). Carries its own
    # per-unit offload decision vector — the selective-offload
    # generalization of the all-or-nothing `offload.wgrad_stash` boolean
    # (its all-True/all-False extremes ARE the boolean's two settings).
    # Excluded from equality/hash: the sequence is derived data validated
    # for consistency below, not an identity knob.
    unit_schedule: Any = dataclasses.field(default=None, compare=False,
                                           repr=False)

    def __post_init__(self) -> None:
        from llama_pipeline_parallel_tpu.parallel.sp import SP_STRATEGIES

        if self.sequence_parallel not in SP_STRATEGIES:
            raise ValueError(
                f"unknown sequence_parallel {self.sequence_parallel!r}; "
                f"choose one of {SP_STRATEGIES}")
        if self.num_microbatches < 1:
            raise ValueError("num_microbatches must be >= 1")
        if self.num_stages < 1:
            raise ValueError("num_stages must be >= 1")
        if self.schedule not in SCHEDULES:
            raise ValueError(f"unknown schedule {self.schedule!r}; choose one of {SCHEDULES}")
        if self.loss_chunks < 1:
            raise ValueError("loss_chunks must be >= 1")
        if self.accum_chunks < 1 or self.num_microbatches % self.accum_chunks:
            raise ValueError(
                f"accum_chunks={self.accum_chunks} must divide "
                f"num_microbatches={self.num_microbatches}")
        if self.virtual_stages < 1:
            raise ValueError(
                f"virtual_stages must be >= 1, got {self.virtual_stages}")
        if self.virtual_stages > 1 and self.schedule not in (
                "interleaved_1f1b", "zb1", "solver"):
            raise ValueError(
                f"virtual_stages={self.virtual_stages} requires "
                f"schedule=interleaved_1f1b, zb1, or solver "
                f"(got {self.schedule!r})")
        if self.schedule in ("interleaved_1f1b", "zb1", "solver"):
            uneven = (self.layer_counts is not None
                      and len(set(self.layer_counts)) != 1)
            # zb1/solver at v=1 run UNEQUAL partitions through the unit
            # interpreter — the padded stacked layout and per-chunk vjps are
            # layer-count-agnostic, so "unequal stages just change the unit
            # sequence" (ROADMAP item 3). The round-robin chunk layout
            # (interleaved_1f1b, or any v>1) has no uneven form.
            if uneven and (self.schedule == "interleaved_1f1b"
                           or self.virtual_stages > 1):
                raise ValueError(
                    f"{self.schedule} with virtual_stages="
                    f"{self.virtual_stages} requires an even stage "
                    f"partition (the round-robin chunk layout has no "
                    f"uneven form); got layer_counts={self.layer_counts} — "
                    f"unequal stages run under zb1/solver at "
                    f"virtual_stages: 1, or the flat schedules")
            m_flush = self.num_microbatches // self.accum_chunks
            if (self.schedule != "solver" and self.virtual_stages > 1
                    and m_flush % self.num_stages):
                raise ValueError(
                    f"{self.schedule} with virtual_stages="
                    f"{self.virtual_stages} needs microbatches-per-flush "
                    f"({self.num_microbatches}/{self.accum_chunks}="
                    f"{m_flush}) divisible by num_stages={self.num_stages} "
                    f"(the round-robin unit groups hold one microbatch per "
                    f"stage)")
        if self.schedule == "solver":
            us = self.unit_schedule
            if us is None:
                raise ValueError(
                    "schedule: solver needs a unit sequence — load one with "
                    "train.py's schedule_file key or emit one via "
                    "tools/preflight.py --select --emit-schedule")
            m_flush = self.num_microbatches // self.accum_chunks
            mismatches = [
                f"{name}: sequence {got} vs config {want}"
                for name, got, want in (
                    ("num_stages", us.num_stages, self.num_stages),
                    ("virtual_stages", us.virtual_stages,
                     self.virtual_stages),
                    ("microbatches-per-flush", us.num_microbatches, m_flush))
                if got != want]
            if mismatches:
                raise ValueError(
                    f"unit sequence does not fit this run: "
                    f"{'; '.join(mismatches)}")
            if us.stage_costs is not None:
                mine = (tuple(self.layer_counts) if self.layer_counts
                        is not None else None)
                theirs = tuple(us.stage_costs)
                if len(set(theirs)) != 1 and theirs != mine:
                    raise ValueError(
                        f"unit sequence was generated for stage layer "
                        f"counts {theirs} but this run partitions as "
                        f"{mine or 'even'} — re-emit the sequence for "
                        f"this partition (tools/preflight.py "
                        f"--emit-schedule)")
            if self.offload_wgrad:
                raise ValueError(
                    "schedule: solver carries its own per-unit offload "
                    "decision vector — drop offload.wgrad_stash (the "
                    "boolean is the all-or-nothing special case)")
            usched.validate(us)
        elif self.unit_schedule is not None:
            raise ValueError(
                f"unit_schedule is only meaningful under schedule: solver "
                f"(got schedule={self.schedule!r})")
        if self.offload_wgrad and self.schedule != "zb1":
            raise ValueError(
                f"offload.wgrad_stash requires schedule: zb1 (only the "
                f"split backward stashes a W queue; got "
                f"{self.schedule!r})")
        if self.offload_activations and self.schedule == "gpipe":
            raise ValueError(
                "offload.activations requires a hand-written-backward "
                "schedule (1f1b / interleaved_1f1b / zb1): gpipe's stored "
                "activations are AD-internal, there is no explicit ring "
                "buffer to tier")
        if self.layer_counts is not None:
            object.__setattr__(self, "layer_counts",
                               tuple(int(c) for c in self.layer_counts))
            if len(self.layer_counts) != self.num_stages:
                raise ValueError(
                    f"layer_counts has {len(self.layer_counts)} entries for "
                    f"num_stages={self.num_stages}")
        llama.resolve_remat_policy(self.remat_policy)  # fail fast on typos


def bubble_fraction(pcfg: PipelineConfig) -> float:
    """Analytic pipeline-bubble estimate for THIS implementation's lockstep
    scan schedules, reported next to MFU so schedule regressions are visible
    without a profiler (the measured breakdown OptPipe/SkipPipe-style
    schedule work optimizes against — PAPERS.md).

    Since PR 11 the number is COUNTED from the schedule's emitted unit
    sequence (schedule.bubble_stats — idle units over wall units in
    F=B=W costs), not maintained per schedule; the closed forms below
    document what the canonical sequences count to, and the counted
    integer pairs reduce to the identical rationals, so the floats are
    bit-equal to the old formulas. Solver sequences get the same
    treatment for free; gpipe (no sequence) keeps its closed form.

    Every schedule runs S stages over M microbatches in `accum_chunks` (= c)
    sequential flushes of m = M/c microbatches, every tick the same cost
    across stages (in-jit scan: warmup/drain ticks take a full tick's wall
    time even where a stage's slot is masked):

    - "1f1b": interleaved_1f1b's sequence at v = 1 (one grid, two names:
      of the m + 2(S-1) ticks only the m steady ones run both halves)
      -> bubble = c(S-1) / (M + c(S-1)).
    - "interleaved_1f1b": each flush runs m*v chunk-sized units per stage
      (v = virtual_stages), phased as vS-1 forward-only warmup ticks +
      mv + S - 1 - (vS-1) combined ticks + vS-1 backward-only drain ticks
      (the canonical interleaved grid's segments). A warmup tick costs one chunk
      FORWARD and a drain tick one chunk BACKWARD, so the two phases pair
      into vS-1 full chunk ticks and the flush totals mv + S - 1 chunk-tick
      equivalents, mv useful -> bubble = c(S-1) / (Mv + c(S-1)) —
      independent of the fwd/bwd cost split, ~vx below flat 1f1b for
      m >> S (the shorter fill: a chunk is 1/v of a stage).
    - "zb1": the backward is SPLIT into B (input-grad) and W (weight-grad)
      units, so the cost split matters and the unit accounting goes to
      thirds: F = B = W = 1 unit (the zero-bubble family's symmetric-cost
      assumption — dL/dx = dy W^T and dL/dW = x^T dy are the same matmul
      flops as the forward; W-unit recompute is charged to the backward
      exactly as remat's recompute already is in every schedule above).
      A full fused tick is F+B+W = 3 units. Per flush: vS-1 warmup ticks
      cost F each, mv + S - vS steady ticks cost F+B, vS-1 drain ticks
      cost B each (the W half the fused drain would pay is GONE — that is
      the zb1 win), and the W queue drains in mv single-unit W ticks:
      wall = (vS-1) + 2(mv + S - vS) + (vS-1) + mv = 3mv + 2(S-1) units,
      3mv useful -> bubble = 2c(S-1) / (3Mv + 2c(S-1)) — strictly below
      interleaved's 3c(S-1) / (3Mv + 3c(S-1)) for every S > 1
      (docs/SCHEDULES.md pins the derivation; tests/test_zero_bubble.py
      the ordering zb1 <= interleaved <= flat across the grid).
    - "gpipe": the forward scan is m + S - 1 ticks and the AD transpose
      mirrors it, m useful each way
      -> bubble = c(S-1) / (M + c(S-1)).
    """
    s = pcfg.num_stages
    if s <= 1:
        return 0.0
    m, c = pcfg.num_microbatches, pcfg.accum_chunks
    if pcfg.schedule == "gpipe":
        per_flush = s - 1
        return per_flush * c / (m + per_flush * c)
    # Every unit-sequence schedule: COUNT the per-flush sequence's idle
    # units instead of hand-maintaining a closed form per schedule. The
    # closed forms above used to live here; the integer (idle, wall) pair
    # this derives reduces to the identical rational number, so the float
    # is bit-identical — and solver sequences get the same treatment for
    # free (the c flushes scale idle and wall together).
    idle, wall = usched.bubble_stats(_unit_schedule_for(
        dataclasses.replace(pcfg, num_microbatches=m // c, accum_chunks=1)))
    return (idle * c) / (wall * c) if wall else 0.0


def wgrad_queue_peak(pcfg: PipelineConfig) -> int:
    """Peak W-queue occupancy (stashed B/W residuals, HBM + host slots
    combined) for any split-backward schedule — schedule-determined, not
    data-dependent. Canonical zb1 queues every per-flush unit until the
    trailing W drain, so the peak is Mv / accum_chunks (raising
    accum_chunks is the stash-memory lever, at the usual extra-flush
    bubble price); solver sequences that retire W units earlier carry a
    smaller slot count after liveness reuse (parallel/schedule.py). 0 for
    fused-backward schedules — the wgrad_queue_depth metrics/health key
    (docs/OBSERVABILITY.md)."""
    hbm, host = wgrad_partition(pcfg)
    return hbm + host


def wgrad_partition(pcfg: PipelineConfig) -> tuple[int, int]:
    """(hbm_slots, host_slots) of the W queue's residual-pair slots — the
    split every byte model reads: zb1's boolean offload.wgrad_stash puts
    the whole queue on one side; a solver sequence's per-unit decision
    vector splits it (with liveness slot reuse per destination buffer)."""
    if pcfg.schedule == "zb1":
        peak = (pcfg.num_microbatches // pcfg.accum_chunks) * pcfg.virtual_stages
        return (0, peak) if pcfg.offload_wgrad else (peak, 0)
    if pcfg.schedule == "solver" and pcfg.unit_schedule is not None \
            and pcfg.unit_schedule.split_backward:
        return (pcfg.unit_schedule.wq_hbm_slots,
                pcfg.unit_schedule.wq_host_slots)
    return (0, 0)


def wgrad_offloaded_units(pcfg: PipelineConfig) -> int:
    """Per-flush count of W residuals that CROSS the host link (one D2H at
    B time + one H2D at W time each) — the traffic term of the offload
    feasibility bound. Differs from the host SLOT count when liveness
    reuse packs many units through few slots."""
    if pcfg.schedule == "zb1" and pcfg.offload_wgrad:
        return (pcfg.num_microbatches // pcfg.accum_chunks) * pcfg.virtual_stages
    if pcfg.schedule == "solver" and pcfg.unit_schedule is not None:
        return pcfg.unit_schedule.offloaded_units
    return 0


def wgrad_stash_bytes(pcfg: PipelineConfig, mb_rows: int, local_seqlen: int,
                      hidden_size: int, dtype_bytes: int = 2) -> int:
    """Per-device bytes of the zb1 W-stash: two hidden-sized buffers (chunk
    input + output cotangent) per queued unit, at this shard's LOCAL
    microbatch rows and (sp-sharded) sequence length. The term
    tools/preflight.py adds to its memory model — XLA's compile-time
    analysis counts the same buffers, this names them and sizes the
    actionable remedy (accum_chunks) when they blow the headroom."""
    return (2 * wgrad_queue_peak(pcfg) * mb_rows * local_seqlen
            * hidden_size * dtype_bytes)


def activation_ring_slots(pcfg: PipelineConfig) -> int:
    """Stage-input ring-buffer slots per flush — the schedules' in-flight
    activation store (xbuf): min(2S-1, m) flat, min(2vS-1, mv) chunked
    (the liveness bounds the canonical generators encode in
    UnitSchedule.ring_slots — parallel/schedule.py). 0 where no buffer exists (gpipe's
    store is AD-internal; the flat schedule at S=1 skips its forward half
    entirely)."""
    s, v = pcfg.num_stages, pcfg.virtual_stages
    m_flush = pcfg.num_microbatches // pcfg.accum_chunks
    if pcfg.schedule == "gpipe":
        return 0
    if pcfg.schedule == "solver" and pcfg.unit_schedule is not None:
        us = pcfg.unit_schedule
        return us.ring_slots if bool(us.has_f.any()) else 0
    if pcfg.schedule == "1f1b":
        return min(2 * s - 1, m_flush) if s > 1 else 0
    return min(2 * v * s - 1, m_flush * v)


def activation_ring_bytes(pcfg: PipelineConfig, mb_rows: int,
                          local_seqlen: int, hidden_size: int,
                          dtype_bytes: int = 2) -> int:
    """Per-device bytes of the stage-input ring buffer at this shard's
    local microbatch shape — the HBM term `offload.activations` tiers to
    host DRAM (tools/preflight.py's memory model subtracts/adds it when
    enumerating candidates)."""
    return (activation_ring_slots(pcfg) * mb_rows * local_seqlen
            * hidden_size * dtype_bytes)


def stash_dims(mb_rows: int, seqlen: int, sp: int, hidden_size: int,
               dtype) -> tuple:
    """The (mb_rows, local_seqlen, hidden_size, dtype_bytes) tuple every
    ring/stash byte model here takes — ONE spelling shared by the trainer's
    offload metrics (train.py), tools/preflight.py's memory model, and the
    selection tests, so the consumers can never disagree on a shard's slot
    shape. `seqlen` is the GLOBAL row length; sp-sharding is applied here."""
    return (int(mb_rows), int(seqlen) // max(int(sp), 1), int(hidden_size),
            jnp.dtype(dtype).itemsize)


def host_stash_bytes(pcfg: PipelineConfig, mb_rows: int, local_seqlen: int,
                     hidden_size: int, dtype_bytes: int = 2) -> int:
    """Per-device bytes RESIDENT IN HOST DRAM under the enabled offload
    knobs (the metrics line's offload_stash_resident_gib; includes each
    host ring's one garbage slot — utils/host_stash.py). 0 with offload
    off."""
    slot = mb_rows * local_seqlen * hidden_size * dtype_bytes
    total = 0
    host_slots = wgrad_partition(pcfg)[1]
    if host_slots:
        # two buffers per slot + each host ring's one garbage slot
        total += 2 * host_slots * slot + 2 * slot
    if pcfg.offload_activations and activation_ring_slots(pcfg):
        total += activation_ring_bytes(pcfg, mb_rows, local_seqlen,
                                       hidden_size, dtype_bytes) + slot
    return total


def loss_head_bytes(pcfg: PipelineConfig, mb_rows: int, local_seqlen: int,
                    hidden_size: int, vocab_size: int) -> int:
    """Live per-device bytes of the LAST stage's loss head — the term
    tools/preflight.py adds to its memory model and lets --select score as
    the ce axis. XLA path: one fp32 [tokens, V/loss_chunks] logits block
    (the whole [tokens, V] at loss_chunks=1) plus, when chunked, the
    backward scan's fp32 [tokens, hidden] dh accumulator. Pallas path
    (`kernels.ce: pallas`): ~0 — the logits tile and the dh accumulator
    live in VMEM scratch; only [tokens]-sized statistics reach HBM
    (ops/pallas_ce.py)."""
    tokens = mb_rows * local_seqlen
    if pcfg.kernel_ce:
        return 0
    logits_block = tokens * (vocab_size // max(pcfg.loss_chunks, 1)) * 4
    dh_acc = tokens * hidden_size * 4 if pcfg.loss_chunks > 1 else 0
    return logits_block + dh_acc


def _head_ce_sum_count(pcfg: PipelineConfig):
    """The fused lm-head+CE op the cond-gated head branches call — the XLA
    vocab-chunked scan (ops/cross_entropy.py) or its Pallas promotion
    (ops/pallas_ce.py) under `kernels.ce: pallas`. One resolution point so
    the three schedules' heads cannot drift."""
    if pcfg.kernel_ce:
        from llama_pipeline_parallel_tpu.ops.pallas_ce import pallas_ce_sum_count

        return lambda h, w, t: pallas_ce_sum_count(h, w, t, pcfg.loss_chunks)
    from llama_pipeline_parallel_tpu.ops.cross_entropy import fused_ce_sum_count

    return lambda h, w, t: fused_ce_sum_count(h, w, t, pcfg.loss_chunks)


def _head_loss(pcfg: PipelineConfig, cfg: LlamaConfig, head_w, hn, targets):
    """(loss sum, valid count) from final-normed hiddens: the tp=1 head
    projection and its loss, fused or not, under the one `lm_head_loss`
    scope (forward, recompute and backward of the head all carry it)."""
    with jax.named_scope(trace.SCOPE_LM_HEAD_LOSS):
        if pcfg.loss_chunks > 1 or pcfg.kernel_ce:
            return _head_ce_sum_count(pcfg)(
                hn, llama.cast_weight(head_w, cfg.dtype), targets)
        logits = llama.lm_head({"lm_head": head_w}, hn, cfg)
        return llama.token_loss_sum_and_count_preshifted(logits, targets)


# ---------------------------------------------------------------------------
# Param layout: [n_layers, ...] <-> [num_stages, layers_per_stage, ...]
# (or [num_stages, virtual_stages, layers_per_chunk, ...] under interleaving)
# ---------------------------------------------------------------------------

def _reshape_leaf(x, shape: tuple[int, ...]):
    # works for concrete arrays AND abstract ShapeDtypeStruct templates
    if isinstance(x, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct(shape, x.dtype,
                                    sharding=_reshaped_sharding(x, shape))
    return x.reshape(shape)


def _reshaped_sharding(x: jax.ShapeDtypeStruct, shape: tuple[int, ...]):
    """Carry a template's NamedSharding through the stacked<->canonical
    reshape when the mapping is expressible: merging [S, k, ...] -> [S*k, ...]
    (or splitting back) keeps the leading-axis sharding as long as the k dim
    is unsharded — each stage's k layers are one contiguous block. Restores
    then place arrays SHARDED (65B canonical params never funnel through one
    device); inexpressible cases (uneven partitions) drop to unsharded."""
    from jax.sharding import NamedSharding

    s = getattr(x, "sharding", None)
    if not isinstance(s, NamedSharding):
        return None
    spec = list(s.spec) + [None] * (len(x.shape) - len(s.spec))
    if len(shape) == len(x.shape) - 1 and x.shape[0] * x.shape[1] == shape[0]:
        if spec[1] is None:  # merge (unstack): [S, k, ...] -> [n, ...]
            return NamedSharding(s.mesh, P(spec[0], *spec[2:]))
    elif len(shape) == len(x.shape) + 1 and shape[0] * shape[1] == x.shape[0]:
        axis = spec[0]  # split (stack): [n, ...] -> [S, k, ...]
        n_shards = 1 if axis is None else s.mesh.shape[axis]
        if shape[0] % n_shards == 0:  # stage blocks align with shard blocks
            return NamedSharding(s.mesh, P(axis, None, *spec[1:]))
    return None


def _interleaved_sharding(x, stacking: bool):
    """Sharding carry for the interleaved stack/unstack: the round-robin
    chunk gather reorders whole layer slices along the LEADING dim (stage
    blocks are non-contiguous in canonical layer order), so leading-dim
    sharding is inexpressible and drops to replicated, while trailing-dim
    shardings survive verbatim — the same policy (and the same reason it is
    load-bearing) as the uneven unstack path below."""
    from jax.sharding import NamedSharding

    src = getattr(x, "sharding", None)
    if not isinstance(src, NamedSharding):
        return None
    spec = list(src.spec) + [None] * (len(x.shape) - len(src.spec))
    if stacking:  # canonical [n, feat...] -> stacked [S, v, k, feat...]
        lead, trailing = (None, None, None), spec[1:]
    else:         # stacked [S, v, k, feat...] -> canonical [n, feat...]
        lead, trailing = (None,), spec[3:]
    return NamedSharding(src.mesh, P(*lead, *trailing))


def _stack_interleaved(layers: Params, manifest: StageManifest) -> Params:
    """Canonical [n, ...] -> [num_stages, virtual_stages, k, ...]: global
    chunk c (layers [c*k, (c+1)*k)) lands at [c % S, c // S] — a pure
    reshape + transpose, so the round trip is bit-exact by construction."""
    s, v, k = (manifest.num_stages, manifest.virtual_stages,
               manifest.layers_per_chunk)

    def leaf(x):
        shape = (s, v, k) + tuple(x.shape[1:])
        if isinstance(x, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(
                shape, x.dtype, sharding=_interleaved_sharding(x, stacking=True))
        y = jnp.asarray(x).reshape((v, s, k) + tuple(x.shape[1:]))
        return jnp.moveaxis(y, 0, 1)

    return jax.tree.map(leaf, layers)


def _unstack_interleaved(layers: Params, manifest: StageManifest) -> Params:
    n = manifest.num_layers
    s, v, k = (manifest.num_stages, manifest.virtual_stages,
               manifest.layers_per_chunk)

    def leaf(x):
        shape = (n,) + tuple(x.shape[3:])
        if isinstance(x, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(
                shape, x.dtype, sharding=_interleaved_sharding(x, stacking=False))
        return jnp.moveaxis(jnp.asarray(x), 1, 0).reshape(shape)

    return jax.tree.map(leaf, layers)


def stack_stages(params: Params, manifest: StageManifest) -> Params:
    """Canonical [n_layers, ...] -> stacked [num_stages, k_max, ...] leaves,
    exposing the stage axis for pp sharding.

    Even partitions are a pure reshape. Uneven partitions gather each stage's
    layers into its first `layer_counts[s]` slots and ZERO the padding slots —
    an all-zero residual block is an exact identity with identically zero
    gradients (see manifest.py), so the padded layout is correct by
    construction. Interleaved manifests (virtual_stages > 1) grow a
    virtual-chunk axis ahead of the layer-slot axis —
    [num_stages, virtual_stages, k, ...] — via the round-robin chunk
    assignment (see _stack_interleaved); the canonical checkpoint layout is
    unchanged, so PR-2 checkpoints and the HF converter restore into any
    schedule's layout through this one pair of functions."""
    s, k = manifest.num_stages, manifest.max_layers_per_stage
    if manifest.virtual_stages > 1:
        out = dict(params)
        out["layers"] = _stack_interleaved(params["layers"], manifest)
        return out
    if manifest.is_even:
        out = dict(params)
        out["layers"] = jax.tree.map(
            lambda x: _reshape_leaf(x, (s, k) + tuple(x.shape[1:])), params["layers"])
        return out

    import numpy as np

    idx = np.zeros((s, k), np.int32)
    mask = np.zeros((s, k), bool)
    for st in range(s):
        for j, layer in enumerate(manifest.layers_of_stage(st)):
            idx[st, j], mask[st, j] = layer, True

    def stack_leaf(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct((s, k) + tuple(x.shape[1:]), x.dtype)
        g = jnp.asarray(x)[idx]  # [s, k, ...]
        m = mask.reshape((s, k) + (1,) * (g.ndim - 2))
        return jnp.where(m, g, jnp.zeros((), g.dtype))

    out = dict(params)
    out["layers"] = jax.tree.map(stack_leaf, params["layers"])
    return out


def unstack_stages(params: Params, manifest: StageManifest) -> Params:
    n = manifest.num_layers
    s, k = manifest.num_stages, manifest.max_layers_per_stage
    if manifest.virtual_stages > 1:
        out = dict(params)
        out["layers"] = _unstack_interleaved(params["layers"], manifest)
        return out
    if manifest.is_even:
        out = dict(params)
        out["layers"] = jax.tree.map(
            lambda x: _reshape_leaf(x, (n,) + tuple(x.shape[2:])), params["layers"])
        return out

    import numpy as np

    flat_idx = np.zeros((n,), np.int32)
    for st in range(s):
        for j, layer in enumerate(manifest.layers_of_stage(st)):
            flat_idx[layer] = st * k + j

    def unstack_leaf(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            # The uneven gather reorders whole layer slices along the LEADING
            # dim only, so trailing-dim shardings survive verbatim (the
            # ZeRO-2 offload's dp dim lives there — dropping it here would
            # blow a 65B resume's host DRAM back to full-size leaves);
            # leading-dim sharding is genuinely inexpressible (the gather
            # crosses stage-shard boundaries) and falls to replicated.
            from jax.sharding import NamedSharding

            sharding = None
            src = getattr(x, "sharding", None)
            if isinstance(src, NamedSharding):
                spec = list(src.spec) + [None] * (len(x.shape) - len(src.spec))
                sharding = NamedSharding(src.mesh, P(None, *spec[2:]))
            return jax.ShapeDtypeStruct((n,) + tuple(x.shape[2:]), x.dtype,
                                        sharding=sharding)
        flat = jnp.asarray(x).reshape((s * k,) + tuple(x.shape[2:]))
        return flat[flat_idx]

    out = dict(params)
    out["layers"] = jax.tree.map(unstack_leaf, params["layers"])
    return out


def stage_param_specs(params: Params, tp: bool = False) -> Params:
    """PartitionSpec tree for stage-stacked params: layer leaves sharded over
    pp on the stage axis, embed/final-norm replicated.

    With `tp`, matmul weights additionally shard Megatron-style over the tp
    axis: qkv/gate/up column-parallel (output dim), wo/down row-parallel
    (input dim); norms stay replicated over tp. The lm_head is
    vocab-parallel (output vocab dim over tp) and the loss computes a
    vocab-parallel cross-entropy — full [.., vocab] logits never exist on
    any one device."""
    specs = jax.tree.map(lambda _: P(), params)
    specs["layers"] = jax.tree.map(lambda _: P(AXIS_PP), params["layers"])
    if tp:
        # matmul leaves are [S, k, in, out] flat or [S, v, k, in, out]
        # interleaved — place tp by counting from the TRAILING (matmul) dims
        # so both stacked layouts shard identically
        nd = len(params["layers"]["attn"]["wq"].shape)
        col = P(AXIS_PP, *([None] * (nd - 3)), None, AXIS_TP)
        row = P(AXIS_PP, *([None] * (nd - 3)), AXIS_TP, None)
        specs["layers"]["attn"] = {"wq": col, "wk": col, "wv": col, "wo": row}
        specs["layers"]["mlp"] = {"gate": col, "up": col, "down": row}
        specs["lm_head"] = P(None, AXIS_TP)
    return specs


def _sp_shift_labels(labels: jnp.ndarray, sp_size: int) -> jnp.ndarray:
    """Align next-token targets with a sequence-sharded label slab.

    The causal shift crosses sp-shard boundaries: the target for this slab's
    last position is the NEXT slab's first label, fetched with one tiny
    `ppermute` (labels are integers — no gradient flows, so a bare collective
    is safe inside the differentiated region). The global last position gets
    IGNORE_INDEX (no target exists). At sp=1 this degenerates to the plain
    shift with an IGNORE-padded tail.
    """
    if sp_size == 1:
        tail = jnp.full_like(labels[:, :1], llama.IGNORE_INDEX)
    else:
        perm = [(i, (i - 1) % sp_size) for i in range(sp_size)]
        tail = jax.lax.ppermute(labels[:, :1], AXIS_SP, perm)
        is_global_last = jax.lax.axis_index(AXIS_SP) == sp_size - 1
        tail = jnp.where(is_global_last, llama.IGNORE_INDEX, tail)
    return jnp.concatenate([labels[:, 1:], tail], axis=1)


@jax.named_scope(trace.SCOPE_LM_HEAD_LOSS)
def _vocab_parallel_token_loss(params: Params, h: jnp.ndarray, labels: jnp.ndarray,
                               cfg: LlamaConfig, preshifted: bool = False,
                               last_stage: jnp.ndarray | None = None,
                               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Shifted CE with the lm_head vocab-sharded over tp.

    Each rank computes logits only for its vocab shard; the log-sum-exp and
    the target logit are combined with `tp_reduce` (psum forward, identity
    backward — the correct VJP under the pipeline's unchecked shard_map; a
    bare psum inside the differentiated region would double-count, see
    _loss_and_grad_local). The row max used for stability goes through
    `tp_max` (zero-gradient pmax), so the softmax gradient stays exact.

    `preshifted`: labels are already next-token targets aligned with h
    (the sequence-parallel form, see _sp_shift_labels).

    `last_stage`: optional scalar bool. When given (the pipeline schedules),
    the HEAVY per-shard work — the [d, V/tp] head matmul and the exp/gather
    CE statistics — runs under `lax.cond` so only the stage that owns the
    loss pays it; every tp COLLECTIVE (tp_copy's backward psum, tp_max,
    tp_reduce) stays outside the cond and executes stage-uniformly, which is
    what the no-collectives-in-divergent-branches rule actually constrains
    (the psum participants are the tp peers of ONE pp stage, but keeping
    collectives unconditional makes uniformity true by construction). Skipped
    stages feed neutral operands (z=1, target=0) into the psums so no
    inf/nan intermediate ever exists, even masked. The reference pays the
    head only on the last stage by construction
    (models/llama_ds_mp_wrap.py:191-195); this recovers that property under
    tp>1. Returns (0, count) on skipped stages.
    """
    from llama_pipeline_parallel_tpu.parallel.tp import tp_copy, tp_max, tp_reduce

    head_local = llama.cast_weight(params["lm_head"], cfg.dtype)  # [d, V/n] local shard
    # column-parallel matmul input: replicated h fans into vocab shards, so dh
    # must be psum'd across tp in backward (the Megatron f operator). Must sit
    # OUTSIDE any stage-divergent cond: its backward psum has to run on every
    # stage (zeros flow from skipped stages' cond transpose).
    hc = tp_copy(h, AXIS_TP)
    if not preshifted:
        hc, labels = hc[:, :-1, :], labels[:, 1:]
    valid = labels != llama.IGNORE_INDEX
    v_local = head_local.shape[1]
    offset = jax.lax.axis_index(AXIS_TP) * v_local

    def _logits(hc_, w):
        lg = (hc_ @ w).astype(jnp.float32)  # [b, s, V/n]
        # local row-max computed in-branch so skipped stages don't even scan
        # their zeros buffer
        return lg, jax.lax.stop_gradient(lg.max(axis=-1))

    if last_stage is None:
        logits, m_local = _logits(hc, head_local)
    else:
        logits, m_local = jax.lax.cond(
            last_stage, _logits,
            lambda hc_, w: (jnp.zeros(hc_.shape[:-1] + (v_local,), jnp.float32),
                            jnp.zeros(hc_.shape[:-1], jnp.float32)),
            hc, head_local)

    m = tp_max(m_local, AXIS_TP)  # [b, s]

    def _stats(logits_, m_):
        z_local = jnp.exp(logits_ - m_[..., None]).sum(axis=-1)
        local_idx = jnp.where(valid, labels, 0) - offset
        owned = (local_idx >= 0) & (local_idx < v_local) & valid
        safe_idx = jnp.clip(local_idx, 0, v_local - 1)
        picked = jnp.take_along_axis(logits_, safe_idx[..., None], axis=-1)[..., 0]
        return z_local, jnp.where(owned, picked, 0.0)

    if last_stage is None:
        z_local, t_local = _stats(logits, m)
    else:
        z_local, t_local = jax.lax.cond(
            last_stage, _stats,
            # ones (not zeros) for z: keeps log(z) finite on skipped stages so
            # no inf/nan exists anywhere, even where-masked out
            lambda logits_, m_: (jnp.ones_like(m_), jnp.zeros_like(m_)),
            logits, m)

    z = tp_reduce(z_local, AXIS_TP)
    target = tp_reduce(t_local, AXIS_TP)
    token_loss = (m + jnp.log(z)) - target
    loss_sum = jnp.where(valid, token_loss, 0.0).sum()
    if last_stage is not None:
        loss_sum = jnp.where(last_stage, loss_sum, 0.0)
    return loss_sum, valid.sum()


# ---------------------------------------------------------------------------
# The schedule
# ---------------------------------------------------------------------------

def _slot_valid(pcfg: PipelineConfig, stage, tp_size: int, sp_size: int,
                k_max: int):
    """[k_max] bool mask of REAL layer slots for this stage under an uneven
    partition, or None when all slots are real — or when the layer body
    contains collectives (tp/sp > 1), where cond-skipping is unsafe and the
    zero-weight padding computes as an exact identity instead."""
    if (pcfg.layer_counts is None or len(set(pcfg.layer_counts)) == 1
            or tp_size > 1 or sp_size > 1):
        return None
    counts = jnp.asarray(pcfg.layer_counts, jnp.int32)
    return jnp.arange(k_max) < counts[stage]

def _act_stat_update(carry: tuple, y: jnp.ndarray, valid) -> tuple:
    """Fold one tick's stage-boundary activation into the running
    (absmax, mean-square sum, tick count) accumulators — the per-stage
    numerics-observatory stats (utils/numerics.py). `stop_gradient` keeps
    the reductions out of any AD transpose (gpipe differentiates the scan
    these accumulators ride in)."""
    absmax, msq_sum, n = carry
    yf = jax.lax.stop_gradient(y).astype(jnp.float32)
    absmax = jnp.maximum(absmax,
                         jnp.where(valid, jnp.max(jnp.abs(yf)), 0.0))
    msq_sum = msq_sum + jnp.where(valid, jnp.mean(jnp.square(yf)), 0.0)
    return absmax, msq_sum, n + valid.astype(jnp.float32)


_ACT_STATS_ZERO = lambda: (jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.0))


def _act_stats_zero_chunks(v: int):
    """Per-virtual-chunk accumulators ([v] each) for the interleaved
    schedule; folds elementwise exactly like the scalar flat-schedule ones."""
    z = jnp.zeros((v,), jnp.float32)
    return (z, z, z)


def _act_stat_update_chunk(carry: tuple, y: jnp.ndarray, valid, ch, v: int
                           ) -> tuple:
    """Fold one tick's chunk-boundary activation into the [v]-shaped
    accumulators at virtual-chunk index `ch` (traced)."""
    absmax, msq_sum, n = carry
    yf = jax.lax.stop_gradient(y).astype(jnp.float32)
    onehot = (jnp.arange(v) == ch) & valid
    absmax = jnp.maximum(absmax, jnp.where(onehot, jnp.max(jnp.abs(yf)), 0.0))
    msq_sum = msq_sum + jnp.where(onehot, jnp.mean(jnp.square(yf)), 0.0)
    return absmax, msq_sum, n + onehot.astype(jnp.float32)


def _sched_act_stats_zero(pcfg: PipelineConfig):
    """Schedule-appropriate zero activation-stat carry (shapes must agree
    across the accum_chunks fold)."""
    if pcfg.schedule in ("interleaved_1f1b", "zb1", "solver"):
        return _act_stats_zero_chunks(pcfg.virtual_stages)
    return _ACT_STATS_ZERO()


# ---------------------------------------------------------------------------
# Interleaved unit indexing (schedule: interleaved_1f1b)
#
# One scheduling UNIT is one (microbatch, virtual-chunk) pair — a microbatch
# passing through one stage's chunk of layers. Units are ordered in groups
# of v*S: group g covers microbatches [g*S, (g+1)*S) through all v chunks,
# chunk-major — so unit u and unit u+S are the SAME microbatch on the NEXT
# chunk, which is exactly one lap of the pp ring later. That makes the
# plain (i -> i+1) ring ppermute carry BOTH the stage->stage handoff and the
# last-stage -> first-stage chunk transition, with no special cases (and its
# reverse do the same for cotangents). Requires m % S == 0 per flush
# (validated in PipelineConfig).
# ---------------------------------------------------------------------------

def _unit_mb_chunk(u, s: int, v: int):
    """Forward unit index -> (microbatch, virtual chunk)."""
    grp = u // (v * s)
    return grp * s + u % s, (u // s) % v


def _bwd_unit_mb_chunk(g, s: int, v: int):
    """Backward unit index -> (microbatch, virtual chunk): same group/slot
    layout with the CHUNK order reversed — backward starts at the last
    chunk (the loss end of the virtual pipeline) and descends."""
    grp = g // (v * s)
    return grp * s + g % s, v - 1 - (g // s) % v


def _mb_streams(batch: Batch, cfg: LlamaConfig, pcfg: PipelineConfig):
    """Per-microbatch data access shared by the schedule loops (runs INSIDE
    shard_map). Returns (mb_rows, seqlen, mb_data) where `mb_data(idx)` ->
    (ids, pad_mask, cos, sin, targets) of microbatch `idx`.

    Labels are pre-shifted to next-token targets ONCE for the whole chunk
    (microbatch slicing is over the batch dim, so it commutes with the
    sequence-dim shift): under sp the shift is a collective, and hoisting it
    here keeps it off the schedules' per-tick critical path AND
    stage-uniform."""
    m_total = pcfg.num_microbatches
    ids = batch["input_ids"]
    bsz, seqlen = ids.shape
    if bsz % m_total:
        raise ValueError(f"per-dp batch {bsz} not divisible by microbatches {m_total}")
    mb = bsz // m_total
    sp_size = jax.lax.axis_size(AXIS_SP)
    # seqlen here is the LOCAL slab length; fallback positions must be global
    sp_pos_base = jax.lax.axis_index(AXIS_SP) * seqlen if sp_size > 1 else 0

    def mb_view(x):
        return x.reshape((m_total, mb) + x.shape[1:])

    ids_m = mb_view(ids)
    mask_m = mb_view(batch["attention_mask"]) if batch.get("attention_mask") is not None else None
    pos_m = mb_view(batch["position_ids"]) if batch.get("position_ids") is not None else None
    targets_m = mb_view(_sp_shift_labels(batch["labels"], sp_size))

    def mb_data(idx):
        my_ids = jax.lax.dynamic_index_in_dim(ids_m, idx, keepdims=False)
        if pos_m is not None:
            pos = jax.lax.dynamic_index_in_dim(pos_m, idx, keepdims=False)
        else:
            pos = sp_pos_base + jnp.broadcast_to(
                jnp.arange(seqlen, dtype=jnp.int32), (mb, seqlen))
        pad = (jax.lax.dynamic_index_in_dim(mask_m, idx, keepdims=False)
               if mask_m is not None else None)
        targets = jax.lax.dynamic_index_in_dim(targets_m, idx, keepdims=False)
        cos, sin = rope_cos_sin(pos, cfg.head_dim, cfg.rope_theta, dtype=cfg.dtype)
        return my_ids, pad, cos, sin, targets

    return mb, seqlen, mb_data


def _pipeline_loss_local(
    params: Params,
    batch: Batch,
    cfg: LlamaConfig,
    pcfg: PipelineConfig,
    attn_fn: Callable = attention,
    collect_stats: bool = False,
) -> tuple:
    """Runs INSIDE shard_map. Local views: layer leaves [1, k, ...]; batch is
    this dp-shard's [M*mb, L]. Returns local (loss_sum, token_count) pairs
    (pre-psum) — plus, with `collect_stats`, this stage's activation
    (absmax, mean-square sum, tick count) accumulators over its LIVE ticks.
    The caller reduces and differentiates.

    Understands interleaved manifests (pcfg.virtual_stages > 1, layer leaves
    [1, v, k, ...]): the forward walks the v*S virtual-stage ring with the
    interleaved unit ordering, which is what lets
    `make_pipeline_eval_fn` evaluate a training run configured with
    `schedule: interleaved_1f1b` (training grads for the unit schedules use
    the interpreter `_pipeline_units_local`, not AD of this loop)."""
    s_total = pcfg.num_stages
    v = pcfg.virtual_stages
    m_total = pcfg.num_microbatches
    n_units = m_total * v
    stage = jax.lax.axis_index(AXIS_PP)
    is_first = stage == 0
    is_last = stage == s_total - 1

    local_layers = jax.tree.map(lambda x: x[0], params["layers"])  # [(v,) k, ...]
    if collect_stats and v > 1:
        raise NotImplementedError(
            "collect_stats on the forward-only loop is gpipe-only; "
            "interleaved training stats come from the unit-sequence "
            "interpreter (_pipeline_units_local)")

    mb, seqlen, mb_data = _mb_streams(batch, cfg, pcfg)
    num_ticks = n_units + s_total - 1
    hidden_shape = (mb, seqlen, cfg.hidden_size)
    x_init = jnp.zeros(hidden_shape, cfg.dtype)
    tp_size = jax.lax.axis_size(AXIS_TP)
    sp_size = jax.lax.axis_size(AXIS_SP)

    def mb_loss(h, targets, take):
        """Per-microbatch loss from last-stage hiddens. Checkpointed in the
        tick so the [mb, L, vocab] logits are recomputed in backward from the
        (already stored) hiddens — never M copies of logits.

        `take` (scalar bool: last stage AND a live microbatch) cond-gates the
        head so only the owning stage's live ticks pay final-norm + lm-head +
        CE. At tp=1 the whole head is collective-free and sits in the branch;
        at tp>1 the gating happens inside _vocab_parallel_token_loss so the
        tp collectives stay stage-uniform."""
        if tp_size > 1:
            hn = llama.final_norm(params, h, cfg)
            return _vocab_parallel_token_loss(params, hn, targets, cfg,
                                              preshifted=True, last_stage=take)

        def head(h_, targets_):
            hn = llama.final_norm(params, h_, cfg)
            return _head_loss(pcfg, cfg, params["lm_head"], hn, targets_)

        return jax.lax.cond(
            take, head,
            lambda h_, targets_: (jnp.float32(0.0), jnp.int32(0)),
            h, targets)

    mb_loss = jax.checkpoint(mb_loss)

    def tick(carry, t):
        x_prev, loss_sum, count, act_stats = carry
        # Unit for this tick: stage 0 consumes unit t; this stage computes
        # unit (t - stage). At v == 1 a unit IS a microbatch.
        my_idx = t - stage
        u = jnp.clip(my_idx, 0, n_units - 1)
        mb_idx, ch = _unit_mb_chunk(u, s_total, v)
        mb_idx = jnp.clip(mb_idx, 0, m_total - 1)

        my_ids, pad_mask, cos, sin, targets = mb_data(mb_idx)
        take = is_last & (ch == v - 1) & (my_idx >= 0)
        with jax.named_scope(trace.SCOPE_PP_FWD):
            emb = llama.embed(params, my_ids, cfg)
            x_in = jnp.where(is_first & (ch == 0), emb, x_prev)

            tp_axis = AXIS_TP if tp_size > 1 else None
            chunk_layers = (jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, ch, keepdims=False),
                local_layers) if v > 1 else local_layers)
            k_max = jax.tree.leaves(chunk_layers)[0].shape[0]
            y = llama.run_layers(chunk_layers, x_in, pad_mask, cos, sin, cfg,
                                 attn_fn=attn_fn, remat=pcfg.remat,
                                 tp_axis=tp_axis,
                                 remat_policy=pcfg.remat_policy,
                                 slot_valid=_slot_valid(pcfg, stage, tp_size,
                                                        sp_size, k_max)
                                 if v == 1 else None,
                                 pallas_prologue=pcfg.kernel_prologue)

            # The last stage's finished microbatch contributes its loss
            # in-tick (nothing is collected into an M-sized buffer; the head
            # itself is cond-gated inside mb_loss so only the owning stage
            # pays it).
            mb_sum, mb_count = mb_loss(y, targets, take)
        loss_sum = loss_sum + jnp.where(take, mb_sum, 0.0)
        count = count + jnp.where(take, mb_count, 0)

        if collect_stats:
            # Stage-boundary activation stats over this stage's LIVE ticks
            # (warmup/drain ticks recompute a clipped microbatch — masked).
            live = (my_idx >= 0) & (my_idx < n_units)
            act_stats = _act_stat_update(act_stats, y, live)

        # Hand off to the next stage over the ICI ring (NCCL-P2P analogue).
        if s_total > 1:
            perm = [(i, (i + 1) % s_total) for i in range(s_total)]
            with jax.named_scope(trace.SCOPE_PP_HANDOFF):
                x_next = jax.lax.ppermute(y, AXIS_PP, perm)
        else:
            x_next = y
        return (x_next, loss_sum, count, act_stats), None

    (_, loss_sum, count, act_stats), _ = jax.lax.scan(
        tick, (x_init, jnp.float32(0.0), jnp.int32(0), _ACT_STATS_ZERO()),
        jnp.arange(num_ticks))

    # Only the last stage's numbers are real.
    loss_sum = jnp.where(is_last, loss_sum, 0.0)
    count = jnp.where(is_last, count, 0)
    if collect_stats:
        return loss_sum, count, act_stats
    return loss_sum, count


def _unit_schedule_for(pcfg: PipelineConfig):
    """The PER-FLUSH unit sequence the interpreter executes: the loaded
    solver sequence, or the canonical generator's re-emission of the named
    schedule (parallel/schedule.py — the data form of the three deleted
    hand-written phase scans). Callers pass a pcfg whose num_microbatches
    is already the per-flush count (accum_chunks=1)."""
    if pcfg.schedule == "solver":
        us = pcfg.unit_schedule
        if (us.stage_costs is None or len(set(us.stage_costs)) == 1) \
                and pcfg.layer_counts is not None \
                and len(set(pcfg.layer_counts)) != 1:
            # a costless (or uniform-cost — same accounting) sequence run
            # on an unequal partition: attach the run's layer counts so
            # the bubble accounting stays honest (unit placement is
            # cost-independent)
            us = dataclasses.replace(us, stage_costs=tuple(pcfg.layer_counts))
        return us
    counts = (tuple(pcfg.layer_counts)
              if pcfg.layer_counts is not None
              and len(set(pcfg.layer_counts)) != 1 else None)
    return _canonical_cached(pcfg.schedule,
                             pcfg.num_microbatches // pcfg.accum_chunks,
                             pcfg.num_stages, pcfg.virtual_stages,
                             pcfg.offload_wgrad, counts)


def schedule_slot_counts(pcfg: PipelineConfig) -> list[dict] | None:
    """Per stage, the F, B and W slots one optimizer step executes and how
    many of each are masked (unit index < 0: full-price device work that
    contributes nothing), counted from the very table slices the interpreter
    scans, segment by segment. This is the pipeline's bubble as the
    device sees it; `bubble_fraction` is a closed form with F = B = W. None
    for gpipe, which scans no unit tables."""
    import numpy as np

    if pcfg.schedule not in UNIT_SCHEDULES:
        return None
    # the PER-FLUSH unit sequence this config's interpreter executes
    us = _unit_schedule_for(dataclasses.replace(
        pcfg, num_microbatches=pcfg.num_microbatches // pcfg.accum_chunks,
        accum_chunks=1))
    counts = [{"stage": s, "f": 0, "f_masked": 0, "b": 0, "b_masked": 0,
               "w": 0, "w_masked": 0} for s in range(pcfg.num_stages)]
    for seg in usched.segments(us):
        for key, scanned, table in (("f", seg.has_f, us.f_unit),
                                    ("b", seg.has_b, us.b_unit),
                                    ("w", seg.has_w, us.w_unit)):
            if not scanned:
                continue
            rows = np.asarray(table)[seg.t0:seg.t1]
            for s, c in enumerate(counts):
                c[key] += int(rows.shape[0]) * pcfg.accum_chunks
                c[key + "_masked"] += (int((rows[:, s] < 0).sum())
                                       * pcfg.accum_chunks)
    return counts


@functools.lru_cache(maxsize=64)
def _canonical_cached(schedule: str, m: int, s: int, v: int,
                      offload_wgrad: bool, stage_costs: tuple | None = None):
    return usched.canonical_schedule(schedule, m, s, v,
                                     offload_wgrad=offload_wgrad,
                                     stage_costs=stage_costs)


def _pipeline_units_local(
    params: Params,
    batch: Batch,
    cfg: LlamaConfig,
    pcfg: PipelineConfig,
    attn_fn: Callable,
    global_count: jnp.ndarray,
    us,
    collect_stats: bool = False,
) -> tuple:
    """The unit-sequence INTERPRETER: executes any validated UnitSchedule
    (parallel/schedule.py) inside shard_map — the single replacement for
    the three hand-written phase scans (flat 1f1b's one-scan
    warmup/steady/drain formulas, the interleaved three-phase clock, and
    zb1's fourth W-drain phase), which now exist only as canonical
    sequences re-emitted by the generator.

    Runs INSIDE shard_map; returns this shard's (normalized loss, grads)
    — the caller psums. How a sequence executes:

    - Ticks are grouped into SEGMENTS of equal structural flags
      (has_f/has_b/has_w + ring directions); each segment compiles to one
      `lax.scan` whose body contains exactly the active halves, with the
      per-tick [num_stages] unit-index rows as the scan's xs and this
      stage's entry selected by `jnp.take(row, stage)`. The canonical
      sequences: flat and interleaved = F-only warmup / F+B steady /
      B-only drain (a half that is -1 on EVERY stage of a tick is in no
      body), zb1 = those plus a trailing W-only segment.
    - An idle (-1) slot is masked, not skipped: the forward computes a
      clipped unit and the predicated buffer write discards it; the
      backward seeds zero cotangents through the linear vjp; the W replay
      seeds zeros. Masked work costs a full tick slot (the lockstep-scan
      model schedule.bubble_stats charges) but contributes EXACTLY zero
      to every accumulator — which is why an interpreter run is
      bit-identical to the old scans, and why dropping a half that is
      masked on every stage (flat 1f1b, PR 38) changes no bit: the same
      live units fold in the same order with the same masking, regardless
      of what masked compute surrounds them.
    - F units: chunk forward (embed cond-gated on (stage 0, chunk 0)),
      buffering the received stage input in the `ring_slots` ring for the
      later backward recompute. B units: the backward — fused schedules
      vjp w.r.t. (params, input); split-backward sequences vjp w.r.t. the
      INPUT only (params closed over, so XLA never builds the weight-grad
      matmuls there) and push the (chunk input, ring cotangent) residual
      into the W queue, each unit to its `wq_slot` in the HBM or host
      buffer per the sequence's per-unit `offload_units` decision
      (PipeOffload-style selective tiering; host pushes stream D2H behind
      the tick's remaining compute). W units: pop the residual and vjp
      w.r.t. PARAMS only, folding dparams into the same fp32 accumulators
      — ascending canonical unit order preserves zb1's bit-exact parity
      with the fused backward. A W-only segment whose units ALL tier to
      host runs double-buffered: the scan carries the next unit's pair so
      its H2D fetch streams behind the current replay (the
      prefetch-one-ahead contract tests pin).
    - `ring_fwd`/`ring_bwd` ticks hand activations/cotangents to the ring
      neighbors via the usual `ppermute`s, outside every cond (the
      no-collectives-in-divergent-branches rule): the flags are per-tick,
      identical on every stage, so no device ever skips a collective its
      peers execute. At S=1 the "ring" degenerates to carrying this
      tick's output to the next tick.
    """
    s_total = pcfg.num_stages
    v = us.virtual_stages
    m_total = us.num_microbatches
    n_units = us.n_units
    split = us.split_backward
    flat_stats = pcfg.schedule == "1f1b"  # scalar per-stage accumulators
    stage = jax.lax.axis_index(AXIS_PP)
    is_first = stage == 0
    is_last = stage == s_total - 1
    tp_size = jax.lax.axis_size(AXIS_TP)
    tp_axis = AXIS_TP if tp_size > 1 else None
    sp_size = jax.lax.axis_size(AXIS_SP)

    mb, seqlen, mb_data = _mb_streams(batch, cfg, pcfg)

    def chunk_fwd(p, x_in, ch, my_ids, pad, cos, sin, targets, with_loss,
                  loss_gate=None):
        """One virtual chunk forward (+ cond-gated loss head). `ch` is the
        traced virtual-chunk index; the chunk's layers are dynamically
        sliced from the [v, k, ...] local leaves, so the param-side vjp
        scatter-adds each chunk's gradient into its own slice (zeros
        elsewhere — exact, not approximate). At v == 1 this IS the flat
        stage function, including cond-skipping an uneven partition's
        padded layer slots where that is safe (_slot_valid)."""
        x0 = jax.lax.cond(
            is_first & (ch == 0),
            lambda emb, x: llama.embed({"embed": emb}, my_ids, cfg),
            lambda emb, x: x,
            p["embed"], x_in)
        if v == 1:  # degenerate: flat [1, k, ...] leaves, the one chunk
            chunk_layers = jax.tree.map(lambda a: a[0], p["layers"])
        else:
            chunk_layers = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a[0], ch, keepdims=False),
                p["layers"])
        k_max = jax.tree.leaves(chunk_layers)[0].shape[0]
        y = llama.run_layers(chunk_layers, x0, pad, cos, sin, cfg,
                             attn_fn=attn_fn, remat=pcfg.remat,
                             tp_axis=tp_axis, remat_policy=pcfg.remat_policy,
                             slot_valid=_slot_valid(pcfg, stage, tp_size,
                                                    sp_size, k_max)
                             if v == 1 else None,
                             pallas_prologue=pcfg.kernel_prologue)
        if not with_loss:
            return y

        owns_loss = is_last & (ch == v - 1)
        gate = owns_loss if loss_gate is None else owns_loss & loss_gate
        if tp_size > 1:
            # tp collectives stay stage-uniform; the heavy matmul + CE stats
            # are cond-gated inside (_vocab_parallel_token_loss, `last_stage`
            # mode) — the no-collectives-in-divergent-branches rule.
            h = llama.final_norm({"norm": p["norm"]}, y, cfg)
            mb_sum = _vocab_parallel_token_loss(
                {"lm_head": p["lm_head"]}, h, targets, cfg,
                preshifted=True, last_stage=gate)[0]
        else:
            def head_branch(norm_w, head_w, y_):
                h = llama.final_norm({"norm": norm_w}, y_, cfg)
                return _head_loss(pcfg, cfg, head_w, h, targets)[0]

            mb_sum = jax.lax.cond(
                gate, head_branch, lambda norm_w, head_w, y_: jnp.float32(0.0),
                p["norm"], p["lm_head"], y)
        return y, mb_sum

    b_slots = us.ring_slots
    hidden_shape = (mb, seqlen, cfg.hidden_size)
    fwd_perm = [(i, (i + 1) % s_total) for i in range(s_total)]
    bwd_perm = [(i, (i - 1) % s_total) for i in range(s_total)]

    # -- the sequence's grids as device constants ---------------------------
    import numpy as np

    f_tbl = jnp.asarray(us.f_unit, jnp.int32)
    b_tbl = jnp.asarray(us.b_unit, jnp.int32)
    w_tbl = jnp.asarray(us.w_unit, jnp.int32)
    off_np = us.offload_units if split else np.zeros(0, bool)
    n_off = int(off_np.sum()) if split else 0
    n_keep_units = (n_units - n_off) if split else 0
    wq_slot_tbl = jnp.asarray(us.wq_slot, jnp.int32) if split else None
    off_tbl = jnp.asarray(off_np) if split and 0 < n_off < n_units else None
    use_act_stash = pcfg.offload_activations and bool(us.has_f.any())
    # a B unit runs its stage forward inside the vjp (nested in `pp_bwd`).
    # Where the sequence has F units that is the forward run AGAIN; where it
    # has none (S == 1: the B units are the whole step) it is the only
    # forward there is
    b_fwd_scope = (trace.SCOPE_PP_RECOMPUTE if bool(us.has_f.any())
                   else trace.SCOPE_PP_FWD)

    # each half runs whole under its slot's scope: the unit's own work and
    # the index, buffer and queue plumbing around it
    @jax.named_scope(trace.SCOPE_PP_FWD)
    def fwd_half(f_row, x_recv, xbuf):
        f = jnp.take(f_row, stage)
        f_valid = f >= 0
        f_c = jnp.clip(f, 0, n_units - 1)
        mb_f, ch_f = _unit_mb_chunk(f_c, s_total, v)
        ids_f, pad_f, cos_f, sin_f, _ = mb_data(jnp.clip(mb_f, 0, m_total - 1))
        y_f = chunk_fwd(params, x_recv, ch_f, ids_f, pad_f, cos_f, sin_f,
                        None, with_loss=False)
        # Buffer the raw received chunk input for the later backward
        # recompute; predicated so masked slots never clobber a live one
        # (under offload.activations the ring lives in host DRAM and
        # predication routes invalid writes to the stash's garbage slot
        # instead of an RMW — utils/host_stash.py).
        slot_f = f_c % b_slots
        if use_act_stash:
            xbuf = host_stash.stash_push(xbuf, x_recv, slot_f, f_valid)
        else:
            old = jax.lax.dynamic_index_in_dim(xbuf, slot_f, keepdims=False)
            xbuf = jax.lax.dynamic_update_index_in_dim(
                xbuf, jnp.where(f_valid, x_recv, old), slot_f, 0)
        return y_f, xbuf

    def wq_push(wq, g_c, valid, x_val, dy_val):
        """Push one W residual pair to its sequence-assigned destination:
        the HBM queue via a predicated where-write, the host queue via the
        stash's garbage-slot predication (one D2H per buffer, streaming
        behind the tick's remaining compute). Mixed sequences write both
        buffers with complementary predicates — NOTE the garbage-slot
        push is still a real D2H, so a mixed vector pays the FULL link
        traffic (preflight.offload_traffic_bytes charges it); the
        selective win is host residency (few live slots), not bytes
        moved."""
        slot = jnp.take(wq_slot_tbl, g_c)
        parts = list(wq)
        i = 0
        if n_keep_units:
            keep_ok = valid if off_tbl is None else \
                valid & ~jnp.take(off_tbl, g_c)
            slot_k = jnp.clip(slot, 0, us.wq_hbm_slots - 1)
            for j, val in ((0, x_val), (1, dy_val)):
                old = jax.lax.dynamic_index_in_dim(parts[i + j], slot_k,
                                                   keepdims=False)
                parts[i + j] = jax.lax.dynamic_update_index_in_dim(
                    parts[i + j], jnp.where(keep_ok, val, old), slot_k, 0)
            i += 2
        if n_off:
            off_ok = valid if off_tbl is None else \
                valid & jnp.take(off_tbl, g_c)
            slot_h = jnp.clip(slot, 0, us.wq_host_slots - 1)
            for j, val in ((0, x_val), (1, dy_val)):
                parts[i + j] = host_stash.stash_push(parts[i + j], val,
                                                     slot_h, off_ok)
        return tuple(parts)

    def wq_pop(wq, g_c):
        """Fetch unit g's residual pair from whichever buffer holds it
        (mixed sequences read BOTH buffers and where-select — the host pop
        is a real H2D either way, counted by the traffic model)."""
        slot = jnp.take(wq_slot_tbl, g_c)
        i = 0
        kept = hosted = None
        if n_keep_units:
            slot_k = jnp.clip(slot, 0, us.wq_hbm_slots - 1)
            kept = tuple(jax.lax.dynamic_index_in_dim(wq[i + j], slot_k,
                                                      keepdims=False)
                         for j in (0, 1))
            i += 2
        if n_off:
            slot_h = jnp.clip(slot, 0, us.wq_host_slots - 1)
            hosted = tuple(host_stash.stash_pop(wq[i + j], slot_h)
                           for j in (0, 1))
        if kept is None:
            return hosted
        if hosted is None:
            return kept
        is_off = jnp.take(off_tbl, g_c)
        return tuple(jnp.where(is_off, h, k) for h, k in zip(hosted, kept))

    @jax.named_scope(trace.SCOPE_PP_BWD)
    def bwd_half(b_row, dy_recv, xbuf, gacc, loss_acc, act_stats, wq):
        g = jnp.take(b_row, stage)
        b_valid = g >= 0
        g_c = jnp.clip(g, 0, n_units - 1)
        mb_b, ch_b = _bwd_unit_mb_chunk(g_c, s_total, v)
        mb_b = jnp.clip(mb_b, 0, m_total - 1)
        # the FORWARD unit index of this backward unit, for the buffer slot
        f_idx = ((g_c // (v * s_total)) * (v * s_total)
                 + ch_b * s_total + g_c % s_total)
        ids_b, pad_b, cos_b, sin_b, targets_b = mb_data(mb_b)
        if use_act_stash:
            # H2D fetch dispatched at the top of the backward half — the
            # copy overlaps the forward half's compute above it (no data
            # dependence between them; XLA's async copy-start/copy-done)
            x_in_b = host_stash.stash_pop(xbuf, f_idx % b_slots)
        else:
            x_in_b = jax.lax.dynamic_index_in_dim(xbuf, f_idx % b_slots,
                                                  keepdims=False)

        def h(p, x_in):
            return chunk_fwd(p, x_in, ch_b, ids_b, pad_b, cos_b, sin_b,
                             targets_b, with_loss=True, loss_gate=b_valid)

        with jax.named_scope(b_fwd_scope):
            if split:
                # B unit: input-grad only. Params are CLOSED OVER, so the
                # vjp never builds the weight-grad matmuls — the tick pays
                # just the chunk recompute + the cotangent chain the
                # upstream stage is waiting on. The (input, cotangent)
                # residual is stashed for the sequence's W units.
                (y_b, mb_sum), pullback = jax.vjp(lambda x: h(params, x),
                                                  x_in_b)
            else:
                (y_b, mb_sum), pullback = jax.vjp(h, params, x_in_b)
        if collect_stats:
            # stage/chunk-boundary activation stats from the backward
            # recompute (covers S=1, whose forward half may not exist,
            # with the same b_valid gate as the loss)
            if flat_stats:
                act_stats = _act_stat_update(act_stats, y_b, b_valid)
            else:
                act_stats = _act_stat_update_chunk(act_stats, y_b, b_valid,
                                                   ch_b, v)
        # Only the (last stage, chunk v-1) unit ends the virtual pipeline —
        # every OTHER last-stage chunk's output went to stage 0, so it DOES
        # consume the ring cotangent. vjp is linear in the cotangent, so
        # masked ticks contribute exactly zero.
        owns_loss = is_last & (ch_b == v - 1)
        dy_ct = jnp.where(b_valid & ~owns_loss, 1.0, 0.0).astype(cfg.dtype) * dy_recv
        loss_ct = jnp.where(b_valid, 1.0, 0.0) / global_count
        if split:
            (dx,) = pullback((dy_ct, loss_ct))
            wq = wq_push(wq, g_c, b_valid, x_in_b, dy_ct)
        else:
            dparams, dx = pullback((dy_ct, loss_ct))
            gacc = jax.tree.map(jnp.add, gacc, dparams)
        loss_acc = loss_acc + jnp.where(b_valid, mb_sum, 0.0)
        return dx, gacc, loss_acc, act_stats, wq

    loss_ct_w = jnp.float32(1.0) / global_count

    def w_replay(gacc, g, x_w, dy_w, valid):
        """One W unit: vjp the chunk w.r.t. PARAMS from its residual pair
        and fold dparams into the fp32 accumulators (the canonical
        sequences replay in ascending unit order = the fused backward's
        fold order = bit-exact parity; masked slots seed exact zeros)."""
        mb_w, ch_w = _bwd_unit_mb_chunk(g, s_total, v)
        ids_w, pad_w, cos_w, sin_w, targets_w = mb_data(mb_w)

        def h_p(p):
            return chunk_fwd(p, x_w, ch_w, ids_w, pad_w, cos_w, sin_w,
                             targets_w, with_loss=True)

        with jax.named_scope(trace.SCOPE_PP_W):
            with jax.named_scope(trace.SCOPE_PP_RECOMPUTE):
                _, pullback = jax.vjp(h_p, params)
            dy_seed = jnp.where(valid, dy_w, jnp.zeros_like(dy_w))
            (dparams,) = pullback((dy_seed, jnp.where(valid, loss_ct_w, 0.0)))
            return jax.tree.map(jnp.add, gacc, dparams)

    @jax.named_scope(trace.SCOPE_PP_W)
    def w_half(w_row, gacc, wq):
        g = jnp.take(w_row, stage)
        g_c = jnp.clip(g, 0, n_units - 1)
        x_w, dy_w = wq_pop(wq, g_c)
        return w_replay(gacc, g_c, x_w, dy_w, g >= 0)

    # -- segment runner: one lax.scan per run of equal structural flags -----
    def make_seg_body(has_f, has_b, has_w, r_f, r_b):
        def body(carry, xs):
            x_recv, dy_recv, xbuf, gacc, loss_acc, act_stats, *wq = carry
            wq = tuple(wq)
            y_f = dx = None
            if has_f:
                y_f, xbuf = fwd_half(xs["f"], x_recv, xbuf)
            if has_b:
                dx, gacc, loss_acc, act_stats, wq = bwd_half(
                    xs["b"], dy_recv, xbuf, gacc, loss_acc, act_stats, wq)
            if has_w:
                gacc = w_half(xs["w"], gacc, wq)
            # ring handoffs sit outside every cond and run tick-uniformly;
            # at S=1 the handoff degenerates to the scan carry itself
            with jax.named_scope(trace.SCOPE_PP_HANDOFF):
                if r_f:
                    x_recv = (jax.lax.ppermute(y_f, AXIS_PP, fwd_perm)
                              if s_total > 1 else y_f)
                if r_b:
                    dy_recv = (jax.lax.ppermute(dx, AXIS_PP, bwd_perm)
                               if s_total > 1 else dx)
            return (x_recv, dy_recv, xbuf, gacc, loss_acc, act_stats, *wq), None
        return body

    def run_w_segment(t0, t1, gacc, wq):
        """A W-only segment as its own scan over the grad accumulators
        (the zb1 fourth phase's structure, preserved): in-HBM residuals
        read directly; an all-host segment runs DOUBLE-BUFFERED — the
        carry holds unit g's pair already fetched, and the body's first
        dispatch prefetches unit g+1 H2D with no data dependence on the
        replay below it, so the copy streams behind the weight-grad
        compute (the prefetch-one-unit-ahead contract)."""
        rows = w_tbl[t0:t1]
        if split and n_off == n_units:
            host_x, host_dy = wq[0], wq[1]

            def pop_pair(row):
                g_c = jnp.clip(jnp.take(row, stage), 0, n_units - 1)
                slot = jnp.clip(jnp.take(wq_slot_tbl, g_c), 0,
                                us.wq_host_slots - 1)
                return (host_stash.stash_pop(host_x, slot),
                        host_stash.stash_pop(host_dy, slot))

            def w_body(carry, xs):
                gacc, x_w, dy_w = carry
                row, row_next = xs
                x_nxt, dy_nxt = pop_pair(row_next)
                g = jnp.take(row, stage)
                gacc = w_replay(gacc, jnp.clip(g, 0, n_units - 1), x_w, dy_w,
                                g >= 0)
                return (gacc, x_nxt, dy_nxt), None

            rows_next = jnp.concatenate([rows[1:], rows[-1:]])
            first = pop_pair(rows[0])
            (gacc, _, _), _ = jax.lax.scan(w_body, (gacc,) + first,
                                           (rows, rows_next))
            return gacc

        def w_body(gacc, row):
            return w_half(row, gacc, wq), None

        gacc, _ = jax.lax.scan(w_body, gacc, rows)
        return gacc

    # -- initial carry + the segment walk -----------------------------------
    carry = (
        jnp.zeros(hidden_shape, cfg.dtype),
        jnp.zeros(hidden_shape, cfg.dtype),
        (host_stash.stash_init(b_slots, hidden_shape, cfg.dtype)
         if use_act_stash
         else jnp.zeros((b_slots,) + hidden_shape, cfg.dtype)),
        jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params),
        jnp.float32(0.0),
        _ACT_STATS_ZERO() if flat_stats else _act_stats_zero_chunks(v),
    )
    if split:
        # The W queue: the sequence's slot-assigned residual store, HBM
        # and/or host per the per-unit offload vector (wgrad_partition —
        # the memory term tools/preflight.py models). accum_chunks shrinks
        # n_units; the offload vector moves slots off-device entirely.
        wq0: tuple = ()
        if n_keep_units:
            wq0 += (jnp.zeros((us.wq_hbm_slots,) + hidden_shape, cfg.dtype),
                    jnp.zeros((us.wq_hbm_slots,) + hidden_shape, cfg.dtype))
        if n_off:
            wq0 += (host_stash.stash_init(us.wq_host_slots, hidden_shape,
                                          cfg.dtype),
                    host_stash.stash_init(us.wq_host_slots, hidden_shape,
                                          cfg.dtype))
        carry = carry + wq0

    for seg in usched.segments(us):
        if seg.has_w and not (seg.has_f or seg.has_b):
            x_recv, dy_recv, xbuf, gacc, loss_acc, act_stats, *wq = carry
            gacc = run_w_segment(seg.t0, seg.t1, gacc, tuple(wq))
            carry = (x_recv, dy_recv, xbuf, gacc, loss_acc, act_stats, *wq)
        else:
            xs = {}
            if seg.has_f:
                xs["f"] = f_tbl[seg.t0:seg.t1]
            if seg.has_b:
                xs["b"] = b_tbl[seg.t0:seg.t1]
            if seg.has_w:
                xs["w"] = w_tbl[seg.t0:seg.t1]
            carry, _ = jax.lax.scan(
                make_seg_body(seg.has_f, seg.has_b, seg.has_w,
                              seg.ring_fwd, seg.ring_bwd), carry, xs)
    _, _, _, grads, loss_acc, act_stats, *_ = carry

    # loss_acc is nonzero on the last stage only (cond zero branch elsewhere)
    if collect_stats:
        return loss_acc / global_count, grads, act_stats
    return loss_acc / global_count, grads


def _loss_and_grad_local(params, batch, cfg, pcfg, attn_fn,
                         collect_stats=False):
    """shard_map body: global-mean loss + fully reduced grads (+ per-stage
    activation stats when `collect_stats` — see utils/numerics.py).

    All `psum`s happen OUTSIDE `value_and_grad`: differentiating through a
    psum under shard_map with replication checking off re-reduces the already
    replicated cotangent and scales gradients by the axis size. The token
    count has no dependence on params, so the global normalizer can be
    computed up front and the differentiated function stays psum-free.
    """
    labels = batch["labels"]
    sp_size = jax.lax.axis_size(AXIS_SP)
    # valid-target count of this shard's slab (sp shards see boundary-crossing
    # targets via _sp_shift_labels, so counts add up exactly to the global one)
    local_count = (_sp_shift_labels(labels, sp_size) != llama.IGNORE_INDEX).sum()
    global_count = jnp.maximum(
        jax.lax.psum(local_count, (AXIS_DP, AXIS_SP)), 1).astype(jnp.float32)

    chunks = pcfg.accum_chunks
    chunk_pcfg = dataclasses.replace(
        pcfg, num_microbatches=pcfg.num_microbatches // chunks, accum_chunks=1)

    if pcfg.schedule in UNIT_SCHEDULES:
        # ONE interpreter for every hand-written-backward schedule: the
        # named schedules resolve to their canonical generated sequences,
        # `solver` to the loaded one (docs/SCHEDULES.md "Solver
        # schedules"). Generation is trace-time numpy — free.
        us = _unit_schedule_for(chunk_pcfg)

        def chunk_loss_and_grad(p, chunk_batch):
            out = _pipeline_units_local(p, chunk_batch, cfg, chunk_pcfg,
                                        attn_fn, global_count, us,
                                        collect_stats=collect_stats)
            return out if collect_stats else (*out, _sched_act_stats_zero(pcfg))
    else:
        def chunk_loss(p, chunk_batch):
            out = _pipeline_loss_local(p, chunk_batch, cfg, chunk_pcfg, attn_fn,
                                       collect_stats=collect_stats)
            # nonzero on the last stage only; stats ride as AD aux
            stats = out[2] if collect_stats else _ACT_STATS_ZERO()
            return out[0] / global_count, stats

        def chunk_loss_and_grad(p, chunk_batch):
            (l, stats), g = jax.value_and_grad(chunk_loss, has_aux=True)(
                p, chunk_batch)
            return l, g, stats

    if chunks == 1:
        local_loss, grads, act_stats = chunk_loss_and_grad(params, batch)
    else:
        # Sequential pipeline flushes: each chunk's fwd+bwd completes (and its
        # activations are freed) before the next starts; grads accumulate in
        # fp32. Normalizing every chunk by the same global token count makes
        # the sum exactly the full-batch gradient.
        chunked = jax.tree.map(
            lambda x: x.reshape((chunks, x.shape[0] // chunks) + x.shape[1:]), batch)

        def accum(carry, chunk_batch):
            acc_loss, acc_grads, acc_stats = carry
            l, g, s = chunk_loss_and_grad(params, chunk_batch)
            # stats fold across chunks: max of absmax, sums of (msq, n)
            stats = (jnp.maximum(acc_stats[0], s[0]),
                     acc_stats[1] + s[1], acc_stats[2] + s[2])
            return (acc_loss + l, jax.tree.map(jnp.add, acc_grads, g),
                    stats), None

        zero_grads = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
        (local_loss, grads, act_stats), _ = jax.lax.scan(
            accum, (jnp.float32(0.0), zero_grads, _sched_act_stats_zero(pcfg)),
            chunked)
    loss = jax.lax.psum(local_loss, (AXIS_PP, AXIS_DP, AXIS_SP))

    # Stage-sharded leaves: reduce across dp replicas and sp shards (each sp
    # shard saw only its sequence slab, so its grads are partial). Replicated
    # leaves (embed/norm/head): reduce across pp too so every replica stays
    # identical.
    with jax.named_scope(trace.SCOPE_GRAD_REDUCE):
        grads["layers"] = jax.lax.psum(grads["layers"], (AXIS_DP, AXIS_SP))
        for key in ("embed", "norm", "lm_head"):
            grads[key] = jax.lax.psum(grads[key],
                                      (AXIS_PP, AXIS_DP, AXIS_SP))
    if not collect_stats:
        return loss, grads

    # Per-stage activation stats stay STAGE-LOCAL over pp (out_spec P(pp)
    # stitches the [1]-shaped shard values into the global [S] vector) but
    # must be replicated over dp/sp/tp for the out_spec to be truthful:
    # absmax -> pmax, rms -> tick-weighted mean of mean-squares. Under the
    # interleaved schedule the accumulators are [v] per shard and the
    # reductions are elementwise; the stats then index [S, v] (the
    # *_per_chunk keys) with the per-stage keys reduced over chunks.
    absmax, msq_sum, n = act_stats
    absmax = jax.lax.pmax(absmax, (AXIS_DP, AXIS_SP, AXIS_TP))
    msq_sum = jax.lax.psum(msq_sum, (AXIS_DP, AXIS_SP))
    n = jax.lax.psum(n, (AXIS_DP, AXIS_SP))
    msq = jax.lax.pmax(msq_sum / jnp.maximum(n, 1.0),
                       AXIS_TP)  # tp replicas agree; pmax re-asserts it
    if pcfg.schedule in ("interleaved_1f1b", "zb1", "solver"):
        v = pcfg.virtual_stages
        stage_msq = jax.lax.pmax(
            jnp.sum(msq_sum) / jnp.maximum(jnp.sum(n), 1.0), AXIS_TP)
        stats = {"act_absmax_per_chunk": absmax.reshape(1, v),
                 "act_rms_per_chunk": jnp.sqrt(msq).reshape(1, v),
                 "act_absmax_per_stage": jnp.max(absmax).reshape(1),
                 "act_rms_per_stage": jnp.sqrt(stage_msq).reshape(1)}
    else:
        stats = {"act_absmax_per_stage": absmax.reshape(1),
                 "act_rms_per_stage": jnp.sqrt(msq).reshape(1)}
    return loss, grads, stats


def _check_stacked_layout(params_like: Params, pcfg: PipelineConfig) -> None:
    """The stacked param layout must match the schedule: interleaved wants
    the virtual-chunk axis ([S, v, k, ...] — stack_stages with a
    virtual_stages manifest), flat/gpipe the plain [S, k, ...]. A mismatch
    here means the manifest and the PipelineConfig came from different
    places; failing at build time beats a shape error deep inside shard_map."""
    shape = tuple(params_like["layers"]["attn"]["wq"].shape)
    if (pcfg.schedule in ("interleaved_1f1b", "zb1", "solver")
            and pcfg.virtual_stages > 1):
        if len(shape) != 5 or shape[1] != pcfg.virtual_stages:
            raise ValueError(
                f"schedule={pcfg.schedule} (virtual_stages="
                f"{pcfg.virtual_stages}) needs params stacked "
                f"[S, v, k, ...] — build them with stack_stages on a "
                f"StageManifest(virtual_stages={pcfg.virtual_stages}); got "
                f"a layer leaf of shape {shape}")
    elif len(shape) != 4:
        raise ValueError(
            f"schedule={pcfg.schedule!r} expects flat-stacked params "
            f"[S, k, ...]; got a layer leaf of shape {shape} (stacked with "
            f"a virtual_stages manifest? set schedule: interleaved_1f1b "
            f"or zb1)")


def make_pipeline_eval_fn(
    mesh: Mesh,
    cfg: LlamaConfig,
    pcfg: PipelineConfig,
    params_like: Params,
    attn_fn: Callable = attention,
) -> Callable[[Params, Batch], tuple[jnp.ndarray, jnp.ndarray]]:
    """Loss-only pipeline pass (no grads) for evaluation; returns the global
    (token-loss sum, valid-token count) pair for exact cross-batch weighting.

    Fills the hole in the reference, whose `do_eval`/evaluator config is dead
    (conf yaml:71-72,113-114 reference absent classes; SURVEY.md §2.4) — its
    trainer has no eval loop at all.
    """
    _check_stacked_layout(params_like, pcfg)
    param_specs = stage_param_specs(params_like, tp=mesh.shape[AXIS_TP] > 1)
    b_specs = batch_specs(mesh)
    if mesh.shape[AXIS_SP] > 1:
        attn_fn = make_sp_attention(pcfg.sequence_parallel, attn_fn,
                                    packed=pcfg.packed)

    def local(params, batch):
        labels = batch["labels"]
        sp_size = jax.lax.axis_size(AXIS_SP)
        count = jax.lax.psum(
            (_sp_shift_labels(labels, sp_size) != llama.IGNORE_INDEX).sum(),
            (AXIS_DP, AXIS_SP))
        loss_sum, _ = _pipeline_loss_local(params, batch, cfg, pcfg, attn_fn)
        # (sum, count) so callers can weight across batches exactly — no
        # mean-of-means bias (the defect this module fixes vs the reference)
        return jax.lax.psum(loss_sum, (AXIS_PP, AXIS_DP, AXIS_SP)), count

    return jax.shard_map(local, mesh=mesh, in_specs=(param_specs, b_specs),
                     out_specs=(P(), P()), check_vma=False)


def make_pipeline_loss_and_grad(
    mesh: Mesh,
    cfg: LlamaConfig,
    pcfg: PipelineConfig,
    params_like: Params,
    attn_fn: Callable = attention,
    collect_stats: bool = False,
) -> Callable[[Params, Batch], tuple]:
    """Build the (jit-able) SPMD loss+grad function over stage-stacked params.

    `params_like` supplies the pytree structure for spec construction only.
    `collect_stats` adds a third output: the numerics observatory's
    per-stage stage-boundary activation stats, `{"act_absmax_per_stage",
    "act_rms_per_stage"}` as [num_stages] arrays sharded over pp — computed
    in-graph (utils/numerics.py; no host round-trip).
    """
    if mesh.shape[AXIS_PP] != pcfg.num_stages:
        raise ValueError(
            f"PipelineConfig.num_stages={pcfg.num_stages} does not match the "
            f"mesh pp axis size {mesh.shape[AXIS_PP]}")
    _check_stacked_layout(params_like, pcfg)
    sp = mesh.shape[AXIS_SP]
    tp = mesh.shape[AXIS_TP]
    if pcfg.layer_counts is not None:
        k_max = jax.tree.leaves(params_like["layers"])[0].shape[1]
        if sum(pcfg.layer_counts) != cfg.num_hidden_layers:
            raise ValueError(
                f"layer_counts {pcfg.layer_counts} sum to "
                f"{sum(pcfg.layer_counts)} but the model has "
                f"{cfg.num_hidden_layers} layers")
        if max(pcfg.layer_counts) != k_max:
            raise ValueError(
                f"layer_counts {pcfg.layer_counts} (max "
                f"{max(pcfg.layer_counts)}) do not match the stacked params' "
                f"{k_max} slots per stage — stack_stages used a different "
                f"manifest")
    if sp > 1 and pcfg.sequence_parallel == "ulysses":
        local_heads = cfg.num_attention_heads // max(tp, 1)
        if local_heads % sp:
            raise ValueError(
                f"sequence_parallel=ulysses needs heads/tp divisible by sp: "
                f"{cfg.num_attention_heads}/{tp} = {local_heads} vs sp={sp} "
                f"(use sequence_parallel=ring, which has no head constraint)")
    if pcfg.loss_chunks > 1:
        if tp > 1:
            raise ValueError(
                "loss_chunks > 1 is redundant under tp > 1: the "
                "vocab-parallel CE already never materializes full logits")
        if cfg.vocab_size % pcfg.loss_chunks:
            raise ValueError(
                f"loss_chunks={pcfg.loss_chunks} must divide "
                f"vocab_size={cfg.vocab_size}")
    if pcfg.kernel_ce and tp > 1:
        raise ValueError(
            "kernels.ce=pallas is redundant under tp > 1: the "
            "vocab-parallel CE already never materializes full logits "
            "(shard the head wider instead)")
    if pcfg.kernel_ce and jax.default_backend() == "tpu":
        # The binding VMEM term is the backward dW kernel's fp32
        # [d, V/loss_chunks] scratch plus its double-buffered output block
        # of the same shape (4 B/elem regardless of the compute dtype; the
        # fwd/dh kernels' blocks are smaller) against the kernels' scoped
        # budget (ops/pallas_common.VMEM_LIMIT_BYTES). Refuse at build time
        # — with the actionable knob — instead of dying deep inside a
        # Mosaic allocation failure. Interpret mode (every other backend)
        # has no such limit, which is why this cannot live in
        # PipelineConfig.__post_init__. On a v5e, 7B width compiles at
        # 640-, 256- and 128-wide tiles (PERF.md "Bring-up").
        tile = cfg.hidden_size * (cfg.vocab_size // pcfg.loss_chunks) * 4
        if 3 * tile > VMEM_LIMIT_BYTES:
            raise ValueError(
                f"kernels.ce=pallas needs its fp32 [hidden, "
                f"vocab/loss_chunks] dW scratch and output blocks to fit "
                f"VMEM: 3 x [{cfg.hidden_size}, "
                f"{cfg.vocab_size // pcfg.loss_chunks}] is "
                f"{3 * tile / (1 << 20):.0f} MiB against the "
                f"{VMEM_LIMIT_BYTES >> 20} MiB scoped budget — raise "
                f"loss_vocab_chunks (128-wide tiles: "
                f"loss_vocab_chunks={max(cfg.vocab_size // 128, 1)}) or "
                f"fall back to kernels.ce=xla (docs/KERNELS.md)")
    if pcfg.kernel_prologue and jax.default_backend() == "tpu":
        # Same build-time posture for the prologue, which has no chunking
        # knob: every grid step holds the WHOLE wq/wk/wv (forward, dhidden)
        # or three fp32 [d, width_local] dW scratches plus same-shape
        # output blocks (dW) in VMEM. On a v5e the kernel compiles and runs
        # at tp=8 width (3 x [4096, 512]); at full 7B width Mosaic refuses
        # it, in its own words (PERF.md "Bring-up"). Until it is re-tiled
        # over the weight columns (ROADMAP A6) the remedies are tp-sharding
        # the projections or the XLA path.
        widths = (cfg.hidden_size + 2 * cfg.kv_heads * cfg.head_dim) // tp
        scratch = cfg.hidden_size * widths * 4
        if 2 * scratch > VMEM_LIMIT_BYTES:
            raise ValueError(
                f"kernels.prologue=pallas keeps whole weights in VMEM: "
                f"{2 * scratch / (1 << 20):.0f} MiB of fp32 dW scratch + "
                f"output blocks ([{cfg.hidden_size}] rows x {widths} local "
                f"q+k+v columns) against the {VMEM_LIMIT_BYTES >> 20} MiB "
                f"scoped budget. Mosaic on a TPU v5e, forward kernel at "
                f"[4096] x 3 x [4096]: \"RESOURCE_EXHAUSTED: Ran out of "
                f"memory in memory space vmem while allocating on stack "
                f"... Scoped allocation with size 100.25M and limit 64.00M "
                f"exceeded scoped vmem limit by 36.25M.\" Shard the "
                f"projections wider (tp) or use kernels.prologue=xla "
                f"(docs/KERNELS.md)")
    if tp > 1:
        if cfg.kv_heads % tp or cfg.num_attention_heads % tp:
            raise ValueError(
                f"tp={tp} must divide both num_attention_heads="
                f"{cfg.num_attention_heads} and kv_heads={cfg.kv_heads}")
        if cfg.intermediate_size % tp:
            raise ValueError(f"tp={tp} must divide intermediate_size={cfg.intermediate_size}")
        if cfg.vocab_size % tp:
            raise ValueError(f"tp={tp} must divide vocab_size={cfg.vocab_size} "
                             f"(vocab-parallel lm_head)")
    param_specs = stage_param_specs(params_like, tp=tp > 1)
    if sp > 1:
        attn_fn = make_sp_attention(pcfg.sequence_parallel, attn_fn,
                                    packed=pcfg.packed)

    out_specs: tuple = (P(), param_specs)
    if collect_stats:
        stats_specs = {"act_absmax_per_stage": P(AXIS_PP),
                       "act_rms_per_stage": P(AXIS_PP)}
        if pcfg.schedule in ("interleaved_1f1b", "zb1", "solver"):
            # [1, v] local -> [S, v] global; the chunk axis is replicated
            stats_specs.update({"act_absmax_per_chunk": P(AXIS_PP),
                                "act_rms_per_chunk": P(AXIS_PP)})
        out_specs += (stats_specs,)
    fn = jax.shard_map(
        partial(_loss_and_grad_local, cfg=cfg, pcfg=pcfg, attn_fn=attn_fn,
                collect_stats=collect_stats),
        mesh=mesh,
        in_specs=(param_specs, batch_specs(mesh)),
        out_specs=out_specs,
        check_vma=False,
    )
    return fn


def batch_specs(mesh: Mesh) -> dict:
    """Batch PartitionSpecs: batch dim over dp, sequence dim over sp (when
    the mesh has one — every field is per-token [b, L] data, SURVEY.md §3.5)."""
    spec = P(AXIS_DP, AXIS_SP) if mesh.shape[AXIS_SP] > 1 else P(AXIS_DP)
    return {"input_ids": spec, "attention_mask": spec,
            "position_ids": spec, "labels": spec}
