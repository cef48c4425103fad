"""AOT memory preflight: compile the full train step for a big config on a
VIRTUAL device mesh and report XLA's per-device memory analysis vs the HBM
budget — no hardware needed.

This backs the BASELINE ladder's large configs (conf/llama_65b_pp8_tp2_dp2.yaml,
conf/codellama_34b_16k.yaml, conf/llama2_70b_pp4_tp4_dp2.yaml) with a
checked artifact instead of hand-computed HBM comments: the same technique
tests/test_pipeline.py::test_1f1b_memory_bounded_in_microbatches uses to pin
the 1F1B memory bound. The reference had no equivalent — its 65B memory
story is a README sentence (reference README.md:70-71).

Caveats (printed with the report): the analysis is XLA-CPU's compilation of
the SPMD program — TPU layouts/padding and Mosaic (flash) kernel VMEM differ,
so treat the numbers as an estimate with margin, not a guarantee.

Usage:
  python tools/preflight.py --config conf/llama_65b_pp8_tp2_dp2.yaml \
      [--hbm-gb 95] [key=value ...]
Exit code 1 when the estimate exceeds the budget.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _mesh_product(config_path: str, overrides: list[str]) -> int:
    """Device count from the yaml's mesh block WITHOUT importing the package
    (jax must see XLA_FLAGS before its first import)."""
    import yaml

    with open(config_path) as f:
        raw = yaml.safe_load(f)
    mesh = dict(raw.get("mesh") or {})
    for ov in overrides:
        key, _, val = ov.lstrip("-").partition("=")
        if key.startswith("mesh."):
            mesh[key[len("mesh."):]] = int(val)
    n = 1
    for axis in ("pp", "dp", "tp", "sp"):
        n *= int(mesh.get(axis, 1))
    return n


def _host_transfers_enabled() -> bool:
    from llama_pipeline_parallel_tpu.utils import host_stash

    return host_stash.transfers_enabled()


def counted_device_terms_gib(pcfg, dims: tuple) -> float:
    """GiB a GATED-OFF compile (no host memory space) keeps device-resident
    for the schedule's ring/stash stores: the full buffers, plus the host
    rings' garbage slots for the stores marked tiered — what must be
    subtracted from an anchored compile's peak before re-adding the real
    shape's terms (see preflight()'s anchored-compile mode)."""
    from llama_pipeline_parallel_tpu.parallel import pipeline as pl

    mb_rows, local_seqlen, hidden_size, dtype_bytes = dims
    slot = mb_rows * local_seqlen * hidden_size * dtype_bytes
    total = (pl.activation_ring_bytes(pcfg, *dims)
             + pl.wgrad_stash_bytes(pcfg, *dims))
    if pl.wgrad_partition(pcfg)[1]:
        total += 2 * slot
    if pcfg.offload_activations and pl.activation_ring_slots(pcfg):
        total += slot
    return total / (1 << 30)


def _step_compute_seconds(model_cfg, mesh_cfg, pcfg, mb_rows: int, seq: int,
                          mfu: float, chip_flops: float | None) -> float:
    """Modeled per-device compute seconds of one training step: the
    overlap budget the offload traffic must hide inside. Uses the same
    train_flops_per_token the bench MFU math uses; each device sees its dp
    shard's tokens through 1/(pp*tp*sp) of the model."""
    from llama_pipeline_parallel_tpu.utils.metrics import (
        detect_chip_peak_flops,
        train_flops_per_token,
    )

    peak = chip_flops or detect_chip_peak_flops() or 197e12
    tokens = mb_rows * pcfg.num_microbatches * seq
    shards = mesh_cfg.pp * mesh_cfg.tp * mesh_cfg.sp
    return train_flops_per_token(model_cfg, seq) * tokens / shards / (
        peak * max(mfu, 1e-6))


def offload_traffic_bytes(pcfg, dims: tuple) -> int:
    """Host-link bytes ONE STEP moves for the enabled offload knobs, both
    directions (every tiered residual goes D2H once at stash time and H2D
    once at consume time; accum_chunks shifts WHEN, not how much): the
    zb1 W queue moves 2 buffers per unit x Mv units x 2 directions, the
    activation ring 1 buffer per unit x 2 directions."""
    from llama_pipeline_parallel_tpu.parallel import pipeline as pl

    mb_rows, local_seqlen, hidden_size, dtype_bytes = dims
    slot = mb_rows * local_seqlen * hidden_size * dtype_bytes
    units = pcfg.num_microbatches * pcfg.virtual_stages
    total = 0
    # W-residual link traffic. A MIXED per-unit vector is charged the FULL
    # per-flush unit count, not just the tiered subset: the interpreter's
    # tick-uniform SPMD body pushes the host buffer every B tick (the
    # predicate only redirects non-tiered units to the garbage slot — the
    # D2H copy still moves) and where-selects every W pop from both
    # buffers (one H2D per unit either way). Selective offload's win is
    # host RESIDENCY (few slots live), never link bytes — the model must
    # not promise hiding the hardware won't deliver.
    hbm_slots, host_slots = pl.wgrad_partition(pcfg)
    if host_slots:
        wgrad_units = (pl.wgrad_offloaded_units(pcfg) if hbm_slots == 0
                       else units // pcfg.accum_chunks)
        total += 4 * wgrad_units * pcfg.accum_chunks * slot
    if pcfg.offload_activations and pl.activation_ring_slots(pcfg):
        total += 2 * units * slot
    return total


def offload_feasibility(pcfg, dims: tuple, step_compute_s: float,
                        host_bw_gibps: float) -> dict:
    """The bandwidth half of the memory model: modeled transfer seconds
    over modeled compute seconds (`offload_hide_ratio`). Ratios <= 1 can
    in principle hide entirely behind compute (XLA's async copies overlap
    the scan phases — parallel/pipeline.py); ratios above it WILL stall
    the step no matter how the copies are scheduled."""
    gib = 1 << 30
    traffic = offload_traffic_bytes(pcfg, dims)
    transfer_s = traffic / (host_bw_gibps * gib)
    return {
        "offload_traffic_gib_per_step": round(traffic / gib, 2),
        "offload_transfer_s_model": round(transfer_s, 3),
        "offload_compute_s_model": round(step_compute_s, 3),
        "offload_hide_ratio": round(transfer_s / max(step_compute_s, 1e-9),
                                    3),
    }


# ---------------------------------------------------------------------------
# Schedule selection: enumerate (schedule, v, accum, offload) candidates
# against the budget and pick analytically (OptPipe-style: solve for the
# schedule/memory trade instead of hand-picking it — PAPERS.md 2510.05186)
# ---------------------------------------------------------------------------

def _stash_device_bytes(hbm_slots: int, host_slots: int, slot: int) -> int:
    """Device-resident bytes of a W queue's slot split: the full HBM-side
    buffers plus, when anything tiers to host, the in-flight transfer
    slots (2 per buffer direction, capped at 4 slot-equivalents). ONE
    spelling shared by candidate_device_terms_gib and solver_candidates'
    binary-search estimator so the two can never drift."""
    return 2 * hbm_slots * slot + (min(2 * host_slots * slot, 4 * slot)
                                   if host_slots else 0)


def candidate_device_terms_gib(pcfg, dims: tuple, vocab: int | None = None
                               ) -> dict:
    """The schedule-DEPENDENT device-memory terms of one candidate, GiB:
    the stage-input ring buffer and (zb1) the W stash — each replaced by
    two in-flight transfer slots when its store tiers to host — plus, when
    `vocab` is given, the last stage's loss-head term (the live fp32
    logits block + chunked-backward dh accumulator of the XLA path; ~0 for
    `kernels.ce: pallas` — pl.loss_head_bytes). Everything else in the
    step (weights, grads, optimizer, transient activations) is
    schedule-independent at fixed batch shape, which is what lets selection
    anchor on ONE compiled peak (see select_schedule)."""
    from llama_pipeline_parallel_tpu.parallel import pipeline as pl

    gib = 1 << 30
    mb_rows, local_seqlen, hidden_size, dtype_bytes = dims
    slot = mb_rows * local_seqlen * hidden_size * dtype_bytes
    ring = pl.activation_ring_bytes(pcfg, *dims)
    ring_dev = min(ring, 2 * slot) if pcfg.offload_activations else ring
    hbm_slots, host_slots = pl.wgrad_partition(pcfg)
    stash_dev = _stash_device_bytes(hbm_slots, host_slots, slot)
    head = (pl.loss_head_bytes(pcfg, mb_rows, local_seqlen, hidden_size,
                               vocab) if vocab else 0)
    return {"ring_gib": ring_dev / gib, "stash_gib": stash_dev / gib,
            "host_gib": pl.host_stash_bytes(pcfg, *dims) / gib,
            "loss_head_gib": head / gib}


def enumerate_candidates(num_stages: int, microbatches: int, num_layers: int,
                         max_virtual: int = 4,
                         accum_options: tuple = (1, 2, 4, 8),
                         ce_options: tuple | None = None,
                         layer_counts: tuple | None = None) -> list:
    """Every valid PipelineConfig in the selection grid: schedule x
    virtual_stages (layer-divisible) x accum_chunks (microbatch-divisible)
    x offload tiers (wgrad for zb1, activations for all hand-written
    backwards) x — when `ce_options` is given — the loss-head axis, each
    entry a (loss_chunks, kernel_ce) pair (docs/KERNELS.md; the default
    keeps the legacy grid so the axis is opt-in). Validity delegates to
    PipelineConfig's own constructor — one source of truth for the
    divisibility rules.

    `layer_counts`: an UNEQUAL stage partition (from
    StageManifest.balanced at layer-indivisible pp — the layout lane's
    cost-balancing). Offered to the flat and zb1-v1 schedules only (the
    round-robin chunk layout has no uneven form); their bubble_fraction is
    then counted with per-stage unit costs (parallel/schedule.py)."""
    from llama_pipeline_parallel_tpu.parallel import pipeline as pl

    uneven = (layer_counts is not None and len(set(layer_counts)) != 1)
    ce_axis = tuple(ce_options) if ce_options else ((1, False),)
    cands = []
    for schedule in ("1f1b", "interleaved_1f1b", "zb1"):
        if schedule == "1f1b":
            vs = (1,)
        elif uneven:
            vs = (1,) if schedule == "zb1" else ()
        else:
            vs = tuple(v for v in (1, 2, 4)
                       if v <= max_virtual
                       and num_layers % (num_stages * v) == 0)
        for v in vs:
            for c in accum_options:
                offloads = [(False, False), (False, True)]
                if schedule == "zb1":
                    offloads += [(True, False), (True, True)]
                for ow, oa in offloads:
                    for ce_chunks, ce_kernel in ce_axis:
                        try:
                            cands.append(pl.PipelineConfig(
                                num_stages=num_stages,
                                num_microbatches=microbatches,
                                schedule=schedule, virtual_stages=v,
                                accum_chunks=c, offload_wgrad=ow,
                                offload_activations=oa,
                                loss_chunks=ce_chunks,
                                kernel_ce=ce_kernel,
                                layer_counts=layer_counts))
                        except ValueError:
                            continue
    return cands


def solver_candidates(num_stages: int, microbatches: int, num_layers: int,
                      base_gib: float, dims: tuple, hbm_gb: float,
                      max_virtual: int = 4,
                      accum_options: tuple = (1, 2, 4, 8),
                      head_gib: float = 0.0,
                      mem_scale: float = 1.0) -> list:
    """Solver-EMITTED sequences as selection candidates (the list-scheduling
    search beyond the three canonical shapes — docs/SCHEDULES.md 'Solver
    schedules'). For each split-backward (v, accum, W-placement) grid
    point the list scheduler emits a sequence, then sizes its per-unit
    offload decision vector against the budget: tier the MINIMUM number
    of residual units for base + ring + remaining HBM stash slots to fit
    (fewest tiered bytes at the canonical bubble — strictly better than
    the all-or-nothing boolean whenever 0 < k < n fits). The k=0 and
    k=n_units boundary points reproduce `offload.wgrad_stash` off/on
    exactly. Candidates that cannot fit even fully tiered are emitted
    fully tiered and left for select_schedule to refuse with the others."""
    from llama_pipeline_parallel_tpu.parallel import pipeline as pl
    from llama_pipeline_parallel_tpu.parallel import schedule as usched

    import numpy as np

    gib = 1 << 30
    mb_rows, local_seqlen, hidden_size, dtype_bytes = dims
    slot = mb_rows * local_seqlen * hidden_size * dtype_bytes
    cands = []
    vs = tuple(v for v in (1, 2, 4)
               if v <= max_virtual and num_layers % (num_stages * v) == 0)
    for v in vs:
        for c in accum_options:
            if microbatches % c:
                continue
            m_flush = microbatches // c
            if v > 1 and m_flush % num_stages:
                continue
            for placement in ("trailing", "drain"):
                try:
                    seq = usched.list_schedule(m_flush, num_stages, v,
                                               w_placement=placement)
                except usched.ScheduleError:
                    continue

                def build(vector):
                    s = usched.with_offload(seq, vector)
                    return pl.PipelineConfig(
                        num_stages=num_stages, num_microbatches=microbatches,
                        schedule="solver", virtual_stages=v, accum_chunks=c,
                        unit_schedule=s)

                # the ring term is offload-vector-invariant: hoist it out
                # of the binary search
                ring = seq.ring_slots * slot if bool(seq.has_f.any()) else 0

                def est(vector):
                    # must mirror select_schedule's scoring — candidate_
                    # device_terms_gib for a no-activation-offload solver
                    # config (the stash term via the SHARED
                    # _stash_device_bytes spelling) — including the
                    # loss-head term it charges when a vocab is in play
                    # (`head_gib` — solver rows run the as-written dense
                    # head; a vector sized without it would come up short
                    # at exactly the tight budgets this lane exists for).
                    # Computed from the slot assignment DIRECTLY (not via
                    # a PipelineConfig, whose constructor re-validates the
                    # whole sequence — the binary search probes this a
                    # dozen times per grid point, and the layout lane runs
                    # the grid per mesh)
                    s = usched.with_offload(seq, vector)
                    stash = _stash_device_bytes(s.wq_hbm_slots,
                                                s.wq_host_slots, slot)
                    # mem_scale: the calibrated live/model peak ratio
                    # (perf.derive_calibration) — the SAME scaling
                    # select_schedule applies, or the vector would be
                    # sized against a different budget than it's scored by
                    return (base_gib + (ring + stash) / gib
                            + head_gib) * mem_scale

                n = seq.n_units
                if est(np.zeros(n, bool)) <= hbm_gb:
                    k = 0
                else:
                    # minimal k: tier the earliest-scheduled units first
                    # (their transfers start streaming soonest); binary
                    # search on the actual slot assignment, not the
                    # arithmetic guess — drain placements reuse slots
                    lo, hi = 1, n
                    while lo < hi:
                        mid = (lo + hi) // 2
                        vec = np.zeros(n, bool)
                        vec[:mid] = True
                        if est(vec) <= hbm_gb:
                            hi = mid
                        else:
                            lo = mid + 1
                    k = lo
                vec = np.zeros(n, bool)
                vec[:k] = True
                cands.append(build(vec))
    return cands


def select_schedule(candidates: list, base_gib: float, dims: tuple,
                    hbm_gb: float, host_bw_gibps: float,
                    step_compute_fn, hide_max: float = 1.0,
                    vocab: int | None = None,
                    mem_scale: float = 1.0) -> tuple:
    """Score every candidate against the HBM budget AND the host-bandwidth
    bound, and pick the feasible one with the lowest analytic bubble
    (ties: lower host residency first — never move bytes for nothing —
    then lower device peak; the ce axis resolves through the peak, since
    the loss-head term is the only byte it moves). `base_gib` is the
    schedule-independent anchor: the as-written config's compiled device
    peak minus ITS ring/stash (and, with `vocab`, loss-head) terms.
    `step_compute_fn(pcfg) -> seconds` models the overlap budget
    (accum_chunks does not change it — same flops, more flushes).
    `mem_scale` (measured live peak / byte-model peak, from the memory
    observatory via `--calibration`) scales every candidate's estimate —
    a >1 ratio tightens the feasibility cut to what the live telemetry
    actually saw, re-ranking the frontier from measurement.
    Returns (winner_row_or_None, all_rows)."""
    from llama_pipeline_parallel_tpu.parallel import pipeline as pl

    rows = []
    for pcfg in candidates:
        terms = candidate_device_terms_gib(pcfg, dims, vocab)
        est = (base_gib + terms["ring_gib"] + terms["stash_gib"]
               + terms["loss_head_gib"]) * mem_scale
        feas = offload_feasibility(pcfg, dims, step_compute_fn(pcfg),
                                   host_bw_gibps)
        fits_hbm = est <= hbm_gb
        hides = feas["offload_hide_ratio"] <= hide_max
        row_extra = {}
        if pcfg.schedule == "solver":
            us = pcfg.unit_schedule
            row_extra = {"label": us.label,
                         "wgrad_offload_units": us.offloaded_units,
                         "wgrad_units_total": us.n_units,
                         "_pcfg": pcfg}
        rows.append({
            "schedule": pcfg.schedule, "virtual_stages": pcfg.virtual_stages,
            "accum_chunks": pcfg.accum_chunks,
            "offload_wgrad": pcfg.offload_wgrad,
            "offload_activations": pcfg.offload_activations,
            "loss_chunks": pcfg.loss_chunks,
            "kernel_ce": pcfg.kernel_ce,
            **row_extra,
            "est_peak_gib": round(est, 2) + 0.0,  # normalize -0.0
            "host_stash_gib": round(terms["host_gib"], 2) + 0.0,
            "loss_head_gib": round(terms["loss_head_gib"], 2) + 0.0,
            "bubble_fraction": round(pl.bubble_fraction(pcfg), 4),
            "hide_ratio": feas["offload_hide_ratio"],
            "feasible": fits_hbm and hides,
            "why_not": None if fits_hbm and hides else
                       ("exceeds HBM budget" if not fits_hbm else
                        "offload traffic cannot hide behind compute"),
        })
    feasible = [r for r in rows if r["feasible"]]
    winner = min(feasible, key=lambda r: (r["bubble_fraction"],
                                          r["host_stash_gib"],
                                          r["est_peak_gib"]),
                 default=None)
    return winner, rows


def ce_axis_options(loss_chunks: int, vocab: int, tp: int) -> tuple | None:
    """The loss-head axis --select scores (docs/KERNELS.md): the as-written
    chunking, an 8-way chunked XLA head where the vocab divides, and ONE
    Pallas option at the kernel's own VMEM sizing — lane-exact 128-wide
    vocab tiles (V/128 chunks), per pallas_ce_sum_count's contract. The
    XLA-scale chunk counts are never offered for the kernel: its
    [d, V/chunks] weight tile at 8 chunks is tens of MiB against ~16 MiB
    VMEM, a Mosaic refusal interpret-mode CI cannot see. None at tp>1: the
    head is already vocab-parallel there and the trainer REJECTS
    loss_chunks/kernels.ce overrides, so selection must not emit them."""
    if tp > 1:
        return None
    opts = {(loss_chunks, False)}
    if vocab % 8 == 0:
        opts.add((8, False))
    if vocab % 128 == 0:
        opts.add((vocab // 128, True))
    return tuple(sorted(opts))


# ---------------------------------------------------------------------------
# Layout auto-selection: grow the OUTER (pp, tp, dp, sp) axes for a device
# count, re-evaluate the memory model per candidate mesh, rank the frontier
# by an analytic step-time score, and emit the supervisor ladder as DATA
# (ROADMAP item 3: the hand-written --layout-ladder becomes generated).
# ---------------------------------------------------------------------------

def _divisors(n: int) -> tuple:
    return tuple(d for d in range(1, n + 1) if n % d == 0)


def enumerate_layouts(devices: int, model_cfg, seq: int,
                      global_batch_examples: int, mb_rows: int,
                      max_tp: int = 8, max_sp: int = 4) -> list[dict]:
    """Every (pp, tp, dp, sp) mesh of EXACTLY `devices` chips the model and
    batch shape admit, each with its microbatch count at the PRESERVED
    global batch (the elastic data contract: a dp change is compensated in
    gradient_accumulation_steps, never in examples/step) and its stage
    partition (even where layers divide, StageManifest.balanced counts
    where they don't — the unequal-stage lever SkipPipe/MPMD-PP open).

    The divisibility rules mirror the trainer's own validation
    (parallel/pipeline.py make_pipeline_loss_and_grad, mesh.MeshConfig):
    anything emitted here must survive the launch line."""
    from llama_pipeline_parallel_tpu.models.llama.manifest import StageManifest

    layouts = []
    for pp in _divisors(devices):
        if pp > model_cfg.num_hidden_layers:
            continue
        for tp in _divisors(devices // pp):
            if tp > max_tp:
                continue
            if (model_cfg.num_attention_heads % tp
                    or model_cfg.kv_heads % tp
                    or model_cfg.intermediate_size % tp
                    or model_cfg.vocab_size % tp):
                continue
            for sp in _divisors(devices // (pp * tp)):
                if sp > max_sp or seq % sp:
                    continue
                dp = devices // (pp * tp * sp)
                micro, rem = divmod(global_batch_examples, mb_rows * dp)
                if rem or micro < 1:
                    continue
                if model_cfg.num_hidden_layers % pp == 0:
                    counts = None
                else:
                    counts = StageManifest.balanced(
                        model_cfg, pp).stage_layer_counts
                layouts.append({"pp": pp, "tp": tp, "dp": dp, "sp": sp,
                                "microbatches": micro,
                                "layer_counts": counts})
    return layouts


def layout_device_gib(model_cfg, pp: int, tp: int, dp: int,
                      layer_counts: tuple | None = None,
                      optimizer_offload: bool = True,
                      zero2: bool = True) -> float:
    """Schedule-INDEPENDENT analytic device memory of a layout, GiB: the
    bf16 working params of one stage's (padded) layer slots at the tp
    shard width plus the replicated embed / final norm / vocab-parallel
    lm-head, the fp32 gradient trees the step holds live (accumulator +
    per-tick grads + returned grads — the returned tree dp-sharded under
    ZeRO-2's reduce-scatter), and — on the fused path — the fp32 masters +
    dp-sharded Adam moments. The schedule-dependent ring/stash/loss-head
    terms are NOT here: candidate_device_terms_gib adds them per schedule
    candidate, exactly as the fixed-mesh selection does.

    This is a model, not a compile: --select calibrates it against the one
    compiled peak it already paid for (the residual covers transient
    activations and XLA slack, scaled to each layout's per-tick work) and
    the verdicts inherit the usual CPU-estimate caveat."""
    import numpy as np

    d = model_cfg.hidden_size
    kv_dim = model_cfg.kv_heads * model_cfg.head_dim
    matmul = (2 * d * d + 2 * d * kv_dim
              + 3 * d * model_cfg.intermediate_size)
    k_max = (max(layer_counts) if layer_counts
             else -(-model_cfg.num_hidden_layers // pp))
    stage = k_max * (matmul / tp + 2 * d)
    shared = (model_cfg.vocab_size * d            # embed, replicated
              + model_cfg.vocab_size * d / tp     # lm-head, vocab-parallel
              + d)                                # final norm
    n = stage + shared
    dtype_b = np.dtype(model_cfg.dtype).itemsize
    weights = n * dtype_b
    if optimizer_offload:
        grads = n * 4 * (2 + (1.0 / dp if zero2 else 1.0))
        opt = 0.0
    else:
        grads = n * 4 * 2
        opt = n * 4 + n * 8 / dp  # fp32 masters + ZeRO-1 dp-sharded moments
    return (weights + grads + opt) / (1 << 30)


def layout_step_seconds(model_cfg, lay: dict, bubble: float, mb_rows: int,
                        seq: int, mfu: float, chip_flops: float | None,
                        ici_bw_gibps: float, zero2: bool = True) -> float:
    """Analytic per-step seconds of a layout running its chosen schedule —
    the RANKING score of the frontier (absolute accuracy is not the point;
    bench.py's extra:layout-* rows put the measured number next to it):

      compute/(1-bubble)           the lockstep pipeline wall (compute is
                                   layout-invariant at fixed devices — the
                                   whole model's flops spread over all
                                   chips — so bubble and collectives are
                                   what separate layouts)
    + tp allreduces                4 per layer per microbatch of the
                                   [mb, seq/sp, d] block (Megatron f/g),
                                   ring-allreduce 2(tp-1)/tp bytes
    + dp gradient reduction        the stage's fp32 grads, reduce-scatter
                                   (dp-1)/dp under ZeRO-2, allreduce
                                   2(dp-1)/dp otherwise
    + pp ring handoff              one [mb, seq/sp, d] slab per unit each
                                   direction
    + sp ring-attention rotation   (sp-1) k/v-slab hops per layer per
                                   microbatch, ~3x for fwd+bwd

    Collectives are charged SERIALLY at --ici-bw-gibps — conservative (XLA
    overlaps some of them), which is the right bias for a ranking that
    must not over-promise exotic layouts."""
    import numpy as np

    from llama_pipeline_parallel_tpu.utils.metrics import (
        detect_chip_peak_flops,
        train_flops_per_token,
    )

    pp, tp, dp, sp = lay["pp"], lay["tp"], lay["dp"], lay["sp"]
    micro = lay["microbatches"]
    devices = pp * tp * dp * sp
    peak = chip_flops or detect_chip_peak_flops() or 197e12
    tokens = mb_rows * micro * dp * seq
    t_comp = (train_flops_per_token(model_cfg, seq) * tokens / devices
              / (peak * max(mfu, 1e-6)))
    wall = t_comp / max(1.0 - bubble, 1e-6)

    d = model_cfg.hidden_size
    dtype_b = np.dtype(model_cfg.dtype).itemsize
    bw = ici_bw_gibps * (1 << 30)
    slab = mb_rows * (seq // sp) * d * dtype_b
    counts = lay.get("layer_counts")
    k_max = max(counts) if counts else -(-model_cfg.num_hidden_layers // pp)
    t_tp = (2 * (tp - 1) / tp) * 4 * k_max * micro * slab / bw if tp > 1 \
        else 0.0
    kv_dim = model_cfg.kv_heads * model_cfg.head_dim
    matmul = 2 * d * d + 2 * d * kv_dim + 3 * d * model_cfg.intermediate_size
    stage_grads = k_max * (matmul / tp) * 4
    dp_factor = (dp - 1) / dp if zero2 else 2 * (dp - 1) / dp
    t_dp = dp_factor * stage_grads / bw if dp > 1 else 0.0
    t_pp = 2 * micro * slab / bw if pp > 1 else 0.0
    kv_slab = 2 * mb_rows * (seq // sp) * kv_dim * dtype_b
    t_sp = 3 * (sp - 1) * k_max * micro * kv_slab / bw if sp > 1 else 0.0
    return wall + t_tp + t_dp + t_pp + t_sp


def layout_frontier(model_cfg, devices: int, mb_rows: int, seq: int,
                    global_batch_examples: int, base_gib_aw: float,
                    aw_layout: tuple, hbm_gb: float,
                    host_bw_gibps: float = 30.0, mfu: float = 0.45,
                    chip_flops: float | None = None,
                    ici_bw_gibps: float = 90.0, hide_max: float = 1.0,
                    optimizer_offload: bool = True, zero2: bool = True,
                    loss_chunks_aw: int = 1, vocab_enabled: bool = True,
                    solver_lane: bool = True,
                    max_virtual: int = 4, mem_scale: float = 1.0) -> tuple:
    """The full (pp, tp, dp, sp) frontier at `devices` chips: per layout,
    re-run the schedule/offload/ce selection against the memory model at
    THAT mesh (base re-derived analytically, calibrated by the residual
    between the as-written layout's compiled base `base_gib_aw` and its
    analytic model; the residual — transients + XLA slack — scales with
    each layout's per-tick tp/sp shard width), then rank the feasible
    survivors by layout_step_seconds. Returns (winner_row, rows) ordered
    best-first. Pure arithmetic: the one compile was already paid for."""
    from llama_pipeline_parallel_tpu.parallel import pipeline as pl
    from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig

    pp_aw, tp_aw, dp_aw, sp_aw = aw_layout
    residual = base_gib_aw - layout_device_gib(
        model_cfg, pp_aw, tp_aw, dp_aw,
        optimizer_offload=optimizer_offload, zero2=zero2)
    rows = []
    for lay in enumerate_layouts(devices, model_cfg, seq,
                                 global_batch_examples, mb_rows):
        pp, tp, dp, sp = lay["pp"], lay["tp"], lay["dp"], lay["sp"]
        micro = lay["microbatches"]
        dims = pl.stash_dims(mb_rows, seq, sp, model_cfg.hidden_size,
                             model_cfg.dtype)
        base = (layout_device_gib(model_cfg, pp, tp, dp,
                                  layer_counts=lay["layer_counts"],
                                  optimizer_offload=optimizer_offload,
                                  zero2=zero2)
                + residual * (tp_aw * sp_aw) / (tp * sp))
        ce_axis = (ce_axis_options(loss_chunks_aw, model_cfg.vocab_size, tp)
                   if vocab_enabled else None)
        vocab = (model_cfg.vocab_size if vocab_enabled and tp <= 1 else None)
        cands = enumerate_candidates(pp, micro, model_cfg.num_hidden_layers,
                                     max_virtual=max_virtual,
                                     ce_options=ce_axis,
                                     layer_counts=lay["layer_counts"])
        if solver_lane and lay["layer_counts"] is None:
            solver_head = 0.0
            if vocab:
                solver_head = pl.loss_head_bytes(
                    pl.PipelineConfig(num_stages=pp, num_microbatches=micro),
                    *dims[:3], vocab) / (1 << 30)
            cands += solver_candidates(pp, micro,
                                       model_cfg.num_hidden_layers, base,
                                       dims, hbm_gb, max_virtual=max_virtual,
                                       head_gib=solver_head,
                                       mem_scale=mem_scale)
        mesh_cfg = MeshConfig(pp=pp, tp=tp, dp=dp, sp=sp)
        compute_fn = lambda c, _mc=mesh_cfg: _step_compute_seconds(
            model_cfg, _mc, c, mb_rows, seq, mfu, chip_flops)
        sched_winner, _ = select_schedule(cands, base, dims, hbm_gb,
                                          host_bw_gibps, compute_fn,
                                          hide_max=hide_max, vocab=vocab,
                                          mem_scale=mem_scale)
        row = {"pp": pp, "tp": tp, "dp": dp, "sp": sp,
               "layout": f"pp{pp}xtp{tp}xdp{dp}xsp{sp}",
               "microbatches": micro,
               "layer_counts": (list(lay["layer_counts"])
                                if lay["layer_counts"] else None),
               "base_gib": round(base, 2)}
        if sched_winner is None:
            row.update({"feasible": False, "score_s": None,
                        "why_not": "no schedule fits this layout's memory "
                                   "model"})
        else:
            score = layout_step_seconds(model_cfg, lay,
                                        sched_winner["bubble_fraction"],
                                        mb_rows, seq, mfu, chip_flops,
                                        ici_bw_gibps, zero2=zero2)
            row.update({"feasible": True, "score_s": round(score, 4),
                        "_score": score,
                        "why_not": None, "sched": sched_winner,
                        "est_peak_gib": sched_winner["est_peak_gib"],
                        "bubble_fraction": sched_winner["bubble_fraction"]})
        rows.append(row)
    rows.sort(key=lambda r: (not r["feasible"],
                             r.get("_score", float("inf")), r["layout"]))
    winner = rows[0] if rows and rows[0]["feasible"] else None
    return winner, rows


def layout_overrides(row: dict, schedule_file: str | None = None) -> list:
    """One frontier row as the override LIST a supervisor ladder rung (or
    an operator's launch line) appends to the training command — the mesh
    axes, the preserved-global-batch microbatch count, the explicit stage
    partition when uneven, and the chosen schedule's own overrides. Every
    string here must round-trip train.py's config validation
    (tests/test_layout_select.py pins the grid)."""
    parts = [f"mesh.pp={row['pp']}", f"mesh.tp={row['tp']}",
             f"mesh.dp={row['dp']}", f"mesh.sp={row['sp']}",
             f"gradient_accumulation_steps={row['microbatches']}"]
    if row.get("layer_counts"):
        parts.append("layer_counts=[" +
                     ",".join(str(c) for c in row["layer_counts"]) + "]")
    parts += select_overrides(row["sched"], schedule_file=schedule_file).split()
    return parts


def build_ladder(model_cfg, devices: int, mb_rows: int, seq: int,
                 global_batch_examples: int, base_gib_aw: float,
                 aw_layout: tuple, hbm_gb: float, top_k: int = 3,
                 schedule_file_for=None, **frontier_kw) -> tuple:
    """The generated supervisor ladder: the top-k frontier survivors at
    `devices` chips, then the single best survivor at each HALVED device
    count (the elastic-resize rungs: lose half the pod, walk down a rung,
    keep the global batch) — best-first, tools/supervisor.py's
    --layout-ladder format verbatim ({name, devices, overrides}).
    `schedule_file_for(rung_name, pcfg) -> path` serializes a solver
    winner's unit sequence and returns the path its rung references (None
    = restrict rungs to canonical schedules). Returns (rungs, frontiers)
    where frontiers maps device count -> the scored rows."""
    rungs, frontiers = [], {}
    n = devices
    while n >= 1:
        kw = dict(frontier_kw)
        if schedule_file_for is None:
            kw["solver_lane"] = False  # a solver rung needs its sequence file
        _, rows = layout_frontier(model_cfg, n, mb_rows, seq,
                                  global_batch_examples, base_gib_aw,
                                  aw_layout, hbm_gb, **kw)
        frontiers[n] = rows
        feasible = [r for r in rows if r["feasible"]]
        for r in feasible[:top_k if n == devices else 1]:
            name = f"{r['layout']}-{r['sched']['schedule']}"
            if any(rg["name"] == name for rg in rungs):
                name += f"-c{r['sched']['accum_chunks']}"
            sfile = None
            if r["sched"]["schedule"] == "solver":
                sfile = schedule_file_for(name, r["sched"]["_pcfg"])
            rungs.append({"name": name, "devices": n,
                          "overrides": layout_overrides(
                              r, schedule_file=sfile)})
        if n == 1:
            break
        n //= 2
    return rungs, frontiers


def select_overrides(row: dict, schedule_file: str | None = None) -> str:
    """The winning candidate as `key=value` config overrides — what the
    operator (or the supervisor's layout ladder) pastes onto the launch
    line to run the chosen schedule. A solver winner additionally needs
    its emitted sequence file (`--emit-schedule` writes it; without one
    the override line carries a placeholder to fill in)."""
    parts = [f"pipeline_schedule={row['schedule']}",
             f"virtual_stages={row['virtual_stages']}",
             f"gradient_accumulation_chunks={row['accum_chunks']}"]
    if row["schedule"] == "solver":
        parts.append(
            f"schedule_file={schedule_file or '<path from --emit-schedule>'}")
    if row["offload_wgrad"]:
        parts.append("offload.wgrad_stash=true")
    if row["offload_activations"]:
        parts.append("offload.activations=true")
    if row.get("loss_chunks", 1) > 1:
        parts.append(f"loss_vocab_chunks={row['loss_chunks']}")
    if row.get("kernel_ce"):
        parts.append("kernels.ce=pallas")
    return " ".join(parts)


def _as_written_pcfg(cfg: dict):
    """The as-written config's PipelineConfig, rebuilt with the trainer's
    own builders (preflight() constructs the same thing internally but
    does not return it) — shared by the --emit-schedule and FAIL-remedies
    paths in main()."""
    from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig
    from llama_pipeline_parallel_tpu.train import (
        build_manifest,
        build_model_config,
        build_pipeline_config,
    )

    mesh_cfg = MeshConfig(**cfg.get("mesh", {}))
    model_cfg = build_model_config(cfg["model"])
    return build_pipeline_config(
        cfg, mesh_cfg, build_manifest(cfg, model_cfg, mesh_cfg.pp))


def stash_remedies(pcfg) -> str:
    """Remedies for a blown W-stash, DERIVED from emitted sequences instead
    of a hard-coded list of schedule names: the queue depth comes from the
    sequence's slot accounting, and each fallback is named with its bubble
    counted from ITS canonical sequence's idle ticks at this exact shape —
    so the error text can never drift from what the interpreter runs."""
    import dataclasses as _dc

    from llama_pipeline_parallel_tpu.parallel import pipeline as pl

    depth = pl.wgrad_queue_peak(pcfg)
    own_b = pl.bubble_fraction(pcfg)
    parts = [f"raise gradient_accumulation_chunks (the per-flush W-queue "
             f"holds {depth} residual units; each doubling halves it)",
             "tier residuals to host DRAM (offload.wgrad_stash, or a "
             "solver sequence's per-unit offload vector via --select)"]
    alts = []
    for name, v in (("interleaved_1f1b", pcfg.virtual_stages), ("1f1b", 1)):
        try:
            alt = _dc.replace(pcfg, schedule=name, virtual_stages=v,
                              offload_wgrad=False, unit_schedule=None)
            alts.append((pl.bubble_fraction(alt), name))
        except ValueError:
            continue
    if alts:
        b, name = min(alts)
        parts.append(
            f"fall back to pipeline_schedule: {name} (no W stash; bubble "
            f"{100 * b:.2f}% vs {100 * own_b:.2f}% here — both counted "
            f"from the schedules' emitted sequences)")
    return "; ".join(parts)


def _compile_abstract(cfg: dict, mesh, mesh_cfg, model_cfg, manifest, pcfg):
    """Lower + compile the trainer's own program ABSTRACTLY (eval_shape
    state, ShapeDtypeStruct batch — no arrays materialize) and return
    ``(compiled, seq)``. Shared by preflight() and memory_audit(): both
    must compile the SAME program the real run executes, at whatever
    accum shape their caller baked into ``cfg``/``pcfg``."""
    import jax
    from jax.sharding import NamedSharding

    from llama_pipeline_parallel_tpu.models.llama import model as llama
    from llama_pipeline_parallel_tpu.optim import OptimizerConfig, make_optimizer
    from llama_pipeline_parallel_tpu.parallel import pipeline as pl
    from llama_pipeline_parallel_tpu.parallel import train_step as ts
    from llama_pipeline_parallel_tpu.train import select_attention

    # the trainer probes the collator for the real row length; the synthetic
    # dataset's seq_length is that probe's answer for these configs
    data_cfg = cfg.get("dataset") or {}
    if not data_cfg or data_cfg.get("synthetic"):
        seq = data_cfg.get("seq_length", cfg.get("max_seq_length", 512))
    else:
        seq = cfg.get("max_seq_length", 512)
    # `auto` would try to TIME kernels — preflight must stay measurement-free.
    # Resolve it to EXACT, the conservative choice: at runtime auto may pick
    # either backend, and exact's O(L^2) score tensors are the memory
    # worst case (a flash-compiled estimate would under-count runs where
    # auto picks exact). Configs that pin `attention: flash` compile flash.
    impl = cfg.get("attention", "auto")
    attn_fn = select_attention("exact" if impl == "auto" else impl, seq, mesh,
                               sequence_parallel=pcfg.sequence_parallel,
                               packed=pcfg.packed)

    ocfg = OptimizerConfig(learning_rate=cfg.get("learning_rate", 1e-6),
                           total_steps=10, warmup_steps=1)
    tx, sched = make_optimizer(ocfg)

    # abstract, sharding-annotated state: eval_shape never runs the init
    def build(rng):
        return pl.stack_stages(llama.init_params(rng, model_cfg), manifest)

    stacked_abs = jax.eval_shape(build, jax.random.PRNGKey(0))
    shardings = ts.state_shardings(mesh, tx, stacked_abs)

    def annotate(tree_abs, tree_shard):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree_abs, tree_shard)

    opt_abs = jax.eval_shape(tx.init, stacked_abs)
    state_abs = ts.TrainState(
        step=jax.ShapeDtypeStruct((), jax.numpy.int32, sharding=shardings.step),
        params=annotate(stacked_abs, shardings.params),
        opt_state=annotate(opt_abs, shardings.opt_state))

    import jax.numpy as jnp

    # NOT multiplied by packing_factor: the loader feeds micro*accum*pack
    # EXAMPLES per replica, but the packed collator emits examples //
    # pack_factor ROWS (data/collator.py) — the device program sees
    # micro*accum rows either way
    global_batch = (cfg.get("per_device_train_batch_size", 1)
                    * pcfg.num_microbatches * mesh_cfg.dp)
    b_specs = pl.batch_specs(mesh)
    batch_abs = {
        k: jax.ShapeDtypeStruct((global_batch, seq), jnp.int32,
                                sharding=NamedSharding(mesh, b_specs[k]))
        for k in ("input_ids", "attention_mask", "position_ids", "labels")
    }

    if cfg.get("optimizer_offload"):
        # The offload path's DEVICE program is loss+grad only: bf16 working
        # params in, fp32 grads out; masters + Adam moments live in host
        # DRAM (optim/offload.py) exactly like the reference's 65B
        # ZeRO-offload run (reference conf yaml:160-162, README.md:70-71).
        # Under optimizer_offload_zero2 the grads leave the device
        # dp-sharded (reduce-scatter), matching the trainer's program.
        param_specs = pl.stage_param_specs(stacked_abs,
                                           tp=mesh.shape["tp"] > 1)
        bf16_abs = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, model_cfg.dtype, sharding=NamedSharding(mesh, s)),
            stacked_abs, param_specs)
        out_shardings = None
        if cfg.get("optimizer_offload_zero2") and mesh_cfg.dp > 1:
            out_shardings = (None, ts.specs_to_shardings(
                mesh, ts.zero2_param_specs(stacked_abs, mesh)))
        grad_fn = jax.jit(pl.make_pipeline_loss_and_grad(
            mesh, model_cfg, pcfg, stacked_abs, attn_fn=attn_fn),
            out_shardings=out_shardings)
        compiled = grad_fn.lower(bf16_abs, batch_abs).compile()
    else:
        step = ts.make_train_step(mesh, model_cfg, pcfg, tx, sched, stacked_abs,
                                  attn_fn=attn_fn)
        compiled = step.lower(state_abs, batch_abs).compile()
    return compiled, seq


def preflight(cfg: dict, hbm_gb: float, host_bw_gibps: float = 30.0,
              mfu: float = 0.45, hide_max: float = 1.0,
              chip_flops: float | None = None) -> dict:
    """Lower + compile the training step ABSTRACTLY (no arrays materialize:
    65B fp32 masters never exist) and return the per-device byte breakdown."""
    import jax
    import numpy as np

    from llama_pipeline_parallel_tpu.models.llama import model as llama
    from llama_pipeline_parallel_tpu.parallel import pipeline as pl
    from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig, make_mesh
    from llama_pipeline_parallel_tpu.train import (
        build_manifest,
        build_model_config,
        build_pipeline_config,
    )

    if cfg.get("optimizer_offload_zero2") and not cfg.get("optimizer_offload"):
        # mirror the trainer's rejection (train.py) — preflight passing a
        # config the real run refuses defeats its purpose
        raise ValueError("optimizer_offload_zero2 requires optimizer_offload: "
                         "true")
    mesh_cfg = MeshConfig(**cfg.get("mesh", {}))
    mesh = make_mesh(mesh_cfg)
    model_cfg = build_model_config(cfg["model"])
    # the trainer's own builders: the preflight must compile the SAME program
    manifest = build_manifest(cfg, model_cfg, mesh_cfg.pp)
    pcfg = build_pipeline_config(cfg, mesh_cfg, manifest)

    # Anchored-compile mode for host-offload configs on backends that
    # cannot express host memory (utils/host_stash.py gating — XLA-CPU,
    # i.e. every CLI preflight): the gated-off compile holds the tiered
    # stash DEVICE-resident, and XLA-CPU additionally over-counts stash
    # buffers past 2^31 elements (~2.4x at the 65B micro-8 shape, where
    # the same program at micro 2 — exactly 2^31 — and the whole 7B grid
    # match the closed-form model to the 0.1 GiB). So the device peak is
    # estimated from a compile of the SAME program at the smallest valid
    # M (queue shrunk under the cliff), with the schedule's ring/stash
    # terms swapped to the real shape analytically — every other term is
    # M-independent (ring slots cap at 2vS-1; scan trip counts are free).
    pcfg_real, anchor_m = pcfg, None
    if ((pcfg.offload_wgrad or pcfg.offload_activations)
            and not _host_transfers_enabled()):
        m_min = pcfg.num_stages * pcfg.accum_chunks
        if m_min < pcfg.num_microbatches:
            anchor_m = m_min
            cfg = {**cfg, "gradient_accumulation_steps": m_min}
            pcfg = build_pipeline_config(cfg, mesh_cfg, manifest)

    compiled, seq = _compile_abstract(cfg, mesh, mesh_cfg, model_cfg,
                                      manifest, pcfg)
    ma = compiled.memory_analysis()
    if ma is None:
        raise RuntimeError("backend exposes no compile-time memory analysis")

    gib = 1 << 30
    arg = getattr(ma, "argument_size_in_bytes", 0)
    out = getattr(ma, "output_size_in_bytes", 0)
    temp = getattr(ma, "temp_size_in_bytes", 0)
    alias = getattr(ma, "alias_size_in_bytes", 0)
    # donated state aliases into the outputs: alias bytes are counted once
    peak = arg + out + temp - alias
    mb_rows = int(cfg.get("per_device_train_batch_size", 1))
    dims = pl.stash_dims(mb_rows, seq, mesh_cfg.sp, model_cfg.hidden_size,
                         model_cfg.dtype)
    # Device-peak estimate for offload configs: a GATED-OFF compile holds
    # the tiered stash in regular memory (one flat address space on that
    # backend), so the modeled host bytes are subtracted — via the anchored
    # mode above when it applies, directly otherwise. When transfers are
    # REAL (pinned_host exists), the compile already placed the stash in
    # the host space and the raw peak is taken as-is — subtracting there
    # would double-count the relief and understate device HBM by the whole
    # stash (whether memory_analysis excludes host-space buffers is a
    # calibration question; taking the raw number can only overstate).
    host_bytes = pl.host_stash_bytes(pcfg_real, *dims)
    if anchor_m:
        terms_real = candidate_device_terms_gib(pcfg_real, dims)
        peak_device_gib = (peak / gib - counted_device_terms_gib(pcfg, dims)
                           + terms_real["ring_gib"] + terms_real["stash_gib"])
    elif host_bytes and not _host_transfers_enabled():
        peak_device_gib = (peak - host_bytes) / gib
    else:
        peak_device_gib = peak / gib
    report = {
        "compiled_path": "offload_loss_and_grad" if cfg.get("optimizer_offload")
                         else "fused_train_step",
        "devices": int(np.prod(list(mesh.shape.values()))),
        "global_batch_rows": mb_rows * pcfg_real.num_microbatches
                             * mesh_cfg.dp,
        "seq": seq,
        "schedule": pcfg_real.schedule,
        "arguments_gib": round(arg / gib, 2),
        "outputs_gib": round(out / gib, 2),
        "temp_gib": round(temp / gib, 2),
        "aliased_gib": round(alias / gib, 2),
        "per_device_peak_gib": round(peak_device_gib, 2),
        "hbm_budget_gib": hbm_gb,
        "fits": peak_device_gib <= hbm_gb,
    }
    # The loss head's live term (pl.loss_head_bytes): the [tokens, V/chunks]
    # fp32 logits block + chunked-backward dh accumulator of the XLA path,
    # ~0 under kernels.ce=pallas (docs/KERNELS.md) — named so the operator
    # can see what the ce axis of --select is trading. Under tp the head is
    # vocab-PARALLEL (each shard's logits block is [tokens, V/tp]; the
    # loss_chunks/kernels.ce knobs are rejected there), so the shard width
    # is the vocab the term sees.
    report["loss_head_gib"] = round(
        pl.loss_head_bytes(pcfg_real, *dims[:3],
                           model_cfg.vocab_size // max(mesh_cfg.tp, 1))
        / gib, 2)
    kernels_on = [n for n, on in (("ce", pcfg_real.kernel_ce),
                                  ("prologue", pcfg_real.kernel_prologue))
                  if on]
    if kernels_on:
        report["kernels"] = "+".join(kernels_on)
    if anchor_m:
        report["anchor_microbatches"] = anchor_m
        report["anchor_peak_gib"] = round(peak / gib, 2)
        report["anchor_note"] = (
            f"device peak estimated from an M={anchor_m} compile of the "
            f"same program (this backend cannot express host memory, so a "
            f"full-M compile would hold the tiered stash device-resident, "
            f"and XLA-CPU over-counts stash buffers past 2^31 elements); "
            f"ring/stash terms re-added analytically at "
            f"M={pcfg_real.num_microbatches}")
    hbm_slots, host_slots = pl.wgrad_partition(pcfg_real)
    if host_bytes:
        if not anchor_m and not _host_transfers_enabled():
            report["xla_raw_peak_gib"] = round(peak / gib, 2)
        report["host_stash_gib"] = round(host_bytes / gib, 2)
        wgrad_tier = "wgrad_stash"
        if (pcfg_real.schedule == "solver" and host_slots
                and hbm_slots):  # selective vector: name the split
            wgrad_tier = (f"wgrad_stash"
                          f"[{pl.wgrad_offloaded_units(pcfg_real)}"
                          f"/{pcfg_real.unit_schedule.n_units}]")
        report["offload"] = "+".join(
            n for n, on in ((wgrad_tier, host_slots > 0),
                            ("activations", pcfg_real.offload_activations))
            if on)
    if pl.wgrad_queue_peak(pcfg_real):
        # The split backward stashes a (chunk input, ring cotangent)
        # residual per queued W unit (docs/SCHEDULES.md "W-stash memory
        # bound"). The explicit term names the schedule's memory tax and
        # sizes the remedies when it blows the headroom (see the FAIL
        # message in main()): accum_chunks divides the per-flush queue;
        # offload.wgrad_stash (or a solver sequence's per-unit vector)
        # tiers it to host DRAM. Only the HBM-RESIDENT portion counts
        # against headroom — a solver vector's host slots already left.
        stash = pl.wgrad_stash_bytes(pcfg_real, *dims)
        slot_b = dims[0] * dims[1] * dims[2] * dims[3]
        stash_hbm = 2 * hbm_slots * slot_b
        report["wgrad_queue_depth"] = pl.wgrad_queue_peak(pcfg_real)
        report["wgrad_stash_gib"] = round(stash / gib, 2)
        if host_slots and not hbm_slots:
            report["wgrad_stash_verdict"] = (
                "tiered to host DRAM (offload.wgrad_stash or an all-host "
                "sequence vector) — HBM holds only the in-flight transfer "
                "slots")
        else:
            headroom = hbm_gb - (peak_device_gib - stash_hbm / gib)
            if stash_hbm / gib > max(headroom, 0.0):
                report["wgrad_stash_verdict"] = (
                    f"HBM-resident W-stash {round(stash_hbm / gib, 2)} GiB "
                    f"exceeds the {round(max(headroom, 0.0), 2)} GiB "
                    f"headroom left by the rest of the step — "
                    f"{stash_remedies(pcfg_real)}")
            else:
                report["wgrad_stash_verdict"] = "fits within headroom"
    if pcfg_real.offload_activations or host_slots:
        # Host-bandwidth feasibility (the PipeOffload bound): the stash
        # traffic must stream behind the step's compute, or the offload
        # trades an OOM for a stall — rejected HERE, analytically, not
        # discovered on device.
        feas = offload_feasibility(
            pcfg_real, dims,
            _step_compute_seconds(model_cfg, mesh_cfg, pcfg_real, mb_rows,
                                  seq, mfu, chip_flops),
            host_bw_gibps)
        report.update(feas)
        if feas["offload_hide_ratio"] > hide_max:
            report["fits"] = False
            report["offload_bw_verdict"] = (
                f"offload traffic cannot hide behind compute: modeled "
                f"transfer time is {feas['offload_hide_ratio']:.2f}x the "
                f"step's compute at {host_bw_gibps} GiB/s host bandwidth "
                f"(--host-bw-gibps) and {mfu} MFU — raise "
                f"gradient_accumulation_chunks, shrink the stash, or drop "
                f"the offload")
        else:
            report["offload_bw_verdict"] = "hides behind compute"
    if cfg.get("optimizer_offload"):
        # host side: fp32 masters + two fp32 Adam moments, sharded per
        # process (optim/offload.py keeps only each host's device shards)
        stacked_abs = jax.eval_shape(
            lambda rng: pl.stack_stages(llama.init_params(rng, model_cfg),
                                        manifest),
            jax.random.PRNGKey(0))
        n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(stacked_abs))
        report["host_dram_total_gib"] = round(n_params * 12 / gib, 1)
    return report


def memory_audit(cfg: dict, top_buffers: int = 8) -> dict:
    """Per-buffer evidence behind the anchored-estimate mode: compile the
    SAME program at a ladder of microbatch counts and, per rung, put the
    byte model's candidate terms (candidate_device_terms_gib) next to
    `memory_analysis()`'s raw numbers plus top-buffer attribution
    (utils/memwatch.py). The residual (raw peak minus the model's ring +
    stash terms) is M-independent when XLA counts honestly — a residual
    that JUMPS between rungs localizes the over-count to the buffers the
    attribution lists, which is exactly the 2^31-element XLA-CPU cliff
    the anchored mode in preflight() works around
    (docs/PREFLIGHT.md "Memory audit")."""
    from llama_pipeline_parallel_tpu.parallel import pipeline as pl
    from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig, make_mesh
    from llama_pipeline_parallel_tpu.train import (
        build_manifest,
        build_model_config,
        build_pipeline_config,
    )
    from llama_pipeline_parallel_tpu.utils import memwatch

    gib = 1 << 30
    mesh_cfg = MeshConfig(**cfg.get("mesh", {}))
    mesh = make_mesh(mesh_cfg)
    model_cfg = build_model_config(cfg["model"])
    manifest = build_manifest(cfg, model_cfg, mesh_cfg.pp)
    pcfg_real = build_pipeline_config(cfg, mesh_cfg, manifest)

    # M-ladder: the smallest valid microbatch count (the anchored mode's
    # compile shape), the as-written M, and a midpoint rung when the two
    # are far apart — three points separate "residual is flat" from
    # "residual jumps at one rung".
    m_min = pcfg_real.num_stages * pcfg_real.accum_chunks
    m_real = pcfg_real.num_microbatches
    ladder = {m for m in (m_min, m_real) if m >= m_min}
    if m_real >= 4 * m_min:
        ladder.add(2 * m_min)
    mb_rows = int(cfg.get("per_device_train_batch_size", 1))

    rungs = []
    for m in sorted(ladder):
        cfg_m = {**cfg, "gradient_accumulation_steps": m}
        try:
            pcfg_m = build_pipeline_config(cfg_m, mesh_cfg, manifest)
            compiled, seq = _compile_abstract(cfg_m, mesh, mesh_cfg,
                                              model_cfg, manifest, pcfg_m)
        except Exception as e:  # invalid rung (schedule constraint) — skip
            rungs.append({"microbatches": m, "error": f"{type(e).__name__}: {e}"})
            continue
        info = memwatch.compiled_memory(compiled, top_buffers=top_buffers,
                                        label=f"M={m}")
        if info is None:
            rungs.append({"microbatches": m,
                          "error": "backend exposes no memory analysis"})
            continue
        dims = pl.stash_dims(mb_rows, seq, mesh_cfg.sp, model_cfg.hidden_size,
                             model_cfg.dtype)
        terms = candidate_device_terms_gib(pcfg_m, dims)
        peak_gib = info["peak_bytes"] / gib
        # flag buffers past the XLA-CPU over-count cliff: 2^31 ELEMENTS
        bufs = []
        for b in info["top_buffers"]:
            elements = 1
            for d in b.get("shape") or ():
                elements *= d
            bufs.append({**b, "gib": round(b["bytes"] / gib, 2),
                         "over_2^31_elements": elements >= (1 << 31)})
        rungs.append({
            "microbatches": m,
            "anchor_rung": m == m_min and m != m_real,
            "as_written": m == m_real,
            "raw_peak_gib": round(peak_gib, 2),
            "arguments_gib": round(info["argument_bytes"] / gib, 2),
            "outputs_gib": round(info["output_bytes"] / gib, 2),
            "temp_gib": round(info["temp_bytes"] / gib, 2),
            "ring_gib": round(terms["ring_gib"], 2),
            "stash_gib": round(terms["stash_gib"], 2),
            "loss_head_gib": round(terms["loss_head_gib"], 2),
            "residual_gib": round(peak_gib - terms["ring_gib"]
                                  - terms["stash_gib"], 2),
            "top_buffers": bufs,
        })
    return {"schedule": pcfg_real.schedule, "anchor_microbatches": m_min,
            "as_written_microbatches": m_real,
            "devices": _prod(mesh.shape.values()),
            "rungs": rungs}


def _prod(vals) -> int:
    out = 1
    for v in vals:
        out *= int(v)
    return out


def print_memory_audit(audit: dict) -> None:
    """The --memory-audit table: one row per ladder rung, residual last —
    a flat residual column validates the byte model's M-scaling; a jump
    names the over-counted rung, and the per-rung buffer attribution
    below names the tensor (docs/PREFLIGHT.md commits these tables for
    the 7B and 65B conf shapes)."""
    print(f"memory audit: schedule {audit['schedule']}, "
          f"anchor M={audit['anchor_microbatches']}, "
          f"as-written M={audit['as_written_microbatches']}")
    hdr = (f"{'M':>6s} {'raw_peak':>9s} {'temp':>8s} {'ring':>7s} "
           f"{'stash':>7s} {'head':>7s} {'residual':>9s}  note")
    print(hdr)
    for r in audit["rungs"]:
        if "error" in r:
            print(f"{r['microbatches']:>6d} {'-':>9s} {'-':>8s} {'-':>7s} "
                  f"{'-':>7s} {'-':>7s} {'-':>9s}  {r['error']}")
            continue
        note = ("anchor" if r.get("anchor_rung")
                else "as-written" if r.get("as_written") else "")
        print(f"{r['microbatches']:>6d} {r['raw_peak_gib']:>9.2f} "
              f"{r['temp_gib']:>8.2f} {r['ring_gib']:>7.2f} "
              f"{r['stash_gib']:>7.2f} {r['loss_head_gib']:>7.2f} "
              f"{r['residual_gib']:>9.2f}  {note}")
    for r in audit["rungs"]:
        if "error" in r or not r.get("top_buffers"):
            continue
        print(f"\ntop buffers at M={r['microbatches']}:")
        for b in r["top_buffers"]:
            flag = "  <-- over 2^31 elements" if b["over_2^31_elements"] else ""
            shape = ",".join(str(d) for d in (b.get("shape") or ()))
            print(f"  {b['gib']:>8.2f} GiB  {b['dtype']}[{shape}]  "
                  f"%{b['name']}{flag}")


def calibrate() -> dict:
    """Compile the BENCH config's single-chip train step on BOTH backends —
    the TPU and XLA-CPU, in this one process — and report both
    `memory_analysis()` peaks side by side. This puts an error bar on every
    XLA-CPU preflight verdict (the tool's own caveat: TPU layouts/padding and
    Mosaic VMEM differ). Record the margin in docs/PREFLIGHT.md. AOT only —
    no arrays materialize and no step runs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from __graft_entry__ import _bench_config  # repo root on sys.path (module top)

    from llama_pipeline_parallel_tpu.models.llama import model as llama
    from llama_pipeline_parallel_tpu.models.llama.manifest import StageManifest
    from llama_pipeline_parallel_tpu.optim import OptimizerConfig, make_optimizer
    from llama_pipeline_parallel_tpu.parallel import pipeline as pl
    from llama_pipeline_parallel_tpu.parallel import train_step as ts
    from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig, make_mesh

    cfg = _bench_config()
    manifest = StageManifest.for_config(cfg, 1)
    tx, sched = make_optimizer(OptimizerConfig(learning_rate=1e-4,
                                               total_steps=1000, warmup_steps=10))
    gib = 1 << 30
    out: dict = {"model": "bench-550m", "batch": 8, "seq": 512}
    # The cpu half alone costs ~25 min of XLA-CPU compile on a 1-core host;
    # CALIBRATE_BACKENDS=tpu skips it (the cpu number is obtainable offline).
    backends = tuple(b.strip().lower()
                     for b in os.environ.get("CALIBRATE_BACKENDS",
                                             "cpu,tpu").split(",")
                     if b.strip())
    if "tpu" not in backends:
        # cpu-only run: never take the chip from a process that needs it
        jax.config.update("jax_platforms", "cpu")
    for backend in backends:
        try:
            devices = jax.devices(backend)
        except RuntimeError as e:
            out[backend] = f"backend unavailable: {e}"
            continue
        mesh = make_mesh(MeshConfig(), devices=devices[:1])
        stacked_abs = jax.eval_shape(
            lambda rng: pl.stack_stages(llama.init_params(rng, cfg), manifest),
            jax.random.PRNGKey(0))
        shardings = ts.state_shardings(mesh, tx, stacked_abs)
        opt_abs = jax.eval_shape(tx.init, stacked_abs)

        def annotate(tree_abs, tree_shard):
            return jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
                tree_abs, tree_shard)

        state_abs = ts.TrainState(
            step=jax.ShapeDtypeStruct((), jnp.int32, sharding=shardings.step),
            params=annotate(stacked_abs, shardings.params),
            opt_state=annotate(opt_abs, shardings.opt_state))
        b_spec = NamedSharding(mesh, pl.batch_specs(mesh)["input_ids"])
        batch_abs = {k: jax.ShapeDtypeStruct((8, 512), jnp.int32, sharding=b_spec)
                     for k in ("input_ids", "attention_mask", "position_ids",
                               "labels")}
        pcfg = pl.PipelineConfig(num_stages=1, num_microbatches=1, remat=False)
        step = ts.make_train_step(mesh, cfg, pcfg, tx, sched, stacked_abs)
        ma = step.lower(state_abs, batch_abs).compile().memory_analysis()
        if ma is None:
            out[backend] = "no memory analysis exposed"
            continue
        arg = getattr(ma, "argument_size_in_bytes", 0)
        o = getattr(ma, "output_size_in_bytes", 0)
        temp = getattr(ma, "temp_size_in_bytes", 0)
        alias = getattr(ma, "alias_size_in_bytes", 0)
        out[backend] = {"arguments_gib": round(arg / gib, 3),
                        "outputs_gib": round(o / gib, 3),
                        "temp_gib": round(temp / gib, 3),
                        "peak_gib": round((arg + o + temp - alias) / gib, 3)}
        print(f"calibrate[{backend}]: {out[backend]}", flush=True)
    if isinstance(out.get("tpu"), dict) and isinstance(out.get("cpu"), dict):
        cpu_peak, tpu_peak = out["cpu"]["peak_gib"], out["tpu"]["peak_gib"]
        out["tpu_over_cpu"] = round(tpu_peak / cpu_peak, 3) if cpu_peak else None
    return out


def resume_compat(cfg: dict) -> dict | None:
    """Elastic-resume preflight (docs/RESILIENCE.md "Elastic resume"): when
    the config's output_dir already holds a checkpoint this run would
    resume, compare its recorded source topology and data contract against
    the config — BEFORE burning a compile on a resume that will warn about
    (or silently accept) a changed global batch. Returns None when there is
    nothing to resume; never fails the preflight (topology changes are
    legal — that is the point of elastic restore)."""
    import json as _json

    out_dir = cfg.get("output_dir")
    if not out_dir or not os.path.isdir(out_dir) or not cfg.get("resume", True):
        return None
    # read meta.json directly (no CheckpointManager: the preflight must not
    # create dirs or spin up Orbax just to peek at a marker file)
    latest = None
    try:
        import re as _re

        for d in os.listdir(out_dir):
            m = _re.match(r"^checkpoint-(\d+)$", d)
            if m and os.path.isfile(os.path.join(out_dir, d, "meta.json")):
                latest = max(latest or 0, int(m.group(1)))
        if latest is None:
            return None
        with open(os.path.join(out_dir, f"checkpoint-{latest}",
                               "meta.json")) as f:
            meta = _json.load(f)
    except (OSError, ValueError):
        return None  # torn/corrupt meta: the trainer's quarantine handles it
    mesh = dict(cfg.get("mesh") or {})
    current = {"pp": int(mesh.get("pp", 1)), "dp": int(mesh.get("dp", 1)),
               "tp": int(mesh.get("tp", 1)), "sp": int(mesh.get("sp", 1)),
               "schedule": cfg.get("pipeline_schedule", "1f1b"),
               "virtual_stages": int(cfg.get("virtual_stages", 1) or 1)}
    report: dict = {"resume_step": latest}
    source = meta.get("topology")
    if source and "layer_counts" in source:
        # mirror the trainer's partition-aware restore labeling: a ladder
        # rung that changes layer_counts is a topology change here too
        try:
            from llama_pipeline_parallel_tpu.train import (
                build_manifest,
                build_model_config,
            )

            man = build_manifest(cfg, build_model_config(cfg["model"]),
                                 current["pp"])
            current["layer_counts"] = (
                f"even/{man.stage_layer_counts[0]}" if man.is_even
                else list(man.stage_layer_counts))
        except Exception:
            pass  # unresolvable model node: skip the partition comparison
    if source:
        changed = sorted(k for k in current if source.get(k) != current[k])
        report["source_topology"] = source.get("layout", source)
        report["topology_changed"] = changed or "no"
        if source.get("schedule") != current["schedule"]:
            # a schedule change is as restore-relevant as a topology one:
            # the stacked layout changes (flat [S,k] vs chunked [S,v,k])
            # even though the canonical checkpoint restores into either
            report["schedule_changed"] = (
                f"{source.get('schedule', '1f1b')} -> {current['schedule']} "
                f"(layout re-stacks from the canonical checkpoint; "
                f"docs/SCHEDULES.md)")
    data_state = meta.get("data_state")
    if data_state:
        packing = int(cfg.get("packing_factor", 1) or 1)
        g_now = (current["dp"] * int(cfg.get("per_device_train_batch_size", 1))
                 * int(cfg.get("gradient_accumulation_steps", 1)) * packing)
        g_ckpt = data_state.get("global_batch_examples")
        report["global_batch_examples"] = {"checkpoint": g_ckpt,
                                           "config": g_now}
        report["data_contract"] = (
            "exact (O(1) reposition, zero dropped/duplicated samples)"
            if g_ckpt == g_now else
            "REMAPPED — global batch changed; re-trains at most one partial "
            "batch and shifts the lr-schedule/epoch mapping "
            "(docs/RESILIENCE.md)")
    return report


def _run_all(patterns: list[str], hbm_gb: float, overrides: list[str]) -> None:
    """Preflight every config matching `patterns` in its own subprocess (each
    needs a different virtual device count, fixed at jax import) and print a
    pass/fail table — one command reproduces docs/PREFLIGHT.md."""
    import glob as globmod
    import re
    import subprocess

    paths = sorted({p for pat in patterns for p in globmod.glob(pat)})
    if not paths:
        raise SystemExit(f"no configs match {patterns!r}")
    rows, any_fail = [], False
    for path in paths:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--config", path,
             "--hbm-gb", str(hbm_gb), *overrides],
            capture_output=True, text=True)
        peak = "?"
        m = re.search(r"per_device_peak_gib: ([0-9.]+)", proc.stdout)
        if m:
            peak = m.group(1)
        ok = proc.returncode == 0
        any_fail |= not ok
        rows.append((path, peak, "OK" if ok else "FAIL"))
        print(f"{'OK  ' if ok else 'FAIL'} {path}: peak {peak} GiB",
              flush=True)
        if not ok and not m:  # compile error, not a budget miss: show why
            print((proc.stdout + proc.stderr).strip()[-800:], flush=True)
    print(f"\n{'config':<40} {'peak GiB':>9}  verdict")
    for path, peak, verdict in rows:
        print(f"{path:<40} {peak:>9}  {verdict}")
    if any_fail:
        sys.exit(1)


CALIBRATION_KEYS = {"mfu": "mfu", "host_bw_gibps": "host_bw_gibps",
                    "ici_bw_gibps": "ici_bw_gibps",
                    "mem_scale": "mem_scale"}


def load_calibration(path: str) -> dict:
    """Read a perf_report --emit-calibration constants file. Raises
    SystemExit with a readable message on unreadable/garbage input — a
    user pointing --calibration at the wrong file must get a verdict, not
    a traceback; a file with no usable keys returns {} (the CLI defaults
    then stand)."""
    import json

    try:
        with open(path) as f:
            calib = json.load(f)
    except (OSError, ValueError) as e:
        raise SystemExit(f"--calibration {path} is not readable JSON: {e}")
    if not isinstance(calib, dict):
        raise SystemExit(f"--calibration {path} is not a JSON object "
                         f"(got {type(calib).__name__})")
    out = {}
    for key in CALIBRATION_KEYS:
        try:
            v = float(calib[key])
        except (KeyError, TypeError, ValueError):
            continue
        if v > 0:
            out[key] = v
    return out


def apply_calibration(args, path: str) -> dict:
    """Override the CLI model constants with the file's measured values
    (only the keys it carries). Returns what was applied — the
    measured-re-selection loop: bench/train measure, perf_report distills,
    --select re-ranks from the measurements."""
    applied = load_calibration(path)
    for key, attr in CALIBRATION_KEYS.items():
        if key in applied:
            setattr(args, attr, applied[key])
    return applied


def main(argv: list[str] | None = None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", default=None,
                   help="one config yaml (or use --all for a sweep)")
    p.add_argument("--hbm-gb", type=float, default=95.0,
                   help="per-chip HBM budget in GiB (TPU v5p: 95)")
    p.add_argument("--all", dest="all_globs", nargs="*", default=None,
                   metavar="GLOB",
                   help="preflight every config matching the GLOB pattern(s) "
                        "(default conf/*.yaml; unquoted shell-expanded paths "
                        "work too), one subprocess each (XLA device counts "
                        "differ per config), and print a summary table; "
                        "exit 1 if any fails")
    p.add_argument("--calibrate", action="store_true",
                   help="compile the bench config on the real TPU and/or "
                        "XLA-CPU (CALIBRATE_BACKENDS=cpu,tpu — default "
                        "both; cpu alone costs ~25 min of XLA-CPU compile) "
                        "and print each memory_analysis() peak — the error "
                        "bar for every CPU-estimate verdict (AOT only, "
                        "runs nothing)")
    p.add_argument("--select", action="store_true",
                   help="after the as-written verdict, enumerate "
                        "(schedule, virtual_stages, accum_chunks, offload) "
                        "candidates against the HBM budget + host-bandwidth "
                        "bound and print the analytically chosen config "
                        "(OptPipe-style selection; docs/SCHEDULES.md "
                        "'Host offload')")
    p.add_argument("--emit-schedule", default=None, metavar="PATH",
                   help="dump the selected unit sequence (the --select "
                        "winner's, else the as-written config's canonical "
                        "re-emission) as JSON to PATH and print the "
                        "per-stage ASCII timeline — debug a refused or "
                        "surprising schedule without a TPU; the file feeds "
                        "pipeline_schedule: solver + schedule_file")
    p.add_argument("--layout-devices", type=int, default=None, metavar="N",
                   help="with --select: grow the OUTER (pp, tp, dp, sp) "
                        "axes — enumerate every mesh of N devices (default: "
                        "the as-written world size), re-run the memory "
                        "model + schedule selection per mesh, and rank the "
                        "frontier by the analytic step-time score "
                        "(docs/PREFLIGHT.md 'Layout auto-selection')")
    p.add_argument("--emit-ladder", default=None, metavar="PATH",
                   help="with --select: write the layout frontier's top-k "
                        "survivors (plus the best rung at each halved "
                        "device count — the elastic-resize rungs) as a "
                        "tools/supervisor.py --layout-ladder JSON; solver "
                        "rungs get their unit-sequence files written next "
                        "to PATH")
    p.add_argument("--ladder-top-k", type=int, default=3,
                   help="rungs to emit at the full device count (default "
                        "3 — the set bench.py's extra:layout-* rows "
                        "measure)")
    p.add_argument("--ici-bw-gibps", type=float, default=90.0,
                   help="assumed ICI per-link bandwidth, GiB/s, for the "
                        "layout score's collective terms (v5p ~90)")
    p.add_argument("--host-bw-gibps", type=float, default=30.0,
                   help="assumed host-link bandwidth, GiB/s, for the "
                        "offload feasibility bound (measure the real one "
                        "with bench.py's extra:offload-bw row)")
    p.add_argument("--mfu", type=float, default=0.45,
                   help="assumed MFU for the modeled step-compute time the "
                        "offload traffic must hide inside (higher = "
                        "stricter: faster compute leaves less hiding room)")
    p.add_argument("--hide-ratio-max", type=float, default=1.0,
                   help="reject offload whose modeled transfer/compute "
                        "ratio exceeds this")
    p.add_argument("--mem-scale", type=float, default=1.0,
                   help="measured live-peak / byte-model-peak ratio "
                        "scaling every --select candidate's est_peak_gib "
                        "(1.0 = trust the model; the memory observatory's "
                        "mem_peak_gib rows calibrate it via --calibration)")
    p.add_argument("--memory-audit", action="store_true",
                   help="compile the SAME program at a ladder of "
                        "microbatch counts and print the per-term "
                        "byte-model vs memory_analysis() residual table "
                        "with top-buffer attribution — the per-buffer "
                        "evidence behind the anchored-estimate mode "
                        "(docs/PREFLIGHT.md 'Memory audit')")
    p.add_argument("--chip-flops", type=float, default=None,
                   help="chip peak FLOP/s for the compute model (default: "
                        "detect, else 197e12)")
    p.add_argument("--calibration", default=None, metavar="JSON",
                   help="measured constants file from tools/perf_report.py "
                        "--emit-calibration: keys present there (mfu, "
                        "host_bw_gibps, ici_bw_gibps, mem_scale) override "
                        "the CLI assumptions above, so --select re-ranks "
                        "the frontier from MEASURED bandwidth/MFU/memory "
                        "instead of guesses (docs/PREFLIGHT.md "
                        "'Calibration')")
    p.add_argument("overrides", nargs="*", help="key=value config overrides")
    args, unknown = p.parse_known_args(argv)
    bad = [u for u in unknown if not (u.startswith("--") and "=" in u)]
    if bad:
        p.error(f"unrecognized arguments: {' '.join(bad)}")
    args.overrides += unknown

    if args.calibrate:
        import json

        print(json.dumps(calibrate(), indent=2))
        return
    if args.calibration:
        applied = apply_calibration(args, args.calibration)
        if applied:
            print("calibration: " + ", ".join(
                f"{k}={v}" for k, v in applied.items())
                + f" (measured — {args.calibration})")
        else:
            print(f"calibration: {args.calibration} carries no usable keys; "
                  f"keeping the CLI assumptions")
    if (args.emit_ladder or args.layout_devices) and not args.select:
        p.error("--emit-ladder/--layout-devices extend --select (the layout "
                "lane calibrates against the compiled peak --select anchors "
                "on)")
    if args.all_globs is not None:
        if args.config:
            p.error("--config and --all are mutually exclusive")
        # nargs='*' greedily consumes trailing key=value overrides too:
        # route anything that isn't a yaml path/glob back to overrides
        globs = [g for g in args.all_globs
                 if g.endswith((".yaml", ".yml")) or "*" in g]
        stray = [g for g in args.all_globs if g not in globs]
        if any("=" not in s for s in stray):
            p.error(f"--all takes .yaml globs; got {stray}")
        _run_all(globs or ["conf/*.yaml"], args.hbm_gb,
                 stray + args.overrides)
        return
    if args.config is None:
        p.error("--config is required (or pass --all for a sweep)")

    n_devices = _mesh_product(args.config, args.overrides)
    os.environ["JAX_PLATFORMS"] = "cpu"
    import re

    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n_devices}").strip()

    import jax

    jax.config.update("jax_platforms", "cpu")  # an XLA-CPU estimate by design

    from llama_pipeline_parallel_tpu.utils.config import load_config

    cfg = load_config(args.config, args.overrides)
    print(f"preflight: {args.config} on {n_devices} virtual devices "
          f"(XLA-CPU estimate; TPU layouts/Mosaic VMEM differ — keep margin)")
    report = preflight(cfg, args.hbm_gb, host_bw_gibps=args.host_bw_gibps,
                       mfu=args.mfu, hide_max=args.hide_ratio_max,
                       chip_flops=args.chip_flops)
    for k, v in report.items():
        print(f"  {k}: {v}")
    resume = resume_compat(cfg)
    if resume:
        print("resume preflight (elastic — docs/RESILIENCE.md):")
        for k, v in resume.items():
            print(f"  {k}: {v}")
    if args.memory_audit:
        print()
        print_memory_audit(memory_audit(cfg))
    if args.select:
        _print_selection(cfg, report, args)
    elif args.emit_schedule:
        _emit_schedule(args.emit_schedule, None, None,
                       int((cfg.get("mesh") or {}).get("pp", 1)),
                       _as_written_pcfg(cfg))
    if not report["fits"]:
        print(f"preflight FAIL: per-device peak {report['per_device_peak_gib']} GiB "
              f"exceeds the {args.hbm_gb} GiB budget"
              if "offload_bw_verdict" not in report
              or report["offload_hide_ratio"] <= args.hide_ratio_max else
              f"preflight FAIL: {report['offload_bw_verdict']}")
        if "wgrad_queue_depth" in report and not report.get("offload"):
            # actionable split-backward guidance: the W-stash is the
            # schedule's own memory tax; the remedies (and the fallback's
            # bubble) are DERIVED from the emitted sequences at this exact
            # shape, not hard-coded schedule names (docs/SCHEDULES.md)
            print(f"  W-stash: {report['wgrad_stash_gib']} GiB across "
                  f"{report['wgrad_queue_depth']} queued units — "
                  f"{stash_remedies(_as_written_pcfg(cfg))}")
        sys.exit(1)
    print("preflight OK")


def _print_selection(cfg: dict, report: dict, args) -> None:
    """The --select pass: anchor on the compiled peak, enumerate the
    candidate grid, print the scored table + the chosen config (or why
    nothing fits). Pure arithmetic after the one compile the as-written
    report already paid for."""
    from llama_pipeline_parallel_tpu.parallel import pipeline as pl
    from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig
    from llama_pipeline_parallel_tpu.train import (
        build_manifest,
        build_model_config,
        build_pipeline_config,
    )

    mesh_cfg = MeshConfig(**cfg.get("mesh", {}))
    model_cfg = build_model_config(cfg["model"])
    manifest = build_manifest(cfg, model_cfg, mesh_cfg.pp)
    pcfg = build_pipeline_config(cfg, mesh_cfg, manifest)
    import jax.numpy as jnp

    mb_rows = int(cfg.get("per_device_train_batch_size", 1))
    seq = report["seq"]
    dims = pl.stash_dims(mb_rows, seq, mesh_cfg.sp, model_cfg.hidden_size,
                         model_cfg.dtype)
    # schedule-independent anchor: the compiled DEVICE peak minus the
    # as-written config's own ring/stash/loss-head terms. The ce axis
    # (docs/KERNELS.md) only exists at tp=1: under tp the head is already
    # vocab-parallel and the trainer REJECTS loss_chunks/kernels.ce
    # overrides, so selection must not recommend them (the head term is
    # then candidate-invariant and stays inside the anchor). Pallas
    # candidates are offered CHUNKED only — at loss_chunks=1 the kernel's
    # [d, V] weight block cannot fit VMEM at production vocabs.
    vocab = model_cfg.vocab_size if mesh_cfg.tp <= 1 else None
    mem_scale = getattr(args, "mem_scale", 1.0) or 1.0
    terms = candidate_device_terms_gib(pcfg, dims, vocab)
    base = (report["per_device_peak_gib"] - terms["ring_gib"]
            - terms["stash_gib"] - terms["loss_head_gib"])
    compute_fn = lambda c: _step_compute_seconds(
        model_cfg, mesh_cfg, c, mb_rows, seq, args.mfu, args.chip_flops)
    ce_axis = ce_axis_options(pcfg.loss_chunks, model_cfg.vocab_size,
                              mesh_cfg.tp)
    candidates = enumerate_candidates(mesh_cfg.pp, pcfg.num_microbatches,
                                      model_cfg.num_hidden_layers,
                                      ce_options=ce_axis,
                                      layer_counts=pcfg.layer_counts)
    # the solver lane: list-scheduled sequences with budget-sized per-unit
    # offload vectors, scored in the SAME pass under the same constraints
    # (incl. the dense loss-head term solver rows are charged — they carry
    # the as-written head, never a ce override)
    solver_head = 0.0
    if vocab:
        import dataclasses as _dc

        solver_head = pl.loss_head_bytes(
            _dc.replace(pcfg, loss_chunks=1, kernel_ce=False),
            *dims[:3], vocab) / (1 << 30)
    if pcfg.layer_counts is None or len(set(pcfg.layer_counts)) == 1:
        # the solver lane emits even sequences; on an unequal as-written
        # partition its rows would be scored with uncosted bubbles and
        # unfairly beat the canonical candidates — skip it there (the
        # layout lane already skips uneven layouts the same way)
        candidates += solver_candidates(mesh_cfg.pp, pcfg.num_microbatches,
                                        model_cfg.num_hidden_layers, base,
                                        dims, args.hbm_gb,
                                        head_gib=solver_head,
                                        mem_scale=mem_scale)
    winner, rows = select_schedule(
        candidates, base, dims, args.hbm_gb, args.host_bw_gibps, compute_fn,
        hide_max=args.hide_ratio_max, vocab=vocab, mem_scale=mem_scale)
    scale_note = (f", mem_scale {mem_scale}" if mem_scale != 1.0 else "")
    print(f"schedule selection ({len(rows)} candidates; base "
          f"{round(base, 2)} GiB + per-candidate ring/stash/loss-head; "
          f"bw {args.host_bw_gibps} GiB/s, mfu {args.mfu}{scale_note}):")
    print(f"  {'schedule':<17} {'v':>2} {'c':>2} {'offload':<14} "
          f"{'ce':<10} {'peak GiB':>9} {'host GiB':>9} {'head GiB':>9} "
          f"{'bubble%':>8} {'hide':>6}  verdict")
    for r in sorted(rows, key=lambda r: (not r["feasible"],
                                         r["bubble_fraction"],
                                         r["est_peak_gib"])):
        off = "+".join(n for n, on in (("wgrad", r["offload_wgrad"]),
                                       ("acts", r["offload_activations"]))
                       if on) or "-"
        if r.get("wgrad_offload_units"):
            off = (f"wgrad[{r['wgrad_offload_units']}"
                   f"/{r['wgrad_units_total']}]")
        sched_name = r["schedule"]
        if r.get("label"):
            sched_name = r["label"]
        ce = (f"{'pallas' if r['kernel_ce'] else 'xla'}/"
              f"{r['loss_chunks']}")
        mark = "*" if r is winner else " "
        print(f" {mark}{sched_name:<17} {r['virtual_stages']:>2} "
              f"{r['accum_chunks']:>2} {off:<14} {ce:<10} "
              f"{r['est_peak_gib']:>9} {r['host_stash_gib']:>9} "
              f"{r['loss_head_gib']:>9} "
              f"{100 * r['bubble_fraction']:>8.2f} {r['hide_ratio']:>6} "
              f" {'OK' if r['feasible'] else r['why_not']}")
    if args.layout_devices or args.emit_ladder:
        # the OUTER axes: every (pp, tp, dp, sp) mesh of the device count,
        # each re-scored by the same memory model — runs even when nothing
        # fits the as-written mesh (another layout may be the fix)
        _print_layout_frontier(cfg, args, model_cfg, mesh_cfg, pcfg, base,
                               mb_rows, seq)
    if winner is None:
        print("selection: NO feasible candidate — grow the mesh (tp/pp) or "
              "shrink the batch shape")
        if getattr(args, "emit_schedule", None):
            # the debug-a-refused-schedule case the flag exists for: emit
            # the as-written config's canonical sequence so the operator
            # can read the timeline even though nothing fit
            _emit_schedule(args.emit_schedule, None, None, mesh_cfg.pp, pcfg)
        return
    emitted = None
    if getattr(args, "emit_schedule", None):
        emitted = _emit_schedule(args.emit_schedule, winner.get("_pcfg"),
                                 winner, mesh_cfg.pp, pcfg)
    print(f"selected: {select_overrides(winner, schedule_file=emitted)}  "
          f"(est peak {winner['est_peak_gib']} GiB, bubble "
          f"{100 * winner['bubble_fraction']:.2f}%, host stash "
          f"{winner['host_stash_gib']} GiB)")


def _print_layout_frontier(cfg: dict, args, model_cfg, mesh_cfg, pcfg,
                           base: float, mb_rows: int, seq: int) -> None:
    """The layout lane of --select: print the scored (pp, tp, dp, sp)
    frontier and — with --emit-ladder — write the generated supervisor
    ladder (+ any solver rungs' unit-sequence files). Pure arithmetic on
    top of the one compile the as-written report paid for."""
    import json as _json

    devices = args.layout_devices or mesh_cfg.world_size
    g_examples = mb_rows * pcfg.num_microbatches * mesh_cfg.dp
    aw_layout = (mesh_cfg.pp, mesh_cfg.tp, mesh_cfg.dp, mesh_cfg.sp)
    kw = dict(host_bw_gibps=args.host_bw_gibps, mfu=args.mfu,
              chip_flops=args.chip_flops, ici_bw_gibps=args.ici_bw_gibps,
              hide_max=args.hide_ratio_max,
              optimizer_offload=bool(cfg.get("optimizer_offload")),
              zero2=bool(cfg.get("optimizer_offload_zero2")),
              loss_chunks_aw=pcfg.loss_chunks,
              mem_scale=getattr(args, "mem_scale", 1.0) or 1.0)
    # the display frontier ranks LAYOUTS, and the layout score depends on
    # the bubble, not on where the W residuals live — the canonical lane
    # ranks identically, so the solver refinement (slower: a per-unit
    # binary search per grid point) is saved for the ladder's actual rungs
    winner, rows = layout_frontier(model_cfg, devices, mb_rows, seq,
                                   g_examples, base, aw_layout, args.hbm_gb,
                                   solver_lane=False, **kw)
    print(f"layout frontier ({devices} devices, global batch {g_examples} "
          f"examples preserved; analytic memory model calibrated on the "
          f"compiled as-written peak, score = compute/(1-bubble) + "
          f"collectives at {args.ici_bw_gibps} GiB/s ICI):")
    print(f"  {'layout':<20} {'M':>4} {'partition':<14} {'schedule':<17} "
          f"{'v':>2} {'c':>2} {'peak GiB':>9} {'bubble%':>8} "
          f"{'score s':>8}  verdict")
    for r in rows:
        part = ("even" if not r["layer_counts"]
                else ",".join(str(c) for c in r["layer_counts"]))
        mark = "*" if r is winner else " "
        if r["feasible"]:
            s = r["sched"]
            name = s.get("label") or s["schedule"]
            print(f" {mark}{r['layout']:<20} {r['microbatches']:>4} "
                  f"{part:<14} {name:<17} {s['virtual_stages']:>2} "
                  f"{s['accum_chunks']:>2} {r['est_peak_gib']:>9} "
                  f"{100 * r['bubble_fraction']:>8.2f} {r['score_s']:>8}  OK")
        else:
            print(f" {mark}{r['layout']:<20} {r['microbatches']:>4} "
                  f"{part:<14} {'-':<17} {'-':>2} {'-':>2} "
                  f"{r['base_gib']:>9} {'-':>8} {'-':>8}  {r['why_not']}")
    if winner is not None:
        print(f"layout selected: {winner['layout']} "
              f"(score {winner['score_s']} s, est peak "
              f"{winner['est_peak_gib']} GiB) — overrides: "
              f"{' '.join(layout_overrides(winner))}")
    else:
        print("layout selection: NO feasible layout at this device count — "
              "shrink the batch shape or raise --hbm-gb")
    if args.emit_ladder:
        stem = args.emit_ladder
        if stem.endswith(".json"):
            stem = stem[: -len(".json")]

        def schedule_file_for(rung_name: str, rung_pcfg) -> str:
            from llama_pipeline_parallel_tpu.parallel import schedule as usched

            path = f"{stem}-{rung_name}.schedule.json"
            with open(path, "w") as fh:
                fh.write(usched.to_json(rung_pcfg.unit_schedule))
            return path

        rungs, _ = build_ladder(model_cfg, devices, mb_rows, seq,
                                g_examples, base, aw_layout, args.hbm_gb,
                                top_k=args.ladder_top_k,
                                schedule_file_for=schedule_file_for, **kw)
        with open(args.emit_ladder, "w") as fh:
            _json.dump(rungs, fh, indent=1)
            fh.write("\n")
        print(f"emitted ladder -> {args.emit_ladder} ({len(rungs)} rungs, "
              f"best-first; feed it to tools/supervisor.py "
              f"--layout-ladder @{args.emit_ladder}):")
        for rg in rungs:
            print(f"  {rg['devices']:>5} devices  {rg['name']:<28} "
                  f"{' '.join(rg['overrides'])}")


def _emit_schedule(path: str, winner_pcfg, row: dict | None, pp: int,
                   as_written_pcfg=None) -> str:
    """`--emit-schedule <path>`: serialize the relevant unit sequence
    (the --select winner's, else the as-written config's canonical
    re-emission) as JSON and print the compact per-stage ASCII timeline —
    so a refused or surprising schedule is debuggable without a TPU."""
    from llama_pipeline_parallel_tpu.parallel import pipeline as pl
    from llama_pipeline_parallel_tpu.parallel import schedule as usched

    import dataclasses as _dc

    pcfg = winner_pcfg
    if pcfg is None and row is None and as_written_pcfg is not None \
            and as_written_pcfg.schedule == "gpipe":
        print("--emit-schedule: gpipe has no unit sequence (its backward "
              "is AD of the forward scan) — nothing emitted")
        return path
    if pcfg is None and row is not None:
        # a canonical winner: rebuild its pcfg from the row (the winner's
        # grid shares the as-written config's total microbatch count)
        if as_written_pcfg is None:
            raise ValueError("_emit_schedule needs the as-written pcfg to "
                             "size a canonical winner's flush")
        pcfg = pl.PipelineConfig(
            num_stages=pp,
            num_microbatches=as_written_pcfg.num_microbatches,
            schedule=row["schedule"], virtual_stages=row["virtual_stages"],
            accum_chunks=row["accum_chunks"],
            offload_wgrad=row["offload_wgrad"],
            offload_activations=row["offload_activations"])
    if pcfg is None:
        pcfg = as_written_pcfg
    flush_pcfg = _dc.replace(
        pcfg, num_microbatches=pcfg.num_microbatches // pcfg.accum_chunks,
        accum_chunks=1)
    seq = (flush_pcfg.unit_schedule if flush_pcfg.schedule == "solver"
           else usched.canonical_schedule(
               flush_pcfg.schedule, flush_pcfg.num_microbatches,
               flush_pcfg.num_stages, flush_pcfg.virtual_stages,
               offload_wgrad=flush_pcfg.offload_wgrad))
    with open(path, "w") as fh:
        fh.write(usched.to_json(seq))
    idle, wall = usched.bubble_stats(seq)
    print(f"emitted unit sequence -> {path} ({seq.num_ticks} ticks, "
          f"{idle}/{wall} idle units = {idle / wall:.4f} bubble per flush)")
    print(usched.ascii_timeline(seq))
    return path


if __name__ == "__main__":
    main()
