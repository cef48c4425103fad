#!/usr/bin/env python
"""Single-chip training-throughput benchmark.

Runs the real train-step path (pipeline machinery at PP=1, bf16 compute,
fp32 AdamW with ZeRO-1 layout) on a ~550M-param LLaMA-shaped model at the
reference workload shape (seq 512; reference conf yaml:32) and prints ONE
JSON line: {"metric", "value", "unit", "vs_baseline", ..., "device"}.

It measures, so it needs a chip it knows: a device_kind missing from
utils/metrics.TPU_PEAK_FLOPS (the CPU backend included) is an error before
anything compiles. A failed headline config raises; a failed non-headline
(`extra:`) row is reported on stderr and makes the exit code nonzero after
the JSON line is out. (ROADMAP C1: this file is due to be replaced by a
fixed table of cells; until then it is the sweep it always was.)

Sweeps the configuration knobs a user would actually tune on one chip —
remat on/off (HBM is plentiful at this size; recompute is pure overhead when
memory allows), exact vs flash attention, and the vocab-chunked fused CE
(which never materializes the fp32 [tokens, V] logits) — and reports the BEST measured
configuration as the headline, with every config's number in the detail
field. The reference publishes no throughput numbers (BASELINE.md), so
vs_baseline is measured MFU / 0.45 — the 45%-MFU north-star from
BASELINE.json.

After the headline sweep, three NON-headline rows bench the paths the 65B
run of record actually uses (they appear under `all_configs` prefixed
`extra:` but never win the headline — their tokens/s are not
shape-comparable):
- `extra:offload` — the SAME step with the host-offloaded AdamW
  (optim/offload.py, the trainer-default device-norm streaming path)
  instead of the fused optax update; its delta vs the matching fused row is
  the measured offload stall, and the row carries the phase breakdown from
  `host.last_timings` (norm_ms + the streamed d2h/update/h2d span).
- `extra:packed` — a FLAN-shaped packed batch (segment-id masks, ~real
  workload); its tokens/s counts REAL (non-pad) tokens only, the
  `real_tokens_per_sec` headline of packed training.
- `extra:seq2048-flash` — the long-context shape on the flash kernel.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _parse_args(argv=None):
    """CLI surface (env vars keep working; flags win where both exist):

    --full-trajectory  the one-shot runbook: force every `extra:*` row
                       family ON (sched-*, layout-*, offload-*, mem-*,
                       kernel-*, serve-*) regardless of the BENCH_* env
                       toggles and write every row into the perf ledger
                       — one run records everything in one pass.
    --perf-ledger P    append utils/perf.py rows (model-vs-measured pairs
                       per row) to P; defaults to ./perf.jsonl under
                       --full-trajectory.
    --row-budget-s B   per-row wall budget for the extras families: a new
                       family may start only while the extras wall stays
                       within B x rows-completed (+1); families skipped by
                       an exhausted budget land in the ledger as
                       reason-tagged rows, so perf_report can tell
                       "skipped" from "never attempted".
    """
    p = argparse.ArgumentParser(add_help=False)
    p.add_argument("--full-trajectory", action="store_true")
    p.add_argument("--perf-ledger", default=os.environ.get("BENCH_PERF_LEDGER"))
    p.add_argument("--row-budget-s", type=float,
                   default=float(os.environ.get("BENCH_ROW_BUDGET_S", "0") or 0))
    args, _ = p.parse_known_args(argv)
    if args.full_trajectory:
        for var in ("BENCH_EXTRAS", "BENCH_SCHEDULES", "BENCH_LAYOUT",
                    "BENCH_OFFLOAD", "BENCH_MEM", "BENCH_KERNELS",
                    "BENCH_SERVING"):
            os.environ[var] = "1"
        if not args.perf_ledger:
            args.perf_ledger = "perf.jsonl"
    return args


class _RowBudget:
    """Per-row wall budget over the extras families (--row-budget-s).
    `allow(name)` gates each family: permitted only while the extras wall
    is within budget x (rows completed so far + 1) — one overrunning row
    eats the later families' budget instead of the harness's patience.
    Skips are recorded for the ledger."""

    def __init__(self, per_row_s: float, count_rows=None):
        self.per_row = per_row_s
        self.t0 = None
        self._count = count_rows or (lambda: 0)
        self._initial = 0
        self.skipped: list[str] = []

    def start(self) -> None:
        self.t0 = time.perf_counter()
        self._initial = self._count()

    def allow(self, name: str) -> bool:
        if not self.per_row or self.t0 is None:
            return True
        rows_done = max(self._count() - self._initial, 0)
        elapsed = time.perf_counter() - self.t0
        if elapsed <= self.per_row * (rows_done + 1):
            return True
        print(f"bench row family {name} skipped: extras wall "
              f"{elapsed:.0f}s exceeds the --row-budget-s {self.per_row:.0f}"
              f"s x {rows_done + 1} rows", file=sys.stderr, flush=True)
        self.skipped.append(name)
        return False


def _write_ledger(path: str | None, summary: dict,
                  skipped: list[str]) -> None:
    """Append this round's rows to the perf ledger (--perf-ledger): the
    model-vs-measured pairs, plus one reason-tagged row per family the row
    budget skipped. Never raises: the measurement JSON line is already out
    when this runs."""
    if not path:
        return
    try:
        import jax

        from llama_pipeline_parallel_tpu.utils import perf

        label = os.environ.get("BENCH_RUN_LABEL") or \
            f"bench-{time.strftime('%Y%m%d-%H%M%S')}"
        rows = perf.rows_from_bench_summary(summary, run=label)
        # stamp the backend: derive_calibration must only ever feed TPU
        # measurements into preflight's TPU model constants
        backend = jax.default_backend()
        for row in rows:
            row.setdefault("context", {})["backend"] = backend
        rows += [perf.make_row("bench_row_family", source="bench", run=label,
                               reason=f"skipped: row budget exhausted "
                                      f"before {name}")
                 for name in skipped]
        n = perf.append_rows(path, rows)
        print(f"perf ledger: {n} row(s) appended to {path}",
              file=sys.stderr, flush=True)
    except Exception as e:
        print(f"perf ledger write failed: {e!r}", file=sys.stderr, flush=True)


def main() -> None:
    cli = _parse_args()
    results: dict[str, dict] = {}  # name -> {"dt": s/step, "tokens_per_step": n}
    summary_ctx: dict = {}
    row_budget = _RowBudget(cli.row_budget_s, count_rows=lambda: len(results))

    def report():
        # extras (offload/packed/long-seq rows) are excluded from the
        # headline: their tokens/s are not shape-comparable with the sweep
        headliners = {k: r for k, r in results.items()
                      if r.get("headline", True)}
        tps_of = lambda r: r["tokens_per_step"] / r["dt"]
        best_name = max(headliners, key=lambda k: tps_of(headliners[k]))
        best = headliners[best_name]
        tps = tps_of(best)
        mfu = summary_ctx["flops_token"] * tps / summary_ctx["peak"]
        return {
            "metric": "tokens_per_sec_per_chip",
            "value": round(tps, 1),
            "unit": "tokens/s/chip",
            "vs_baseline": round(mfu / 0.45, 4),
            "mfu": round(mfu, 4),
            "step_time_ms": round(1000 * best["dt"], 1),
            "best_config": best_name,
            # untimed gauge rows (extra:mem-pagepool) carry dt=0: no tok_s
            "all_configs": {k: {"ms": round(1000 * r["dt"], 1),
                                "tok_s": round(tps_of(r), 1) if r["dt"]
                                else None,
                                **r.get("detail", {})}
                            for k, r in results.items()},
            # round-1 emitted a flat name->ms map under this key; keep it so
            # round-over-round consumers keep parsing (ADVICE round-3)
            "all_configs_ms": {k: round(1000 * r["dt"], 1)
                               for k, r in results.items()},
            "model": summary_ctx["model"],
            "device": summary_ctx["device"],
        }

    import jax
    import jax.numpy as jnp
    import numpy as np

    from __graft_entry__ import _bench_config
    from llama_pipeline_parallel_tpu.utils import compile_cache
    from llama_pipeline_parallel_tpu.utils.metrics import (
        require_chip_peak_flops,
    )

    # A measurement needs a known chip: no CPU re-pinning, no default peak.
    peak = require_chip_peak_flops()
    compile_cache.setup()
    from llama_pipeline_parallel_tpu.models.llama import model as llama
    from llama_pipeline_parallel_tpu.models.llama.manifest import StageManifest
    from llama_pipeline_parallel_tpu.ops.attention import attention
    from llama_pipeline_parallel_tpu.ops.flash_attention import flash_attention
    from llama_pipeline_parallel_tpu.optim import OptimizerConfig, make_optimizer
    from llama_pipeline_parallel_tpu.parallel import pipeline as pl
    from llama_pipeline_parallel_tpu.parallel import train_step as ts
    from llama_pipeline_parallel_tpu.parallel.mesh import MeshConfig, make_mesh
    from llama_pipeline_parallel_tpu.utils.metrics import train_flops_per_token

    cfg, model_name = _bench_config(), "llama-550m"
    # Batch sizes to sweep: 8 is the reference-comparable per-replica shape
    # (reference conf yaml:75); larger batches raise arithmetic intensity on
    # one chip, and the headline is the best measured config. Listed largest
    # (likely fastest per token) first.
    batches = [int(b) for b in
               os.environ.get("BENCH_BATCH", "32,16,8").split(",")]
    seq = int(os.environ.get("BENCH_SEQ", "512"))
    n_steps = int(os.environ.get("BENCH_STEPS", "10"))

    mesh = make_mesh(MeshConfig())  # single chip
    manifest = StageManifest.for_config(cfg, 1)
    canonical = llama.init_params(jax.random.PRNGKey(0), cfg)
    stacked = pl.stack_stages(canonical, manifest)
    tx, sched = make_optimizer(OptimizerConfig(learning_rate=1e-4, total_steps=1000,
                                               warmup_steps=10))

    def make_batch(batch_size: int, seq_len: int | None = None,
                   packed: bool = False) -> dict:
        L = seq_len or seq
        rs = np.random.RandomState(0)
        ids = rs.randint(3, cfg.vocab_size, (batch_size, L)).astype(np.int32)
        if not packed:
            return {
                "input_ids": jnp.asarray(ids),
                "attention_mask": jnp.ones((batch_size, L), jnp.int32),
                "position_ids": jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32),
                                                 (batch_size, L)),
                "labels": jnp.asarray(ids),
            }
        # FLAN-shaped packing: variable-length segments greedily packed per
        # row (the packed collator's contract — attention_mask carries
        # segment ids 1..k, 0 = pad; position_ids restart per segment;
        # segment-start labels ignored). Mean segment ~L/4 so rows carry
        # several segments plus a realistic pad tail.
        from llama_pipeline_parallel_tpu.models.llama.model import (
            IGNORE_INDEX as IGNORE,
        )

        mask = np.zeros((batch_size, L), np.int32)
        pos = np.zeros((batch_size, L), np.int32)
        labels = ids.astype(np.int32).copy()
        for b in range(batch_size):
            cursor, seg_id = 0, 1
            while L - cursor >= max(8, L // 16):
                length = min(int(rs.randint(L // 8, L // 2)), L - cursor)
                mask[b, cursor:cursor + length] = seg_id
                pos[b, cursor:cursor + length] = np.arange(length)
                labels[b, cursor] = IGNORE
                cursor += length
                seg_id += 1
            labels[b, cursor:] = IGNORE  # pad tail
        return {
            "input_ids": jnp.asarray(ids),
            "attention_mask": jnp.asarray(mask),
            "position_ids": jnp.asarray(pos),
            "labels": jnp.asarray(labels),
        }

    flops_token = train_flops_per_token(cfg, seq)
    summary_ctx.update(peak=peak, flops_token=flops_token,
                       model=f"{model_name} seq{seq} bf16 1f1b",
                       device={"platform": jax.devices()[0].platform,
                               "kind": jax.devices()[0].device_kind,
                               "count": jax.device_count()})

    offload_phases: dict = {}  # host.last_timings of the latest offload row

    def measure(remat: bool, attn_name: str, batch_size: int,
                loss_chunks: int = 1, trace_dir: str | None = None,
                seq_len: int | None = None, packed: bool = False,
                offload: bool = False, kernel_ce: bool = False,
                kernel_prologue: bool = False) -> float:
        """Mean steady-state step seconds for one config. Raises if the
        config fails to build or run, or its loss is not finite (a
        fast-but-broken config must never win the headline). `trace_dir`
        captures a profiler trace of the timed loop only (the
        warmup/compile step stays outside the trace). `offload` swaps the
        fused optax update for the host-offloaded AdamW (the 65B path's
        optimizer) and records its phase breakdown in `offload_phases`."""
        import math

        batch = make_batch(batch_size, seq_len, packed)
        attn_fn = flash_attention if attn_name == "flash" else attention
        pcfg = pl.PipelineConfig(num_stages=1, num_microbatches=1,
                                 remat=remat, loss_chunks=loss_chunks,
                                 kernel_ce=kernel_ce,
                                 kernel_prologue=kernel_prologue)
        if offload:
            from llama_pipeline_parallel_tpu.optim.offload import (
                HostOffloadAdamW,
            )

            host = HostOffloadAdamW(OptimizerConfig(
                learning_rate=1e-4, total_steps=1000, warmup_steps=0),
                device_norm=True)  # the trainer's default streaming path
            host.init(stacked)
            grad_fn = jax.jit(pl.make_pipeline_loss_and_grad(
                mesh, cfg, pcfg, host.abstract_tree(), attn_fn=attn_fn))
            dev_box = [host.device_params(cfg.dtype)]

            def step_once():
                loss, grads = grad_fn(dev_box[0], batch)
                dev_box[0] = host.update_and_refresh(grads, cfg.dtype)
                return loss
        else:
            state_box = [ts.init_train_state(stacked, tx, mesh)]
            step = ts.make_train_step(mesh, cfg, pcfg, tx, sched, stacked,
                                      attn_fn=attn_fn)

            def step_once():
                state_box[0], metrics = step(state_box[0], batch)
                return metrics["loss"]

        # warmup (compile) off the clock, then steady state; the timed
        # region ends in block_until_ready on the last step's loss, which
        # depends on every earlier step through the donated state
        jax.block_until_ready(step_once())
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        try:
            t0 = time.perf_counter()
            for _ in range(n_steps):
                loss = step_once()
            jax.block_until_ready(loss)
            dt = (time.perf_counter() - t0) / n_steps
        finally:
            if trace_dir:  # finalize whatever was captured, even on error
                jax.profiler.stop_trace()
        if offload:
            offload_phases.clear()
            offload_phases.update({k: round(v, 2)
                                   for k, v in host.last_timings.items()})
        if not math.isfinite(float(loss)):
            raise ValueError(
                f"bench config remat={remat} attn={attn_name} "
                f"bs={batch_size} ce_chunks={loss_chunks} produced "
                f"non-finite loss {float(loss)}")
        return dt

    failures: list[str] = []

    def failed(what: str, e: Exception) -> None:
        """A non-headline row family failed: say so now, and fail the run
        at exit (after the headline JSON line is out). A failed headline
        row is not caught at all."""
        print(f"bench {what} failed: {e!r}", file=sys.stderr, flush=True)
        failures.append(what)

    # The flash rows run only at the LARGEST batch (short-seq flash wins,
    # if any, come from batch-boosted occupancy): each extra config costs a
    # full XLA compile.
    configs = {f"remat={int(remat)},attn={attn_name},bs={bs}":
               (remat, attn_name, bs, 1)
               for remat in (False, True) for attn_name in ("exact", "flash")
               for bs in batches
               if attn_name == "exact" or bs == max(batches)}
    # The vocab-chunked fused CE at the largest batch: the PP=1 step's
    # biggest single buffer is the fp32 [tokens, V] logits (2 GiB at bs32
    # seq512 V32k); the online-logsumexp head never materializes it, so this
    # row is the HBM-traffic winner candidate. One extra compile.
    bs_top = max(batches)
    head = {f"remat=0,attn=exact,bs={bs_top}":
            configs.pop(f"remat=0,attn=exact,bs={bs_top}"),
            f"remat=0,attn=exact,bs={bs_top},ce=chunk8":
            (False, "exact", bs_top, 8)}
    configs = {**head, **configs}
    for name, (remat, attn_name, bs, chunks) in configs.items():
        results[name] = {"dt": measure(remat, attn_name, bs, chunks),
                         "tokens_per_step": bs * seq}

    # Non-headline rows: the paths the 65B run of record actually exercises
    # (offloaded optimizer, packed FLAN-shaped batches, long-context flash).
    # BENCH_EXTRAS=0 skips them.
    if os.environ.get("BENCH_EXTRAS", "1") != "0":
        row_budget.start()  # the per-row wall budget covers the extras families
        bs_big = max(batches)
        long_seq = 2048

        try:
            dt = measure(False, "exact", bs_big, offload=True)
            fused = results.get(f"remat=0,attn=exact,bs={bs_big}")
            detail = {"phases_ms": dict(offload_phases)}
            if fused:  # measured offload stall vs the matching fused row
                detail["stall_vs_fused_ms"] = round(1000 * (dt - fused["dt"]), 1)
            results[f"extra:offload,bs={bs_big}"] = {
                "dt": dt, "tokens_per_step": bs_big * seq,
                "headline": False, "detail": detail}
        except Exception as e:
            failed("offload row", e)

        try:
            packed_batch = make_batch(bs_big, packed=True)
            real_tokens = int(
                (np.asarray(packed_batch["attention_mask"]) != 0).sum())
            dt = measure(False, "exact", bs_big, packed=True)
            # tokens/s counts REAL (non-pad) tokens: the packed-training
            # headline number (real_tokens_per_sec)
            results[f"extra:packed,bs={bs_big}"] = {
                "dt": dt, "tokens_per_step": real_tokens, "headline": False,
                "detail": {"real_tokens_per_step": real_tokens,
                           "padded_tokens_per_step": bs_big * seq}}
        except Exception as e:
            failed("packed row", e)

        try:
            dt = measure(False, "flash", 8, seq_len=long_seq)
            results[f"extra:seq{long_seq}-flash,bs=8"] = {
                "dt": dt, "tokens_per_step": 8 * long_seq, "headline": False,
                "detail": {"seq": long_seq}}
        except Exception as e:
            failed(f"seq{long_seq} flash row", e)

        # Schedule ladder (BENCH_SCHEDULES=0 skips): flat vs interleaved vs
        # zb1 loss+grad step on a real pp ring over the chips this process
        # can see, each row carrying its analytic bubble_fraction NEXT to
        # the measured step time — so one run lands a model-vs-measured
        # schedule trajectory point in one shot. Non-headline: a pp-ring
        # step at these shapes is not tokens/s-comparable with the pp1
        # sweep.
        if os.environ.get("BENCH_SCHEDULES", "1") != "0" and row_budget.allow("sched"):
            n_dev = jax.device_count()
            pp_s = 4 if n_dev >= 4 else n_dev
            m_s = int(os.environ.get("BENCH_SCHED_MICROBATCHES", "8"))
            if pp_s < 2:
                print("bench schedule rows skipped: one visible device "
                      "(a pp ring needs >= 2 chips)", file=sys.stderr,
                      flush=True)
            else:
                sched_mesh = make_mesh(MeshConfig(pp=pp_s))
                sbatch = make_batch(m_s)  # one row per microbatch
                stacked_by_v: dict[int, tuple] = {}  # v -> (manifest, params)
            for sched_name, v_s in ((("1f1b", 1), ("interleaved_1f1b", 2),
                                ("zb1", 2), ("solver", 2))
                               if pp_s >= 2 else ()):
                if cfg.num_hidden_layers % (pp_s * v_s) or m_s % pp_s:
                    print(f"bench schedule row {sched_name} skipped: "
                          f"{cfg.num_hidden_layers} layers / m={m_s} do not "
                          f"fit pp={pp_s} v={v_s}", file=sys.stderr, flush=True)
                    continue
                try:
                    if v_s not in stacked_by_v:  # one ~550M re-stack per v
                        man_s = StageManifest.for_config(cfg, pp_s,
                                                         virtual_stages=v_s)
                        stacked_by_v[v_s] = (man_s,
                                             pl.stack_stages(canonical, man_s))
                    man_s, stacked_s = stacked_by_v[v_s]
                    seq_s = None
                    if sched_name == "solver":
                        # the list scheduler's drain-interleaved W variant:
                        # canonical zb1 bubble, compressed W queue — the
                        # measured point for the solver lane next to the
                        # three canonical rows (docs/SCHEDULES.md)
                        from llama_pipeline_parallel_tpu.parallel import (
                            schedule as usched,
                        )

                        seq_s = usched.list_schedule(m_s, pp_s, v_s,
                                                     w_placement="drain")
                    pcfg_s = pl.PipelineConfig(
                        num_stages=pp_s, num_microbatches=m_s,
                        schedule=sched_name, virtual_stages=v_s,
                        unit_schedule=seq_s)
                    fn = jax.jit(pl.make_pipeline_loss_and_grad(
                        sched_mesh, cfg, pcfg_s, stacked_s))
                    float(fn(stacked_s, sbatch)[0])  # compile off the clock
                    t0 = time.perf_counter()
                    for _ in range(n_steps):
                        last = float(fn(stacked_s, sbatch)[0])
                    dt = (time.perf_counter() - t0) / n_steps
                    if not np.isfinite(last):
                        raise ValueError(f"non-finite loss {last}")
                    detail = {
                        "schedule": sched_name, "pp": pp_s,
                        "virtual_stages": v_s, "microbatches": m_s,
                        "bubble_fraction_analytic":
                            round(pl.bubble_fraction(pcfg_s), 4)}
                    if pl.wgrad_queue_peak(pcfg_s):
                        detail["wgrad_queue_depth"] = pl.wgrad_queue_peak(pcfg_s)
                    if sched_name == "solver":
                        detail["sequence"] = seq_s.label
                    results[f"extra:sched-{sched_name},pp={pp_s}"] = {
                        "dt": dt, "tokens_per_step": m_s * seq,
                        "headline": False, "detail": detail}
                except Exception as e:
                    failed(f"schedule row {sched_name} pp={pp_s} v={v_s}", e)

        # Cost-model auto-layout rows (BENCH_LAYOUT=0 skips): the generated
        # ladder's top rungs (tools/preflight.py layout_frontier — the
        # (pp, tp, dp, sp) frontier at the chips this process can see, the
        # same lane `--select --emit-ladder` walks), each measured over the
        # SAME global batch with the ANALYTIC step-time score emitted NEXT
        # to the measured step time — so one run records the whole
        # model-vs-measured frontier in one pass.
        if os.environ.get("BENCH_LAYOUT", "1") != "0" and row_budget.allow("layout"):
            try:
                sys.path.insert(0, os.path.join(os.path.dirname(
                    os.path.abspath(__file__)), "tools"))
                import preflight as _pf

                n_dev = jax.device_count()
                mb_l = 1
                m_l = int(os.environ.get("BENCH_SCHED_MICROBATCHES", "8"))
                g_l = mb_l * m_l * n_dev  # examples/step, rung-invariant
                # anchor the memory model on its own pp1 estimate (no
                # compile here — the budget only prunes absurd layouts; the
                # point of these rows is score-vs-measured, and
                # vocab_enabled=False keeps every rung on the as-written
                # loss head so the layout axis is the only variable)
                base_aw = _pf.layout_device_gib(cfg, 1, 1, 1)
                _, lrows = _pf.layout_frontier(
                    cfg, n_dev, mb_l, seq, g_l, base_aw, (1, 1, 1, 1),
                    float(os.environ.get("BENCH_LAYOUT_HBM_GB", "95")),
                    chip_flops=peak, vocab_enabled=False, solver_lane=False)
                top = [r for r in lrows if r["feasible"]][:3]
                if not top:
                    print("bench layout rows skipped: no feasible layout "
                          f"at {n_dev} device(s)", file=sys.stderr, flush=True)
                for r in top:
                    s = r["sched"]
                    try:
                        lay_mesh = make_mesh(MeshConfig(
                            pp=r["pp"], tp=r["tp"], dp=r["dp"], sp=r["sp"]))
                        man_l = StageManifest(
                            num_layers=cfg.num_hidden_layers,
                            num_stages=r["pp"],
                            layer_counts=(tuple(r["layer_counts"])
                                          if r["layer_counts"] else None),
                            virtual_stages=s["virtual_stages"])
                        stacked_l = pl.stack_stages(canonical, man_l)
                        pcfg_l = pl.PipelineConfig(
                            num_stages=r["pp"],
                            num_microbatches=r["microbatches"],
                            schedule=s["schedule"],
                            virtual_stages=s["virtual_stages"],
                            accum_chunks=s["accum_chunks"],
                            offload_wgrad=s["offload_wgrad"],
                            offload_activations=s["offload_activations"],
                            layer_counts=(None if man_l.is_even
                                          else man_l.stage_layer_counts))
                        fn = jax.jit(pl.make_pipeline_loss_and_grad(
                            lay_mesh, cfg, pcfg_l, stacked_l))
                        lbatch = make_batch(g_l)
                        float(fn(stacked_l, lbatch)[0])  # compile
                        t0 = time.perf_counter()
                        for _ in range(n_steps):
                            last = float(fn(stacked_l, lbatch)[0])
                        dt = (time.perf_counter() - t0) / n_steps
                        if not np.isfinite(last):
                            raise ValueError(f"non-finite loss {last}")
                        results[f"extra:layout-{r['layout']}"] = {
                            "dt": dt, "tokens_per_step": g_l * seq,
                            "headline": False, "detail": {
                                "layout": r["layout"],
                                "microbatches": r["microbatches"],
                                "layer_counts": r["layer_counts"],
                                "schedule": s["schedule"],
                                "virtual_stages": s["virtual_stages"],
                                "accum_chunks": s["accum_chunks"],
                                "bubble_fraction_analytic":
                                    r["bubble_fraction"],
                                "score_s_model": r["score_s"],
                                "est_peak_gib_model": r["est_peak_gib"]}}
                    except Exception as e:
                        failed(f"layout row {r['layout']}", e)
            except Exception as e:
                failed("layout rows", e)

        # Host-stash offload rows (BENCH_OFFLOAD=0 skips): the measured
        # D2H/H2D host-link bandwidth (the number tools/preflight.py's
        # --host-bw-gibps feasibility assumption should be fed) and the
        # zb1 W-stash-offload step against its in-HBM twin, each row
        # carrying the MODELED transfer time and stash-hide ratio next to
        # the measured step time — one run = a model-vs-measured offload
        # point.
        if os.environ.get("BENCH_OFFLOAD", "1") != "0" and row_budget.allow("offload"):
            try:
                from llama_pipeline_parallel_tpu.utils import host_stash

                bw = host_stash.measure_transfer_bandwidth()
                probe_gib = bw["probe_mib"] / 1024
                results["extra:offload-bw"] = {
                    "dt": probe_gib / max(bw["d2h_gibps"], 1e-9),
                    "tokens_per_step": 0, "headline": False, "detail": bw}

                n_dev = jax.device_count()
                m_o = int(os.environ.get("BENCH_SCHED_MICROBATCHES", "8"))
                # largest ring (4 then 2) whose v=2 partition + microbatch
                # round-robin both divide — tiny's 4 layers land on pp=2
                pp_o = next((p for p in (4, 2)
                             if p <= n_dev and m_o % p == 0
                             and cfg.num_hidden_layers % (2 * p) == 0), 0)
                if pp_o:
                    off_mesh = make_mesh(MeshConfig(pp=pp_o))
                    man_o = StageManifest.for_config(cfg, pp_o,
                                                     virtual_stages=2)
                    stacked_o = pl.stack_stages(canonical, man_o)
                    obatch = make_batch(m_o)
                    dts = {}
                    for wgrad in (False, True):
                        pcfg_o = pl.PipelineConfig(
                            num_stages=pp_o, num_microbatches=m_o,
                            schedule="zb1", virtual_stages=2,
                            offload_wgrad=wgrad)
                        fn = jax.jit(pl.make_pipeline_loss_and_grad(
                            off_mesh, cfg, pcfg_o, stacked_o))
                        float(fn(stacked_o, obatch)[0])  # compile
                        t0 = time.perf_counter()
                        for _ in range(n_steps):
                            last = float(fn(stacked_o, obatch)[0])
                        dts[wgrad] = (time.perf_counter() - t0) / n_steps
                        if not np.isfinite(last):
                            raise ValueError(f"non-finite loss {last}")
                    pcfg_on = pl.PipelineConfig(
                        num_stages=pp_o, num_microbatches=m_o,
                        schedule="zb1", virtual_stages=2, offload_wgrad=True)
                    mb_o = obatch["input_ids"].shape[0] // m_o
                    stash = pl.wgrad_stash_bytes(pcfg_on, mb_o, seq,
                                                 cfg.hidden_size, 2)
                    # every residual pair moves D2H once + H2D once
                    transfer_s = 2 * stash / (
                        min(bw["d2h_gibps"], bw["h2d_gibps"]) * (1 << 30))
                    results[f"extra:offload-wgrad-stash,pp={pp_o}"] = {
                        "dt": dts[True], "tokens_per_step": m_o * seq,
                        "headline": False, "detail": {
                            "schedule": "zb1", "pp": pp_o,
                            "offload": "wgrad_stash",
                            "stash_mib": round(stash / (1 << 20), 1),
                            "in_hbm_step_ms": round(1000 * dts[False], 1),
                            "transfer_stall_ms":
                                round(1000 * (dts[True] - dts[False]), 1),
                            "transfer_ms_model": round(1000 * transfer_s, 2),
                            "stash_hide_ratio":
                                round(transfer_s / dts[False], 3)}}
            except Exception as e:
                failed("offload rows", e)

        # Memory observatory rows (BENCH_MEM=0 skips): the compiled
        # memory_analysis() peak (the byte model's measured counterpart —
        # utils/memwatch.py) next to the LIVE device peak after a real
        # step, plus a page-pool fragmentation point. The mem-peak pair is
        # what perf_report distills into the `mem_scale` calibration
        # constant preflight --select re-ranks with; off-TPU the live half
        # is host RSS-ish and the row is tagged with its backend so
        # derive_calibration excludes it (cpu rows never calibrate).
        if os.environ.get("BENCH_MEM", "1") != "0" and row_budget.allow("mem"):
            try:
                from llama_pipeline_parallel_tpu.utils import memwatch

                n_dev = jax.device_count()
                m_m = int(os.environ.get("BENCH_SCHED_MICROBATCHES", "8"))
                pp_m = next((p for p in (4, 2, 1)
                             if p <= n_dev and m_m % p == 0
                             and cfg.num_hidden_layers % p == 0), 1)
                mem_mesh = make_mesh(MeshConfig(pp=pp_m))
                man_m = StageManifest.for_config(cfg, pp_m)
                stacked_m = pl.stack_stages(canonical, man_m)
                mbatch = make_batch(m_m)
                pcfg_m = pl.PipelineConfig(num_stages=pp_m,
                                           num_microbatches=m_m)
                fn = jax.jit(pl.make_pipeline_loss_and_grad(
                    mem_mesh, cfg, pcfg_m, stacked_m))
                info = memwatch.compiled_memory(
                    fn.lower(stacked_m, mbatch).compile(), top_buffers=4,
                    label="bench_step")
                t0 = time.perf_counter()
                last = float(fn(stacked_m, mbatch)[0])
                dt_m = time.perf_counter() - t0
                if not np.isfinite(last):
                    raise ValueError(f"non-finite loss {last}")
                live = memwatch.live_sample()
                live_peak = live.get("device_peak_bytes")
                gib = 1 << 30
                results["extra:mem-peak"] = {
                    "dt": dt_m, "tokens_per_step": m_m * seq,
                    "headline": False, "detail": {
                        "backend": jax.devices()[0].platform,
                        "pp": pp_m,
                        "compiled_peak_gib":
                            round(info["peak_bytes"] / gib, 3)
                            if info else None,
                        "temp_gib": round(info["temp_bytes"] / gib, 3)
                        if info else None,
                        "live_peak_gib": round(live_peak / gib, 3)
                        if live_peak else None,
                        "live_source": "device" if live_peak else "none",
                        "top_buffers": (info or {}).get("top_buffers",
                                                        [])[:4]}}

                # page-pool fragmentation point: reserve worst-case demand,
                # back only the prompt — the reserved-vs-allocated gap the
                # serving gauges publish per tick, measured here once
                from llama_pipeline_parallel_tpu.serve import pages as pages_mod

                kvp = pages_mod.PagedKVCache(cfg, max_slots=4, max_len=64,
                                             page_size=16, num_pages=32)
                demand = kvp.demand_pages(32, 16)
                kvp.reserve(demand)
                slot = kvp.acquire("bench-mem", demand)
                kvp.ensure_capacity(slot, 32)
                g = kvp.fragmentation_gauges()
                results["extra:mem-pagepool"] = {
                    "dt": 0.0, "tokens_per_step": 0, "headline": False,
                    "detail": {
                        "backend": jax.devices()[0].platform,
                        "pool_gib": round(pages_mod.paged_pool_bytes(
                            cfg, 32, 16) / gib, 4),
                        "reserved_gap_gib":
                            round(g["reserved_gap_bytes"] / gib, 6),
                        **{k: g[k] for k in ("pages_free", "pages_used",
                                             "pages_reserved",
                                             "reserved_unbacked",
                                             "fragmentation")}}}
            except Exception as e:
                failed("memory rows", e)

        # Pallas kernel rows (BENCH_KERNELS=0 skips): the fused CE head and
        # the fused rms_norm->RoPE->QKV prologue (`kernels.*`,
        # docs/KERNELS.md) against their XLA twins at the same shape, each
        # row carrying the MODELED bytes the kernel keeps in VMEM next to
        # the measured step-time delta and the implied bandwidth — so the
        # win is measured, not asserted.
        if os.environ.get("BENCH_KERNELS", "1") != "0" and row_budget.allow("kernel"):
            try:
                from llama_pipeline_parallel_tpu.ops.pallas_ce import (
                    ce_head_traffic_bytes,
                )
                from llama_pipeline_parallel_tpu.ops.pallas_prologue import (
                    prologue_traffic_bytes,
                )

                gib = 1 << 30
                tokens = bs_big * seq
                # the kernel's own VMEM sizing (lane-exact 128-wide vocab
                # tiles — the XLA-scale 8 would blow VMEM on a real TPU and
                # the row would silently vanish from the one environment
                # that matters); twin measured at the SAME chunking
                ce_chunks = (cfg.vocab_size // 128
                             if cfg.vocab_size % 128 == 0 else 0)

                def kernel_row(name, dt_kernel, twin, bytes_model):
                    detail = {
                        "bytes_model_gib": round(bytes_model / gib, 3)}
                    if twin is not None:
                        delta = twin["dt"] - dt_kernel
                        detail["xla_step_ms"] = round(1000 * twin["dt"], 1)
                        detail["saved_ms"] = round(1000 * delta, 1)
                        if delta > 0:
                            # the bandwidth the deleted traffic effectively
                            # ran at — compare against the chip's HBM spec
                            detail["achieved_gibps"] = round(
                                bytes_model / gib / delta, 1)
                    results[name] = {"dt": dt_kernel,
                                     "tokens_per_step": tokens,
                                     "headline": False, "detail": detail}

                dt = (measure(False, "exact", bs_big, loss_chunks=ce_chunks,
                              kernel_ce=True) if ce_chunks else None)
                if not ce_chunks:
                    print(f"bench kernel-ce row skipped: vocab "
                          f"{cfg.vocab_size} has no 128-wide tiling",
                          file=sys.stderr, flush=True)
                if dt is not None:
                    twin = results.get(
                        f"remat=0,attn=exact,bs={bs_big},ce=chunk{ce_chunks}")
                    if twin is None:
                        twin_dt = measure(False, "exact", bs_big,
                                          loss_chunks=ce_chunks)
                        twin = ({"dt": twin_dt} if twin_dt is not None
                                else None)
                    kernel_row(f"extra:kernel-ce,bs={bs_big}", dt, twin,
                               ce_head_traffic_bytes(
                                   tokens, cfg.hidden_size, cfg.vocab_size,
                                   ce_chunks))

                dt = measure(False, "exact", bs_big, kernel_prologue=True)
                if dt is not None:
                    twin = results.get(f"remat=0,attn=exact,bs={bs_big}")
                    per_layer = prologue_traffic_bytes(
                        tokens, cfg.hidden_size,
                        cfg.num_attention_heads * cfg.head_dim,
                        cfg.kv_heads * cfg.head_dim,
                        jnp.dtype(cfg.dtype).itemsize)
                    kernel_row(f"extra:kernel-prologue,bs={bs_big}", dt, twin,
                               cfg.num_hidden_layers * per_layer)
            except Exception as e:
                failed("kernel rows", e)

        # Serving microbench (BENCH_SERVING=0 skips): prefill TTFT + steady-
        # state per-token decode latency at fixed batch through the REAL
        # continuous-batching engine (serve/engine.py), i.e. the numbers
        # docs/SERVING.md's SLOs are made of.
        if os.environ.get("BENCH_SERVING", "1") != "0" and row_budget.allow("serve"):
            # Paged-KV rows (docs/SERVING.md "Paged KV cache"): steady-state
            # decode through the engine (fp and int8 pages), each row
            # carrying the pool-vs-reservation resident byte model NEXT to
            # the measured per-token time, so one live TPU run lands the
            # int8 capacity doubling as a measured delta.
            try:
                from llama_pipeline_parallel_tpu.models.llama.decode import (
                    GenerationConfig,
                )
                from llama_pipeline_parallel_tpu.serve import (
                    ServeConfig,
                    ServeEngine,
                    ServeRequest,
                )
                from llama_pipeline_parallel_tpu.serve.pages import (
                    dense_kv_cache_bytes,
                    paged_pool_bytes,
                )

                slots = int(os.environ.get("BENCH_SERVE_SLOTS", "8"))
                decode_steps = int(os.environ.get("BENCH_SERVE_STEPS", "32"))
                budget = decode_steps + 8
                page = 16
                # bucket rounded DOWN to a page multiple (paged buckets
                # must be page-aligned; a seq that isn't must not silently
                # drop these rows)
                p_len = max(page, min(128, seq) // page * page)
                max_len_p = -(-(p_len + budget + 1) // page) * page
                dense_mib = dense_kv_cache_bytes(cfg, slots,
                                                 max_len_p) / (1 << 20)
                rs = np.random.RandomState(0)
                prompt = rs.randint(3, cfg.vocab_size, (p_len,)).tolist()
                for quant in ("fp", "int8"):
                    scfg = ServeConfig(
                        max_slots=slots, max_len=max_len_p,
                        prompt_buckets=(p_len,), max_queue=4 * slots,
                        page_size=page, kv_quant=quant)
                    eng = ServeEngine(pl.unstack_stages(stacked, manifest),
                                      cfg, scfg)
                    for _ in range(slots):
                        eng.submit(ServeRequest(
                            input_ids=prompt,
                            gen=GenerationConfig(max_new_tokens=budget)))
                    eng.step()  # admissions + first tick (compiles)
                    t0 = time.perf_counter()
                    for _ in range(decode_steps):
                        eng.step()
                    dt = (time.perf_counter() - t0) / decode_steps
                    detail = {
                        "per_token_ms": round(1000 * dt / slots, 3),
                        "step_ms": round(1000 * dt, 2), "slots": slots,
                        "page_size": page,
                        "pages_used": eng.slots.pages_used,
                        "pages_total": eng.slots.num_pages,
                        "pool_mib": round(paged_pool_bytes(
                            cfg, scfg.resolved_num_pages, page,
                            quant) / (1 << 20), 2),
                        "dense_cache_mib": round(dense_mib, 2),
                        "kv_quant": quant}
                    tag = "-int8" if quant == "int8" else ""
                    results[f"extra:serve-paged{tag}-decode,bs={slots}"] = {
                        "dt": dt, "tokens_per_step": slots,
                        "headline": False, "detail": detail}
                    eng.shutdown()
            except Exception as e:
                failed("paged serving rows", e)

            # Chunked-prefill row: the synthetic traffic generator
            # (tools/serve_traffic.py — Poisson arrivals, prompt/output
            # length mixes) replayed against a paged engine with a bounded
            # per-tick prefill budget; the row's metadata records the mix
            # that generated the load, and the SLO percentiles are what
            # interleaved admissions cost in-flight decodes.
            try:
                sys.path.insert(0, os.path.join(os.path.dirname(
                    os.path.abspath(__file__)), "tools"))
                import serve_traffic as _tr

                p_small = max(16, min(64, seq) // 16 * 16)  # page-aligned
                chunk = p_small
                max_len_t = 4 * p_small
                prompt_mix = _tr.parse_mix(f"{p_small}:0.6,{2 * p_small}:0.4")
                output_mix = _tr.parse_mix("8:0.5,16:0.5")
                rate = float(os.environ.get("BENCH_TRAFFIC_RATE", "16"))
                n_req = int(os.environ.get("BENCH_TRAFFIC_REQUESTS", "12"))
                eng = ServeEngine(
                    pl.unstack_stages(stacked, manifest), cfg,
                    ServeConfig(
                        max_slots=4, max_len=max_len_t,
                        prompt_buckets=(p_small, 2 * p_small),
                        max_queue=4 * n_req,
                        page_size=16, prefill_chunk_tokens=chunk))
                trace_reqs = _tr.poisson_trace(0, rate, n_req, prompt_mix,
                                               output_mix)
                summary = _tr.run_trace(eng, trace_reqs)
                eng.shutdown()
                results["extra:serve-prefill-chunked"] = {
                    "dt": summary["wall_s"],
                    "tokens_per_step": summary.get("tokens_generated", 0),
                    "headline": False, "detail": {
                        "mix": {"prompt": _tr.mix_label(prompt_mix),
                                "output": _tr.mix_label(output_mix),
                                "rate_rps": rate, "seed": 0,
                                "requests": n_req},
                        "chunk_tokens": chunk, **{
                            k: summary[k] for k in (
                                "requests_completed", "refused_pages",
                                "refused_overload", "tokens_per_sec",
                                "prefill_chunks_total",
                                "prefill_tokens_total")
                            if k in summary},
                        **{k: summary[k] for k in summary
                           if k.startswith(("ttft_", "tpot_"))}}}
            except Exception as e:
                failed("prefill traffic row", e)

            # Prefix-cache rows (docs/SERVING.md "Prefix caching"): the
            # SAME 90%-shared-prefix mix replayed twice — cache on
            # (`extra:serve-prefix-hot`) vs off (`-cold`) — so one run
            # lands the cache-hit TTFT win as a measured delta, plus the
            # capacity story: how many same-prefix requests a FIXED page
            # pool admits (queued, never stepped, until 429) under page
            # sharing vs without it. Separate try per the extras posture.
            try:
                from llama_pipeline_parallel_tpu.serve import ServeOverloaded

                page = 16
                tail = page
                bucket = max(2 * page, min(64, seq) // page * page)
                pre_len = bucket - tail
                prefix_mix = _tr.parse_prefix_mix(
                    f"sys{pre_len}:0.9,cold:0.1")
                prompt_mix_p = _tr.parse_mix(f"{tail}:1.0")
                output_mix_p = _tr.parse_mix("8:1.0")
                rate = float(os.environ.get("BENCH_TRAFFIC_RATE", "16"))
                n_req = int(os.environ.get("BENCH_TRAFFIC_REQUESTS", "12"))
                pool_pages = 4 * bucket // page  # fixed, deliberately tight
                shared = _tr.prefix_ids(f"sys{pre_len}", pre_len,
                                        cfg.vocab_size)

                def prefix_req(sd):
                    tail_ids = np.random.RandomState(sd).randint(
                        3, cfg.vocab_size, size=tail).tolist()
                    return ServeRequest(
                        input_ids=shared + tail_ids,
                        gen=GenerationConfig(max_new_tokens=8), seed=sd)

                for label, cache_on in (("hot", True), ("cold", False)):
                    eng = ServeEngine(
                        pl.unstack_stages(stacked, manifest), cfg,
                        ServeConfig(max_slots=4, max_len=bucket + page,
                                    prompt_buckets=(tail, bucket),
                                    max_queue=4 * n_req,
                                    page_size=page, prefix_cache=cache_on))
                    # pay every compile off the clock (full prefill at
                    # both buckets, and — hot — the warm span path), and
                    # leave the shared chain registered so the trace's
                    # first hot request is already a hit; without this the
                    # hot row measures XLA compiles, not the cache
                    for wr in (prefix_req(0), prefix_req(10_000),
                               ServeRequest(
                                   input_ids=list(range(3, 3 + tail)),
                                   gen=GenerationConfig(max_new_tokens=8),
                                   seed=0)):
                        eng.submit(wr)
                        eng.drain(timeout_s=600)
                    trace_reqs = _tr.poisson_trace(
                        0, rate, n_req, prompt_mix_p, output_mix_p,
                        prefix_mix=prefix_mix)
                    s = _tr.run_trace(eng, trace_reqs)
                    eng.shutdown()
                    # admissions at a fixed pool: warm the cache with one
                    # drained request, then queue same-prefix requests
                    # without stepping until the pool refuses
                    eng = ServeEngine(
                        pl.unstack_stages(stacked, manifest), cfg,
                        ServeConfig(max_slots=4, max_len=bucket + page,
                                    prompt_buckets=(bucket,),
                                    max_queue=16 * pool_pages,
                                    page_size=page,
                                    num_pages=pool_pages,
                                    prefix_cache=cache_on))
                    eng.submit(prefix_req(1))
                    eng.drain(timeout_s=600)
                    admitted = 0
                    try:
                        for sd in range(2, 2 + 16 * pool_pages):
                            eng.submit(prefix_req(sd))
                            admitted += 1
                    except ServeOverloaded:
                        pass
                    eng.shutdown()
                    ttft_p50 = s.get("ttft_p50_ms")
                    results[f"extra:serve-prefix-{label}"] = {
                        "dt": (ttft_p50 or 0) / 1000.0,
                        "tokens_per_step": s.get("tokens_generated", 0),
                        "headline": False, "detail": {
                            "mix": {"prompt": _tr.mix_label(prompt_mix_p),
                                    "output": _tr.mix_label(output_mix_p),
                                    "prefix": _tr.prefix_mix_label(
                                        prefix_mix),
                                    "rate_rps": rate, "seed": 0,
                                    "requests": n_req},
                            "prefix_cache": cache_on,
                            "admitted_at_fixed_pool": admitted,
                            "pool_pages": pool_pages, "page_size": page,
                            **{k: s[k] for k in (
                                "requests_completed", "refused_pages",
                                "prefix_hits", "prefix_misses",
                                "prefix_hit_rate", "prefix_cached_tokens",
                                "prefix_cow_forks") if k in s},
                            **{k: s[k] for k in s
                               if k.startswith(("ttft_", "tpot_"))}}}
            except Exception as e:
                failed("prefix cache rows", e)

    summary = report()
    print(json.dumps(summary), flush=True)
    _write_ledger(cli.perf_ledger, summary, row_budget.skipped)

    # BENCH_PROFILE=<dir>: afterwards, capture a profiler trace of the
    # winning config's steady state — the per-op breakdown for the MFU hunt
    # (SURVEY.md §5.1). Separate from the timed runs above.
    profile_dir = os.environ.get("BENCH_PROFILE")
    if profile_dir:
        best = summary["best_config"]
        measure(*configs[best], trace_dir=profile_dir)
        print(f"profiler trace for {best} written to {profile_dir}",
              file=sys.stderr, flush=True)
    if failures:
        sys.exit(f"bench: {len(failures)} non-headline row(s) failed: "
                 f"{', '.join(failures)}")


if __name__ == "__main__":
    main()
