"""Job `serve_closed_ssm`: `serve_closed_hybrid` for a configuration of the
state-space / expert family (models/ssm_moe/: every layer one of a Mamba-2
mixer, a grouped-query softmax layer or a latent expert feed-forward of
which this chip holds a range). The same `ServeEngine` under the same
closed-loop clients, the same ramp and window, the same client-side
end-to-end numbers.

What it shares with `serve_closed_hybrid` it takes by loading that module, as
`serve_closed_mla` takes the latent job's: its `_drive` (set-up, ramp, window,
trace, what is resident), `build_engine`, `_Client`, `warm_up`,
`sample_finished`. A loaded job is a module object of this job's own, so two
of its names are set here before `_drive` runs: `model_config` (an
`SsmMoEConfig` from the published keys) and the weights' module
(`benchmark/ssm_moe_weights.py`). `run` is this job's: the plain reference is
`benchmark/reference/ssm_moe_decoder.py` and the checks are this family's
(PERF.md "Open questions" lists the jobs for the benchmark PR that folds
them).

`correct`. The gap by which a served token's reference logit lies below the
reference's best, over a seeded sample of three finished requests, the
longest among them: the MEAN over the sample's served tokens against
`served_logit_gap_mean`, and the WIDEST against `served_logit_gap` where the
cell's file gives that limit too (each with its readings there). Beyond
that, two exact counts of the program's own counters over every
`serve_decode_step` span of the run against the host's count of the rows
that decoded (`tokens`): `routed_total` is that x experts a token x expert
layers, `ssm_rows` that x state-space layers.

The control of the first comparison is committed with it: with
`SERVE_CLOSED_SSM_CONTROL=fp8` in the environment the run is the same run,
but the gaps are those of the tokens the reference puts first when its
matrix products are computed in float8, the nearest precision below the
bfloat16 the configuration states, read at the served tokens' positions: the
float8 reference in the program's place. Such a run must come out `correct:
false` by `served_logit_gap_mean` and by no other check (PERF.md has the
readings). The driver's runs do not set the variable.
"""

from __future__ import annotations

import gc
import itertools
import os
import time

from benchmark import hybrid_scopes, registry, ssm_moe_weights, stats
from benchmark.harness import Check

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONTROL_ENV = "SERVE_CLOSED_SSM_CONTROL"     # unset: the served tokens' gaps


def model_config(cell):
    import jax.numpy as jnp

    from llama_pipeline_parallel_tpu.models.ssm_moe.config import SsmMoEConfig

    return SsmMoEConfig.from_published(
        cell.config,
        dtype=jnp.dtype(cell.config["compute_dtype"]).type,
        param_dtype=jnp.dtype(cell.config["weights_dtype"]).type)


def _shared():
    """`serve_closed_hybrid`, loaded for this job and given this family's
    configuration and weights."""
    job = registry.load_job(ROOT, "serve_closed_hybrid")
    job.model_config = model_config
    job.hybrid_moe_weights = ssm_moe_weights
    return job


_hybrid = _shared()
sample_finished = _hybrid.sample_finished


def reference_gaps(ctx, sample: list, precision: str = "float32") -> list:
    """Per sampled request the gaps of its served tokens (the reference's
    `served_token_gaps`), all requests in one batch so that each layer's
    weights are made once. The weights are made anew from the seed, in the
    dtype the engine held them, then widened: the same values."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import ssm_moe_decoder

    if not sample:
        return []
    model = ctx.cell.model
    dtype = jnp.dtype(ctx.cell.config["weights_dtype"]).type
    seed = ctx.seed % (2 ** 32)
    top = jax.tree.map(lambda x: x.astype(jnp.float32),
                       ssm_moe_weights.make_top(seed, model, dtype))
    return ssm_moe_decoder.served_token_gaps(
        top, ssm_moe_weights.layer_fn(seed, model, dtype),
        [r["request"]["prompt"] for r in sample],
        [r["tokens"] for r in sample], model,
        ctx.cell.params["engine"]["max_len"], precision)


def run(ctx) -> dict:
    from benchmark import device

    cell = ctx.cell
    vocab = cell.model["vocab_size"]
    driven = _hybrid._drive(ctx)
    gc.collect()
    records, spans, snapshot, alive = (driven[k] for k in (
        "records", "spans", "snapshot", "alive"))
    t0, t1 = driven["window"]

    # -- the client's side of the window (as serve_closed.run) ---------------
    in_window = lambda t: t0 <= t <= t1
    tokens = sum(1 for r in records for t in r["token_times"] if in_window(t))
    submitted = [r for r in records if in_window(r["t_submit"])]
    failed = [r for r in submitted if r["status"] == "failed"]
    ttft = [(r["token_times"][0] - r["t_submit"]) if r["token_times"]
            else float("inf")
            for r in submitted
            if r["token_times"] or r["status"] == "failed"]
    finished = [r for r in records
                if r["status"] == "done" and in_window(r["token_times"][-1])]
    tpot = [(r["token_times"][-1] - r["token_times"][0]) / (len(r["tokens"]) - 1)
            for r in finished if len(r["tokens"]) > 1]
    short = [r for r in finished
             if len(r["tokens"]) != r["request"]["max_new_tokens"]]
    outside = [t for r in finished for t in r["tokens"] if not 0 <= t < vocab]
    print(f"serve: window={t1 - t0:.3f}s submitted={len(submitted)} "
          f"finished={len(finished)} failed={len(failed)} tokens={tokens} "
          f"engine completed={snapshot['requests_completed']} rejected="
          f"{snapshot['requests_rejected']}; resident {driven['resident']}",
          flush=True)
    print(f"serve: {tokens / (t1 - t0):.2f} tokens/s; gap between tokens over "
          f"{len(tpot)} finished requests: p50 "
          f"{1e3 * stats.percentile(tpot, 50):.2f} ms, p90 "
          f"{1e3 * stats.percentile(tpot, 90):.2f} ms", flush=True)
    window_spans = [s for s in spans if in_window(s["ts"])]
    by_name = lambda n: [s["dur"] for s in window_spans if s["name"] == n]
    prefills, decodes = by_name("serve_prefill"), by_name("serve_decode_step")
    print(f"serve: prefills {len(prefills)} in {sum(prefills):.3f}s (longest "
          f"{max(prefills, default=0.0):.3f}s); decode spans "
          f"{sum(decodes):.3f}s (longest {max(decodes, default=0.0):.3f}s)",
          flush=True)

    # -- the program's own counts, over every span of the run ----------------
    counted = hybrid_scopes.counter_sums({"spans": spans})
    pattern = cell.model["hybrid_override_pattern"]
    topk = cell.model["num_experts_per_tok"]
    ssm_rows = sum(s.get("ssm_rows", 0) for s in spans
                   if s["name"] == "serve_decode_step")
    miscount = ssm_miscount = float("inf")
    if counted:
        miscount = abs(counted["routed_total"]
                       - counted["tokens"] * topk * pattern.count("E"))
        ssm_miscount = abs(ssm_rows - counted["tokens"] * pattern.count("M"))
    if counted and counted["routed_here"]:
        print(f"serve: experts: {counted['ticks']} ticks, {counted['tokens']} "
              f"decoded tokens, routed_total {counted['routed_total']}, "
              f"routed_here {counted['routed_here']} "
              f"({100.0 * counted['routed_here'] / counted['routed_total']:.3f}% "
              f"of the router), held experts hit "
              f"{100.0 * counted['experts_hit'] / counted['experts_held']:.1f}%, "
              f"largest load over mean "
              f"{hybrid_scopes.load_max_over_mean(counted, cell.model['n_routed_experts']):.2f}; "
              f"state-space rows {ssm_rows}", flush=True)

    # -- free the program's state, read the peak, the notes, the reference ---
    xplane_trace = None
    if ctx.trace:
        from benchmark import xplane

        path = xplane.find_xplane(os.path.join(ctx.run_dir, "profile"))
        xplane_trace = xplane.read(path) if path else None
    memory_peak = device.memory_peak_bytes(ctx.devices)
    sample = sample_finished(finished, ctx.seed, cell.params["check_requests"])
    observations = {
        "kind": "serve", "cell": cell, "devices": ctx.devices,
        "window": (t0, t1), "spans": window_spans,
        "xplane": xplane_trace, "finished": len(finished),
        "client": {"ttft_s": ttft, "tpot_s": tpot},
        "check_sample": sample,
        "tokens_per_s": tokens / (t1 - t0)}
    if ctx.trace:
        # what accepted readers would report here, printed as notes: their
        # `workloads` lists are held by tests to the cells they have
        for name in cell.params.get("notes_from", ()):
            value = registry.load_layer_metric(ctx.root, name).read(observations)
            print(f"serve: note {name} = {value}", flush=True)

    t_ref = time.time()
    precision = os.environ.get(CONTROL_ENV, "float32")
    if precision != "float32":
        print(f"serve: CONTROL ({CONTROL_ENV}={precision}): the gaps below are "
              f"those of the {precision} reference's first choices, not of the "
              f"served tokens; this run has to come out not correct",
              flush=True)
    gaps = reference_gaps(ctx, sample, precision)
    flat = list(itertools.chain.from_iterable(gaps))
    mean_gap = sum(flat) / len(flat) if flat else float("inf")
    widest = max(flat, default=float("inf"))
    print(f"serve: reference ran {len(sample)} requests "
          f"({[len(r['request']['prompt']) for r in sample]} prompt tokens, "
          f"{[len(r['tokens']) for r in sample]} served), in "
          f"{time.time() - t_ref:.1f}s (not in setup_s); mean gap {mean_gap}, "
          f"by request {[sum(g) / len(g) for g in gaps if g]}, "
          f"{sum(1 for g in flat if g > 0)} of {len(flat)} tokens off the "
          f"reference's first choice, widest gap {widest}", flush=True)

    limits = cell.params["checks"]
    checks = [Check("served_logit_gap_mean", float(mean_gap),
                    limits["served_logit_gap_mean"])]
    if "served_logit_gap" in limits:
        checks.append(Check("served_logit_gap", float(widest),
                            limits["served_logit_gap"]))
    checks += [
        Check("routed_total_off_tokens_x_topk_x_layers", float(miscount), 0.0),
        Check("ssm_rows_off_tokens_x_layers", float(ssm_miscount), 0.0),
        Check("requests_failed_or_refused",
              float(len(failed) + snapshot["requests_rejected"]
                    + snapshot["requests_failed"]), 0.0),
        Check("finished_with_wrong_token_count", float(len(short)), 0.0),
        Check("token_ids_outside_vocabulary", float(len(outside)), 0.0),
        Check("client_threads_left", float(len(alive)), 0.0),
    ]
    return {
        "end_to_end": {
            "serve_tokens_per_s": tokens / (t1 - t0),
            "serve_tpot_ms_p90": 1e3 * stats.percentile(tpot, 90),
            "setup_s": t0 - ctx.t_start},
        "attempted": len(submitted), "failed": len(failed),
        "checks": checks, "window": (t0, t1),
        "memory_peak_bytes": memory_peak,
        "observations": observations,
    }
