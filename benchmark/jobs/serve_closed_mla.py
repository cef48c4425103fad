"""Job `serve_closed_mla`: `serve_closed_latent` for a configuration of the
latent-attention family that has ONE kind of layer: plain multi-head latent
attention without indexer, window or gate, under YaRN, a leading dense layer
and sparse experts of which this chip holds a range. The same `ServeEngine`
under the same closed-loop clients, the same ramp, window and chunked
prefill, the same client-side end-to-end numbers.

What it shares with `serve_closed_latent` it takes by loading that module:
its `_drive` (set-up, ramp, window, trace, what is resident), `model_config`,
`serve_config`, `_Client`, `warm_up`, `sample_finished`. A loaded job is a
module object of this job's own, so three of its names are set here before
`_drive` runs: the weights' module (`benchmark/mla_moe_weights.py`, the
program's layout for a period of one layer), `replay_selection` (nothing to
replay: no layer selects) and `build_engine`, whose engine is handed to
`_drive` behind a view that shows an empty `index` leaf in the pool, which
`_drive` reads for its line on what is resident and this configuration does
not keep. `run` is this job's: the plain reference is
`benchmark/reference/mla_moe_decoder.py` and the checks are this family's
(PERF.md "Open questions" lists the three jobs for the benchmark PR that
folds them).

`correct`. The gap by which a served token's reference logit lies below the
reference's best, over a seeded sample of three finished requests, the
longest among them: the MEAN over the sample's served tokens against
`served_logit_gap_mean` and the WIDEST against `served_logit_gap` where the
cell's file gives that limit too (each with its readings there). Beyond
that, exact counts of the program's own counters over every span of the run:
- `routed_total` of the `serve_decode_step` spans is the host's decoded rows
  x experts a token x expert layers;
- `latent_visible` over every tick of the run is the sum over decoded rows
  of the positions the row can see, times the layers, from the lengths alone
  (each request's prompt and the tokens its client received, plus the
  warm-up's): every layer reads every visible position, no more and no
  fewer.

The control of the first comparison is committed with it: with
`SERVE_CLOSED_MLA_CONTROL=fp8` in the environment the run is the same run,
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

from benchmark import hybrid_scopes, mla_moe_weights, registry, stats
from benchmark.harness import Check

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONTROL_ENV = "SERVE_CLOSED_MLA_CONTROL"     # unset: the served tokens' gaps


class _View:
    """An object with some of its attributes computed here instead."""

    def __init__(self, obj, **computed):
        self.__dict__.update(_obj=obj, _computed=computed)

    def __getattr__(self, name):
        computed = self.__dict__["_computed"]
        if name in computed:
            return computed[name]()
        return getattr(self.__dict__["_obj"], name)


class _NoBytes:
    nbytes = 0


def _shared():
    """`serve_closed_latent`, loaded for this job and given this family's
    weights, no replay and an engine whose pool shows an empty `index`."""
    job = registry.load_job(ROOT, "serve_closed_latent")
    build = job.build_engine

    def build_engine(ctx, params):
        engine = build(ctx, params)
        slots = _View(engine.slots, pool=lambda: {
            **engine.slots.pool, "index": _NoBytes})
        return _View(engine, slots=lambda: slots)

    job.latent_moe_weights = mla_moe_weights
    job.replay_selection = lambda ctx, params, sample: []
    job.build_engine = build_engine
    return job


_latent = _shared()
model_config, serve_config = _latent.model_config, _latent.serve_config


def reference_gaps(ctx, sample: list, precision: str = "float32",
                   alter: tuple = ()) -> list:
    """Per sampled request the gaps of its served tokens: the reference's
    `served_token_gaps` over the sample. The weights are made anew from the
    seed, in the dtype the engine held them, then widened: the same
    values. `alter` reaches the reference's mixer (tests and controls)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import mla_moe_decoder

    if not sample:
        return []
    model = ctx.cell.model
    dtype = jnp.dtype(ctx.cell.config["weights_dtype"]).type
    seed = ctx.seed % (2 ** 32)
    top = jax.tree.map(lambda x: x.astype(jnp.float32),
                       mla_moe_weights.make_top(seed, model, dtype))
    return mla_moe_decoder.served_token_gaps(
        top, mla_moe_weights.layer_fn(seed, model, dtype),
        [r["request"]["prompt"] for r in sample],
        [r["tokens"] for r in sample], model,
        ctx.cell.params["engine"]["max_len"], precision, alter=alter)


def host_latent_visible(records: list, warm_buckets, layers: int) -> int:
    """What `latent_visible` must sum to over every tick of a run, from the
    lengths alone: a request of n prompt tokens whose client received m
    tokens went through m - 1 ticks, the j-th with n + j positions to see;
    each warm-up request (a prompt the bucket long, two tokens) through
    one."""
    total = sum(b + 1 for b in warm_buckets)
    for r in records:
        n, m = len(r["request"]["prompt"]), len(r["tokens"])
        total += (m - 1) * n + m * (m - 1) // 2 if m > 1 else 0
    return total * layers


def run(ctx) -> dict:
    cell, mix = ctx.cell, ctx.cell.mix
    vocab = cell.model["vocab_size"]
    driven = _latent._drive(ctx)
    gc.collect()
    records, spans, snapshot, alive, finished, sample = (driven[k] for k in (
        "records", "spans", "snapshot", "alive", "finished", "sample"))
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

    # both serving numbers, whichever of them BENCHMARK.json lists the cell
    # under (PERF.md has their spreads)
    print(f"serve: {tokens / (t1 - t0):.2f} tokens/s; gap between tokens over "
          f"{len(tpot)} finished requests: p50 "
          f"{1e3 * stats.percentile(tpot, 50):.2f} ms, p90 "
          f"{1e3 * stats.percentile(tpot, 90):.2f} ms", flush=True)
    window_spans = [s for s in spans if in_window(s["ts"])]
    stamps = sorted(t for r in records for t in r["token_times"] if in_window(t))
    arrival_gaps = [b - a for a, b in zip(stamps, stamps[1:])]
    by_name = lambda n: [s["dur"] for s in window_spans if s["name"] == n]
    prefills, decodes = by_name("serve_prefill"), by_name("serve_decode_step")
    print(f"serve: longest gap between token arrivals "
          f"{1e3 * max(arrival_gaps, default=0.0):.1f} ms, gaps over 0.5 s: "
          f"{sum(1 for g in arrival_gaps if g > 0.5)}; prefill units "
          f"{len(prefills)} in {sum(prefills):.3f}s (longest "
          f"{max(prefills, default=0.0):.3f}s); decode spans "
          f"{sum(decodes):.3f}s (longest {max(decodes, default=0.0):.3f}s)",
          flush=True)

    # -- the program's own counts, over every span of the run ----------------
    counted = hybrid_scopes.counter_sums({"spans": spans})
    layers = cell.model["num_hidden_layers"]
    expert_layers = layers - cell.model["first_k_dense_replace"]
    topk = cell.model["num_experts_per_tok"]
    miscount = (abs(counted["routed_total"]
                    - counted["tokens"] * topk * expert_layers)
                if counted else float("inf"))
    ticks = [s for s in spans if s["name"] == "serve_decode_step"
             and "latent_visible" in s]
    seen = sum(s["latent_visible"] for s in ticks)
    want_seen = host_latent_visible(
        records, cell.params["engine"]["prompt_buckets"], layers)
    visible_miscount = abs(seen - want_seen) if ticks else float("inf")
    if counted and counted["routed_here"]:
        print(f"serve: experts: {counted['ticks']} ticks, {counted['tokens']} "
              f"decoded tokens, routed_total {counted['routed_total']}, "
              f"routed_here {counted['routed_here']} "
              f"({100.0 * counted['routed_here'] / counted['routed_total']:.3f}% "
              f"of the router), held experts hit "
              f"{100.0 * counted['experts_hit'] / counted['experts_held']:.1f}%, "
              f"largest load over mean "
              f"{hybrid_scopes.load_max_over_mean(counted, cell.model['n_routed_experts']):.2f}; "
              f"dense read: the ticks saw {seen} positions (host's count "
              f"{want_seen}), {seen / max(counted['tokens'] * layers, 1):.0f} "
              f"a row and layer", flush=True)

    # -- what accepted readers would report here, printed as notes: their
    # `workloads` lists are held by tests to the cells they have (the latent
    # and expert readers to their one cell, the tick readers to end in the
    # other latent cell), and the engine's three move tokens/s, which this
    # cell does not report (PERF.md "Open questions")
    xplane_trace = None
    if ctx.trace:
        from benchmark import xplane

        path = xplane.find_xplane(os.path.join(ctx.run_dir, "profile"))
        xplane_trace = xplane.read(path) if path else None
    observations = {
        "kind": "serve", "cell": cell, "devices": ctx.devices,
        "window": (t0, t1), "spans": window_spans,
        "xplane": xplane_trace, "finished": len(finished),
        "client": {"ttft_s": ttft, "tpot_s": tpot},
        "check_sample": sample,
        "tokens_per_s": tokens / (t1 - t0)}
    if ctx.trace:
        for name in cell.params.get("notes_from", ()):
            value = registry.load_layer_metric(ctx.root, name).read(observations)
            print(f"serve: note {name} = {value}", flush=True)

    # -- the reference, over the sample ---------------------------------------
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
          f"({[len(r['request']['prompt']) for r in sample]} prompt tokens), "
          f"{len(flat)} served tokens, in {time.time() - t_ref:.1f}s (not in "
          f"setup_s); mean gap {mean_gap}, {sum(1 for g in flat if g > 0)} "
          f"tokens off the reference's first choice, widest gap {widest}",
          flush=True)

    limits = cell.params["checks"]
    checks = [Check("served_logit_gap_mean", float(mean_gap),
                    limits["served_logit_gap_mean"])]
    if "served_logit_gap" in limits:
        checks.append(Check("served_logit_gap", float(widest),
                            limits["served_logit_gap"]))
    checks += [
        Check("routed_total_off_tokens_x_topk_x_layers", float(miscount), 0.0),
        Check("latent_visible_off_host_count", float(visible_miscount), 0.0),
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
        "memory_peak_bytes": driven["memory_peak"],
        "observations": observations,
    }
