"""Job `serve_closed_window`: `serve_closed_hybrid` for a configuration of the
window / full softmax family (models/window_moe/: grouped-query layers of
two kinds in a published order, pages for the full layers and a ring a slot
for the window layers, sigmoid-routed experts of which this chip holds a
range and nothing beside the routed sum). The same `ServeEngine` under the
same closed-loop clients, the same ramp and window, the same client-side
end-to-end numbers; the engine prefills in chunks (`prefill_chunk_tokens` in
the cell's file): a bucket no larger than the chunk whole, larger ones a
chunk a step before the tick. Both serving numbers are returned; which of
them the cell reports is `BENCHMARK.json`'s to say (tokens a second, as the
cell's issue fixed it; the gap between tokens is printed beside it).

What it shares with `serve_closed_hybrid` it takes by loading that module, as
`serve_closed_ssm` does: its `_drive` (set-up, ramp, window, trace),
`build_engine`, `_Client`, `warm_up`, `sample_finished`. A loaded job is a
module object of this job's own, so two of its names are set here before
`_drive` runs: `model_config` (a `WindowMoEConfig` from the published keys)
and the weights' module (`benchmark/window_moe_weights.py`). `run` is this
job's: the plain reference is `benchmark/reference/window_moe_decoder.py`
and the checks are this family's (PERF.md "Open questions" lists the jobs
for the benchmark PR that folds them).

A third name is set on the loaded job: `traffic`, so that `_drive` draws its
requests from `scheduled_stream` below. Where the mix states a
`schedule_seed`, every run serves the SAME schedule (the generator's own
draw at that seed: which class comes when, each prompt's length inside its
class, each answer's length) and only the token ids, the sampling seeds and
the weights follow `--seed`. A window of 30 s finishes some 55 of this
cell's requests, under three blocks of 20, and one prompt of the longest
class is sixteen steps of chunk and tick, 4% of the window: with the
schedule drawn from `--seed` too, the seed decided how much work a run did
(tokens a second spread by 4 to 5%, PERF.md). A mix without the key is
served as the generator draws it.

`correct`. The gap by which a served token's reference logit lies below the
reference's best, over a seeded sample of three finished requests, the
longest among them (so a long prompt through the chunks and the ring's
wrap): the MEAN over the sample's served tokens against
`served_logit_gap_mean`, and the WIDEST against `served_logit_gap` where the
cell's file gives that limit too (each with its readings there). Beyond
that, three exact counts of the program's own counters over every
`serve_decode_step` span of the run against the host's count from the
requests' lengths alone (each request's prompt and the tokens its client
received, plus the warm-up's): `routed_total` is the decoded rows x experts
a token x expert layers; `window_entries_read` the sum over decoded rows of
min(context, window) x window layers; `full_entries_read` the sum of their
contexts x full layers.

The control of the first comparison is committed with it: with
`SERVE_CLOSED_WINDOW_CONTROL=fp8` in the environment the run is the same run,
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
import types

import numpy as np

from benchmark import (
    hybrid_scopes,
    registry,
    stats,
    traffic,
    window_moe_weights,
    window_work,
)
from benchmark.harness import Check

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONTROL_ENV = "SERVE_CLOSED_WINDOW_CONTROL"  # unset: the served tokens' gaps
REFERENCE_PAD = 1024      # a request's reference length is a multiple of this


def model_config(cell):
    import jax.numpy as jnp

    from llama_pipeline_parallel_tpu.models.window_moe.config import (
        WindowMoEConfig,
    )

    return WindowMoEConfig.from_published(
        cell.config,
        dtype=jnp.dtype(cell.config["compute_dtype"]).type,
        param_dtype=jnp.dtype(cell.config["weights_dtype"]).type)


def scheduled_stream(mix: dict, seed: int, vocab_size: int):
    """`traffic.request_stream` with the work of a run taken out of the
    seed's hands: the requests are the generator's at the mix's
    `schedule_seed` (classes in their order, prompt and answer lengths), and
    request `i` gets its token ids and its sampling seed from (`seed`, `i`).
    Without a `schedule_seed` the generator's stream at `seed`, untouched."""
    if "schedule_seed" not in mix:
        yield from traffic.request_stream(mix, seed, vocab_size)
        return
    schedule = traffic.request_stream(mix, mix["schedule_seed"], vocab_size)
    for index, request in enumerate(schedule):
        rng = np.random.default_rng([seed, index])
        yield {**request,
               "prompt": rng.integers(0, vocab_size,
                                      size=len(request["prompt"]),
                                      dtype=np.int32).tolist(),
               "seed": int(rng.integers(0, 2 ** 31 - 1))}


def _shared():
    """`serve_closed_hybrid`, loaded for this job and given this family's
    configuration, weights and stream of requests."""
    job = registry.load_job(ROOT, "serve_closed_hybrid")
    job.model_config = model_config
    job.hybrid_moe_weights = window_moe_weights
    job.traffic = types.SimpleNamespace(request_stream=scheduled_stream)
    return job


_hybrid = _shared()
sample_finished = _hybrid.sample_finished


def reference_gaps(ctx, sample: list, precision: str = "float32") -> list:
    """Per sampled request the gaps of its served tokens (the reference's
    `served_token_gaps`): each request at its own length, one at a time
    inside a layer, so that each layer's weights are made once. The weights
    are made anew from the seed, in the dtype the engine held them, then
    widened: the same values."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import window_moe_decoder

    if not sample:
        return []
    model = ctx.cell.model
    dtype = jnp.dtype(ctx.cell.config["weights_dtype"]).type
    seed = ctx.seed % (2 ** 32)
    top = jax.tree.map(lambda x: x.astype(jnp.float32),
                       window_moe_weights.make_top(seed, model, dtype))
    return window_moe_decoder.served_token_gaps(
        top, window_moe_weights.layer_fn(seed, model, dtype),
        [r["request"]["prompt"] for r in sample],
        [r["tokens"] for r in sample], model, REFERENCE_PAD, precision)


def page_pool_bytes(cell) -> int:
    """Bytes of the full layers' pages, by shape (keys are stored wider than
    values: the shared `_drive` reckons a pool of two equal leaves)."""
    import jax

    from llama_pipeline_parallel_tpu.models.family import family_of

    cfg, engine = model_config(cell), cell.params["engine"]
    shapes = jax.eval_shape(lambda: family_of(cfg).init_page_pool(
        cfg, engine["num_pages"], engine["page_size"]))
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))


def pages_reserved_peak(records: list, engine: dict) -> int:
    """The most pages the requests of a run held reserved at once, from the
    records alone: a request reserves its worst case (its bucket and all its
    new tokens) from its submission to its last token."""
    page = engine["page_size"]
    events = []
    for r in records:
        bucket = next(b for b in engine["prompt_buckets"]
                      if b >= len(r["request"]["prompt"]))
        demand = -(-(bucket + r["request"]["max_new_tokens"]) // page)
        end = (r["token_times"][-1] if r["status"] == "done"
               else float("inf"))       # cut by the run's end: held till then
        events += [(r["t_submit"], demand), (end, -demand)]
    held = peak = 0
    for _, change in sorted(events):
        held += change
        peak = max(peak, held)
    return peak


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
    engine = cell.params["engine"]
    resident = {**driven["resident"], "page_pool_bytes": page_pool_bytes(cell)}
    print(f"serve: window={t1 - t0:.3f}s submitted={len(submitted)} "
          f"finished={len(finished)} failed={len(failed)} tokens={tokens} "
          f"engine completed={snapshot['requests_completed']} rejected="
          f"{snapshot['requests_rejected']}; resident {resident}; pages "
          f"reserved at once, at most {pages_reserved_peak(records, engine)} "
          f"of {engine['num_pages']}", flush=True)
    first = min((r["t_submit"] for r in records), default=t0)
    print(f"serve: set-up {t0 - ctx.t_start:.1f}s: {first - ctx.t_start:.1f}s "
          f"to the first submission (weights, engine, every shape warmed), "
          f"then {t0 - first:.1f}s of ramp", flush=True)
    print(f"serve: {tokens / (t1 - t0):.2f} tokens/s; gap between tokens over "
          f"{len(tpot)} finished requests: p50 "
          f"{1e3 * stats.percentile(tpot, 50):.2f} ms, p90 "
          f"{1e3 * stats.percentile(tpot, 90):.2f} ms; prompts of the "
          f"finished: {sorted(len(r['request']['prompt']) for r in finished)}",
          flush=True)
    window_spans = [s for s in spans if in_window(s["ts"])]
    by_name = lambda n: [s for s in window_spans if s["name"] == n]
    prefills, decodes = by_name("serve_prefill"), by_name("serve_decode_step")
    chunks = [s for s in prefills if s["chunk"] < s["bucket"]]
    print(f"serve: prefill units {len(prefills)} in "
          f"{sum(s['dur'] for s in prefills):.3f}s ({len(chunks)} of them "
          f"chunks of larger buckets; longest "
          f"{max((s['dur'] for s in prefills), default=0.0):.3f}s); decode "
          f"spans {sum(s['dur'] for s in decodes):.3f}s over "
          f"{sum(s['ticks'] for s in decodes)} ticks", flush=True)

    # -- the program's own counts, over every span of the run ----------------
    counted = hybrid_scopes.counter_sums({"spans": spans})
    read = window_work.counter_sums({"spans": spans})
    sz = window_work.sizes(cell.model)
    topk = cell.model["num_experts_per_tok"]
    expert_layers = sum(cell.model["moe_layer_freq"])
    miscount = window_off = full_off = float("inf")
    if counted and read:
        miscount = abs(counted["routed_total"]
                       - counted["tokens"] * topk * expert_layers)
        want_window, want_full = window_work.host_entries(
            records, engine["prompt_buckets"], sz)
        window_off = abs(read[window_work.WINDOW_COUNTER] - want_window)
        full_off = abs(read[window_work.FULL_COUNTER] - want_full)
        rows = max(counted["tokens"], 1)
        print(f"serve: experts: {counted['ticks']} ticks, {counted['tokens']} "
              f"decoded tokens, routed_total {counted['routed_total']}, "
              f"routed_here {counted['routed_here']} "
              f"({100.0 * counted['routed_here'] / counted['routed_total']:.3f}% "
              f"of the router), held experts hit "
              f"{100.0 * counted['experts_hit'] / counted['experts_held']:.1f}%, "
              f"largest load over mean "
              f"{hybrid_scopes.load_max_over_mean(counted, cell.model['n_routed_experts']):.2f}; "
              f"attention: the ticks read {read[window_work.WINDOW_COUNTER]} "
              f"ring entries (host's count {want_window}) and "
              f"{read[window_work.FULL_COUNTER]} page entries (host's count "
              f"{want_full}): "
              f"{read[window_work.WINDOW_COUNTER] / rows / sz['window_layers']:.1f}"
              f" and "
              f"{read[window_work.FULL_COUNTER] / rows / sz['full_layers']:.0f} "
              f"a row and layer", flush=True)

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
        Check("window_entries_read_off_host_count", float(window_off), 0.0),
        Check("full_entries_read_off_host_count", float(full_off), 0.0),
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
