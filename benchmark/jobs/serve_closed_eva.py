"""Job `serve_closed_eva`: `serve_closed_latent` for a configuration of the
compressed-window family (models/eva/: every layer reads the exact keys of
its query's window and one pooled key and value a chunk of every earlier
window, in one softmax; a dense feed-forward; no experts). The same
`ServeEngine` under the same closed-loop clients, the same ramp, window and
chunked prefill, the same client-side end-to-end numbers.

What it shares with `serve_closed_latent` it takes by loading that module,
as `serve_closed_mla` does: its `_drive` (set-up, ramp, window, trace, what
is resident), `serve_config`, `_Client`, `warm_up`, `sample_finished`. A
loaded job is a module object of this job's own, so four of its names are set
here before `_drive` runs: `model_config` (an `EvaConfig` from the published
keys), the weights' module (`benchmark/eva_weights.py`), `replay_selection`
(nothing to replay: no layer selects) and `build_engine`, whose engine is
handed to `_drive` behind a view that shows the pool's `k` and `v` leaves
under the two names `_drive` reads for its line on what is resident
(`latent`: both leaves' bytes; `index`: none). `run` is this job's: the plain
reference is `benchmark/reference/eva_decoder.py` and the checks are this
family's (PERF.md "Open questions" lists the jobs for the benchmark PR that
folds them).

`correct`. The gap by which a served token's reference logit lies below the
reference's best, over a seeded sample of three finished requests, the
longest among them: the MEAN over the sample's served tokens against
`served_logit_gap_mean` (the widest is printed). Beyond that, exact counts
of the program's own counters over every tick of the run:
`eva_window_visible` and `eva_summary_visible` of the `serve_decode_step`
spans against the host's sums over decoded rows of `(p mod W + 1) x layers`
and `(p // W) x (W / C) x layers`, from the lengths alone (each request's
prompt and the tokens its client received, plus the warm-up's): every layer
reads every exact entry of the row's window and every pooled entry of its
earlier windows, no more and no fewer.

Two controls of the first comparison are committed with it, chosen by
`SERVE_CLOSED_EVA_CONTROL` in the environment; either way the run is the same
run, but the gaps are those of the tokens ANOTHER computation puts first,
read at the served tokens' positions against the float32 reference:
- `fp8`: the reference with its matrix products in float8, the nearest
  precision below the bfloat16 the configuration states;
- `no_summaries`: the reference with the set of summaries left EMPTY, what a
  program that dropped the pooled entries would compute.
Such a run must come out `correct: false` by `served_logit_gap_mean` and by
no other check (PERF.md has the readings). The driver's runs do not set the
variable.
"""

from __future__ import annotations

import gc
import itertools
import os
import time

from benchmark import eva_weights, eva_work, registry, stats
from benchmark.harness import Check

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONTROL_ENV = "SERVE_CLOSED_EVA_CONTROL"     # unset: the served tokens' gaps
CONTROLS = {"fp8": ("fp8", ()), "no_summaries": ("float32", ("no_summaries",))}


def model_config(cell):
    import jax.numpy as jnp

    from llama_pipeline_parallel_tpu.models.eva.config import EvaConfig

    return EvaConfig.from_published(
        cell.config,
        dtype=jnp.dtype(cell.config["compute_dtype"]).type,
        param_dtype=jnp.dtype(cell.config["weights_dtype"]).type)


# an object with some of its attributes computed here instead: the wrapper
# `serve_closed_mla` shows `_drive` its engine through
_View = registry.load_job(ROOT, "serve_closed_mla")._View


class _Bytes:
    def __init__(self, nbytes: int):
        self.nbytes = nbytes


def _shared():
    """`serve_closed_latent`, loaded for this job and given this family's
    configuration and weights, no replay and an engine whose pool shows its
    pages under the names `_drive` prints."""
    job = registry.load_job(ROOT, "serve_closed_latent")

    def build_engine(ctx, params):
        from llama_pipeline_parallel_tpu import serve

        engine = serve.ServeEngine(params, model_config(ctx.cell),
                                   job.serve_config(ctx.cell))
        pages = lambda: {
            **engine.slots.pool,
            "latent": _Bytes(sum(x.nbytes
                                 for x in engine.slots.pool.values())),
            "index": _Bytes(0)}
        slots = _View(engine.slots, pool=pages)
        return _View(engine, slots=lambda: slots)

    job.model_config = model_config
    job.latent_moe_weights = eva_weights
    job.replay_selection = lambda ctx, params, sample: []
    job.build_engine = build_engine
    return job


_latent = _shared()
serve_config = _latent.serve_config


def reference_gaps(ctx, sample: list, precision: str = "float32",
                   alter: tuple = ()) -> list:
    """Per sampled request the gaps of its served tokens: the reference's
    `served_token_gaps` over the sample. The weights are made anew from the
    seed, in the dtype the engine held them, then widened: the same
    values. `precision` and `alter` are the controls'."""
    import jax.numpy as jnp

    from benchmark.reference import eva_decoder

    if not sample:
        return []
    model = ctx.cell.model
    params = eva_weights.make_reference_weights(
        ctx.seed % (2 ** 32), model,
        jnp.dtype(ctx.cell.config["weights_dtype"]).type)
    return [eva_decoder.served_token_gaps(
        params, r["request"]["prompt"], r["tokens"], model,
        ctx.cell.params["engine"]["max_len"], precision, alter=alter)
        for r in sample]


def run(ctx) -> dict:
    cell = ctx.cell
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
    resident = driven["resident"]
    print(f"serve: window={t1 - t0:.3f}s submitted={len(submitted)} "
          f"finished={len(finished)} failed={len(failed)} tokens={tokens} "
          f"engine completed={snapshot['requests_completed']} rejected="
          f"{snapshot['requests_rejected']}; resident: weights "
          f"{resident['weights_bytes']} B, page pool (window and summary "
          f"pages, one pool) {resident['latent_pages_bytes']} B", flush=True)
    print(f"serve: {tokens / (t1 - t0):.2f} tokens/s (a note: "
          f"serve_tokens_per_s); gap between tokens over {len(tpot)} finished "
          f"requests: p50 {1e3 * stats.percentile(tpot, 50):.2f} ms, p90 "
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

    # -- the program's own counts, over every tick of the run ----------------
    ticks = eva_work.counted_spans({"spans": spans}, "serve_decode_step")
    seen_w = sum(s[eva_work.WINDOW] for s in ticks)
    seen_s = sum(s[eva_work.SUMMARY] for s in ticks)
    want_w, want_s = eva_work.host_visible(
        records, cell.params["engine"]["prompt_buckets"], cell.model)
    off_w = abs(seen_w - want_w) if ticks else float("inf")
    off_s = abs(seen_s - want_s) if ticks else float("inf")
    rows = sum(s["tokens"] for s in ticks)
    units = eva_work.counted_spans({"spans": spans}, "serve_prefill")
    print(f"serve: read: {sum(s['ticks'] for s in ticks)} ticks, {rows} "
          f"decoded rows read {seen_w} exact entries (host's count {want_w}) "
          f"and {seen_s} pooled ones (host's count {want_s}), "
          f"{(seen_w + seen_s) / max(rows * cell.model['num_hidden_layers'], 1):.0f}"
          f" a row and layer; summaries written: "
          f"{sum(s[eva_work.WRITTEN] for s in ticks)} by ticks, "
          f"{sum(s[eva_work.WRITTEN] for s in units)} by {len(units)} prefill "
          f"units", flush=True)

    # -- what accepted readers would report here, printed as notes: their
    # `workloads` lists are held by tests to the cells they have
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
    control = os.environ.get(CONTROL_ENV, "")
    precision, alter = CONTROLS[control] if control else ("float32", ())
    if control:
        print(f"serve: CONTROL ({CONTROL_ENV}={control}): the gaps below are "
              f"those of the first choices of the reference computed with "
              f"precision={precision} alter={alter}, not of the served "
              f"tokens; this run has to come out not correct", flush=True)
    gaps = reference_gaps(ctx, sample, precision, alter)
    flat = list(itertools.chain.from_iterable(gaps))
    mean_gap = sum(flat) / len(flat) if flat else float("inf")
    print(f"serve: reference ran {len(sample)} requests "
          f"({[len(r['request']['prompt']) for r in sample]} prompt tokens), "
          f"{len(flat)} served tokens, in {time.time() - t_ref:.1f}s (not in "
          f"setup_s); mean gap {mean_gap}, {sum(1 for g in flat if g > 0)} "
          f"tokens off the reference's first choice, widest gap "
          f"{max(flat, default=float('inf'))}; by request "
          f"{[sum(g) / max(len(g), 1) for g in gaps]}", flush=True)

    checks = [
        Check("served_logit_gap_mean", float(mean_gap),
              cell.params["checks"]["served_logit_gap_mean"]),
        Check("eva_window_visible_off_host_count", float(off_w), 0.0),
        Check("eva_summary_visible_off_host_count", float(off_s), 0.0),
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
