"""Job `serve_closed_granite`: `serve_closed_hybrid` for a configuration of
the dense state-space block (models/ssm_moe/ with a dense SwiGLU half a
layer: a Mamba-2 or a no-rope softmax mixer and its feed-forward, four
multipliers, the head tied to the table), served WHOLE on one chip. The same
`ServeEngine` under the same closed-loop clients, the same ramp and window,
the same client-side end-to-end numbers; the engine prefills in chunks
(`prefill_chunk_tokens` in the cell's file): a bucket no larger than the
chunk whole, larger ones a chunk a step before the tick, each carrying its
slot's recurrent state forward. Both serving numbers are returned; which of
them the cell reports is `BENCHMARK.json`'s to say (the gap between tokens,
as the cell's issue fixed it; tokens a second are printed beside it).

What it shares with `serve_closed_hybrid` it takes by loading that module, as
`serve_closed_window` does: its `_drive` (set-up, ramp, window, trace),
`build_engine`, `_Client`, `warm_up`, `sample_finished`. A loaded job is a
module object of this job's own, so three of its names are set here before
`_drive` runs: `model_config` (an `SsmMoEConfig` from the published
`granitemoehybrid` keys), the weights' module
(`benchmark/granite_hybrid_weights.py`) and `traffic`, so that `_drive` draws
its requests from `serve_closed_window`'s `scheduled_stream`: where the mix
states a `schedule_seed` every run serves the SAME schedule of lengths and
only the token ids, the sampling seeds and the weights follow `--seed`.
`run` is this job's: the plain reference is
`benchmark/reference/granite_hybrid_decoder.py` and the checks are this
family's (PERF.md "Open questions" lists the jobs for the benchmark PR that
folds them).

`correct`. The gap by which a served token's reference logit lies below the
reference's best, over a seeded sample of three finished requests, the
longest among them (so a long prompt through its chunks): the MEAN over the
sample's served tokens against `served_logit_gap_mean`, and the WIDEST
against `served_logit_gap` where the cell's file gives that limit too (each
with its readings there). Beyond that, exact counts of the program's own
counters against the host's. Over every `serve_decode_step` span of the
run, from the requests' lengths alone (each request's prompt and the tokens
its client received, plus the warm-up's): `ssm_rows` is the decoded rows x
Mamba-2 layers, `kv_entries_read` the sum of their contexts x softmax
layers. Over every `serve_prefill` span of the run, from the span's own
place (`bucket`, `prompt`, `offset`, `chunk`): `ssm_positions` is the valid
prompt positions the unit holds x Mamba-2 layers, `state_carries` the chunks
that were not their row's first x Mamba-2 layers. A unit that never ran
leaves no span, so the units are held to the clients' records too: every
request a client received whole (and every warm-up request) must have units,
under ONE request id, whose `ssm_positions` add up to its prompt's length x
Mamba-2 layers (`finished_prompts_not_scanned_whole`).

Two controls are committed with the first comparison, chosen by
`SERVE_CLOSED_GRANITE_CONTROL` in the environment (the driver's runs do not
set it; PERF.md has the readings):
- `fp8`, the control the limit is set against: the same run, but the gaps are
  those of the tokens the reference puts first when its matrix products are
  computed in float8, the nearest precision below the bfloat16 the
  configuration states, read at the served tokens' positions: the float8
  reference in the program's place. It must come out `correct: false` by
  `served_logit_gap_mean` and by no other check.
- `nocarry`, a probe of what the served tokens can see of the mechanism this
  cell exists for: the engine's chunk program is handed the slot's row of
  `state` and `conv` ZEROED, so every chunk scans as if nothing came before
  it while the mask, the pages and every counter stay as they were. At the
  tests' tiny size (a chunk of 8 places) the run comes out `correct: false`
  by the gap alone. AT THE CELL'S SIZE IT COMES OUT `correct: true` (my chip
  run, PR 53, seed 4100500604: 0 of 1,312 served tokens moved): a bucket is
  left-padded, so the chunk in front of the first served token is always
  full, and under the seeded draw (`A` = -U(1, 16), `dt` near logU(1e-3,
  1e-1)) under 1% of the heads keep 1% of a state through 2,048 places. The
  served tokens cannot tell a carried state from a forgotten one there; the
  exact counts above, the bytes carried and the CPU tests (a bucket in 1, 2
  and 4 chunks against the whole bucket, `state`, `conv` and pages) hold
  the carry, and PERF.md "Open questions" says what check would see it.
"""

from __future__ import annotations

import dataclasses
import gc
import itertools
import os
import time
import types

from benchmark import granite_hybrid_weights, granite_work, registry, stats
from benchmark.harness import Check

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONTROL_ENV = "SERVE_CLOSED_GRANITE_CONTROL"  # unset: the served tokens' gaps
REFERENCE_PAD = 1024      # a request's reference length is a multiple of this
COUNTED = ("ssm_rows", "ssm_positions", "kv_entries_read", "state_carries",
           "state_bytes_carried")


def model_config(cell):
    import jax.numpy as jnp

    from llama_pipeline_parallel_tpu.models.ssm_moe.config import SsmMoEConfig

    return SsmMoEConfig.from_published(
        cell.config, dtype=jnp.dtype(cell.config["compute_dtype"]).type,
        param_dtype=jnp.dtype(cell.config["weights_dtype"]).type)


_window = registry.load_job(ROOT, "serve_closed_window")
pages_reserved_peak = _window.pages_reserved_peak


def _shared():
    """`serve_closed_hybrid`, loaded for this job and given this family's
    configuration, weights and stream of requests."""
    job = registry.load_job(ROOT, "serve_closed_hybrid")
    job.model_config = model_config
    job.hybrid_moe_weights = granite_hybrid_weights
    job.traffic = types.SimpleNamespace(
        request_stream=_window.scheduled_stream)
    return job


_hybrid = _shared()
sample_finished = _hybrid.sample_finished


def forgetful(build_engine):
    """CONTROL `nocarry`: `build_engine`, whose engine's chunk program finds
    the slot's row of the recurrent store zeroed (in place: the store is
    donated), whatever the chunk before it left there."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    @partial(jax.jit, donate_argnums=0)
    def forget(pool, slot):
        zero = lambda a: jax.lax.dynamic_update_slice(
            a, jnp.zeros((a.shape[0], 1) + a.shape[2:], a.dtype),
            (0, slot) + (0,) * (a.ndim - 2))
        return {**pool, "state": zero(pool["state"]),
                "conv": zero(pool["conv"])}

    def build(ctx, params):
        engine = build_engine(ctx, params)
        family = engine._family

        def chunk(params, ids, mask, positions, pool, table_row, slot, *rest):
            return family.paged_prefill_chunk(
                params, ids, mask, positions, forget(pool, slot), table_row,
                slot, *rest)

        engine._family = dataclasses.replace(family, paged_prefill_chunk=chunk)
        return engine

    return build


def reference_gaps(ctx, sample: list, precision: str = "float32") -> list:
    """Per sampled request the gaps of its served tokens (the reference's
    `served_token_gaps`): each request at its own length, one at a time
    inside a layer, so that each layer's weights are made once. The weights
    are made anew from the seed, in the dtype the engine held them, then
    widened: the same values."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import granite_hybrid_decoder

    if not sample:
        return []
    model = ctx.cell.model
    dtype = jnp.dtype(ctx.cell.config["weights_dtype"]).type
    seed = ctx.seed % (2 ** 32)
    top = jax.tree.map(lambda x: x.astype(jnp.float32),
                       granite_hybrid_weights.make_top(seed, model, dtype))
    return granite_hybrid_decoder.served_token_gaps(
        top, granite_hybrid_weights.layer_fn(seed, model, dtype),
        [r["request"]["prompt"] for r in sample],
        [r["tokens"] for r in sample], model, REFERENCE_PAD, precision)


def run(ctx) -> dict:
    from benchmark import device

    cell = ctx.cell
    vocab = cell.model["vocab_size"]
    control = os.environ.get(CONTROL_ENV, "")
    if control not in ("", "fp8", "nocarry"):
        raise ValueError(f"{CONTROL_ENV}={control!r}: fp8 or nocarry")
    build = _hybrid.build_engine
    if control == "nocarry":
        _hybrid.build_engine = forgetful(build)
        print(f"serve: CONTROL ({CONTROL_ENV}=nocarry): every chunk finds its "
              f"slot's row of the recurrent store zeroed (a probe: not "
              f"correct at the tests' size, correct at the cell's)",
              flush=True)
    try:
        driven = _hybrid._drive(ctx)
    finally:
        _hybrid.build_engine = build
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
    print(f"serve: window={t1 - t0:.3f}s submitted={len(submitted)} "
          f"finished={len(finished)} failed={len(failed)} tokens={tokens} "
          f"engine completed={snapshot['requests_completed']} rejected="
          f"{snapshot['requests_rejected']}; resident {driven['resident']}; "
          f"pages reserved at once, at most "
          f"{pages_reserved_peak(records, engine)} of {engine['num_pages']}",
          flush=True)
    first = min((r["t_submit"] for r in records), default=t0)
    print(f"serve: set-up {t0 - ctx.t_start:.1f}s: {first - ctx.t_start:.1f}s "
          f"to the first submission (weights, engine, every shape warmed), "
          f"then {t0 - first:.1f}s of ramp over "
          f"{sum(1 for r in records if r['t_submit'] < t0)} requests",
          flush=True)
    print(f"serve: {tokens / (t1 - t0):.2f} tokens/s; gap between tokens over "
          f"{len(tpot)} finished requests: p50 "
          f"{1e3 * stats.percentile(tpot, 50):.2f} ms, p90 "
          f"{1e3 * stats.percentile(tpot, 90):.2f} ms; prompts of the "
          f"finished: {sorted(len(r['request']['prompt']) for r in finished)}",
          flush=True)
    # what decides the p90: the few requests with the widest mean gap, each
    # with the seconds before the window at which its first token came (a
    # request's gap holds whatever stalled the engine since then, ramp
    # included)
    slowest = sorted(zip(tpot, (r for r in finished if len(r["tokens"]) > 1)),
                     key=lambda pair: -pair[0])[:8]
    print("serve: widest gaps (ms, prompt, served, first token at s of the "
          "window): " + str([
              (round(1e3 * g, 2), len(r["request"]["prompt"]),
               len(r["tokens"]), round(r["token_times"][0] - t0, 1))
              for g, r in slowest]), flush=True)
    ramp_spans = [s for s in spans if first <= s["ts"] < t0
                  and s["name"] != "serve_queue_wait"]
    longest = sorted(ramp_spans, key=lambda s: -s["dur"])[:5]
    host = lambda key: sum(s.get(key, 0) for s in ramp_spans)
    print(f"serve: ramp: "
          f"{sum(1 for s in ramp_spans if s['name'] == 'serve_prefill')} "
          f"units in "
          f"{sum(s['dur'] for s in ramp_spans if s['name'] == 'serve_prefill'):.3f}s, "
          f"{sum(s.get('ticks', 0) for s in ramp_spans)} ticks in "
          f"{sum(s['dur'] for s in ramp_spans if s['name'] == 'serve_decode_step'):.3f}s; "
          f"compiles {host('compiles')} ({host('compile_s'):.3f}s), "
          f"collector {host('gc_s'):.3f}s; longest spans (name, s of the "
          f"ramp, s): " + str([(s["name"], round(s["ts"] - first, 2),
                                round(s["dur"], 3)) for s in longest]),
          flush=True)
    window_spans = [s for s in spans if in_window(s["ts"])]
    by_name = lambda n, of=window_spans: [s for s in of if s["name"] == n]
    prefills, decodes = by_name("serve_prefill"), by_name("serve_decode_step")
    chunks = [s for s in prefills if s["chunk"] < s["bucket"]]
    print(f"serve: prefill units {len(prefills)} in "
          f"{sum(s['dur'] for s in prefills):.3f}s ({len(chunks)} of them "
          f"chunks of larger buckets, "
          f"{sum(s.get('chunks_skipped', 0) for s in prefills)} pad-only "
          f"chunks never run; longest "
          f"{max((s['dur'] for s in prefills), default=0.0):.3f}s); decode "
          f"spans {sum(s['dur'] for s in decodes):.3f}s over "
          f"{sum(s['ticks'] for s in decodes)} ticks", flush=True)

    # -- the program's own counts, over every span of the run ----------------
    sz = granite_work.sizes(cell.model)
    ticks = [s for s in by_name("serve_decode_step", spans)
             if granite_work.COUNTER in s]
    units = [s for s in by_name("serve_prefill", spans)
             if granite_work.COUNTER in s]
    off = dict.fromkeys(("ssm_rows", "kv_entries_read", "ssm_positions",
                         "state_carries", "unscanned"), float("inf"))
    if ticks and units:
        tick = {k: sum(s[k] for s in ticks) for k in COUNTED + ("tokens",)}
        unit = {k: sum(s[k] for s in units) for k in COUNTED}
        want = granite_work.host_tick_counts(
            records, engine["prompt_buckets"], sz)
        want_units = granite_work.host_unit_counts(
            units, sz, granite_work.DTYPE_BYTES[cell.config["compute_dtype"]])
        off["ssm_rows"] = abs(tick["ssm_rows"] - tick["tokens"]
                              * sz["ssm_layers"])
        off["kv_entries_read"] = abs(tick["kv_entries_read"]
                                     - want["kv_entries_read"])
        off["ssm_positions"] = abs(unit["ssm_positions"]
                                   - want_units["ssm_positions"])
        off["state_carries"] = (
            abs(unit["state_carries"] - want_units["state_carries"])
            + abs(unit["state_bytes_carried"]
                  - want_units["state_bytes_carried"])
            # a tick scans nothing and carries nothing
            + tick["ssm_positions"] + tick["state_carries"])
        off["unscanned"] = granite_work.prompts_not_scanned_whole(
            units, [len(r["request"]["prompt"]) for r in records
                    if r["status"] == "done"] + list(engine["prompt_buckets"]),
            sz)
        rows = max(tick["tokens"], 1)
        print(f"serve: state-space: the ticks advanced {tick['ssm_rows']} "
              f"rows ({tick['tokens']} decoded rows x {sz['ssm_layers']} "
              f"layers; the host counts {want['rows']} from the lengths) and "
              f"read {tick['kv_entries_read']} page entries (host's count "
              f"{want['kv_entries_read']}: "
              f"{tick['kv_entries_read'] / rows / sz['softmax_layers']:.0f} a "
              f"row and layer); {len(units)} prefill units scanned "
              f"{unit['ssm_positions']} positions (host's count "
              f"{want_units['ssm_positions']}), "
              f"{unit['state_carries'] // sz['ssm_layers']} of them carried "
              f"their slot's row in "
              f"({unit['state_bytes_carried'] / 1e9:.2f} GB; the snapshot's "
              f"prefill_state_carries_total "
              f"{snapshot.get('prefill_state_carries_total')})", flush=True)

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
    precision = "fp8" if control == "fp8" else "float32"
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
        Check("ssm_rows_off_tokens_x_layers", float(off["ssm_rows"]), 0.0),
        Check("kv_entries_read_off_host_count",
              float(off["kv_entries_read"]), 0.0),
        Check("ssm_positions_off_host_count", float(off["ssm_positions"]),
              0.0),
        Check("state_carries_off_host_count", float(off["state_carries"]),
              0.0),
        Check("finished_prompts_not_scanned_whole", float(off["unscanned"]),
              0.0),
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
