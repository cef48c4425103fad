"""Job `serve_closed_latent`: `serve_closed` for a configuration of the
latent-attention family (MLA layers whose full kind reads the positions a
learned indexer chooses and whose sliding kind sees a window, a leading dense
layer, sparse experts of which this chip holds a range): the same
`ServeEngine` under the same closed-loop clients, the same ramp and window,
the same client-side end-to-end numbers. The engine prefills in chunks
(`prefill_chunk_tokens` in the cell's file): a bucket no larger than the
chunk whole, larger ones a chunk a tick between decode ticks.

What differs from `serve_closed`, and why this is a file of its own: the
configuration is a `LatentMoEConfig` built from the published keys
(`LatentMoEConfig.from_published`), the weights come from
`benchmark/latent_moe_weights.py` in the program's layout, and the plain
reference is `benchmark/reference/latent_moe_decoder.py`, run one layer at a
time. `_Client`, `warm_up` and `sample_finished` are `serve_closed`'s own,
imported; `_drive` and `run` are copies of `serve_closed_hybrid`'s with those
changes and the checks below (PERF.md "Open questions" lists them for the
benchmark PR that folds the three jobs together).

`correct`. `serve_closed`'s comparison with one change: the number compared
is the MEAN over the sample's served tokens of the gap by which a served
token's reference logit lies below the reference's best, not the widest.
Under bfloat16 the program's selection differs from the float32 reference's
in a tenth of its places at a 16k row (hidden states a percent apart move
index scores across the 2048th's threshold), so one served token in some
hundreds can lie as far below the reference's best as the float8 control's
(readings in the cell's file): the widest gap cannot tell the two apart,
the mean does, by a factor. The widest is printed. Beyond that:
- the expert layers' own count, as `serve_closed_hybrid`: `routed_total` of
  the `serve_decode_step` spans is the host's decoded rows x experts a token
  x expert layers, exactly;
- the indexer's own count: `index_selected` over every tick of the run is
  the sum over decoded rows and full layers of min(positions the row can
  see, `index_topk`), exactly; the host's side from the lengths alone (each
  request's prompt and the tokens its client received, plus the warm-up's);
- the selection itself: once the window has closed and the engine is gone,
  the sampled requests are replayed through the family's own programs over
  a fresh store of the engine's shapes (the engine's compiled programs: the
  last prefill unit of the prompt, then one tick a served token), and the
  places the program selected in its last prefill unit's last query and in
  its last tick are compared with the reference's own selection at the same
  two queries: the share of the reference's set the program misses, the
  largest over requests, queries and full layers.
"""

from __future__ import annotations

import gc
import itertools
import os
import threading
import time

from benchmark import hybrid_scopes, latent_moe_weights, registry, stats, traffic
from benchmark.harness import Check

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_dense = registry.load_job(ROOT, "serve_closed")
_Client, warm_up, sample_finished = (
    _dense._Client, _dense.warm_up, _dense.sample_finished)


def model_config(cell):
    import jax.numpy as jnp

    from llama_pipeline_parallel_tpu.models.latent_moe.config import (
        LatentMoEConfig,
    )

    return LatentMoEConfig.from_published(
        cell.config,
        dtype=jnp.dtype(cell.config["compute_dtype"]).type,
        param_dtype=jnp.dtype(cell.config["weights_dtype"]).type)


def serve_config(cell):
    from llama_pipeline_parallel_tpu import serve

    e = dict(cell.params["engine"])
    e["prompt_buckets"] = tuple(e["prompt_buckets"])
    return serve.ServeConfig(**e)


def build_engine(ctx, params):
    from llama_pipeline_parallel_tpu import serve

    return serve.ServeEngine(params, model_config(ctx.cell),
                             serve_config(ctx.cell))


def query_rows(sample: list) -> list:
    """Per sampled request, the two queries whose selection is compared, as
    positions in prompt + served tokens: the prompt's last token (the last
    prefill unit's last query) and the last token a tick took in."""
    return [[len(r["request"]["prompt"]) - 1,
             len(r["request"]["prompt"]) + len(r["tokens"]) - 2]
            for r in sample]


def replay_selection(ctx, params, sample: list) -> list:
    """The places the PROGRAM selects at `query_rows`, per request a list
    (one entry a query) of per-full-layer sets of token positions. The
    requests are admitted as the engine admits them (left-padded to their
    bucket, whole or in chunks) into slots of a fresh `PagedKVCache` of the
    engine's shapes, then decode together, greedy tokens forced to the
    served ones."""
    import jax.numpy as jnp
    import numpy as np

    from llama_pipeline_parallel_tpu import serve
    from llama_pipeline_parallel_tpu.models.family import family_of

    cfg, scfg = model_config(ctx.cell), serve_config(ctx.cell)
    family = family_of(cfg)
    cache = serve.PagedKVCache(cfg, scfg.max_slots, scfg.max_len,
                               scfg.page_size, scfg.resolved_num_pages)
    chunk = scfg.prefill_chunk_tokens
    S = scfg.max_slots
    rows, out_sets = {}, [[None, None] for _ in sample]

    def places(selection, row, pad):
        chosen, ok = (np.asarray(a) for a in selection)
        return [set((chosen[d, row][ok[d, row]] - pad).tolist())
                for d in range(chosen.shape[0])]

    for i, r in enumerate(sample):
        prompt, served = r["request"]["prompt"], r["tokens"]
        bucket = next(b for b in scfg.prompt_buckets if b >= len(prompt))
        demand = cache.demand_pages(bucket, len(served))
        assert cache.reserve(demand)
        slot = cache.acquire(f"replay-{i}", demand)
        pad = bucket - len(prompt)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, pad:] = prompt
        mask = np.zeros((1, bucket), np.int32)
        mask[0, pad:] = 1
        positions = np.clip(np.cumsum(mask, axis=1) - 1, 0, None).astype(np.int32)
        if not chunk or bucket <= chunk:
            out = family.prefill_prompt(params, jnp.asarray(ids),
                                        jnp.asarray(mask), cfg, bucket)
            cache.admit(slot, out)
        else:
            cache.reset_mask_row(slot)
            for c0 in range(0, bucket, chunk):
                c1 = c0 + chunk
                cache.ensure_capacity(slot, c1)
                out = family.paged_prefill_chunk(
                    params, jnp.asarray(ids[:, c0:c1]),
                    jnp.asarray(mask[:, c0:c1]),
                    jnp.asarray(positions[:, c0:c1]), cache.pool,
                    jnp.asarray(cache.page_table[slot]), jnp.int32(slot),
                    cache.kv_mask, jnp.int32(c0), cfg)
                cache.pool, cache.kv_mask = out["pool"], out["kv_mask"]
        out_sets[i][0] = places(out["selection"], 0, pad)
        if len(served) > 1:
            rows[slot] = {"i": i, "pad": pad, "at": 0, "served": served,
                          "pos": len(prompt), "write": bucket}
    while rows:
        token, pos, write, active = (np.zeros(S, np.int32) for _ in range(4))
        for slot, row in rows.items():
            token[slot], pos[slot] = row["served"][row["at"]], row["pos"]
            write[slot], active[slot] = row["write"], 1
            cache.ensure_capacity(slot, row["write"] + 1)
        out = family.paged_decode_step(
            params, jnp.asarray(token), cache.pool,
            jnp.asarray(cache.page_table), jnp.asarray(pos),
            jnp.asarray(write), cache.kv_mask, jnp.asarray(active),
            jnp.zeros((S, 2), jnp.uint32), jnp.zeros((S,), jnp.float32),
            jnp.zeros((S,), jnp.int32), jnp.ones((S,), jnp.float32), cfg)
        cache.update_from_step(out)
        for slot in list(rows):
            row = rows[slot]
            row["at"] += 1
            row["pos"] += 1
            row["write"] += 1
            if row["at"] == len(row["served"]) - 1:   # its last tick ran
                out_sets[row["i"]][1] = places(out["selection"], slot,
                                               row["pad"])
                del rows[slot]
    return out_sets


def missed_share(program_sets: list, reference_masks) -> float:
    """The largest share of the reference's own selection that the program's
    misses, over requests, queries and full layers. `reference_masks`: bool
    [full layers, requests, queries, positions]."""
    import numpy as np

    masks = np.asarray(reference_masks)
    worst = 0.0
    for i, per_query in enumerate(program_sets):
        for q, per_layer in enumerate(per_query):
            if per_layer is None:       # one served token: no tick ran
                continue
            for d, mine in enumerate(per_layer):
                want = set(np.flatnonzero(masks[d, i, q]).tolist())
                worst = max(worst, len(want - mine) / len(want))
    return worst


def reference_check(ctx, sample: list, program_sets: list,
                    precision: str = "float32", alter: tuple = ()):
    """(per sampled request the gaps of its served tokens, the selection's
    missed share): the reference's `served_token_gaps` over the sample with
    its own selections at `query_rows`. The weights are made anew from the
    seed, in the dtype the engine held them, then widened: the same
    values. `alter` reaches the reference's mixer (tests and controls)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import latent_moe_decoder

    if not sample:
        return [], float("inf")
    model = ctx.cell.model
    dtype = jnp.dtype(ctx.cell.config["weights_dtype"]).type
    seed = ctx.seed % (2 ** 32)
    top = jax.tree.map(lambda x: x.astype(jnp.float32),
                       latent_moe_weights.make_top(seed, model, dtype))
    gaps, masks = latent_moe_decoder.served_token_gaps(
        top, latent_moe_weights.layer_fn(seed, model, dtype),
        [r["request"]["prompt"] for r in sample],
        [r["tokens"] for r in sample], model,
        ctx.cell.params["engine"]["max_len"], precision,
        rows=query_rows(sample), alter=alter)
    return gaps, missed_share(program_sets, masks)


def host_index_selected(records: list, warm_buckets, model: dict) -> int:
    """What `index_selected` must sum to over every tick of a run, from the
    lengths alone: a request of n prompt tokens whose client received m
    tokens went through m - 1 ticks, the j-th with n + j positions to see;
    each warm-up request (a prompt the bucket long, two tokens) through
    one."""
    topk = model["index_topk"]
    layers = sum(kind.startswith("full") for kind in
                 model["layer_types"][:model["num_hidden_layers"]])
    total = sum(min(b + 1, topk) for b in warm_buckets)
    for r in records:
        n = len(r["request"]["prompt"])
        total += sum(min(n + j, topk) for j in range(1, len(r["tokens"])))
    return total * layers


def _drive(ctx) -> dict:
    """Set-up and the window: `serve_closed._drive` with this family's
    weights and engine; then, with the loop stopped and the engine dropped,
    the replay of the sampled requests that reads the program's selection.
    Everything that holds the engine or its weights is local here and dies
    on return."""
    import jax
    import jax.numpy as jnp

    from llama_pipeline_parallel_tpu import serve
    from llama_pipeline_parallel_tpu.utils import trace as program_trace

    cell, mix = ctx.cell, ctx.cell.mix
    vocab = cell.model["vocab_size"]
    clients_n = mix["clients"]
    model_config(cell)      # a program without the family fails here, at once

    # -- set-up --------------------------------------------------------------
    params = latent_moe_weights.make_program_weights(
        ctx.seed % (2 ** 32), cell.model,
        jnp.dtype(cell.config["weights_dtype"]).type)
    engine = build_engine(ctx, params)
    spans: list = []
    keep = ("serve_decode_step", "serve_queue_wait", "serve_prefill")
    listener = lambda rec: spans.append(dict(rec)) if rec["name"] in keep else None
    program_trace.recorder().add_listener(listener)
    loop = serve.ServeLoop(engine).start()
    warm_up(engine, vocab, cell.params["engine"]["prompt_buckets"])

    stream = traffic.request_stream(mix, ctx.seed, vocab)
    stream_lock = threading.Lock()

    def next_request():
        with stream_lock:
            req = next(stream)
        req["temperature"] = mix["temperature"]
        return req

    records: list = []
    rec_lock = threading.Lock()
    stop = threading.Event()
    clients = [_Client(i, engine, next_request, stop, records, rec_lock)
               for i in range(clients_n)]
    for c in clients:
        c.start()

    def done_count():
        with rec_lock:
            return sum(1 for r in records if r["status"] == "done")

    deadline = time.time() + 900.0
    full_once = False
    while time.time() < deadline:
        full_once = full_once or engine.slots.active_count >= min(
            clients_n, cell.params["engine"]["max_slots"])
        if full_once and done_count() >= mix["ramp_completions"]:
            break
        time.sleep(0.01)
    else:
        raise RuntimeError("the ramp never finished: slots never filled")

    # -- the window ----------------------------------------------------------
    t0 = time.time()
    t1 = t0 + ctx.seconds
    if ctx.trace:
        trace_dir = os.path.join(ctx.run_dir, "profile")
        lead = min(2.0, ctx.seconds / 4)
        time.sleep(lead)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host threads stay as they are
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        time.sleep(min(cell.params["trace_seconds"], ctx.seconds - 2 * lead))
        jax.profiler.stop_trace()
    time.sleep(max(0.0, t1 - time.time()))
    t1 = time.time()
    stop.set()
    loop.stop(timeout_s=60.0)               # shuts the engine down: cuts
    for c in clients:
        c.join(timeout=60.0)
    alive = [c.name for c in clients if c.is_alive()]
    program_trace.recorder().remove_listener(listener)
    snapshot = engine.metrics_snapshot()
    pool = engine.slots.pool
    resident = {"ring_store_bytes": engine.slots.recurrent_store_bytes,
                "latent_pages_bytes": pool["latent"].nbytes,
                "index_pages_bytes": pool["index"].nbytes,
                "weights_bytes": sum(x.nbytes for x in jax.tree.leaves(params))}
    with rec_lock:
        records = list(records)

    # -- the stores are dropped; the sample is replayed over fresh ones ------
    from benchmark import device

    del engine, loop, pool, clients
    gc.collect()
    memory_peak = device.memory_peak_bytes(ctx.devices)
    in_window = lambda t: t0 <= t <= t1
    finished = [r for r in records
                if r["status"] == "done" and in_window(r["token_times"][-1])]
    sample = sample_finished(finished, ctx.seed, cell.params["check_requests"])
    t_replay = time.time()
    program_sets = replay_selection(ctx, params, sample)
    print(f"serve: replayed {len(sample)} sampled requests through the "
          f"program's own prefill and tick in {time.time() - t_replay:.1f}s "
          f"(after the window; not in setup_s)", flush=True)
    return {"records": records, "spans": spans, "snapshot": snapshot,
            "alive": alive, "window": (t0, t1), "resident": resident,
            "memory_peak": memory_peak, "finished": finished,
            "sample": sample, "program_sets": program_sets}


def run(ctx) -> dict:
    cell, mix = ctx.cell, ctx.cell.mix
    vocab = cell.model["vocab_size"]
    driven = _drive(ctx)
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

    # a note, not this cell's end-to-end metric unless BENCHMARK.json lists
    # the cell under it (PERF.md)
    print(f"serve: gap between tokens over {len(tpot)} finished requests: "
          f"p50 {1e3 * stats.percentile(tpot, 50):.2f} ms, p90 "
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
    layers = cell.model["num_hidden_layers"] - cell.model["first_k_dense_replace"]
    topk = cell.model["num_experts_per_tok"]
    miscount = (abs(counted["routed_total"] - counted["tokens"] * topk * layers)
                if counted else float("inf"))
    ticks = [s for s in spans if s["name"] == "serve_decode_step"
             and "index_selected" in s]
    selected = sum(s["index_selected"] for s in ticks)
    want_selected = host_index_selected(
        records, cell.params["engine"]["prompt_buckets"], cell.model)
    index_miscount = abs(selected - want_selected) if ticks else float("inf")
    if counted and counted["routed_here"]:
        seen = sum(s["index_visible"] for s in ticks)
        print(f"serve: experts: {counted['ticks']} ticks, {counted['tokens']} "
              f"decoded tokens, routed_total {counted['routed_total']}, "
              f"routed_here {counted['routed_here']} "
              f"({100.0 * counted['routed_here'] / counted['routed_total']:.3f}% "
              f"of the router), held experts hit "
              f"{100.0 * counted['experts_hit'] / counted['experts_held']:.1f}%, "
              f"largest load over mean "
              f"{hybrid_scopes.load_max_over_mean(counted, cell.model['n_routed_experts']):.2f}; "
              f"indexer: the ticks saw {seen} positions and selected "
              f"{selected} (host's count {want_selected}): "
              f"{100.0 * selected / max(seen, 1):.1f}% kept", flush=True)

    # -- the reference, over the sample the replay read ----------------------
    xplane_trace = None
    if ctx.trace:
        from benchmark import xplane

        path = xplane.find_xplane(os.path.join(ctx.run_dir, "profile"))
        xplane_trace = xplane.read(path) if path else None
    t_ref = time.time()
    gaps, missed = reference_check(ctx, sample, driven["program_sets"])
    flat = list(itertools.chain.from_iterable(gaps))
    mean_gap = sum(flat) / len(flat) if flat else float("inf")
    print(f"serve: reference ran {len(sample)} requests "
          f"({[len(r['request']['prompt']) for r in sample]} prompt tokens), "
          f"{len(flat)} served tokens, in {time.time() - t_ref:.1f}s (not in "
          f"setup_s); mean gap {mean_gap}, {sum(1 for g in flat if g > 0)} "
          f"tokens off the reference's first choice, widest gap "
          f"{max(flat, default=float('inf'))} (a note: one token among "
          f"hundreds, PERF.md PR 30); the program's selection misses at most "
          f"{100.0 * missed:.2f}% of the reference's", flush=True)

    checks = [
        Check("served_logit_gap_mean", float(mean_gap),
              cell.params["checks"]["served_logit_gap_mean"]),
        Check("selection_missed_share", float(missed),
              cell.params["checks"]["selection_missed_share"]),
        Check("routed_total_off_tokens_x_topk_x_layers", float(miscount), 0.0),
        Check("index_selected_off_host_count", float(index_miscount), 0.0),
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
        "observations": {
            "kind": "serve", "cell": cell, "devices": ctx.devices,
            "window": (t0, t1), "spans": window_spans,
            "xplane": xplane_trace, "finished": len(finished),
            "client": {"ttft_s": ttft, "tpot_s": tpot},
            "check_sample": sample,
            "tokens_per_s": tokens / (t1 - t0)},
    }
