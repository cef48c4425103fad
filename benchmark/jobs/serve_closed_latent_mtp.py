"""Job `serve_closed_latent_mtp`: `serve_closed_latent` for a configuration of
the latent-attention family that DRAFTS with its multi-token-prediction
module (every layer MLA under an indexer, a leading dense layer, sparse
experts of which this chip holds a range, one MTP module behind the last
layer: models/latent_moe/draft.py). The same `ServeEngine` under the same
closed-loop clients, the same ramp and window, the same client-side
end-to-end numbers; the engine runs its verify tick (two queries a row, one
or two tokens a row) because the configuration states the module, and for no
other reason. Both serving numbers are returned; which of them the cell
reports is `BENCHMARK.json`'s to say (the gap between tokens, as the cell's
issue fixed it; tokens a second are printed beside it).

What it shares with `serve_closed_latent` it takes by loading that module, as
`serve_closed_granite` loads `serve_closed_hybrid`: its `_drive` (set-up,
ramp, window, trace, then the replay of the sampled requests with the engine
gone), `serve_config`, `_Client`, `warm_up`, `sample_finished`,
`missed_share`. A loaded job is a module object of this job's own, so five of
its names are set here before `_drive` runs: `model_config` (a
`LatentMoEConfig` from the published `glm_moe_dsa` keys), the weights' module
(`benchmark/glm_mtp_weights.py`), `traffic` (so that `_drive` draws its
requests from `serve_closed_window`'s `scheduled_stream`: where the mix
states a `schedule_seed` every run serves the SAME schedule of lengths and
only the token ids, the sampling seeds and the weights follow `--seed`),
`build_engine` (the same engine, whose `submit` also keeps every handle: a
handle names its request, whose row-ticks the spans record) and
`replay_selection` (this job's `replay`). `run` is this job's: the plain
reference is `benchmark/reference/glm_dsa_mtp_decoder.py`.

What the module and the second query PRODUCED is rated from the timed run
itself. Every verify tick's fetched vector carries, a row, how many tokens
the tick made, the draft it verified and the second query's first choice
(`models/tick_io.py`), and the engine puts them on the tick's
`serve_decode_step` span by request (`verify_rows`): the ticks the clients
were served by, 32 rows live, a tick and a unit in flight. Only the
SELECTIONS, which cannot leave the device, are read from a replay of the
sampled requests through the family's own programs over a fresh store, as
`serve-long-32.dots3` reads them.

`correct`, beside what every serving job checks (no request failed or
refused, every finished request its whole budget, ids in the vocabulary, no
thread left, nothing compiled in the window):

(a) the TRUNK's served logits against the reference at the published widths,
with `serve-long-32.dots3`'s measures: `served_logit_gap_mean`, the mean over
the sample's served tokens of the gap by which a served token's reference
logit lies below the reference's best (every served token was drawn from a
first query's logits, or from a second's behind an accepted draft);
`second_query_gap_mean`, the same of the token the run's own SECOND query put
first at sampled ticks, under the reference's logits for the sequence with
the draft the tick verified appended (the second query's logits are read
whether or not the draft is accepted); `selection_missed_share`, the largest
share of the reference's own selection that the program's misses, over the
prompt's last query, both queries of each request's last tick, and the
module's position there, in every layer (the replay's);
(b) the MODULE's logits: `mtp_draft_gap_mean`, the mean over every tick the
run made for the sampled requests of the gap by which the DRAFT that tick
verified (the module's first choice a tick before, or `first_draft`'s behind
the prompt) lies below the reference's module's best at the same position,
the reference's module given the served prefix and the token after the
position. A module fed the wrong token, or one that goes wrong at 32 rows,
drafts something else;
(c) exact counters, the device's sums over every `serve_decode_step` span of
the run against the host's own count (`spec_work.host_verify_counts`, from
each request's prompt length and the tokens each of its row-ticks made, off
the spans' `verify_rows`): tokens made = row-ticks + drafts accepted; drafts
offered = row-ticks; `index_visible` and `index_selected` over both queries
and all six caches; routed assignments = (trunk queries x 8 x 4 + module
positions x 8); dead entries = refused drafts x 5; and over every
`serve_prefill` span `mtp_positions` against the units' own places;
(d) no token is emitted from a refused draft's logits, over EVERY request of
the run and from the run's own record: a row-tick made two tokens exactly
where the draft it verified is the token the handle received first, and the
second it then received is the second query's first choice (the traffic is
greedy) (`emitted_off_the_one_token_reading`, exact). That the first is the
first query's own is what (a) holds.

Three controls are committed, chosen by `SERVE_CLOSED_MTP_CONTROL` in the
environment (the driver's runs do not set it; PERF.md has the readings), each
the same run with the reference altered, each to come out `correct: false`:
- `fp8`: the gaps are those of the tokens the reference puts first when its
  matrix products are computed in float8, the nearest precision below the
  bfloat16 the configuration states;
- `most_recent`: the reference selects the most recent 2048 positions
  instead of the largest index scores (`selection_missed_share`);
- `unshifted`: the reference's module is fed E[t_i] where E[t_{i+1}] belongs
  (`mtp_draft_gap_mean`, with the module's layer's part of
  `selection_missed_share`; no check of the trunk).
"""

from __future__ import annotations

import gc
import os
import time
import types

from benchmark import glm_mtp_weights, registry, spec_work, stats
from benchmark.harness import Check

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONTROL_ENV = "SERVE_CLOSED_MTP_CONTROL"
CONTROLS = ("", "fp8", "most_recent", "unshifted")
REFERENCE_PAD = 256       # a request's reference length is a multiple of this
SECOND_QUERY_TICKS = 2    # ticks a sampled request whose second query is read


def model_config(cell):
    import jax.numpy as jnp

    from llama_pipeline_parallel_tpu.models.latent_moe.config import (
        LatentMoEConfig,
    )

    return LatentMoEConfig.from_published(
        cell.config,
        dtype=jnp.dtype(cell.config["compute_dtype"]).type,
        param_dtype=jnp.dtype(cell.config["weights_dtype"]).type)


_window = registry.load_job(ROOT, "serve_closed_window")
HANDLES: list = []        # every handle of the run's engine, in submit order
ENGINES: list = []        # the run's engine, for `release` once it is done


def _shared():
    """`serve_closed_latent`, loaded for this job and given this
    configuration, its weights, the scheduled stream, an engine that keeps
    its handles, and this job's replay."""
    job = registry.load_job(ROOT, "serve_closed_latent")
    build = job.build_engine

    def build_engine(ctx, params):
        engine = build(ctx, params)
        ENGINES.append(engine)
        submit = engine.submit

        def keeping(request):
            handle = submit(request)
            HANDLES.append(handle)
            return handle

        engine.submit = keeping
        return engine

    job.model_config = model_config
    job.latent_moe_weights = glm_mtp_weights
    job.traffic = types.SimpleNamespace(
        request_stream=_window.scheduled_stream)
    job.build_engine = build_engine
    job.replay_selection = replay
    return job


def release() -> None:
    """Give the device back what the run's engine held, its weights (the
    tree `_drive` made: the engine holds the same arrays) and its stores,
    whoever still refers to the engine: the stopped clients' thread objects
    do, so 11.7 GB stood on the device under the reference (my chip runs,
    PR 55), which needs the room. The replay has run by then."""
    import jax

    for engine in ENGINES:
        for leaf in jax.tree.leaves((engine.params, engine.slots.pool,
                                     engine.slots.kv_mask)):
            if not leaf.is_deleted():
                leaf.delete()
    del ENGINES[:]


def replay(ctx, params, sample: list) -> dict:
    """The sampled requests through the family's own programs over a fresh
    store of the engine's shapes, admitted as the engine admits them (whole
    or in chunks, each chunk with the id after it, then the first draft),
    decoding together, the served tokens fed back: for the SELECTIONS, which
    never leave the device in the timed run. Per request "sets": the
    selections at the prompt's last query and at the last tick's two trunk
    queries and module position, per layer, with the first query's position
    ("at") and the draft that tick verified ("drafted"); and how many
    replayed tokens were not the served ones ("off_served": another batch,
    the same arithmetic)."""
    import jax.numpy as jnp
    import numpy as np

    from llama_pipeline_parallel_tpu import serve
    from llama_pipeline_parallel_tpu.models.family import family_of

    cfg, scfg = model_config(ctx.cell), serve_config(ctx.cell)
    family = family_of(cfg)
    cache = serve.PagedKVCache(cfg, scfg.max_slots, scfg.max_len,
                               scfg.page_size, scfg.pool_pages(cfg))
    chunk, S = scfg.prefill_chunk_tokens, scfg.max_slots
    out = [{} for _ in sample]
    rows, off_served = {}, 0

    def places(chosen, ok, pad):
        return set((chosen[ok] - pad).tolist())

    for i, r in enumerate(sample):
        prompt, served = r["request"]["prompt"], r["tokens"]
        bucket = next(b for b in scfg.prompt_buckets if b >= len(prompt))
        demand = cache.demand_pages(bucket, len(served) + 2)
        assert cache.reserve(demand)
        slot = cache.acquire(f"replay-{i}", demand)
        pad = bucket - len(prompt)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, pad:] = prompt
        mask = np.zeros((1, bucket), np.int32)
        mask[0, pad:] = 1
        positions = np.clip(np.cumsum(mask, axis=1) - 1, 0, None).astype(np.int32)
        if not chunk or bucket <= chunk:
            unit = family.prefill_prompt(params, jnp.asarray(ids),
                                         jnp.asarray(mask), cfg, bucket)
            cache.admit(slot, unit)
        else:
            cache.reset_mask_row(slot)
            for c0 in range(0, bucket, chunk):
                c1 = c0 + chunk
                cache.ensure_capacity(slot, c1)
                after = (ids[0, c1:c1 + 1] if c1 < bucket
                         else np.full(1, -1, np.int32))
                unit = family.paged_prefill_chunk(
                    params, jnp.asarray(ids[:, c0:c1]),
                    jnp.asarray(mask[:, c0:c1]),
                    jnp.asarray(positions[:, c0:c1]), cache.pool,
                    jnp.asarray(cache.page_table[slot]), jnp.int32(slot),
                    cache.kv_mask, jnp.int32(c0), cfg,
                    next_id=jnp.asarray(after))
                cache.pool, cache.kv_mask = unit["pool"], unit["kv_mask"]
        chosen, ok = (np.asarray(a) for a in unit["selection"])
        out[i]["prompt"] = [places(chosen[d, 0], ok[d, 0], pad)
                            for d in range(chosen.shape[0])]
        first = np.zeros(S, np.int32)
        first[slot] = served[0]
        cache.pool = family.first_draft(
            params, unit["hidden"], jnp.asarray(first), cache.pool,
            jnp.asarray(cache.page_table[slot]), jnp.int32(slot),
            cache.kv_mask, jnp.int32(len(prompt) - 1), jnp.int32(bucket - 1),
            cfg)
        if len(served) > 1:
            rows[slot] = {"i": i, "pad": pad, "at": 0, "served": served,
                          "n": len(prompt), "bucket": bucket}
    zero_keys = jnp.zeros((S, 2), jnp.uint32)
    greedy = (jnp.zeros((S,), jnp.float32), jnp.zeros((S,), jnp.int32),
              jnp.ones((S,), jnp.float32))
    while rows:
        token, pos, write, active = (np.zeros(S, np.int32) for _ in range(4))
        for slot, row in rows.items():
            token[slot] = row["served"][row["at"]]
            pos[slot] = row["n"] + row["at"]
            write[slot], active[slot] = row["bucket"] + row["at"], 1
            cache.ensure_capacity(slot, min(write[slot] + 2, scfg.max_len))
        tick = family.paged_decode_step(
            params, jnp.asarray(token), cache.pool,
            jnp.asarray(cache.page_table), jnp.asarray(pos),
            jnp.asarray(write), cache.kv_mask, jnp.asarray(active), zero_keys,
            *greedy, cfg)
        cache.update_from_step(tick)
        got = {k: np.asarray(tick[k]) for k in ("tokens", "count", "drafted")}
        drop = []
        for slot in list(rows):
            row = rows[slot]
            served, at, i = row["served"], row["at"], row["i"]
            made, drafted = int(got["count"][slot]), int(got["drafted"][slot])
            # a second token past the served stream's end (a budget that
            # ended on the first of two) has nothing to be held to
            emitted = got["tokens"][slot].tolist()[
                :min(made, len(served) - 1 - at)]
            kept = 0
            while kept < len(emitted) and \
                    emitted[kept] == served[at + 1 + kept]:
                kept += 1
            if kept < len(emitted):
                # another token than the served one (this batch is not the
                # window's): the served one goes on, without a draft
                off_served += 1
                drop.append(slot)
            row["at"] = at + max(kept, 1)
            if row["at"] >= len(served) - 1:    # its last tick ran
                chosen, ok = (np.asarray(a) for a in tick["selection"])
                mc, mok = (np.asarray(a) for a in tick["mtp_selection"])
                sets = out[i]
                sets["first"] = [places(chosen[d, slot, 0], ok[d, slot, 0],
                                        row["pad"])
                                 for d in range(chosen.shape[0])]
                sets["module"] = [places(mc[slot, 0], mok[slot, 0],
                                         row["pad"])]
                sets["at"] = row["n"] + at      # the first query's position
                if drafted >= 0:
                    sets["second"] = [places(chosen[d, slot, 1],
                                             ok[d, slot, 1], row["pad"])
                                      for d in range(chosen.shape[0])]
                    sets["drafted"] = drafted
                del rows[slot]
        if drop:
            no = cache.pool["mtp_draft"].at[jnp.asarray(drop)].set(-1)
            cache.pool = {**cache.pool, "mtp_draft": no}
    return {"sets": out, "off_served": int(off_served)}


_latent = _shared()
serve_config, sample_finished = _latent.serve_config, _latent.sample_finished


def window_rows(spans: list) -> dict:
    """Every request's row-ticks as the run's spans recorded them, in tick
    order: {request id: [[tokens made, the draft verified, the second
    query's first choice], ...]}, overruns among them."""
    rows: dict = {}
    for s in sorted(spec_work.spec_spans(spans), key=lambda s: s["ts"]):
        for request, ticks in s["verify_rows"].items():
            rows.setdefault(request, []).extend(ticks)
    return rows


def rows_of(sample: list, rows: dict) -> list:
    """The sampled records' row-ticks: a record is its handle's by the
    request it made (prompt and seed)."""
    key = lambda prompt, seed: (int(seed), tuple(int(t) for t in prompt))
    by_request = {key(h.request.input_ids, h.request.seed):
                  h.request.request_id for h in HANDLES}
    return [rows.get(by_request.get(
        key(r["request"]["prompt"], r["request"]["seed"])), [])
        for r in sample]


def off_the_one_token_reading(rows: dict) -> int:
    """(d) over every request of the run, from the run's own record: the
    row-ticks that made two tokens where the draft they verified is not the
    token the handle then received, or one where it is, and the second
    tokens that are not the second query's first choice. A tick's tokens
    past what the handle received (an overrun's, a second behind the budget's
    end) have nothing to be held to."""
    wrong = 0
    for h in HANDLES:
        tokens, at = list(h.tokens_out), 0
        for made, drafted, second in rows.get(h.request.request_id, []):
            if at + 1 < len(tokens):
                wrong += (made == 2) != (drafted == tokens[at + 1])
            if made == 2 and at + 2 < len(tokens):
                wrong += tokens[at + 2] != second
            at += made
    return int(wrong)


def reference_check(ctx, sample: list, ticks: list, replayed: dict,
                    precision: str = "float32", alter: tuple = ()) -> dict:
    """The reference over the sample and over a few sequences with a draft
    appended, one request at a time inside a layer. `ticks`: the sampled
    requests' row-ticks of the RUN (`rows_of`); `replayed`: the replay's
    selections. Returns the gaps of (a) and (b) and the selection's missed
    share. The weights are made anew from the seed, in the dtype the engine
    held them, then widened: the same values."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import glm_dsa_mtp_decoder as reference

    empty = {"gaps": [], "second": [], "drafts": [],
             "missed": float("inf")}
    if not sample:
        return empty
    model = ctx.cell.model
    dtype = jnp.dtype(ctx.cell.config["weights_dtype"]).type
    seed = ctx.seed % (2 ** 32)
    top = jax.tree.map(lambda x: x.astype(jnp.float32),
                       glm_mtp_weights.make_top(seed, model, dtype))
    layer_fn = glm_mtp_weights.layer_fn(seed, model, dtype)
    # the sequences: every sampled request whole, then, for a few ticks of
    # each, what the request held then with the draft appended
    seqs, asks, rows_asked, owner = [], [], [], []
    for r, rows, sets in zip(sample, ticks, replayed["sets"]):
        prompt, served = r["request"]["prompt"], r["tokens"]
        seq = list(prompt) + list(served)
        n = len(prompt)
        ask = [("logits", n - 1 + k, served[k]) for k in range(len(served))]
        # tick k fed served[at] at position n + at: the draft it verified is
        # the module's at the position before, given served[at] as its next;
        # its second query stood at n + at + 1, behind the draft
        seconds, at = [], 0
        for made, drafted, second in rows:
            if at + 1 >= len(served):
                break           # an overrun's tick: past the served stream
            if drafted >= 0:
                ask.append(("mtp_logits", n + at - 1, drafted))
                seconds.append((n + at + 1, drafted, second))
            at += made
        seqs.append(seq)
        asks.append(ask)
        rows_asked.append([n - 1, sets.get("at", n - 1)])
        owner.append(("whole", sets))
        picks = sorted({len(seconds) // 2, len(seconds) - 1}) if seconds \
            else []
        for j in picks[:SECOND_QUERY_TICKS]:
            held, drafted, choice = seconds[j]
            seqs.append(seq[:held] + [drafted])
            asks.append([("logits", held, choice)])
            rows_asked.append([held, held])
            # the replay's second query selected behind ITS draft
            owner.append(("second", sets if held == sets.get("at", -2) + 1
                          and sets.get("drafted") == drafted else None))
    longest = max(len(s) for s in seqs)
    pad_to = -(-longest // REFERENCE_PAD) * REFERENCE_PAD
    ids = jnp.asarray([s + [0] * (pad_to - len(s)) for s in seqs], jnp.int32)
    # logits only where they are asked for: a row of them is the vocabulary
    ref = reference.forward(top, layer_fn, ids, model, "float32",
                            rows=jnp.asarray(rows_asked, jnp.int32),
                            alter=alter, heads=False)
    low = (reference.forward(top, layer_fn, ids, model, precision,
                             heads=False)
           if precision != "float32" else None)
    heads = {"logits": ("hidden", reference.trunk_logits),
             "mtp_logits": ("mtp_hidden", reference.module_logits)}
    out = {"gaps": [], "second": [], "drafts": [], "missed": 0.0}
    masks = np.asarray(ref["selections"])            # [layers, b, 2, s]
    module_masks = np.asarray(ref["mtp_selections"])          # [b, 2, s]

    def missed(mine: list, want_masks) -> float:
        worst = 0.0
        for mine_d, mask in zip(mine, want_masks):
            want = set(np.flatnonzero(mask).tolist())
            worst = max(worst, len(want - mine_d) / len(want))
        return worst

    for b, (ask, (kind, sets)) in enumerate(zip(asks, owner)):
        for name, (hidden, head) in heads.items():
            mine = [(at, token) for which, at, token in ask if which == name]
            if not mine:
                continue
            at = jnp.asarray([a for a, _ in mine], jnp.int32)
            token = jnp.asarray([t for _, t in mine], jnp.int32)
            logits = head(top, ref[hidden][b, at], model)         # [n, V]
            if low is not None:
                token = jnp.argmax(head(top, low[hidden][b, at], model,
                                        precision), axis=-1)
            gaps = np.asarray(jnp.max(logits, axis=-1) - jnp.take_along_axis(
                logits, token[:, None], axis=-1)[:, 0]).tolist()
            out["second" if kind == "second" else
                "gaps" if name == "logits" else "drafts"] += gaps
        if sets is None:
            continue
        if kind == "whole":
            out["missed"] = max(out["missed"],
                                missed(sets["prompt"], masks[:, b, 0]))
            if "first" in sets:
                out["missed"] = max(
                    out["missed"], missed(sets["first"], masks[:, b, 1]),
                    missed(sets["module"], module_masks[None, b, 1]))
        else:
            out["missed"] = max(out["missed"],
                                missed(sets["second"], masks[:, b, 0]))
    return out


def run(ctx) -> dict:
    cell = ctx.cell
    vocab = cell.model["vocab_size"]
    control = os.environ.get(CONTROL_ENV, "")
    if control not in CONTROLS:
        raise ValueError(f"{CONTROL_ENV}={control!r}: one of {CONTROLS[1:]}")
    del HANDLES[:]
    driven = _latent._drive(ctx)
    release()
    gc.collect()
    records, spans, snapshot, alive, finished, sample = (driven[k] for k in (
        "records", "spans", "snapshot", "alive", "finished", "sample"))
    replayed = driven["program_sets"]
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
    engine = cell.params["engine"]
    print(f"serve: window={t1 - t0:.3f}s submitted={len(submitted)} "
          f"finished={len(finished)} failed={len(failed)} tokens={tokens} "
          f"engine completed={snapshot['requests_completed']} rejected="
          f"{snapshot['requests_rejected']}; resident {driven['resident']}; "
          f"pages reserved at once, at most "
          f"{_window.pages_reserved_peak(records, engine)} of "
          f"{engine['num_pages']}", flush=True)
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
    slowest = sorted(zip(tpot, (r for r in finished if len(r["tokens"]) > 1)),
                     key=lambda pair: -pair[0])[:8]
    print("serve: widest gaps (ms, prompt, served, first token at s of the "
          "window): " + str([
              (round(1e3 * g, 2), len(r["request"]["prompt"]),
               len(r["tokens"]), round(r["token_times"][0] - t0, 1))
              for g, r in slowest]), flush=True)
    window_spans = [s for s in spans if in_window(s["ts"])]
    by_name = lambda n, of=window_spans: [s for s in of if s["name"] == n]
    prefills, decodes = by_name("serve_prefill"), by_name("serve_decode_step")
    ticks_n = sum(s["ticks"] for s in decodes)
    print(f"serve: prefill units {len(prefills)} in "
          f"{sum(s['dur'] for s in prefills):.3f}s "
          f"({sum(1 for s in prefills if s['chunk'] < s['bucket'])} of them "
          f"chunks, {sum(s.get('chunks_skipped', 0) for s in prefills)} "
          f"pad-only chunks never run, "
          f"{sum(s['ahead'] for s in prefills)} read behind the next "
          f"hand-over; longest "
          f"{max((s['dur'] for s in prefills), default=0.0):.3f}s); decode "
          f"spans {sum(s['dur'] for s in decodes):.3f}s over {ticks_n} ticks, "
          f"{sum(s['ticks_ahead'] for s in decodes)} of them enqueued behind "
          f"a tick in flight", flush=True)

    # -- (c): the program's own counts, over every span of the run -----------
    rows = window_rows(spans)
    ticks = spec_work.spec_spans(spans)
    units = spec_work.spec_spans(spans, "serve_prefill")
    off = dict.fromkeys(("tokens", "offered", "index", "routed", "dead",
                         "units"), float("inf"))
    if ticks and units:
        dev = {k: sum(s[k] for s in ticks) for k in spec_work.COUNTERS + (
            "tokens", "row_ticks", "tokens_discarded", "rows_overrun",
            "index_visible", "index_selected", "routed_total")}
        want = spec_work.host_verify_counts(
            [(len(h.request.input_ids),
              [made for made, _, _ in rows.get(h.request.request_id, [])])
             for h in HANDLES], cell.model)
        off["tokens"] = (abs(dev["tokens"] - dev["row_ticks"]
                             - dev["spec_accepted"])
                         + abs(dev["spec_tokens"] - dev["tokens"])
                         + abs(dev["tokens"] - want["tokens"])
                         + abs(dev["spec_accepted"] - want["accepted"]))
        off["offered"] = (abs(dev["spec_offered"] - dev["row_ticks"])
                          + abs(dev["row_ticks"] - want["row_ticks"]))
        off["index"] = (abs(dev["index_visible"] - want["index_visible"])
                        + abs(dev["index_selected"] - want["index_selected"]))
        off["routed"] = (abs(dev["routed_total"] - want["routed_total"])
                         + abs(dev["mtp_positions"] - want["mtp_positions"]))
        off["dead"] = abs(dev["spec_dead_entries"] - want["dead_entries"])
        unit_positions = sum(s["mtp_positions"] for s in units)
        off["units"] = abs(unit_positions
                           - spec_work.host_unit_positions(units)) + sum(
            s["spec_offered"] + s["spec_tokens"] for s in units)
        delivered = sum(max(len(h.tokens_out) - 1, 0) for h in HANDLES)
        print(f"serve: drafting: {dev['row_ticks']} row-ticks made "
              f"{dev['tokens']} tokens ({dev['spec_accepted']} of "
              f"{dev['spec_offered']} drafts accepted: "
              f"{100.0 * dev['spec_accepted'] / max(dev['spec_offered'], 1):.4f}%; "
              f"host's count {want['tokens']} tokens, {want['accepted']} "
              f"accepted), {dev['tokens_discarded']} discarded "
              f"({dev['rows_overrun']} row-ticks overran), {delivered} "
              f"delivered after the first tokens; the queries saw "
              f"{dev['index_visible']} positions and selected "
              f"{dev['index_selected']} (host's count "
              f"{want['index_selected']}: "
              f"{100.0 * dev['index_selected'] / max(dev['index_visible'], 1):.1f}% "
              f"kept); routed_total {dev['routed_total']} (host's "
              f"{want['routed_total']}); {dev['spec_dead_entries']} dead "
              f"entries; the module ran {dev['mtp_positions']} positions in "
              f"ticks and kept {unit_positions} in {len(units)} prefill "
              f"units; snapshot spec_offered_total "
              f"{snapshot.get('spec_offered_total')}, spec_accepted_total "
              f"{snapshot.get('spec_accepted_total')}", flush=True)
        off["tokens"] += abs(dev["tokens"] - dev["tokens_discarded"]
                             - delivered)

    # -- the reference, over the sample the replay read ----------------------
    xplane_trace = None
    if ctx.trace:
        from benchmark import xplane

        path = xplane.find_xplane(os.path.join(ctx.run_dir, "profile"))
        xplane_trace = xplane.read(path) if path else None
    t_ref = time.time()
    in_use = (ctx.devices[0].memory_stats() or {}).get("bytes_in_use")
    print(f"serve: {in_use} bytes in use on the device before the reference "
          f"(the engine, its weights and the replay's stores are gone)",
          flush=True)
    precision = "fp8" if control == "fp8" else "float32"
    alter = (control,) if control in ("most_recent", "unshifted") else ()
    if control:
        print(f"serve: CONTROL ({CONTROL_ENV}={control}): the reference is "
              f"altered; this run has to come out not correct", flush=True)
    ref = reference_check(ctx, sample, rows_of(sample, rows), replayed,
                          precision, alter)
    violations = off_the_one_token_reading(rows)
    mean = lambda xs: sum(xs) / len(xs) if xs else float("inf")
    print(f"serve: the run's own record: {violations} tokens emitted off the "
          f"one-token reading over {len(HANDLES)} requests and "
          f"{sum(len(v) for v in rows.values())} row-ticks; replay (the "
          f"selections): {replayed['off_served']} replayed tokens differ "
          f"from the served ones; reference ran {len(sample)} requests "
          f"({[len(r['request']['prompt']) for r in sample]} prompt tokens, "
          f"{[len(r['tokens']) for r in sample]} served) and "
          f"{len(ref['second'])} sequences with a draft appended in "
          f"{time.time() - t_ref:.1f}s (not in setup_s); trunk: mean gap "
          f"{mean(ref['gaps'])} over {len(ref['gaps'])} served tokens, "
          f"{sum(1 for g in ref['gaps'] if g > 0)} off the reference's first "
          f"choice, widest {max(ref['gaps'], default=float('inf'))}; second "
          f"query: gaps {ref['second']}; module: mean gap "
          f"{mean(ref['drafts'])} over {len(ref['drafts'])} drafts, "
          f"{sum(1 for g in ref['drafts'] if g > 0)} off the reference's "
          f"module's first choice, widest "
          f"{max(ref['drafts'], default=float('inf'))}; the program's "
          f"selection misses at most {100.0 * ref['missed']:.2f}% of the "
          f"reference's", flush=True)

    limits = cell.params["checks"]
    checks = [
        Check("served_logit_gap_mean", float(mean(ref["gaps"])),
              limits["served_logit_gap_mean"]),
        Check("second_query_gap_mean", float(mean(ref["second"])),
              limits["second_query_gap_mean"]),
        Check("mtp_draft_gap_mean", float(mean(ref["drafts"])),
              limits["mtp_draft_gap_mean"]),
        Check("selection_missed_share", float(ref["missed"]),
              limits["selection_missed_share"]),
        Check("emitted_off_the_one_token_reading", float(violations), 0.0),
        Check("tokens_made_off_row_ticks_plus_accepted", float(off["tokens"]),
              0.0),
        Check("drafts_offered_off_row_ticks", float(off["offered"]), 0.0),
        Check("index_counts_off_host_count", float(off["index"]), 0.0),
        Check("routed_total_off_queries_and_module_positions",
              float(off["routed"]), 0.0),
        Check("dead_entries_off_refused_drafts", float(off["dead"]), 0.0),
        Check("prefill_mtp_positions_off_host_count", float(off["units"]),
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
        "memory_peak_bytes": driven["memory_peak"],
        "observations": {
            "kind": "serve", "cell": cell, "devices": ctx.devices,
            "window": (t0, t1), "spans": window_spans,
            "xplane": xplane_trace, "finished": len(finished),
            "client": {"ttft_s": ttft, "tpot_s": tpot},
            "check_sample": sample,
            "tokens_per_s": tokens / (t1 - t0)},
    }
