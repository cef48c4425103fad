"""Job `serve_closed`: one `ServeEngine` in this process under closed-loop
clients, each a thread that submits its next request the moment its last one
completes and stamps every token as it arrives on `RequestHandle.tokens()`.
The client's side of the stream is the source of the end-to-end numbers; the
engine's own spans feed the per-layer ones.

Set-up: weights made on the device from the seed (benchmark/weights.py, in
the dtype the configuration states), the engine built, every prompt bucket and
the decode tick warmed up, the clients started. The window opens once every
slot has been occupied and `ramp_completions` requests have completed, so the
clients are out of step with each other, and closes `--seconds` later; what is
still in flight then is cut (neither completed nor failed).

`correct`, once the window has closed and the engine's state is freed: a
sample drawn from the seed of the requests the window finished, the longest
among them, each run once through the plain reference (prompt and served
tokens in one float32 forward); the number compared is the widest gap by which
a served (greedy) token's reference logit lies below the reference's best.
Plus: no request failed or was refused, every finished request has exactly the
tokens it asked for, and every id is in the vocabulary.
"""

from __future__ import annotations

import gc
import itertools
import os
import threading
import time

from benchmark import stats, traffic, weights
from benchmark.harness import Check


class _Client(threading.Thread):
    """One closed-loop client. Records, per request, the submit time and the
    arrival time of every token, all on time.time()."""

    def __init__(self, index, engine, next_request, stop, records, lock):
        super().__init__(name=f"client-{index}", daemon=True)
        self.engine, self.next_request = engine, next_request
        self.stop_flag, self.records, self.lock = stop, records, lock

    def run(self) -> None:
        from llama_pipeline_parallel_tpu.models.llama.decode import (
            GenerationConfig,
        )
        from llama_pipeline_parallel_tpu.serve import (
            EngineShutdown,
            ServeRequest,
        )

        while not self.stop_flag.is_set():
            req = self.next_request()
            rec = {"request": req, "t_submit": time.time(), "token_times": [],
                   "tokens": [], "status": "in_flight"}
            with self.lock:
                self.records.append(rec)
            try:
                handle = self.engine.submit(ServeRequest(
                    input_ids=req["prompt"], seed=req["seed"],
                    gen=GenerationConfig(max_new_tokens=req["max_new_tokens"],
                                         temperature=req["temperature"])))
                for token in handle.tokens(timeout=120.0):
                    rec["token_times"].append(time.time())
                    rec["tokens"].append(int(token))
                rec["status"] = "done"
            except EngineShutdown:
                rec["status"] = "cut"       # the run ended under it
            except Exception as e:          # refused, failed, timed out
                rec["status"] = "failed"
                rec["error"] = repr(e)
                if self.stop_flag.is_set():
                    rec["status"] = "cut"


def build_engine(ctx, params):
    import jax.numpy as jnp

    from llama_pipeline_parallel_tpu import serve
    from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig

    e = dict(ctx.cell.params["engine"])
    cfg = LlamaConfig(
        **ctx.cell.llama_config_sizes(),
        dtype=jnp.dtype(ctx.cell.config["compute_dtype"]).type,
        param_dtype=jnp.dtype(ctx.cell.config["weights_dtype"]).type)
    e["prompt_buckets"] = tuple(e["prompt_buckets"])
    return serve.ServeEngine(params, cfg, serve.ServeConfig(**e))


def warm_up(engine, vocab: int, buckets) -> None:
    """Every prompt bucket once, all in flight together so the decode tick
    runs too; each asks for two tokens."""
    from llama_pipeline_parallel_tpu.models.llama.decode import GenerationConfig
    from llama_pipeline_parallel_tpu.serve import ServeRequest

    handles = [engine.submit(ServeRequest(
        input_ids=[(7 * i + 1) % vocab for i in range(b)], seed=0,
        gen=GenerationConfig(max_new_tokens=2, temperature=0.0)))
        for b in buckets]
    for h in handles:
        h.result(timeout=1100.0)


def sample_finished(finished: list, seed: int, count: int) -> list:
    """The longest finished request and `count - 1` others drawn from the
    seed."""
    import numpy as np

    if not finished:
        return []
    size = lambda r: len(r["request"]["prompt"]) + len(r["tokens"])
    longest = max(range(len(finished)), key=lambda i: size(finished[i]))
    others = [i for i in range(len(finished)) if i != longest]
    rng = np.random.default_rng([seed, 0xC0FFEE])
    picks = rng.choice(len(others), size=min(count - 1, len(others)),
                       replace=False).tolist() if others else []
    return [finished[longest]] + [finished[others[i]] for i in picks]


def reference_gaps(ctx, sample: list, precision: str = "float32") -> list:
    """Per sampled request, the gaps of its served tokens (see the
    reference's `served_token_gaps`). Makes the weights anew from the seed."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import dense_decoder

    model = ctx.cell.model
    # in the dtype the engine holds them, then widened: the same values
    params = jax.tree.map(
        lambda x: x.astype(jnp.float32),
        weights.make_weights(ctx.seed % (2 ** 32), model, jnp.dtype(
            ctx.cell.config["weights_dtype"]).type))
    pad_to = ctx.cell.params["engine"]["max_len"]
    out = [dense_decoder.served_token_gaps(
        params, r["request"]["prompt"], r["tokens"], model, pad_to, precision)
        for r in sample]
    del params
    return out


def _drive(ctx) -> dict:
    """Set-up and the window. Everything that holds the engine or its
    weights is local here and dies on return, so the reference that follows
    finds the device empty."""
    import jax
    import jax.numpy as jnp

    from llama_pipeline_parallel_tpu import serve
    from llama_pipeline_parallel_tpu.utils import trace as program_trace

    cell, mix = ctx.cell, ctx.cell.mix
    model, vocab = cell.model, cell.model["vocab_size"]
    clients_n = mix["clients"]

    # -- set-up --------------------------------------------------------------
    params = weights.make_weights(
        ctx.seed % (2 ** 32), model,
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

    with rec_lock:
        records = list(records)
    return {"records": records, "spans": spans, "snapshot": snapshot,
            "alive": alive, "window": (t0, t1)}


def run(ctx) -> dict:
    from benchmark import device

    cell, mix = ctx.cell, ctx.cell.mix
    vocab = cell.model["vocab_size"]
    driven = _drive(ctx)
    gc.collect()
    records, spans, snapshot, alive = (driven[k] for k in (
        "records", "spans", "snapshot", "alive"))
    t0, t1 = driven["window"]

    # -- the client's side of the window ---------------------------------------
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
          f"{snapshot['requests_rejected']}", flush=True)

    # what a far-off run looked like from inside (a note, not a metric): a
    # host stall shows as one long gap between token arrivals
    window_spans = [s for s in spans if in_window(s["ts"])]
    stamps = sorted(t for r in records for t in r["token_times"] if in_window(t))
    arrival_gaps = [b - a for a, b in zip(stamps, stamps[1:])]
    by_name = lambda n: [s["dur"] for s in window_spans if s["name"] == n]
    prefills, decodes = by_name("serve_prefill"), by_name("serve_decode_step")
    print(f"serve: longest gap between token arrivals "
          f"{1e3 * max(arrival_gaps, default=0.0):.1f} ms, gaps over 0.2 s: "
          f"{sum(1 for g in arrival_gaps if g > 0.2)}; prefills {len(prefills)} in "
          f"{sum(prefills):.3f}s (longest {max(prefills, default=0.0):.3f}s); "
          f"decode spans {sum(decodes):.3f}s (longest "
          f"{max(decodes, default=0.0):.3f}s)", flush=True)

    # -- free the program's state, read the peak, run the reference ----------
    xplane_trace = None
    if ctx.trace:
        from benchmark import xplane

        path = xplane.find_xplane(os.path.join(ctx.run_dir, "profile"))
        xplane_trace = xplane.read(path) if path else None
    memory_peak = device.memory_peak_bytes(ctx.devices)
    t_ref = time.time()
    sample = sample_finished(finished, ctx.seed, cell.params["check_requests"])
    gaps = reference_gaps(ctx, sample)
    n_tokens = sum(len(g) for g in gaps)
    widest = max(itertools.chain.from_iterable(gaps), default=float("inf"))
    print(f"serve: reference ran {len(sample)} requests, {n_tokens} served "
          f"tokens, in {time.time() - t_ref:.1f}s (not in setup_s); widest "
          f"gap {widest}", flush=True)

    checks = [
        Check("served_logit_gap", float(widest),
              cell.params["checks"]["served_logit_gap"]),
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
        "observations": {
            "kind": "serve", "cell": cell, "devices": ctx.devices,
            "window": (t0, t1), "spans": window_spans,
            "xplane": xplane_trace, "finished": len(finished),
            "client": {"ttft_s": ttft, "tpot_s": tpot},
            "check_sample": sample,
            "tokens_per_s": tokens / (t1 - t0)},
    }
