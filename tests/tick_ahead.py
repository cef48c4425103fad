"""Helpers for the tests of the engine's one decode tick in flight
(`serve/engine.py`): the SERIAL order it replaced, kept here as the
reference, a stepped run of either order with its spans, and the checks
every family's serving tests make of the two."""

from llama_pipeline_parallel_tpu import serve
from llama_pipeline_parallel_tpu.utils import trace

SUMMED = ("ticks", "tokens", "ticks_ahead", "rows_overrun", "h2d_copies",
          "d2h_copies")


def step_serially(engine) -> bool:
    """The engine's order before it kept a tick in flight: admit, stage,
    dispatch, wait and emit in turn. A step, then the collection of the tick
    it enqueued, so the next tick is staged from tokens and keys the host
    has read and no row is ever fed from the tick before on the device."""
    did = engine.step()
    engine._collect()
    return did


def busy(engine) -> bool:
    return bool(engine._occupants or engine._prefilling
                or engine.queue_depth() or engine._in_flight is not None)


def run(engine, requests, serially: bool = False, spread: int = 1,
        during=None) -> dict:
    """Submit `requests` one every `spread` steps, then step to the end
    (`during(engine, step_index)` is called before every step). Returns
    {"handles", "tokens" (what each handle received, an unfinished or failed
    one's too), "spans" (every `serve_decode_step`, the tail flushed),
    "restarts" (ticks dispatched with none in flight), "sums"}."""
    step = (lambda: step_serially(engine)) if serially else engine.step
    spans, restarts, steps = [], [], [0]
    listener = lambda rec: spans.append(dict(rec))
    real_dispatch = engine._dispatch_tick

    def dispatch(before):
        tick = real_dispatch(before)
        if tick is not None and before is None:
            restarts.append(steps[0])
        return tick

    def stepped():
        if during is not None:
            during(engine, steps[0])
        steps[0] += 1
        return step()

    engine._dispatch_tick = dispatch
    trace.recorder().add_listener(listener)
    try:
        handles = []
        for request in requests:
            handles.append(engine.submit(request))
            for _ in range(spread):
                stepped()
        while busy(engine):
            stepped()
            assert steps[0] < 2000, "the engine does not come to an end"
        engine.step()                       # the idle boundary flushes
    finally:
        trace.recorder().remove_listener(listener)
        engine._dispatch_tick = real_dispatch
    ticks = [s for s in spans if s["name"] == "serve_decode_step"]
    names = SUMMED + tuple(engine._family.counters)
    return {"handles": handles, "spans": ticks,
            "tokens": [list(h.tokens_out) for h in handles],
            "restarts": len(restarts),
            "sums": {k: sum(s[k] for s in ticks) for k in names}}


def check_the_spans(result: dict, serially: bool = False) -> None:
    """What holds on every `serve_decode_step` span of a run, whatever it
    served: one copy each way a tick, and a tick is ahead unless it restarted
    the pipeline (in the serial order none is); over the run, every row-tick
    the device ran is a token a handle received after its first, the
    overruns apart."""
    for s in result["spans"]:
        assert s["h2d_copies"] == s["d2h_copies"] == s["ticks"]
        assert 0 <= s["ticks_ahead"] <= s["ticks"]
        assert 0 <= s["rows_overrun"] <= s["tokens"]
    sums = result["sums"]
    assert sums["ticks_ahead"] == (
        0 if serially else sums["ticks"] - result["restarts"])
    delivered = sum(max(len(t) - 1, 0) for t in result["tokens"])
    assert sums["tokens"] - sums["rows_overrun"] == delivered


def both_orders(make_engine, make_requests, **how) -> tuple:
    """The same requests through a fresh engine in the serial order and
    through another with a tick in flight: (serial result, pipelined
    result), after the checks every such pair must pass: the streams are
    bit-equal, the spans' rules hold on both, and the serial order never
    overruns. (A slot freed at a collection is free for admission a step
    later than in the serial order, so a tick's ROWS may differ between the
    two and with them the counters that depend on who shares a tick: a
    family's test sets its per-row counters against the host's own count.)"""
    serial = run(make_engine(), make_requests(), serially=True, **how)
    ahead = run(make_engine(), make_requests(), **how)
    assert ahead["tokens"] == serial["tokens"]
    check_the_spans(serial, serially=True)
    check_the_spans(ahead)
    assert serial["sums"]["rows_overrun"] == 0
    return serial, ahead


def eos_of(tokens: list, least: int = 2) -> tuple:
    """(index, token): the first token of a served stream from position
    `least` on that occurs nowhere before it, and is not the stream's last:
    a request that asks for it as its `eos_token_id` ends there, a tick
    before its budget."""
    for i in range(least, len(tokens) - 1):
        if tokens[i] not in tokens[:i]:
            return i, tokens[i]
    raise AssertionError(f"no token of {tokens} can serve as an eos")


def requests_of(prompts, budgets, knobs, eos=None) -> list:
    """A request a prompt: `budgets[i]` new tokens, `knobs[i]` its sampling
    knobs, `eos[i]` its `eos_token_id` where given; seeds 0, 1, ..."""
    from llama_pipeline_parallel_tpu.models import family as families

    return [serve.ServeRequest(
        input_ids=prompt, seed=i, gen=families.GenerationConfig(
            max_new_tokens=n, eos_token_id=(eos or {}).get(i), **kw))
        for i, (prompt, n, kw) in enumerate(zip(prompts, budgets, knobs))]


def contexts_run(result: dict, prompts, overran=()) -> list:
    """The context (positions a row sees) of every row-tick the device ran:
    a request of n prompt tokens whose handle received m tokens went through
    m - 1 ticks, the j-th at n + j; one of `overran` through one more."""
    out = []
    for i, (prompt, tokens) in enumerate(zip(prompts, result["tokens"])):
        ticks = len(tokens) - 1 + (i in overran)
        out.extend(len(prompt) + j for j in range(1, ticks + 1))
    return out
