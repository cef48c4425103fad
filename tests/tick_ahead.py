"""Helpers for the tests of the engine's one decode tick and one prefill
unit in flight (`serve/engine.py`): the SERIAL order they replaced, kept
here as the reference, a stepped run of either order with its spans, and the
checks every family's serving tests make of the two."""

import jax

from llama_pipeline_parallel_tpu import serve
from llama_pipeline_parallel_tpu.utils import trace

SUMMED = ("ticks", "tokens", "ticks_ahead", "rows_overrun", "rows_joined_fed",
          "h2d_copies", "d2h_copies")


class Counting:
    """A module as the engine sees it, whose `asarray` counts and lets
    through the transfers of `kind` made inside a tick; every other
    attribute is the module's own."""

    def __init__(self, module, kind, ticking):
        self._module, self._kind, self._ticking = module, kind, ticking
        self.seen = []

    def __getattr__(self, name):
        return getattr(self._module, name)

    def asarray(self, a, *args, **kwargs):
        if self._ticking and isinstance(a, self._kind):
            self.seen.append(a)
            with jax.transfer_guard("allow"):
                return self._module.asarray(a, *args, **kwargs)
        return self._module.asarray(a, *args, **kwargs)


def step_serially(engine) -> bool:
    """The engine's order before it kept a tick in flight: admit, stage,
    dispatch, wait and emit in turn. A step, then the collection of the tick
    it enqueued, so the next tick is staged from tokens and keys the host
    has read and no row is ever fed from the tick before on the device."""
    did = engine.step()
    engine._collect()
    return did


def units_read_at_once(engine):
    """The engine's order before it kept a prefill unit in flight: every
    unit is read as soon as it is handed over, so a row joins its first tick
    with a token and a key the host has read. Returns the undo."""
    real = engine._run_prefill_chunk

    def unit_then_read(pf, cost):
        finished = real(pf, cost)
        engine._collect_unit()
        return finished

    engine._run_prefill_chunk = unit_then_read
    return lambda: setattr(engine, "_run_prefill_chunk", real)


def busy(engine) -> bool:
    return bool(engine._occupants or engine._prefilling
                or engine.queue_depth() or engine._in_flight is not None)


def run(engine, requests, serially: bool = False, spread: int = 1,
        during=None) -> dict:
    """Submit `requests` one every `spread` steps, then step to the end
    (`during(engine, step_index)` is called before every step). Returns
    {"handles", "tokens" (what each handle received, an unfinished or failed
    one's too), "spans" (every `serve_decode_step`, the tail flushed),
    "units" (every `serve_prefill`), "restarts" (ticks dispatched with none
    in flight), "sums"}. `serially`: no tick and no prefill unit is ever in
    flight when the host stages the next."""
    step = (lambda: step_serially(engine)) if serially else engine.step
    undo = units_read_at_once(engine) if serially else lambda: None
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
        undo()
    ticks = [s for s in spans if s["name"] == "serve_decode_step"]
    names = SUMMED + tuple(engine._family.counters)
    return {"handles": handles, "spans": ticks,
            "units": [s for s in spans if s["name"] == "serve_prefill"],
            "tokens": [list(h.tokens_out) for h in handles],
            "restarts": len(restarts),
            "sums": {k: sum(s[k] for s in ticks) for k in names}}


def check_the_spans(result: dict, serially: bool = False) -> None:
    """What holds on every `serve_decode_step` span of a run, whatever it
    served: one copy each way a tick, and a tick is ahead unless it restarted
    the pipeline (in the serial order none is); over the run, every row-tick
    the device ran is a token a handle received after its first, the
    overruns apart; a prefill unit makes at most one read, after the next
    hand-over (in the serial order: before it), and a row whose first token
    the host had not read joined its tick fed (in the serial order none)."""
    for u in result["units"]:
        assert u["reads"] in (0, 1) and u["ahead"] in (0, 1)
        assert u["ahead"] == 0 or not serially
    joined = sum(len(t) > 1 for t in result["tokens"])
    assert serially or sum(u["ahead"] for u in result["units"]) >= joined
    # the row of a step's LAST unit joins the step's tick with its token
    # unread (the rows of the units before it in a burst were read since)
    assert result["sums"]["rows_joined_fed"] <= (
        0 if serially else len(result["units"]))
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
