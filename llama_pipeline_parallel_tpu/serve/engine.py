"""Continuous-batching inference engine (docs/SERVING.md).

The admission/batch scheduler over the page pool (`pages.PagedKVCache`):
requests enter a bounded FIFO wait queue (`submit`, thread-safe — overload
raises `ServeOverloaded`, a worst-case page demand the pool cannot cover
raises `ServePagesExhausted`, both mapped to HTTP 429 + Retry-After by the
frontend), and at every `step()` boundary the engine

1. **admits** queued requests into free slots — each admission left-pads
   the prompt to the smallest configured bucket, runs `prefill_prompt`
   (one compile per bucket), samples the request's FIRST token with its own
   rng chain, and splices the row into the slot's pages — prefill-
   then-join. With `prefill_chunk_tokens` set, a bucket larger than the
   budget instead prefills INCREMENTALLY: at most that many prompt tokens
   per tick (`paged_prefill_chunk`), so in-flight decodes keep producing a
   token every tick — chunked batched prefill, no full-prefill stall. Such
   a row starts at the first chunk that holds a real token: the chunks in
   front of it hold nothing but left pads, whose places the slot's zeroed
   mask row already hides, and are never handed to the device
   (`chunks_skipped`; the last chunk always runs, it yields the logits);
2. stages and enqueues ONE `paged_decode_step` over every slot (static
   shape, one compile) — per-row write positions, rope positions, rng
   chains, and sampling knobs, so requests at different depths and with
   different `GenerationConfig`s share the tick;
3. collects the tick it enqueued at the boundary BEFORE: distributes its
   sampled tokens to their streaming handles and frees the slots of
   finished rows (eos or budget) immediately — pages and reservations
   included — so the next boundary can admit again.

One decode tick is always in flight: tick k is handed to the device while
tick k-1 still runs, its rows' tokens and rng keys fed back on the device
(`models/tick_io.py`), so the host's share of a tick (fetch, emit, the loop,
staging, the copy in, the call) runs under the device's. What follows from
it: a client sees a token one dispatch later than the device made it; a row
that ends by `eos_token_id` is seen one tick late, and its row of the tick
already enqueued is an OVERRUN (run, written inside the pages its
reservation covers, its token discarded: `rows_overrun`); a row that ends by
length is known a tick ahead and left out. `shutdown()`, an idle boundary
and a cancellation collect the tick in flight first, so every row-tick the
device ran is a token a handle received, the overruns apart.

A family that DRAFTS (`models/family.py` `drafts`; the model's configuration
says so, nothing here does) runs a verify tick that emits one OR TWO tokens a
row: the row's own and, where the module's draft was right, the one after
it, the same stream as one-token ticks. The host then does not know a tick
ahead how far a row in flight advanced: it stages upper bounds (two places a
tick in flight), grows the row's pages for them, and the program takes the
row's `pos` and `write_pos` from the tick before on the device, as it takes
token and key; the host learns them at the read (`_collect_tick`). A row that
reaches its eos or its budget on the first of two tokens drops the second;
one whose budget ran out inside the tick in flight overruns once, as an eos
does, inside the one place past its budget that its reservation covers.

A prefill unit is not waited for either: it is handed to the device, and the
host reads its result (its counters; a final unit's first token and rng
chain) only after the NEXT hand-over is enqueued behind it, the next unit of
the step's burst or the step's decode tick. The first token is drawn on the
device and fed to that tick there (`tick_io.first_token`), so the row joins
the tick before the host has seen its token; a first token that is the eos
is seen one tick late and overruns once, like any other eos. A request's
first token reaches its handle one hand-over later than the device made it,
never later: no unit is in flight across a step boundary.

The engine's thread accounts for its own seconds (`_HostThread`): it waits
for the device in two places and nowhere else, `serve_tick_block` and a
unit's deferred read, and everything else is the host working. The pending
`serve_decode_step` span carries the partition of the steps' wall
(`HOST_SUMS`) and what held the thread outside those waits and inside them
(the collector, the compiler: `utils/trace.HostWatch`), and a phase of host
work of `STALL_S` or more is a `serve_host_stall` record that names its
phase and its causes (docs/OBSERVABILITY.md "The engine's thread").

Token parity contract: a request served here emits EXACTLY the tokens of an
independent `generate(params, padded_prompt, cfg, gen,
rng=PRNGKey(request.seed))` call (prompt left-padded to the same bucket) —
the decode-layer entry points reproduce generate()'s arithmetic per row,
and tests/test_serving.py pins it.

Per-request determinism: the rng chain is derived from `request.seed` only
— admission order, co-tenants, and slot index cannot perturb a request's
tokens.

This module is deliberately host-side and single-stepper: `step()` is
driven either by `ServeLoop` (a background thread for in-process use), by
tools/serve.py's main loop (so serve spans land in the RunClock's `serve`
bucket), or manually by tests.
"""

from __future__ import annotations

import dataclasses
import itertools
import queue as queue_mod
import threading
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from llama_pipeline_parallel_tpu.models import tick_io
from llama_pipeline_parallel_tpu.models.family import (
    GenerationConfig,
    family_of,
    sample_rowwise,
    sampler_branch,
)
from llama_pipeline_parallel_tpu.ops.paged_attention import pages_per_step
from llama_pipeline_parallel_tpu.serve.pages import PagedKVCache
from llama_pipeline_parallel_tpu.serve.reqtrace import TraceContext
from llama_pipeline_parallel_tpu.serve.telemetry import SLOStats, retry_after_s
from llama_pipeline_parallel_tpu.utils import trace
from llama_pipeline_parallel_tpu.utils.logging import get_logger

logger = get_logger(__name__)

_REQUEST_IDS = itertools.count()

# seconds summed over a `serve_decode_step` span's ticks: the four phases of
# each tick (`stage` and `dispatch` at the boundary that enqueued it, `wait`
# and `emit` at the next, where it was collected), and the two parts of
# dispatch that a metric reads (the rest of it grows the pages and adopts the
# outputs)
TICK_SUMS = ("stage_s", "dispatch_s", "wait_s", "emit_s",
             "h2d_s", "enqueue_s")

# seconds and counts of the engine's own THREAD, summed over a
# `serve_decode_step` span's steps and flushed with `TICK_SUMS`
# (`_HostThread`; docs/OBSERVABILITY.md "The engine's thread"). The thread
# waits for the device in two places, `serve_tick_block` and
# `serve_prefill_first`; everything else is the host working. First the
# seconds the tick's phases lacked: over a run `admit_s + stage_s + dispatch_s
# + wait_s + unit_wait_s + emit_s + loop_s` is `step_s` (`loop_s`: the thread
# under no annotation of the engine: from a step that did work to the start of
# the next, and inside a step the folding of a tick into the span and what
# follows the last phase; an idle wait is not the loop's time), and `block_s`
# is the `serve_tick_block` part of `wait_s`. Then what held the thread
# (`utils/trace.HostWatch`): the collector OUTSIDE the two waits (`gc_s`) and
# inside them (`wait_gc_s`), the compiler over the steps.
HOST_SUMS = ("admit_s", "unit_wait_s", "block_s", "loop_s", "step_s",
             "gc_s", "compile_s", "wait_gc_s")
HOST_COUNTS = ("steps", "gc_collections", "gc_gen2", "compiles",
               "ticks_found_ready")
# one phase of host work this long, or a device wait in which other threads'
# collections ran for this long, is a stall record (`serve_host_stall`): each
# such phase costs 0.3 to 3 ms in the benchmark's cells, a unit's hand-over
# about 3
STALL_S = 0.020
# a `block_until_ready` that returns within this found its tick READY: the
# device had finished it before the host came for it. Counted
# (`ticks_found_ready`) where no prefill unit was enqueued behind the tick:
# then the device had nothing but the next tick to go on with, which the host
# had enqueued a moment before, so the host set the pace. Behind a unit the
# device was busy with the unit and the host was merely late. A block on a
# ready array costs 1.4 us on the v5e's host, 2.9 at its 99th percentile and
# 39 at the worst of 20,000 (PERF.md section 6, PR 50)
READY_S = 50e-6
# stall records carried on one `serve_decode_step` span; the rest are counted
MAX_STALLS = 16
# the phase of a stall record under no annotation of the engine
LOOP = "loop"


class ServeOverloaded(RuntimeError):
    """Wait queue full: the backpressure signal (HTTP 429 upstream).
    `retry_after_s` is a coarse retry hint the frontend forwards as the
    Retry-After header."""

    retry_after_s: float = 1.0


class ServePagesExhausted(ServeOverloaded):
    """The free-page pool cannot cover this request's worst-case page
    demand on top of everything already promised: refuse NOW (HTTP 429 +
    Retry-After) instead of admitting and failing mid-decode."""

    def __init__(self, message: str, retry_after_s: float = 1.0):
        super().__init__(message)
        self.retry_after_s = retry_after_s


class EngineShutdown(RuntimeError):
    """The engine is shut down: nothing will ever serve this request
    (HTTP 503 upstream — the client must go to another replica)."""


class RequestRejected(ValueError):
    """Request can never be served by this engine's shape budget."""


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine shape/scheduling budget, fixed at construction (the page pool
    is allocated once from it)."""

    max_slots: int = 8
    max_len: int = 2048                # per-slot KV capacity (prompt + new)
    prompt_buckets: tuple = (64, 128, 256, 512, 1024)
    max_queue: int = 64                # bounded wait queue (backpressure)
    metrics_every: int = 16            # completions per serving metrics line
    # decode ticks per aggregated serve_decode_step span line: ticks run at
    # token rate (orders of magnitude denser than train steps), so per-tick
    # jsonl lines would grow spans.jsonl without bound on a long-lived
    # replica; durations still accumulate exactly (the RunClock listener
    # sees the aggregate), only the file granularity coarsens
    decode_span_every: int = 32
    # -- the page pool (docs/SERVING.md "Paged KV cache") ------------------
    # one legal value, read by nothing: the benchmark's workload files pass
    # the key, and it goes when they drop it (ROADMAP.md, debt (a) of PR 28)
    kv_cache: str = "paged"
    page_size: int = 64                # tokens per KV page
    # pool size; None = one max_len row a slot (max_slots * max_len tokens)
    num_pages: int | None = None
    kv_quant: str = "fp"               # "fp" | "int8" pages
    # per-tick prefill token budget AND chunk granularity: 0 = whole-prompt
    # admissions; > 0 = a bucket larger than this prefills in pieces of
    # exactly this many tokens, interleaved with decode ticks
    prefill_chunk_tokens: int = 0
    # prefix caching (docs/SERVING.md "Prefix caching"):
    # share physical pages between requests with identical padded prompt
    # prefixes — cache-hit admissions skip the shared span's prefill and
    # reserve only their new pages. Off (the default) keeps the engine
    # byte-identical to the plain paged scheduler.
    prefix_cache: bool = False

    def __post_init__(self) -> None:
        if self.decode_span_every < 1:
            raise ValueError("decode_span_every must be >= 1")
        if not self.prompt_buckets:
            raise ValueError("prompt_buckets must be non-empty")
        if tuple(sorted(self.prompt_buckets)) != tuple(self.prompt_buckets):
            raise ValueError(f"prompt_buckets must be ascending, got "
                             f"{self.prompt_buckets}")
        if min(self.prompt_buckets) < 1:
            raise ValueError("prompt buckets must be >= 1")
        if min(self.prompt_buckets) + 1 > self.max_len:
            raise ValueError(
                f"max_len {self.max_len} cannot hold even the smallest "
                f"bucket {min(self.prompt_buckets)} plus one generated token")
        if self.max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        if self.kv_cache != "paged":
            raise ValueError(
                f"kv_cache: {self.kv_cache!r}: the paged pool is the "
                f"engine's only KV store since PR 28 (the dense slot cache "
                f"is gone); drop the key")
        if self.kv_quant not in ("fp", "int8"):
            raise ValueError(f"kv_quant must be 'fp' or 'int8', got "
                             f"{self.kv_quant!r}")
        if self.page_size < 1:
            raise ValueError("page_size must be >= 1")
        if self.max_len % self.page_size:
            raise ValueError(f"max_len {self.max_len} must be a multiple of "
                             f"page_size {self.page_size}")
        for b in self.prompt_buckets:
            if b % self.page_size:
                raise ValueError(f"prompt bucket {b} must be a multiple of "
                                 f"page_size {self.page_size} (page-aligned "
                                 f"prefill writes)")
        if self.prefill_chunk_tokens:
            if self.prefill_chunk_tokens % self.page_size:
                raise ValueError(
                    f"prefill_chunk_tokens {self.prefill_chunk_tokens} must "
                    f"be a multiple of page_size {self.page_size}")
            for b in self.prompt_buckets:
                if b > self.prefill_chunk_tokens and \
                        b % self.prefill_chunk_tokens:
                    raise ValueError(
                        f"bucket {b} must be a multiple of "
                        f"prefill_chunk_tokens {self.prefill_chunk_tokens} "
                        f"(static chunk shapes)")
        # whether `num_pages` holds one full-length request is the page
        # manager's check: what such a request demands is its family's to
        # say (serve/pages.py)

    @property
    def resolved_num_pages(self) -> int:
        """The pool size: as configured, or one `max_len` row a slot (the
        logical tokens of a `[max_slots, max_len]` reservation) where the
        family is not known; `pool_pages` asks the family."""
        if self.num_pages is not None:
            return self.num_pages
        return self.max_slots * self.max_len // self.page_size

    def pool_pages(self, cfg) -> int:
        """The pool size for a model: as configured, or one full-length
        request a slot as the model's family states its demand."""
        if self.num_pages is not None:
            return self.num_pages
        return self.max_slots * len(family_of(cfg).table_columns(
            cfg, self.max_len, self.max_len, self.page_size))


@dataclasses.dataclass
class ServeRequest:
    input_ids: list
    gen: GenerationConfig = dataclasses.field(default_factory=GenerationConfig)
    seed: int = 0
    request_id: str = dataclasses.field(
        default_factory=lambda: f"req-{next(_REQUEST_IDS)}")
    arrival: float = dataclasses.field(default_factory=time.time)
    # SLO class for per-tenant attribution (telemetry.SLOStats `tenants`
    # map, fleet rollup, request traces); None = unattributed
    tenant: str | None = None
    # W3C trace context (serve/reqtrace.TraceContext): the frontend parses
    # an incoming `traceparent` header into one; `submit()` mints one when
    # absent, so every submitted request has a trace id whether or not a
    # RequestTraceRecorder is attached
    trace: TraceContext | None = None
    # gateway-tier dispatch attribution (serve/gateway.py): when the
    # request arrived through the routing tier this carries
    # {"attempt": n, "replay": bool, "hedge": bool} — copied verbatim onto
    # the request-trace record so one trace_id joins the gateway journal
    # row to the replica-side attempt that actually served it
    gateway: dict | None = None


class RequestHandle:
    """The caller's end of a submitted request: a streaming token iterator
    plus a blocking result. Thread-safe — the engine loop pushes, frontend
    threads consume."""

    _DONE = object()

    def __init__(self, request: ServeRequest):
        self.request = request
        self.tokens_out: list[int] = []
        self.error: Exception | None = None
        # padded-row positions served from the prefix cache (0 = cold /
        # cache off) — set at submit, read by traffic tooling hit-rate math
        self.prefix_cached_tokens = 0
        self._q: queue_mod.Queue = queue_mod.Queue()
        self._done = threading.Event()

    # -- engine side -------------------------------------------------------

    def _push(self, token: int) -> None:
        self.tokens_out.append(token)
        self._q.put(token)

    def _finish(self, error: Exception | None = None) -> None:
        self.error = error
        self._done.set()
        self._q.put(self._DONE)

    # -- caller side -------------------------------------------------------

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def tokens(self, timeout: float | None = None):
        """Yield tokens as they are generated; raises the request's error
        (if any) after the stream ends. `timeout` bounds the wait for EACH
        token, not the whole stream."""
        while True:
            item = self._q.get(timeout=timeout)
            if item is self._DONE:
                break
            yield item
        if self.error is not None:
            raise self.error

    def result(self, timeout: float | None = None) -> list[int]:
        """All tokens, blocking until the request completes."""
        if not self._done.wait(timeout=timeout):
            raise TimeoutError(
                f"request {self.request.request_id} not done in {timeout}s")
        if self.error is not None:
            raise self.error
        return list(self.tokens_out)


@dataclasses.dataclass
class _Running:
    """Host-side state of one occupied slot."""

    request: ServeRequest
    handle: RequestHandle
    # the last token the host has READ and the [2] uint32 rng chain after it:
    # the next tick's input for a row in no tick in flight (a row in one is
    # fed the tick's own on the device)
    token: int
    key: np.ndarray
    pos: int                 # rope position of the next tick to DISPATCH
    write_pos: int           # its cache row
    emitted: int             # tokens pushed to the handle
    t_admit: float
    t_first: float
    in_flight: int = 0       # ticks dispatched with this row, not collected
    finished: bool = False   # left the batch: a row still in flight overran
    # its first token is its prefill unit's result, which the host has not
    # read yet: a tick it joins meanwhile is fed token and key on the device
    first_unread: bool = False
    # a drafting family: `pos` and `write_pos` are the row's as of the last
    # tick READ (a tick in flight may advance them by up to two), and `limit`
    # the places of its row that its reservation covers
    limit: int = 0


@dataclasses.dataclass
class _Tick:
    """A decode tick the device was handed and the host has not read."""

    fetch: jax.Array         # its `tick_io.pack_result`, still on the device
    rows: list               # [(slot, _Running)]: the rows it decodes
    ts: float                # wall clock at its dispatch
    ahead: bool              # enqueued while the tick before was in flight
    pages: tuple             # (live, table, steps) of its rows' logical pages
    branch: int              # `sampler_branch` of its staged knobs
    joined_fed: int          # rows fed their FIRST token from a unit in flight
    stage_s: float
    dispatch_s: float
    h2d_s: float
    enqueue_s: float
    h2d_copies: int          # transfers in, counted where they were made


@dataclasses.dataclass
class _Prefilling:
    """Host-side state of a slot whose prompt is still prefilling (chunked
    admissions; at most one request is mid-prefill at a time —
    FIFO order makes a second partial pointless)."""

    request: ServeRequest
    handle: RequestHandle
    slot: int
    bucket: int
    ids: np.ndarray          # [1, bucket] left-padded prompt
    mask: np.ndarray         # [1, bucket]
    positions: np.ndarray    # [1, bucket] rope positions
    done: int                # places of the bucket behind the prefill
    t_admit: float
    # where `done` started, and the leading chunks of nothing but left pads
    # that a cold chunked row never ran to start there (on the span of the
    # row's first unit)
    start: int = 0
    skipped: int = 0
    # prefix cache: the submit-time verdict (None = cache off), and
    # whether positions [0, done) at start came from shared pages — a warm
    # prefill recomputes only its tail via decode.paged_prefill_span
    match: object = None
    warm: bool = False


@dataclasses.dataclass
class _Unit:
    """A prefill unit the device was handed and the host has not read."""

    pf: _Prefilling
    # its one read, still on the device: the family's counters of the unit,
    # behind the first token and the rng chain's two words where the unit
    # was its request's last (`tick_io.first_token`); None: nothing to read
    vector: jax.Array | None
    row: _Running | None     # the row a final unit made, its token unread
    ts: float                # wall clock at its hand-over
    t0: float                # `perf_counter` then
    handover_s: float        # host seconds the hand-over took
    offset: int
    cost: int
    first: bool              # of its request's units


class _HostThread:
    """The engine thread's account of its own seconds: `HOST_SUMS` and
    `HOST_COUNTS` of the pending `serve_decode_step` span, and the stall
    records.

    The thread's time is a chain of STRETCHES of host work, each ended by a
    device wait (`enter_wait` .. `leave_wait`) or by an idle boundary. The
    collector's seconds (`utils/trace.HostWatch.gc_s`) are read on either
    side of a wait: what they grew by over it is `wait_gc_s` (other
    threads' collections: this one slept) and is kept OUT of `gc_s`, which
    `flush` takes, like the watch's other totals, as the growth since
    `_base`, their values at the last flush moved forward by what the waits
    and the idle time saw. A stretch's phases are appended to `phases` as
    (innermost annotation, `perf_counter` at its end) by the code that reads
    the clock there anyway; a stretch shorter than `STALL_S` (one
    comparison, at the wait that ends it) is dropped unread, a longer one is
    searched for a phase that long, which makes a record with what the
    watch's kept events say held THAT phase (`HostWatch.held`)."""

    def __init__(self, context):
        self._context = context         # () -> {"step", "active", "units"}
        self.watch = trace.host_watch()
        self.phases: list = []
        self.stalls: list = []
        self.stalls_dropped = 0
        # running totals, for the metrics line
        self.host_stalls = 0
        self.host_stall_s = 0.0
        self.ticks_found_ready = 0
        # None outside admission; inside it the annotation over the thread:
        # `LOOP` while abandoned requests are cancelled, then `serve_admit`
        self.admitting: str | None = None
        # where the step's last named phase ended: from there to the next
        # one's start, or the step's end, the thread is under no annotation,
        # which is `loop` too (`unnamed_until`)
        self.tail = 0.0
        # seconds other sums hold (a collected tick's wait and emit, a unit's
        # deferred read): what `admit_s` leaves out of its interval
        self.elsewhere = 0.0
        self._elsewhere0 = 0.0
        self._zero()
        self._mark: float | None = None     # end of the last step that worked
        self._t_step: float | None = None   # where the step's wall is counted from
        self._stretch_t0 = float("inf")     # no stretch yet: none too long
        # the watch's totals at the last flush, moved past the idle time
        # (and `gc_s` past the waits); and as they stood when the thread
        # last went idle
        self._base = list(self._totals())
        self._idle = self._totals()

    def _zero(self) -> None:
        self.admit_s = self.unit_wait_s = self.block_s = 0.0
        self.loop_s = self.step_s = self.wait_gc_s = 0.0
        self.steps = self.found_ready = 0

    def _totals(self) -> tuple:
        w = self.watch
        return w.gc_s, w.compile_s, w.gc_collections, w.gc_gen2, w.compiles

    # -- a step ------------------------------------------------------------

    def begin(self) -> None:
        """A step begins, with the cancellations: admission's seconds, under
        no annotation. After a step that did work the stretch goes on (the
        time since is `loop`); after an idle wait a new one begins here, and
        what the watch counted meanwhile is not the steps'."""
        now = time.perf_counter()
        if self._mark is None:
            self._base = [base + now_ - then for base, now_, then in zip(
                self._base, self._totals(), self._idle)]
            self._stretch_t0 = now
            self.phases.clear()
        else:
            self.loop_s += now - self._mark
            self.step_s += now - self._mark
            self.phases.append((LOOP, now))
        self._t_step = now
        self._elsewhere0 = self.elsewhere
        self.admitting = LOOP

    def annotated(self) -> None:
        """`serve_admit` begins: the cancellations are done."""
        self.phases.append((LOOP, time.perf_counter()))
        self.admitting = trace.SERVE_ADMIT

    def admitted(self) -> None:
        """`serve_admit` ended: the wall since the step began less what a
        collection inside it (a cancellation's, a burst's deferred reads)
        already gave other sums."""
        now = time.perf_counter()
        self.admit_s += (now - self._t_step) - (self.elsewhere
                                                - self._elsewhere0)
        self.phases.append((self.admitting, now))
        self.admitting = None
        self.tail = now

    def unnamed_until(self, now: float) -> None:
        """A named phase begins at `now`: the step's time since the last one
        ended was under no annotation (inside admission everything is
        admission's; outside a step nothing is the steps')."""
        if self.admitting is None and self._t_step is not None:
            self.loop_s += now - self.tail

    def end(self, worked: bool) -> None:
        """The step ends. `worked`: the time to the next step's start is
        `loop`; else the thread goes idle and the stretch ends here."""
        now = time.perf_counter()
        self.step_s += now - self._t_step
        self.loop_s += now - self.tail
        self._t_step = None
        if worked:
            self.steps += 1
            self._mark = now
            return
        self._mark = None
        self.phases.append((LOOP, now))
        self._idle = self._totals()
        self._end_stretch(now)

    # -- a device wait -------------------------------------------------------

    def enter_wait(self, now: float) -> float:
        """The stretch ends at a device wait (`now`: the clock where the
        wait's code began; the time since the last boundary is under the
        annotation it was under before). Returns the collector's seconds, to
        hand `leave_wait`."""
        self.phases.append((self.admitting or LOOP, now))
        self._end_stretch(now)
        return self.watch.gc_s

    def leave_wait(self, phase: str, before: float, t_wait: float) -> float:
        """The wait under `phase` returned; a new stretch begins. What the
        collector's seconds grew by over the wait is the wait's (another
        thread's collections: this one slept, and could not come back from
        the wait until the collecting thread let the interpreter lock go),
        and `STALL_S` of it is a record that says `in_wait`: the wait's own
        length is the device's work and proves nothing. Returns the clock at
        the wait's end."""
        now = time.perf_counter()
        gc_s = self.watch.gc_s - before
        self.wait_gc_s += gc_s
        self._base[0] += gc_s
        if gc_s >= STALL_S:
            held = min(now - t_wait, gc_s)
            self._record(phase, now - held, now, {
                "in_wait": 1, "wait_gc_s": gc_s, "other_s": 0.0})
        self._stretch_t0 = now
        return now

    def tick_blocked(self, t_entry: float, t_block: float, t_blocked: float,
                     behind: bool) -> None:
        """`serve_tick_block` ran from `t_block` to `t_blocked`, in a wait
        that began at `t_entry`; `behind`: a prefill unit is enqueued behind
        the tick, so the device had work whenever the tick ended."""
        self.block_s += t_blocked - t_entry
        if t_blocked - t_block < READY_S and not behind:
            self.found_ready += 1
            self.ticks_found_ready += 1

    # -- stalls --------------------------------------------------------------

    def _end_stretch(self, now: float) -> None:
        if now - self._stretch_t0 >= STALL_S:
            start = self._stretch_t0
            for phase, end in self.phases:
                if end - start >= STALL_S:
                    self._record(phase, start, end,
                                 self.watch.held(start, end))
                start = end
        self.phases.clear()

    def _record(self, phase: str, start: float, end: float,
                fields: dict) -> None:
        """One stall record: a `serve_host_stall` line and a warning now, and
        a place on the pending `serve_decode_step` span (`start`, `end` on
        `perf_counter`; `ts` is the wall clock at its start)."""
        ts = time.time() - (time.perf_counter() - start)
        rec = {"phase": phase, "ts": ts, "dur": end - start, **fields,
               **self._context()}
        self.host_stalls += 1
        self.host_stall_s += rec["dur"]
        if len(self.stalls) < MAX_STALLS:
            self.stalls.append(rec)
        else:
            self.stalls_dropped += 1
        trace.recorder().emit("serve_host_stall", **rec)
        logger.warning(
            "host stall: %.1f ms under %s at step %d%s: %s",
            1e3 * rec["dur"], phase, rec["step"],
            " (in a device wait)" if rec.get("in_wait") else "",
            ", ".join(f"{k}={v:.4g}" for k, v in rec.items()
                      if k not in ("phase", "ts", "dur", "step", "in_wait")))

    # -- the span ------------------------------------------------------------

    def flush(self) -> dict:
        """`HOST_SUMS`, `HOST_COUNTS`, `gc_longest_s` (the process's longest
        pause so far: not a sum), `stalls` and `stalls_dropped` since the
        last flush; then zeroed."""
        now = time.perf_counter()
        if self._t_step is not None:    # inside a step: its wall so far
            self.step_s += now - self._t_step
            self._t_step = now
        totals = self._totals()
        gc_s, compile_s, collections, gen2, compiles = (
            max(now_ - base, 0) for now_, base in zip(totals, self._base))
        self._base = list(totals)
        out = {
            "admit_s": self.admit_s, "unit_wait_s": self.unit_wait_s,
            "block_s": self.block_s, "loop_s": self.loop_s,
            "step_s": self.step_s, "gc_s": gc_s, "compile_s": compile_s,
            "wait_gc_s": self.wait_gc_s, "steps": self.steps,
            "gc_collections": collections, "gc_gen2": gen2,
            "compiles": compiles, "ticks_found_ready": self.found_ready,
            "gc_longest_s": self.watch.gc_longest_s, "stalls": self.stalls,
            "stalls_dropped": self.stalls_dropped}
        self._zero()
        self.stalls, self.stalls_dropped = [], 0
        return out


class ServeEngine:
    def __init__(self, params: dict, cfg, serve_cfg: ServeConfig,
                 metrics_writer=None, profiler=None,
                 slo=None, reqtrace=None):
        """`params` in the CANONICAL (unstacked) layout —
        `ckpt.load_module_checkpoint` hands them out straight from any
        training checkpoint (the train->serve handoff). `cfg` is the
        model's configuration object; its family (models/family.py)
        supplies the prefill and tick programs, and what it cannot run yet
        (a model with recurrent layers: prefix cache, chunked and span
        prefill, int8 pages) is refused here, by name.
        The engine calls every program with `self.params`: the leaves the
        family's programs would convert to the compute dtype at each use,
        converted once here (`_serving_weights`), the rest `params`' own.

        Observatory hooks (docs/OBSERVABILITY.md): `slo`
        (telemetry.SLOThresholds) checks every completed request; a breach
        bumps `slo_breaches` and fires `profiler`
        (utils/profiler.TriggeredProfiler), whose bounded capture window
        advances one tick per `step()`. `reqtrace` (a
        reqtrace.RequestTraceRecorder) turns on the request observatory:
        one span tree per request written to request_trace.jsonl at
        completion (docs/SERVING.md "Request tracing"); None (the
        default) keeps every per-token path free of tracing work."""
        self.cfg = cfg
        self.serve_cfg = serve_cfg
        self._family = family_of(cfg)
        self._family.check_serve_config(
            serve_cfg.kv_quant, serve_cfg.prefill_chunk_tokens,
            serve_cfg.prefix_cache)
        self.params = self._serving_weights(params)
        self._prefix = serve_cfg.prefix_cache
        self.slots = PagedKVCache(
            cfg, serve_cfg.max_slots, serve_cfg.max_len,
            serve_cfg.page_size, serve_cfg.pool_pages(cfg),
            serve_cfg.kv_quant, prefix_cache=serve_cfg.prefix_cache)
        self.stats = SLOStats()
        self._metrics_writer = metrics_writer
        self._profiler = profiler
        self._slo = slo
        self._reqtrace = reqtrace
        # request_id -> in-flight RequestTraceBuilder (loop thread only;
        # empty forever when tracing is OFF — the structural free-ness pin)
        self._rt: dict = {}
        if reqtrace is not None:
            # attribute page-pool hand-outs to the owning slot's request
            self.slots.alloc_listener = self._on_page_alloc
        self._occupants: dict[int, _Running] = {}
        self._prefilling: deque = deque()   # chunked admissions
        self._queue: deque = deque()
        # request ids the frontend saw disconnect: cancelled at the next
        # step boundary (queued, prefilling, or decoding alike)
        self._abandoned: set = set()
        self._closed = False
        # degraded-mode admission (docs/RESILIENCE.md "Actuation"): while
        # set (draining for a deploy restart, a mid-resize tier), submits
        # shed coherently — 429 + honest Retry-After — instead of queueing
        # work this process will not live to finish
        self._degraded: str | None = None
        self._lock = threading.Lock()
        self._work = threading.Event()   # ServeLoop parks on this when idle
        # a prefilled row's first token, drawn where the unit's logits lie
        self._first_token = tick_io.first_token(sample_rowwise,
                                                serve_cfg.max_slots)
        # a verify tick may write one place past a row's budget (its last
        # token's own and a draft's behind it): room and pages for two
        self._drafts = self._family.drafts
        self._draft_room = 2 if self._drafts else 0
        self.spec_offered_total = 0
        self.spec_accepted_total = 0
        self._spec_at = tuple(self._family.counters.index(name) for name in (
            "spec_offered", "spec_accepted")) if self._drafts else ()
        # the tick's program: one staged buffer in, one fetched vector out
        self._tick_program = self._family.decode_tick
        # the tick in flight (None at start, after an idle boundary and
        # after a cancellation), and what stands for the tick before it in a
        # tick no row of which is fed from one
        self._in_flight: _Tick | None = None
        self._no_fetch = jnp.zeros(
            self._family.fetch_rows * serve_cfg.max_slots
            + len(self._family.counters), jnp.int32)
        # the prefill unit in flight (None at every step boundary), and the
        # vector the step's tick takes as `prev` once a unit of the step
        # made a row: the tick in flight's with the new rows' slots holding
        # their first tokens and keys (None: that tick's own)
        self._unit: _Unit | None = None
        self._feed: jax.Array | None = None
        self.steps = 0
        self.prefill_chunks_last_tick = 0
        self.prefill_chunks_total = 0
        self.prefill_chunks_skipped_total = 0
        self.prefill_tokens_total = 0
        # units that started from what the unit before them left in the
        # slot's row of a recurrent store (a family that counts them)
        self.prefill_state_carries_total = 0
        # pending aggregated serve_decode_step span (decode_span_every)
        self._tick_ts = 0.0
        self._tick_accum = 0.0
        self._tick_count = 0
        self._tick_active = 0
        self._tick_tokens = 0            # rows that decoded, summed over ticks
        # a drafting family: `_tick_tokens` counts the tokens the ticks MADE
        # (one or two a row-tick); the row-ticks and the tokens made and not
        # pushed (an overrun's, a second token behind an eos or the budget)
        self._tick_row_ticks = 0
        self._tick_discarded = 0
        # and the row-ticks' own record, by request in tick order: (tokens
        # made, the draft verified, the second query's first choice), the
        # span's `verify_rows`
        self._tick_verified: dict = {}
        # logical pages of the decoding rows that hold tokens, and that
        # their page-table rows have: the share of a whole-row read that
        # the tick's attention still makes (ops/paged_attention.py)
        # and the grid steps that attention walks for them: a row's live
        # pages over the pages the kernel takes under one softmax update
        self._tick_pages = [0, 0, 0]     # live, table, steps
        self._kv_step_pages = self._pages_per_kernel_step()
        # ticks whose knobs made the program's sampler draw, and sort
        # (`sampler_branch` of the staged arrays, as the program reads it)
        self._tick_sampler = [0, 0]      # sampled, sorted
        self._tick_sums = [0.0] * len(TICK_SUMS)
        # transfers the engine's thread made over the pending span's ticks,
        # counted where they are made: one each way a tick
        self._tick_copies = [0, 0]       # host to device, device to host
        # ticks enqueued behind a tick in flight; row-ticks run and discarded
        self._tick_ahead = 0
        self._tick_overrun = 0
        # rows that joined a tick with token and key fed from a unit in flight
        self._tick_joined_fed = 0
        # sums of the family's tick counters over the pending span (empty
        # for a family that returns none)
        self._tick_counters = dict.fromkeys(self._family.counters, 0)
        # the thread's own seconds and what held it, folded into the same span
        self._host = _HostThread(lambda: {
            "step": self.steps, "active": len(self._occupants),
            "units": int(self._unit is not None)})

    def _pages_per_kernel_step(self) -> int:
        """Pages the tick's attention takes under one grid step, asked of the
        kernel's own rule for this pool's shapes (`ops/paged_attention.py`);
        1 for a pool that keeps no `k` and `v` pages (the latent families,
        whose ticks read through kernels of their own)."""
        pool = self.slots.pool
        if "k" not in pool or "v" not in pool:
            return 1
        return pages_per_step(pool["k"], pool["v"],
                              self.slots.page_table.shape[1])

    def _serving_weights(self, params: dict) -> dict:
        """The tree every program of this engine is called with: the
        family's `serving_weights` where it states one (the leaves its
        programs would convert at every use, converted here once), else
        the caller's tree itself. The caller's arrays stay the caller's:
        one who keeps a float32 tree alive pays for both. Recorded as one
        `serve_weights_cast` span (docs/OBSERVABILITY.md)."""
        with trace.span("serve_weights_cast") as rec:
            held = params
            if self._family.serving_weights is not None:
                held = jax.block_until_ready(
                    self._family.serving_weights(params, self.cfg))
            given_leaves, held_leaves = (jax.tree.leaves(t)
                                         for t in (params, held))
            cast = sum(a is not b for a, b in zip(given_leaves, held_leaves))
            rec.update(
                leaves_cast=cast, leaves_kept=len(given_leaves) - cast,
                bytes_given=sum(x.nbytes for x in given_leaves),
                bytes_held=sum(x.nbytes for x in held_leaves))
        logger.info(
            "serve weights: %d leaves cast, %d kept as given; %.3f -> %.3f "
            "GB in %.3f s", rec["leaves_cast"], rec["leaves_kept"],
            rec["bytes_given"] / 1e9, rec["bytes_held"] / 1e9, rec["dur"])
        return held

    # -- submission (any thread) ------------------------------------------

    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    def _retry_after(self, request: "ServeRequest") -> float:
        """Honest Retry-After (telemetry.retry_after_s): backlog ahead of
        this request / measured drain rate + deterministic per-request
        jitter. Called with the engine lock held — the SLOStats lock is
        leaf-only, so the nesting can never invert."""
        pending = (len(self._queue) + len(self._occupants)
                   + len(self._prefilling))
        return retry_after_s(pending, self.stats.drain_rate(),
                             key=request.request_id)

    def set_degraded(self, reason: str) -> None:
        """Enter degraded-mode admission: every submit sheds with 429 +
        honest Retry-After until cleared. In-flight and already-queued
        requests keep decoding — degraded is about NEW work only."""
        with self._lock:
            self._degraded = reason

    def clear_degraded(self) -> None:
        with self._lock:
            self._degraded = None

    def pick_bucket(self, prompt_len: int, max_new_tokens: int) -> int:
        """Smallest configured bucket holding the prompt whose budget still
        fits the slot capacity; RequestRejected when none can ever."""
        for bucket in self.serve_cfg.prompt_buckets:
            if (bucket >= prompt_len
                    and bucket + max_new_tokens + self._draft_room
                    <= self.serve_cfg.max_len):
                return bucket
        raise RequestRejected(
            f"prompt of {prompt_len} tokens + {max_new_tokens} new does not "
            f"fit any bucket {self.serve_cfg.prompt_buckets} within "
            f"max_len {self.serve_cfg.max_len}" + (
                f" (a drafting model keeps {self._draft_room} places past "
                f"the budget)" if self._drafts else ""))

    def submit(self, request: ServeRequest) -> RequestHandle:
        """Enqueue a request; returns its streaming handle. Raises
        `RequestRejected` (unservable shape) or `ServeOverloaded` (wait
        queue full — shed load upstream). Both count as rejections in the
        SLO stats — an operator watching `requests_rejected` must see a
        storm of unservable shapes as clearly as queue overload."""
        if request.trace is None:
            request.trace = TraceContext.mint()
        try:
            if len(request.input_ids) == 0:
                raise RequestRejected("empty prompt")
            bucket = self.pick_bucket(len(request.input_ids),
                                      request.gen.max_new_tokens)
            demand = self.slots.demand_pages(
                bucket, request.gen.max_new_tokens + self._draft_room)
            if demand > self.slots.num_pages:
                raise RequestRejected(
                    f"worst-case demand of {demand} pages exceeds the "
                    f"pool ({self.slots.num_pages} pages of "
                    f"{self.slots.page_size} tokens)")
        except RequestRejected:
            self.stats.record_rejected(request.tenant)
            self._record_shed(request, "rejected")
            raise
        handle = RequestHandle(request)
        ids_row = mask_row = None
        if self._prefix:
            # padded-row layout is fixed at submit (bucket is), so the
            # block-hash chain can be computed here — identical to what
            # _start_prefill will rebuild
            pad = bucket - len(request.input_ids)
            ids_row = np.zeros(bucket, np.int32)
            ids_row[pad:] = np.asarray(request.input_ids, np.int32)
            mask_row = np.zeros(bucket, np.int32)
            mask_row[pad:] = 1
        with self._lock:
            if self._closed:  # a late submit must fail loudly, never hang
                # drain-time-derived Retry-After, the degraded-429 rule
                # applied to shutdown: a relaunched replica (or a sibling
                # behind the gateway) is up well within the hint, so the
                # 503 tells clients WHEN to come back instead of inviting
                # a hot retry against a dying process
                exc = EngineShutdown("serve engine shut down")
                exc.retry_after_s = self._retry_after(request)
                raise exc
            if self._degraded is not None:
                # shed, don't queue: this process is draining/mid-resize;
                # the honest hint covers the time to finish what it WILL
                # serve (a relaunched replica is up well within it)
                self.stats.record_rejected(request.tenant)
                exc = ServeOverloaded(
                    f"degraded ({self._degraded}) — retry on this or "
                    f"another replica")
                exc.retry_after_s = self._retry_after(request)
                self._record_shed(request, f"degraded:{self._degraded}",
                                  exc.retry_after_s)
                raise exc
            if len(self._queue) >= self.serve_cfg.max_queue:
                self.stats.record_rejected(request.tenant)
                exc = ServeOverloaded(
                    f"wait queue full ({self.serve_cfg.max_queue})")
                # honest backpressure: the measured time for the backlog
                # ahead to drain, not a static hint
                exc.retry_after_s = self._retry_after(request)
                self._record_shed(request, "queue_full", exc.retry_after_s)
                raise exc
            match = None
            if self._prefix:
                # cache-aware admission: shared prefix pages cost 0 new
                # pages, so the worst-case reservation shrinks by the
                # matched chain — a fully cached prompt admits into a pool
                # the cache-off math would have refused
                match = self.slots.match_and_reserve(
                    request.request_id, ids_row, mask_row, demand)
                if match is None:
                    self.stats.record_rejected(request.tenant)
                    self.stats.record_page_refused()
                    retry = self._retry_after(request)
                    self._record_shed(request, "pages_exhausted", retry)
                    raise ServePagesExhausted(
                        f"free-page pool cannot cover this request's "
                        f"worst-case demand even with prefix sharing "
                        f"({self.slots.pages_free} free, "
                        f"{self.slots.pages_reserved}/"
                        f"{self.slots.num_pages} reserved) — retry after a "
                        f"request completes", retry_after_s=retry)
                self.stats.record_prefix(match.tokens, len(match.pages),
                                         match.fork_src is not None)
                handle.prefix_cached_tokens = match.tokens
            elif not self.slots.reserve(demand):
                # refuse NOW: admitting would strand the request mid-decode
                # when the pool runs dry under it
                self.stats.record_rejected(request.tenant)
                self.stats.record_page_refused()
                retry = self._retry_after(request)
                self._record_shed(request, "pages_exhausted", retry)
                raise ServePagesExhausted(
                    f"free-page pool cannot cover the worst-case demand of "
                    f"{demand} pages ({self.slots.pages_free} free, "
                    f"{self.slots.pages_reserved}/{self.slots.num_pages} "
                    f"reserved) — retry after a request completes",
                    retry_after_s=retry)
            self._queue.append((request, handle, demand, match))
        self._work.set()
        return handle

    def _record_shed(self, request: ServeRequest, reason: str,
                     retry_after_s: float | None = None) -> None:
        """A rejection's terminal trace record (request-rate, any thread;
        no-op with tracing OFF)."""
        if self._reqtrace is not None:
            self._reqtrace.record_shed(request, reason, retry_after_s)

    def note_abandoned(self, request: ServeRequest) -> None:
        """The frontend observed a client disconnect mid-stream: bump
        `requests_abandoned`, stamp the trace, and CANCEL the request at
        the next step boundary — queued entries drop their reservation,
        an in-flight slot is freed with its unshared pages released
        (shared prefix pages just drop a refcount) and `tokens_discarded`
        recorded on the abandoned trace event. Best-effort by nature: a
        disconnect racing the final completion write may land as a
        separate late record, and up to one more token can be decoded
        before the boundary."""
        self.stats.record_abandoned(request.tenant)
        with self._lock:
            if not self._closed:
                self._abandoned.add(request.request_id)
        self._work.set()
        if self._reqtrace is None:
            return
        b = self._rt.get(request.request_id)
        if b is not None:
            b.mark_abandoned(time.time())
        else:
            self._reqtrace.record_abandoned_late(request)

    # -- scheduling (the loop thread) -------------------------------------

    def step(self) -> bool:
        """One step boundary: admit (without a chunk budget: whole prompts)
        or advance bounded prefill chunks (with one), then stage and enqueue
        one decode tick over all slots, then collect the tick enqueued at the
        boundary before and the step's last prefill unit. Returns False when
        there was nothing to do (caller may sleep)."""
        host = self._host
        host.begin()
        self._cancel_abandoned()
        # admission and prefill chunks: the loop's host work outside the
        # decode tick, one profiler event a step (`serve_prefill` nests in it)
        with trace.annotate(trace.SERVE_ADMIT):
            host.annotated()
            self._advance_prefill()
        host.admitted()
        if not self._occupants:
            # rows that overran their eos may be all a tick in flight holds;
            # a step that only prefills reads its unit here
            self._collect()
            if self._prefilling:      # prefill-only tick is still work
                self._tick_done()
                host.end(worked=True)
                return True
            self._flush_decode_span()  # idle boundary: publish the tail
            self._work.clear()
            # submit() may have raced the clear — don't sleep on a full queue
            if self.queue_depth():
                self._work.set()
            host.end(worked=False)     # an idle wait is not the loop's time
            return False
        self._decode_tick()
        self._tick_done()
        host.end(worked=True)
        return True

    def _tick_done(self) -> None:
        """Count the step; an attached profiler's bounded capture window
        advances one tick per counted step."""
        self.steps += 1
        if self._profiler is not None:
            self._profiler.observe_step(self.steps)

    # -- cancellation (loop thread; the PR 18 "no-cancellation gap") --------

    def _cancel_abandoned(self) -> None:
        """Cancel every request the frontend flagged abandoned since the
        last boundary: queued entries return their page reservation (and
        release their prefix-match pins), a mid-prefill or decoding slot is
        freed — unshared pages back to the pool, shared prefix pages drop
        one refcount — and the trace ends as `abandoned` with the token
        count the client never consumed. A decoding row is cancelled after
        the tick in flight is collected. No SLO record: the request has no
        honest completion latency."""
        if not self._abandoned:
            return
        with self._lock:
            doomed = self._abandoned
            self._abandoned = set()
            kept: deque = deque()
            queued = []
            while self._queue:
                entry = self._queue.popleft()
                (queued if entry[0].request_id in doomed
                 else kept).append(entry)
            self._queue = kept
        for request, handle, demand, match in queued:
            if match is not None:
                self.slots.cancel_match(match)
            else:
                self.slots.unreserve(demand)
            self._finish_abandoned(request, handle, discarded=0)
        for pf in [p for p in self._prefilling
                   if p.request.request_id in doomed]:
            self._prefilling.remove(pf)
            if (pf.match is not None and pf.match.fork_src is not None
                    and not pf.match.forked):
                self.slots.unpin_page(pf.match.fork_src)
            self.slots.release(pf.slot)
            self._finish_abandoned(pf.request, pf.handle,
                                   discarded=len(pf.handle.tokens_out))
        if any(r.request.request_id in doomed
               for r in self._occupants.values()):
            # the row's token of the tick in flight is still delivered (it
            # may be its last); the row is out of the NEXT tick
            self._collect()
        for slot, r in [(s, r) for s, r in self._occupants.items()
                        if r.request.request_id in doomed]:
            self._occupants.pop(slot)
            self.slots.release(slot)
            self._finish_abandoned(r.request, r.handle, discarded=r.emitted)

    def _finish_abandoned(self, request: ServeRequest, handle: RequestHandle,
                          discarded: int) -> None:
        if self._reqtrace is not None:
            b = self._rt.pop(request.request_id, None)
            if b is not None:
                self._reqtrace.write(b.build(
                    "abandoned", time.time(), tokens=len(handle.tokens_out),
                    tokens_discarded=discarded))
        handle._finish(None)

    # -- admission: the ONE prefill path -----------------------------------

    def _advance_prefill(self) -> None:
        """Spend at most `prefill_chunk_tokens` prompt tokens on prefill
        work this tick (unbounded when 0 — whole prompts are admitted):
        continue the in-progress chunked prefill first, then admit queued
        requests into free slots. A bucket no larger than the chunk budget
        prefills in ONE shot (the `prefill_prompt` + splice path); a larger
        bucket runs in chunk-sized pieces across ticks, so in-flight
        decodes keep producing a token every tick — no full-prefill stall."""
        chunk = self.serve_cfg.prefill_chunk_tokens
        spent = 0
        chunks_run = 0
        while True:
            pf = self._prefilling[0] if self._prefilling else None
            if pf is None:
                entry = self._pop_admittable()
                if entry is None:
                    break
                pf = self._start_prefill(*entry)
                if pf is None:     # start failed; its handle already failed
                    continue
                self._prefilling.append(pf)
            if pf.warm:
                # only the tail past the cached prefix costs prefill work;
                # its length is not chunk-aligned, so the last (often only)
                # span is whatever remains
                remaining = pf.bucket - pf.done
                cost = remaining if not chunk else min(chunk, remaining)
            else:
                cost = pf.bucket if not chunk or pf.bucket <= chunk else chunk
            if chunk and spent + cost > chunk:
                break              # budget for this tick is spent
            try:
                finished = self._run_prefill_chunk(pf, cost)
            except Exception as e:
                self._fail_prefill(pf, e)
                continue
            spent += cost
            chunks_run += 1
            if finished:
                self._prefilling.remove(pf)
        self.prefill_chunks_last_tick = chunks_run
        if chunks_run:
            self.prefill_chunks_total += chunks_run
            self.prefill_tokens_total += spent

    def _pop_admittable(self):
        with self._lock:
            if not self._queue:
                return None
            request, handle, demand, match = self._queue[0]
            slot = self.slots.acquire(
                request.request_id,
                demand if match is None else match.new_demand, match=match)
            if slot is None:
                return None
            self._queue.popleft()
        return request, handle, slot, demand, match

    def _start_prefill(self, request: ServeRequest, handle: RequestHandle,
                       slot: int, demand: int,
                       match=None) -> "_Prefilling | None":
        try:
            gen = request.gen
            t_admit = time.time()
            trace.recorder().emit("serve_queue_wait", ts=request.arrival,
                                  dur=t_admit - request.arrival,
                                  request=request.request_id)
            bucket = self.pick_bucket(len(request.input_ids),
                                      gen.max_new_tokens)
            pad = bucket - len(request.input_ids)
            ids = np.zeros((1, bucket), np.int32)
            ids[0, pad:] = np.asarray(request.input_ids, np.int32)
            mask = np.zeros((1, bucket), np.int32)
            mask[0, pad:] = 1
            positions = np.clip(np.cumsum(mask, axis=1) - 1, 0,
                                None).astype(np.int32)
            chunk = self.serve_cfg.prefill_chunk_tokens
            warm = match is not None and match.tokens > 0
            skipped = 0
            if warm:
                # prefix-cache hit: positions [0, match.tokens) are served
                # by shared pages already mapped into the slot's table row
                # by acquire() — mark them valid (and everything past them
                # dead) in one row rewrite, fork the divergence page
                # copy-on-write when the split lands mid-page, and start
                # the prefill clock at the divergence point
                self.slots.set_mask_row_prefix(slot, mask[0], match.tokens)
                if match.fork_src is not None:
                    self.slots.fork_page(slot, match.fork_src)
                    match.forked = True
                    self.slots.unpin_page(match.fork_src)
            elif chunk and bucket > chunk:
                # incremental writes: the previous occupant's mask must die.
                # Behind a zeroed mask row nothing of the slot's stores is
                # visible, which is all a chunk of left pads would leave:
                # the row starts at its first chunk with a token in it (the
                # last chunk runs whatever it holds: it yields the logits)
                self.slots.reset_mask_row(slot)
                skipped = min(pad // chunk, bucket // chunk - 1)
                self.prefill_chunks_skipped_total += skipped
            start = match.tokens if warm else skipped * chunk
            if self._reqtrace is not None:
                b = self._reqtrace.begin(request)
                b.admitted(t_admit, slot, bucket,
                           demand if match is None else match.new_demand)
                if warm:
                    b.prefix_hit(match.tokens, len(match.pages),
                                 match.fork_src is not None)
                self._rt[request.request_id] = b
            return _Prefilling(request=request, handle=handle, slot=slot,
                               bucket=bucket, ids=ids, mask=mask,
                               positions=positions,
                               done=start, t_admit=t_admit, start=start,
                               skipped=skipped, match=match, warm=warm)
        except Exception as e:
            logger.exception("admission of %s failed", request.request_id)
            self.stats.record_failed(request.tenant)
            if (match is not None and match.fork_src is not None
                    and not match.forked):
                self.slots.unpin_page(match.fork_src)
            self.slots.release(slot)
            self._rt.pop(request.request_id, None)
            self._record_shed(request, "admission_failed")
            handle._finish(e)
            return None

    def _run_prefill_chunk(self, pf: _Prefilling, cost: int) -> bool:
        """Hand one prefill unit of `cost` tokens for `pf` to the device,
        and only then read the unit handed over before it (the device runs
        this one meanwhile): a unit's result is read one hand-over late.
        Returns True when the request finished prefilling."""
        unit = self._hand_over_unit(pf, cost)
        self._collect_unit(ahead=True)
        self._unit = unit
        return unit.row is not None

    def _hand_over_unit(self, pf: _Prefilling, cost: int) -> _Unit:
        """Enqueue one prefill unit and wait for nothing. On the final unit
        the request's first token is drawn on the device (the same
        `sample_rowwise` and rng discipline whichever prefill produced the
        logits) and written over the row's slot of the vector the step's
        tick takes as `prev`; the row joins the decode batch with its token
        unread (`first_unread`). Everything else the tick needs of the row
        the host knows now."""
        slot = pf.slot
        ts, t0 = time.time(), time.perf_counter()
        offset0 = pf.done
        row = None
        with trace.annotate("serve_prefill"):
            if pf.warm or cost < pf.bucket:
                # a chunk, or a prefix-cache tail: recompute only [done,
                # done + cost). A tail's start and length are
                # divergence-determined, not page-aligned, so the span
                # kernel scatters per-token into the slot's (possibly
                # just-forked) pages
                program = (self._family.paged_prefill_span if pf.warm
                           else self._family.paged_prefill_chunk)
                c0, c1 = pf.done, pf.done + cost
                self.slots.ensure_capacity(slot, c1)
                # a drafting model's module keeps each position with the id
                # AFTER it: the chunk takes the next chunk's first id
                after = ({"next_id": jnp.asarray(
                    pf.ids[0, c1:c1 + 1] if c1 < pf.bucket
                    else np.full(1, -1, np.int32))} if self._drafts else {})
                t_call = time.perf_counter()
                with trace.annotate(trace.PREFILL_ENQUEUE):
                    out = program(
                        self.params, jnp.asarray(pf.ids[:, c0:c1]),
                        jnp.asarray(pf.mask[:, c0:c1]),
                        jnp.asarray(pf.positions[:, c0:c1]), self.slots.pool,
                        jnp.asarray(self.slots.page_table[slot]),
                        jnp.int32(slot), self.slots.kv_mask, jnp.int32(c0),
                        self.cfg, **after)
                t_called = time.perf_counter()
                self.slots.pool = out["pool"]
                self.slots.kv_mask = out["kv_mask"]
                pf.done = c1
            else:
                # single shot: a row the bucket long, which write_pages
                # pages
                t_call = time.perf_counter()
                with trace.annotate(trace.PREFILL_ENQUEUE):
                    out = self._family.prefill_prompt(
                        self.params, jnp.asarray(pf.ids),
                        jnp.asarray(pf.mask), self.cfg, pf.bucket)
                t_called = time.perf_counter()
                self.slots.admit(slot, out)
                pf.done = pf.bucket
            vector = out.get("counters")
            if pf.done >= pf.bucket:
                if self._prefix and pf.match is not None:
                    # index the freshly written prompt pages so later
                    # requests can map them; registered pages survive this
                    # slot's release as cached pages
                    self.slots.register_prefix(slot, pf.match.hashes,
                                               pf.ids[0], pf.mask[0])
                gen = pf.request.gen
                vector, self._feed = self._first_token(
                    out["logits"],
                    jnp.asarray(tick_io.stage_first(
                        pf.request.seed, slot, gen.temperature, gen.top_k,
                        gen.top_p)),
                    self._prev(), vector)
                if self._drafts:
                    # the row's first draft, from the module at the prompt's
                    # last position and the token just drawn, on the device
                    self.slots.pool = self._family.first_draft(
                        self.params, out["hidden"], self._feed,
                        self.slots.pool,
                        jnp.asarray(self.slots.page_table[slot]),
                        jnp.int32(slot), self.slots.kv_mask,
                        jnp.int32(pf.positions[0, -1]),
                        jnp.int32(pf.bucket - 1), self.cfg)
                # the rope position of the first generated token is the
                # host's own count (the programs' `next_pos`, never read)
                row = _Running(
                    request=pf.request, handle=pf.handle, token=0,
                    key=np.zeros(2, np.uint32),
                    pos=int(pf.positions[0, -1]) + 1, write_pos=pf.bucket,
                    emitted=0, t_admit=pf.t_admit, t_first=0.0,
                    first_unread=True,
                    limit=pf.bucket + gen.max_new_tokens + self._draft_room - 1)
                self._occupants[slot] = row
        t_handed = time.perf_counter()
        self._host.phases += (
            (trace.SERVE_ADMIT, t0), ("serve_prefill", t_call),
            (trace.PREFILL_ENQUEUE, t_called), ("serve_prefill", t_handed))
        return _Unit(pf=pf, vector=vector, row=row, ts=ts, t0=t0,
                     handover_s=t_handed - t0, offset=offset0, cost=cost,
                     first=offset0 == pf.start)

    def _prev(self) -> jax.Array:
        """What the next tick takes as the tick before's vector."""
        if self._feed is not None:
            return self._feed
        return (self._no_fetch if self._in_flight is None
                else self._in_flight.fetch)

    def _collect_unit(self, ahead: bool = False) -> None:
        """Read the prefill unit in flight, if any: its one transfer back.
        `ahead`: the next hand-over (a unit, or the step's tick) was enqueued
        behind it first. The unit's `serve_prefill` span is closed here with
        its counters (`dur`: the host's seconds of the hand-over and of this
        read, which tile with the tick's; `ahead`; `reads`); a final unit's
        first token is pushed, `t_first` stamped and the request trace's
        `first_token` written here, one hand-over after the device made it.
        A read that raises fails the unit's own request and nobody else's.
        The read is one of the thread's two device waits (`unit_wait_s`);
        what follows it on the host is admission's (`serve_prefill_result`,
        in `admit_s` wherever in the step it falls)."""
        unit, self._unit = self._unit, None
        if unit is None:
            return
        host = self._host
        t0 = t_read = time.perf_counter()
        host.unnamed_until(t0)
        fetched = error = None
        if unit.vector is not None:
            # the one place admission waits for the device
            before = host.enter_wait(t0)
            try:
                with trace.annotate(trace.PREFILL_FIRST):
                    fetched = np.asarray(unit.vector)
            except Exception as e:
                error = e
            t_read = host.leave_wait(trace.PREFILL_FIRST, before, t0)
            host.unit_wait_s += t_read - t0
            host.elsewhere += t_read - t0
        with trace.annotate(trace.PREFILL_RESULT):
            if error is not None:
                self._fail_prefill(unit.pf, error)
            else:
                self._unit_result(unit, fetched, ahead, t0, t_read)
        t_done = time.perf_counter()
        host.phases.append((trace.PREFILL_RESULT, t_done))
        if host.admitting is None:
            host.admit_s += t_done - t_read
            host.tail = t_done

    def _unit_result(self, unit: _Unit, fetched, ahead: bool, t0: float,
                     t_read: float) -> None:
        """What the host does with a unit's result once it is read (from
        `t0` to `t_read`; or has nothing to read): the span, the anchor, the
        first token."""
        pf, row = unit.pf, unit.row
        counters = fetched
        if row is not None:
            token, chain, counters = tick_io.split_first(fetched)
        counted = (dict(zip(self._family.counters, counters.tolist()))
                   if counters is not None else {})
        self.prefill_state_carries_total += bool(
            counted.get("state_carries"))
        trace.recorder().emit(
            "serve_prefill", ts=unit.ts, dur=unit.handover_s + t_read - t0,
            request=pf.request.request_id, bucket=pf.bucket, slot=pf.slot,
            prompt=len(pf.request.input_ids),
            chunk=unit.cost, offset=unit.offset, ahead=int(ahead),
            reads=int(fetched is not None),
            **({"chunks_skipped": pf.skipped} if unit.first else {}),
            **counted)
        # like the tick's flush: a span line, an anchor (a cell of long
        # chunks flushes too seldom to anchor a capture of seconds)
        trace.wallclock_anchor()
        rt_b = (self._rt.get(pf.request.request_id)
                if self._reqtrace is not None else None)
        if rt_b is not None:
            # hand-over to result: what the request waited for this unit
            rt_b.prefill_chunk(unit.ts, t_read - unit.t0, unit.offset,
                               unit.cost, tick=self.steps)
        if row is None:
            return
        row.t_first = time.time()
        if rt_b is not None:
            rt_b.first_token(row.t_first)
        row.token, row.key = token, chain
        row.emitted, row.first_unread = 1, False
        pf.handle._push(token)
        gen = pf.request.gen
        if (gen.eos_token_id is not None and token == gen.eos_token_id) \
                or gen.max_new_tokens == 1:
            # an eos is seen here, a tick late if the row joined one (an
            # overrun); a budget of one token never joined
            self._finish(pf.slot, row)

    def _fail_prefill(self, pf: _Prefilling, error: Exception) -> None:
        """A unit of `pf` raised, at its hand-over or at its deferred read:
        fail its request and free its slot; the rows beside it are as they
        were (a row it had already made leaves the batch, and its row of a
        tick enqueued meanwhile is an overrun)."""
        logger.error("prefill of %s failed", pf.request.request_id,
                     exc_info=error)
        self.stats.record_failed(pf.request.tenant)
        if pf in self._prefilling:
            self._prefilling.remove(pf)
        r = self._occupants.get(pf.slot)
        if r is not None and r.handle is pf.handle:
            self._occupants.pop(pf.slot)
            r.finished = True
        self.slots.release(pf.slot)
        self._write_failed_trace(pf.request, len(pf.handle.tokens_out))
        pf.handle._finish(error)

    def _decode_tick(self) -> None:
        """Hand the device its next tick, THEN read the one before: while the
        host fetches and emits tick k-1 (and comes round the loop, admits and
        stages), the device runs tick k."""
        before = self._in_flight
        self._in_flight = self._dispatch_tick(before)
        self._feed = None
        if before is not None:
            self._collect_tick(before)
        # the step's last prefill unit, in the device's order: behind the
        # tick before it, in front of the tick just enqueued
        self._collect_unit(ahead=self._in_flight is not None)

    def _collect(self) -> None:
        """Read the tick in flight, then the prefill unit in flight, if any,
        with nothing enqueued behind them."""
        before, self._in_flight = self._in_flight, None
        self._feed = None
        if before is not None:
            self._collect_tick(before)
        self._collect_unit()

    def _dispatch_tick(self, before: "_Tick | None") -> "_Tick | None":
        """Stage and enqueue one decode tick over every row that has a token
        left to decode once `before` (the tick in flight) lands, in two host
        phases: `stage` (the rows of ONE staging buffer, `models/tick_io.py`)
        and `dispatch` (`grow`: page growth, then the page table into the
        buffer; `h2d`: the buffer's copy, the tick's one transfer to the
        device; `enqueue`: the jitted call; then adopting its outputs). The
        host knows everything it stages a tick ahead but the token and rng
        key of a row `before` holds: those the program reads from `before`'s
        fetched vector, on the device (`fed`), and a row a prefill unit of
        this step made from the unit's (`_prev`: `before`'s vector with such
        rows written over their slots). A row that ends by length when
        `before` lands is left out; one that ends by eos is not known yet and
        overruns. None where no row is left to decode."""
        scfg = self.serve_cfg
        t_entry = time.perf_counter()
        rows = [(slot, r) for slot, r in self._occupants.items()
                if r.emitted + r.in_flight + r.first_unread
                < r.request.gen.max_new_tokens]
        if not rows:
            return None
        self._host.unnamed_until(t_entry)
        with trace.annotate(trace.TICK_STAGE):
            # fresh every tick: nothing writes a buffer the device was given
            staged = tick_io.stage(scfg.max_slots,
                                   self.slots.page_table.shape[1])
            pages_live = steps_visited = joined_fed = 0
            ahead = 2 if self._drafts else 0   # places a tick in flight may add
            for slot, r in rows:
                if r.in_flight or r.first_unread:
                    # its token and key are a result the host has not read:
                    # the tick in flight's, or its prefill unit's; behind a
                    # verify tick in flight its position is one too
                    staged.fed[slot] = (tick_io.FED_ALL
                                        if self._drafts and r.in_flight
                                        else tick_io.FED_TOKEN)
                    joined_fed += r.first_unread
                else:
                    staged.token[slot] = r.token
                    staged.keys[slot] = r.key
                write = r.write_pos + ahead * r.in_flight
                staged.pos[slot] = r.pos + ahead * r.in_flight
                staged.write_pos[slot] = write
                staged.temperature[slot] = r.request.gen.temperature
                staged.top_k[slot] = r.request.gen.top_k
                staged.top_p[slot] = r.request.gen.top_p
                live = write // scfg.page_size + 1
                pages_live += live
                steps_visited += -(-live // self._kv_step_pages)
            branch = int(sampler_branch(staged.temperature, staged.top_k,
                                        staged.top_p))

        t_wall = time.time()
        t0 = time.perf_counter()
        with trace.annotate(trace.TICK_DISPATCH):
            with trace.annotate(trace.TICK_GROW):
                # back the next write of every row BEFORE the tick: the
                # submit-time reservation guarantees these allocations
                # succeed
                for slot, r in rows:
                    if self._drafts:
                        # two places past the furthest the row can stand, as
                        # far as its reservation goes
                        self.slots.ensure_capacity(slot, min(
                            int(staged.write_pos[slot]) + 2, r.limit))
                    else:
                        self.slots.ensure_capacity(slot, r.write_pos + 1)
                    # only these rows may write/mark kv: a mid-prefill slot
                    # already owns live pages and mask spans this tick must
                    # not touch
                    staged.active[slot] = 1
                # a copy: the table itself changes under later growth and
                # releases
                staged.page_table[:] = self.slots.page_table
            t_grown = time.perf_counter()
            with trace.annotate(trace.TICK_H2D):
                staged_d = jnp.asarray(staged.buffer)
                h2d_copies = 1
            t_copied = time.perf_counter()
            with trace.annotate(trace.TICK_ENQUEUE):
                # its return is the enqueue's return
                out = self._tick_program(
                    self.params, staged_d, self._prev(),
                    self.slots.pool, self.slots.kv_mask, self.cfg)
            t_enqueued = time.perf_counter()
            # release the staged copy now, while the device runs the tick, as
            # a call's own temporaries are, not between two ticks at this
            # function's return
            del staged_d
            self.slots.update_from_step(out)
            for _, r in rows:
                if not self._drafts:   # a verify tick's advance is read back
                    r.pos += 1
                    r.write_pos += 1
                r.in_flight += 1
        t_dispatched = time.perf_counter()
        self._host.phases += (
            (trace.TICK_STAGE, t0), (trace.TICK_GROW, t_grown),
            (trace.TICK_H2D, t_copied), (trace.TICK_ENQUEUE, t_enqueued),
            (trace.TICK_DISPATCH, t_dispatched))
        self._host.tail = t_dispatched
        return _Tick(
            fetch=out["fetch"], rows=rows, ts=t_wall,
            ahead=before is not None,
            pages=(pages_live, len(rows) * self.slots.page_table.shape[1],
                   steps_visited),
            branch=branch, joined_fed=joined_fed, stage_s=t0 - t_entry,
            dispatch_s=t_dispatched - t0,
            h2d_s=t_copied - t_grown, enqueue_s=t_enqueued - t_copied,
            h2d_copies=h2d_copies)

    def _collect_tick(self, tick: _Tick) -> None:
        """Read a dispatched tick, in two host phases: `wait` (`block`: until
        its tokens are ready; `fetch`: token, keys and counters to numpy, the
        tick's one transfer back) and `emit` (token push, finishes). A row
        that left the batch since the dispatch (an eos the host saw a tick
        late) overran: its token is dropped and counted. The tick's host
        counts and the device's counters fold into the pending
        `serve_decode_step` span here, together. Each phase is a profiler
        annotation; the four phases, `h2d` and `enqueue` are also sums on the
        span (`TICK_SUMS`), whose `dur` stays dispatch + wait. One clock read
        a boundary: no phase is timed twice."""
        host = self._host
        t_entry = time.perf_counter()
        host.unnamed_until(t_entry)
        with trace.annotate(trace.TICK_WAIT):
            # the thread's other device wait: the collector's seconds are
            # read on either side of it, outside `block`'s own event
            before = host.enter_wait(t_entry)
            t_block = time.perf_counter()
            # block, then convert: the device's gap while the host sleeps
            # belongs to `block` (launch before the program's first
            # operation, wake after its last), not to the conversion
            with trace.annotate(trace.TICK_BLOCK):
                jax.block_until_ready(tick.fetch)
            t_blocked = host.leave_wait(trace.TICK_BLOCK, before, t_entry)
            host.tick_blocked(t_entry, t_block, t_blocked,
                              behind=self._unit is not None)
            with trace.annotate(trace.TICK_FETCH):
                fetched = np.asarray(tick.fetch)
                if self._drafts:
                    (next_token, new_keys, first_token, count, next_pos,
                     next_write, drafted, second, counters) = \
                        tick_io.split_drafting(fetched,
                                               self.serve_cfg.max_slots)
                else:
                    next_token, new_keys, counters = tick_io.split_result(
                        fetched, self.serve_cfg.max_slots)
                d2h_copies = 1
        t_fetched = time.perf_counter()
        overrun = 0
        made = discarded = 0
        with trace.annotate(trace.TICK_EMIT):
            for slot, r in tick.rows:
                r.in_flight -= 1
                if self._drafts:
                    made += int(count[slot])
                    self._tick_verified.setdefault(
                        r.request.request_id, []).append(
                            (int(count[slot]), int(drafted[slot]),
                             int(second[slot])))
                if r.finished:
                    overrun += 1
                    discarded += int(count[slot]) if self._drafts else 0
                    continue
                if self._reqtrace is not None:
                    # tick-rate but bounded by max_slots dict lookups; tracing
                    # OFF skips even the branch body (the structural free-ness
                    # pin)
                    b = self._rt.get(r.request.request_id)
                    if b is not None:
                        b.decode_tick(self.steps, len(tick.rows))
                r.key = new_keys[slot]
                gen = r.request.gen
                if self._drafts:
                    # where the row stands is the tick's own result
                    r.pos, r.write_pos = (int(next_pos[slot]),
                                          int(next_write[slot]))
                    toks = [int(first_token[slot])]
                    if count[slot] == 2:
                        toks.append(int(next_token[slot]))
                else:
                    toks = [int(next_token[slot])]
                for i, tok in enumerate(toks):
                    r.token = tok
                    r.emitted += 1
                    r.handle._push(tok)
                    if (gen.eos_token_id is not None
                            and tok == gen.eos_token_id) \
                            or r.emitted >= gen.max_new_tokens:
                        # an eos or the budget on the first of two tokens
                        # drops the second
                        discarded += len(toks) - i - 1
                        self._finish(slot, r)
                        break
        t_emitted = time.perf_counter()
        host.phases += ((trace.TICK_FETCH, t_fetched),
                        (trace.TICK_EMIT, t_emitted))
        host.elsewhere += t_emitted - t_entry
        self._note_decode_tick(
            tick, counters.tolist(), overrun, d2h_copies,
            wait_s=t_fetched - t_entry, emit_s=t_emitted - t_fetched,
            made=made if self._drafts else len(tick.rows),
            discarded=discarded)
        host.tail = t_emitted   # folding it into the span is under no event

    def _note_decode_tick(self, tick: _Tick, counters: list, overrun: int,
                          d2h_copies: int, wait_s: float, emit_s: float,
                          made: int, discarded: int) -> None:
        """Fold one collected decode tick into the pending aggregated
        `serve_decode_step` span; flush every `decode_span_every` ticks
        (and at idle boundaries / shutdown). The emitted span's `dur` is
        the exact sum of its `ticks` ticks' dispatch + wait (the dispatch at
        the boundary that enqueued the tick, the wait at the next: host
        seconds that tile, so RunClock's `serve` bucket and the goodput
        fraction lose nothing to the aggregation or to the tick in flight —
        only the spans.jsonl line rate drops from token rate). `tokens` is
        the host's own count of the row-ticks the device ran over those ticks
        (`active` is the last tick's alone), `rows_overrun` the ones among
        them whose token was discarded, `ticks_ahead` the ticks enqueued
        behind a tick in flight. The seconds of `TICK_SUMS`,
        `kv_pages_live`, `kv_pages_table`, `kv_steps_visited`,
        `ticks_sampled`, `ticks_sorted`
        (counted where the rows are staged), `h2d_copies` / `d2h_copies` (one
        each a tick, where they are made) and the family's counters are
        summed the same way, all of the SAME ticks: every one is folded here,
        when its tick is collected. Under a drafting family `tokens` is what
        the ticks MADE (`made`: one or two a row-tick, the device's own
        counts), `row_ticks` the row-ticks, `tokens_discarded` the tokens
        made and pushed to no handle (an overrun row's, a second token behind
        an eos or the end of the budget), and `verify_rows` every row-tick's
        record by request, in tick order: [tokens made, the draft verified,
        the second query's first choice], overruns among them."""
        if self._tick_count == 0:
            self._tick_ts = tick.ts
        self._tick_accum += tick.dispatch_s + wait_s
        self._tick_count += 1
        self._tick_active = len(tick.rows)
        self._tick_tokens += made
        self._tick_row_ticks += len(tick.rows)
        self._tick_discarded += discarded
        self._tick_overrun += overrun
        self._tick_ahead += tick.ahead
        self._tick_joined_fed += tick.joined_fed
        for i, pages in enumerate(tick.pages):
            self._tick_pages[i] += pages
        self._tick_sampler[0] += tick.branch >= 1
        self._tick_sampler[1] += tick.branch == 2
        self._tick_copies[0] += tick.h2d_copies
        self._tick_copies[1] += d2h_copies
        for i, seconds in enumerate((tick.stage_s, tick.dispatch_s, wait_s,
                                     emit_s, tick.h2d_s, tick.enqueue_s)):
            self._tick_sums[i] += seconds
        for name, n in zip(self._family.counters, counters):
            self._tick_counters[name] += n
        if self._drafts:
            self.spec_offered_total += counters[self._spec_at[0]]
            self.spec_accepted_total += counters[self._spec_at[1]]
        if self._tick_count >= self.serve_cfg.decode_span_every:
            self._flush_decode_span()

    def _flush_decode_span(self) -> None:
        if self._tick_count == 0:
            return
        drafting = ({"row_ticks": self._tick_row_ticks,
                     "tokens_discarded": self._tick_discarded,
                     "verify_rows": self._tick_verified}
                    if self._drafts else {})
        # the host's wall clock on a running capture's clock, one a span
        trace.wallclock_anchor()
        trace.recorder().emit("serve_decode_step", ts=self._tick_ts,
                              dur=self._tick_accum, ticks=self._tick_count,
                              active=self._tick_active,
                              tokens=self._tick_tokens,
                              rows_overrun=self._tick_overrun,
                              ticks_ahead=self._tick_ahead,
                              rows_joined_fed=self._tick_joined_fed,
                              kv_pages_live=self._tick_pages[0],
                              kv_pages_table=self._tick_pages[1],
                              kv_steps_visited=self._tick_pages[2],
                              kv_pages_per_step=self._kv_step_pages,
                              ticks_sampled=self._tick_sampler[0],
                              ticks_sorted=self._tick_sampler[1],
                              h2d_copies=self._tick_copies[0],
                              d2h_copies=self._tick_copies[1],
                              **dict(zip(TICK_SUMS, self._tick_sums)),
                              **self._tick_counters, **drafting,
                              **self._host.flush())
        self._tick_row_ticks = self._tick_discarded = 0
        self._tick_verified = {}
        self._tick_ts, self._tick_accum = 0.0, 0.0
        self._tick_count, self._tick_active, self._tick_tokens = 0, 0, 0
        self._tick_overrun, self._tick_ahead = 0, 0
        self._tick_joined_fed = 0
        self._tick_pages = [0, 0, 0]
        self._tick_sampler = [0, 0]
        self._tick_copies = [0, 0]
        self._tick_sums = [0.0] * len(TICK_SUMS)
        self._tick_counters = dict.fromkeys(self._tick_counters, 0)

    def _on_page_alloc(self, slot: int, pages: int) -> None:
        """pages.PagedKVCache alloc_listener (installed only when tracing
        is ON): attribute a page hand-out to the slot's owning request —
        an occupant, or the mid-prefill request still filling the row."""
        r = self._occupants.get(slot)
        request_id = r.request.request_id if r is not None else None
        if request_id is None:
            for pf in self._prefilling:
                if pf.slot == slot:
                    request_id = pf.request.request_id
                    break
        b = self._rt.get(request_id) if request_id is not None else None
        if b is not None:
            b.page_alloc(self.steps, pages)

    def _finish(self, slot: int, r: _Running,
                error: Exception | None = None) -> None:
        t_done = time.time()
        ttft = r.t_first - r.request.arrival
        tpot = ((t_done - r.t_first) / (r.emitted - 1)
                if r.emitted > 1 else None)
        queue_wait = r.t_admit - r.request.arrival
        trace.recorder().emit(
            "serve_request", ts=r.request.arrival,
            dur=t_done - r.request.arrival, request=r.request.request_id,
            tokens=r.emitted, ttft=ttft, tpot=tpot, queue_wait=queue_wait,
            slot=slot)
        self.stats.record(ttft=ttft, tpot=tpot, queue_wait=queue_wait,
                          tokens=r.emitted, tenant=r.request.tenant)
        breaches: list = []
        capture_dir = None
        if self._slo is not None and error is None:
            breaches = self._slo.breaches(ttft, tpot, queue_wait)
            if breaches:
                self.stats.record_slo_breach(r.request.tenant)
                if self._profiler is not None:
                    # bounded capture of the ticks around the breach —
                    # retention-capped, never raises into the loop. The
                    # capture_meta carries the breaching request's trace
                    # id, so the capture and the request-trace waterfall
                    # name the same request.
                    meta = {"request_id": r.request.request_id}
                    if r.request.trace is not None:
                        meta["trace_id"] = r.request.trace.trace_id
                    if r.request.tenant:
                        meta["tenant"] = r.request.tenant
                    if self._profiler.trigger(f"serve_slo_{breaches[0]}",
                                              step=self.steps, meta=meta):
                        capture_dir = self._profiler.last_capture_dir
        if self._reqtrace is not None:
            b = self._rt.pop(r.request.request_id, None)
            if b is not None:
                self._reqtrace.write(b.build(
                    "failed" if error is not None else "completed", t_done,
                    tokens=r.emitted, ttft=ttft, tpot=tpot,
                    queue_wait=queue_wait, slo_breach=breaches or None,
                    capture=capture_dir))
        # released at once, though a tick in flight may still hold the row (an
        # eos seen a tick late): the device runs programs in the order they
        # were enqueued, so a later prefill into the slot, or a later row's
        # growth onto a freed page, follows the overrun's write
        self._occupants.pop(slot, None)
        r.finished = True
        self.slots.release(slot)
        r.handle._finish(error)
        if (self._metrics_writer is not None
                and self.stats.completed % self.serve_cfg.metrics_every == 0):
            self._metrics_writer.log(self.stats.completed,
                                     self.metrics_snapshot())

    # -- introspection / teardown -----------------------------------------

    def metrics_snapshot(self) -> dict:
        """The serving metrics line: SLO percentiles + live occupancy."""
        snap = {"serving": 1, **self.stats.snapshot()}
        snap["active_slots"] = self.slots.active_count
        snap["queue_depth"] = self.queue_depth()
        snap["slot_allocations"] = self.slots.allocations
        snap["decode_steps"] = self.steps
        # what held the engine's thread, as running totals: stall records
        # and their seconds, the process's collections and programs compiled
        # (`utils/trace.HostWatch`), ticks the host found ready
        host = self._host
        snap["host_stalls"] = host.host_stalls
        snap["host_stall_s"] = round(host.host_stall_s, 6)
        snap["gc_s"] = round(host.watch.gc_s, 6)
        snap["compiles"] = host.watch.compiles
        snap["ticks_found_ready"] = host.ticks_found_ready
        if self._degraded is not None:
            snap["degraded"] = self._degraded
        scfg = self.serve_cfg
        snap["kv_cache"] = "paged"
        snap["kv_quant"] = scfg.kv_quant
        snap["page_size"] = scfg.page_size
        snap["pages_total"] = self.slots.num_pages
        snap["pages_used"] = self.slots.pages_used
        snap["pages_free"] = self.slots.pages_free
        snap["pages_reserved"] = self.slots.pages_reserved
        # the reservation-vs-allocation gap: HBM promised to worst-case
        # demand that has not materialized as written tokens (pages.py
        # fragmentation docstring) — /healthz serves this verbatim and
        # the fleet aggregates it across pods
        snap["reserved_unbacked"] = self.slots.reserved_unbacked
        snap["page_fragmentation"] = round(self.slots.fragmentation, 4)
        snap["reserved_gap_bytes"] = (self.slots.reserved_unbacked
                                      * self.slots.page_bytes())
        snap["page_allocations"] = self.slots.page_allocations
        snap["prefilling"] = len(self._prefilling)
        snap["prefill_chunks_last_tick"] = self.prefill_chunks_last_tick
        snap["prefill_chunks_total"] = self.prefill_chunks_total
        snap["prefill_chunks_skipped_total"] = \
            self.prefill_chunks_skipped_total
        snap["prefill_tokens_total"] = self.prefill_tokens_total
        snap["prefill_state_carries_total"] = \
            self.prefill_state_carries_total
        if self._drafts:
            # drafts verified and drafts that were right, over every tick read
            snap["spec_offered_total"] = self.spec_offered_total
            snap["spec_accepted_total"] = self.spec_accepted_total
        if self._prefix:
            # cache-off snapshots stay byte-identical to the plain
            # paged engine (the PR 13 pin) — these keys only exist
            # when prefix caching is on
            snap["prefix_cache"] = 1
            snap["pages_cached"] = self.slots.pages_cached
            snap["prefix_cow_forks"] = self.slots.cow_forks
            snap["prefix_evictions"] = self.slots.prefix_evictions
        return snap

    def drain(self, timeout_s: float = 60.0) -> None:
        """Step until queue and slots are empty and no tick is in flight
        (tests / synchronous use)."""
        deadline = time.monotonic() + timeout_s
        while (self._occupants or self._prefilling or self.queue_depth()
               or self._in_flight is not None):
            if time.monotonic() > deadline:
                raise TimeoutError("engine did not drain in time")
            self.step()

    def shutdown(self) -> None:
        """Fail every queued and in-flight request (process exit path);
        later submits raise EngineShutdown instead of queueing into a dead
        engine. The decode tick and the prefill unit in flight are collected
        first: their tokens reach their handles (and may finish them) before
        the rest fail."""
        self._host.begin()      # its waits are the thread's, like a step's
        self._host.admitted()
        try:
            self._collect()
        except Exception:
            # a failed step left the stores poisoned (ServeLoop._run): the
            # tick and the unit in flight cannot be read either
            logger.exception("what was in flight at shutdown is lost")
        self._flush_decode_span()
        self._host.end(worked=False)
        if self._profiler is not None:
            self._profiler.close()  # finalize an open capture window
        err = EngineShutdown("serve engine shut down")
        with self._lock:
            self._closed = True
            pending = list(self._queue)
            self._queue.clear()
        for request, handle, demand, match in pending:
            if match is not None:
                self.slots.cancel_match(match)
            else:
                self.slots.unreserve(demand)
            self._record_shed(request, "shutdown")
            handle._finish(err)
        while self._prefilling:
            pf = self._prefilling.popleft()
            if (pf.match is not None and pf.match.fork_src is not None
                    and not pf.match.forked):
                self.slots.unpin_page(pf.match.fork_src)
            self.slots.release(pf.slot)
            self._write_failed_trace(pf.request, len(pf.handle.tokens_out))
            pf.handle._finish(err)
        for slot in list(self._occupants):
            r = self._occupants.pop(slot)
            self.slots.release(slot)
            self._write_failed_trace(r.request, r.emitted)
            r.handle._finish(err)

    def _write_failed_trace(self, request: ServeRequest, tokens: int) -> None:
        """Shutdown path: an in-flight request's trace ends as `failed`."""
        if self._reqtrace is None:
            return
        b = self._rt.pop(request.request_id, None)
        if b is not None:
            self._reqtrace.write(b.build("failed", time.time(),
                                         tokens=tokens))


class ServeLoop:
    """Background driver for in-process use (tests, notebooks): a thread
    calling `engine.step()`, parking on the engine's work event when idle.
    tools/serve.py does NOT use this — its loop runs on the main thread so
    serve spans feed the RunClock buckets."""

    def __init__(self, engine: ServeEngine, idle_wait_s: float = 0.05):
        self.engine = engine
        self._idle_wait = idle_wait_s
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="serve-loop")

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                if not self.engine.step():
                    self.engine._work.wait(self._idle_wait)
            except Exception:
                # the tick and the splice DONATE the long-lived pool, so a
                # failed step leaves the slot state poisoned — retrying
                # would raise forever while blocked clients hang. Fail every
                # handle (and future submits) instead, like the process
                # loop's exit path does.
                logger.exception("serve loop step failed; shutting the "
                                 "engine down")
                self.engine.shutdown()
                return

    def start(self) -> "ServeLoop":
        self._thread.start()
        return self

    def stop(self, timeout_s: float = 10.0) -> None:
        self._stop.set()
        self.engine._work.set()
        self._thread.join(timeout=timeout_s)
        if self._thread.is_alive():
            # a step (e.g. a long TPU compile) is still running: shutting
            # the engine down now would free slots and finish handles
            # CONCURRENTLY with that step's own bookkeeping — leave the
            # state alone and let the daemon thread die with the process
            logger.warning("serve loop still inside a step after %.0fs; "
                           "skipping engine shutdown", timeout_s)
            return
        self.engine.shutdown()

    def __enter__(self) -> "ServeLoop":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
