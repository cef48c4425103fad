"""The decode tick's two transfers: what the host stages goes to the device
as ONE int32 buffer, what the host reads of the tick comes back as one.

A transfer of a few kilobytes costs its fixed part, an allocation, a
linearisation and a dispatch on the way in and a round trip on the way out
(0.27 ms and 0.3 to 0.5 ms on a v5e's host, PERF.md), so a tick pays for the
COUNT of its transfers and not for their bytes. `stage` lays every array
`paged_decode_step` takes from the host side by side, a row a slot:

    [S, COLUMNS + pages_per_slot] int32
    token | pos | write_pos | active | top_k | fed | key word 0 | key word 1 |
    temperature | top_p | the slot's page-table row

The `uint32` keys and the `float32` knobs are VIEWS of their columns on the
host and `bitcast_convert_type`s of them in the program: every bit as it
was, no conversion either way. `packed` builds, from a family's
thirteen-argument `paged_decode_step`, the program the engine runs: the same
body between `unpack` and `pack_result`, under the same name (the trace's
`jit(paged_decode_step)`, which the benchmark's readers find the tick by).
Written once for every family: they share the argument list to the letter.

The engine keeps one tick in flight (`serve/engine.py`): it stages and
enqueues tick k before it has read tick k-1's result. Everything it stages it
knows a tick ahead but a row's `token` and `keys`, which ARE that result. So
the program takes the previous tick's fetched vector beside the buffer, as
the device array it still is, and a row whose `fed` column is set reads its
token and key words from there (one `where` in `unpack`); a row whose first
token the host has read reads the buffer's, which the host filled.

A prefill unit is not waited for either. A row's FIRST token is drawn on the
device (`first_token`: the request's seed to its key, the split, the family's
sampler over the unit's logits, as the host did them one by one) and written,
with the rng chain's two words, over the row's slot of the vector the next
tick takes as `prev`: the row joins that tick `fed`, like a row of the tick
before. The host reads the unit's own small vector (`split_first`) one
hand-over later.

A family that DRAFTS (a verify tick that emits one or two tokens a row:
`models/latent_moe/draft.py`) has a wider vector and another `fed`
(`packed_drafting`, `DRAFT_ROWS`):

    [S] the LAST token emitted (the next tick's input) | [S * 2] key words |
    [S] the first token emitted | [S] how many were (1 or 2) |
    [S] the next tick's pos | [S] its write_pos |
    [S] the draft the tick verified (-1: none) |
    [S] the second query's first choice | the family's counters

The first three parts lie where the plain vector's lie, so `first_token`
writes a prefilled row's token and chain into either. What the host no
longer knows a tick ahead is how far a row in flight advanced: its `pos` and
`write_pos` are then the tick's own results too, so a row staged `fed` 2
takes all four from the vector on the device and the host stages upper
bounds it grows pages by and learns the truth at the read; a row staged
`fed` 1 (its first token is a prefill unit's, unread) takes token and key
from the vector and `pos` / `write_pos` from the buffer, where the host,
which knows the prompt, put them. The draft is never staged: it rides the
family's per-slot store beside the pages from tick to tick. The last two
parts are the tick's RECORD and feed nothing: what the module had drafted
for this tick and what the second query made of it, whether or not the
draft was accepted, so that what the module and the second query produced
can be rated from the run that produced it (the engine puts them on the
`serve_decode_step` span, `verify_rows`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# the columns in front of a slot's page-table row: the six int32 fields in
# `Staged`'s order, the key's two words, the two float32 knobs
_INTS = 6
_KEYS = slice(_INTS, _INTS + 2)
_TEMPERATURE, _TOP_P = _KEYS.stop, _KEYS.stop + 1
COLUMNS = _TOP_P + 1


class Staged(NamedTuple):
    """One tick's staging buffer and the typed views the host fills it
    through (each a view of `buffer`: a write to one is a write to it)."""

    buffer: np.ndarray          # [S, COLUMNS + pages_per_slot] int32
    token: np.ndarray           # [S] int32, and the five after it
    pos: np.ndarray
    write_pos: np.ndarray
    active: np.ndarray
    top_k: np.ndarray
    # 1: token and keys are the previous tick's (a drafting family: its first
    # token's unit's; 2: token, keys, pos and write_pos are the tick's)
    fed: np.ndarray
    keys: np.ndarray            # [S, 2] uint32
    temperature: np.ndarray     # [S] float32
    top_p: np.ndarray           # [S] float32
    page_table: np.ndarray      # [S, pages_per_slot] int32


def stage(slots: int, pages_per_slot: int) -> Staged:
    """A FRESH buffer, every row an unoccupied slot's (greedy: temperature
    0, no top-k, top-p 1). Fresh every tick, never one buffer reused: on the
    CPU backend `jnp.asarray` of an aligned numpy array shares its memory,
    so a buffer written again would change under the program it was handed
    to; one that nobody writes after the hand-over cannot."""
    buffer = np.zeros((slots, COLUMNS + pages_per_slot), np.int32)
    top_p = buffer[:, _TOP_P].view(np.float32)
    top_p[:] = 1.0
    return Staged(
        buffer, *(buffer[:, j] for j in range(_INTS)),
        keys=buffer[:, _KEYS].view(np.uint32),
        temperature=buffer[:, _TEMPERATURE].view(np.float32), top_p=top_p,
        page_table=buffer[:, COLUMNS:])


def unpack(staged: jnp.ndarray, prev: jnp.ndarray) -> tuple:
    """In the program: the staged buffer back to the nine arrays, in the
    order `paged_decode_step` takes them (its `pool` and `kv_mask` come
    beside them, donated): token, page_table, pos, write_pos, active, keys,
    temperature, top_k, top_p. `prev` is the previous tick's `pack_result`
    (any vector of its shape where no row is `fed`): a fed row's token and
    key words are that tick's, bit for bit, never seen by the host."""
    token, pos, write_pos, active, top_k, fed = (
        staged[:, j] for j in range(_INTS))
    slots = staged.shape[0]
    fed = fed != 0
    token = jnp.where(fed, prev[:slots], token)
    key_words = jnp.where(fed[:, None], prev[slots:3 * slots].reshape(slots, 2),
                          staged[:, _KEYS])
    bits = jax.lax.bitcast_convert_type
    return (token, staged[:, COLUMNS:], pos, write_pos, active,
            bits(key_words, jnp.uint32),
            bits(staged[:, _TEMPERATURE], jnp.float32), top_k,
            bits(staged[:, _TOP_P], jnp.float32))


def pack_result(token: jnp.ndarray, keys: jnp.ndarray,
                counters: jnp.ndarray | None) -> jnp.ndarray:
    """In the program: what the host reads of a tick as one int32 vector,
    [S] tokens, [S * 2] key words, then the family's counters if it has
    any."""
    parts = [token, jax.lax.bitcast_convert_type(keys, jnp.int32).reshape(-1)]
    if counters is not None:
        parts.append(counters)
    assert all(p.dtype == jnp.int32 for p in parts), [p.dtype for p in parts]
    return jnp.concatenate(parts)


def split_result(fetched: np.ndarray, slots: int) -> tuple:
    """On the host: the fetched vector's parts, as views: ([S] tokens,
    [S, 2] uint32 keys, the counters that follow them, possibly none)."""
    return (fetched[:slots],
            fetched[slots:3 * slots].view(np.uint32).reshape(slots, 2),
            fetched[3 * slots:])


# the first token's knobs, one int32 vector: the seed's low 32 bits (what
# `jax.random.PRNGKey` keeps of a Python integer), top-k, the row's slot, and
# the two float32 knobs as their bits
FIRST_COLUMNS = 5


def stage_first(seed: int, slot: int, temperature: float, top_k: int,
                top_p: float) -> np.ndarray:
    """On the host: what `first_token` takes beside the unit's logits, in
    one buffer (one copy in)."""
    staged = np.empty(FIRST_COLUMNS, np.int32)
    staged[:1].view(np.uint32)[0] = seed & 0xFFFFFFFF
    staged[1:3] = top_k, slot
    staged[3:].view(np.float32)[:] = temperature, top_p
    return staged


@functools.cache
def first_token(sample, slots: int):
    """The program that draws a prefilled row's first token where the logits
    lie, for an engine of `slots` slots, made from the families'
    `sample(logits, temperature, top_k, top_p, keys)`: (logits [1, V],
    `stage_first`'s vector, prev, the unit's counters or None) -> (the
    unit's one read: int32 [token, chain word 0, chain word 1, then the
    counters], `prev` with the row's slot holding that token and chain, in
    `pack_result`'s layout). The key is `PRNGKey(seed)`'s, the
    chain and the token's key its `split`'s: the bits the host's eager calls
    gave. `prev` is not donated: the tick in flight's vector is still to be
    read."""

    def prefill_first(logits, staged, prev, counters):
        bits = jax.lax.bitcast_convert_type
        seed, slot = bits(staged[0], jnp.uint32), staged[2]
        knobs = bits(staged[3:], jnp.float32)
        chain, key = jax.random.split(jax.random.PRNGKey(seed))
        token = sample(logits, knobs[:1], staged[1:2], knobs[1:], key[None])
        words = bits(chain, jnp.int32)
        parts = [token, words] + ([] if counters is None else [counters])
        fed = jax.lax.dynamic_update_slice(prev, token, (slot,))
        fed = jax.lax.dynamic_update_slice(fed, words, (slots + 2 * slot,))
        return jnp.concatenate(parts), fed

    return jax.jit(prefill_first)


def split_first(fetched: np.ndarray) -> tuple:
    """On the host: (token, [2] uint32 rng chain, the counters that follow,
    possibly none) of a final unit's fetched vector."""
    return int(fetched[0]), fetched[1:3].view(np.uint32), fetched[3:]


@functools.cache
def packed(step):
    """The program an engine runs a tick with, made from a family's jitted
    thirteen-argument `step`: (params, staged, prev, pool, kv_mask, cfg) ->
    {"fetch": `pack_result` of the tick's token, keys and counters, "pool",
    "kv_mask"}; `prev` is the tick before's "fetch" (`unpack`), which is not
    donated: the host reads it after this call. It traces `step`'s own body (`__wrapped__`, no nested jit:
    the operations keep their paths under `jit(paged_decode_step)`) and
    returns only what the engine reads: a family's further outputs, such as
    the latent tick's `selection`, are not computed for it. One jitted
    program a `step`, whoever asks."""
    body = step.__wrapped__

    def paged_decode_step(params, staged, prev, pool, kv_mask, cfg):
        (token, page_table, pos, write_pos, active, keys, temperature,
         top_k, top_p) = unpack(staged, prev)
        out = body(params, token, pool, page_table, pos, write_pos, kv_mask,
                   active, keys, temperature, top_k, top_p, cfg)
        return {"fetch": pack_result(out["token"], out["keys"],
                                     out.get("counters")),
                "pool": out["pool"], "kv_mask": out["kv_mask"]}

    return jax.jit(paged_decode_step, static_argnames=("cfg",),
                   donate_argnames=("pool", "kv_mask"))


# -- a family whose tick emits one or two tokens a row --------------------------

# int32 parts a slot in a drafting family's fetched vector (module docstring)
DRAFT_ROWS = 9
FED_TOKEN, FED_ALL = 1, 2


def fetch_rows(drafts: bool) -> int:
    """Parts of `slots` int32 in front of the counters of a fetched vector."""
    return DRAFT_ROWS if drafts else 3


def unpack_drafting(staged: jnp.ndarray, prev: jnp.ndarray) -> tuple:
    """`unpack` for a drafting family: a row `fed` at all takes token and key
    words from `prev`, one fed `FED_ALL` its `pos` and `write_pos` too."""
    slots = staged.shape[0]
    part = lambda j: prev[j * slots:(j + 1) * slots]
    (token, page_table, pos, write_pos, active, keys, temperature, top_k,
     top_p) = unpack(staged, prev)
    whole = staged[:, _INTS - 1] == FED_ALL
    return (token, page_table, jnp.where(whole, part(5), pos),
            jnp.where(whole, part(6), write_pos), active, keys, temperature,
            top_k, top_p)


def split_drafting(fetched: np.ndarray, slots: int) -> tuple:
    """On the host: ([S] last tokens, [S, 2] uint32 keys, [S] first tokens,
    [S] counts, [S] next pos, [S] next write_pos, [S] drafts verified, [S]
    second queries' first choices, the counters), as views."""
    part = lambda j: fetched[j * slots:(j + 1) * slots]
    return (part(0), fetched[slots:3 * slots].view(np.uint32).reshape(slots, 2),
            *(part(j) for j in range(3, DRAFT_ROWS)),
            fetched[DRAFT_ROWS * slots:])


@functools.cache
def packed_drafting(step):
    """`packed` for a drafting family's `step` (its verify tick under the
    thirteen arguments): the same program between `unpack_drafting` and the
    wider vector, under the same name."""
    body = step.__wrapped__

    def paged_decode_step(params, staged, prev, pool, kv_mask, cfg):
        (token, page_table, pos, write_pos, active, keys, temperature,
         top_k, top_p) = unpack_drafting(staged, prev)
        out = body(params, token, pool, page_table, pos, write_pos, kv_mask,
                   active, keys, temperature, top_k, top_p, cfg)
        fetch = jnp.concatenate([
            out["token"],
            jax.lax.bitcast_convert_type(out["keys"], jnp.int32).reshape(-1),
            out["tokens"][:, 0], out["count"], out["pos"], out["write_pos"],
            out["drafted"], out["second"], out["counters"]])
        return {"fetch": fetch, "pool": out["pool"], "kv_mask": out["kv_mask"]}

    return jax.jit(paged_decode_step, static_argnames=("cfg",),
                   donate_argnames=("pool", "kv_mask"))
