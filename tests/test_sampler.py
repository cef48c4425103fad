"""The serving sampler does the work its batch asks for
(`models/llama/decode.py` `sample_rowwise`, `sampler_branch`).

Three things are pinned: the tokens are those of the `vmap` of the row
sampler as it stood before the branches (kept below as the reference, two
sorts a row), bit for bit; the program sorts only inside the branch that
filters, once a row; and the engine's `ticks_sampled` / `ticks_sorted` count
what the program branched on.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llama_pipeline_parallel_tpu.models import tick_io
from llama_pipeline_parallel_tpu.models.llama import decode
from llama_pipeline_parallel_tpu.models.llama import model as llama
from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.utils import trace

ROWS = 8


def reference_row(logits, temperature, top_k, top_p, key):
    """`_sample_row` before the sampler read its batch: every row sorted
    for the k-th value and again inside `_top_p_mask`, whatever its knobs."""
    vocab = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.where(temperature > 0.0, temperature, 1.0)
    l = logits / safe_t
    sorted_desc = jnp.sort(l, axis=-1)[..., ::-1]
    kth = sorted_desc[jnp.clip(top_k, 1, vocab) - 1]
    l = jnp.where((top_k > 0) & (l < kth), -jnp.inf, l)
    l = jnp.where(top_p < 1.0, decode._top_p_mask(l, top_p), l)
    sampled = jax.random.categorical(key, l, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


reference_rowwise = jax.jit(jax.vmap(reference_row))
sample_rowwise = jax.jit(decode.sample_rowwise)


def tied_logits(vocab: int, seed: int) -> jnp.ndarray:
    """Rows on a grid of halves, so that values tie: at the top, at the
    k-th place and at the nucleus' edge."""
    rs = np.random.RandomState(seed)
    return jnp.asarray(np.round(rs.normal(size=(ROWS, vocab)) * 4) / 2,
                       jnp.float32)


def knobs(samples: bool, with_k: bool, with_p: bool, vocab: int):
    """[ROWS] temperature, top_k, top_p. Greedy rows (temperature 0) keep
    filters of their own in every case: they must never ask for a sort.
    top-k at 1, at the vocabulary and above it; top-p below the top token's
    own probability (1e-9) among ordinary ones."""
    greedy = np.arange(ROWS) % 3 == 0
    temps = np.where(greedy, 0.0, [0.7, 1.3, 0.5, 2.0] * 2) if samples \
        else np.zeros(ROWS)
    top_ks = np.asarray([1, vocab, vocab + 9, 5, 0, 2, 1, 0])
    top_ps = np.asarray([0.9, 1e-9, 0.5, 1.0, 0.999, 1e-9, 0.3, 0.95])
    if not with_k:
        top_ks = np.where(temps > 0, 0, top_ks)
    if not with_p:
        top_ps = np.where(temps > 0, 1.0, top_ps)
    return (temps.astype(np.float32), top_ks.astype(np.int32),
            top_ps.astype(np.float32))


def row_keys(seed: int) -> jnp.ndarray:
    return jax.vmap(jax.random.PRNGKey)(jnp.arange(ROWS) + 100 * seed)


COMBINATIONS = [(s, k, p) for s in (False, True) for k in (False, True)
                for p in (False, True)]


def branch_of(samples, with_k, with_p) -> int:
    return 0 if not samples else 2 if (with_k or with_p) else 1


@pytest.mark.parametrize("vocab", [64, 257])
@pytest.mark.parametrize("samples,with_k,with_p", COMBINATIONS)
def test_tokens_are_the_two_sort_samplers_bit_for_bit(samples, with_k, with_p,
                                                      vocab):
    temps, top_ks, top_ps = knobs(samples, with_k, with_p, vocab)
    assert decode.sampler_branch(temps, top_ks, top_ps) == branch_of(
        samples, with_k, with_p)
    for seed in range(4):
        args = (tied_logits(vocab, seed), jnp.asarray(temps),
                jnp.asarray(top_ks), jnp.asarray(top_ps), row_keys(seed))
        got, want = sample_rowwise(*args), reference_rowwise(*args)
        assert got.dtype == want.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_batch_mixing_every_kind_of_row_with_unoccupied_ones():
    """Greedy, temperature-only, top-k, top-p and both in one batch, beside
    rows staged as the engine stages an empty slot (0, 0, 1): branch 2 for
    every row, and every row's token is the reference's."""
    vocab = 128
    temps = np.asarray([0.0, 0.8, 0.9, 0.0, 1.1, 0.6, 0.0, 1.0], np.float32)
    top_ks = np.asarray([0, 0, 4, 0, 0, 1, 0, vocab + 1], np.int32)
    top_ps = np.asarray([1.0, 1.0, 1.0, 1.0, 0.7, 1e-9, 1.0, 0.5], np.float32)
    assert decode.sampler_branch(temps, top_ks, top_ps) == 2
    for seed in range(6):
        args = (tied_logits(vocab, seed), jnp.asarray(temps),
                jnp.asarray(top_ks), jnp.asarray(top_ps), row_keys(seed))
        np.testing.assert_array_equal(np.asarray(sample_rowwise(*args)),
                                      np.asarray(reference_rowwise(*args)))
    # top-k 1 and a nucleus below the top token's probability leave the
    # best value alone (its ties with it), whatever the key
    logits = tied_logits(vocab, 0)
    got = np.asarray(sample_rowwise(
        logits, jnp.asarray(temps), jnp.asarray(top_ks), jnp.asarray(top_ps),
        row_keys(9)))
    assert logits[5, got[5]] == jnp.max(logits[5])


@pytest.mark.parametrize("top_k", [0, 1, 3, 64, 70])
def test_the_masked_sort_is_the_sort_of_the_masked_row(top_k):
    """What lets branch 2 sort once: below the k-th value lies a suffix of
    the descending sort, ties with the k-th value kept on both sides."""
    row = tied_logits(64, 3)[0]
    sorted_desc = jnp.sort(row)[::-1]
    kth = sorted_desc[np.clip(top_k, 1, 64) - 1]
    masked = jnp.where((top_k > 0) & (row < kth), -jnp.inf, row)
    np.testing.assert_array_equal(
        np.asarray(jnp.sort(masked)[::-1]),
        np.asarray(jnp.where((top_k > 0) & (sorted_desc < kth), -jnp.inf,
                             sorted_desc)))
    np.testing.assert_array_equal(
        np.asarray(decode._top_p_mask(masked, 0.8)),
        np.asarray(decode._top_p_mask_sorted(masked, jnp.sort(masked)[::-1],
                                             0.8)))


# -- what the program holds ---------------------------------------------------

def _flat(jaxpr):
    """Every equation of a jaxpr, its sub-jaxprs' included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _flat(sub)


def _named(jaxpr, primitive: str) -> list:
    return [e for e in _flat(jaxpr) if e.primitive.name == primitive]


def _sampler_args(vocab=64):
    temps, top_ks, top_ps = knobs(False, False, False, vocab)
    return (tied_logits(vocab, 0), jnp.asarray(temps), jnp.asarray(top_ks),
            jnp.asarray(top_ps), row_keys(0))


def test_the_sampler_sorts_only_in_its_third_branch_and_once_a_row():
    jaxpr = jax.make_jaxpr(decode.sample_rowwise)(*_sampler_args()).jaxpr
    (switch,) = _named(jaxpr, "cond")
    assert not any(e.primitive.name == "sort" for e in jaxpr.eqns)
    branches = switch.params["branches"]
    assert [len(_named(b.jaxpr, "sort")) for b in branches] == [0, 0, 1]
    # the one sort is over the whole batch, a row a lane: [ROWS, vocab]
    (sort,) = _named(branches[2].jaxpr, "sort")
    assert sort.invars[0].aval.shape == (ROWS, 64)
    # before the change: two, in the open
    assert len(_named(jax.make_jaxpr(jax.vmap(reference_row))(
        *_sampler_args()).jaxpr, "sort")) == 2


def test_the_compiled_samplers_entry_holds_no_sort():
    """What the device runs for an all-greedy batch: the entry computation
    of the compiled program reaches a sort only through its conditional."""
    text = sample_rowwise.lower(*_sampler_args()).compile().as_text()
    entry = text[text.index("ENTRY "):]
    entry = entry[:entry.index("\n}")]
    assert " sort(" not in entry and "conditional(" in entry
    assert " sort(" in text


def test_the_dense_tick_program_sorts_only_under_the_samplers_switch():
    from llama_pipeline_parallel_tpu.serve.pages import PagedKVCache

    cfg = LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    slots = PagedKVCache(cfg, max_slots=2, max_len=16, page_size=8,
                         num_pages=4)
    z = jnp.zeros(2, jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: decode.paged_decode_step(*a, cfg=cfg))(
        params, z, slots.pool, jnp.asarray(slots.page_table), z, z,
        slots.kv_mask, z, jnp.zeros((2, 2), jnp.uint32),
        jnp.zeros(2, jnp.float32), z, jnp.ones(2, jnp.float32)).jaxpr
    switches = [c for c in _named(jaxpr, "cond")
                if len(c.params["branches"]) == 3]
    assert [len(_named(b.jaxpr, "sort"))
            for b in switches[-1].params["branches"]] == [0, 0, 1]
    assert len(_named(jaxpr, "sort")) == 1


# -- the host's count and the program's branch --------------------------------

@pytest.mark.parametrize("samples,with_k,with_p", COMBINATIONS)
def test_the_hosts_predicate_is_the_programs_branch_index(samples, with_k,
                                                          with_p):
    """One function on both sides: numpy arrays in the engine's stage
    phase, traced arrays in the program. Same arrays, same index."""
    staged = knobs(samples, with_k, with_p, 64)
    on_host = decode.sampler_branch(*staged)
    in_program = jax.jit(decode.sampler_branch)(*map(jnp.asarray, staged))
    assert int(on_host) == int(in_program) == branch_of(samples, with_k, with_p)


def test_rows_that_do_not_sample_never_raise_the_branch():
    """An unoccupied slot is staged (0, 0, 1); a greedy request may carry
    any top-k or top-p. Neither asks for a draw or a sort."""
    zeros = np.zeros(4, np.float32)
    assert decode.sampler_branch(zeros, np.zeros(4, np.int32),
                                 np.ones(4, np.float32)) == 0
    assert decode.sampler_branch(zeros, np.asarray([5, 0, 1, 0], np.int32),
                                 np.asarray([.5, 1, .1, 1], np.float32)) == 0
    one = np.asarray([0, 0.7, 0, 0], np.float32)
    assert decode.sampler_branch(one, np.asarray([5, 0, 1, 0], np.int32),
                                 np.asarray([.5, 1, .1, 1], np.float32)) == 1


def test_the_tick_span_counts_the_ticks_that_sampled_and_that_sorted():
    """Greedy, temperature-only and top-p requests in turn through a tiny
    engine, a span a tick: `ticks_sampled` and `ticks_sorted` equal the
    count, over the knob arrays the tick program was given, of what its
    sampler branches on."""
    from llama_pipeline_parallel_tpu.serve import (
        ServeConfig,
        ServeEngine,
        ServeRequest,
    )

    cfg = LlamaConfig.tiny()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(params, cfg, ServeConfig(
        max_slots=2, max_len=24, prompt_buckets=(16,), page_size=8,
        max_queue=8, decode_span_every=1))
    program_branch = jax.jit(lambda staged, prev: decode.sampler_branch(
        *tick_io.unpack(staged, prev)[-3:]))
    given = []
    real_step = engine._tick_program

    def recording_step(params, staged, prev, *rest):
        # the knobs as the program unpacks them from the staged buffer
        given.append(int(program_branch(staged, prev)))
        return real_step(params, staged, prev, *rest)

    engine._tick_program = recording_step
    spans = []
    listener = lambda rec: spans.append(dict(rec))
    trace.recorder().add_listener(listener)
    gens = [decode.GenerationConfig(max_new_tokens=4),
            decode.GenerationConfig(max_new_tokens=3, temperature=0.8),
            decode.GenerationConfig(max_new_tokens=5, temperature=0.8,
                                    top_p=0.9)]
    try:
        for i, gen in enumerate(gens):
            engine.submit(ServeRequest(input_ids=[5, 6, 7], seed=i, gen=gen))
            engine.drain(timeout_s=120)
        # and two at once: a greedy row beside a top-k row sorts
        for i, gen in enumerate([gens[0], decode.GenerationConfig(
                max_new_tokens=4, temperature=0.5, top_k=3)]):
            engine.submit(ServeRequest(input_ids=[5, 6, 7], seed=i, gen=gen))
        engine.drain(timeout_s=120)
        engine.shutdown()
    finally:
        trace.recorder().remove_listener(listener)
    ticks = [s for s in spans if s["name"] == "serve_decode_step"]
    assert [s["ticks"] for s in ticks] == [1] * len(given)
    assert given == [0] * 3 + [1] * 2 + [2] * 4 + [2] * 3
    assert [s["ticks_sampled"] for s in ticks] == [int(b >= 1) for b in given]
    assert [s["ticks_sorted"] for s in ticks] == [int(b == 2) for b in given]
