"""A pin on the programs an engine runs: the sha256 of the lowered text of
every family's tick (as the engine runs it and with its thirteen arguments),
prefill, chunk, span and page splice at the tests' sizes, against
`tests/lowered_pins.json`. A PR that means to leave the compiled programs
alone (one that changes the ORDER of the host's reads, say) proves it here;
one that means to change a program writes the file anew and says in
CHANGES.md which entries moved and why:

    python tests/test_lowered_pins.py --write tests/lowered_pins.json

(on another tree: copy this file and `tests/serving_tiny.py` there first).
The file in the tree was written from the parent of PR 47 (d797252), so
PR 47 passing it is the proof that it changed none of them; PR 49, which
rebuilt the tick's paged kernel, wrote the two tick entries of the five
families that run it anew and moved no other. The text is
StableHLO without locations: moving source lines does not move a hash; a
jax upgrade does, and then the file is written anew on the parent first."""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (HERE, os.path.dirname(HERE)):      # run as a script, too
    if path not in sys.path:
        sys.path.insert(0, path)
PINS = os.path.join(HERE, "lowered_pins.json")

import serving_tiny  # noqa: E402

FAMILIES = serving_tiny.FAMILIES
PINNED: dict = {}
if os.path.exists(PINS):                        # absent while first written
    with open(PINS) as _f:
        PINNED = json.load(_f)


def _lowered(engine) -> dict:
    """{program: lowered} for every program the family has, at the engine's
    shapes: the smallest bucket whole, and one chunk (or span) of it."""
    from llama_pipeline_parallel_tpu.models import tick_io

    fam, cfg, slots = engine._family, engine.cfg, engine.slots
    S, pages = slots.page_table.shape
    bucket = engine.serve_cfg.prompt_buckets[0]
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    staged = i32(S, tick_io.COLUMNS + pages)
    prev = i32(3 * S + len(fam.counters))
    f = jax.eval_shape(tick_io.unpack, staged, prev)
    out = {
        "decode_tick": fam.decode_tick.lower(
            engine.params, staged, prev, slots.pool, slots.kv_mask, cfg),
        "paged_decode_step": fam.paged_decode_step.lower(
            engine.params, f[0], slots.pool, *f[1:4], slots.kv_mask, *f[4:],
            cfg),
        "prefill_prompt": fam.prefill_prompt.lower(
            engine.params, i32(1, bucket), i32(1, bucket), cfg, bucket)}
    whole = jax.eval_shape(
        lambda p, ids, mask: fam.prefill_prompt(p, ids, mask, cfg, bucket),
        engine.params, i32(1, bucket), i32(1, bucket))
    columns = len(fam.table_columns(cfg, bucket, engine.serve_cfg.max_len,
                                    engine.serve_cfg.page_size))
    out["write_pages"] = fam.write_pages.lower(
        slots.pool, slots.kv_mask, i32(), i32(columns), whole["cache"],
        whole["kv_mask"])
    piece = min(bucket, engine.serve_cfg.prefill_chunk_tokens or bucket)
    for name in ("paged_prefill_chunk", "paged_prefill_span"):
        program = getattr(fam, name)
        if program is not None:
            out[name] = program.lower(
                engine.params, i32(1, piece), i32(1, piece), i32(1, piece),
                slots.pool, i32(pages), i32(), slots.kv_mask, i32(), cfg)
    return out


def hashes(family: str) -> dict:
    return {name: hashlib.sha256(lowered.as_text().encode()).hexdigest()
            for name, lowered in _lowered(serving_tiny.engine(family)).items()}


_MADE: dict = {}


@pytest.mark.parametrize("family,program", [
    (family, program) for family in FAMILIES
    for program in PINNED.get(family, ())])
def test_the_lowered_program_is_the_pinned_one(family, program):
    if family not in _MADE:
        _MADE[family] = hashes(family)
    now = _MADE[family]
    assert sorted(now) == sorted(PINNED[family])     # the programs it has
    assert now[program] == PINNED[family][program], (
        f"{family}'s {program} lowers to another text than the pinned one: "
        f"if that is meant, write tests/lowered_pins.json anew and say so")


def test_every_family_is_pinned():
    assert sorted(PINNED) == sorted(FAMILIES)


if __name__ == "__main__":
    assert sys.argv[1] == "--write", __doc__
    with open(sys.argv[2], "w") as f:
        json.dump({family: hashes(family) for family in FAMILIES}, f,
                  indent=1, sort_keys=True)
        f.write("\n")
