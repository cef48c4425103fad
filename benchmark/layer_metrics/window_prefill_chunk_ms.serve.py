"""Host-clock time of one prefill chunk of the window / full softmax family:
the mean `dur` of the window's `serve_prefill` spans that carry the family's
counters (`full_entries_read`) and ran a piece of a bucket (`chunk` <
`bucket`), from a unit's hand-over to its result. Every row that decodes
waits behind the chunk of its engine step, so this is what a long prompt
costs the others. A run prints how many there were and the whole-bucket
prefills beside them. None where the window ran no such chunk."""

from benchmark import window_work

LAYER = "serving engine admission"
UNIT = "ms"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(obs: dict):
    if obs.get("kind") != "serve":
        return None
    units = [s for s in obs["spans"] if s["name"] == "serve_prefill"
             and window_work.FULL_COUNTER in s and "chunk" in s
             and "bucket" in s]
    chunks = [s["dur"] for s in units if s["chunk"] < s["bucket"]]
    if not chunks:
        return None
    whole = [s["dur"] for s in units if s["chunk"] >= s["bucket"]]
    print(f"window_prefill_chunk_ms.serve: {len(chunks)} chunks, "
          f"{len(whole)} whole-bucket prefills, mean "
          f"{1e3 * sum(whole) / max(len(whole), 1):.1f} ms", flush=True)
    return 1e3 * sum(chunks) / len(chunks)
