"""Host-clock time of one prefill chunk of the dense state-space block: the
mean `dur` of the window's `serve_prefill` spans that carry the family's
counters (`ssm_positions`) and ran a piece of a bucket (`chunk` < `bucket`),
from a unit's hand-over to its result. Every row that decodes waits behind
the chunk of its engine step, so this is what a long prompt costs the other
sessions' gap between tokens. A run prints how many there were, how many of
them carried the state in, and the whole-bucket prefills beside them. None
where the window ran no such chunk."""

from benchmark import granite_work

LAYER = "serving engine admission"
UNIT = "ms"
MOVES = "serve_tpot_ms_p90"
SOURCE = "program_span"


def read(obs: dict):
    chunks = granite_work.chunk_spans(obs)
    if not chunks:
        return None
    whole = [s["dur"] for s in granite_work.family_spans(obs, "serve_prefill")
             if s.get("chunk", 0) >= s.get("bucket", 0)]
    print(f"mamba_chunk_ms.serve: {len(chunks)} chunks "
          f"({sum(1 for s in chunks if s['state_carries'])} carried their "
          f"slot's row in), {len(whole)} whole-bucket prefills, mean "
          f"{1e3 * sum(whole) / max(len(whole), 1):.1f} ms", flush=True)
    return 1e3 * sum(s["dur"] for s in chunks) / len(chunks)
