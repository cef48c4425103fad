"""Host-clock time of one prefill chunk: the mean `dur` of the window's
`serve_prefill` spans that ran a piece of a bucket (`chunk` < `bucket`; the
engine fetches the chunk's counters inside the span, so `dur` covers the
device's work). A run prints how many there were, their share of the window
and the whole-bucket prefills beside them. A decode tick waits behind the
chunk of its engine step, so this is what a long prompt costs every row that
is decoding. None where the window ran no chunk."""

LAYER = "serving engine admission"
UNIT = "ms"
MOVES = "serve_tpot_ms_p90"
SOURCE = "program_span"


def read(obs: dict):
    if obs.get("kind") != "serve":
        return None
    units = [s for s in obs["spans"]
             if s["name"] == "serve_prefill" and "chunk" in s and "bucket" in s]
    chunks = [s["dur"] for s in units if s["chunk"] < s["bucket"]]
    if not chunks:
        return None
    whole = [s["dur"] for s in units if s["chunk"] >= s["bucket"]]
    window = obs["window"][1] - obs["window"][0]
    print(f"prefill_chunk_ms.serve: {len(chunks)} chunks, "
          f"{100.0 * sum(chunks) / window:.1f}% of the window; {len(whole)} "
          f"whole-bucket prefills, mean "
          f"{1e3 * sum(whole) / max(len(whole), 1):.1f} ms, "
          f"{100.0 * sum(whole) / window:.1f}% of the window", flush=True)
    return 1e3 * sum(chunks) / len(chunks)
