"""Share of the chip's roofline the verify tick's sparse read reaches: the
index keys of the positions its queries could see (`index_visible` x 256 B)
and the entries of the positions they selected (`index_selected` x 1,152 B),
each read once, over both trunk queries, the module's positions and all six
caches, with the indexer's and the absorbed attention's products
(benchmark/spec_work.py `verify_read_work`), a tick's mean over the window's
`serve_decode_step` spans, over the published peaks, over the time a traced
tick spends under the SCOPES `index_score`, `index_topk`, `latent_gather` and
`sparse_attn` in the decode-tick program, whatever implements them;
bytes-bound. None where the spans carry no drafting counters or no tick was
traced."""

from benchmark import (
    hybrid_scopes,
    kernel_work,
    latent_scopes,
    peaks,
    spec_work,
)

LAYER = "latent attention layer"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "device_trace"


def read(obs: dict):
    spans = spec_work.spec_spans(obs)
    ticks = sum(s["ticks"] for s in spans)
    trace = latent_scopes.latent_trace(obs) if ticks else None
    timed = (hybrid_scopes.tick_seconds_under(obs, trace,
                                              latent_scopes.SPARSE_READ)
             if trace else None)
    if not timed or not timed[0]:
        return None
    seconds, traced = timed
    seen, kept = (sum(s[k] for s in spans) / ticks
                  for k in latent_scopes.COUNTERS)
    flops, hbm = spec_work.verify_read_work(seen, kept, obs["cell"].model)
    share, bound = kernel_work.roofline_percent(
        flops, hbm, seconds, peaks.peaks_for(obs["devices"][0].device_kind))
    print(f"verify_attn_roofline.serve: {traced} ticks traced, "
          f"{1e3 * seconds:.3f} ms a tick under index_score + index_topk + "
          f"latent_gather + sparse_attn; a tick's queries see {seen:.0f} "
          f"positions and select {kept:.0f}: {hbm / 1e6:.1f} MB, "
          f"{flops / 1e9:.2f} GFLOP, bound by {bound}", flush=True)
    return share
