"""Share of the chip's roofline the decode tick's sparse read reaches: the
index keys of the positions the decoding rows could see (`index_visible`) and
the entries of the positions they selected (`index_selected`), each read
once, with the indexer's and the absorbed attention's products
(benchmark/latent_work.py), a tick's mean over the window's
`serve_decode_step` spans, over the published peaks, over the time a traced
tick spends under `index_score`, `index_topk`, `latent_gather` and
`sparse_attn` in the decode-tick program; bytes-bound. None where the spans
carry no counters or no tick was traced."""

from benchmark import (
    hybrid_scopes,
    kernel_work,
    latent_scopes,
    latent_work,
    peaks,
)

LAYER = "latent attention layer"
UNIT = "%"
MOVES = "serve_tpot_ms_p90"
SOURCE = "device_trace"


def read(obs: dict):
    trace = latent_scopes.latent_trace(obs)
    sums = (latent_scopes.index_sums(obs, ("serve_decode_step",))
            if trace else None)
    ticks = sum(s["ticks"] for s in obs.get("spans", ())
                if s["name"] == "serve_decode_step" and "index_visible" in s)
    timed = (hybrid_scopes.tick_seconds_under(obs, trace,
                                              latent_scopes.SPARSE_READ)
             if sums and ticks else None)
    if not timed or not timed[0]:
        return None
    seconds, traced = timed
    seen, kept = (sums[k] / ticks for k in latent_scopes.COUNTERS)
    flops, hbm = latent_work.sparse_tick_work(seen, kept, obs["cell"].model)
    share, bound = kernel_work.roofline_percent(
        flops, hbm, seconds, peaks.peaks_for(obs["devices"][0].device_kind))
    print(f"sparse_decode_attn_roofline.serve: {traced} ticks traced, "
          f"{1e3 * seconds:.3f} ms a tick under index_score + index_topk + "
          f"latent_gather + sparse_attn; a tick sees {seen:.0f} positions and "
          f"selects {kept:.0f}: {hbm / 1e6:.1f} MB, {flops / 1e9:.2f} GFLOP, "
          f"bound by {bound}", flush=True)
    return share
