"""Share of the chip's roofline the decode tick's latent expert products
reach: the two matrices of the held experts that had a row (`experts_hit`)
and the FLOPs of the rows routed here (`routed_here`), a tick's mean over
the window's spans (benchmark/ssm_work.py `expert_tick_work`), over the
published peaks, over the time a traced tick spends under `moe_experts` in
the decode-tick program. NEEDED bytes: an expert read once however many
row tiles its run crosses, so `expert_visits / experts_hit` is printed
beside it. None where the spans carry no counters or no tick was traced."""

from benchmark import hybrid_scopes, kernel_work, peaks, ssm_work

LAYER = "expert layer"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs: dict):
    trace = ssm_work.ssm_trace(obs)
    sums = hybrid_scopes.counter_sums(obs) if trace else None
    timed = (hybrid_scopes.tick_seconds_under(obs, trace, ("moe_experts",))
             if sums else None)
    if not timed or not timed[0]:
        return None
    seconds, ticks = timed
    hit, here = (sums[k] / sums["ticks"] for k in ("experts_hit", "routed_here"))
    flops, hbm = ssm_work.expert_tick_work(
        hit, here, ssm_work.sizes(obs["cell"].model))
    share, bound = kernel_work.roofline_percent(
        flops, hbm, seconds, peaks.peaks_for(obs["devices"][0].device_kind))
    visits = sum(s.get("expert_visits", 0) for s in obs["spans"]
                 if s["name"] == "serve_decode_step")
    print(f"latent_experts_roofline.serve: {ticks} ticks traced, "
          f"{1e3 * seconds:.3f} ms a tick under moe_experts; a tick hits "
          f"{hit:.1f} held experts with {here:.1f} rows: {hbm / 1e6:.1f} MB, "
          f"{flops / 1e9:.2f} GFLOP, bound by {bound}; expert_visits / "
          f"experts_hit {visits / max(sums['experts_hit'], 1):.3f}",
          flush=True)
    return share
