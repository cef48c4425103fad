"""Device idle time the engine's own host phases own, a tick: the part of
the traced window in which no operation ran on the device and that lies
under a `serve_tick_stage`, `serve_tick_dispatch`, `serve_tick_emit` or
`serve_admit` event of the host plane, over the number of `serve_tick_wait`
events (one a tick). Idle under `serve_tick_wait` is the device done and
the host not yet woken; idle under none is the loop outside the engine."""

from benchmark import xplane

LAYER = "serving engine decode tick"
UNIT = "ms"
MOVES = "serve_tpot_ms_p90"
SOURCE = "device_trace"

HOST_PHASES = ("serve_tick_stage", "serve_tick_dispatch", "serve_tick_emit",
               "serve_admit")


def read(obs: dict):
    trace = obs.get("xplane") or {}
    if obs.get("kind") != "serve" or not trace.get("devices"):
        return None
    window = xplane.device_window(trace)
    in_window = lambda s, e: e > window[0] and s < window[1]
    ticks = sum(1 for name, s, e in trace["host"]
                if name == "serve_tick_wait" and in_window(s, e))
    if not ticks:
        return None
    plane = trace["devices"][sorted(trace["devices"])[0]]
    busy = xplane.merge((s, e) for _, s, e in xplane.clip(plane, window))
    edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
    gaps = list(zip(edges[0::2], edges[1::2]))
    owned = xplane.merge((s, e) for name, s, e in trace["host"]
                         if name in HOST_PHASES and in_window(s, e))
    idle_ns, i = 0, 0
    for g0, g1 in gaps:               # both lists are sorted and disjoint
        while i < len(owned) and owned[i][1] <= g0:
            i += 1
        j = i
        while j < len(owned) and owned[j][0] < g1:
            idle_ns += min(g1, owned[j][1]) - max(g0, owned[j][0])
            j += 1
    return 1e-6 * idle_ns / ticks
