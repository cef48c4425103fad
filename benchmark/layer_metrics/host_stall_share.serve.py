"""Share of the window the engine's thread spent in stall records: the sum
of `dur` over the `stalls` the window's `serve_decode_step` spans carry (one
record a phase of host WORK of 20 ms or more, or a device wait in which other
threads' collections ran that long: `serve/engine.py` `STALL_S`), over the
window's seconds. 0 where the spans carry the thread's account and no record;
None where they do not carry it (a program before PR 50).

**What it cannot see.** A record is made of the thread's own work. While the
thread sleeps in a device wait (`serve_tick_block`, `serve_prefill_first`)
the wait's length is the device's work and proves nothing, so a process that
is stopped from outside while its engine thread sits in a wait makes NO
record, however long the device idles: a gap of 3.2 s under
`serve_tick_wait` (80.5% idle, -12% tokens/s) read 0.385% here (PERF.md
section 6, PR 50). An untraced run's value is therefore a lower bound of the
seconds lost to stalls; a traced run prints every idle gap of 20 ms or more
that the records do not cover, so the two cases can be told apart there.

Prints the thread's partition (each phase's share of `step_s`, the
unaccounted rest, the host's share of its own thread), what held the thread
outside its two device waits and inside them, the records' seconds by cause
(collector / compiler / other) and by phase, and the five longest with their
causes. In a traced run also: the share of the first device plane's idle time
that lies inside a record (records placed by the wall-clock anchors,
`tick_gap.clock_offset`), the idle gaps no record covers with the engine's
event over each, and for each record inside the capture the idle time inside
it, how far the longest idle gap that touches it begins from it, and the
runtime's own host events (any thread) that overlap it most
(`benchmark/host_stall.py`)."""

from benchmark import host_stall

LAYER = "serving engine host thread"
UNIT = "%"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"

NAME = "host_stall_share.serve"


def read(obs: dict):
    spans = host_stall.account_spans(obs)
    acc = host_stall.account(spans)
    if acc is None:
        return None
    records = host_stall.stalls_of(spans)
    stalled = sum(r["dur"] for r in records)
    print(f"{NAME}: {host_stall.describe_partition(acc)}", flush=True)
    print(f"{NAME}: {host_stall.describe_causes(acc)}", flush=True)
    by_cause, by_phase = host_stall.split(records)
    print(f"{NAME}: {len(records)} record(s), {stalled:.4f} s of "
          f"{host_stall.window_s(obs):.3f} ({acc.get('stalls_dropped', 0)} "
          f"more dropped from full spans; a stop of the process while the "
          f"thread slept in a device wait makes none); s by cause: "
          + ", ".join(f"{c} {s:.4f}" for c, s in by_cause.items())
          + "; s by phase: "
          + (", ".join(f"{p} {s:.4f}" for p, s in sorted(
              by_phase.items(), key=lambda kv: -kv[1])) or "none"),
          flush=True)
    for rec in sorted(records, key=lambda r: -r["dur"])[:5]:
        print(f"{NAME}: {host_stall.describe_record(rec)}", flush=True)
    joined = host_stall.join(obs, records)
    if joined is not None:
        for line in host_stall.describe_joined(joined):
            print(f"{NAME} traced: {line}", flush=True)
    return host_stall.stall_share(obs)
