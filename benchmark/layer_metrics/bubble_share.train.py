"""Share of a pipeline stage's busy time spent in masked schedule slots,
mean over the stages: slot counts from the `schedule` the trainer put on its
`profile_window` span (counted from the unit tables the interpreter scans),
slot times from that stage's plane of the trace (`scopes.bubble_by_stage`).
The per-stage values are printed."""

from benchmark import scopes

LAYER = "pipeline schedule"
UNIT = "%"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(obs: dict):
    trace = scopes.for_observation(obs, "train")
    schedule = next((s["schedule"] for s in obs.get("spans", ())
                     if s["name"] == "profile_window" and s.get("schedule")),
                    None)
    if schedule is None or trace is None:
        return None
    by_stage = scopes.bubble_by_stage(trace, schedule)
    if not by_stage:
        return None
    print("bubble_share.train by stage: "
          + ", ".join(f"{s['stage']}: {b:.2f}%"
                      for s, b in zip(schedule, by_stage)), flush=True)
    return sum(by_stage) / len(by_stage)
