"""Where a profiled window's device time went, by the program's own names.

Point it at a capture the program wrote: the trainer's `profile_steps`
window (`<output_dir>/profile`) or `tools/serve.py`'s profiler output. It
reads the newest `.xplane.pb` under the directory with the benchmark's own
reduction (benchmark/xplane.py, benchmark/scopes.py), so the numbers are the
ones the per-layer metrics report:

- busy and idle share of the traced window, mean over the chips;
- time by class (forward / recompute / backward / weight-gradient /
  optimizer / hand-off / other) and by leaf scope of the vocabulary in
  llama_pipeline_parallel_tpu/utils/trace.py, as shares of busy time;
- for a trainer's capture, `grad_fold` as a line of its own: the self time
  of the operations that add a parameter's gradient into its float32
  accumulator (`utils/trace.GRAD_FOLD`, inside `pp_bwd` / `pp_w`, whose leaf
  in the table above it stays), as a share of busy time. A fold that the
  compiler put into the weight-gradient product's own fusion carries the
  product's name and is not in it;
- the operations with the most device time of their own;
- the longest idle gaps, each with the host event that covers most of it
  (the serving tick's `serve_tick_*` annotations, the trainer's spans);
- for a serving capture (one that holds `serve_tick_wait` events), the idle
  time a tick taken apart by the innermost event of the engine over it, with
  launch and wake told apart inside `serve_tick_block`, after the device's
  clock is moved to where the trace is causal (benchmark/tick_gap.py);
- the offset between the profiler's clock and the wall clock, from the
  capture's `wallclock_us=` anchors: add it to a `spans.jsonl` or
  `request_trace.jsonl` time to place the line on the trace;
- where the run's `spans.jsonl` is found (`--spans`, else the trace
  directory or one of the two above it), how often the engine's one tick in
  flight engaged: `ticks_ahead` of `ticks` (ticks enqueued while the tick
  before them had not been collected) and `rows_overrun` of `tokens`
  (row-ticks run and discarded: an eos is seen one tick late) and
  `rows_joined_fed` (rows that joined a tick with their first token and key
  fed from a prefill unit on the device), summed over the file's
  `serve_decode_step` lines; from the same lines, what the tick's paged
  attention walked: `kv_pages_live / kv_steps_visited` (pages under one
  running-softmax update) and `kv_steps_visited` over the steps the rows'
  whole page tables have at `kv_pages_per_step` pages a step (the share of
  a whole-row walk the kernel's grid still makes);
  and how often the prefill unit in flight did:
  units whose result was read after the next hand-over was enqueued
  (`ahead`) of all `serve_prefill` lines, and the round trips they made
  (`reads`), and beside the units run the chunks never run (`chunks_skipped`
  on a request's first unit: the leading chunks of its bucket that held
  nothing but left pads);
- where the lines carry a state-space family's counters
  (`models/ssm_moe/model.py` COUNTERS), their sums over the ticks and over
  the prefill units apart: `ssm_rows`, `ssm_positions`, `kv_entries_read`,
  `state_carries`, `state_bytes_carried`;
- where the `serve_decode_step` lines carry a drafting family's counters
  (`models/latent_moe/draft.py` COUNTERS), the acceptance (`spec_accepted`
  of `spec_offered`), the tokens a row-tick (`tokens / row_ticks`), the
  tokens made and discarded, the cache places written and not kept, and the
  module's positions in ticks and in prefill units;
- from the same lines, the engine thread's own account (`serve/engine.py`
  `HOST_SUMS` / `HOST_COUNTS`, benchmark/host_stall.py): each phase's share
  of `step_s` and the unaccounted rest, what held the thread outside its two
  device waits and inside them, the ratios as the benchmark's readers
  compute them (`host_stall_share.serve`, `gc_pause_share.serve`,
  `host_bound_tick_share.serve`; over the thread's own seconds the lines
  cover, the sum of `step_s`) and the stall records longest first; with a
  capture, each record that lies inside it beside the device idle time
  inside it, and the idle gaps of 20 ms or more that no record covers.

A directory that holds a `spans.jsonl` and no capture prints the sections
that need none.

Usage:
  python tools/trace_summary.py <trace_dir> [--top 15] [--spans spans.jsonl]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import host_stall, scopes, tick_gap, xplane  # noqa: E402
from llama_pipeline_parallel_tpu.utils.trace import GRAD_FOLD  # noqa: E402


def summarize(path: str, top: int = 15, trace: dict | None = None) -> dict:
    """The summary as data: what `main` prints (`trace`: `xplane.read(path)`
    where the caller has it already)."""
    trace = trace or xplane.read(path)
    if not any(trace["devices"].values()):
        raise SystemExit(
            f"{path} holds no device operation (planes named "
            f"{xplane.DEVICE_PREFIX}<n>, line {xplane.OPS_LINE!r}): a capture "
            f"of a CPU run has host events only")
    scoped = scopes.read(path)
    part, shift = tick_gap.shifted_partition(trace)
    busy_s, window_s = xplane.busy_and_window(trace)
    by_leaf = scopes.leaf_shares(scoped)
    return {
        "chips": len(trace["devices"]),
        "window_s": window_s, "busy_s": busy_s,
        "idle_percent": 100.0 * (1.0 - busy_s / window_s),
        "by_class": scopes.class_shares(scoped),
        "by_scope": by_leaf,
        # not a word of the benchmark's vocabulary: its leaf above is the
        # `pp_bwd` / `pp_w` it lies in
        "grad_fold_percent": scopes.share_under(scoped, (GRAD_FOLD,)),
        "scoped_percent": sum(v for k, v in by_leaf.items()
                              if k != "(no scope)"),
        "top_ops": xplane.top_ops(trace, top),
        "idle_gaps": xplane.idle_gaps(trace, top),
        "tick_gap": part, "device_clock_shift": shift,
        "clock": tick_gap.clock_offset(trace),
    }


PIPELINE = ("ticks", "ticks_ahead", "tokens", "rows_overrun")
# on the same lines since the prefill unit in flight; and on `serve_prefill`
JOINED = "rows_joined_fed"
UNITS = ("ahead", "reads")
KV_STEPS = ("tokens", "kv_pages_live", "kv_pages_table", "kv_steps_visited",
            "kv_pages_per_step")
# a state-space family's own counters, on both kinds of line
RECURRENT = ("ssm_rows", "ssm_positions", "kv_entries_read", "state_carries",
             "state_bytes_carried")


def find_spans(trace_dir: str):
    """The `spans.jsonl` of the run that wrote `trace_dir`: in it, or in one
    of the two directories above it (`<output_dir>/profile`); None."""
    at = os.path.abspath(trace_dir)
    for _ in range(3):
        path = os.path.join(at, "spans.jsonl")
        if os.path.isfile(path):
            return path
        at = os.path.dirname(at)
    return None


def _span_lines(spans_path: str, name: str, carrying: tuple) -> list:
    """The file's lines of span `name` that carry every key of `carrying`."""
    from llama_pipeline_parallel_tpu.utils.perf import read_jsonl

    return read_jsonl(spans_path, keep=lambda r: (
        r.get("name") == name and all(k in r for k in carrying)))


def tick_pipeline(spans_path: str):
    """Sums of `PIPELINE` and `JOINED` over the `serve_decode_step` lines
    that carry all of `PIPELINE`; None where the file holds no such line (a
    trainer's, or a build before the engine kept a tick in flight)."""
    rows = _span_lines(spans_path, "serve_decode_step", PIPELINE)
    if not rows:
        return None
    return {k: sum(r.get(k, 0) for r in rows) for k in PIPELINE + (JOINED,)}


def kv_steps(spans_path: str):
    """{"pages_live", "steps_visited", "steps_table"} over the
    `serve_decode_step` lines that carry `KV_STEPS`: the decoding rows' live
    pages, the grid steps the tick's attention walked for them, and the
    steps their whole page-table rows hold (a row's `kv_pages_table /
    tokens` pages at `kv_pages_per_step` a step); None where the file holds
    no such line with a decoding row (each has a live page, so no sum is 0)."""
    rows = [r for r in _span_lines(spans_path, "serve_decode_step", KV_STEPS)
            if r["tokens"]]
    if not rows:
        return None
    return {
        "pages_live": sum(r["kv_pages_live"] for r in rows),
        "steps_visited": sum(r["kv_steps_visited"] for r in rows),
        "steps_table": sum(
            r["tokens"] * -(-(r["kv_pages_table"] // r["tokens"])
                            // r["kv_pages_per_step"]) for r in rows)}


def unit_pipeline(spans_path: str):
    """{"units", "ahead", "reads", "skipped"} over the `serve_prefill` lines
    that carry `UNITS` (`skipped`: their `chunks_skipped`, which a request's
    first unit carries); None where the file holds none (a build whose
    prefill units were each waited for)."""
    rows = _span_lines(spans_path, "serve_prefill", UNITS)
    if not rows:
        return None
    return {"units": len(rows), **{k: sum(r[k] for r in rows) for k in UNITS},
            "skipped": sum(r.get("chunks_skipped", 0) for r in rows)}


def recurrent_counters(spans_path: str):
    """{"ticks": sums, "units": sums} of `RECURRENT` over the file's
    `serve_decode_step` and `serve_prefill` lines that carry them; None where
    no line does (another family's run, or a build before the counters)."""
    sums = {where: {k: sum(r[k] for r in rows) for k in RECURRENT}
            for where, name in (("ticks", "serve_decode_step"),
                                ("units", "serve_prefill"))
            if (rows := _span_lines(spans_path, name, RECURRENT))}
    return sums or None


DRAFTING = ("spec_offered", "spec_accepted", "spec_tokens",
            "spec_dead_entries", "mtp_positions")


def drafting_counters(spans_path: str):
    """Sums of `DRAFTING`, `tokens`, `row_ticks` and `tokens_discarded` over
    the `serve_decode_step` lines that carry them, and "unit_positions", the
    `mtp_positions` of the `serve_prefill` lines; None where no tick line
    does (a family that does not draft)."""
    rows = _span_lines(spans_path, "serve_decode_step",
                       DRAFTING + ("row_ticks", "tokens_discarded"))
    if not rows:
        return None
    keys = DRAFTING + ("tokens", "row_ticks", "tokens_discarded")
    return {**{k: sum(r[k] for r in rows) for k in keys},
            "unit_positions": sum(r["mtp_positions"] for r in _span_lines(
                spans_path, "serve_prefill", ("mtp_positions",)))}


def host_thread(spans_path: str, trace: dict | None = None):
    """The engine thread's account over the file's `serve_decode_step` lines
    that carry it, as the benchmark's readers compute it: {"account",
    "window_s" (the thread's own seconds the lines cover, the sum of their
    `step_s`: a file has no window of a job's), "ratios" ({reader: percent
    or None}), "records" (longest first), "joined" (`host_stall.join`
    against `trace`; None without a capture)}; None where no line carries it
    (a trainer's file, or a build before the engine kept the account)."""
    rows = _span_lines(spans_path, "serve_decode_step", ("step_s",))
    if not rows:
        return None
    rows.sort(key=lambda r: r["ts"])
    obs = {"kind": "serve", "spans": rows, "xplane": trace,
           "window": (rows[0]["ts"], rows[0]["ts"] + sum(
               r["step_s"] for r in rows))}
    records = host_stall.stalls_of(rows)
    return {
        "account": host_stall.account(rows),
        "window_s": host_stall.window_s(obs),
        "ratios": {
            "host_stall_share.serve": host_stall.stall_share(obs),
            "gc_pause_share.serve": host_stall.share_of_window(
                obs, "gc_s", "wait_gc_s"),
            "host_bound_tick_share.serve": host_stall.found_ready_share(
                obs)},
        "records": sorted(records, key=lambda r: -r["dur"]),
        "joined": host_stall.join(obs, records) if trace else None}


def _print_host_thread(found: dict, top: int) -> None:
    acc = found["account"]
    print(f"\n== the engine's thread, by its own account "
          f"({acc['spans']} lines, {found['window_s']:.3f} s) ==\n"
          f"  {host_stall.describe_partition(acc)}\n"
          f"  {host_stall.describe_causes(acc)}")
    for name, value in found["ratios"].items():
        print(f"  {name} "
              + ("not known" if value is None else f"{value:.4f}%"))
    records = found["records"]
    print(f"  {len(records)} stall record(s)"
          f" ({acc.get('stalls_dropped', 0)} more dropped from full spans)")
    for rec in records[:top]:
        print(f"  {host_stall.describe_record(rec)}")
    if found["joined"] is not None:
        for line in host_stall.describe_joined(found["joined"]):
            print(f"  capture: {line}")


def _table(title: str, rows: dict) -> None:
    print(f"\n== {title} (% of busy time) ==")
    for name, share in sorted(rows.items(), key=lambda kv: -kv[1]):
        print(f"  {share:6.2f}%  {name}")


def main(argv: list[str] | None = None) -> None:
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawTextHelpFormatter)
    p.add_argument("trace_dir")
    p.add_argument("--top", type=int, default=15,
                   help="operations and idle gaps to list")
    p.add_argument("--spans", default=None,
                   help="the run's spans.jsonl (default: looked for in the "
                        "trace directory and the two above it)")
    args = p.parse_args(argv)

    path = xplane.find_xplane(args.trace_dir)
    spans_path = args.spans or find_spans(args.trace_dir)
    if path is None:
        found = host_thread(spans_path) if spans_path else None
        if found is None:
            raise SystemExit(
                f"no .xplane.pb under {args.trace_dir} (is this a "
                f"jax.profiler output dir? expected "
                f"plugins/profile/<time>/*.xplane.pb)")
        print(f"no capture under {args.trace_dir}: the sections that need "
              f"none, from {spans_path}")
        _print_host_thread(found, args.top)
        return
    trace = xplane.read(path)
    s = summarize(path, args.top, trace)
    print(f"trace: {path}")
    print(f"{s['chips']} chip(s), window {s['window_s']:.4f} s, busy "
          f"{s['busy_s']:.4f} s, idle {s['idle_percent']:.3f}%; "
          f"{s['scoped_percent']:.1f}% of busy time under a named scope")
    _table("by class", s["by_class"])
    _table("by scope", s["by_scope"])
    if {"pp_bwd", "pp_w"} & set(s["by_scope"]):
        print(f"  {s['grad_fold_percent']:6.2f}%  {GRAD_FOLD} (of the "
              f"pp_bwd / pp_w above: gradients added into their float32 "
              f"accumulators)")
    print("\n== operations with the most device time of their own ==")
    for name, seconds in s["top_ops"]:
        print(f"  {1e3 * seconds:10.3f} ms  {name}")
    print("\n== longest idle gaps, by the host event over them ==")
    for name, seconds in s["idle_gaps"]:
        print(f"  {1e3 * seconds:10.3f} ms  {name}")
    if s["tick_gap"] is not None:
        part = s["tick_gap"]
        print(f"\n== idle time a serving tick, by the innermost engine event "
              f"over it ==\n  {tick_gap.ms_a_tick(part):10.3f} ms a tick over "
              f"{part['ticks']} ticks")
        for name in tick_gap.PARTS:
            if part["parts_ns"][name]:
                print(f"  {tick_gap.ms_a_tick(part, name):10.3f} ms  {name}")
        print(f"  {tick_gap.describe_shift(s['device_clock_shift'])}")
    print(f"\nclock: {tick_gap.describe_clock(s['clock'])}")
    pipeline = tick_pipeline(spans_path) if spans_path else None
    if pipeline is not None:
        print(f"\n== the engine's tick in flight ({spans_path}) ==\n"
              f"  ticks_ahead {pipeline['ticks_ahead']} of "
              f"{pipeline['ticks']} ticks "
              f"({100.0 * pipeline['ticks_ahead'] / max(pipeline['ticks'], 1):.2f}%)"
              f"\n  rows_overrun {pipeline['rows_overrun']} of "
              f"{pipeline['tokens']} row-ticks"
              f"\n  rows_joined_fed {pipeline[JOINED]}")
    walked = kv_steps(spans_path) if spans_path else None
    if walked is not None:
        print(f"\n== the tick's paged attention ==\n"
              f"  kv_pages_live / kv_steps_visited "
              f"{walked['pages_live'] / walked['steps_visited']:.2f} "
              f"pages a step\n  kv_steps_visited {walked['steps_visited']} of "
              f"{walked['steps_table']} steps of the rows' whole tables "
              f"({walked['steps_visited'] / walked['steps_table']:.3f})")
    units = unit_pipeline(spans_path) if spans_path else None
    if units is not None:
        both = units["units"] + units["skipped"]
        print(f"\n== the engine's prefill unit in flight ==\n"
              f"  units ahead {units['ahead']} of {units['units']} "
              f"({100.0 * units['ahead'] / units['units']:.2f}%)"
              f"\n  reads {units['reads']} "
              f"({units['reads'] / units['units']:.2f} a unit)"
              f"\n  units run {units['units']} / skipped {units['skipped']} "
              f"({100.0 * units['skipped'] / both:.2f}% of both were chunks "
              f"of nothing but pads)")
    drafted = drafting_counters(spans_path) if spans_path else None
    if drafted is not None:
        print(f"\n== drafting with the multi-token-prediction module ==\n"
              f"  spec_accepted {drafted['spec_accepted']} of "
              f"{drafted['spec_offered']} drafts offered "
              f"({100.0 * drafted['spec_accepted'] / max(drafted['spec_offered'], 1):.4f}%)"
              f"\n  tokens {drafted['tokens']} over {drafted['row_ticks']} "
              f"row-ticks "
              f"({drafted['tokens'] / max(drafted['row_ticks'], 1):.5f} a "
              f"row-tick), {drafted['tokens_discarded']} discarded"
              f"\n  spec_dead_entries {drafted['spec_dead_entries']} cache "
              f"places written and not kept"
              f"\n  mtp_positions {drafted['mtp_positions']} in ticks, "
              f"{drafted['unit_positions']} in prefill units")
    recurrent = recurrent_counters(spans_path) if spans_path else None
    if recurrent is not None:
        print("\n== the state-space family's counters, summed over layers ==")
        for where, sums in recurrent.items():
            print(f"  {where}: " + ", ".join(
                f"{k} {sums[k]}" for k in RECURRENT))
    found = host_thread(spans_path, trace) if spans_path else None
    if found is not None:
        _print_host_thread(found, args.top)


if __name__ == "__main__":
    main()
