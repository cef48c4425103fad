"""Drives one run of one cell and builds its result line.

`run.py` looks for the chip and calls `run_cell`; the tests call `run_cell`
directly with whatever devices they have, which is how the rest of a run is
exercised without a chip.
"""

from __future__ import annotations

import dataclasses
import math
import os
import shutil
import time

from benchmark import registry


@dataclasses.dataclass
class Context:
    root: str
    cell: registry.Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    run_dir: str
    t_start: float        # process start on time.time(): set-up counts from here


@dataclasses.dataclass
class Check:
    """One number compared beside its limit (`value <= limit` is sound)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


class CompileCounter:
    """Counts programs compiled, or fetched from the persistent cache: both
    mean a shape was not warmed up. `in_window` is judged on time.time()."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring

        self.stamps: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.stamps.append(time.time())

    def in_window(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.stamps if t0 <= t <= t1)


def run_cell(root: str, cell_name: str, seed: int, seconds: float,
             trace: bool, devices: list, t_start: float) -> dict:
    """One run; returns the result line as a dict. Prints every check."""
    cell = registry.load_cell(root, cell_name)
    job = registry.load_job(root, cell.job)
    run_dir = os.path.join(root, "benchmark", ".runs",
                           f"{cell_name}.{seed}.{int(trace)}")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    ctx = Context(root=root, cell=cell, seed=seed, seconds=seconds,
                  trace=trace, devices=list(devices), run_dir=run_dir,
                  t_start=t_start)
    compiles = CompileCounter()
    try:
        out = job.run(ctx)
        checks = list(out["checks"])
        t0, t1 = out["window"]
        checks.append(Check("compiles_in_window",
                            float(compiles.in_window(t0, t1)), 0.0))
        for c in checks:
            print(f"check {c.name}: value={c.value!r} limit={c.limit!r} "
                  f"{'ok' if c.ok else 'NOT OK'}", flush=True)
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": out["memory_peak_bytes"]}
        result = {"correct": all(c.ok for c in checks),
                  "attempted": out["attempted"], "failed": out["failed"]}
        bench = registry.load_benchmark(root)
        units = {m["name"]: m["unit"]
                 for m in bench["end_to_end"] + bench["per_layer"]}
        if not trace:
            values = {n: out["end_to_end"][n] for n in cell.end_to_end}
        else:
            obs = out["observations"]
            values = {}
            for name in cell.per_layer:
                value = registry.load_layer_metric(root, name).read(obs)
                if value is not None:
                    values[name] = value
            if obs.get("xplane") and any(obs["xplane"]["devices"].values()):
                from benchmark import xplane

                busy_s, window_s = xplane.busy_and_window(obs["xplane"])
                device["busy_s"], device["window_s"] = busy_s, window_s
                result["breakdown"] = xplane.breakdown(obs["xplane"])
        result["metrics"] = {n: {"value": v, "unit": units[n]}
                             for n, v in values.items()}
        result["device"] = device
        return result
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)
