"""Three-source memory accounting — the memory observatory
(docs/OBSERVABILITY.md "Memory").

The selection machinery (`preflight --select`, the solver offload
vectors, the 65B frontier) ranks candidates against an *analytic* byte
model, patched by the anchored-compile heuristic ("XLA-CPU over-counts
>2^31-element stash buffers"); PR 14 closed the model-vs-measured loop
for **time** but memory had no measured counterpart. This module is that
counterpart, from three independent sources:

1. **compiled** — `compiled.memory_analysis()` (argument / output /
   temp / alias bytes) plus best-effort top-N buffer attribution from
   the HLO text, captured once per jitted program the run compiles
   (train step, eval, prefill, decode). Available at compile time on
   any backend; degrades to nothing where a backend hides it.
2. **live** — a per-step host-side sampler polling
   `device.memory_stats()` (bytes_in_use / peak / largest alloc on
   TPU), host RSS, and the host-stash/offload resident estimate into an
   opt-in `memory.jsonl`. OFF is zero overhead: the sampler never
   touches the compiled graph (no callback, no extra output — pinned in
   tests/test_memwatch.py).
3. **serving** — the page-pool occupancy / fragmentation gauges
   (serve/engine.py reads serve/pages.py; this module only defines the
   shared reader + snapshot plumbing).

All three feed the perf ledger (`mem_peak_gib` model-vs-measured rows →
`perf_report --emit-calibration` → `preflight --calibration --mem-scale`)
and the OOM forensics path: `dump_oom_snapshot` writes a bounded
snapshot (last memory rows, compiled analyses, top buffers, page table)
to `<output_dir>/oom/` when a RESOURCE_EXHAUSTED surfaces, which the
supervisor labels as an `oom` outcome and the fleet observatory alerts
on (`oom_recent`).
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import time
from typing import Any

from llama_pipeline_parallel_tpu.utils.logging import get_logger

logger = get_logger(__name__)

MEMORY_KEYS = {"enabled", "every", "top_buffers"}

GIB = 1024 ** 3

# Bounded forensics: keep the newest N snapshots, the last M live rows.
OOM_KEEP_SNAPSHOTS = 8
OOM_KEEP_ROWS = 32


@dataclasses.dataclass(frozen=True)
class MemoryConfig:
    """The `memory.*` config block, parsed in one place (train.py +
    tools/serve.py agree on the keys; unknown keys rejected)."""

    enabled: bool = False
    every: int = 1  # sample every N steps
    top_buffers: int = 8  # HLO buffer attribution depth per program

    @classmethod
    def from_cfg(cls, node: Any) -> "MemoryConfig":
        node = node or {}
        if not isinstance(node, dict):
            raise ValueError(
                f"memory must be a mapping, e.g. memory: {{enabled: "
                f"true}} — got {node!r}")
        unknown = set(node) - MEMORY_KEYS
        if unknown:
            raise ValueError(f"unknown memory.* key(s) {sorted(unknown)}; "
                             f"known: {sorted(MEMORY_KEYS)}")
        raw = node.get("every", 1)
        every = 1 if raw is None else int(raw)  # `every:` empty = default
        if every < 1:
            raise ValueError(f"memory.every must be >= 1, got {every}")
        raw = node.get("top_buffers", 8)
        top = 8 if raw is None else int(raw)
        if top < 0:
            raise ValueError(f"memory.top_buffers must be >= 0, got {top}")
        return cls(enabled=bool(node.get("enabled", False)), every=every,
                   top_buffers=top)


# -- live telemetry (the one spelling; trace.py delegates here) --------------

def device_peak_bytes() -> tuple[int | None, str]:
    """(max peak bytes across local devices, source).

    TPU/GPU report `memory_stats()["peak_bytes_in_use"]`; the CPU backend
    returns None, where the process peak RSS (ru_maxrss) stands in so the
    metrics field exists on every platform — the source tag keeps the two
    from being compared against each other."""
    try:
        import jax

        peaks = []
        for d in jax.local_devices():
            stats = d.memory_stats()
            if stats and stats.get("peak_bytes_in_use") is not None:
                peaks.append(int(stats["peak_bytes_in_use"]))
        if peaks:
            return max(peaks), "device"
    except Exception as e:
        logger.debug("memory_stats unavailable: %r", e)
    try:
        import resource

        # linux reports ru_maxrss in KiB
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024, "host_rss"
    except Exception:
        return None, "unavailable"


def live_sample() -> dict:
    """One host-side poll of every live source: per-device
    bytes_in_use / peak / largest alloc (worst device, plus the
    bytes_in_use of EACH local device in `jax.local_devices()` order — the
    evidence that a sharded state really landed on every chip), host RSS.
    Purely observational — never touches a compiled program."""
    out: dict[str, Any] = {}
    try:
        import jax

        in_use, peak, largest = [], [], []
        for d in jax.local_devices():
            stats = d.memory_stats()
            if not stats:
                continue
            if stats.get("bytes_in_use") is not None:
                in_use.append(int(stats["bytes_in_use"]))
            if stats.get("peak_bytes_in_use") is not None:
                peak.append(int(stats["peak_bytes_in_use"]))
            if stats.get("largest_alloc_size") is not None:
                largest.append(int(stats["largest_alloc_size"]))
        if in_use:
            out["device_bytes_in_use"] = max(in_use)
            out["device_bytes_in_use_each"] = in_use
        if peak:
            out["device_peak_bytes"] = max(peak)
        if largest:
            out["device_largest_alloc"] = max(largest)
    except Exception as e:
        logger.debug("live memory_stats unavailable: %r", e)
    try:
        import resource

        out["host_rss_bytes"] = \
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:
        pass
    return out


# -- compiled-program analysis ----------------------------------------------

# HLO buffer lines look like
#   `  %fusion.3 = bf16[8,512,8192]{2,1,0} fusion(...)` — the dtype[shape]
# token is enough to rank the program's biggest values for attribution.
_HLO_VALUE = re.compile(
    r"%([\w.\-]+)\s*=\s*([a-z][a-z0-9]*)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


def _top_hlo_buffers(hlo_text: str, n: int) -> list[dict]:
    """Best-effort largest-value attribution from the optimized HLO text:
    name, dtype, shape, bytes for the top-n distinct values. A ranking
    aid for "what IS that 40 GiB temp", not an allocator ground truth
    (XLA may alias or split them) — wrapped so an unparseable dump
    degrades to []."""
    if n <= 0:
        return []
    try:
        best: dict[str, dict] = {}
        for m in _HLO_VALUE.finditer(hlo_text):
            name, dtype, dims = m.group(1), m.group(2), m.group(3)
            unit = _DTYPE_BYTES.get(dtype)
            if unit is None:
                continue
            elems = 1
            if dims:
                for d in dims.split(","):
                    elems *= int(d)
            nbytes = elems * unit
            prev = best.get(name)
            if prev is None or nbytes > prev["bytes"]:
                best[name] = {"name": name, "dtype": dtype,
                              "shape": [int(d) for d in dims.split(",")]
                              if dims else [], "bytes": nbytes}
        ranked = sorted(best.values(), key=lambda b: -b["bytes"])[:n]
        return ranked
    except Exception as e:
        logger.debug("HLO buffer attribution failed: %r", e)
        return []


def compiled_memory(compiled, top_buffers: int = 8,
                    label: str = "") -> dict | None:
    """The compile-time memory evidence for one jitted program: the
    `memory_analysis()` aggregates (argument / output / temp / alias
    bytes, peak = arg + out + temp − alias) plus top-N HLO buffer
    attribution. Returns None where the backend hides the analysis —
    callers treat compiled evidence as optional everywhere."""
    try:
        ma = compiled.memory_analysis()
    except Exception as e:
        logger.debug("memory_analysis unavailable (%s): %r", label, e)
        return None
    if ma is None:
        return None
    try:
        arg = int(ma.argument_size_in_bytes)
        out_b = int(ma.output_size_in_bytes)
        temp = int(ma.temp_size_in_bytes)
        alias = int(ma.alias_size_in_bytes)
    except Exception as e:
        logger.debug("memory_analysis attrs unreadable (%s): %r", label, e)
        return None
    rec = {
        "label": label,
        "argument_bytes": arg,
        "output_bytes": out_b,
        "temp_bytes": temp,
        "alias_bytes": alias,
        "generated_bytes": getattr(ma, "generated_code_size_in_bytes", None),
        "peak_bytes": arg + out_b + temp - alias,
        # the compiler's own peak of its buffer assignment, where it says
        "compiler_peak_bytes": getattr(ma, "peak_memory_in_bytes", None),
    }
    if top_buffers:
        try:
            hlo = compiled.as_text()
        except Exception:
            hlo = ""
        rec["top_buffers"] = _top_hlo_buffers(hlo, top_buffers)
    return rec


# -- the run-side watch ------------------------------------------------------

class MemoryWatch:
    """The trainer/server-side driver: captures compiled analyses (one
    shot per label), samples the live sources on a step cadence into
    `memory.jsonl`, keeps a bounded ring of recent rows for OOM
    snapshots, and pairs compiled-vs-live into perf-ledger rows.

    Everything here is host-side bookkeeping: a MemoryWatch never
    changes what gets compiled or dispatched (the zero-cost pin)."""

    def __init__(self, output_dir: str, every: int = 1,
                 top_buffers: int = 8, write: bool = True,
                 stash_bytes: int | None = None):
        self.every = max(int(every), 1)
        self.top_buffers = int(top_buffers)
        self.stash_bytes = stash_bytes  # host-stash resident estimate
        self.compiled: dict[str, dict] = {}
        self.path = os.path.join(output_dir, "memory.jsonl")
        self._f = None
        if write:
            try:
                os.makedirs(output_dir or ".", exist_ok=True)
                self._f = open(self.path, "a", buffering=1)
            except OSError:
                logger.exception("memory.jsonl open failed (sampling "
                                 "continues unwritten)")
        self._recent: list[dict] = []  # ring for the OOM snapshot
        self.last_sample: dict | None = None

    def note_compiled(self, label: str, compiled) -> dict | None:
        """Record one program's compile-time analysis (first call per
        label wins — re-compiles of the same program would only repeat
        it). `compiled` is a jax Compiled (train step, eval, prefill,
        decode...)."""
        if label in self.compiled:
            return self.compiled[label]
        rec = compiled_memory(compiled, self.top_buffers, label=label)
        if rec is not None:
            self.compiled[label] = rec
            self._write({"kind": "compiled", "time": time.time(), **rec})
            logger.info(
                "compiled memory (%s): peak %.2f GiB (arg %.2f + out %.2f "
                "+ temp %.2f - alias %.2f)", label,
                rec["peak_bytes"] / GIB, rec["argument_bytes"] / GIB,
                rec["output_bytes"] / GIB, rec["temp_bytes"] / GIB,
                rec["alias_bytes"] / GIB)
        return rec

    def sample(self, step: int) -> dict | None:
        """One live poll (respecting the `every` cadence) -> one
        memory.jsonl row. Returns the row (or None when skipped)."""
        if step % self.every != 0:
            return None
        row = {"kind": "sample", "step": int(step), "time": time.time(),
               **live_sample()}
        if self.stash_bytes is not None:
            row["host_stash_bytes"] = int(self.stash_bytes)
        self.last_sample = row
        self._recent.append(row)
        if len(self._recent) > OOM_KEEP_ROWS:
            self._recent = self._recent[-OOM_KEEP_ROWS:]
        self._write(row)
        return row

    def _write(self, rec: dict) -> None:
        if self._f is None:
            return
        try:
            self._f.write(json.dumps(rec) + "\n")
        except (OSError, ValueError, TypeError):
            logger.exception("memory.jsonl write failed (record dropped)")

    def health_gauges(self) -> dict:
        """Live gauges for the metrics line / health.json — present only
        once a sample exists, so downstream joins never see fabricated
        zeros."""
        if not self.last_sample:
            return {}
        out = {}
        for k in ("device_bytes_in_use", "device_peak_bytes",
                  "host_rss_bytes"):
            if self.last_sample.get(k) is not None:
                out[k] = self.last_sample[k]
        return out

    def perf_rows(self, run: str | None = None) -> list[dict]:
        """Perf-ledger pairing: per compiled program a
        `compiled_peak_gib:<label>` row, plus one `mem_peak_gib` row
        with model = the train step's compiled peak, measured = the live
        device peak — the memory analogue of the mfu/bubble rows."""
        from llama_pipeline_parallel_tpu.utils import perf

        rows: list[dict] = []
        for label, rec in self.compiled.items():
            rows.append(perf.make_row(
                f"compiled_peak_gib:{label}",
                model=round(rec["peak_bytes"] / GIB, 3), measured=None,
                unit="GiB", source="memwatch", run=run,
                temp_gib=round(rec["temp_bytes"] / GIB, 3),
                argument_gib=round(rec["argument_bytes"] / GIB, 3)))
        step_rec = (self.compiled.get("train_step")
                    or next(iter(self.compiled.values()), None))
        live_peak = None
        live_src = None
        if self.last_sample and self.last_sample.get("device_peak_bytes"):
            live_peak = self.last_sample["device_peak_bytes"]
            live_src = "device"
        else:
            b, src = device_peak_bytes()
            if b is not None and src == "device":
                live_peak, live_src = b, src
        if step_rec is not None or live_peak is not None:
            rows.append(perf.make_row(
                "mem_peak_gib",
                model=(round(step_rec["peak_bytes"] / GIB, 3)
                       if step_rec is not None else None),
                measured=(round(live_peak / GIB, 3)
                          if live_peak is not None else None),
                unit="GiB", source="memwatch", run=run,
                measured_source=live_src))
        return rows

    def snapshot(self) -> dict:
        """The bounded forensics payload: recent live rows + every
        compiled analysis (top buffers included)."""
        return {"recent": list(self._recent[-OOM_KEEP_ROWS:]),
                "compiled": dict(self.compiled)}

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None


def read_memory(path: str) -> list[dict]:
    """Every parseable record of a memory.jsonl — missing file, empty
    file, torn tail, or interleaved garbage lines degrade to whatever
    parses (perf.read_jsonl, the one spelling of the tolerant reader)."""
    from llama_pipeline_parallel_tpu.utils.perf import read_jsonl

    return read_jsonl(path)


# -- OOM forensics -----------------------------------------------------------

def is_resource_exhausted(exc: BaseException) -> bool:
    """True for XLA's allocation-failure surface: the exception type name
    or message carries RESOURCE_EXHAUSTED / "out of memory" (jaxlib
    raises XlaRuntimeError with the gRPC-style code prefix; the chaos
    injector raises a plain RuntimeError with the same marker)."""
    text = f"{type(exc).__name__}: {exc}"
    return ("RESOURCE_EXHAUSTED" in text
            or "out of memory" in text.lower()
            or "ResourceExhausted" in type(exc).__name__)


def oom_dir(output_dir: str) -> str:
    return os.path.join(output_dir, "oom")


def dump_oom_snapshot(output_dir: str, step: int | None,
                      error: BaseException | str,
                      memwatch: "MemoryWatch | None" = None,
                      page_table: dict | None = None,
                      extra: dict | None = None) -> str | None:
    """Write one bounded OOM snapshot to `<output_dir>/oom/` — the last
    live rows, every compiled analysis (top buffers included), and the
    page table if a server was involved — atomically (tmp + rename) so a
    watcher never reads a torn file; the newest OOM_KEEP_SNAPSHOTS are
    retained. Swallows its own failures: forensics must never turn an
    OOM abort into a second crash."""
    try:
        d = oom_dir(output_dir)
        os.makedirs(d, exist_ok=True)
        snap: dict[str, Any] = {
            "time": time.time(),
            "step": None if step is None else int(step),
            "error": str(error)[:2000],
            "error_type": (type(error).__name__
                           if isinstance(error, BaseException) else "str"),
            "live": live_sample(),
        }
        if memwatch is not None:
            snap["memwatch"] = memwatch.snapshot()
        if page_table is not None:
            snap["page_table"] = page_table
        if extra:
            snap.update(extra)
        stamp = time.strftime("%Y%m%d-%H%M%S", time.gmtime(snap["time"]))
        path = os.path.join(d, f"oom-{stamp}-{os.getpid()}.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(snap, f, indent=2, default=str)
        os.replace(tmp, path)
        # retention: newest first, drop the tail
        snaps = sorted((p for p in os.listdir(d)
                        if p.startswith("oom-") and p.endswith(".json")),
                       reverse=True)
        for old in snaps[OOM_KEEP_SNAPSHOTS:]:
            try:
                os.remove(os.path.join(d, old))
            except OSError:
                pass
        logger.error("OOM snapshot written: %s", path)
        return path
    except Exception:
        logger.exception("OOM snapshot failed (forensics dropped)")
        return None


def read_oom_snapshots(output_dir: str) -> list[dict]:
    """Every parseable snapshot under `<output_dir>/oom/`, newest first —
    missing dir, torn or garbage files degrade to whatever parses (the
    reader house rule)."""
    d = oom_dir(output_dir)
    out: list[dict] = []
    try:
        names = sorted((p for p in os.listdir(d)
                        if p.startswith("oom-") and p.endswith(".json")),
                       reverse=True)
    except OSError:
        return out
    for name in names:
        try:
            with open(os.path.join(d, name)) as f:
                rec = json.load(f)
            if isinstance(rec, dict):
                rec["_file"] = name
                out.append(rec)
        except (OSError, ValueError):
            continue
    return out


def latest_oom_mtime(output_dir: str) -> float | None:
    """mtime of the newest OOM snapshot, or None — the one spelling the
    supervisor ("crash + fresh snapshot => oom outcome") and the fleet
    alert (`oom_recent`: snapshot newer than the member's registration)
    both compare timestamps against."""
    d = oom_dir(output_dir)
    try:
        times = [os.path.getmtime(os.path.join(d, p))
                 for p in os.listdir(d)
                 if p.startswith("oom-") and p.endswith(".json")]
    except OSError:
        return None
    return max(times) if times else None
