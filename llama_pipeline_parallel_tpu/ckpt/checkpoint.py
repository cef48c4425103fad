"""Checkpoint save/load/resume on Orbax.

Replaces the reference's three cooperating mechanisms (SURVEY.md §5.4):
`engine.save_checkpoint` layer files + `latest` tag (reference
trainer_base_ds_mp.py:205, convert2ckpt.py:76-77), the module-only warm start
with its monkey-patched loader (trainer_base_ds_mp.py:49-121 — patched
upstream bug: stock load insisted on optimizer state), and resume-step
parsing from `checkpoint-N` dirnames (trainer_base_ds_mp.py:452-455).

Design differences from the reference:
- Canonical layout: params are stored with layer leaves `[num_layers, ...]`,
  never `[num_stages, layers_per_stage, ...]`; the stage manifest is metadata,
  not filename arithmetic. Any topology restores any checkpoint — pp resize,
  dp shrink/grow, flat<->interleaved — via resharded Orbax reads against the
  CURRENT run's templates (the reference forbids exactly this, SURVEY.md
  §7.3 item 5; docs/RESILIENCE.md "Elastic resume"). meta.json additionally
  records the writer's `topology` and sampler `data_state` (via save's
  `extra_meta=`) so a resume can explain the resize and reposition the data
  stream in O(1).
- Params and optimizer state are separate Orbax items, so a module-only warm
  start from a FULL training checkpoint needs no monkey-patch — it simply
  doesn't open the optimizer item.
- Integrity (docs/RESILIENCE.md): the commit records per-file sha256 digests
  in meta.json; restores verify them first and QUARANTINE a corrupt
  checkpoint to `checkpoint-N.corrupt` (latest_step() then falls back to the
  previous complete one). meta/tag writes are atomic (tmp + os.replace) and
  all storage I/O runs under the shared transient-retry policy
  (utils/retry.py, LPT_RETRY_* knobs).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import re
from typing import Any

import jax
import numpy as np
import orbax.checkpoint as ocp

from llama_pipeline_parallel_tpu.models.llama.config import LlamaConfig
from llama_pipeline_parallel_tpu.models.llama.manifest import StageManifest
from llama_pipeline_parallel_tpu.parallel import distributed as dist
from llama_pipeline_parallel_tpu.parallel import pipeline as pl
from llama_pipeline_parallel_tpu.utils import faults, retry, trace
from llama_pipeline_parallel_tpu.utils.logging import get_logger

logger = get_logger(__name__)

LATEST_TAG = "latest"  # tag-file name, as in the reference (convert2ckpt.py:76)
_CKPT_RE = re.compile(r"^checkpoint-(\d+)$")
QUARANTINE_SUFFIX = ".corrupt"
# Orbax's default lets one OCDBT data file grow to 2 GiB and writes any array
# up to that size as a single chunk, so a 7B-width checkpoint is a handful of
# files of hundreds of MB and more — and a host with a per-file size limit
# refuses them (EFBIG, met on the driver's first chip run: PERF.md PR 21). With
# this target no chunk exceeds 64 MiB and a data file closes once it reaches
# it, so every file stays under 128 MiB; restores reshard chunk-wise as before.
DATA_FILE_TARGET_BYTES = 64 << 20


class CheckpointCorruptError(RuntimeError):
    """A checkpoint failed integrity verification (or its meta.json is
    unreadable). Deliberately NOT an OSError: the retry layer must never
    re-try a deterministic corruption verdict — the caller falls back to
    the previous complete checkpoint instead (docs/RESILIENCE.md)."""


def _storage_policy() -> retry.RetryPolicy:
    """The shared transient-storage retry policy (env-tunable, LPT_RETRY_*)."""
    return retry.RetryPolicy.from_env()


def _write_file_atomic(path: str, data: str) -> None:
    """Crash-safe small-file write: tmp file + fsync + os.replace, under the
    storage retry policy. A crash mid-write can never publish a truncated
    file — readers see the old content or the new, never a torn one (the
    seed's bare open/write here was exactly how a killed process produced a
    meta.json that made `_is_complete` true but `load_meta` raise)."""

    def write():
        faults.fire("storage_write", tag=path)
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                try:
                    os.remove(tmp)
                except OSError:
                    pass

    retry.retry_call(write, policy=_storage_policy(),
                     describe=f"write {os.path.basename(path)}")


def _digests_enabled() -> bool:
    return os.environ.get("LPT_CKPT_DIGESTS", "1") != "0"


def _verify_default() -> bool:
    return os.environ.get("LPT_CKPT_VERIFY", "1") != "0"


def _file_digest(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _dir_digests(root: str) -> dict[str, str]:
    """sha256 of every file under `root` (relative posix paths), meta.json
    excluded — the digests live INSIDE meta.json, which is written after
    this walk, so it can never hash itself."""
    out: dict[str, str] = {}
    for dirpath, _dirnames, filenames in os.walk(root):
        for name in sorted(filenames):
            full = os.path.join(dirpath, name)
            rel = os.path.relpath(full, root).replace(os.sep, "/")
            if rel == "meta.json":
                continue
            out[rel] = retry.retry_call(
                lambda full=full: _file_digest(full), policy=_storage_policy(),
                describe=f"digest {rel}")
    return out


def _canonicalize_moments(tree: Any, manifest: StageManifest, to_canonical: bool) -> Any:
    """Unstack/stack any params-shaped subtrees inside the optimizer state."""
    fn = pl.unstack_stages if to_canonical else pl.stack_stages

    def walk(node):
        if isinstance(node, dict) and "layers" in node and "embed" in node:
            return fn(node, manifest)
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            mapped = [walk(v) for v in node]
            return type(node)(*mapped) if hasattr(node, "_fields") else type(node)(mapped)
        return node

    return walk(tree)


def _abstract(tree: Any) -> Any:
    def leaf(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        return jax.ShapeDtypeStruct(
            np.shape(x), np.asarray(x).dtype if np.isscalar(x) else x.dtype,
            sharding=getattr(x, "sharding", None))

    return jax.tree.map(leaf, tree)


@dataclasses.dataclass
class CheckpointManager:
    """Layout: <root>/checkpoint-<step>/{params/, opt/, meta.json} + <root>/latest."""

    root: str

    def __post_init__(self) -> None:
        os.makedirs(self.root, exist_ok=True)
        self._ckptr = ocp.AsyncCheckpointer(ocp.PyTreeCheckpointHandler())
        self._pending: Any = None  # in-flight async commit thread
        self._pending_error: Any = None  # exception raised on that thread
        self._commit_seq = 0  # collective save counter -> unique barrier keys
        # Durability backstop (a caller that lets the process exit after
        # save(blocking=False) must not silently lose meta/tag): finalize on
        # interpreter exit. Weakref so the hook never pins the manager alive.
        import atexit
        import weakref

        ref = weakref.ref(self)
        # bounded join in the backstop: if a peer process died before the
        # commit's host_barrier, an unbounded join would hold every surviving
        # process's EXIT for the full barrier timeout (a crashed pod becoming
        # a 30-minute hang per host); explicit finalize() keeps waiting
        # forever because the caller is still alive and wants the result
        # 600s default: generous for a healthy large-model array flush, but
        # well under the commit barrier's 1800s dead-peer timeout — the
        # wedge this bound exists to not inherit. Flush time scales with
        # checkpoint size and storage speed, so very large models on slow
        # object stores can raise it via the env knob.
        timeout = float(os.environ.get("LPT_ATEXIT_COMMIT_TIMEOUT_S", "600"))
        atexit.register(
            lambda: (m := ref()) is not None and m.finalize(timeout_s=timeout))

    def finalize(self, timeout_s: float | None = None) -> None:
        """Block until a `save(..., blocking=False)` commit (array flush,
        meta/tag write, on_complete hook) finishes. No-op when nothing is
        pending. MUST run before process exit — the commit thread is a
        daemon precisely so a crash can't hang shutdown, which means clean
        exits have to wait for it explicitly. Re-raises a failure from the
        background commit: a failed periodic checkpoint must surface exactly
        like a failed blocking one, not vanish into a thread traceback.

        `timeout_s` (atexit backstop only): give up after this long — log
        and abandon the commit instead of wedging interpreter shutdown on a
        barrier whose peers may be dead."""
        t, self._pending = self._pending, None
        if t is not None:
            t.join(timeout_s)
            if t.is_alive():
                # keep tracking the live commit: a later finalize()/save()
                # must re-join THIS thread, not start a second commit racing
                # the shared latest-tag/meta writes
                self._pending = t
                logger.error(
                    "async checkpoint commit still running after %.0fs at "
                    "exit; abandoning the wait (daemon thread dies with the "
                    "process — the checkpoint stays incomplete and resume "
                    "will ignore it)", timeout_s)
                return
        err, self._pending_error = self._pending_error, None
        if err is not None:
            raise RuntimeError("async checkpoint commit failed") from err

    # -- paths ------------------------------------------------------------

    def step_dir(self, step: int) -> str:
        return os.path.join(self.root, f"checkpoint-{step}")

    def _is_complete(self, name: str) -> bool:
        # meta.json is written LAST (after the async array writes finish), so
        # its presence marks a durably complete checkpoint; an interrupted
        # save leaves a dir that must be ignored, not resumed from. Presence
        # is not enough: a meta.json that exists but does not PARSE (torn
        # write from a pre-atomic-writer crash, storage corruption) marks a
        # checkpoint that would explode at restore — quarantine it now so
        # latest_step() falls back instead.
        meta = os.path.join(self.root, name, "meta.json")
        if not os.path.isfile(meta):
            return False

        def read():
            with open(meta) as f:
                return f.read()

        try:
            raw = retry.retry_call(read, policy=_storage_policy(),
                                   non_retryable=(FileNotFoundError,),
                                   describe=f"read {name}/meta.json")
        except FileNotFoundError:
            return False  # quarantined/pruned underneath this scan
        except OSError:
            # a PERSISTENT read failure is a storage outage, not a
            # corruption verdict: do NOT quarantine a possibly-healthy dir,
            # and do NOT answer "incomplete" either — that would let
            # latest_step() return None and a resume silently restart from
            # step 0, overwriting real progress. Fail the query; the
            # supervisor restarts the run once storage recovers.
            logger.error("cannot read %s/meta.json after retries; refusing "
                         "to classify the checkpoint during a storage outage",
                         name)
            raise
        try:
            json.loads(raw)
            return True
        except ValueError:
            # the bytes WERE readable and do not parse: torn write from a
            # pre-atomic-writer crash, or storage corruption
            self._quarantine(name, "unparseable meta.json")
            return False

    def _quarantine(self, name: str, reason: str) -> str | None:
        """Move checkpoint-N aside to checkpoint-N.corrupt so no reader
        (latest_step, find_resume_checkpoint, prune) ever considers it
        again. Rename, not delete: the bytes stay for a post-mortem.
        Best-effort — a peer process racing to the same verdict wins the
        rename and this one just logs."""
        src = os.path.join(self.root, name)
        dst = src + QUARANTINE_SUFFIX
        n = 0
        while os.path.exists(dst):
            n += 1
            dst = f"{src}{QUARANTINE_SUFFIX}.{n}"
        try:
            os.rename(src, dst)
        except OSError as e:
            logger.warning("could not quarantine %s (%s): %r", name, reason, e)
            return None
        logger.error("quarantined %s -> %s (%s); resume will fall back to "
                     "the previous complete checkpoint", name,
                     os.path.basename(dst), reason)
        return dst

    def latest_tag_value(self) -> str | None:
        """Raw contents of the `latest` tag file, if present."""
        tag = os.path.join(self.root, LATEST_TAG)
        if not os.path.exists(tag):
            return None
        with open(tag) as f:
            return f.read().strip()

    def list_steps(self, complete_only: bool = False) -> list[int]:
        """All checkpoint-N step numbers on disk, ascending. Completeness is
        probed on the ACTUAL dirname, so non-canonical spellings (e.g. a
        hand-copied 'checkpoint-007') are still recognized."""
        self.finalize()  # meta.json of an in-flight async save lands first
        return sorted(int(m.group(1)) for d in os.listdir(self.root)
                      if (m := _CKPT_RE.match(d))
                      and (not complete_only or self._is_complete(d)))

    def is_complete(self, step: int) -> bool:
        """Whether checkpoint-<step> finished durably (meta.json present)."""
        self.finalize()
        for d in os.listdir(self.root):
            m = _CKPT_RE.match(d)
            if m and int(m.group(1)) == step:
                return self._is_complete(d)
        return False

    def latest_step(self) -> int | None:
        self.finalize()
        name = self.latest_tag_value()
        if name is not None:
            m = _CKPT_RE.match(name)
            if m and self._is_complete(name):
                return int(m.group(1))
            logger.warning("stale latest tag %r; falling back to directory scan", name)
        steps = self.list_steps(complete_only=True)
        return max(steps) if steps else None

    # -- save -------------------------------------------------------------

    def prune(self, keep_last: int) -> list[int]:
        """Delete the oldest COMPLETE checkpoints beyond the newest
        `keep_last` (disk-retention policy, process 0 only on shared
        storage). Incomplete dirs are left alone — they are either mid-write
        or already ignored by every reader. Returns the pruned steps."""
        import shutil

        if keep_last < 1:
            raise ValueError(f"keep_last must be >= 1, got {keep_last}")
        if jax.process_index() != 0:
            return []
        # raw listing, NOT list_steps(): prune runs on the async commit
        # thread, and list_steps' finalize() would join the current thread.
        # Deletion goes by the ACTUAL dirname, so non-canonical spellings
        # ('checkpoint-007') are pruned too, not step_dir() reconstructions.
        complete = sorted((int(m.group(1)), d) for d in os.listdir(self.root)
                          if (m := _CKPT_RE.match(d)) and self._is_complete(d))
        doomed = complete[:-keep_last]
        for s, dirname in doomed:
            shutil.rmtree(os.path.join(self.root, dirname), ignore_errors=True)
            logger.info("pruned %s (save_total_limit=%d)", dirname, keep_last)
        return [s for s, _ in doomed]

    def save(self, step: int, params_stacked: dict, manifest: StageManifest,
             cfg: LlamaConfig, opt_state: Any | None = None,
             blocking: bool = True, on_complete: Any = None,
             keep_last: int | None = None,
             extra_meta: dict | None = None) -> str:
        """Save train state (canonical layout) + metadata, update `latest`.

        `opt_state=None` produces a module-only checkpoint (the converter's
        output — like reference convert2ckpt.py, which writes no optimizer
        state either).

        `blocking=False` (SURVEY.md §5.3: Orbax ASYNC save): Orbax copies
        the arrays device-to-host synchronously inside `save` (so the caller
        may donate/overwrite its buffers immediately), while the disk flush
        and meta/tag commit run on a background thread — training overlaps
        the checkpoint's durability tail instead of stalling on it. At most
        one async commit is in flight: the next save (or `finalize()`) joins
        the previous one first, re-raising any background failure.

        Async stays async at `process_count > 1` (the reference paid a full
        barrier + s5cmd stall every 50 steps here, trainer_base_ds_mp.py:
        205-223): `_commit` synchronizes processes with a coordination-
        service RPC barrier (`host_barrier`), never a device collective, so
        the commit thread cannot race the main thread's training
        collectives. The only cross-process assumption is the one the
        layout already makes — `root` is shared storage (process 0 alone
        writes meta/tag for everyone).

        `on_complete(path)` runs after the commit (in-thread when async) —
        the off-node sync hook's slot, so it never sees a half-written dir.

        `extra_meta`: extra JSON-serializable keys merged into meta.json —
        the trainer records the run's `topology` (source mesh/schedule) and
        `data_state` (sampler position) here so an elastic resume can
        reshard and reposition without replaying anything
        (docs/RESILIENCE.md "Elastic resume").
        """
        self.finalize()
        path = self.step_dir(step)
        # the span covers what the TRAINING LOOP pays for: the synchronous
        # D2H copy (and, when blocking, the full commit); the async tail is
        # its own `ckpt_commit` span on the commit thread, visible in
        # spans.jsonl but excluded from the RunClock's wall-time buckets
        with trace.span("ckpt_save", step=step, blocking=blocking):
            self._save_item(os.path.join(path, "params"),
                            pl.unstack_stages(params_stacked, manifest))
            if opt_state is not None:
                self._save_item(os.path.join(path, "opt"),
                                _canonicalize_moments(opt_state, manifest, to_canonical=True))

            def commit():
                self._commit(path, step, manifest, cfg,
                             has_optimizer_state=opt_state is not None,
                             **(extra_meta or {}))
                if on_complete is not None:
                    on_complete(path)
                if keep_last:  # None/0 both mean "no retention limit"
                    self.prune(keep_last)

            if blocking:
                commit()

        if not blocking:
            import threading

            def guarded():
                try:
                    with trace.span("ckpt_commit", step=step):
                        commit()
                except BaseException as e:  # surfaced by finalize()
                    self._pending_error = e

            self._pending = threading.Thread(
                target=guarded, name=f"ckpt-commit-{step}", daemon=True)
            self._pending.start()
        return path

    def save_offload(self, step: int, host, manifest: StageManifest,
                     cfg: LlamaConfig, keep_last: int | None = None,
                     extra_meta: dict | None = None) -> str:
        """Streamed save for the host-offloaded optimizer: params, then m,
        then v, each assembled-and-written before the next is assembled —
        extra device HBM is bounded at ONE fp32 tree instead of three (at
        65B the difference between fitting and OOMing: the whole point of
        offload is that p+m+v do NOT fit on device together).

        `keep_last`: same retention semantics as save() (prune after
        commit; None/0 disable)."""
        self.finalize()
        path = self.step_dir(step)
        with trace.span("ckpt_save", step=step, blocking=True, offload=True):
            self._save_item(os.path.join(path, "params"),
                            pl.unstack_stages(host.masters_tree(), manifest))
            self._ckptr.wait_until_finished()
            for attr in ("m", "v"):
                self._save_item(os.path.join(path, f"opt_{attr}"),
                                pl.unstack_stages(host.moments_tree(attr), manifest))
                self._ckptr.wait_until_finished()
            self._commit(path, step, manifest, cfg, has_optimizer_state=True,
                         opt_layout="offload_parts",
                         opt_step_count=int(host.step_count),
                         **(extra_meta or {}))
            if keep_last:
                self.prune(keep_last)
        return path

    def _commit(self, path: str, step: int, manifest: StageManifest,
                cfg: LlamaConfig, **meta_extra) -> None:
        # The checkpointer writes asynchronously; the tag/meta below must
        # only appear once the array data is durably on disk — on EVERY
        # process, not just this one. Barrier first, then let a single
        # process write the completeness marker and tag (concurrent writers
        # of the same shared-storage file would race, and a fast process
        # could otherwise mark the checkpoint complete while a peer's Orbax
        # writes are still in flight). host_barrier, not barrier(): _commit
        # may run on the async commit thread, where a device collective
        # would race training collectives — the RPC barrier cannot.
        # Barrier keys must be globally unique per wait: root-hash (two
        # managers may commit in one run) + step + a per-manager collective
        # save counter (resaving a step after a topology change reuses the
        # step number).
        import zlib

        self._commit_seq += 1
        key = (f"{zlib.crc32(self.root.encode()):08x}-{step}-{self._commit_seq}")
        self._ckptr.wait_until_finished()
        dist.host_barrier(f"ckpt-arrays-{key}")
        # chaos hook: a `die` rule here kills the process AFTER the arrays
        # are durable but BEFORE the completeness marker — the classic
        # crash-mid-async-save window every resume path must survive
        faults.fire("ckpt_commit", tag=path, step=step)
        if jax.process_index() == 0:
            meta = {
                "step": step,
                "manifest": dataclasses.asdict(manifest),
                "model_config": _config_meta(cfg),
                "format_version": 1,
                **meta_extra,
            }
            if _digests_enabled():
                # hashed AFTER every process's arrays landed (the barrier
                # above), so the digests cover the final bytes of all shards
                with trace.span("ckpt_digest", step=step):
                    meta["integrity"] = {"algo": "sha256",
                                         "files": _dir_digests(path)}
            # atomic + retried: a crash between these two writes leaves a
            # complete, verifiable checkpoint with a stale tag — which
            # latest_step() already recovers from via the directory scan
            _write_file_atomic(os.path.join(path, "meta.json"),
                              json.dumps(meta, indent=2))
            _write_file_atomic(os.path.join(self.root, LATEST_TAG),
                              f"checkpoint-{step}")
        dist.host_barrier(f"ckpt-commit-{key}")
        logger.info("saved checkpoint-%d to %s", step, path)

    def _save_item(self, item_path: str, tree: Any) -> None:
        """One Orbax item write under the storage retry policy (a transient
        I/O failure at write INITIATION retries; the async flush tail is
        covered by wait_until_finished surfacing in _commit/finalize)."""

        def save():
            faults.fire("storage_write", tag=item_path)
            self._ckptr.save(
                item_path, force=True,
                args=ocp.args.PyTreeSave(
                    tree,
                    ocdbt_target_data_file_size=DATA_FILE_TARGET_BYTES))

        retry.retry_call(save, policy=_storage_policy(),
                         describe=f"orbax save {os.path.basename(item_path)}")

    def _restore_item(self, item_path: str, template: Any) -> Any:
        """One Orbax item restore under the storage retry policy (restore is
        synchronous and idempotent, so a blipped read simply re-runs)."""

        def restore():
            faults.fire("storage_write", tag=item_path)
            return self._ckptr.restore(
                item_path,
                args=ocp.args.PyTreeRestore(
                    template,
                    restore_args=ocp.checkpoint_utils.construct_restore_args(
                        template)))

        try:
            return retry.retry_call(
                restore, policy=_storage_policy(),
                non_retryable=(FileNotFoundError,),
                describe=f"orbax restore {os.path.basename(item_path)}")
        except FileNotFoundError as e:
            # on a pod, a PEER process may quarantine the checkpoint while
            # this one is mid-restore (its own verify passed first) — the dir
            # vanishing out from under us is a corruption verdict to fall
            # back from, not a fatal missing-file bug
            step_dir = os.path.dirname(item_path)
            if not os.path.isfile(os.path.join(step_dir, "meta.json")):
                raise CheckpointCorruptError(
                    f"{os.path.basename(step_dir)} disappeared mid-restore "
                    f"(quarantined by a peer?): {e}") from e
            raise

    # -- integrity ---------------------------------------------------------

    def verify(self, step: int) -> None:
        """Recompute the per-file digests recorded at commit and compare.

        Raises CheckpointCorruptError — after quarantining the directory —
        on any mismatch or missing file, so a restore can never silently
        consume a bit-flipped or truncated array item. Checkpoints written
        before the integrity format (no `integrity` in meta.json) pass with
        a log line: verification is best-effort there, not a lockout.

        Multi-host cost note: every process verifies independently (N hosts
        re-hash the same shared-storage files). That is convergent — if one
        host quarantines first, the peers' hashing or restore sees the dir
        vanish and raises the same CheckpointCorruptError, so everyone falls
        back together — but it reads the checkpoint N times; on very large
        checkpoints set LPT_CKPT_VERIFY=0 (or verify=False) and rely on the
        commit-time digests plus an offline check."""
        path = self.step_dir(step)
        name = os.path.basename(path)
        try:
            meta = self.load_meta(step)
        except FileNotFoundError as e:
            # the dir (or its marker) vanished — quarantined by a peer, or
            # never complete. Already invisible to every reader, so there is
            # nothing to quarantine; just direct the caller to fall back.
            raise CheckpointCorruptError(
                f"{name}: meta.json missing: {e}") from e
        except ValueError as e:
            # readable bytes that do not parse: corruption, not an outage
            self._quarantine(name, f"unparseable meta.json ({e!r})")
            raise CheckpointCorruptError(
                f"{name}: meta.json unparseable: {e}") from e
        # any other OSError (persistent storage outage) propagates untouched:
        # same do-not-quarantine-on-I/O-failure policy as _is_complete
        integrity = meta.get("integrity")
        if not integrity:
            logger.info("%s has no integrity digests (pre-integrity format); "
                        "skipping verification", name)
            return
        bad: list[str] = []
        with trace.span("ckpt_verify", step=step):
            for rel, want in integrity.get("files", {}).items():
                full = os.path.join(path, rel.replace("/", os.sep))
                if not os.path.isfile(full):
                    bad.append(f"{rel}: missing")
                    continue
                got = retry.retry_call(
                    lambda full=full: _file_digest(full),
                    policy=_storage_policy(), describe=f"digest {rel}")
                if got != want:
                    bad.append(f"{rel}: sha256 {got[:12]}... != recorded "
                               f"{want[:12]}...")
        if bad:
            self._quarantine(name, f"{len(bad)} corrupt item(s)")
            raise CheckpointCorruptError(
                f"{name} failed integrity verification: " + "; ".join(bad))
        logger.info("%s verified (%d files)", name, len(integrity.get("files", {})))

    # -- load -------------------------------------------------------------

    def load_meta(self, step: int) -> dict:
        self.finalize()
        meta_path = os.path.join(self.step_dir(step), "meta.json")

        def read():
            with open(meta_path) as f:
                return json.load(f)

        return retry.retry_call(read, policy=_storage_policy(),
                                non_retryable=(FileNotFoundError,),
                                describe=f"read {meta_path}")

    def load_params(self, step: int, params_template_stacked: dict,
                    manifest: StageManifest, verify: bool | None = None) -> dict:
        """Module-only warm start (reference `load_module_only=True`,
        trainer_base_ds_mp.py:284): restores params into the CURRENT
        topology's stacked layout, regardless of the PP degree at save time.

        `verify` (default: on, unless LPT_CKPT_VERIFY=0): check the commit's
        recorded digests first; corruption quarantines the checkpoint and
        raises CheckpointCorruptError instead of restoring garbage."""
        if _verify_default() if verify is None else verify:
            self.verify(step)
        with trace.span("ckpt_restore", step=step, item="params"):
            canonical = pl.unstack_stages(params_template_stacked, manifest)
            restored = self._restore_item(
                os.path.join(self.step_dir(step), "params"), _abstract(canonical))
            return pl.stack_stages(restored, manifest)

    def load_offload_moments(self, step: int, params_template_stacked: dict,
                             manifest: StageManifest,
                             verify: bool | None = None) -> tuple[dict, dict, int]:
        """Restore the offload layout's moment trees (m, v, step_count),
        one item at a time (same HBM bounding as save_offload)."""
        if _verify_default() if verify is None else verify:
            self.verify(step)
        meta = self.load_meta(step)
        if meta.get("opt_layout") != "offload_parts":
            raise ValueError(
                f"checkpoint-{step} was not written by the offloaded "
                f"optimizer (opt_layout={meta.get('opt_layout')!r})")
        canonical = pl.unstack_stages(params_template_stacked, manifest)
        out = []
        with trace.span("ckpt_restore", step=step, item="offload_moments"):
            for attr in ("m", "v"):
                restored = self._restore_item(
                    os.path.join(self.step_dir(step), f"opt_{attr}"),
                    _abstract(canonical))
                out.append(pl.stack_stages(restored, manifest))
        return out[0], out[1], int(meta["opt_step_count"])

    def load(self, step: int, params_template_stacked: dict, opt_template: Any,
             manifest: StageManifest, verify: bool | None = None
             ) -> tuple[dict, Any, int]:
        """Full-state resume (reference trainer_base_ds_mp.py:297-299).
        One `verify(step)` covers every item in the dir — the params load
        below skips its own pass so the files are hashed once, not twice."""
        if _verify_default() if verify is None else verify:
            self.verify(step)
        meta = self.load_meta(step)
        if not meta.get("has_optimizer_state"):
            raise ValueError(
                f"checkpoint-{step} has no optimizer state (module-only / "
                f"converter output); use load_params for a warm start")
        if meta.get("opt_layout") == "offload_parts":
            raise ValueError(
                f"checkpoint-{step} was written by the host-offloaded "
                f"optimizer (opt_layout=offload_parts); resume it with "
                f"optimizer_offload: true, or warm-start module-only via "
                f"model_name_or_path")
        params = self.load_params(step, params_template_stacked, manifest,
                                  verify=False)
        with trace.span("ckpt_restore", step=step, item="opt"):
            opt_canonical = _canonicalize_moments(opt_template, manifest, to_canonical=True)
            restored_opt = self._restore_item(
                os.path.join(self.step_dir(step), "opt"), _abstract(opt_canonical))
            opt_state = _canonicalize_moments(restored_opt, manifest, to_canonical=False)
        return params, opt_state, int(meta["step"])


def load_module_checkpoint(checkpoint_dir: str, step: int | None = None
                           ) -> tuple[dict, LlamaConfig, StageManifest, int]:
    """Canonical-layout params + config + manifest from a checkpoint dir.

    The one loader standalone tools share (tools/export_hf.py,
    tools/generate.py): resolves `step` (default: latest), rebuilds the
    LlamaConfig/StageManifest from meta.json, and returns params with layer
    leaves `[num_layers, ...]` (unstacked). Dtypes come from the config's
    defaults, not the training run's — tools cast as they need.
    """
    from llama_pipeline_parallel_tpu.models.llama import model as llama_model

    mgr = CheckpointManager(checkpoint_dir)
    if step is None:
        step = mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {checkpoint_dir}")
    meta = mgr.load_meta(step)
    mc = dict(meta["model_config"])
    mc.pop("dtype", None), mc.pop("param_dtype", None)
    cfg = LlamaConfig(**mc)
    manifest = StageManifest(**meta["manifest"])
    template = pl.stack_stages(
        llama_model.init_params(jax.random.PRNGKey(0), cfg), manifest)
    params = pl.unstack_stages(mgr.load_params(step, template, manifest), manifest)
    return params, cfg, manifest, step


def _config_meta(cfg: LlamaConfig) -> dict:
    out = {}
    for k, v in dataclasses.asdict(cfg).items():
        if k in ("dtype", "param_dtype"):
            out[k] = np.dtype(v).name if not isinstance(v, str) else v
        else:
            out[k] = v
    return out


def find_resume_checkpoint(root: str) -> tuple[int, str] | None:
    """Resume detection (reference parses `checkpoint-N` dirnames,
    trainer_base_ds_mp.py:452-455)."""
    if not os.path.isdir(root):
        return None
    mgr = CheckpointManager(root)
    step = mgr.latest_step()
    if step is None:
        return None
    return step, mgr.step_dir(step)
