"""Host-offloaded AdamW: optimizer state in host DRAM, stepped by native code.

Replaces the reference's ZeRO-offload arrangement (`offload_optimizer:
device: cpu, pin_memory: True` + DeepSpeedCPUAdam, reference conf
yaml:160-162, README.md:70-71 — the "~800 GB host RAM for 65B" path): on a
TPU-VM the fp32 master params and Adam moments stay in host DRAM, the device
holds only the bf16 working copy, and each step moves grads D2H and fresh
bf16 params H2D. Unlike the reference, bf16 compute works WITH offload —
there is no fp16 loss-scale state machine to conflict with it (reference
README.md:133-139 documents that incompatibility).

Sharding-aware, multi-host capable: masters/moments are stored PER DEVICE
SHARD, mirroring the param arrays' mesh sharding — each process keeps and
updates only the shards its addressable devices hold (a 65B pp=8 run spreads
the ~780 GB of optimizer state across hosts the way the reference's ZeRO-1
offload spreads it across ranks). The global grad norm deduplicates
replicated shards by min-device ownership and sums across processes with one
tiny host allgather. Checkpoint state is assembled into globally-sharded
jax.Arrays, so Orbax writes each host's shards from that host.

Step-time hygiene: grad D2H transfers for ALL shards are started
asynchronously up front and overlap the per-shard kernel work; the device
working copy is cast fp32->bf16 on the HOST (native round-to-nearest-even
kernel), halving H2D bytes vs uploading fp32 and casting on device. Per-phase
timings are kept in `last_timings`.

The update kernel is C++ (csrc/host_adamw.cpp, OpenMP parallel + SIMD),
compiled on first use with the system g++ into `<checkout>/.lpt_native/`
(keyed by the source's hash) and bound via ctypes — no pybind11 dependency.
There is no fallback: where the build fails, `optimizer_offload` raises.

This module is the HOST-side tier (python-driven D2H/kernel/H2D around the
step); its IN-GRAPH sibling is `utils/host_stash.py`, which generalizes the
same keep-cold-bytes-in-host-DRAM-behind-overlapped-transfers idea to the
pipeline schedules' residual stores (the zb1 W queue, the stage-input ring
buffer) with `jax.device_put`-to-memory-kind transfers XLA schedules
asynchronously INSIDE the jitted step — see docs/SCHEDULES.md "Host
offload". Measure the link both tiers share with
`host_stash.measure_transfer_bandwidth` (bench.py `extra:offload-bw`).
"""

from __future__ import annotations

import ctypes
import dataclasses
import hashlib
import os
import subprocess
import time
from typing import Any

import numpy as np

from llama_pipeline_parallel_tpu.optim.optimizer import OptimizerConfig, warmup_decay_schedule
from llama_pipeline_parallel_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# inside the package so installed wheels ship the kernel source too
_CSRC = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                     "csrc", "host_adamw.cpp"))
# the build lands in the checkout (git-ignored), never in a shared /tmp
_BUILD_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".lpt_native")
_lib = None


def native_lib_path() -> str:
    """Where the kernel built from csrc/host_adamw.cpp AS COMMITTED lives:
    keyed by the source's sha256, so an edited source rebuilds and a binary
    from another checkout or an older source can never be picked up."""
    with open(_CSRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"host_adamw-{digest}.so")


def _load_native():
    """Compile (once per source hash) and load the native kernel. A build
    failure raises: `optimizer_offload: true` without its kernel is an
    error, not a reason to step 65B of optimizer state in numpy."""
    global _lib
    if _lib is not None:
        return _lib
    so_path = native_lib_path()
    if not os.path.exists(so_path):
        os.makedirs(_BUILD_DIR, exist_ok=True)
        tmp = f"{so_path}.{os.getpid()}.tmp"  # concurrent builders: atomic rename
        cmd = ["g++", "-O3", "-march=native", "-fopenmp", "-shared", "-fPIC",
               _CSRC, "-o", tmp]
        try:
            subprocess.run(cmd, check=True, capture_output=True, text=True)
        except (OSError, subprocess.CalledProcessError) as e:
            raise RuntimeError(
                f"could not build the host AdamW kernel ({' '.join(cmd)}): "
                f"{getattr(e, 'stderr', '') or e}") from e
        os.replace(tmp, so_path)
        logger.info("compiled host AdamW kernel -> %s", so_path)
    lib = ctypes.CDLL(so_path)
    lib.adamw_step.argtypes = [ctypes.POINTER(ctypes.c_float)] * 3 + [
        ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
        ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float,
        ctypes.c_float, ctypes.c_int64, ctypes.c_float]
    lib.adamw_step.restype = None
    lib.l2_norm_sq.restype = ctypes.c_double
    lib.l2_norm_sq.argtypes = [ctypes.POINTER(ctypes.c_float), ctypes.c_int64]
    lib.f32_to_bf16.argtypes = [ctypes.POINTER(ctypes.c_float),
                                ctypes.POINTER(ctypes.c_uint16), ctypes.c_int64]
    lib.f32_to_bf16.restype = None
    _lib = lib
    return _lib


def _fptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _cast_bf16(src: np.ndarray, native) -> np.ndarray:
    """fp32 -> bf16 numpy array (native round-to-nearest-even kernel)."""
    import ml_dtypes

    out = np.empty(src.shape, np.uint16)
    native.f32_to_bf16(_fptr(src), out.ctypes.data_as(
        ctypes.POINTER(ctypes.c_uint16)), src.size)
    return out.view(ml_dtypes.bfloat16)


def _index_key(index: tuple) -> tuple:
    return tuple((s.start, s.stop, s.step) for s in index)


@dataclasses.dataclass
class _Shard:
    """One distinct shard of one param leaf, process-local."""

    index: tuple          # tuple of slices into the global array
    devices: list         # addressable devices holding this shard
    owner: bool           # does THIS process own it for global-norm counting?
    p: np.ndarray         # fp32 master
    m: np.ndarray
    v: np.ndarray


class _Leaf:
    """All process-local shards of one param leaf + its global layout."""

    def __init__(self, x) -> None:
        import jax

        self.global_shape = tuple(x.shape)
        self.sharding = x.sharding
        imap = self.sharding.devices_indices_map(self.global_shape)
        by_key: dict = {}
        for d, index in imap.items():
            by_key.setdefault(_index_key(index), []).append(d)
        local_data = {_index_key(s.index): s.data for s in x.addressable_shards}
        pid = jax.process_index()
        self.shards: dict = {}
        for key, devs in by_key.items():
            local_devs = [d for d in devs if d.process_index == pid]
            if not local_devs:
                continue
            owner_dev = min(devs, key=lambda d: d.id)
            self.shards[key] = _Shard(
                index=tuple(slice(*k) for k in key),
                devices=local_devs,
                owner=owner_dev.process_index == pid,
                p=np.array(local_data[key], np.float32, copy=True, order="C"),
                m=np.zeros(local_data[key].shape, np.float32),
                v=np.zeros(local_data[key].shape, np.float32),
            )
        if not self.shards:
            raise ValueError("process holds no shard of a param leaf — the "
                             "mesh leaves this host without addressable devices")

    def grad_shards(self, g) -> dict:
        """key -> host fp32 grad array for each of this leaf's shard keys.
        Falls back to slicing a full transfer when the grad array's sharding
        does not match the masters' (it does on the trainer path)."""
        avail = {_index_key(s.index): s.data for s in g.addressable_shards}
        out, full = {}, None
        for key, shard in self.shards.items():
            if key in avail:
                out[key] = avail[key]
            else:
                if full is None:
                    full = np.asarray(g, np.float32)
                out[key] = np.ascontiguousarray(full[shard.index])
        return out

    def assemble(self, values: dict) -> Any:
        """Build the globally-sharded jax.Array for this leaf from per-key
        host arrays (this process contributes its addressable shards).

        Fully-replicated leaves (one distinct shard) go through
        `jax.device_put(value, sharding)`, which lets the runtime upload once
        and broadcast. Sharded leaves use per-device puts; when a shard is
        replicated across dp those bytes upload once per local replica — an
        ICI-broadcast optimization left for when multi-chip H2D shows up in
        a profile (single-chip, the bench path, has no replicas)."""
        import jax

        if len(self.shards) == 1:
            (shard,) = self.shards.values()
            covers_all = all(
                (sl.start in (0, None)) and (sl.stop in (dim, None))
                for sl, dim in zip(shard.index, self.global_shape))
            if covers_all:
                return jax.device_put(values[_index_key(shard.index)],
                                      self.sharding)
        arrays = []
        for shard in self.shards.values():
            key = _index_key(shard.index)
            for d in shard.devices:
                arrays.append(jax.device_put(values[key], d))
        return jax.make_array_from_single_device_arrays(
            self.global_shape, self.sharding, arrays)


@dataclasses.dataclass
class HostOffloadAdamW:
    """AdamW with fp32 masters + moments in host DRAM, sharding-aware.

    Contract mirrors optax.adamw (chained with clip_by_global_norm) numerics.
    `update(grad_tree)` steps the masters; `device_params(dtype)` builds the
    bf16 working copy; `masters_tree()`/`state_dict()` expose globally
    sharded fp32 arrays for checkpointing.
    """

    cfg: OptimizerConfig
    # Numerics-observatory skip semantics (utils/numerics.py, mirroring the
    # fused step's in-graph guard): when the global grad norm is nonfinite,
    # leave masters/moments/step-count untouched for this step — the working
    # copy re-uploads unchanged. `last_nonfinite` flags the verdict either
    # way; `nonfinite_count` accumulates skips.
    skip_nonfinite: bool = False
    # Compute the global grad norm ON DEVICE (one fused XLA reduction + a
    # scalar D2H) instead of on the host after the full-tree D2H. The host
    # path must pull EVERY gradient byte down before the first AdamW can run
    # (the global clip factor depends on all of them — the SURVEY §7.3-item-3
    # serialization); with the scalar known up front, the fused step streams
    # leaf-by-leaf — wait-for-leaf-i, update-i, cast-i, upload-i — so later
    # leaves' wire time hides behind earlier leaves' host compute. Numerics:
    # fp32 accumulation, exactly optax.clip_by_global_norm's math (the host
    # path accumulates in fp64, so the clip factor can differ in the last
    # ulps — opt-in, and update() always keeps the host path).
    device_norm: bool = False

    def init(self, params_tree: Any) -> None:
        import jax

        leaves, self._treedef = jax.tree_util.tree_flatten(params_tree)
        self._leaves = [_Leaf(x) for x in leaves]
        self.step_count = 0
        self._schedule = warmup_decay_schedule(
            self.cfg.learning_rate, self.cfg.total_steps, self.cfg.warmup_steps)
        self._native = _load_native()
        self._norm_sq_jit = None
        self.last_timings: dict = {}
        self.last_nonfinite = False
        self.nonfinite_count = 0

    # -- master access ----------------------------------------------------

    def _check_tree(self, tree: Any) -> list:
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if treedef != self._treedef or len(leaves) != len(self._leaves):
            raise ValueError("tree does not match the initialized param tree")
        return leaves

    def load_masters(self, params_tree: Any) -> None:
        """Replace the fp32 masters (warm start / resume)."""
        for leaf, x in zip(self._leaves, self._check_tree(params_tree)):
            self._scatter(leaf, x, "p")

    def _scatter(self, leaf: _Leaf, x, attr: str) -> None:
        """Load a (global jax.Array or host numpy) value into leaf shards."""
        shard_data = ({_index_key(s.index): s.data for s in x.addressable_shards}
                      if hasattr(x, "addressable_shards") else None)
        for key, shard in leaf.shards.items():
            if shard_data is not None and key in shard_data:
                val = shard_data[key]
            else:
                val = np.asarray(x)[shard.index]
            setattr(shard, attr,
                    np.array(val, np.float32, copy=True, order="C"))

    def masters_tree(self) -> Any:
        """fp32 masters as globally-sharded jax.Arrays (checkpoint input)."""
        import jax

        vals = [leaf.assemble({_index_key(s.index): s.p
                               for s in leaf.shards.values()})
                for leaf in self._leaves]
        return jax.tree_util.tree_unflatten(self._treedef, vals)

    def abstract_tree(self) -> Any:
        """ShapeDtypeStruct tree of the fp32 masters WITH their mesh
        shardings — the restore template that keeps checkpoint loads sharded
        (no leaf ever funnels through a single device)."""
        import jax

        vals = [jax.ShapeDtypeStruct(leaf.global_shape, np.float32,
                                     sharding=leaf.sharding)
                for leaf in self._leaves]
        return jax.tree_util.tree_unflatten(self._treedef, vals)

    def moments_tree(self, attr: str) -> Any:
        """One moment tree ("m" or "v") as globally-sharded jax.Arrays —
        assembled alone so the checkpoint path can stream p/m/v one at a
        time instead of materializing 12 bytes/param on device at once."""
        import jax

        vals = [leaf.assemble({_index_key(s.index): getattr(s, attr)
                               for s in leaf.shards.values()})
                for leaf in self._leaves]
        return jax.tree_util.tree_unflatten(self._treedef, vals)


    def _cast_working(self, p: np.ndarray, dtype) -> np.ndarray:
        """fp32 master -> working-copy dtype, on the HOST (bf16 via the
        native RNE kernel halves H2D bytes vs uploading fp32). The ONE cast
        policy for both the standalone and the fused step paths; always
        allocates a fresh buffer, so uploads never alias the mutable
        masters."""
        import jax.numpy as jnp

        if jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16):
            return _cast_bf16(p, self._native)
        return p.astype(dtype)

    def device_params(self, dtype=None) -> Any:
        """The bf16 (or `dtype`) device working copy, cast on the HOST so the
        H2D transfer moves half the bytes of an fp32 upload."""
        import jax
        import jax.numpy as jnp

        t0 = time.perf_counter()
        dtype = dtype or jnp.bfloat16
        vals = []
        for leaf in self._leaves:
            cast = {_index_key(s.index): self._cast_working(s.p, dtype)
                    for s in leaf.shards.values()}
            vals.append(leaf.assemble(cast))
        # Cast + transfer DISPATCH only: device_put returns after enqueueing,
        # so the wire time is absorbed by the next dispatched computation
        # (blocking here would serialize away exactly the overlap we want).
        self.last_timings["h2d_dispatch_ms"] = 1000 * (time.perf_counter() - t0)
        return jax.tree_util.tree_unflatten(self._treedef, vals)

    # -- the step ---------------------------------------------------------

    def _gather_grads_and_norm(self, glvs: list) -> tuple[list, float, float]:
        """D2H every grad shard + the clipped-AdamW scale factors.

        All transfers start first (they overlap each other); each leaf's
        norm-square kernel then runs as soon as ITS transfer lands, hiding
        later leaves' wire time behind earlier leaves' norm compute. The
        global norm deduplicates replicated shards by min-device ownership
        and sums across processes with one tiny host allgather.
        Returns (per-leaf grad dicts, lr, grad_scale)."""
        import jax

        for g in glvs:
            if hasattr(g, "copy_to_host_async"):
                g.copy_to_host_async()
        grad_np: list[dict] = []
        norm_sq = 0.0
        for leaf, g in zip(self._leaves, glvs):
            shards = leaf.grad_shards(g)
            gnp = {k: np.ascontiguousarray(np.asarray(v, np.float32))
                   for k, v in shards.items()}
            grad_np.append(gnp)
            for key, shard in leaf.shards.items():
                if not shard.owner:
                    continue
                gs = gnp[key]
                norm_sq += self._native.l2_norm_sq(_fptr(gs), gs.size)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            norm_sq = float(multihost_utils.process_allgather(
                np.asarray(norm_sq, np.float64)).sum())
        lr, grad_scale = self._clip_and_advance(float(np.sqrt(norm_sq)))
        return grad_np, lr, grad_scale

    def _clip_and_advance(self, norm: float) -> tuple[float, float]:
        """Shared epilogue of both norm paths: clip factor from the global
        norm, step count, lr sample, telemetry. A nonfinite norm under
        `skip_nonfinite` advances NOTHING (no step count, no moments later —
        the apply loops check `last_nonfinite`), matching the fused step's
        in-graph where-skip."""
        import math

        self.last_nonfinite = not math.isfinite(norm)
        self.last_grad_norm = norm
        if self.last_nonfinite and self.skip_nonfinite:
            self.nonfinite_count += 1
            self.last_lr = float(self._schedule(self.step_count))
            logger.warning("nonfinite global grad norm (%r); skipping the "
                           "optimizer step (%d skipped so far)", norm,
                           self.nonfinite_count)
            return self.last_lr, 0.0
        clip = self.cfg.max_grad_norm
        grad_scale = clip / norm if (clip and norm > clip) else 1.0
        self.step_count += 1
        lr = float(self._schedule(self.step_count - 1))
        self.last_lr = lr
        return lr, grad_scale

    def _skip_this_step(self) -> bool:
        return self.skip_nonfinite and self.last_nonfinite

    def _apply_shard(self, shard: _Shard, g: np.ndarray, lr: float,
                     grad_scale: float) -> None:
        self._native.adamw_step(
            _fptr(shard.p), _fptr(shard.m), _fptr(shard.v),
            _fptr(g), shard.p.size,
            lr, self.cfg.beta1, self.cfg.beta2, self.cfg.eps,
            self.cfg.weight_decay, self.step_count, grad_scale)

    def update(self, grads_tree: Any) -> None:
        """One clipped AdamW step on every process-local shard."""
        t0 = time.perf_counter()
        grad_np, lr, grad_scale = self._gather_grads_and_norm(
            self._check_tree(grads_tree))
        t1 = time.perf_counter()
        if not self._skip_this_step():
            for leaf, gnp in zip(self._leaves, grad_np):
                for key, shard in leaf.shards.items():
                    self._apply_shard(shard, gnp[key], lr, grad_scale)
        t2 = time.perf_counter()
        # fresh dict: a stale phase key from the OTHER step path must not
        # linger in the metrics stream (d2h_norm_ms covers transfers AND the
        # norm/allgather — the norm kernels overlap the transfer tail)
        self.last_timings = {"d2h_norm_ms": 1000 * (t1 - t0),
                             "update_ms": 1000 * (t2 - t1)}

    def _norm_sq_and_step(self, glvs: list) -> tuple[float, float]:
        """Device-side global grad norm: one fused fp32 reduction (exactly
        optax.clip_by_global_norm's accumulation) whose replicated scalar is
        the only thing the host blocks on — dispatched BEFORE the per-leaf
        D2H stream so it lands while the leaves are still on the wire. Under
        multi-process, GSPMD inserts the cross-host reduction; every process
        calls this every step, so the collective stays uniform. Returns
        (lr, grad_scale) and advances the step count."""
        import jax
        import jax.numpy as jnp

        if self._norm_sq_jit is None:
            # accumulate in fp32 regardless of grad dtype (gpipe grads can
            # arrive bf16): a bf16 norm carries ~8 mantissa bits — wrong
            # clipping decisions near the threshold
            self._norm_sq_jit = jax.jit(
                lambda gs: sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                               for g in gs))
        norm_sq_dev = self._norm_sq_jit(glvs)
        for g in glvs:
            g.copy_to_host_async()
        return self._clip_and_advance(float(jnp.sqrt(norm_sq_dev)))

    def update_and_refresh(self, grads_tree: Any, dtype=None) -> Any:
        """One clipped AdamW step AND the fresh device working copy, software-
        pipelined per leaf: leaf i's bf16 cast + H2D upload are dispatched
        the moment its shards are stepped, so the wire time of leaf i
        overlaps leaf i+1's AdamW kernel instead of waiting for the whole
        update (the SURVEY §7.3-item-3 stall: a serial
        update-everything-then-upload-everything step leaves the device idle
        for the full sum of both phases).

        With `device_norm` (the trainer's default) the full-tree D2H barrier
        goes too: the clip factor comes from a device-side reduction, so the
        loop additionally overlaps leaf i+1's DOWNLOAD with leaf i's AdamW —
        end-to-end streaming, phase keys norm_ms / stream_d2h_update_h2d_ms.
        Otherwise numerics are identical to `update()` + `device_params()` —
        same kernels, same order.

        Safe against in-place master mutation: each upload reads a freshly
        allocated cast buffer, never `shard.p` itself."""
        import jax
        import jax.numpy as jnp

        t0 = time.perf_counter()
        glvs = self._check_tree(grads_tree)
        streaming = self.device_norm and all(
            hasattr(g, "copy_to_host_async") for g in glvs)
        if streaming:
            lr, grad_scale = self._norm_sq_and_step(glvs)
            grad_np = None
        else:
            grad_np, lr, grad_scale = self._gather_grads_and_norm(glvs)
        t1 = time.perf_counter()
        dtype = dtype or jnp.bfloat16
        vals = []
        for i, (leaf, g) in enumerate(zip(self._leaves, glvs)):
            # streaming: block on THIS leaf's transfer only (later leaves
            # keep landing while this one updates)
            gnp = (grad_np[i] if grad_np is not None else
                   {k: np.ascontiguousarray(np.asarray(v, np.float32))
                    for k, v in leaf.grad_shards(g).items()})
            cast = {}
            for key, shard in leaf.shards.items():
                if not self._skip_this_step():
                    self._apply_shard(shard, gnp[key], lr, grad_scale)
                cast[key] = self._cast_working(shard.p, dtype)
            # assemble dispatches this leaf's H2D asynchronously; the next
            # leaf's AdamW kernels run while these bytes are on the wire
            vals.append(leaf.assemble(cast))
        t2 = time.perf_counter()
        # fresh dict: no stale keys from the other step paths
        if streaming:
            self.last_timings = {"norm_ms": 1000 * (t1 - t0),
                                 "stream_d2h_update_h2d_ms": 1000 * (t2 - t1)}
        else:
            self.last_timings = {"d2h_norm_ms": 1000 * (t1 - t0),
                                 "update_h2d_ms": 1000 * (t2 - t1)}
        return jax.tree_util.tree_unflatten(self._treedef, vals)

    # -- checkpoint integration ------------------------------------------

    def state_dict(self) -> dict:
        """Moments as params-shaped TREES of globally-sharded arrays so the
        checkpoint engine's canonical (topology-agnostic) layout transform
        applies to them too."""
        return {"m": self.moments_tree("m"), "v": self.moments_tree("v"),
                "step_count": np.int64(self.step_count)}

    def load_state_dict(self, state: dict) -> None:
        for leaf, x in zip(self._leaves, self._check_tree(state["m"])):
            self._scatter(leaf, x, "m")
        for leaf, x in zip(self._leaves, self._check_tree(state["v"])):
            self._scatter(leaf, x, "v")
        self.step_count = int(state["step_count"])
