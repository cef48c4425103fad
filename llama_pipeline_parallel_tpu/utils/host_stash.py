"""Host-DRAM staging layer for pipeline residuals (PipeOffload-style tiering).

The generalization of the streaming idea `optim/offload.py` already proved
for optimizer state — keep the big, cold bytes in host DRAM and stream them
across the PCIe/DMA link behind device compute — applied to the two
IN-GRAPH residual stores the pipeline schedules carry (PipeOffload, arxiv
2503.01328; ROADMAP item 2):

- the zb1 W-queue: every B tick stashes a `(chunk input, ring cotangent)`
  residual pair that only the W-drain phase consumes. At the 65B
  pp8/M=256/v=2 shape this is 2 x 512 hidden-sized buffers per device —
  64 GiB at the reference micro-batch rows, the reason the zb1 config of
  record had to fund its stash from the batch dimension (micro 8 -> 2).
- the 1f1b/interleaved ring buffer of stage-boundary inputs: min(2vS-1, Mv)
  buffered activations per flush whose only reader is a backward tick
  several ticks later.

Mechanism: `jax.device_put` to a MEMORY KIND inside the jitted program.
XLA's host-offloading legalization turns the annotated values into
host-resident buffers with asynchronous copy-start/copy-done pairs that the
latency-hiding scheduler overlaps against the surrounding compute — no host
callback, no Python in the loop, and the value round-trips bit-exactly
(it is a copy, not a cast), which is why offload on/off stays bit-identical
across the whole parity grid (tests/test_host_stash.py).

Ring-buffer discipline (`stash_init`/`stash_push`/`stash_pop`): buffers get
one extra GARBAGE slot and predicated writes route to it, so the schedules'
clipped warmup/drain indices never need the read-modify-write
(`where(valid, new, old)`) the in-HBM buffers used — an RMW on a
host-resident slot would bounce the old value H2D just to write it back.

Backend gating — by the backend alone, with no override: on a TPU or GPU
the transfers are always emitted, so `offload.*` either compiles there or
the run fails with XLA's own message. They are elided only on the CPU
backend. XLA-CPU in the jax this repo is written for (0.9) does report a
`pinned_host` space and lowers the transfers under plain jit (the parity
tests force them on and run the real round trip), but under the trainer's
sharded jit its SPMD partitioner still rejects the placement custom calls
(`RET_CHECK ... Side-effect HLO must have sharding: custom-call
annotate_device_placement`). So there `to_host`/`to_device` are identity
and the SAME schedule code runs with the stores in regular memory (values
identical either way: the transfer is a copy, not a cast); the trainer
logs the resolved mode once. The transfers stay structurally async: tests
pin that the jaxpr's stash traffic is `device_put` data movement only and
the lowered step contains no host-sync primitive
(callback/infeed/outfeed).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def transfers_enabled() -> bool:
    """Whether to_host/to_device emit real memory-space transfers: everywhere
    but the CPU backend (see the module docstring). Read at TRACE time."""
    return jax.default_backend() != "cpu"


def to_host(tree: Any) -> Any:
    """Move every array leaf to the host memory space (async D2H inside jit;
    XLA emits copy-start/copy-done the scheduler overlaps with compute).
    Identity on the CPU backend — same values, one memory."""
    if not transfers_enabled():
        return tree
    return jax.tree.map(
        lambda x: jax.device_put(x, jax.memory.Space.Host), tree)


def to_device(tree: Any) -> Any:
    """Move every array leaf back to device HBM (async H2D inside jit)."""
    if not transfers_enabled():
        return tree
    return jax.tree.map(
        lambda x: jax.device_put(x, jax.memory.Space.Device), tree)


# ---------------------------------------------------------------------------
# Host-resident ring buffers (the schedules' residual stores)
# ---------------------------------------------------------------------------

def stash_init(n_slots: int, shape: tuple[int, ...], dtype) -> jnp.ndarray:
    """A host-resident [n_slots + 1, *shape] buffer; slot n_slots is the
    garbage slot predicated writes land in (see stash_push)."""
    return to_host(jnp.zeros((n_slots + 1,) + tuple(shape), dtype))


def stash_push(buf: jnp.ndarray, value: jnp.ndarray, slot: jnp.ndarray,
               valid: jnp.ndarray) -> jnp.ndarray:
    """Write `value` D2H into `buf[slot]` when `valid`, else into the
    garbage slot — the predication contract the schedules need (clipped
    warmup/drain indices must never clobber a live slot) without the
    read-modify-write an in-HBM `where(valid, new, old)` store uses."""
    n_slots = buf.shape[0] - 1
    target = jnp.where(valid, slot, n_slots)
    return jax.lax.dynamic_update_index_in_dim(buf, to_host(value), target, 0)


def stash_pop(buf: jnp.ndarray, slot: jnp.ndarray) -> jnp.ndarray:
    """Read `buf[slot]` back H2D. Dispatch it as early in the tick as its
    index is known: the copy-start then runs behind whatever compute sits
    between the dispatch and the first use (the W-drain phase goes one
    further and prefetches a whole unit ahead — parallel/pipeline.py)."""
    return to_device(jax.lax.dynamic_index_in_dim(buf, slot, keepdims=False))


# ---------------------------------------------------------------------------
# Host-link bandwidth probe (bench.py `extra:offload-*` rows)
# ---------------------------------------------------------------------------

def measure_transfer_bandwidth(nbytes: int = 1 << 28, reps: int = 3) -> dict:
    """Measured D2H/H2D bandwidth of the host link, GiB/s. The empirical
    anchor for the preflight memory model's `--host-bw-gibps` feasibility
    assumption (tools/preflight.py) — run it on a live chip (bench.py
    `extra:offload-bw` row) and feed the number back. Uses real transfers
    with hard sync points, so on CPU it reports memcpy bandwidth (the row
    is only meaningful on TPU/GPU)."""
    import time

    import numpy as np

    n = max(nbytes // 4, 1)
    host_buf = np.ones((n,), np.float32)
    dev = jax.device_put(host_buf)
    dev.block_until_ready()
    gib = 1 << 30

    t0 = time.perf_counter()
    for _ in range(reps):
        jax.device_put(host_buf).block_until_ready()
    h2d = reps * host_buf.nbytes / (time.perf_counter() - t0) / gib

    np.asarray(dev)  # warm the D2H path
    t0 = time.perf_counter()
    for _ in range(reps):
        np.asarray(dev)
    d2h = reps * host_buf.nbytes / (time.perf_counter() - t0) / gib
    return {"h2d_gibps": round(h2d, 2), "d2h_gibps": round(d2h, 2),
            "probe_mib": round(host_buf.nbytes / (1 << 20), 1)}
