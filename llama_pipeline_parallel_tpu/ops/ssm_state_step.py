"""Pallas TPU kernel: one step of the Mamba-2 recurrence on the rows of a
recurrent store, where they lie (`models/ssm_moe/decode.py` `tick_logits`).

    S' = exp(dt A) S + (x dt) (x) B        y = S' C

for every slot's row of ONE layer of the store `[layers, slots, H, P, N]`,
float32 throughout. The store is an input aliased to an output and the layer
a block index, so nothing slices a layer out in front and nothing splices it
back behind: a step reads a block of the state from HBM once, steps it, takes
its product with C and writes it to the place it came from. The other layers
of the store are never touched. Plain XLA made three passes over a layer
here (PERF.md PR 45 and PR 46): the fusion that forms S' and reduces it with
C, and a `dynamic-update-slice` that forms S' again to write it.

Schedule: grid (slot, block of `hb` heads). A block is `hb` whole groups'
heads `[hb, P, N]`, one contiguous run of HBM; a group's heads read its B
and C `[1, N]`. What is small is formed by XLA outside: the decay `exp(dt
A)`, a scalar a head, comes in SMEM; `x dt` comes a slot at a time `[P, H]`,
its P along the sublanes as the state's, so a head's column is one lane,
broadcast; y leaves the same way, a head's column set in its lane. Inside a
block a loop walks the groups and only a group's heads are unrolled: a
group's lanes are rotated to the front (`pltpu.roll` by the group's first
head, a traced shift), so every lane index in the body is static and the
program is one group long whatever the block (the heads unrolled across a
block of 128 took 4 to 5 s to trace and lower for the five layers at every
start of the server, compile cache or not: `setup_s`). A group wider than
`_UNROLL` heads (one group of 64, the dense block's) is walked in runs of
`_UNROLL`, each rotated to the front the same way and reading its group's B
and C again, so 36 calls of such a layer cost what 36 calls of a 16-head
group cost to trace and lower (docs/KERNELS.md has the seconds). A row that is not
decoding has dt = 0: decay 1 and an update of 0 keep its state bit for bit,
as the formula always did.

The sum over N runs on the MXU: the float32 products `S' * C` times a matrix
of ones at `Precision.HIGHEST` (their bfloat16 pieces are exact, ones are
exact, the accumulator is float32: a float32 sum in another order). Summed
along the lanes on the XLU, beside the lane broadcasts of `x dt`, the
reduction and not the DMA bounds the kernel (PERF.md PR 46 has both).

The block (`head_block`): HBM streams it in and out and nothing else is of
any size, so it only has to hide a grid step and the rotations a step
brings: the most whole groups that divide H under `_BLOCK_BYTES`. On the
v5e the cell's five layers take 4.75 ms in blocks of one group (512 KB),
4.27 of two, 4.17 of four and of all eight (PERF.md PR 46): from 2 MB on the
DMA alone is left, at 79% of the HBM's speed, which a plain copy through
the same grid reads too.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llama_pipeline_parallel_tpu.ops.pallas_common import (
    compiler_params,
    interpret_mode,
)
from llama_pipeline_parallel_tpu.utils import trace

# a slot's blocks of heads share the slot's block of y
_COMPILER_PARAMS = compiler_params("parallel", "arbitrary")

# state one grid step brings to VMEM and takes back (four buffers this size)
_BLOCK_BYTES = 4 << 20
_LANES = 128
# heads unrolled in the loop's body: a group of no more is one pass (16 a
# group in the expert block's cell); a wider group (64 in the dense block's)
# is walked in runs of this many, its B and C read again a run
_UNROLL = 16


def head_block(heads: int, groups: int, head_dim: int, state: int) -> int:
    """Heads of one block: the most whole groups that divide `heads` and
    keep the float32 block `[hb, head_dim, state]` under `_BLOCK_BYTES` (at
    least one group)."""
    per = heads // groups
    fits = [per * g for g in range(1, groups + 1) if groups % g == 0
            and per * g * head_dim * state * 4 <= _BLOCK_BYTES]
    return max(fits, default=per)


def _kernel(decay_ref, xdt_ref, b_ref, c_ref, state_ref, out_ref, y_ref, *,
            per_group: int):
    slot, j = pl.program_id(0), pl.program_id(1)
    hb, _, N = state_ref.shape
    H = xdt_ref.shape[1]                 # the heads' lanes, whole vregs
    lane = jax.lax.broadcasted_iota(jnp.int32, xdt_ref.shape, 1)
    ones = jnp.ones((N, H), jnp.float32)
    # the heads one pass of the loop unrolls: a group, or `_UNROLL` of a
    # wider group's (the program is that long whatever the block)
    sub = min(per_group, _UNROLL)
    passes_a_group = per_group // sub

    def run(s, y):
        first = j * hb + s * sub             # the run's first head: a lane
        # the run's heads to lanes [0, sub): static lanes from here
        xdt = pltpu.roll(xdt_ref[...], (H - first) % H, 1)
        g = s if passes_a_group == 1 else s // passes_a_group
        B, C = b_ref[g], c_ref[g]                           # [1, N]
        y_run = jnp.zeros_like(xdt)
        for h in range(sub):
            head = s * sub + h
            S = (decay_ref[slot, first + h] * state_ref[head]
                 + xdt[:, h:h + 1] * B)                     # [P, N]
            out_ref[head] = S
            # the sum over N on the MXU: a head's column, on every lane
            summed = jax.lax.dot_general(
                S * C, ones, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)         # [P, H]
            y_run = jnp.where(lane == h, summed, y_run)
        mine = (lane >= first) & (lane < first + sub)
        return jnp.where(mine, pltpu.roll(y_run, first, 1), y)

    # y's block is the slot's, whichever heads' block this is: every head's
    # lane of it is some run's by the slot's last block
    y_ref[...] = jax.lax.fori_loop(0, hb // sub, run, y_ref[...])


def ssm_state_step(store: jnp.ndarray, index: int, x: jnp.ndarray,
                   dt: jnp.ndarray, A: jnp.ndarray, B: jnp.ndarray,
                   C: jnp.ndarray, block_heads: int | None = None):
    """The recurrence for ONE position of every row of layer `index` (a
    Python int) of `store` float32 [layers, slots, H, P, N], which the caller
    gives up (donate it: the result is the same buffer). x: [slots, H, P];
    dt: [slots, H] (0: the row keeps its state); A: [H]; B, C: [slots, G,
    N], a head reading its group's by shape. Returns (y [slots, H, P]
    without the skip, the store with that layer's rows stepped).
    `block_heads` pins the block for the tests and the timing; None takes
    `head_block`'s."""
    layers, slots, H, P, N = store.shape
    G = B.shape[1]
    if store.dtype != jnp.float32 or x.shape != (slots, H, P) or H % G:
        raise ValueError(
            f"store {store.dtype}{store.shape}, x {x.shape} and {G} groups "
            f"do not belong together")
    per = H // G
    hb = block_heads or head_block(H, G, P, N)
    if hb % per or H % hb:
        raise ValueError(f"a block of {hb} heads is not whole groups of "
                         f"{per} dividing {H}")
    blocks, gb = H // hb, hb // per
    decay = jnp.exp(dt * A)                                 # [slots, H]
    # [slots, P, H], the heads along whole vregs of lanes (Mosaic rotates
    # nothing narrower by a traced shift)
    lanes = pl.cdiv(H, _LANES) * _LANES
    xdt = jnp.pad(jnp.swapaxes(x * dt[..., None], 1, 2),
                  ((0, 0), (0, 0), (0, lanes - H)))
    grouped = lambda a: a.reshape(slots, G, 1, N)
    by_slot = pl.BlockSpec((None, P, lanes), lambda s, j, decay: (s, 0, 0))
    by_group = pl.BlockSpec((None, gb, 1, N), lambda s, j, decay: (s, j, 0, 0))
    state_spec = pl.BlockSpec((None, None, hb, P, N),
                              lambda s, j, decay: (index, s, j, 0, 0))
    store, y = pl.pallas_call(
        functools.partial(_kernel, per_group=per),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(slots, blocks),
            in_specs=[by_slot, by_group, by_group, state_spec],
            out_specs=[state_spec, by_slot],
        ),
        out_shape=[jax.ShapeDtypeStruct(store.shape, jnp.float32),
                   jax.ShapeDtypeStruct((slots, P, lanes), jnp.float32)],
        input_output_aliases={4: 0},        # the store, after the prefetch
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret_mode(),
        name=trace.KERNEL_SSM_STATE_STEP,
    )(decay, xdt, grouped(B), grouped(C), store)
    return jnp.swapaxes(y[:, :, :H], 1, 2), store
