"""Helpers shared by the Pallas kernel modules (docs/KERNELS.md): the
interpret gating rule and the token-block ladder exist once, here.
"""

from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

# Mosaic's scoped-VMEM budget for one kernel. Its default (16 MiB on a v5e)
# is below what the kernels' 1024-wide fp32 score tiles and double-buffered
# weight blocks need; a v5e core has 128 MiB of VMEM, so half of it.
VMEM_LIMIT_BYTES = 64 << 20

# preferred token-block heights, largest first (8k-aligned for fp32 tiles);
# the fallback is the full token count (one block)
TOKEN_BLOCKS = (256, 128, 64, 32, 16, 8)


def compiler_params(*dimension_semantics: str) -> pltpu.CompilerParams:
    """Mosaic parameters every kernel here passes: which grid axes are
    independent ("parallel") and which carry an accumulator ("arbitrary"),
    under the shared scoped-VMEM budget."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def interpret_mode() -> bool:
    """Kernel interpret gating, decided by the backend alone: Mosaic-compiled
    on a TPU, interpreted everywhere else (the CPU tests). There is no
    override — nothing can make a kernel interpret on the chip."""
    return jax.default_backend() != "tpu"


def token_block(n: int, block_tokens: int | None) -> int:
    """Token-block height for an `[n, ...]` row grid: the caller's pinned
    value (validated to divide n) or the largest ladder entry dividing n,
    else n itself (one block)."""
    if block_tokens is not None:
        if n % block_tokens:
            raise ValueError(
                f"block_tokens={block_tokens} must divide the flattened "
                f"token count {n}")
        return block_tokens
    for cand in TOKEN_BLOCKS:
        if n % cand == 0:
            return cand
    return n
