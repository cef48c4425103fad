"""One run of one cell: `python benchmark/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>` from the root of a checkout. The last line of
standard output is the result as one JSON object (benchmark/README.md)."""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmark import device, harness, registry

    cell = registry.load_cell(ROOT, args.workload)
    # the program's one rule for the compile cache: JAX_COMPILATION_CACHE_DIR
    # where set, else <checkout>/.jax_cache. Before anything compiles.
    from llama_pipeline_parallel_tpu.utils import compile_cache

    cache_dir = compile_cache.setup()
    devices = device.require_chips(cell.chips)
    print(f"benchmark: {args.workload} seed={args.seed} seconds={args.seconds} "
          f"trace={args.trace} on {len(devices)} x {devices[0].device_kind}; "
          f"compile cache {cache_dir} "
          f"({compile_cache.entry_count(cache_dir)} entries)", flush=True)
    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              bool(args.trace), devices, T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
