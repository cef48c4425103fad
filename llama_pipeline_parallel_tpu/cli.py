"""Console entry point: `lpt-train --config conf/<name>.yaml [key=value ...]`.

Replaces the reference's Hydra `__main__` shim (reference
trainer_base_ds_mp.py:461-473): overrides accept both `key=value` and
`--key=value` forms. The repo-root `train.py` delegates here so both
`python train.py` and the installed script share one implementation.
"""

from __future__ import annotations

import argparse


def main(argv: list[str] | None = None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", required=True, help="path to a YAML config")
    p.add_argument("--platform", default=None,
                   help="force a jax platform (e.g. 'cpu' for smoke runs with "
                        "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    p.add_argument("overrides", nargs="*", help="key=value config overrides")
    args, unknown = p.parse_known_args(argv)
    # torchrun-style `--key=value` flags become overrides too (the reference
    # strips the dashes the same way, trainer_base_ds_mp.py:464-471)
    bad = [u for u in unknown if not (u.startswith("--") and "=" in u)]
    if bad:
        p.error(f"unrecognized arguments: {' '.join(bad)}")
    args.overrides += unknown

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    from llama_pipeline_parallel_tpu.train import run_training
    from llama_pipeline_parallel_tpu.utils import compile_cache
    from llama_pipeline_parallel_tpu.utils.config import load_config

    compile_cache.setup()
    cfg = load_config(args.config, args.overrides)
    summary = run_training(cfg)
    print(f"training done: {summary}")
