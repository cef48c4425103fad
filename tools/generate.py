#!/usr/bin/env python
"""Generate text from a native checkpoint (KV-cache batched decoding).

The reference has no predict/generate path at all (its `prediction_cfg`
names an absent evaluator class, reference conf yaml:107-115; SURVEY.md
§2.4). This tool closes that hole:

    python tools/generate.py --checkpoint_dir /ckpts/run1 \
        --prompt "Once upon a time" --prompt "def main():" \
        --max_new_tokens 64 --temperature 0.8 --top_k 40 --top_p 0.95

Prompts are left-padded into one batch and decoded in a single jitted
`lax.scan` loop (models/llama/decode.py). The pad target is a BUCKET
length (--bucket_sizes, smallest bucket holding the longest prompt), not
the longest prompt itself: `generate` compiles per `[b, P]` shape, so
without bucketing every distinct prompt length pays a fresh XLA compile —
left padding is invisible to the model (positions/kv masks absorb it), so
the extra pad columns only cost prefill FLOPs. A run summary with
tokens/s goes to stderr (stdout stays the decoded text).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048)


def bucket_length(longest: int, buckets=DEFAULT_BUCKETS) -> int:
    """Smallest bucket >= the longest prompt; a prompt past the last bucket
    falls back to its own length (correct, but compiles per shape)."""
    for b in sorted(buckets):
        if b >= longest:
            return b
    return longest


def run(args: argparse.Namespace) -> list[str]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from transformers import AutoTokenizer

    from llama_pipeline_parallel_tpu.ckpt.checkpoint import load_module_checkpoint
    from llama_pipeline_parallel_tpu.data.tokenization import expand_special_tokenizer
    from llama_pipeline_parallel_tpu.models.llama.decode import (
        GenerationConfig,
        generate,
    )

    params, cfg, _, _ = load_module_checkpoint(args.checkpoint_dir, args.step)

    tok_path = args.tokenizer_path or args.checkpoint_dir
    tokenizer = AutoTokenizer.from_pretrained(tok_path)
    added = expand_special_tokenizer(tokenizer)
    # This tool cannot resize the checkpoint's embeddings: any id at or past
    # the model vocab would gather garbage silently (JAX clamps OOB indices).
    if added > 0 or len(tokenizer) > cfg.vocab_size:
        raise ValueError(
            f"tokenizer has {len(tokenizer)} tokens ({added} just added) but "
            f"the checkpoint's vocab is {cfg.vocab_size}; re-convert with "
            f"tools/convert_hf.py (vocab expansion is its default) so the "
            f"embeddings match")

    tokenizer.padding_side = "left"
    if tokenizer.pad_token is None:  # max_length padding needs a pad token
        tokenizer.pad_token = tokenizer.eos_token or tokenizer.unk_token
    lengths = [len(ids) for ids in tokenizer(list(args.prompt))["input_ids"]]
    bucket_arg = getattr(args, "bucket_sizes", None)  # optional for callers
    buckets = (tuple(int(b) for b in bucket_arg.split(","))
               if bucket_arg else DEFAULT_BUCKETS)
    bucket = bucket_length(max(lengths), buckets)
    enc = tokenizer(list(args.prompt), return_tensors="np",
                    padding="max_length", max_length=bucket, truncation=False)
    gen = GenerationConfig(
        max_new_tokens=args.max_new_tokens, temperature=args.temperature,
        top_k=args.top_k, top_p=getattr(args, "top_p", 1.0),
        eos_token_id=tokenizer.eos_token_id,
        pad_token_id=tokenizer.pad_token_id or 0)
    t0 = time.perf_counter()
    out = generate(params, jnp.asarray(enc["input_ids"], jnp.int32),
                   jnp.asarray(enc["attention_mask"], jnp.int32), cfg, gen,
                   rng=jax.random.PRNGKey(args.seed))
    n_tokens = int(np.asarray(out["tokens"]).size)  # blocks on the result
    dt = time.perf_counter() - t0
    print(f"[generate] {len(lengths)} prompt(s) (longest {max(lengths)}) "
          f"padded to bucket {bucket}; {n_tokens} tokens in {dt:.2f}s = "
          f"{n_tokens / max(dt, 1e-9):.1f} tok/s (first call includes "
          f"compile; rerun at any prompt length <= {bucket} reuses it)",
          file=sys.stderr, flush=True)

    texts = []
    for row in np.asarray(out["tokens"]):
        ids = row.tolist()
        if gen.eos_token_id is not None and gen.eos_token_id in ids:
            ids = ids[:ids.index(gen.eos_token_id)]  # truncate at FIRST eos
        texts.append(tokenizer.decode(ids, skip_special_tokens=True))
    return texts


def main(argv: list[str] | None = None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--platform", default=None,
                   help="force a jax platform (e.g. 'cpu'); default: the "
                        "image's platform (TPU when available)")
    p.add_argument("--checkpoint_dir", required=True)
    p.add_argument("--tokenizer_path", default=None,
                   help="defaults to checkpoint_dir (convert_hf.py places "
                        "tokenizer files there)")
    p.add_argument("--step", type=int, default=None)
    p.add_argument("--prompt", action="append", required=True,
                   help="repeatable; prompts batch together")
    p.add_argument("--max_new_tokens", type=int, default=64)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top_k", type=int, default=0)
    p.add_argument("--top_p", type=float, default=1.0,
                   help="nucleus sampling mass (1.0 disables)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--bucket_sizes", default=None,
                   help="comma-separated ascending prompt pad buckets "
                        f"(default {','.join(map(str, DEFAULT_BUCKETS))}); "
                        "distinct buckets, not distinct prompt lengths, "
                        "determine recompiles")
    args = p.parse_args(argv)
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    from llama_pipeline_parallel_tpu.utils import compile_cache

    compile_cache.setup()
    for prompt, text in zip(args.prompt, run(args)):
        print(f"=== {prompt!r}\n{text}\n")


if __name__ == "__main__":
    main()
