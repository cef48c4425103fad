"""The one general traffic generator: a mix is a data file of parameters
under `benchmark/traffic/`, read here, and everything drawn is drawn from
`--seed`.

Two kinds of mix:

- `train_rows`: rows of token ids for a training job. `SeededRows` is a
  dataset in the trainer's own protocol (a user's `dataset._target_`), so the
  rows reach the step through the program's loader, prefetch and collator.
- `closed_loop`: requests for a serving job with a fixed number of clients.
  Every seed gets the SAME multiset of prompt classes and of output lengths
  in each block of `block` requests (exact shares), in another order, with
  other lengths inside the class and other token ids. The order spreads each
  class evenly over the block (its k-th member falls in the k-th of equal
  stretches, at a place drawn from the seed), so that any window of a few
  tens of requests holds nearly the stated shares and the work of a run does
  not depend on the seed.
"""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

KINDS = ("train_rows", "closed_loop")


def load_mix(root: str, name: str) -> dict:
    path = os.path.join(root, "benchmark", "traffic", f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    if mix.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind must be one of {KINDS}")
    return mix


# -- training rows ----------------------------------------------------------

def row_ids(seed: int, index: int, vocab_size: int, seq_length: int):
    rng = np.random.default_rng([seed, index])
    return rng.integers(0, vocab_size, size=seq_length, dtype=np.int32)


@dataclasses.dataclass
class SeededRows:
    """Full-length rows of uniform random token ids, row `i` a function of
    (seed, i) alone; no row repeats inside `length`."""

    seed: int
    vocab_size: int
    seq_length: int
    length: int

    def __len__(self) -> int:
        return self.length

    def __getitem__(self, idx: int) -> dict:
        if not 0 <= idx < self.length:
            raise IndexError(idx)
        ids = row_ids(self.seed, idx, self.vocab_size, self.seq_length)
        return {"input_ids": ids,
                "attention_mask": np.ones(self.seq_length, np.int32),
                "position_ids": np.arange(self.seq_length, dtype=np.int32),
                "labels": ids}


# -- closed-loop serving requests ------------------------------------------

def _quota(classes, block: int) -> list:
    """[(value, count)] with counts = share * block, which must be whole."""
    out = []
    for value, share in classes:
        count = share * block
        if abs(count - round(count)) > 1e-9:
            raise ValueError(f"share {share} of a block of {block} is not a "
                             f"whole number of requests")
        out.append((int(value), int(round(count))))
    if sum(c for _, c in out) != block:
        raise ValueError(f"shares do not add up to a block of {block}")
    return out


def _spread(quota: list, rng) -> list:
    """The values of `quota` in an order that spreads each evenly: the k-th
    of a value's n members sits at (k + u) / n of the block, u drawn from
    `rng`."""
    placed = [((k + rng.random()) / n, value)
              for value, n in quota for k in range(n)]
    return [value for _, value in sorted(placed)]


def request_block(mix: dict, seed: int, block_index: int, vocab_size: int
                  ) -> list:
    """One block of requests: [{"prompt": ids, "max_new_tokens": n,
    "prompt_class": c, "seed": s}]."""
    block = mix["block"]
    rng = np.random.default_rng([seed, block_index])
    prompts = _spread(_quota(mix["prompt_classes"], block), rng)
    outputs = _spread(_quota(mix["output_classes"], block), rng)
    bounds = [0] + sorted(c for c, _ in mix["prompt_classes"])
    lower = {hi: lo for lo, hi in zip(bounds, bounds[1:])}
    requests = []
    for klass, out_len in zip(prompts, outputs):
        length = int(rng.integers(lower[klass] + 1, klass + 1))
        requests.append({
            "prompt": rng.integers(0, vocab_size, size=length,
                                   dtype=np.int32).tolist(),
            "max_new_tokens": int(out_len), "prompt_class": klass,
            "seed": int(rng.integers(0, 2 ** 31 - 1))})
    return requests


def request_stream(mix: dict, seed: int, vocab_size: int):
    """Requests without end, block after block."""
    index = 0
    while True:
        yield from request_block(mix, seed, index, vocab_size)
        index += 1
