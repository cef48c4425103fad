"""Shared by the benchmark's tests: the repo root and this directory on
`sys.path` (tests/ has no package), nothing else."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for path in (REPO, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)
