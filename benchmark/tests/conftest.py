"""Tests of the benchmark itself: CPU, tiny size, not part of tier-1.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(BENCH, "readers"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def checkout(root):
    """A copy of the benchmark under ``root`` for a test to ADD files to,
    beside a link to the program; returns its BENCHMARK.json's content,
    which the test writes back with its entries added."""
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "nomad_tpu"), root / "nomad_tpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)
