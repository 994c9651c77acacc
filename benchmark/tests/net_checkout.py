#!/usr/bin/env python3
"""A copy of the benchmark with ``c2m-10k-net.net-backlog`` ADDED to its
BENCHMARK.json, beside a link to the program (PR 51).

    python3 benchmark/tests/net_checkout.py <directory>
    cd <directory> && python3 benchmark/run.py --workload c2m-10k-net.net-backlog ...

The cell is left out of the repo's BENCHMARK.json (PERF.md section 7, "Cells
left out"); ``deployments/net_entries.json`` holds its entries as they will
be added.  ``test_net_deployment.py`` rehearses the cell from such a copy,
and a builder runs it on the chip from one.
"""

import json
import os
import pathlib
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from conftest import BENCH, checkout  # noqa: E402


def entries():
    with open(os.path.join(BENCH, "deployments", "net_entries.json")) as fh:
        return json.load(fh)


def added(bench, e):
    """``bench`` (BENCHMARK.json's content) with the entries of ``e``."""
    bench["configs"] += e["configs"]
    bench["workloads"] += e["workloads"]
    bench["per_layer"] += e["per_layer"]
    cells = [w["name"] for w in e["workloads"]]
    for m in bench["per_layer"]:
        if m["name"] in e["append_to"]:
            m["workloads"] += [c for c in cells if c not in m["workloads"]]
    return bench


def make(root):
    """``conftest.checkout`` under ``root`` (a directory that holds no
    checkout yet) with the entries added; returns ``root``."""
    root = pathlib.Path(root)
    root.mkdir(parents=True, exist_ok=True)
    bench = added(checkout(root), entries())
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return str(root)


if __name__ == "__main__":
    print(make(sys.argv[1]))
