"""The set-up of ``c2m-10k-rules``: every node fingerprints what rules read.

After the seeded usage is installed and before the warm-up (README.md,
"Adding things"): every node registers AGAIN, as a client does whose
fingerprint changed, with the attributes the configuration's
``cluster.rule_attributes`` state (``attr.*`` into the node's attributes,
``meta.*`` into its meta; each a pure function of the node's index:
``rules_reference.node_attribute``).  A re-registration keeps the node's
matrix row and its usage; both are checked here (a row that moved fails the
run), and the seeded usage is set again through ``srv.matrix.set_usage``
should a re-registration have cleared it.

Returns the tables as plain data: what it set on every node, and what it
saw of rows and usage.
"""

from __future__ import annotations

import copy

import numpy as np

import rules_reference as rules


def install(srv, cfg, seed, rows, seeded):
    cluster, n = cfg["cluster"], cfg["nodes"]
    matrix = srv.matrix
    for i in range(n):
        node_id = f"sim-node-{i:06d}"
        node = copy.copy(srv.store.node_by_id(node_id))
        node.attributes, node.meta = dict(node.attributes), dict(node.meta)
        for name, value in rules.expected_attributes(i, cluster).items():
            kind, key = name.split(".", 1)
            (node.meta if kind == "meta" else node.attributes)[key] = value
        srv.register_node(node)
    moved = int(sum(
        matrix.row_of.get(f"sim-node-{i:06d}") != int(rows[i])
        for i in range(n)))
    if moved:
        raise SystemExit(
            f"benchmark: set-up rules_cluster: {moved} nodes changed their "
            "matrix row on registering again; nothing was measured")
    used = matrix.snapshot_host()["used"][rows]
    cleared = not np.array_equal(used, seeded.astype(used.dtype))
    if cleared:
        prio = matrix.snapshot_host()["prio_used"][rows].copy()
        matrix.set_usage(rows, seeded.astype(np.float32), prio)
    return {
        "nodes": n,
        "attributes": [s["name"] for s in cluster["rule_attributes"]],
        "usage_set_again": bool(cleared),
        "computed_classes": int(len(np.unique(
            matrix.snapshot_host()["class_id"][rows]))),
    }
