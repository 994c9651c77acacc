"""The plain reference for jobs whose bodies ask for ports, disk and devices
(PR 51).

Python and numpy, nothing of ``nomad_tpu``: what Nomad v1.1.3's
``nomad/structs/network.go`` (NetworkIndex: a port belongs to one live
allocation of a node; a dynamic port comes from 20000-32000) and
``scheduler/feasible.go:1173`` (DeviceChecker: a node is feasible for a
device ask where it has that many instances free) define, on the
configuration's own statement of the cluster: ``cluster.devices`` (which
nodes carry which device group), ``cluster.resident`` (the allocations that
run on the cluster before the window, each a pure function of the node's
index) and ``cluster.dynamic_port_range``.  ``reference.py`` keeps what it
shares with every cell: the node totals, ``has_room``, ScoreFit; disk is
held there, by plain sums, with cpu and memory.

* ``node_devices`` / ``device_totals`` -- the device instances of node ``i``.
* ``residents`` -- every resident allocation as plain columns, the dynamic
  ports dealt lowest-free-first from the range's start as the reference's
  NetworkIndex deals them.
* ``asks`` -- what one shape of a traffic file asks for: static ports,
  dynamic labels, devices.
* ``Tables`` -- per node, who holds which port and how many instances of
  each device are taken: built from the residents and the read-back.
* ``blocked`` -- (N,) bool: the nodes on which an ask is infeasible (a live
  allocation holds an asked static port, fewer free instances than asked,
  no room left in the dynamic range).
* the four exact numbers: ``port_collisions``, ``port_ask_unmet``,
  ``device_overcommit``, ``device_on_wrong_node``.
"""

from __future__ import annotations

import collections
from typing import Dict, List, Sequence

import numpy as np

COLUMNS = ("node", "kind", "cpu", "memory_mb", "disk_mb", "ports", "labels",
           "devices")


# -- the cluster as the configuration states it -------------------------------

def _on(i: int, spec: Dict) -> bool:
    return i % spec["period"] == spec["residue"]


def node_devices(i: int, cluster: Dict) -> Dict[str, int]:
    """name -> instances of every device group node ``i`` carries."""
    return {d["name"]: d["instances"] for d in cluster.get("devices", [])
            if _on(i, d)}


def device_totals(n_nodes: int, cluster: Dict) -> Dict[str, np.ndarray]:
    """name -> (N,) instances a node has."""
    idx = np.arange(n_nodes)
    return {
        d["name"]: np.where(idx % d["period"] == d["residue"],
                            d["instances"], 0).astype(np.int64)
        for d in cluster.get("devices", [])}


def residents(n_nodes: int, cluster: Dict) -> Dict[str, List]:
    """Every resident allocation, in columns (``COLUMNS``): allocation ``k``
    runs on node ``node[k]``, is of kind ``kind[k]`` (an entry of
    ``cluster.resident``), uses ``cpu[k]`` / ``memory_mb[k]`` /
    ``disk_mb[k]``, holds ``ports[k]`` under ``labels[k]`` (a static port's
    label is the port, as the program names it) and ``devices[k]`` (name ->
    count).  Dynamic ports are the lowest free ones of the node's range, in
    the order of ``cluster.resident``."""
    lo = cluster["dynamic_port_range"][0]
    out: Dict[str, List] = {c: [] for c in COLUMNS}
    for i in range(n_nodes):
        cursor = lo
        for spec in cluster.get("resident", []):
            if not _on(i, spec):
                continue
            ports = [int(p) for p in spec["static_ports"]]
            labels = [str(p) for p in ports]
            for label in spec["dynamic_ports"]:
                ports.append(cursor)
                labels.append(label)
                cursor += 1
            out["node"].append(i)
            out["kind"].append(spec["name"])
            for d in ("cpu", "memory_mb", "disk_mb"):
                out[d].append(spec[d])
            out["ports"].append(ports)
            out["labels"].append(labels)
            out["devices"].append(dict(spec["devices"]))
    return out


def resident_usage(n_nodes: int, state: Dict) -> np.ndarray:
    """(N, 3) cpu, memory and disk of the resident allocations."""
    used = np.zeros((n_nodes, 3), np.float64)
    np.add.at(used, np.asarray(state["node"], np.int64), np.array(
        [state["cpu"], state["memory_mb"], state["disk_mb"]], np.float64).T)
    return used


# -- what a job asks for ---------------------------------------------------------

def asks(shape: Dict) -> Dict:
    """Static ports, dynamic labels and devices one instance of ``shape``
    asks for (the traffic file's wire form: ``traffic.job_payload``)."""
    static, dynamic = [], []
    for net in shape.get("networks", []):
        static.extend(int(p) for p in net.get("reserved_ports", []))
        dynamic.extend(net.get("dynamic_ports", []))
    devices: Dict[str, int] = {}
    for d in shape.get("devices", []):
        devices[d["name"]] = devices.get(d["name"], 0) + int(d.get("count", 1))
    return {"static": static, "dynamic": dynamic, "devices": devices}


def assigned(alloc: Dict) -> Dict[str, int]:
    """label -> port of an allocation as read back (``assigned_ports``:
    owner -> label -> port), the owners flattened."""
    out: Dict[str, int] = {}
    for ports in (alloc.get("assigned_ports") or {}).values():
        out.update({str(k): int(v) for k, v in ports.items()})
    return out


# -- who holds what ----------------------------------------------------------------

class Tables:
    """Per node: port -> how many live allocations hold it; instances of
    each device taken; instances it has."""

    def __init__(self, n_nodes: int, cluster: Dict):
        self.n = n_nodes
        self.range = tuple(cluster["dynamic_port_range"])
        self.held: List[collections.Counter] = [
            collections.Counter() for _ in range(n_nodes)]
        self.dev_total = device_totals(n_nodes, cluster)
        self.dev_used = {k: np.zeros(n_nodes, np.int64)
                         for k in self.dev_total}
        self.misplaced = 0  # device asks on a node without the device

    def add(self, row: int, ports: Sequence[int], devices: Dict[str, int]):
        self.held[row].update(int(p) for p in ports)
        for name, count in devices.items():
            have = self.dev_total.get(name)
            if have is None or have[row] == 0:
                self.misplaced += 1
            else:
                self.dev_used[name][row] += count

    def add_residents(self, state: Dict) -> "Tables":
        for row, ports, devices in zip(state["node"], state["ports"],
                                       state["devices"]):
            self.add(row, ports, devices)
        return self

    def port_taken(self, port: int) -> np.ndarray:
        return np.fromiter((h[port] > 0 for h in self.held), bool, self.n)

    def dynamic_held(self) -> np.ndarray:
        lo, hi = self.range
        return np.fromiter(
            (sum(c for p, c in h.items() if lo <= p <= hi)
             for h in self.held), np.int64, self.n)


def blocked(tables: Tables, ask: Dict) -> np.ndarray:
    """(N,) bool: nodes on which one instance of the ask is infeasible."""
    out = np.zeros(tables.n, bool)
    for port in ask["static"]:
        out |= tables.port_taken(port)
    if ask["dynamic"]:
        lo, hi = tables.range
        out |= tables.dynamic_held() + len(ask["dynamic"]) > hi - lo + 1
    for name, count in ask["devices"].items():
        total = tables.dev_total.get(name)
        if total is None:
            return np.ones(tables.n, bool)
        out |= total - tables.dev_used[name] < count
    return out


# -- the guarantees, exact -----------------------------------------------------------

def port_collisions(tables: Tables) -> int:
    """Holders of a port of a node beyond the first, over all nodes."""
    return int(sum(c - 1 for h in tables.held for c in h.values() if c > 1))


def ask_unmet(have: Dict[str, int], ask: Dict, port_range) -> bool:
    """``have`` (``assigned`` of an allocation) lacks an asked static port,
    or a port of the dynamic range for one of the dynamic labels."""
    lo, hi = port_range
    if any(have.get(str(p)) != p for p in ask["static"]):
        return True
    return any(not lo <= have.get(label, -1) <= hi
               for label in ask["dynamic"])


def device_overcommit(tables: Tables) -> int:
    """Nodes with more instances of a device taken than they have."""
    over = np.zeros(tables.n, bool)
    for name, total in tables.dev_total.items():
        over |= tables.dev_used[name] > total
    return int(over.sum())
