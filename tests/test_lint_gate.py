"""The lint gate: tier-1 runs the full analyzer in-process and fails on
any non-baselined finding — `python -m nomad_tpu.lint` as a pytest node,
so the gate rides the existing test command with no new CI surface.

(The jaxpr-level semantic gate is its own tier-1 node next door:
tests/test_jaxprpass.py::test_live_tree_contracts_clean_against_baseline
— it needs a JAX backend, this one deliberately does not.)"""

from __future__ import annotations

import json

import pytest

from nomad_tpu.lint import load_baseline, repo_root, run_all, split_baselined


def test_analyzer_is_clean_against_baseline():
    findings = run_all(repo_root())
    baseline = load_baseline()
    new, _suppressed, stale = split_baselined(findings, baseline)
    assert new == [], "non-baselined findings:\n" + "\n".join(
        f.render() for f in new
    )
    # The ratchet: entries that stopped matching anything must be deleted,
    # not accumulated.
    assert stale == [], "stale baseline entries (delete them):\n" + "\n".join(
        f"{e.get('rule')} {e.get('path')} [{e.get('symbol')}]" for e in stale
    )


def test_every_baseline_entry_has_a_justification():
    baseline = load_baseline()
    missing = [e for e in baseline.entries if not e.get("why")]
    assert missing == [], missing


# ----------------------------------------------------------------------
# Baseline hygiene: the loader is the gate, not convention.
# ----------------------------------------------------------------------


def _write_baseline(tmp_path, entries):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps({"exemptions": entries}))
    return str(p)


def _entry(rule="L003", path="a.py", symbol="f", why="because"):
    return {"rule": rule, "path": path, "symbol": symbol, "why": why}


def test_baseline_loader_rejects_duplicate_keys(tmp_path):
    # Duplicates used to be silently tolerated with first-match-wins,
    # which made one of the two `why` texts dead — and which `why` won
    # depended on file order.  Now it's a load error.
    p = _write_baseline(
        tmp_path, [_entry(why="the real reason"), _entry(why="a stale copy")]
    )
    with pytest.raises(ValueError, match="duplicate"):
        load_baseline(p)


def test_baseline_loader_rejects_unsorted_entries(tmp_path):
    p = _write_baseline(
        tmp_path, [_entry(symbol="zeta"), _entry(symbol="alpha")]
    )
    with pytest.raises(ValueError, match="sorted"):
        load_baseline(p)


def test_baseline_loader_accepts_sorted_unique_entries(tmp_path):
    p = _write_baseline(
        tmp_path, [_entry(symbol="alpha"), _entry(symbol="zeta")]
    )
    assert len(load_baseline(p).entries) == 2


def test_committed_baseline_is_canonical():
    # Loading the committed file exercises both hygiene checks; an
    # unsorted or duplicated committed baseline can no longer ship.
    baseline = load_baseline()
    keys = [(e["rule"], e["path"], e["symbol"]) for e in baseline.entries]
    assert keys == sorted(keys) and len(keys) == len(set(keys))


# ----------------------------------------------------------------------
# The documented variables are the ones the code reads.
# ----------------------------------------------------------------------


def test_documented_variables_are_the_ones_read():
    """README's tables of ``NOMAD_TPU_*`` variables name exactly the
    variables the program's source names: a variable deleted from the code
    leaves the document with it, a new one arrives documented."""
    import os
    import re

    root = repo_root()
    name = re.compile(r"NOMAD_TPU_[A-Z0-9_]+")
    sources = [os.path.join(root, f)
               for f in ("chip_smoke.py", "__graft_entry__.py")]
    for top in ("nomad_tpu", "tools"):
        for d, _dirs, files in os.walk(os.path.join(root, top)):
            sources += [os.path.join(d, f) for f in files if f.endswith(".py")]
    read = set()
    for path in sources:
        with open(path, encoding="utf-8") as fh:
            # ``NOMAD_TPU_DEVICE_*`` in a docstring names a family, not a
            # variable.
            read |= {n for n in name.findall(fh.read()) if not n.endswith("_")}
    with open(os.path.join(root, "README.md"), encoding="utf-8") as fh:
        rows = re.findall(r"^\| `(NOMAD_TPU_[A-Z0-9_]+)` \|", fh.read(), re.M)
    assert len(rows) == len(set(rows)), "a variable is documented twice"
    assert set(rows) == read, (
        f"documented but not read: {sorted(set(rows) - read)}; "
        f"read but not documented: {sorted(read - set(rows))}"
    )
