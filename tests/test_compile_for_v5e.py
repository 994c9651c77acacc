"""The node-sharded placement program compiled for a v5e 2x2 that is
described, not attached (PR 46): the wide ``Features`` variant the cell
``c2m-100k-rules.rules-backlog-x4`` launches, at its timed sizes (64 lanes x
102,400 rows on a ``(2, 2)`` mesh, a class operand of 8,192 a lane), as the
one call a launch of the server makes (PR 48: the two packs, the three
node-axis buffers and the carry; the overlay and the chain's flags ride the
lane pack), and the one-chip cells' entry beside it.  What the chip's
compiler would refuse (a variant that does not partition, a program that
does not fit a chip's memory) it refuses here, at no chip time; nothing
runs, so this says nothing of results or times.

The topology is described inside a fixture, in this file alone: only one
process at a time may load the TPU's library, and a worker that cannot
skips these tests without touching any other.
"""

from __future__ import annotations

import os
import re
import sys

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import aot_ops  # noqa: E402

HBM_BYTES = 16 * 1024 ** 3
# ``temp_size_in_bytes`` a device of the same compile at the parent of PR 47
# (580cdd5; ``tools/aot_ops.py`` run on that tree), where every placement
# step read the constraint columns anew through two 52 MB turn buffers.
PARENT_TEMP = {"wide-1": 583_243_264, "wide-2": 557_364_224,
               "plain": 561_742_336}
ROOM = 64 * 1024 ** 2


@pytest.fixture(scope="module")
def mesh():
    import jax

    try:
        mesh = aot_ops.described_mesh()
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Such a compile is written to the persistent cache and cannot be read
    # back without a chip: keep it out.
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()  # (whether it is used is decided once)
    yield mesh
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _holds_the_invariants_outside_the_loop(compiled, parent_temp):
    """PR 47: what a lane's steps share is computed before the placement
    loop: no serial column read (a ``while`` of the feasibility stage) in
    its body, and no more scratch memory than the parent took."""
    text = compiled.as_text()
    assert aot_ops.loops_under(text, "place_scan/while"), "no placement loop"
    in_body = aot_ops.loops_under(
        text, "place_scan/while/body", "/feasibility/")
    assert not in_body, in_body
    assert "feasibility" in text  # ... the stage is still in the program
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= parent_temp + ROOM, (temp, parent_temp)


@pytest.mark.parametrize("dp_width", [1, 2])
def test_the_wide_sharded_variant_compiles_for_four_chips(mesh, dp_width):
    compiled = aot_ops.compile_sharded(mesh, aot_ops.features(
        c_width=8, a_width=2, s_width=2, dp_width=dp_width))
    mem = compiled.memory_analysis()
    # a chip's half of the matrix is resident beside it (244 MB a snapshot)
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES // 4
    names = set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))
    under = {n for n in names if "/rules_exchange/" in n}
    # the chip's compiler keeps the stage's pmax an op of its own, so a
    # profile tells its time (XLA's CPU backend folds it into the step's
    # other pmax)
    assert any(n.endswith("rules_exchange/pmax") for n in under), under
    assert any(n.endswith("rules_exchange/gather") for n in under), under
    _holds_the_invariants_outside_the_loop(
        compiled, PARENT_TEMP[f"wide-{dp_width}"])


def test_the_plain_sharded_variant_has_no_rules_exchange(mesh):
    compiled = aot_ops.compile_sharded(mesh, aot_ops.features(
        **aot_ops.VARIANTS["plain"]))
    assert "rules_exchange" not in compiled.as_text()
    _holds_the_invariants_outside_the_loop(compiled, PARENT_TEMP["plain"])


def test_the_packed_sharded_entry_moves_nothing_between_chips(mesh):
    """A launch is one call of module ``jit_entry`` (the name the four-chip
    cells' ``placement_programs`` find it by): the compiler lays both packs
    out over ``batch`` alone, and the program holds the collectives of the
    placement program that took every operand as its own (each laid out as
    the ``shard_map`` asks) and no other: unpacking at the entry moves
    nothing between chips."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from helpers import collectives

    feats = aot_ops.features(**aot_ops.VARIANTS["plain"])
    live = aot_ops.compile_sharded(mesh, feats)
    text = live.as_text()
    assert re.match(r"HloModule jit_entry\b", text), text[:80]
    lanes = NamedSharding(mesh, P("batch"))
    _arrays, _used, *packs = live.input_shardings[0][:4]
    assert len(packs) == 2
    assert all(s.is_equivalent_to(lanes, 2) for s in packs), packs
    plain = aot_ops.compile_sharded_plain(mesh, feats).as_text()
    assert re.match(r"HloModule jit_entry\b", plain), plain[:80]
    assert collectives(text) == collectives(plain)
    assert sum(collectives(plain).values()) > 10


@pytest.mark.parametrize("variant", sorted(aot_ops.VARIANTS))
def test_the_packed_one_chip_entry_compiles_for_a_v5e(mesh, variant):
    """The one-chip cells' one call a launch, at 64 lanes x 10,240 rows:
    module ``jit_fused_place_batch_live`` (their ``placement_programs``
    look for ``fused_place_batch``), nothing that crosses a chip, well
    inside a chip's memory beside the resident matrix."""
    from helpers import collectives

    compiled = aot_ops.compile_one_chip(
        mesh.devices.flat[0], aot_ops.features(**aot_ops.VARIANTS[variant]))
    text = compiled.as_text()
    assert re.match(r"HloModule jit_fused_place_batch_live\b", text), text[:80]
    assert not collectives(text)
    assert aot_ops.loops_under(text, "place_scan/while"), "no placement loop"
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES // 8


def test_the_op_table_names_a_loop_by_its_scope():
    """``tools/aot_ops.py``'s table on a line of the parent's text (the
    ledger's ``while.120`` of PR 46): no compile."""
    text = """
%wide.body (p: s32[]) -> s32[] {
  %dynamic-update-slice.46 = s32[256,1,51200]{2,0,1:T(8,128)} dynamic-update-slice(%a, %b, %c)
}

ENTRY %main.62_spmd (p: s32[]) -> s32[] {
  %fusion.1 = s32[8]{0:T(128)} fusion(%p), kind=kLoop, calls=%fused_computation.1
  %while.120 = (s32[]{:T(128)}, s32[32,51200]{1,0:T(8,128)S(1)}) while(%tuple.331), condition=%wide.cond, body=%wide.body, backend_config={"known_trip_count":{"n":"256"}}, metadata={op_name="jit(entry)/shard_map/place_scan/while/body/vmap(score)/feasibility/vmap()/gather" stack_frame_id=94}
}
"""
    rows = {r[0]: r for r in aot_ops.op_table(text)}
    assert rows["while.120"][1:3] == ("main.62_spmd", "256")
    assert rows["while.120"][3].endswith("feasibility/vmap()/gather")
    assert rows["dynamic-update-slice.46"][1] == "wide.body (body of while.120)"
    assert [r[0] for r in aot_ops.loops_under(
        text, "place_scan/while/body", "/feasibility/")] == ["while.120"]
