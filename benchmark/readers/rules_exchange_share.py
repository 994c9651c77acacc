"""rules_exchange_share: trace: device time of the placement program's leaf ops under the scope rules_exchange (what the distinct_property stage adds across shards: its values on the spread stage's broadcast, one pmax a step) / of all its leaf ops, in %."""

import glob
import os

import stage_reduce

SCOPE = "rules_exchange"


def under_scope(op_name):
    """``.../update/broadcast/rules_exchange/pmax`` -> True (the last part
    is the primitive, never a scope)."""
    return SCOPE in stage_reduce._WRAPPED.sub(r"\1", op_name).split("/")[:-1]


def read(run):
    if not run.get("device"):
        return None
    files = glob.glob(os.path.join(
        stage_reduce.TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        return None
    ops = stage_reduce.load(
        sorted(files)[-1], tuple(run["cfg"]["placement_programs"]))
    total = sum(seconds for _, seconds in ops)
    under = sum(seconds for name, seconds in ops if under_scope(name))
    if total <= 0 or under <= 0:
        return None  # no launch traced, or a program without the scope
    return 100.0 * under / total
