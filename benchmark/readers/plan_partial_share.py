"""plan_partial_share: plans the applier committed in part / plans it decided, nomad.plan.result{outcome=...} over the window, in %."""

import measure

OUTCOMES = ("committed", "partial", "rejected")


def outcome_share(run, outcome):
    """A counter no plan has moved yet is not in the snapshot: that is 0
    of the plans decided, unless none of the three is there."""
    grown = {o: measure.delta(run, "nomad.plan.result{outcome=%s}" % o)
             for o in OUTCOMES}
    if all(v is None for v in grown.values()):
        return None
    total = sum(v or 0.0 for v in grown.values())
    return measure.ratio(100.0 * (grown[outcome] or 0.0), total)


def read(run):
    return outcome_share(run, "partial")
