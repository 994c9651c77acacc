"""plan_rejected_share: plans the applier rejected entirely / plans it decided, nomad.plan.result{outcome=...} over the window, in %."""

import plan_partial_share


def read(run):
    return plan_partial_share.outcome_share(run, "rejected")
