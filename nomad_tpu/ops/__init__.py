"""Vectorized scheduling math (JAX kernels + request encoding)."""

from .encode import (  # noqa: F401
    CompiledTaskGroup,
    EscapedConstraint,
    RequestEncoder,
    SchedRequest,
    MAX_CONSTRAINTS,
    MAX_SPREADS,
    MAX_SPREAD_VALUES,
)
from .kernels import (  # noqa: F401
    FUSED_PACKED_VERIFIED,
    FUSED_PACKED_WIDTH,
    FULL_FEATURES,
    Features,
    NEG_INF,
    PlacementResult,
    ScoreResult,
    feasibility_mask,
    features_of,
    fit_and_binpack,
    fused_place_batch,
    fused_place_batch_live,
    place_task_group,
    score_nodes,
    verify_plan_fit,
)
