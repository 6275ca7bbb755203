"""Distribution layer: mesh axes, logical-axis sharding rules, helpers.

Mesh axes (production): ``("pod", "data", "model")`` — 2 × 16 × 16 = 512
chips; single-pod is ``("data", "model")`` = 256.

Models annotate activations/params with *logical* axis names
(``batch``, ``seq``, ``embed``, ``heads``, ``mlp``, ``vocab``, ``expert``,
``cache_seq``, …); a per-run :class:`ShardingPlan` maps logical names to
mesh axes.  DP/TP/FSDP/EP/SP are all expressed as rule sets, so the perf
hillclimb is "swap the plan", not "rewrite the model".
"""
from repro.parallel.axes import (
    ShardingPlan,
    batch_shard_map,
    current_plan,
    logical_spec,
    logical_sharding,
    shard,
    under_plan,
    use_plan,
    sanitize_spec,
)
from repro.parallel.plans import (
    BASE_RULES,
    VISION_RULES,
    plan_for,
    vision_plan_for,
)

__all__ = [
    "ShardingPlan",
    "batch_shard_map",
    "current_plan",
    "logical_spec",
    "logical_sharding",
    "shard",
    "under_plan",
    "use_plan",
    "sanitize_spec",
    "BASE_RULES",
    "VISION_RULES",
    "plan_for",
    "vision_plan_for",
]
