"""Serving: per-replica engines + the paper's autoscaler + cluster simulation.

``FleetProvisioner.advance()`` streams: the O(1)-state incremental stepper
behind it (engine carry, pow2 chunk buckets, slot-indexed PRNG) lives in
:mod:`repro.serving.stepper` and is exported here for direct use.
"""
from .autoscaler import (
    FleetProvisioner,
    ReplicaAutoscaler,
    ScalerReport,
    replica_cost_model,
)
from .cluster import ClusterReport, make_window_max_predictor, run_cluster
from .engine import GenerationResult, InferenceEngine
from .metrics import PlanMetrics
from .stepper import (
    StepperState,
    pow2_bucket,
    stepper_chunk,
    stepper_init,
    stepper_tick,
)

__all__ = [
    "FleetProvisioner",
    "PlanMetrics",
    "ReplicaAutoscaler",
    "ScalerReport",
    "StepperState",
    "pow2_bucket",
    "replica_cost_model",
    "stepper_chunk",
    "stepper_init",
    "stepper_tick",
    "ClusterReport",
    "make_window_max_predictor",
    "run_cluster",
    "GenerationResult",
    "InferenceEngine",
]
