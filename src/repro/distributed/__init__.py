from repro.distributed.fault_tolerance import (
    Backoff,
    DeviceFailure,
    ElasticPlan,
    FailFast,
    FaultInjector,
    InjectedFault,
    RestartLoop,
    StepWatchdog,
    StreamTimeout,
    plan_elastic_mesh,
    wait_for,
)

__all__ = [
    "Backoff",
    "DeviceFailure",
    "ElasticPlan",
    "FailFast",
    "FaultInjector",
    "InjectedFault",
    "RestartLoop",
    "StepWatchdog",
    "StreamTimeout",
    "plan_elastic_mesh",
    "wait_for",
]
