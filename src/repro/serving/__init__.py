from repro.serving.engine import (  # noqa: F401
    Request,
    ServingEngine,
)
from repro.serving.faults import (  # noqa: F401
    FAULT_KINDS,
    EngineKilled,
    FaultInjector,
    FaultPlan,
    FaultReport,
    FaultSpec,
    drive_resilient,
    make_storm,
)
from repro.serving.metrics import (  # noqa: F401
    aggregate,
    aggregate_fleet,
    format_summary,
    scale_latencies,
)
from repro.serving.router import (  # noqa: F401
    ROUTER_POLICIES,
    ROUTING_POLICIES,
    Router,
    RoutingPolicy,
    TransitJob,
    drive_fleet,
    make_routing_policy,
)
from repro.serving.scheduler import (  # noqa: F401
    EDF,
    FCFS,
    POLICIES,
    SCHEDULERS,
    SPF,
    Scheduler,
    make_scheduler,
)
from repro.serving.paged import (  # noqa: F401
    PagedSlotManager,
    canonicalize_cache,
    paged_cache_bytes,
)
from repro.serving.slotstate import (  # noqa: F401
    SlotManager,
    SlotSnapshot,
    gather_slots,
    make_slot_manager,
    scatter_slots,
)
from repro.serving.workload import (  # noqa: F401
    VirtualClock,
    WallClock,
    WorkloadItem,
    drive,
    load_trace,
    make_workload,
    profile_items,
    save_trace,
)
from repro.plan.plan import (  # noqa: F401
    FleetPlan,
    ServingPlan,
    WorkloadProfile,
)
