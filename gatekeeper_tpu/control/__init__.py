"""Control plane: state ingestion, readiness, process runtime.

The reference's equivalents live under pkg/controller/, pkg/watch/,
pkg/readiness/, and main.go; here the same architecture runs against an
`EventSource` (a fake in-memory cluster or a real apiserver adapter):
cluster -> WatchManager -> controllers -> constraint-framework Client,
with the ReadinessTracker gating /readyz and `Runner` as the
main()-equivalent.
"""

from .process import Excluder, PROCESS_AUDIT, PROCESS_SYNC, PROCESS_WEBHOOK, PROCESS_STAR  # noqa: F401
from .readiness import ReadinessTracker  # noqa: F401
from .events import (  # noqa: F401
    ADDED,
    DELETED,
    Event,
    EventSource,
    FakeCluster,
    GVK,
    MODIFIED,
)
from .kubecluster import KubeCluster, KubeError  # noqa: F401
from .watch import Registrar, WatchManager  # noqa: F401
from .controllers import (  # noqa: F401
    CONFIG_GVK,
    ConfigController,
    ConstraintController,
    ControllerSwitch,
    EXPANSION_GVK,
    ExpansionTemplateController,
    MUTATOR_GVKS,
    MutatorController,
    PROVIDER_GVK,
    ProviderController,
    SyncController,
    TemplateController,
    TEMPLATE_GVK,
    constraint_gvk,
)
from .status import StatusAggregator, StatusWriter  # noqa: F401
from .upgrade import UpgradeManager  # noqa: F401
from .runner import (  # noqa: F401
    ALL_OPERATIONS,
    OPERATION_AUDIT,
    OPERATION_STATUS,
    OPERATION_WEBHOOK,
    Runner,
    load_yaml_dir,
)
