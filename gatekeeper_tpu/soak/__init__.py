"""Soak plane: open-loop sustained-load harness (docs/operations.md).

Every other bench phase in this repo is a short CLOSED-loop burst: N
threads each wait for their previous response before sending the next
request, so an overloaded system quietly slows its own arrival rate and
the measured p99 flatters it (coordinated omission). The soak plane is
the opposite instrument — an OPEN-loop generator schedules Poisson
arrivals at a fixed target rps whether or not the system keeps up, and
a request that misses its deadline is COUNTED AGAINST THE SLO instead
of back-pressured away. Around that generator:

  * `scenario`   — the declarative timeline (at t=20s add constraints,
    at t=45s arm a fault, at t=90s rotate certs / kill a replica);
  * `loadgen`    — the Poisson arrival scheduler + worker pool;
  * `harness`    — builds the system under test (1..N real
    `WebhookServer` replicas over HTTP(S), mutation + agent planes,
    stub external-data provider, fleet gossip) and executes the
    timeline against it;
  * `report`     — per-window SLO attainment, shed rate, breaker
    transition log, device-time split, capacity model, and leak
    evidence (RSS / cache / trace-ring / metrics-series curves).

Entry points: `bench_webhook.py --soak` for the CLI, `run_soak()` from
code, and the `soak` pytest lane for the ~10 s smoke scenario.
"""

from .loadgen import OpenLoopLoad, run_open_loop  # noqa: F401
from .report import (  # noqa: F401
    SOAK_SCHEMA_FIELDS,
    build_report,
    check_soak_schema,
    monotonic_growth,
    parse_summary_line,
    summarize_soak,
)
from .scenario import (  # noqa: F401
    ACTIONS,
    Scenario,
    ScenarioEvent,
    default_scenario,
    load_scenario,
    multi_tenant_overload_scenario,
    multi_tenant_smoke_scenario,
    sdc_smoke_scenario,
    smoke_scenario,
)
from .harness import SoakHarness, run_soak  # noqa: F401
