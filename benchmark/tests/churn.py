"""The `audit_churn` cells at a size the CPU runs in seconds, shared by
the benchmark's rehearsal and the program's churn tests."""

import copy

from benchmark import harness


def small(cell):
    """(cfg, traffic) of a churn cell cut to a few hundred objects, with
    4 churn steps: one in set-up, 3 for the window."""
    _, cfg, traffic = harness.load_cell(cell)
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    inv = cfg["inventory"]
    if inv.get("services"):
        # shares raised so that a few hundred objects hold every kind
        # of violation, and every step churns some of them
        inv.update(services=300, ingresses=150, namespaces=12,
                   shared_host_share=0.1, nodeport_share=0.05,
                   no_owner_share=0.05)
    else:
        cfg["constraints"] = 40
        inv.update(pods=1500, namespaces=10, violating_share=0.3)
    traffic.update(churn_share=0.05, max_steps=4)
    return cfg, traffic
