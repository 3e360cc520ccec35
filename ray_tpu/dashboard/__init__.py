"""ray_tpu.dashboard — observability HTTP backend.

Reference: dashboard/head.py + state_aggregator.py + modules/metrics +
modules/reporter (SURVEY §2.15). No React frontend — the backend serves
the same data as JSON plus a Prometheus /metrics endpoint, which is what
the reference's Grafana integration actually scrapes.
"""

from ray_tpu.dashboard.head import (  # noqa: F401
    DashboardHead, start_dashboard, stop_dashboard)
