"""Serving fleet: one logical serving surface over N supervised engine
replicas (docs/serving.md "Fleet serving").

- :class:`~trlx_tpu.fleet.router.FleetRouter` — prefix-cache-aware +
  tenant-affinity routing, cross-replica re-route on replica death
  (exactly-once terminal accounting), graceful decommission;
- :class:`~trlx_tpu.fleet.autoscaler.FleetAutoscaler` — gauge-driven
  scale-up/scale-down with hysteresis;
- :class:`~trlx_tpu.fleet.ledger.FleetLedger` — fleet-wide per-tenant /
  per-class SLO accounting into the ``fleet/*`` gauge namespace;
- :func:`~trlx_tpu.fleet.scenario.run_fleet_scenario` — the deterministic
  fleet chaos harness (tests/test_serving_fleet.py).
"""

from trlx_tpu.fleet.autoscaler import FleetAutoscaler
from trlx_tpu.fleet.ledger import FleetLedger
from trlx_tpu.fleet.router import (
    ACTIVE,
    DEAD,
    DRAINING,
    UID_STRIDE,
    FleetRouter,
    ReplicaHandle,
    fleet_factory,
)
from trlx_tpu.fleet.scenario import FleetScenarioReport, run_fleet_scenario

__all__ = [
    "ACTIVE",
    "DEAD",
    "DRAINING",
    "UID_STRIDE",
    "FleetAutoscaler",
    "FleetLedger",
    "FleetRouter",
    "FleetScenarioReport",
    "ReplicaHandle",
    "fleet_factory",
    "run_fleet_scenario",
]
