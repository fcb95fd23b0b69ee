"""Swarm-scale traffic simulator on the virtual clock.

Runs the REAL control plane — ComputeQueue scheduling and group
coalescing, AdmissionController fair-share shedding, the standby
promotion/demotion state machine (server/promotion.py mixin), measured
rebalancing (server/block_selection.rebalance_if_needed), and client-side
Dijkstra routing with ban/quarantine/overload penalty classes
(client/sequence_manager.py) — against thousands of virtual sessions on a
``SteppableClock``, with device compute replaced by a cost
model. Only the two leaves are simulated: the matmul (a ``clock.sleep``
of the modeled cost on the compute thread) and the wire (a virtual RTT).
Everything between — every watermark, dwell window, backoff, and
hysteresis margin — is byte-for-byte the code production runs.

The point is the failure modes that only appear at swarm scale:
metastable shed/retry feedback loops after a flash crowd, promotion
storms and flapping under span loss, rebalance thrash on diurnal ramps,
and retry amplification past the point of no return.  ``python -m
bloombee_tpu.sim --require`` runs the scenario suite and FAILS (exit 3)
on metastable outcomes, the same gate idiom as utils/ledger.py and
utils/lockwatch.py.

Layout:
  engine.py    discrete-event conductor over SteppableClock + counting
               executor (knows when real compute threads are mid-flight)
  cost.py      per-dispatch cost model (constants; not measured on a TPU)
  node.py      SimServer: real queue/admission/promotion/rebalance
  client.py    virtual sessions driving real RemoteSequenceManager routes
  workload.py  generative arrivals: heavy tails, diurnal ramps, agent
               loops with shared prefixes, flash crowds
  scenarios.py swarm topologies + fault scripts (wire/faults.py schedules)
  metrics.py   per-scenario JSON metrics + metastability gates
"""

from bloombee_tpu.sim.cost import CostModel
from bloombee_tpu.sim.engine import SimEngine, SimStalled
from bloombee_tpu.sim.scenarios import SCENARIOS, run_scenario

__all__ = [
    "CostModel",
    "SimEngine",
    "SimStalled",
    "SCENARIOS",
    "run_scenario",
]
