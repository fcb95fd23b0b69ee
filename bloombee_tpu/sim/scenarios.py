"""Scenario catalog: swarm topologies + workloads + scripted faults.

Each scenario builds a small swarm (every server runs the real control
plane — see node.py), generates ≥1000 seeded virtual sessions, scripts
its perturbation, drives the whole thing under the discrete-event engine,
and scores the time series with metrics.evaluate. Horizons scale with the
session count so arrival RATES — the thing the control plane actually
responds to — are identical between the CI-sized run and a quick smoke.

  flash_crowd  an absolute-size crowd of naive gateway sessions lands
               inside a seconds-wide window on a two-span swarm with one
               [4:8) standby: admission must shed, the standby may
               promote, and shedding must CONVERGE after the crowd
               passes even though abandoned first-token timeouts leave
               zombie prefills burning (the metastable-retry gate).
  span_loss    correlated failure: the [4:8) primary crashes at a
               scripted decode step (wire/faults.py FaultSchedule), its
               replica dies 5 virtual seconds later under the failover
               load; the standby must promote within the latency gate and
               every stranded session must recover.
  diurnal      a day-long sine ramp over a swarm whose [4:8) server is a
               slow host (16x compute, nominal advert): at peak the
               measured-load rebalancer must MOVE the spare [0:4) replica
               onto the hot span, and shedding must die with the peak.
"""

from __future__ import annotations

import asyncio
import random

from bloombee_tpu.sim import metrics as sim_metrics
from bloombee_tpu.sim.client import SimSwarm, run_session
from bloombee_tpu.sim.cost import CostModel
from bloombee_tpu.sim.node import SimServer
from bloombee_tpu.sim.workload import (
    diurnal_sessions,
    flash_crowd_sessions,
    poisson_sessions,
)
from bloombee_tpu.swarm.registry import InProcessRegistry
from bloombee_tpu.utils import clock, env
from bloombee_tpu.wire.faults import FaultSchedule, ScheduledFault

MODEL_UID = "sim-model"
NUM_BLOCKS = 8
BASE_PORT = 4200

env.declare(
    "BBTPU_SIM_SESSIONS", int, 1000,
    "virtual sessions per simulator scenario (the --require CI gate "
    "runs this many; --smoke drops to ~200 for chaos rides)",
)
env.declare(
    "BBTPU_SIM_SEED", int, 0,
    "base RNG seed for simulator workload generation and routing jitter "
    "— same seed, same sessions, same verdict",
)
env.declare(
    "BBTPU_SIM_WALL_BUDGET_S", float, 110.0,
    "real-seconds budget per simulator scenario; a scenario that cannot "
    "finish its virtual timeline inside it fails as stalled",
)


def _mk(engine, swarm, faults, sid, start, end, port_off, **kw):
    server = SimServer(
        engine, swarm.registry, MODEL_UID, sid, start, end, NUM_BLOCKS,
        swarm.cost, port=BASE_PORT + port_off, faults=faults, **kw,
    )
    swarm.add(server)
    return server


async def _drive(engine, swarm, specs, seed, horizon_s):
    """Start the swarm, run the session population to completion under
    the conductor, tear down, and hand back (results, samples)."""
    start_t = clock.monotonic()
    for s in swarm.servers.values():
        s.start()
    sampler = sim_metrics.Sampler(swarm, start_t)
    sampler_task = asyncio.create_task(sampler.run())

    rng = random.Random(seed)
    managers: dict = {}
    tasks = []
    for spec in specs:
        sm = managers.get(spec.client_id)
        if sm is None:
            sm = swarm.make_manager(
                rng=random.Random(rng.random())
            )
            managers[spec.client_id] = sm
        tasks.append(asyncio.create_task(run_session(swarm, sm, spec)))

    await engine.run_tasks(
        tasks,
        max_virtual_s=horizon_s + 600.0,
        max_wall_s=float(env.get("BBTPU_SIM_WALL_BUDGET_S")),
    )
    sampler.snap()
    await sim_metrics.cancel_quietly([sampler_task])
    await sim_metrics.cancel_quietly(swarm.zombies)
    for s in swarm.servers.values():
        s.stop()
        await sim_metrics.cancel_quietly(s._tasks)
    return [t.result() for t in tasks], sampler.samples, start_t


def _new_swarm() -> SimSwarm:
    return SimSwarm(InProcessRegistry(), MODEL_UID, NUM_BLOCKS, CostModel())


# ------------------------------------------------------------- flash crowd
async def flash_crowd(engine, sessions: int, seed: int) -> dict:
    horizon = max(120.0, 0.6 * sessions)
    swarm = _new_swarm()
    faults = FaultSchedule([])
    _mk(engine, swarm, faults, "a0", 0, 4, 0)
    _mk(engine, swarm, faults, "b0", 4, 8, 3)
    _mk(engine, swarm, faults, "sb", 4, 8, 6, standby=True)
    crowd_at = horizon * 0.4
    crowd_width = 3.0  # absolute, like the crowd itself: an impulse
    specs = flash_crowd_sessions(
        sessions, horizon, seed=seed, crowd_at_s=crowd_at,
        crowd_width_s=crowd_width,
    )
    results, samples, _ = await _drive(engine, swarm, specs, seed, horizon)
    report, failures = sim_metrics.evaluate(
        "flash_crowd", results, samples, swarm.servers,
        perturb_end_t=crowd_at + crowd_width, expect_shed=True,
    )
    return {**report, "failures": failures}


# --------------------------------------------------------------- span loss
async def span_loss(engine, sessions: int, seed: int) -> dict:
    horizon = max(120.0, 0.6 * sessions)
    swarm = _new_swarm()
    # the primary dies at a scripted decode step — the logical-clock
    # vocabulary chaos e2e tests use (ScheduledFault counts span-output
    # replies on that server's port)
    faults = FaultSchedule([
        ScheduledFault(
            at_step=max(120, int(600 * sessions / 1000)),
            action="crash", port=BASE_PORT + 3, target="b0",
        ),
    ])
    _mk(engine, swarm, faults, "a0", 0, 4, 0)
    b0 = _mk(engine, swarm, faults, "b0", 4, 8, 3)
    b1 = _mk(engine, swarm, faults, "b1", 4, 8, 4)
    _mk(engine, swarm, faults, "sb", 4, 8, 6, standby=True)

    async def correlated_second_crash():
        # the replica absorbs the failover load for 5 virtual seconds,
        # then dies too (shared rack / shared bug — the correlated case
        # that makes the standby the span's only hope)
        while not b0._crashed:
            await clock.async_sleep(1.0)
        await clock.async_sleep(5.0)
        b1.crash()

    watcher = asyncio.create_task(correlated_second_crash())
    specs = poisson_sessions(sessions, horizon, seed=seed)
    results, samples, start_t = await _drive(
        engine, swarm, specs, seed, horizon
    )
    await sim_metrics.cancel_quietly([watcher])
    crash_rel = max(
        (s.crashed_at - start_t)
        for s in (b0, b1) if s.crashed_at is not None
    ) if b0.crashed_at or b1.crashed_at else None
    report, failures = sim_metrics.evaluate(
        "span_loss", results, samples, swarm.servers,
        perturb_end_t=crash_rel, expect_promotion=True,
        min_complete_frac=0.95,
    )
    if not (b0._crashed and b1._crashed):
        failures.append(
            "span_loss: scripted crashes never fired (fault schedule "
            "never came due) — vacuous run"
        )
    return {**report, "failures": failures}


# ----------------------------------------------------------------- diurnal
async def diurnal(engine, sessions: int, seed: int) -> dict:
    horizon = max(120.0, 0.6 * sessions)
    swarm = _new_swarm()
    faults = FaultSchedule([])
    _mk(engine, swarm, faults, "a0", 0, 4, 0)
    # a1 is the spare capacity the rebalancer may move
    _mk(engine, swarm, faults, "a1", 0, 4, 1, rebalance_period=7.0)
    # b0 is a slow host: 16x the modeled compute cost, nominal advert —
    # only its live load advert (measured rebalancing) exposes it
    _mk(engine, swarm, faults, "b0", 4, 8, 3, cost_scale=16.0)
    specs = diurnal_sessions(sessions, horizon, seed=seed)
    results, samples, _ = await _drive(engine, swarm, specs, seed, horizon)
    report, failures = sim_metrics.evaluate(
        "diurnal", results, samples, swarm.servers,
        perturb_end_t=horizon * 0.6, expect_rebalance=True,
    )
    return {**report, "failures": failures}


SCENARIOS = {
    "flash_crowd": flash_crowd,
    "span_loss": span_loss,
    "diurnal": diurnal,
}


def run_scenario(
    name: str, sessions: int | None = None, seed: int | None = None
) -> dict:
    """Run one scenario under a fresh engine; returns its JSON report
    (metrics + per-server counters + gate failures + engine stats)."""
    from bloombee_tpu.sim.engine import SimEngine

    if sessions is None:
        sessions = int(env.get("BBTPU_SIM_SESSIONS"))
    if seed is None:
        seed = int(env.get("BBTPU_SIM_SEED"))
    engine = SimEngine()
    wall0 = clock.perf_counter()
    report = engine.run(SCENARIOS[name], sessions, seed)
    report["wall_s"] = round(clock.perf_counter() - wall0, 3)
    report["advances"] = engine.advances
    report["sessions_requested"] = sessions
    report["seed"] = seed
    return report
