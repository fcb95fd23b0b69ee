"""Swarm traffic simulator: conductor semantics, scenario gates, and the
anti-vacuity proof that the gates can actually fail.

These run in tier-1 only (the whole point of the virtual clock is that a
thousand virtual seconds cost wall milliseconds); the chaos matrix's SIM
entry exercises the same scenarios through the shipped gate itself,
``python -m bloombee_tpu.sim --require --smoke`` — deliberately NOT by
replaying this file, which would double-pay its wall cost for zero new
coverage.
"""

import asyncio
import time

import pytest

from bloombee_tpu.sim.engine import SimEngine
from bloombee_tpu.sim.scenarios import SCENARIOS, run_scenario
from bloombee_tpu.utils import clock as vclock

# The scenario gates define "healthy" for STOCK control-plane tuning; the
# chaos matrix replays these tests under entries that deliberately warp
# that tuning (BBTPU_ADMIT_HIGH_MS=400, BBTPU_MEASURED_REBALANCE=0, ...),
# which would make a red un-attributable. Pin every knob the scenarios'
# physics depends on back to its declared default. The anti-vacuity test
# then re-warps exactly one knob on purpose.
_STOCK_TUNING = [
    "BBTPU_ADMIT", "BBTPU_ADMIT_HIGH_MS", "BBTPU_ADMIT_RETRY_MS",
    "BBTPU_ADMIT_WINDOW_S", "BBTPU_MEASURED_REBALANCE",
    "BBTPU_PROMOTE_HIGH_MS", "BBTPU_PROMOTE_SUSTAIN_S",
    "BBTPU_MIXED_BATCH", "BBTPU_SPEC_BATCH", "BBTPU_BATCH_WINDOW_MS",
    "BBTPU_CHUNK_AGE_S", "BBTPU_KEEPALIVE_S", "BBTPU_CLOCK_SCALE",
    "BBTPU_SIM_SESSIONS", "BBTPU_SIM_SEED",
    "BBTPU_SIM_SETTLE_S", "BBTPU_SIM_RETRY_AMP_MAX",
    "BBTPU_SIM_SHED_AMP_MAX", "BBTPU_SIM_FLAP_MAX",
    "BBTPU_SIM_PROMOTE_LATENCY_S", "BBTPU_SIM_WALL_BUDGET_S",
]


@pytest.fixture(autouse=True)
def _stock_tuning(monkeypatch):
    for name in _STOCK_TUNING:
        monkeypatch.delenv(name, raising=False)


# --------------------------------------------------------------- conductor


def test_engine_advances_virtual_time_for_free():
    """Sleepers wake in deadline order at exact virtual instants, and
    minutes of virtual time cost (well under) seconds of wall time."""
    eng = SimEngine(start=100.0)
    woke = []

    async def sleeper(tag, dur):
        await vclock.async_sleep(dur)
        woke.append((tag, eng.now()))

    async def main(engine):
        tasks = [
            asyncio.ensure_future(sleeper("slow", 250.0)),
            asyncio.ensure_future(sleeper("fast", 100.0)),
        ]
        await engine.run_tasks(tasks, max_virtual_s=1000.0, max_wall_s=30.0)

    w0 = time.perf_counter()
    eng.run(main)
    wall = time.perf_counter() - w0
    assert woke == [("fast", 200.0), ("slow", 350.0)]
    assert eng.advances >= 2
    assert wall < 5.0, f"350 virtual seconds cost {wall:.1f}s wall"


def test_counting_executor_delivers_compute_at_virtual_cost():
    """A cost-model compute (thread-side ``clock.sleep``) completes at
    exactly submit-time + cost, and the single sim worker serializes
    submissions — the conductor never advances past in-flight compute."""
    eng = SimEngine(start=0.0)

    async def main(engine):
        ex = engine.new_executor()

        def compute(cost):
            vclock.sleep(cost)
            return engine.now()

        async def one(cost):
            return await asyncio.wrap_future(ex.submit(compute, cost))

        tasks = [
            asyncio.ensure_future(one(5.0)),
            asyncio.ensure_future(one(3.0)),
        ]
        await engine.run_tasks(tasks, max_virtual_s=100.0, max_wall_s=30.0)
        return [t.result() for t in tasks]

    # one worker: the 3.0 job queues behind the 5.0 job, finishing at 8.0
    assert eng.run(main) == [5.0, 8.0]


def test_stall_detection_fails_loudly():
    """Live tasks with no virtual sleeper is a deadlock in the code under
    test; the conductor must raise, not hang CI."""
    from bloombee_tpu.sim.engine import SimStalled

    eng = SimEngine()

    async def main(engine):
        blocked = asyncio.ensure_future(asyncio.Event().wait())
        try:
            await engine.run_tasks([blocked], max_wall_s=1.0)
        finally:
            blocked.cancel()

    with pytest.raises(SimStalled):
        eng.run(main)


# --------------------------------------------------------------- scenarios


def test_flash_crowd_smoke_passes_gates_with_real_shedding():
    """Healthy stock tuning rides out the crowd: every gate green, and
    the overload machinery demonstrably engaged (sheds, abandons, naive
    retries) — a run where nothing shed would prove nothing."""
    rep = run_scenario("flash_crowd", sessions=200, seed=0)
    m = rep["metrics"]
    assert rep["failures"] == [], rep["failures"]
    assert m["completed"] == m["sessions"]
    assert m["shed_total"] > 0, "crowd never tripped admission control"
    assert m["abandons"] > 0, "no naive client abandoned a slow prefill"
    assert m["retry_amplification"] > 1.0
    assert m["shed_retry_amplification"] >= m["retry_amplification"]


def test_span_loss_smoke_promotes_standby():
    rep = run_scenario("span_loss", sessions=120, seed=0)
    m = rep["metrics"]
    assert rep["failures"] == [], rep["failures"]
    assert m["completed"] == m["sessions"]
    assert m["promotions"] >= 1, "correlated crash never promoted standby"


def test_diurnal_smoke_rebalances():
    rep = run_scenario("diurnal", sessions=120, seed=0)
    m = rep["metrics"]
    assert rep["failures"] == [], rep["failures"]
    assert m["completed"] == m["sessions"]
    assert m["rebalances_moved"] >= 1, (
        "skewed diurnal load never triggered a measured rebalance"
    )


def test_mistuned_retry_hint_trips_metastable_gate(monkeypatch):
    """Anti-vacuity: the gates must be able to FAIL. With the admission
    Retry-After hint floored to 1ms, naive crowd clients re-enter in
    lockstep, abandoned prefills keep burning queue, and the retry storm
    sustains itself — the amplification gates must go red."""
    monkeypatch.setenv("BBTPU_ADMIT_RETRY_MS", "1")
    rep = run_scenario("flash_crowd", sessions=200, seed=0)
    assert rep["failures"], (
        "BBTPU_ADMIT_RETRY_MS=1 passed every gate — the simulator can no "
        "longer distinguish a metastable swarm from a healthy one"
    )
    assert any("attempts" in f or "amplification" in f
               for f in rep["failures"]), rep["failures"]


def test_scenario_catalog_is_stable():
    assert list(SCENARIOS) == ["flash_crowd", "span_loss", "diurnal"]
