"""ComputeQueue unit suite: priority ordering, deadline expiry while
queued, caller cancellation, shutdown drain, and the continuous-batching
group pop (coalescing, compatibility keys, gather window, per-member
outcomes)."""

import asyncio
import threading
import time

import pytest

from bloombee_tpu.server.compute_queue import (
    PRIORITY_INFERENCE,
    PRIORITY_PREFILL_CHUNK,
    PRIORITY_TRAINING,
    ComputeQueue,
    DeadlineExpired,
    aged_chunk_priority,
)


def _jam(q):
    """Occupy the single compute worker until the returned event is set,
    so later submissions provably sit in the queue."""
    gate = threading.Event()
    task = asyncio.create_task(
        q.submit(PRIORITY_INFERENCE, gate.wait, 5.0)
    )
    return gate, task


# ------------------------------------------------------------ plain tasks
def test_priority_ordering():
    """Inference submitted AFTER training still runs first once the worker
    frees up — the queue orders by priority, not arrival."""

    async def run():
        q = ComputeQueue()
        q.start()
        gate, jam = _jam(q)
        await asyncio.sleep(0.05)  # the jam is now on the worker thread
        order = []
        t_train = asyncio.create_task(
            q.submit(PRIORITY_TRAINING, order.append, "train")
        )
        t_inf = asyncio.create_task(
            q.submit(PRIORITY_INFERENCE, order.append, "inference")
        )
        await asyncio.sleep(0.05)
        gate.set()
        await asyncio.gather(jam, t_train, t_inf)
        assert order == ["inference", "train"]
        await q.stop()

    asyncio.run(run())


def test_args_bound_at_submit_time():
    """Each submission's fn/args bind when submitted (functools.partial),
    so rapid-fire submissions can never see each other's arguments."""

    async def run():
        q = ComputeQueue()
        q.start()
        gate, jam = _jam(q)
        await asyncio.sleep(0.05)
        tasks = [
            asyncio.create_task(q.submit(PRIORITY_INFERENCE, lambda x: x, i))
            for i in range(8)
        ]
        gate.set()
        results = await asyncio.gather(jam, *tasks)
        assert results[1:] == list(range(8))
        await q.stop()

    asyncio.run(run())


def test_deadline_expires_while_queued():
    """A task whose monotonic deadline passes while it waits behind a slow
    step raises DeadlineExpired instead of running; in-budget work behind
    it is unaffected."""

    async def run():
        q = ComputeQueue()
        q.start()
        gate, jam = _jam(q)
        await asyncio.sleep(0.05)
        ran = []
        doomed = asyncio.create_task(
            q.submit(PRIORITY_INFERENCE, ran.append, "doomed",
                     deadline=time.monotonic() + 0.05)
        )
        healthy = asyncio.create_task(
            q.submit(PRIORITY_INFERENCE, ran.append, "healthy",
                     deadline=time.monotonic() + 60.0)
        )
        await asyncio.sleep(0.2)  # burn the doomed task's budget
        gate.set()
        await jam
        with pytest.raises(DeadlineExpired):
            await doomed
        await healthy
        assert ran == ["healthy"]
        await q.stop()

    asyncio.run(run())


def test_cancelled_caller_is_skipped():
    """Cancelling the awaiting task while its work is queued drops the
    work without poisoning the worker loop."""

    async def run():
        q = ComputeQueue()
        q.start()
        gate, jam = _jam(q)
        await asyncio.sleep(0.05)
        ran = []
        victim = asyncio.create_task(
            q.submit(PRIORITY_INFERENCE, ran.append, "victim")
        )
        await asyncio.sleep(0.05)
        victim.cancel()
        with pytest.raises(asyncio.CancelledError):
            await victim
        survivor = asyncio.create_task(
            q.submit(PRIORITY_INFERENCE, ran.append, "survivor")
        )
        gate.set()
        await asyncio.gather(jam, survivor)
        assert ran == ["survivor"]
        await q.stop()

    asyncio.run(run())


def test_stop_drains_pending_futures():
    """stop() must fail queued-but-unstarted work with CancelledError —
    a future that never resolves would hang its awaiter (a session
    handler) forever on server shutdown."""

    async def run():
        q = ComputeQueue()
        q.start()
        gate, jam = _jam(q)
        await asyncio.sleep(0.05)
        pending = [
            asyncio.create_task(q.submit(PRIORITY_INFERENCE, lambda: 1))
            for _ in range(3)
        ]
        await asyncio.sleep(0.05)
        await q.stop()
        gate.set()
        for t in pending:
            with pytest.raises(asyncio.CancelledError):
                await asyncio.wait_for(t, timeout=5.0)

    asyncio.run(run())


# ------------------------------------------------------------- group pop
def test_group_coalesces_queued_members():
    """Same-key batchable tasks queued while the worker is busy execute as
    ONE run_group call; each caller gets its own member's outcome."""

    async def run():
        q = ComputeQueue(max_group=8)
        q.start()
        calls = []

        def run_group(payloads):
            calls.append(list(payloads))
            return [p * 10 for p in payloads]

        gate, jam = _jam(q)
        await asyncio.sleep(0.05)
        ts = [
            asyncio.create_task(
                q.submit_group(PRIORITY_INFERENCE, "k", i, run_group)
            )
            for i in range(4)
        ]
        await asyncio.sleep(0.05)
        gate.set()
        results = await asyncio.gather(jam, *ts)
        assert results[1:] == [0, 10, 20, 30]
        assert calls == [[0, 1, 2, 3]]
        await q.stop()

    asyncio.run(run())


def test_group_respects_max_group():
    """More same-key members than max_group split into multiple dispatches
    — none are dropped."""

    async def run():
        q = ComputeQueue(max_group=2)
        q.start()
        calls = []

        def run_group(payloads):
            calls.append(list(payloads))
            return payloads

        gate, jam = _jam(q)
        await asyncio.sleep(0.05)
        ts = [
            asyncio.create_task(
                q.submit_group(PRIORITY_INFERENCE, "k", i, run_group)
            )
            for i in range(5)
        ]
        await asyncio.sleep(0.05)
        gate.set()
        results = await asyncio.gather(jam, *ts)
        assert results[1:] == [0, 1, 2, 3, 4]
        assert [len(c) for c in calls] == [2, 2, 1]
        await q.stop()

    asyncio.run(run())


def test_group_keys_do_not_mix():
    """Different compatibility keys (e.g. different adapters or dtypes)
    never share a dispatch."""

    async def run():
        q = ComputeQueue(max_group=8)
        q.start()
        calls = []

        def run_group(payloads):
            calls.append(sorted(payloads))
            return payloads

        gate, jam = _jam(q)
        await asyncio.sleep(0.05)
        ts = [
            asyncio.create_task(
                q.submit_group(PRIORITY_INFERENCE, key, f"{key}{i}",
                               run_group)
            )
            for i in range(2)
            for key in ("a", "b")
        ]
        await asyncio.sleep(0.05)
        gate.set()
        await asyncio.gather(jam, *ts)
        assert sorted(map(tuple, calls)) == [
            ("a0", "a1"), ("b0", "b1"),
        ]
        await q.stop()

    asyncio.run(run())


def test_compat_predicate_mixes_heterogeneous_keys():
    """A custom compat(members, candidate) predicate admits members with
    DIFFERENT keys into one dispatch (the mixed-batch hook): decode-keyed
    members absorb one chunk-keyed member, a second chunk stays out, and
    admission sees the members gathered so far (the predicate widens as
    the group grows)."""

    async def run():
        def compat(members, cand):
            # any number of "d" keys, at most one "c" key per group
            if cand.key == "c":
                return all(m.key != "c" for m in members)
            return cand.key == "d"

        q = ComputeQueue(max_group=8, compat=compat)
        q.start()
        calls = []

        def run_group(payloads):
            calls.append(sorted(payloads))
            return payloads

        gate, jam = _jam(q)
        await asyncio.sleep(0.05)
        ts = [
            asyncio.create_task(
                q.submit_group(PRIORITY_INFERENCE, key, payload, run_group)
            )
            for key, payload in (
                ("d", "d0"), ("d", "d1"), ("c", "c0"), ("c", "c1"),
            )
        ]
        await asyncio.sleep(0.05)
        gate.set()
        results = await asyncio.gather(jam, *ts)
        assert results[1:] == ["d0", "d1", "c0", "c1"]
        # first pop gathered both decodes AND one chunk; the second chunk
        # was requeued and dispatched on its own
        assert calls == [["c0", "d0", "d1"], ["c1"]]
        await q.stop()

    asyncio.run(run())


def test_group_member_exception_is_scattered():
    """run_group returning an Exception instance for one member fails only
    that member's future; the rest resolve normally."""

    async def run():
        q = ComputeQueue(max_group=8)
        q.start()

        def run_group(payloads):
            return [
                ValueError("bad row") if p == 1 else p for p in payloads
            ]

        gate, jam = _jam(q)
        await asyncio.sleep(0.05)
        ok = asyncio.create_task(
            q.submit_group(PRIORITY_INFERENCE, "k", 0, run_group)
        )
        bad = asyncio.create_task(
            q.submit_group(PRIORITY_INFERENCE, "k", 1, run_group)
        )
        await asyncio.sleep(0.05)
        gate.set()
        await jam
        assert await ok == 0
        with pytest.raises(ValueError, match="bad row"):
            await bad
        await q.stop()

    asyncio.run(run())


def test_group_member_deadline_drops_only_that_member():
    async def run():
        q = ComputeQueue(max_group=8)
        q.start()
        calls = []

        def run_group(payloads):
            calls.append(list(payloads))
            return payloads

        gate, jam = _jam(q)
        await asyncio.sleep(0.05)
        doomed = asyncio.create_task(
            q.submit_group(PRIORITY_INFERENCE, "k", "doomed", run_group,
                           deadline=time.monotonic() + 0.05)
        )
        healthy = asyncio.create_task(
            q.submit_group(PRIORITY_INFERENCE, "k", "healthy", run_group,
                           deadline=time.monotonic() + 60.0)
        )
        await asyncio.sleep(0.2)
        gate.set()
        await jam
        with pytest.raises(DeadlineExpired):
            await doomed
        assert await healthy == "healthy"
        assert calls == [["healthy"]]
        await q.stop()

    asyncio.run(run())


def test_gather_window_catches_late_arrivals(monkeypatch):
    """With BBTPU_BATCH_WINDOW_MS set, a member submitted shortly AFTER
    the worker popped the first one still joins the same dispatch."""
    monkeypatch.setenv("BBTPU_BATCH_WINDOW_MS", "250")

    async def run():
        q = ComputeQueue(max_group=8)
        q.start()
        calls = []

        def run_group(payloads):
            calls.append(list(payloads))
            return payloads

        first = asyncio.create_task(
            q.submit_group(PRIORITY_INFERENCE, "k", "early", run_group)
        )
        await asyncio.sleep(0.05)  # worker popped "early", window open
        second = asyncio.create_task(
            q.submit_group(PRIORITY_INFERENCE, "k", "late", run_group)
        )
        assert await first == "early"
        assert await second == "late"
        assert calls == [["early", "late"]]
        await q.stop()

    asyncio.run(run())


def test_gather_window_dispatches_early_on_full_house(monkeypatch):
    """With a group_hint (the server's open-session count), the gather
    window ends the moment the group holds every possible member instead
    of sleeping out the full window — here the window is far longer than
    the test timeout, so only early dispatch lets this pass."""
    monkeypatch.setenv("BBTPU_BATCH_WINDOW_MS", "30000")

    async def run():
        q = ComputeQueue(max_group=8, group_hint=lambda members: 2)
        q.start()
        calls = []

        def run_group(payloads):
            calls.append(list(payloads))
            return payloads

        first = asyncio.create_task(
            q.submit_group(PRIORITY_INFERENCE, "k", "a", run_group)
        )
        await asyncio.sleep(0.05)  # worker popped "a", window open
        second = asyncio.create_task(
            q.submit_group(PRIORITY_INFERENCE, "k", "b", run_group)
        )
        t0 = time.monotonic()
        assert await asyncio.wait_for(first, timeout=5.0) == "a"
        assert await asyncio.wait_for(second, timeout=5.0) == "b"
        assert time.monotonic() - t0 < 5.0
        assert calls == [["a", "b"]]
        await q.stop()

    asyncio.run(run())


def test_solo_session_skips_gather_window(monkeypatch):
    """group_hint == 1 (one open session): nobody else can ever join, so
    the window must not be slept at all."""
    monkeypatch.setenv("BBTPU_BATCH_WINDOW_MS", "30000")

    async def run():
        q = ComputeQueue(max_group=8, group_hint=lambda members: 1)
        q.start()

        def run_group(payloads):
            return payloads

        t0 = time.monotonic()
        out = await asyncio.wait_for(
            q.submit_group(PRIORITY_INFERENCE, "k", "solo", run_group),
            timeout=5.0,
        )
        assert out == "solo" and time.monotonic() - t0 < 5.0
        await q.stop()

    asyncio.run(run())


def test_wait_stats_report_queue_time():
    async def run():
        q = ComputeQueue()
        q.start()
        assert q.wait_stats_ms() == {
            "p50": 0.0, "p95": 0.0,
            "prefill": {"p50": 0.0, "p95": 0.0},
            "decode": {"p50": 0.0, "p95": 0.0},
        }
        gate, jam = _jam(q)
        await asyncio.sleep(0.05)
        waiter = asyncio.create_task(
            q.submit(PRIORITY_INFERENCE, lambda: None)
        )
        await asyncio.sleep(0.15)
        gate.set()
        await asyncio.gather(jam, waiter)
        stats = q.wait_stats_ms()
        # the second task waited >= ~150 ms behind the jam
        assert stats["p95"] >= 100.0
        assert stats["p50"] >= 0.0
        await q.stop()

    asyncio.run(run())


# ------------------------------------------- stall-free chunk scheduling
def test_per_class_wait_stats_split():
    """task_class buckets wait samples into per-class p50/p95 next to the
    blended numbers — the decode-class wait is the stall-free signal."""

    async def run():
        q = ComputeQueue()
        q.start()
        gate, jam = _jam(q)
        await asyncio.sleep(0.05)
        pre = asyncio.create_task(
            q.submit(PRIORITY_TRAINING, lambda: None, task_class="prefill")
        )
        dec = asyncio.create_task(
            q.submit(PRIORITY_INFERENCE, lambda: None, task_class="decode")
        )
        await asyncio.sleep(0.15)
        gate.set()
        await asyncio.gather(jam, pre, dec)
        stats = q.wait_stats_ms()
        assert stats["prefill"]["p95"] >= 100.0
        assert stats["decode"]["p95"] >= 100.0
        assert stats["p95"] >= 100.0
        await q.stop()

    asyncio.run(run())


def test_fresh_chunk_yields_to_later_decode():
    """A queued prefill chunk at PRIORITY_PREFILL_CHUNK loses to a decode
    step submitted AFTER it — decodes preempt the next chunk."""

    async def run():
        q = ComputeQueue()
        q.start()
        gate, jam = _jam(q)
        await asyncio.sleep(0.05)
        order = []
        t0 = time.monotonic()
        assert aged_chunk_priority(t0, now=t0) == PRIORITY_PREFILL_CHUNK
        chunk = asyncio.create_task(
            q.submit(aged_chunk_priority(t0), order.append, "chunk",
                     task_class="prefill")
        )
        await asyncio.sleep(0.02)
        dec = asyncio.create_task(
            q.submit(PRIORITY_INFERENCE, order.append, "decode",
                     task_class="decode")
        )
        await asyncio.sleep(0.05)
        gate.set()
        await asyncio.gather(jam, chunk, dec)
        assert order == ["decode", "chunk"]
        await q.stop()

    asyncio.run(run())


def test_aged_chunk_competes_at_decode_priority(monkeypatch):
    """Past the BBTPU_CHUNK_AGE_S horizon a chunk stream's priority decays
    to decode priority, so FIFO order protects it from starvation: an old
    stream's chunk submitted BEFORE a decode now runs first."""
    monkeypatch.setenv("BBTPU_CHUNK_AGE_S", "0.01")

    async def run():
        q = ComputeQueue()
        q.start()
        gate, jam = _jam(q)
        await asyncio.sleep(0.05)
        order = []
        started_long_ago = time.monotonic() - 1.0
        assert aged_chunk_priority(started_long_ago) == PRIORITY_INFERENCE
        chunk = asyncio.create_task(
            q.submit(aged_chunk_priority(started_long_ago),
                     order.append, "chunk", task_class="prefill")
        )
        await asyncio.sleep(0.02)
        dec = asyncio.create_task(
            q.submit(PRIORITY_INFERENCE, order.append, "decode",
                     task_class="decode")
        )
        await asyncio.sleep(0.05)
        gate.set()
        await asyncio.gather(jam, chunk, dec)
        assert order == ["chunk", "decode"]
        await q.stop()

    asyncio.run(run())


def test_chunk_priority_decay_is_monotonic():
    t0 = 1000.0
    prios = [
        aged_chunk_priority(t0, now=t0 + dt)
        for dt in (0.0, 0.5, 1.0, 1.9, 2.0, 50.0)
    ]
    assert prios[0] == PRIORITY_PREFILL_CHUNK
    assert all(a >= b for a, b in zip(prios, prios[1:]))
    assert prios[-2] == prios[-1] == PRIORITY_INFERENCE
    # chunks always outrank training work, even fresh
    assert all(PRIORITY_INFERENCE <= p < PRIORITY_TRAINING for p in prios)


def test_chunk_stream_interleaves_queued_decodes():
    """Fake resumable chunk driver (the server's _run_chunked_prefill
    shape, no model needed): each chunk is its own submission, so a decode
    queued while chunk N occupies the worker runs BEFORE chunk N+1 —
    decodes land between chunks instead of waiting out the whole prompt."""

    from bloombee_tpu.utils import clock as vclock
    from bloombee_tpu.utils.clock import ScaledClock

    async def run():
        q = ComputeQueue()
        q.start()
        order = []
        t0 = vclock.monotonic()

        def work(tag):
            # occupy the worker like a device dispatch — on the scaled
            # clock, so the interleaving stays but the waiting shrinks
            vclock.sleep(0.02)
            order.append(tag)

        async def chunk_stream():
            # re-enters the queue between chunks at the aging priority,
            # exactly like the server's chunked-prefill state machine
            for i in range(4):
                await q.submit(
                    aged_chunk_priority(t0), work, f"C{i}",
                    task_class="prefill",
                )

        done = asyncio.Event()

        async def decode_loop():
            i = 0
            while not done.is_set():
                await q.submit(
                    PRIORITY_INFERENCE, work, f"D{i}", task_class="decode"
                )
                i += 1

        dec = asyncio.create_task(decode_loop())
        await asyncio.sleep(0.01)
        await chunk_stream()
        done.set()
        await dec
        chunks = [i for i, t in enumerate(order) if t.startswith("C")]
        assert len(chunks) == 4
        # at least one decode ran strictly between two chunks of the
        # stream (with a monolithic prefill there is nothing "between")
        assert any(b - a > 1 for a, b in zip(chunks, chunks[1:])), order
        stats = q.wait_stats_ms()
        # per-class stats saw both sides of the interleave
        assert stats["decode"] != {"p50": 0.0, "p95": 0.0} or order
        await q.stop()

    prev = vclock.install(ScaledClock(scale=4.0))
    try:
        asyncio.run(run())
    finally:
        vclock.install(prev)


def test_host_path_kinds_add_up_to_the_workers_busy_time(monkeypatch):
    """With the witness on every task the worker ran is in `host_path` under
    its kind, from the same clock pair as `worker.busy_ms`: the kinds' wall
    is the busy time, a kind's legs its wall; `starved + hop + busy` still
    cover the worker's life."""
    from bloombee_tpu.utils import jitwatch

    monkeypatch.setenv("BBTPU_JITWATCH", "1")
    jitwatch.reset()

    def work(ms):
        with jitwatch.stopwatch("bbtpu.dispatch"):
            with jitwatch.span("bbtpu.pack"):
                time.sleep(ms / 1e3)
        return ms

    def run_group(payloads):
        return [work(p) for p in payloads]

    async def run():
        q = ComputeQueue()
        q.start()
        await asyncio.gather(
            q.submit(PRIORITY_INFERENCE, work, 3, task_class="decode"),
            q.submit(PRIORITY_INFERENCE, work, 4, task_class="prefill"),
            q.submit(PRIORITY_INFERENCE, work, 2),
            q.submit_group(PRIORITY_INFERENCE, ("decode1", 0), 2, run_group),
            q.submit_group(PRIORITY_INFERENCE, ("decode1", 0), 1, run_group),
            q.submit_group(PRIORITY_INFERENCE, ("chunkm", 0), 5, run_group),
            q.submit_group(PRIORITY_INFERENCE, ("tree", 0), 1, run_group),
        )
        worker, path = q.worker_stats_ms(), q.host_path()
        await q.stop()
        return worker, path

    worker, path = asyncio.run(run())
    jitwatch.reset()
    assert {k: rec["n"] for k, rec in path.items()} == {
        "decode": 2, "chunk": 2, "other": 2,
    }
    assert sum(rec["n"] for rec in path.values()) == worker["tasks"] == 6
    assert sum(rec["wall_ms"] for rec in path.values()) == pytest.approx(
        worker["busy_ms"], abs=2e-3)
    for rec in path.values():
        assert sum(v["wall_ms"] for v in rec["legs"].values()) == (
            pytest.approx(rec["wall_ms"], abs=1e-5))
        assert set(rec["legs"]) == {"bbtpu.pack", "unnamed"}
        assert rec["cpu_ms"] <= 0.5 * rec["cpu_wall_ms"]  # it slept
        assert rec["launches"] == 0
    assert path["decode"]["wall_ms"] >= 6.0 and path["chunk"]["wall_ms"] >= 9.0
