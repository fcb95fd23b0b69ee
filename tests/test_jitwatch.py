"""Runtime compile/transfer witness (utils/jitwatch.py): region-based
compile attribution, the warmup-fence phase contract, hot-path host-sync
counting, the zero-overhead-when-off contract, the multi-process
report/--require gate (vacuous-green, missing-fence, and steady-
recompile failure modes), and one live e2e swarm run proving a
multi-session steady-state decode incurs ZERO post-warmup recompiles
while observing >=1 warmup compile. Host spans: nesting on the region
stack, the stopwatch, the compute worker's starved/hop/busy account, the
span counts of a served decode step and a chunked prefill, and named
scopes leaving the step's outputs bit-identical.
"""

import asyncio
import json

import jax
import numpy as np
import pytest

from bloombee_tpu.utils import jitwatch


@pytest.fixture(autouse=True)
def fresh_witness():
    jitwatch.reset()
    yield
    jitwatch.reset()


@pytest.fixture
def watch_on(monkeypatch):
    monkeypatch.setenv("BBTPU_JITWATCH", "1")
    monkeypatch.delenv("BBTPU_JITWATCH_REPORT", raising=False)


# --------------------------------------------------------- off = zero cost
def test_off_is_zero_overhead(monkeypatch):
    """With the switch off: hot_wrap returns the function object itself
    (no wrapper in the compute queue's dispatch path), regions and
    syncs record nothing, and install() declines."""
    monkeypatch.delenv("BBTPU_JITWATCH", raising=False)

    def fn():
        return 7

    assert jitwatch.hot_wrap(fn) is fn
    with jitwatch.region("span_step", "b1,t1,p4"):
        jitwatch.host_sync("executor.fetch")
    jitwatch._witness.record_compile(0.0)  # listener never fires when off;
    # a stray direct record still lands unattributed-warmup, but the
    # public paths above must have recorded nothing
    snap = jitwatch.snapshot()
    assert snap["host_syncs"] == {}
    assert jitwatch.install() is False


# ----------------------------------------------------- attribution + phases
def test_region_attribution_and_warmup_phase(watch_on):
    with jitwatch.region("span_step", "b2,t8,p4"):
        jitwatch._witness.record_compile(0.25)
    jitwatch._witness.record_compile(0.05)  # outside any region
    snap = jitwatch.snapshot()
    assert snap["xla_compiles"] == 2
    assert snap["warmup_compiles"] == 2
    assert snap["steady_state_recompiles"] == 0
    assert snap["compile_ms_total"] == pytest.approx(300.0)
    funcs = [(c["function"], c["shape"], c["phase"]) for c in snap["compiles"]]
    assert funcs == [
        ("span_step", "b2,t8,p4", "warmup"),
        ("(unattributed)", "", "warmup"),
    ]


def test_nested_regions_attribute_to_innermost(watch_on):
    with jitwatch.region("decode_loop", "b1,n8,p4"):
        with jitwatch.region("layer_step", "b1,t1,p4"):
            jitwatch._witness.record_compile(0.01)
        jitwatch._witness.record_compile(0.01)
    snap = jitwatch.snapshot()
    assert [c["function"] for c in snap["compiles"]] == [
        "layer_step", "decode_loop",
    ]


def test_fence_splits_steady_from_warmup(watch_on):
    with jitwatch.region("span_step", "b1,t8,p4"):
        jitwatch._witness.record_compile(0.1)
    jitwatch.fence()
    with jitwatch.region("span_step", "b1,t16,p8"):  # bucket escaped warmup
        jitwatch._witness.record_compile(0.2)
    snap = jitwatch.snapshot()
    assert snap["fenced"] is True
    assert snap["warmup_compiles"] == 1
    assert snap["steady_state_recompiles"] == 1
    assert snap["compiles"][1]["phase"] == "steady"


def test_unattributed_steady_compiles_are_counted_not_gated(watch_on):
    """Client-side jnp work can share a test process with the server:
    its compiles are ledgered (visible in the report) but do not count
    as steady-state recompiles — only region-attributed ones are
    provably the serving path's fault."""
    jitwatch.fence()
    jitwatch._witness.record_compile(0.1)  # no region
    snap = jitwatch.snapshot()
    assert snap["xla_compiles"] == 1
    assert snap["steady_state_recompiles"] == 0


def test_reentrant_warmup_reopens_phase(watch_on):
    jitwatch.fence()
    jitwatch.set_phase("warmup")  # elastic rebalance re-warmup
    with jitwatch.region("span_step", "b4,t8,p4"):
        jitwatch._witness.record_compile(0.1)
    snap = jitwatch.snapshot()
    assert snap["warmup_compiles"] == 1
    assert snap["steady_state_recompiles"] == 0


# ------------------------------------------------------- hot-path host syncs
def test_hot_wrap_marks_syncs_hot(watch_on):
    def task():
        jitwatch.host_sync("executor.fetch")
        return 1

    jitwatch.host_sync("executor.fetch")  # off-queue: not hot
    assert jitwatch.hot_wrap(task)() == 1
    snap = jitwatch.snapshot()
    assert snap["host_syncs"] == {"executor.fetch": 2}
    assert snap["host_syncs_hot_path"] == 1
    assert jitwatch.counters()["host_syncs_hot_path"] == 1


def test_hot_wrap_depth_survives_exceptions(watch_on):
    def boom():
        raise RuntimeError("x")

    with pytest.raises(RuntimeError):
        jitwatch.hot_wrap(boom)()
    jitwatch.host_sync("executor.fetch")  # must be cold again
    assert jitwatch.snapshot()["host_syncs_hot_path"] == 0


def test_compile_ledger_is_bounded(watch_on):
    for _ in range(jitwatch._MAX_COMPILES + 50):
        jitwatch._witness.record_compile(0.001)
    snap = jitwatch.snapshot()
    assert len(snap["compiles"]) == jitwatch._MAX_COMPILES
    # counters keep the true totals past the ledger cap
    assert snap["xla_compiles"] == jitwatch._MAX_COMPILES + 50


# ------------------------------------------------------- report + gate CLI
def _warm_then_fence():
    with jitwatch.region("span_step", "b1,t8,p4"):
        jitwatch._witness.record_compile(0.1)
    jitwatch.fence()


def test_flush_merge_and_require_gate(tmp_path, watch_on, capsys):
    report = tmp_path / "jitwatch.jsonl"
    _warm_then_fence()
    jitwatch.host_sync("executor.fetch")
    jitwatch.flush(str(report))
    # second "process": appended as its own line
    jitwatch.flush(str(report))
    assert len(report.read_text().splitlines()) == 2

    merged = jitwatch.merge_lines(report.read_text())
    assert merged["xla_compiles"] == 2
    assert merged["warmup_compiles"] == 2
    assert merged["steady_state_recompiles"] == 0
    assert merged["host_syncs"] == {"executor.fetch": 2}
    assert merged["fenced"] is True

    assert jitwatch._main([str(report), "--require"]) == 0
    out = capsys.readouterr().out
    assert "2 compile(s)" in out and "fenced=True" in out


def test_require_gate_fails_on_empty_report(tmp_path, capsys):
    report = tmp_path / "empty.jsonl"
    report.write_text("")
    assert jitwatch._main([str(report), "--require"]) == 1
    assert "EMPTY" in capsys.readouterr().err
    # without --require an empty report only informs
    assert jitwatch._main([str(report)]) == 0


def test_require_gate_fails_without_fence(tmp_path, watch_on, capsys):
    """A run that compiled but never dropped the warmup fence proves
    nothing about steady state: 'zero recompiles' would be vacuous."""
    report = tmp_path / "nofence.jsonl"
    with jitwatch.region("span_step", "b1,t8,p4"):
        jitwatch._witness.record_compile(0.1)
    jitwatch.flush(str(report))
    assert jitwatch._main([str(report), "--require"]) == 1
    assert "NO WARMUP FENCE" in capsys.readouterr().err


def test_require_gate_fails_on_steady_recompile(tmp_path, watch_on, capsys):
    report = tmp_path / "steady.jsonl"
    _warm_then_fence()
    with jitwatch.region("span_step_ragged", "r4,s2,p8"):
        jitwatch._witness.record_compile(0.3)
    jitwatch.flush(str(report))
    assert jitwatch._main([str(report), "--require"]) == 1
    out = capsys.readouterr()
    assert "steady-state recompile" in out.err
    # the ledger names the exact (function, shape) to pre-compile
    assert "STEADY RECOMPILE span_step_ragged[r4,s2,p8]" in out.out


def test_flush_skips_empty_witness(tmp_path, watch_on):
    report = tmp_path / "noop.jsonl"
    jitwatch.flush(str(report))
    assert not report.exists() or report.read_text() == ""


def test_report_carries_host_spans_and_merges_them(tmp_path, watch_on, capsys):
    """The exit report (one line per process) holds the spans' n and
    total_ms; the merge adds them up and the CLI prints their means."""
    path = tmp_path / "r.jsonl"
    for _ in range(2):  # two processes' lines
        jitwatch.reset()
        with jitwatch.region("span_step_packed", "b1,t1,p4"):
            jitwatch._witness.record_compile(0.01)
        with jitwatch.span("bbtpu.pack"):
            pass
        jitwatch.flush(str(path))
    merged = jitwatch.merge_lines(path.read_text())
    assert merged["host_spans"]["bbtpu.pack"]["n"] == 2
    assert merged["host_spans"]["bbtpu.jit.span_step_packed"]["n"] == 2
    jitwatch._main([str(path)])
    assert "span bbtpu.pack x2 mean" in capsys.readouterr().out


def test_merge_skips_garbage_lines(watch_on):
    merged = jitwatch.merge_lines(
        "not json\n" + json.dumps({"xla_compiles": 3, "fenced": True}) + "\n"
    )
    assert merged["xla_compiles"] == 3
    assert merged["fenced"] is True


# --------------------------------------------------------------- host spans
def test_span_nests_on_the_region_stack_and_pops_on_exception(watch_on):
    stack = jitwatch._witness._regions()
    with pytest.raises(RuntimeError):
        with jitwatch.span("bbtpu.task", task=7):
            with jitwatch.span("bbtpu.pack") as pack:
                assert [f.name for f in stack] == ["bbtpu.task", "bbtpu.pack"]
                # everything a queue task runs repeats its number
                assert pack.ids == {"task": 7}
            with jitwatch.region("span_step_packed", "b2,t1,p64") as reg:
                assert stack[-1] is reg
                assert reg.name == "bbtpu.jit.span_step_packed"
                assert reg.ids == {"bucket": "b2,t1,p64", "task": 7}
                # a plain span inside a region owns no compile: the region
                # keeps it
                with jitwatch.span("bbtpu.h2d"):
                    jitwatch._witness.record_compile(0.01)
                raise RuntimeError("dispatch failed")
    assert stack == []
    spans = jitwatch.host_spans()
    assert {k: v["n"] for k, v in spans.items()} == {
        "bbtpu.task": 1, "bbtpu.pack": 1, "bbtpu.h2d": 1,
        "bbtpu.jit.span_step_packed": 1,
    }
    assert spans["bbtpu.task"]["total_ms"] >= spans["bbtpu.pack"]["total_ms"]
    assert jitwatch.snapshot()["compiles"][0]["function"] == "span_step_packed"
    # a compile under a plain span alone stays unattributed, as before spans
    with jitwatch.span("bbtpu.slice"):
        jitwatch._witness.record_compile(0.01)
    assert jitwatch.snapshot()["compiles"][1]["function"] == "(unattributed)"


def test_annotation_ids_survive_the_profilers_comma_encoding(watch_on):
    """The profiler writes ids as name#k=v,k=v#: a bucket tag's commas
    would cut the value short, so they travel as semicolons."""
    ann = jitwatch._annotation("bbtpu.jit.x", {"bucket": "b2,t1,p64", "n": 3})
    assert ann is not None
    with jitwatch.region("x", "b2,t1,p64") as reg:
        assert reg.shape == "b2,t1,p64"  # the compile ledger keeps commas


def test_spans_off_are_the_shared_noop_and_stopwatch_still_times(monkeypatch):
    monkeypatch.delenv("BBTPU_JITWATCH", raising=False)
    assert jitwatch.span("bbtpu.pack", session="s") is jitwatch._NOOP
    assert jitwatch.region("span_step_packed", "b1,t1,p4") is jitwatch._NOOP

    def fn():
        return 7

    assert jitwatch.hot_wrap(fn, task=1) is fn
    with jitwatch.stopwatch("bbtpu.dispatch", session="s") as sw:
        sum(range(1000))
    assert sw.ns > 0 and sw.ms == sw.ns / 1e6
    assert jitwatch.host_spans() == {}
    assert jitwatch._witness._regions() == []


def test_worker_account_adds_up_to_the_workers_wall_time(watch_on):
    """starved + hop + busy over a scripted sequence is the wall time from
    the queue's creation to the last task's end (within 5%), and each
    class holds what the script put there."""
    import time

    from bloombee_tpu.server.compute_queue import (
        PRIORITY_INFERENCE,
        ComputeQueue,
    )

    async def run():
        t0 = time.perf_counter()
        q = ComputeQueue()
        q.start()
        await asyncio.sleep(0.06)  # no task exists: starved
        first = asyncio.create_task(
            q.submit(PRIORITY_INFERENCE, time.sleep, 0.05)
        )
        await asyncio.sleep(0.005)
        # enqueued while the first runs: hop from its end, nothing starved
        second = asyncio.create_task(
            q.submit(PRIORITY_INFERENCE, time.sleep, 0.03,
                     task_class="decode")
        )
        await asyncio.gather(first, second)
        wall_ms = (time.perf_counter() - t0) * 1000.0
        stats = q.worker_stats_ms()
        await q.stop()
        return wall_ms, stats

    wall_ms, stats = asyncio.run(run())
    assert stats["tasks"] == 2
    total = stats["starved_ms"] + stats["hop_ms"] + stats["busy_ms"]
    assert abs(total - wall_ms) <= 0.05 * wall_ms, (stats, wall_ms)
    assert 55.0 <= stats["starved_ms"] <= 75.0, stats
    assert 78.0 <= stats["busy_ms"] <= 95.0, stats
    assert stats["hop_ms"] <= 12.0, stats
    spans = jitwatch.host_spans()
    assert spans["bbtpu.task"]["n"] == 2
    assert spans["bbtpu.enqueue"]["n"] == 2


def test_worker_account_off_is_the_function_itself(monkeypatch):
    monkeypatch.delenv("BBTPU_JITWATCH", raising=False)
    from bloombee_tpu.server.compute_queue import _WorkerAccount

    def fn():
        return 7

    account = _WorkerAccount()
    assert account.wrap(fn, 0, task=1) is fn
    assert account.stats_ms() == {
        "tasks": 0, "starved_ms": 0.0, "hop_ms": 0.0, "busy_ms": 0.0,
    }


def test_named_scopes_leave_the_span_step_bit_identical(monkeypatch):
    """The layer's named scopes are HLO metadata only: a prefill and two
    decode steps give the same bits with `jax.named_scope` taken out."""
    import contextlib

    import jax.numpy as jnp

    from bloombee_tpu.kv.cache_manager import CacheManager
    from bloombee_tpu.models.llama.block import init_block_params
    from bloombee_tpu.models.spec import ModelSpec
    from bloombee_tpu.runtime.executor import SpanExecutor
    from bloombee_tpu.utils.tree import stack_params

    spec = ModelSpec(
        family="llama", hidden_size=32, intermediate_size=64,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        num_hidden_layers=2, vocab_size=64, rms_norm_eps=1e-5,
        rope_theta=10000.0,
    )
    rng = jax.random.PRNGKey(0)
    params = stack_params([
        init_block_params(k, spec) for k in jax.random.split(rng, 2)
    ])
    hidden = np.asarray(
        jax.random.normal(jax.random.PRNGKey(1), (1, 9, 32)), np.float32
    )

    def serve():
        jax.clear_caches()
        manager = CacheManager(
            num_layers=2, num_pages=16, page_size=4, n_kv_heads=2,
            head_dim=8, dtype=jnp.float32,
        )
        ex = SpanExecutor(params, spec, manager, compute_dtype=jnp.float32)

        async def run():
            async with manager.allocate(1, 16) as handle:
                outs = [ex.prefill(handle, hidden[:, :7])]
                for i in (7, 8):
                    outs.append(ex.decode(handle, hidden[:, i:i + 1]))
            return [np.asarray(o) for o in outs]

        return asyncio.run(run())

    scoped = serve()
    seen = set()
    monkeypatch.setattr(
        jax, "named_scope",
        lambda name: seen.add(name) or contextlib.nullcontext(),
    )
    plain = serve()
    assert seen >= {"norm", "attn_proj", "arena_write", "arena_gather",
                    "attention", "mlp"}, seen
    for a, b in zip(scoped, plain):
        assert a.tobytes() == b.tobytes()


# ------------------------------------------------------------- live e2e run
@pytest.fixture(scope="module")
def tiny_model_dir(tmp_path_factory):
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig(
        hidden_size=64,
        intermediate_size=128,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_hidden_layers=3,
        vocab_size=128,
        max_position_embeddings=256,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    model = LlamaForCausalLM(config).eval().to(torch.float32)
    d = tmp_path_factory.mktemp("tiny_llama_jitwatch")
    model.save_pretrained(d, safe_serialization=True)
    return str(d), config


@pytest.mark.chaos
def test_e2e_steady_state_decode_has_zero_recompiles(
    tiny_model_dir, monkeypatch, tmp_path
):
    """The acceptance run: a live server, warmed at the session's
    buckets, then TWO sessions prefilling and decoding in steady state
    under BBTPU_JITWATCH=1 — the witness must show >=1 warmup compile
    behind a dropped fence, ZERO steady-state recompiles, and hot-path
    host syncs only at the deliberate executor.fetch chokepoint; the
    flushed report must pass the --require gate."""
    import jax.numpy as jnp

    from bloombee_tpu.client.config import ClientConfig
    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.server.block_server import BlockServer
    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer

    monkeypatch.setenv("BBTPU_JITWATCH", "1")
    model_dir, config = tiny_model_dir
    report = tmp_path / "jitwatch.jsonl"

    # earlier tests in a full-suite run may have compiled these very
    # shapes on the executor's module-level jitted functions; drop the
    # in-process executable cache so warmup's compiles actually happen
    # (standalone / chaos.sh runs are fresh processes and unaffected)
    jax.clear_caches()

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()

        def rc():
            return RegistryClient("127.0.0.1", reg.port)

        server = BlockServer(
            model_uid="tiny", start=0, end=3, model_dir=model_dir,
            registry=rc(), compute_dtype=jnp.float32, num_pages=64,
            page_size=4,
        )
        await server.start()
        # warm the buckets the sessions below will hit: batch 1 and 2
        # (two concurrent decodes fuse into one b=2 group dispatch),
        # prompt bucket t=8, and the pb bucket of a <=16-token session
        await server.warmup(batch_sizes=(1, 2), prefill_tokens=8)
        snap = jitwatch.snapshot()
        assert snap["fenced"] is True
        assert snap["warmup_compiles"] >= 1, snap

        cfg = ClientConfig(use_push=False)
        model = DistributedModelForCausalLM.from_pretrained(
            model_dir, rc(), model_uid="tiny", config=cfg
        )
        ids_a = (np.arange(8)[None, :] * 5 + 3) % config.vocab_size
        ids_b = (np.arange(8)[None, :] * 7 + 1) % config.vocab_size

        async def session(input_ids):
            # max length 16 keeps the session inside the warmed page
            # bucket (ceil(17/4) pages -> pb 8 would be a fresh compile)
            async with model.inference_session(16, 1) as sess:
                out = await sess.step(
                    model.embed(input_ids), ids=input_ids
                )
                for _ in range(4):
                    logits = model.logits(out[:, -1:])[:, 0]
                    nxt = np.argmax(logits, axis=-1).astype(
                        input_ids.dtype
                    )[:, None]
                    out = await sess.step(model.embed(nxt), ids=nxt)

        await asyncio.gather(session(ids_a), session(ids_b))

        # the counters also ride rpc_info (BB006 surfacing)
        from bloombee_tpu.wire.rpc import connect

        conn = await connect("127.0.0.1", server.port)
        info, _ = await conn.call("rpc_info", {})
        assert info["xla_compiles"] >= 1
        assert info["steady_state_recompiles"] == 0, info
        await conn.close()

        await server.stop()
        await reg.stop()

    asyncio.run(run())

    snap = jitwatch.snapshot()
    assert snap["warmup_compiles"] >= 1
    assert snap["steady_state_recompiles"] == 0, [
        c for c in snap["compiles"] if c["phase"] == "steady"
    ]
    # every hot-path sync went through the one deliberate chokepoint
    assert set(snap["host_syncs"]) <= {"executor.fetch"}, snap["host_syncs"]

    # the flushed report passes the zero-steady-state-recompile gate
    jitwatch.flush(str(report))
    assert jitwatch._main([str(report), "--require"]) == 0
    # under scripts/chaos.sh the same line feeds the entry's gate (the
    # autouse reset leaves nothing for the atexit flush to double-write)
    jitwatch.flush()


@pytest.mark.chaos
def test_e2e_served_steps_grow_host_spans_by_the_right_counts(
    tiny_model_dir, monkeypatch
):
    """A live server under BBTPU_JITWATCH=1: a chunked prefill of two
    chunks, then one decode step, read through rpc_info["host_spans"] and
    rpc_info["worker"] before and after each. Every boundary of the
    served step shows up the right number of times, and the wire's
    t_compute_ms is the dispatch and fetch spans' own durations."""
    import jax.numpy as jnp

    from bloombee_tpu.client.config import ClientConfig
    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.server.block_server import BlockServer
    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer
    from bloombee_tpu.wire.rpc import connect

    monkeypatch.setenv("BBTPU_JITWATCH", "1")
    model_dir, config = tiny_model_dir

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()

        def rc():
            return RegistryClient("127.0.0.1", reg.port)

        server = BlockServer(
            model_uid="tiny", start=0, end=3, model_dir=model_dir,
            registry=rc(), compute_dtype=jnp.float32, num_pages=64,
            page_size=4, prefill_chunk=4,
        )
        await server.start()
        conn = await connect("127.0.0.1", server.port)

        async def info():
            got, _ = await conn.call("rpc_info", {})
            return got["host_spans"], got["worker"]

        def grew(before, after, name):
            return after[name]["n"] - before.get(name, {"n": 0})["n"]

        model = DistributedModelForCausalLM.from_pretrained(
            model_dir, rc(), model_uid="tiny",
            config=ClientConfig(use_push=False),
        )
        ids = (np.arange(8)[None, :] * 5 + 3) % config.vocab_size
        async with model.inference_session(16, 1) as sess:
            spans0, worker0 = await info()
            out = await sess.step(model.embed(ids), ids=ids)
            spans1, worker1 = await info()
            # an 8-token prompt in chunks of 4: two queue tasks, each one
            # pack, one jitted span step; ONE fetch of both chunks' rows
            for name in ("bbtpu.enqueue", "bbtpu.task", "bbtpu.dispatch",
                         "bbtpu.pack", "bbtpu.h2d",
                         "bbtpu.jit.span_step_packed", "bbtpu.slice"):
                assert grew(spans0, spans1, name) == 2, (name, spans1)
            assert grew(spans0, spans1, "bbtpu.commit") == 1  # last chunk
            assert grew(spans0, spans1, "bbtpu.fetch") == 1
            assert worker1["tasks"] - worker0["tasks"] == 2

            nxt = np.argmax(
                model.logits(out[:, -1:])[:, 0], axis=-1
            ).astype(ids.dtype)[:, None]
            await sess.step(model.embed(nxt), ids=nxt)
            spans2, worker2 = await info()
            for name in ("bbtpu.enqueue", "bbtpu.task", "bbtpu.dispatch",
                         "bbtpu.pack", "bbtpu.h2d",
                         "bbtpu.jit.span_step_packed", "bbtpu.slice",
                         "bbtpu.fetch"):
                assert grew(spans1, spans2, name) == 1, (name, spans2)
            assert worker2["tasks"] - worker1["tasks"] == 1
            # the wire's numbers ARE the spans' durations: one clock pair
            t = sess.timings[-1]
            assert t["tokens"] == 1
            d_dispatch = (spans2["bbtpu.dispatch"]["total_ms"]
                          - spans1["bbtpu.dispatch"]["total_ms"])
            d_fetch = (spans2["bbtpu.fetch"]["total_ms"]
                       - spans1["bbtpu.fetch"]["total_ms"])
            assert t["span_compute_ms"][0] == pytest.approx(
                d_dispatch + d_fetch, abs=0.01
            )
        # the worker's account covers its wall time
        total = sum(worker2[k] for k in ("starved_ms", "hop_ms", "busy_ms"))
        assert total > 0 and worker2["busy_ms"] >= (
            spans2["bbtpu.task"]["total_ms"] * 0.999
        )
        # the wire codec's synchronous bodies are spans too
        assert spans2["bbtpu.codec.decode"]["n"] >= 1
        assert spans2["bbtpu.codec.encode"]["n"] >= 1
        await conn.close()
        await server.stop()
        await reg.stop()

    asyncio.run(run())


# ------------------------------------------- the task's ledger (host_path)
FULL = [n for n in range(2000) if jitwatch.read_in_full(n)]
PLAIN = [n for n in range(2000) if not jitwatch.read_in_full(n)]


def _run_task(body, **ids):
    """Run `body` as one compute-queue task; returns the closed task span."""
    got = []
    jitwatch.hot_wrap(body, got.append, **ids)()
    return got[0]


def test_task_ledger_keeps_self_time_and_legs_sum_to_the_nanosecond(watch_on):
    """A span's leg gets its duration less what its direct children cover;
    the legs sum to the task's wall, and to its CPU, exactly."""
    import time

    held = {}

    def body():
        with jitwatch.stopwatch("bbtpu.dispatch", session="s") as outer:
            with jitwatch.span("bbtpu.pack") as pack:
                with jitwatch.span("bbtpu.step", kind="decode") as step:
                    sum(range(2000))
                sum(range(2000))
            with jitwatch.span("bbtpu.slice") as cut:
                pass
            time.sleep(0.002)
        held.update(outer=outer, pack=pack, step=step, cut=cut)

    task = _run_task(body, task=FULL[1], **{"class": "decode"})
    spans = task.spans
    assert spans["bbtpu.step"][:3] == [1, held["step"].ns, held["step"].ns]
    assert spans["bbtpu.pack"][2] == held["pack"].ns - held["step"].ns
    assert spans["bbtpu.dispatch"][2] == (
        held["outer"].ns - held["pack"].ns - held["cut"].ns
    )
    assert spans["bbtpu.task"][2] == task.ns - held["outer"].ns
    legs = jitwatch.legs_of(task.spans)
    assert set(legs) == {"unnamed", "bbtpu.pack", "bbtpu.step", "bbtpu.slice"}
    assert legs["unnamed"][0] == (
        spans["bbtpu.task"][2] + spans["bbtpu.dispatch"][2]
    )
    assert sum(w for w, _ in legs.values()) == task.ns
    assert task.full and sum(c for _, c in legs.values()) == task.cpu_ns
    assert legs["unnamed"][0] >= 2_000_000  # the sleep lies under no leg
    # the same spans reached the witness's sums as the task closed
    assert jitwatch.host_spans()["bbtpu.pack"]["n"] == 1
    assert jitwatch.host_spans()["bbtpu.task"]["total_ms"] == round(
        task.ns / 1e6, 3
    )


def test_task_ledger_reads_cpu_beside_wall_and_a_sleep_is_off_cpu(watch_on):
    import time

    def body():
        with jitwatch.span("bbtpu.pack"):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < 0.01:
                pass
        with jitwatch.span("bbtpu.h2d"):
            time.sleep(0.02)

    task = _run_task(body, task=FULL[2])
    assert task.full
    # a span's CPU pair is read inside its wall pair; a SELF time is a
    # difference of such pairs, so its CPU may pass its wall by the clocks'
    # own cost (microseconds), never by more
    for name, (_, wall, own, own_cpu) in task.spans.items():
        assert 0 <= own <= wall and 0 <= own_cpu <= own + 50_000, (
            name, task.spans)
    assert task.cpu_ns <= task.ns
    _, _, busy, busy_cpu = task.spans["bbtpu.pack"]
    _, _, slept, slept_cpu = task.spans["bbtpu.h2d"]
    assert busy_cpu >= 0.5 * busy  # spinning: on the CPU
    assert slept >= 20_000_000 and slept_cpu <= 0.1 * slept  # off it


def test_one_task_in_32_is_read_in_full(watch_on, monkeypatch):
    """`read_in_full` picks about one task number in 32, spread so that no
    short period meets it; only such a task reads the CPU clock (a pair a
    span) and asks the device at a launch, and the account keeps the wall
    of THOSE tasks beside their CPU."""
    import time

    from bloombee_tpu.server.compute_queue import _WorkerAccount

    assert jitwatch.read_in_full(0)  # a task nobody numbered
    assert 40 <= len(FULL) <= 90  # of 2000
    for period in (2, 3, 4, 5, 8, 16, 32):
        assert len({n % period for n in FULL}) == period, period

    reads, asked = [], []
    real = time.thread_time_ns

    class _Clock:
        perf_counter_ns = staticmethod(time.perf_counter_ns)

        @staticmethod
        def thread_time_ns():
            reads.append(1)
            return real()

    monkeypatch.setattr(jitwatch, "time", _Clock)

    def body():
        with jitwatch.stopwatch("bbtpu.dispatch"):
            with jitwatch.span("bbtpu.pack"):
                sum(range(20000))
            jitwatch.launch(lambda: asked.append(1) or True)
            with jitwatch.region("span_step_packed", "b1,t1,p4"):
                pass

    account = _WorkerAccount()
    numbers = PLAIN[:30] + FULL[1:3]
    for number in numbers:
        del reads[:]
        account.wrap(body, 0, task=number, kinds="decode1")()
        # four spans, a pair each, or none
        assert len(reads) == (8 if jitwatch.read_in_full(number) else 0)
    assert len(asked) == 2
    rec = account.host_path()["decode"]
    assert (rec["n"], rec["full_n"]) == (32, 2)
    assert (rec["launches"], rec["launches_on_idle"]) == (2, 2)
    assert 0 < rec["cpu_ms"] <= rec["cpu_wall_ms"] < 0.2 * rec["wall_ms"]
    pack = rec["legs"]["bbtpu.pack"]
    assert 0 < pack["cpu_wall_ms"] < 0.2 * pack["wall_ms"]  # 2 tasks of 32
    assert 0.5 * pack["cpu_wall_ms"] <= pack["cpu_ms"] <= (
        pack["cpu_wall_ms"] + 0.05)
    assert rec["jit_idle_ms"] <= rec["legs"]["jit_call"]["cpu_wall_ms"] + 1e-6
    walls = sum(v["wall_ms"] for v in rec["legs"].values())
    assert walls == pytest.approx(rec["wall_ms"], abs=1e-5)


def test_a_span_on_another_thread_adds_nothing_to_the_task(watch_on):
    import threading

    def elsewhere():
        with jitwatch.span("bbtpu.fetch") as sp:
            pass
        assert sp._task is None and sp.cpu_ns == 0

    def body():
        t = threading.Thread(target=elsewhere)
        t.start()
        t.join()

    task = _run_task(body, task=FULL[1])
    assert set(task.spans) == {"bbtpu.task"}
    assert jitwatch.host_spans()["bbtpu.fetch"]["n"] == 1
    # and a span outside any task reads no CPU clock
    with jitwatch.span("bbtpu.pack") as sp:
        pass
    assert sp._task is None and sp.cpu_ns == 0


def test_jit_regions_fold_to_one_leg_and_carry_the_launch_bit(watch_on):
    """Every `bbtpu.jit.*` is the leg `jit_call`; `launch` says what the
    device was doing, the next region carries it as the id `device`, and
    the task splits the leg's wall by it."""
    regions = []

    def body():
        jitwatch.launch(lambda: True)
        with jitwatch.region("span_step_packed", "b1,t1,p4") as reg:
            pass
        regions.append(reg)
        jitwatch.launch(bool, 0)
        with jitwatch.region("span_step_ragged", "r8,s2,p4") as reg:
            pass
        regions.append(reg)
        with jitwatch.region("decode_loop", "b1,n4,p4") as reg:
            pass  # nobody said: counted in the leg, in no launch
        regions.append(reg)

    task = _run_task(body, task=FULL[1], kinds="chunkm+decode1")
    idle, busy, unsaid = regions
    assert idle.ids["device"] == "idle" and busy.ids["device"] == "busy"
    assert "device" not in unsaid.ids
    assert "," not in idle.ids["device"] and ";" not in idle.ids["device"]
    assert task.launches == [2, 1, idle.ns, busy.ns]
    legs = jitwatch.legs_of(task.spans)
    assert set(legs) == {"unnamed", "jit_call"}
    assert legs["jit_call"][0] == idle.ns + busy.ns + unsaid.ns


@pytest.mark.parametrize("ids, kind", [
    ({"class": "decode"}, "decode"),
    ({"class": "prefill"}, "chunk"),
    ({"class": ""}, "other"),
    ({}, "other"),
    ({"kinds": "decode1"}, "decode"),
    ({"kinds": "chunkm"}, "chunk"),
    ({"kinds": "chunkm+decode1"}, "fused"),
    ({"kinds": "chunkm+decode1+tree"}, "fused"),
    ({"kinds": "tree"}, "other"),
    ({"kinds": "decode1+tree"}, "other"),
])
def test_task_kind_by_class_and_kinds(ids, kind):
    from bloombee_tpu.server.compute_queue import task_kind

    assert task_kind(ids) == kind


class _NeverAsked:
    """An arena slab that fails the test if anybody asks about it."""

    size = 8

    def is_ready(self):
        raise AssertionError("is_ready() asked with the witness off")


def test_witness_off_contract_no_cpu_clock_no_is_ready_no_account(monkeypatch):
    """BBTPU_JITWATCH unset: `hot_wrap(fn) is fn`, the account wraps
    nothing and `host_path` is {}, no span reads the thread's CPU clock
    (nor does a stopwatch), and a dispatch never asks the arena whether it
    is ready."""
    from bloombee_tpu.runtime.executor import SpanExecutor
    from bloombee_tpu.server.compute_queue import ComputeQueue, _WorkerAccount

    monkeypatch.delenv("BBTPU_JITWATCH", raising=False)

    class _Clock:
        perf_counter_ns = staticmethod(jitwatch.time.perf_counter_ns)

        @staticmethod
        def thread_time_ns():
            raise AssertionError("thread_time_ns read with the witness off")

    monkeypatch.setattr(jitwatch, "time", _Clock)

    def fn():
        with jitwatch.stopwatch("bbtpu.dispatch") as sw:
            with jitwatch.span("bbtpu.pack"):
                jitwatch.launch(_NeverAsked().is_ready)
                with jitwatch.region("span_step_packed", "b1,t1,p4"):
                    pass
        return sw.ns

    assert jitwatch.hot_wrap(fn, lambda task: None, task=1) is fn
    account = _WorkerAccount()
    assert account.wrap(fn, 0, task=1, kinds="decode1") is fn
    assert fn() > 0
    assert account.host_path() == {}

    async def empty():
        return ComputeQueue().host_path()

    assert asyncio.run(empty()) == {}
    result, used = SpanExecutor._dispatch(
        SpanExecutor, lambda kernel: "out", False, {"k": _NeverAsked()},
        "test",
    )
    assert (result, used) == ("out", False)
    assert jitwatch.host_spans() == {}


def test_witness_on_a_dispatch_asks_the_first_slab_that_holds_bytes(watch_on):
    """With the witness on `_dispatch` asks the arena it donates, once,
    before the call; a K/V arena of no rows asks the state arena."""
    from bloombee_tpu.runtime.executor import SpanExecutor

    asked = []

    class _Slab:
        def __init__(self, name, size, ready):
            self.name, self.size, self.ready = name, size, ready

        def is_ready(self):
            asked.append(self.name)
            return self.ready

    def run(_kernel):
        with jitwatch.region("span_step_packed", "b1,t1,p4") as reg:
            pass
        return reg

    def body():
        arenas = (
            {"k": _Slab("k", 8, False), "v": _Slab("v", 8, False)},
            {"k": _Slab("k0", 0, True), "v": _Slab("v0", 0, True),
             "state": _Slab("state", 4, True)},
        )
        return [SpanExecutor._dispatch(SpanExecutor, run, False, a, "test")[0]
                for a in arenas]

    got = []
    jitwatch.hot_wrap(lambda: got.extend(body()), task=FULL[1])()
    assert asked == ["k", "state"]
    assert [r.ids["device"] for r in got] == ["busy", "idle"]
