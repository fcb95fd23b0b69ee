"""Wire protocol: tensor codec roundtrips, RPC unary/stream/push, registry.

Ports the intent of /root/reference/tests/test_lossless_transport.py (codec
roundtrip + gates) plus basic transport-level coverage the reference gets from
hivemind itself.
"""

import asyncio

import ml_dtypes
import numpy as np
import pytest

from bloombee_tpu.swarm.data import ModuleInfo, ServerInfo, ServerState
from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer
from bloombee_tpu.swarm.spans import compute_spans
from bloombee_tpu.wire.rpc import RpcError, RpcServer, connect
from bloombee_tpu.wire.tensor_codec import (
    MIN_COMPRESS_BYTES,
    deserialize_tensor,
    serialize_tensor,
)


@pytest.mark.parametrize(
    "dtype", [np.float32, np.float16, ml_dtypes.bfloat16, np.int32, np.bool_]
)
def test_codec_roundtrip(dtype):
    rng = np.random.default_rng(0)
    arr = rng.normal(size=(33, 257)).astype(dtype)
    meta, payload = serialize_tensor(arr)
    out = deserialize_tensor(meta, payload)
    assert out.dtype == arr.dtype and out.shape == arr.shape
    np.testing.assert_array_equal(
        out.view(np.uint8) if dtype == ml_dtypes.bfloat16 else out,
        arr.view(np.uint8) if dtype == ml_dtypes.bfloat16 else arr,
    )


def test_codec_small_payload_ships_raw():
    arr = np.zeros((10,), np.float32)
    meta, _ = serialize_tensor(arr)
    assert meta.codec == "raw"


def test_codec_compresses_large_redundant_bf16():
    n = MIN_COMPRESS_BYTES  # bytes/2 items -> 2n bytes > threshold
    arr = np.ones((n,), ml_dtypes.bfloat16)
    meta, payload = serialize_tensor(arr)
    assert meta.codec in ("zstd", "zlib") and meta.byte_split
    assert len(payload) < arr.nbytes // 10
    out = deserialize_tensor(meta, payload)
    np.testing.assert_array_equal(out.view(np.uint8), arr.view(np.uint8))


def test_codec_concurrent_threads_roundtrip():
    """The codec pool's workers (and every event loop that encodes inline)
    compress at once. zstandard's context objects are not thread-safe —
    one shared compressor crashed the process under concurrent sessions —
    so each thread must get its own: more threads than cores hammer the
    codec for a bounded time and every payload must round-trip."""
    import os
    import sys
    import threading
    import time

    n_threads = 2 * (os.cpu_count() or 4)
    rng = np.random.default_rng(3)
    # compressible, distinct per thread, above the compression threshold
    arrays = [
        np.repeat(rng.normal(size=(MIN_COMPRESS_BYTES // 64,)), 64)
        .astype(ml_dtypes.bfloat16)
        for _ in range(n_threads)
    ]
    assert serialize_tensor(arrays[0])[0].codec == "zstd"
    deadline = time.monotonic() + 1.5
    rounds = [0] * n_threads
    errors = []

    def work(i):
        try:
            while time.monotonic() < deadline:
                meta, payload = serialize_tensor(arrays[i])
                out = deserialize_tensor(meta, payload)
                if out.tobytes() != arrays[i].tobytes():
                    raise AssertionError(f"thread {i}: payload corrupted")
                rounds[i] += 1
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    old_interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [
            threading.Thread(target=work, args=(i,)) for i in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old_interval)
    assert not errors, errors
    assert all(r > 0 for r in rounds), rounds


def test_codec_incompressible_ships_raw():
    rng = np.random.default_rng(1)
    arr = rng.integers(0, 255, size=(MIN_COMPRESS_BYTES * 2,), dtype=np.uint8)
    meta, payload = serialize_tensor(arr)
    assert meta.codec == "raw" and len(payload) == arr.nbytes


def test_rpc_unary_stream_push():
    async def run():
        got_pushes = []

        async def echo(meta, tensors):
            return {"echo": meta["x"] + 1}, [t * 2 for t in tensors]

        async def stream_handler(stream):
            # double every item until client half-closes, then send a summary
            n = 0
            while True:
                item = await stream.recv()
                if item is None:
                    break
                meta, tensors = item
                n += 1
                await stream.send({"seq": meta["seq"]}, [tensors[0] + 1])
            await stream.send({"done": True, "count": n})
            await stream.close()

        async def on_push(meta, tensors):
            got_pushes.append((meta, tensors))

        server = RpcServer(
            unary_handlers={"echo": echo},
            stream_handlers={"session": stream_handler},
            push_handlers={"note": on_push},
            host="127.0.0.1",
        )
        await server.start()
        conn = await connect("127.0.0.1", server.port)

        # unary with tensors
        meta, tensors = await conn.call(
            "echo", {"x": 41}, [np.arange(8, dtype=np.float32)]
        )
        assert meta["echo"] == 42
        np.testing.assert_array_equal(tensors[0], np.arange(8) * 2.0)

        # unknown method -> RpcError
        with pytest.raises(RpcError):
            await conn.call("nope", {})

        # bidirectional stream
        stream = await conn.open_stream("session", {"model": "m"})
        for i in range(3):
            await stream.send({"seq": i}, [np.full((4,), i, np.float32)])
        await stream.close()
        outs = []
        while True:
            item = await stream.recv()
            if item is None or item[0].get("done"):
                assert item is None or item[0]["count"] == 3
                break
            outs.append(item)
        assert [m["seq"] for m, _ in outs] == [0, 1, 2]
        np.testing.assert_array_equal(outs[2][1][0], np.full((4,), 3.0))

        # push
        await conn.push("note", {"k": "v"}, [np.ones(2, np.float32)])
        await asyncio.sleep(0.05)
        assert got_pushes and got_pushes[0][0]["k"] == "v"

        await conn.close()
        await server.stop()

    asyncio.run(run())


def test_rpc_concurrent_calls_multiplex():
    async def run():
        async def slow(meta, tensors):
            await asyncio.sleep(meta["delay"])
            return {"v": meta["v"]}, []

        server = RpcServer(unary_handlers={"slow": slow}, host="127.0.0.1")
        await server.start()
        conn = await connect("127.0.0.1", server.port)
        r = await asyncio.gather(
            conn.call("slow", {"delay": 0.05, "v": 1}),
            conn.call("slow", {"delay": 0.0, "v": 2}),
        )
        assert [m["v"] for m, _ in r] == [1, 2]
        await conn.close()
        await server.stop()

    asyncio.run(run())


def test_registry_announce_fetch_expire():
    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()
        client = RegistryClient("127.0.0.1", reg.port)

        info_a = ServerInfo(host="127.0.0.1", port=1111, throughput=5.0)
        info_b = ServerInfo(host="127.0.0.1", port=2222, throughput=3.0)
        await client.declare_blocks("model", "A", range(0, 3), info_a, 30.0)
        await client.declare_blocks("model", "B", range(2, 5), info_b, 0.05)

        infos = await client.get_module_infos("model", range(0, 5))
        spans = compute_spans(infos)
        assert (spans["A"].start, spans["A"].end) == (0, 3)
        assert (spans["B"].start, spans["B"].end) == (2, 5)
        assert spans["A"].server_info.throughput == 5.0

        await asyncio.sleep(0.06)  # B's records expire (the failure detector)
        infos = await client.get_module_infos("model", range(0, 5))
        spans = compute_spans(infos)
        assert "B" not in spans and "A" in spans

        # revoke = clean OFFLINE announce
        await client.revoke_blocks("model", "A", range(0, 3))
        infos = await client.get_module_infos("model", range(0, 5))
        assert compute_spans(infos) == {}

        await client.close()
        await reg.stop()

    asyncio.run(run())


def test_compute_spans_skips_offline():
    info = ServerInfo(state=ServerState.JOINING)
    infos = [ModuleInfo(uid="m.0", servers={"X": info})]
    assert compute_spans(infos) == {}


def test_transport_stats_counters():
    """Codec profiling counters (reference lossless_transport profiling
    channels): tx/rx tensor counts, raw vs wire bytes, compression ratio."""
    import numpy as np

    from bloombee_tpu.wire.tensor_codec import (
        deserialize_tensor,
        reset_transport_stats,
        serialize_tensor,
        transport_stats,
    )

    reset_transport_stats()
    big = np.zeros((256, 256), np.float32)  # compressible, above min size
    small = np.ones((4,), np.float32)  # ships raw
    for arr in (big, small):
        meta, blob = serialize_tensor(arr)
        out = deserialize_tensor(meta, blob)
        np.testing.assert_array_equal(out, arr)
    st = transport_stats()
    assert st["tx"]["n"] == 2 and st["rx"]["n"] == 2
    assert st["tx"]["compressed"] == 1  # only the big one
    assert st["tx"]["raw_bytes"] == big.nbytes + small.nbytes
    assert st["tx"]["wire_bytes"] < st["tx"]["raw_bytes"]
    assert 0.0 < st["tx"]["ratio"] < 1.0
    assert st["tx"]["s"] >= 0.0


def test_flow_limiter_adapts():
    """The adaptive push limiter grows under queue pressure with fast sends,
    shrinks under slow sends or failures, and stays within bounds."""
    import asyncio

    from bloombee_tpu.wire.flow import FlowLimiter

    async def drive(lim, n, send_s=0.0, fail=False, waiters=1):
        async def one():
            try:
                async with lim.slot():
                    if send_s:
                        await asyncio.sleep(send_s)
                    if fail:
                        raise OSError("boom")
            except OSError:
                pass

        for _ in range(n):
            await asyncio.gather(*[one() for _ in range(waiters)])

    async def run():
        # queue pressure with instant sends -> limit grows
        lim = FlowLimiter(initial=1, decide_every=4, wait_up_ms=0.0)
        await drive(lim, 16, waiters=4)
        assert lim.limit > 1, lim.limit

        # consecutive failures -> limit shrinks to the floor, never below
        lim2 = FlowLimiter(initial=3, lo=1, decide_every=2)
        await drive(lim2, 32, fail=True)
        assert lim2.limit == 1, lim2.limit

        # slow sends with no waiters -> backpressure shrink
        lim3 = FlowLimiter(
            initial=4, decide_every=2, send_slow_ms=1.0
        )
        await drive(lim3, 8, send_s=0.005)
        assert lim3.limit < 4, lim3.limit

        # concurrent holders must not share timing state: a slow send
        # overlapped by fast ones still registers as slow
        lim4 = FlowLimiter(initial=4, decide_every=1000)

        async def slow():
            async with lim4.slot():
                await asyncio.sleep(0.05)

        async def fast():
            await asyncio.sleep(0.01)  # start after slow() holds its slot
            async with lim4.slot():
                pass

        await asyncio.gather(slow(), fast(), fast(), fast())
        # EWMA saw one 50 ms sample among ~0 ms ones; with alpha=0.2 and
        # the slow sample landing last it must remain clearly visible
        assert lim4.ewma_send_ms > 5.0, lim4.ewma_send_ms

    asyncio.run(run())
