"""Streaming zero-copy wire path: off-loop codec pipeline, vectored
framing, negotiated compression.

Covers the PR-18 wire rebuild: the codec matrix across every wire dtype x
{raw, zstd, zlib} x byte-split (including the pure-numpy fallback when
the native byte_split_lib is absent), read-only zero-copy deserialize
views, lean-meta compat defaults, vectored frame buffers, per-connection
codec negotiation against new and legacy peers (both directions), stream
ordering under the off-loop pipeline, and codec-failure isolation. The
chaos-marked e2e at the bottom is the CODEC matrix entry's workload
(scripts/chaos.sh): a real swarm decode, every frame forced through the
codec pool, token-identical to HF greedy under seeded delay+reset faults.
"""

import asyncio
import struct

import ml_dtypes
import numpy as np
import pytest

import bloombee_tpu.native as native_mod
from bloombee_tpu.wire import faults, pipeline as pipeline_mod
from bloombee_tpu.wire.pipeline import CodecPipeline
from bloombee_tpu.wire.rpc import (
    RpcError,
    RpcServer,
    _encode_frame,
    _frame_buffers,
    connect,
)
from bloombee_tpu.wire.tensor_codec import (
    LEGACY_WIRE_CODECS,
    TensorMeta,
    deserialize_tensor,
    register_codec,
    serialize_tensor,
    supported_codecs,
    unregister_codec,
)

WIRE_DTYPES = [
    np.float32, np.float16, ml_dtypes.bfloat16, np.int32, np.int64,
    np.uint8, np.bool_, np.float64,
]


def _u8(arr):
    """Comparable view for dtypes numpy can't compare natively (bf16)."""
    return arr.view(np.uint8)


@pytest.fixture(autouse=True)
def no_leaked_plan():
    yield
    faults.set_plan(None)


@pytest.fixture
def force_compression(monkeypatch):
    """Drop the size/gain gates so the matrix below exercises every codec
    on small arrays (the gates themselves are covered in test_wire.py)."""
    monkeypatch.setenv("BBTPU_MIN_COMPRESS_BYTES", "0")
    monkeypatch.setenv("BBTPU_MIN_COMPRESS_GAIN", "-1000000000")


# --------------------------------------------------- codec roundtrip matrix
@pytest.mark.parametrize("codec", ["raw", "zstd", "zlib"])
@pytest.mark.parametrize("dtype", WIRE_DTYPES)
def test_codec_roundtrip_matrix(dtype, codec, force_compression):
    """Every wire dtype through every built-in codec; 2-byte dtypes take
    the byte-split plane layout whenever a compressor is chosen."""
    if codec not in supported_codecs():
        pytest.skip(f"{codec} not available in this environment")
    rng = np.random.default_rng(5)
    arr = (rng.integers(0, 4, size=(7, 33)) * 3).astype(dtype)
    if codec == "raw":
        meta, payload = serialize_tensor(arr, compression=False)
        assert meta.codec == "raw" and not meta.byte_split
    else:
        meta, payload = serialize_tensor(arr, allowed=frozenset({codec}))
        assert meta.codec == codec
        assert meta.byte_split == (np.dtype(dtype).itemsize == 2)
    out = deserialize_tensor(meta, payload)
    assert out.dtype == arr.dtype and out.shape == arr.shape
    np.testing.assert_array_equal(_u8(out), _u8(arr))


@pytest.mark.parametrize("dtype", [np.float16, ml_dtypes.bfloat16])
def test_byte_split_pure_numpy_fallback(dtype, force_compression,
                                        monkeypatch):
    """Without the native byte_split_lib the numpy plane transpose must
    produce the SAME wire bytes (the fallback is a layout contract, not a
    best-effort): payloads from either implementation cross-decode."""
    rng = np.random.default_rng(6)
    arr = rng.normal(size=(65, 17)).astype(dtype)
    meta_native, payload_native = serialize_tensor(
        arr, allowed=frozenset({"zlib"})
    )
    monkeypatch.setattr(native_mod, "byte_split_lib", lambda: None)
    meta_fb, payload_fb = serialize_tensor(arr, allowed=frozenset({"zlib"}))
    assert meta_fb.codec == "zlib" and meta_fb.byte_split
    assert bytes(payload_fb) == bytes(payload_native)
    # fallback decode of a (possibly native-encoded) payload
    out = deserialize_tensor(meta_native, payload_native)
    np.testing.assert_array_equal(_u8(out), _u8(arr))


def test_from_wire_lean_meta_defaults():
    """An older peer's lean meta (dtype+shape only) must not KeyError:
    absent codec means raw bytes, absent byte_split means off."""
    meta = TensorMeta.from_wire({"d": "f32", "s": [2, 3]})
    assert meta.codec == "raw" and meta.byte_split is False
    arr = np.arange(6, dtype=np.float32).reshape(2, 3)
    out = deserialize_tensor(meta, arr.tobytes())
    np.testing.assert_array_equal(out, arr)


def test_deserialize_raw_is_readonly_zero_copy_view():
    """Raw-codec payloads come back as a read-only view over the receive
    buffer — no copy on the hot path; writable=True is the one path that
    still pays it."""
    arr = np.arange(64, dtype=np.float32)
    meta, payload = serialize_tensor(arr, compression=False)
    buf = memoryview(bytes(payload))
    out = deserialize_tensor(meta, buf)
    assert not out.flags.writeable
    assert np.shares_memory(out, np.frombuffer(buf, dtype=np.uint8))
    with pytest.raises((ValueError, RuntimeError)):
        out[0] = 1.0
    w = deserialize_tensor(meta, buf, writable=True)
    assert w.flags.writeable
    assert not np.shares_memory(w, np.frombuffer(buf, dtype=np.uint8))
    w[0] = -1.0  # mutating the copy never touches the receive buffer
    np.testing.assert_array_equal(out, arr)


# ------------------------------------------------------------ frame layout
def test_frame_buffers_vectored_layout_matches_encode_frame():
    """writelines ships _frame_buffers as-is: prefix+header first, then
    every blob object UNCOPIED, and the concatenation is byte-identical
    to the contiguous _encode_frame used by tests/tooling."""
    blobs = [memoryview(b"abcdef"), b"0123456789"]
    header = {"t": "sitem", "id": 7, "meta": {"x": 1}}
    bufs = _frame_buffers(header, blobs)
    assert bufs[1] is blobs[0] and bufs[2] is blobs[1]  # zero-copy payloads
    joined = b"".join(bytes(b) for b in bufs)
    assert joined == _encode_frame(header, blobs)
    total, header_len = struct.unpack("<II", joined[:8])
    assert len(joined) == 4 + total
    assert joined[8 + header_len:] == b"abcdef0123456789"


# ------------------------------------------------------- pipeline scheduling
class _CountingExecutor:
    """Real thread pool that counts submissions (observing the off-loop
    boundary without guessing at timings)."""

    def __init__(self):
        import concurrent.futures

        self.pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)
        self.submits = 0

    def submit(self, fn, *args):
        self.submits += 1
        return self.pool.submit(fn, *args)


def test_pipeline_inline_threshold_skips_executor(monkeypatch):
    """Payloads under BBTPU_WIRE_PIPELINE_INLINE (de)serialize in-line —
    a thread hop costs more than codec work on tiny frames — while bigger
    ones go through the pool."""
    monkeypatch.setenv("BBTPU_WIRE_PIPELINE", "1")
    monkeypatch.setenv("BBTPU_WIRE_PIPELINE_INLINE", "256")
    counting = _CountingExecutor()
    monkeypatch.setattr(pipeline_mod, "codec_executor", lambda: counting)

    async def run():
        pipe = CodecPipeline()
        small = np.zeros(4, np.float32)  # 16 B
        big = np.zeros(4096, np.float32)  # 16 KiB
        await pipe.encode([small], compression=False)
        assert counting.submits == 0
        metas, blobs = await pipe.encode([big], compression=False)
        assert counting.submits == 1
        fut = pipe.decode_submit(
            [serialize_tensor(small, compression=False)[0].to_wire()],
            [small.tobytes()],
        )
        assert fut.done()  # inline decode resolves before any awaiting
        assert counting.submits == 1
        await pipe.decode_wait(metas, blobs)
        assert counting.submits == 2

    asyncio.run(run())
    counting.pool.shutdown()


def test_stream_ordering_under_forced_pipeline(monkeypatch):
    """Mixed-size items (some decoded off-loop, some inline, finishing at
    different times) must arrive in send order: the single drain task is
    the ordering guarantee, not decode completion order."""
    monkeypatch.setenv("BBTPU_WIRE_PIPELINE", "1")
    monkeypatch.setenv("BBTPU_WIRE_PIPELINE_INLINE", "0")
    monkeypatch.setenv("BBTPU_WIRE_PIPELINE_DEPTH", "4")
    N = 40

    async def run():
        async def echo_stream(stream):
            while True:
                item = await stream.recv()
                if item is None:
                    break
                meta, tensors = item
                await stream.send({"seq": meta["seq"]}, [tensors[0]])
            await stream.close()

        server = RpcServer(
            stream_handlers={"s": echo_stream}, host="127.0.0.1"
        )
        await server.start()
        conn = await connect("127.0.0.1", server.port)
        stream = await conn.open_stream("s", {})
        rng = np.random.default_rng(11)
        sent = []
        for i in range(N):
            size = int(rng.choice([4, 64, 20000]))
            arr = rng.normal(size=(size,)).astype(np.float32)
            sent.append(arr)
            await stream.send({"seq": i}, [arr])
        await stream.close()
        got = []
        while True:
            item = await stream.recv()
            if item is None:
                break
            got.append(item)
        assert [m["seq"] for m, _ in got] == list(range(N))
        for (_, tensors), arr in zip(got, sent):
            np.testing.assert_array_equal(tensors[0], arr)
        stats = server.pipeline_stats()
        assert stats["enabled"] and stats["rx_jobs"] >= N
        assert conn.pipeline.stats()["tx_jobs"] >= N
        await conn.close()
        await server.stop()

    asyncio.run(run())


def test_codec_failure_fails_one_call_not_the_connection(monkeypatch):
    """A frame whose payload fails the codec (corruption, peer bug) kills
    that one call/stream — the other multiplexed users keep going."""
    monkeypatch.setenv("BBTPU_WIRE_PIPELINE", "1")
    bad_meta = {"d": "f32", "s": [64], "c": "zstd", "b": False}

    async def run():
        async def echo(meta, tensors):
            return {"n": len(tensors)}, list(tensors)

        async def echo_stream(stream):
            while True:
                item = await stream.recv()
                if item is None:
                    break
                meta, tensors = item
                await stream.send({"seq": meta["seq"]}, list(tensors))
            await stream.close()

        server = RpcServer(
            unary_handlers={"echo": echo},
            stream_handlers={"s": echo_stream},
            host="127.0.0.1",
        )
        await server.start()
        conn = await connect("127.0.0.1", server.port)

        # unary with a garbage zstd payload: the server answers an err
        # frame (decode happens in the handler task, not the read loop)
        rid = next(conn._ids)
        fut = asyncio.get_running_loop().create_future()
        conn._pending[rid] = fut
        await conn._send(
            {"t": "req", "id": rid, "m": "echo", "meta": {},
             "tm": [bad_meta]},
            [b"not zstd at all"],
        )
        with pytest.raises(RpcError):
            await asyncio.wait_for(fut, 10.0)

        # a corrupt sitem fails only its stream (ordered drain path)
        stream = await conn.open_stream("s", {})
        server_conn = next(iter(server._conns))
        client_stream_on_server = None
        for _ in range(100):
            if server_conn._streams:
                client_stream_on_server = next(
                    iter(server_conn._streams.values())
                )
                break
            await asyncio.sleep(0.01)
        assert client_stream_on_server is not None
        await server_conn._send_payload(
            {"t": "sitem", "id": stream.id, "meta": {"seq": 0}}, None
        )
        # hand-corrupt: send a bad payload as if it were a stream item
        await server_conn._send(
            {"t": "sitem", "id": stream.id, "meta": {"seq": 1},
             "tm": [bad_meta]},
            [b"garbage"],
        )
        item = await stream.recv()  # the good item arrives first (ordered)
        assert item is not None and item[0]["seq"] == 0
        with pytest.raises(RpcError):
            await stream.recv()

        # the connection survived both: a normal call still answers
        meta, tensors = await conn.call(
            "echo", {}, [np.arange(4, dtype=np.float32)]
        )
        assert meta["n"] == 1
        np.testing.assert_array_equal(tensors[0], np.arange(4.0))

        await conn.close()
        await server.stop()

    asyncio.run(run())


# ---------------------------------------------------------- codec negotiation
@pytest.fixture
def test_codec():
    """A registered throwaway codec, preferred when negotiated; removed
    again afterwards so no other test sees it."""
    calls = {"c": 0, "d": 0}

    def compress(buf):
        calls["c"] += 1
        return b"T" + bytes(buf)

    def decompress(buf):
        calls["d"] += 1
        assert bytes(buf[:1]) == b"T"
        return bytes(buf[1:])

    register_codec("testc", compress, decompress, prefer=True)
    try:
        yield calls
    finally:
        unregister_codec("testc")


def test_supported_codecs_registry_and_allowlist(test_codec, monkeypatch):
    assert {"raw", "zlib", "testc"} <= supported_codecs()
    monkeypatch.setenv("BBTPU_WIRE_CODECS", "zlib")
    assert supported_codecs() == frozenset({"raw", "zlib"})  # raw always
    monkeypatch.setenv("BBTPU_WIRE_CODECS", "raw")
    assert supported_codecs() == frozenset({"raw"})


def test_unnegotiated_serialize_never_picks_registered_codec(
    test_codec, force_compression
):
    """allowed=None is the pre-negotiation contract: a registered codec —
    even a preferred one — must NOT leak into payloads for peers that
    never advertised it."""
    arr = np.zeros(4096, np.float32)
    meta, _ = serialize_tensor(arr)
    assert meta.codec in LEGACY_WIRE_CODECS
    assert test_codec["c"] == 0
    meta2, payload2 = serialize_tensor(
        arr, allowed=frozenset({"testc", "raw"})
    )
    assert meta2.codec == "testc" and test_codec["c"] == 1
    out = deserialize_tensor(meta2, payload2)
    np.testing.assert_array_equal(out, arr)


def _echo_server(**kw):
    async def echo(meta, tensors):
        return {"ok": True}, [np.ascontiguousarray(t) for t in tensors]

    return RpcServer(unary_handlers={"echo": echo}, host="127.0.0.1", **kw)


def test_negotiation_new_peers_adopt_registered_codec(
    test_codec, force_compression
):
    """new<->new: the codec advert rides the first frames each side sends,
    so the server's reply to the FIRST call — and everything after — uses
    the negotiated preferred codec. Values stay exact."""

    async def run():
        server = _echo_server()
        await server.start()
        conn = await connect("127.0.0.1", server.port)
        arr = np.arange(2048, dtype=np.float32)
        meta, tensors = await conn.call("echo", {"i": 0}, [arr])
        np.testing.assert_array_equal(tensors[0], arr)
        # the req frame carried our advert, so the reply already used the
        # negotiated codec; our request could not (no advert seen yet)
        assert test_codec["c"] >= 1 and test_codec["d"] >= 1
        before = test_codec["c"]
        meta, tensors = await conn.call("echo", {"i": 1}, [arr])
        np.testing.assert_array_equal(tensors[0], arr)
        # second request: the client has seen the server's advert too, so
        # BOTH directions now compress with the test codec
        assert test_codec["c"] >= before + 2
        assert conn.peer_codecs >= {"testc"}
        await conn.close()
        await server.stop()

    asyncio.run(run())


@pytest.mark.parametrize("legacy_side", ["server", "client"])
def test_negotiation_mixed_swarm_degrades_to_legacy(
    legacy_side, test_codec, force_compression
):
    """new<->old in both directions: a legacy peer never advertises (and
    ignores ours), so the registered codec must never appear on the wire
    — both sides fall back to the pre-negotiation contract byte-for-byte,
    and values stay exact."""

    async def run():
        server = _echo_server(legacy_wire=(legacy_side == "server"))
        await server.start()
        conn = await connect(
            "127.0.0.1", server.port,
            legacy_wire=(legacy_side == "client"),
        )
        arr = np.arange(2048, dtype=np.float32)
        for i in range(3):
            meta, tensors = await conn.call("echo", {"i": i}, [arr])
            np.testing.assert_array_equal(tensors[0], arr)
        assert test_codec["c"] == 0 and test_codec["d"] == 0
        if legacy_side == "server":
            # the client saw no advert: still assuming the legacy set
            assert conn.peer_codecs == LEGACY_WIRE_CODECS
            assert not next(iter(server._conns)).pipeline.enabled
        else:
            assert not conn.pipeline.enabled  # legacy emulation: sync codec
        await conn.close()
        await server.stop()

    asyncio.run(run())


# ------------------------------------------------------- chaos e2e (CODEC=1)
@pytest.fixture(scope="module")
def tiny_model_dir(tmp_path_factory):
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig(
        hidden_size=64,
        intermediate_size=128,
        num_attention_heads=4,
        num_key_value_heads=2,
        # 2 layers as two 1-layer spans: every server compiles the SAME
        # span shape, so the swarm pays one trace instead of two
        num_hidden_layers=2,
        vocab_size=128,
        max_position_embeddings=256,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    model = LlamaForCausalLM(config).eval().to(torch.float32)
    # tiny configs default eos_token_id=2: HF greedy would stop the
    # moment argmax lands on token 2, truncating the reference while the
    # swarm generates all max_new_tokens — disable eos stopping so both
    # sides emit the same number of argmax tokens
    model.generation_config.eos_token_id = None
    d = tmp_path_factory.mktemp("tiny_llama_wire")
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model, config


# the three ways a decode step's frames can be scheduled: codec work off
# the loop (default), on the loop (BBTPU_WIRE_PIPELINE=0), and against a
# peer from before the negotiation (sync codec, no advert, ours ignored)
_LEGS = {"on": ("1", False), "off": ("0", False), "legacy": ("1", True)}
_leg_results: dict = {}


def _decode_leg(tiny_model_dir, leg):
    """One greedy decode through a real one-server swarm under seeded wire
    delays; what it generated, what the wire carried, and whether the
    server's accepted connections ran the off-loop pipeline."""
    if leg in _leg_results:
        return _leg_results[leg]
    import jax.numpy as jnp
    import torch

    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.server.block_server import BlockServer
    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer
    from bloombee_tpu.wire.faults import FaultPlan, FaultRule
    from bloombee_tpu.wire.tensor_codec import transport_stats

    pipeline_on, legacy_peer = _LEGS[leg]
    model_dir, hf_model, config = tiny_model_dir
    prompt = np.arange(5)[None, :] % config.vocab_size
    with torch.no_grad():
        ref = hf_model.generate(
            torch.tensor(prompt), max_new_tokens=6, do_sample=False,
            use_cache=True,
        ).numpy()

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()
        srv = BlockServer(
            model_uid="tiny", start=0, end=2, model_dir=model_dir,
            registry=RegistryClient("127.0.0.1", reg.port),
            compute_dtype=jnp.float32, num_pages=64, page_size=4,
        )
        await srv.start()
        srv.rpc.legacy_wire = legacy_peer
        try:
            model = DistributedModelForCausalLM.from_pretrained(
                model_dir, RegistryClient("127.0.0.1", reg.port),
                model_uid="tiny",
            )
            plan = FaultPlan(seed=29)
            plan.add(FaultRule(site="send", action="delay", method="sitem",
                               prob=0.25, delay_s=0.004))
            faults.set_plan(plan)
            before = transport_stats()["tx"]
            async with model.inference_session(16, 1) as session:
                ids = await model.generate(
                    prompt, max_new_tokens=6, session=session,
                )
                pipe = srv.rpc.pipeline_stats()
            after = transport_stats()["tx"]
        finally:
            faults.set_plan(None)
            await srv.stop()
            await reg.stop()
        return {
            "ids": np.asarray(ids),
            "ref": ref,
            "raw_bytes": after["raw_bytes"] - before["raw_bytes"],
            "wire_bytes": after["wire_bytes"] - before["wire_bytes"],
            "pipelined": bool(pipe["enabled"]) and pipe["rx_jobs"] > 0,
        }

    with pytest.MonkeyPatch.context() as mp:
        # enablement is read as a Connection is made: set it while this
        # leg's swarm comes up, and send every frame through the pool
        mp.setenv("BBTPU_WIRE_PIPELINE", pipeline_on)
        mp.setenv("BBTPU_WIRE_PIPELINE_INLINE", "0")
        _leg_results[leg] = asyncio.run(run())
    return _leg_results[leg]


@pytest.mark.parametrize("leg", list(_LEGS))
def test_decode_leg_matches_pipeline_on_in_tokens_and_bytes(
    tiny_model_dir, leg
):
    """The pipeline and the negotiation choose where codec work runs and
    which codec a peer is offered, never what is computed or shipped: each
    leg generates HF greedy's tokens and puts the same bytes per token on
    the wire as the default leg, and only the default leg's server ran
    frames through the codec pool."""
    got, on = _decode_leg(tiny_model_dir, leg), _decode_leg(tiny_model_dir, "on")
    np.testing.assert_array_equal(got["ids"], got["ref"])
    assert got["raw_bytes"] == on["raw_bytes"] > 0
    assert got["wire_bytes"] == on["wire_bytes"]
    assert got["pipelined"] == (leg == "on")


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_decode_through_forced_codec_pool(tiny_model_dir, monkeypatch):
    # slow: runs inside tier-1 anyway via the chaos gate's CODEC matrix
    # entry (-m chaos) — the direct tier-1 pass skipping it avoids paying
    # the ~15s swarm twice per suite run
    """The CODEC matrix entry's workload: every frame forced through the
    off-loop codec pool (inline threshold 0), decode under seeded delay +
    reset + in-flight corruption faults with the integrity layer on and a
    reroute-capable swarm — tokens must equal the fault-free HF greedy
    reference, and the server must show pipelined frames actually
    flowed."""
    import jax.numpy as jnp
    import torch

    from bloombee_tpu.client.config import ClientConfig
    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.server.block_server import BlockServer
    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer
    from bloombee_tpu.wire.faults import (
        FaultPlan,
        FaultRule,
        _is_span_output_reply,
    )

    monkeypatch.setenv("BBTPU_WIRE_PIPELINE", "1")
    monkeypatch.setenv("BBTPU_WIRE_PIPELINE_INLINE", "0")
    model_dir, hf_model, config = tiny_model_dir

    def _server(registry, start, end, **kw):
        kw.setdefault("compute_dtype", jnp.float32)
        kw.setdefault("num_pages", 64)
        kw.setdefault("page_size", 4)
        kw.setdefault("integrity", True)  # stamp out_digest on replies
        return BlockServer(
            model_uid="tiny", start=start, end=end, model_dir=model_dir,
            registry=registry, **kw,
        )

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()

        def rc():
            return RegistryClient("127.0.0.1", reg.port)

        # every block gets a backup: the corrupt fault can land on ANY
        # span-output reply (head included), and an integrity reroute
        # with no alternate covering server would hang on ban expiry —
        # flaky under the chaos matrix's ambient jitter
        s_a = _server(rc(), 0, 1, throughput=10.0)
        s_b = _server(rc(), 1, 2, throughput=10.0)  # preferred tail
        s_c = _server(rc(), 1, 2, throughput=1.0)  # tail reroute target
        s_d = _server(rc(), 0, 1, throughput=1.0)  # head reroute target
        for s in (s_a, s_b, s_c, s_d):
            await s.start()

        input_ids = np.arange(5)[None, :] % config.vocab_size
        with torch.no_grad():
            ref = hf_model.generate(
                torch.tensor(input_ids), max_new_tokens=6,
                do_sample=False, use_cache=True,
            ).numpy()

        # compose with any ambient chaos.sh plan instead of replacing it,
        # so the matrix entry's DELAY_P jitter stays live under this test
        plan = faults.get_plan() or FaultPlan(seed=13)
        # most-specific first: _pick returns the first matching rule
        plan.add(FaultRule(site="send", action="corrupt", method="sitem",
                           nth=1, count=1,
                           predicate=_is_span_output_reply))
        plan.add(FaultRule(site="send", action="reset", method="sitem",
                           port=s_b.port, nth=3, count=1))
        plan.add(FaultRule(site="send", action="delay", method="sitem",
                           port=s_a.port, delay_s=0.01, nth=1, count=4))
        faults.set_plan(plan)

        # the ban window must stay SHORTER than the recovery-retry horizon:
        # the matrix's ambient corruption can ban BOTH servers covering a
        # block at once, and recovery only succeeds once a ban lapses —
        # 2s bans against ~0.6s of retry backoff is a guaranteed flake
        cfg = ClientConfig(use_push=False, ban_timeout=0.25, ban_max=1.0,
                           max_retries=6, integrity=True)
        model = DistributedModelForCausalLM.from_pretrained(
            model_dir, rc(), model_uid="tiny", config=cfg
        )
        session = model.inference_session(16, 1)
        await session.__aenter__()
        assert s_b.port in {
            sp.span.server_info.port for sp in session._spans
        }
        ids = await model.generate(input_ids, max_new_tokens=6,
                                   session=session)
        np.testing.assert_array_equal(ids, ref)
        # the pipelined path actually carried frames: probe while the
        # session is still open — after reroutes/close a server may hold
        # zero live conns, and stats()["enabled"] is an any() over them
        servers = (s_a, s_b, s_c, s_d)
        stats = [s.rpc.pipeline_stats() for s in servers]
        assert any(p["enabled"] for p in stats), stats
        assert sum(p["rx_jobs"] for p in stats) > 0, stats
        final_ports = {sp.span.server_info.port for sp in session._spans}
        await session.__aexit__(None, None, None)

        # the faults landed
        actions = {(site, act) for site, act, _ in plan.log}
        # the one legitimate excuse for an unfired reset: the matrix's
        # ambient corruption banned the preferred tail before its 3rd
        # send, so the session finished the decode on the reroute target
        # and the port-pinned rule had no traffic left to hit
        assert ("send", "reset") in actions or s_b.port not in final_ports
        assert ("send", "delay") in actions
        assert ("send", "corrupt") in actions
        # the corruption was CAUGHT (digest mismatch -> replay), not
        # silently decoded into the token stream
        assert session.integrity_reroutes >= 1

        faults.set_plan(None)
        for s in servers:
            await s.stop()
        await reg.stop()

    asyncio.run(run())
