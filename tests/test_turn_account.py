"""A session's turn, cut into named legs on the server's clock (wire/turn.py).

A client and a server in one process, and a clock that moves only where the
test moves it: every leg of two turns has the value worked out by hand, the
first turn carries `open`, a request without the client's entry leaves
`away` unsplit, a retry answered from the record counts once, and an older
peer on either side still serves. Counts and milliseconds of a hand clock
only: no time is measured here."""

import asyncio
import types

import numpy as np
import pytest
import torch

import jax.numpy as jnp

from bloombee_tpu.client.config import ClientConfig
from bloombee_tpu.client.model import DistributedModelForCausalLM
from bloombee_tpu.server.block_server import BlockServer
from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer
from bloombee_tpu.utils import jitwatch
from bloombee_tpu.wire import faults, pipeline, turn
from bloombee_tpu.wire.rpc import connect

PROMPT = (np.arange(13)[None, :] * 5 + 3) % 128
# what the test makes each leg last, in ms
OPEN, FIRST_OTHER, EMBED, ENCODE, TO_SERVER, DECODE = 9, 1.5, 3, 5, 4, 2
FETCH, TO_CLIENT, OTHER, HEAD = 11, 1, 6, 7
STALL = 0.5  # the server's thread waits for the interpreter after a write


@pytest.fixture(scope="module")
def tiny_model_dir(tmp_path_factory):
    from transformers import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=3, vocab_size=128,
        max_position_embeddings=256, rms_norm_eps=1e-5,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    model = LlamaForCausalLM(config).eval().to(torch.float32)
    d = tmp_path_factory.mktemp("tiny_llama_turn")
    model.save_pretrained(d, safe_serialization=True)
    return str(d)


class HandClock:
    def __init__(self):
        self.ns = 5_000_000_000

    def now(self) -> int:
        return self.ns

    def ms(self, ms: float) -> None:
        self.ns += int(ms * 1e6)


@pytest.fixture
def hand(monkeypatch):
    """Every clock a leg is read off stands still unless the test moves it:
    `turn.now_ns`, the stopwatches' clock, and a codec call that lasts
    ENCODE / DECODE ms where it has tensors to work on."""
    clk = HandClock()
    monkeypatch.setattr(turn, "now_ns", clk.now)
    monkeypatch.setattr(
        jitwatch, "time", types.SimpleNamespace(perf_counter_ns=clk.now))
    encode, decode = pipeline.encode_now, pipeline.decode_now

    def slow_encode(tensors, *args, **kw):
        if tensors:
            clk.ms(ENCODE)
        return encode(tensors, *args, **kw)

    def slow_decode(metas, blobs, *args, **kw):
        if blobs:
            clk.ms(DECODE)
        return decode(metas, blobs, *args, **kw)

    monkeypatch.setattr(pipeline, "encode_now", slow_encode)
    monkeypatch.setattr(pipeline, "decode_now", slow_decode)
    return clk


async def _swarm(model_dir, **kw):
    reg = RegistryServer(host="127.0.0.1")
    await reg.start()

    def rc():
        return RegistryClient("127.0.0.1", reg.port)

    server = BlockServer(
        model_uid="tiny", start=0, end=3, model_dir=model_dir,
        registry=rc(), compute_dtype=jnp.float32, num_pages=64,
        page_size=4, **kw,
    )
    await server.start()
    model = DistributedModelForCausalLM.from_pretrained(
        model_dir, rc(), model_uid="tiny", config=ClientConfig())

    async def stop():
        await server.stop()
        await reg.stop()

    return server, model, stop


async def _info(server) -> dict:
    conn = await connect("127.0.0.1", server.port)
    try:
        info, _ = await conn.call("rpc_info", {})
        return info
    finally:
        await conn.close()


class _ToServer(faults.FaultPlan):
    """The request's way to the server: time passes after the frame's header
    was packed and before the server reads its last byte."""

    def __init__(self, clk):
        super().__init__()
        self.clk = clk

    async def on_send(self, conn, header, blobs):
        if blobs:
            self.clk.ms(TO_SERVER)
        return None


def _slow_wire(clk, session, server, model, monkeypatch):
    """Wire both ways, the server's fetch, and the client's own calls, each
    lasting what the table at the top says."""
    conn = session._spans[0].conn
    conn.fault_plan = _ToServer(clk)
    read = conn.reader.readexactly

    async def from_server(n):
        data = await read(n)
        if n > 64:  # a reply's body, not a frame's 8-byte head
            clk.ms(TO_CLIENT)
        return data

    conn.reader.readexactly = from_server
    # after a reply's bytes are with the socket the server's thread may wait
    # for the interpreter while the client already reads them: that time is
    # the turn's `away` (its `wire`), never the `reply` leg before it
    for served in server.rpc._conns:
        drain = served.writer.drain

        async def stalled(drain=drain):
            clk.ms(STALL)
            await drain()

        served.writer.drain = stalled
    fetch = server.executor.fetch

    def slow_fetch(out_dev):
        clk.ms(FETCH)
        return fetch(out_dev)

    monkeypatch.setattr(server.executor, "fetch", slow_fetch)
    embed, logits = model.embed, model.logits

    def slow_embed(ids):
        clk.ms(EMBED)
        return embed(ids)

    def slow_logits(hidden):
        clk.ms(HEAD)
        return logits(hidden)

    monkeypatch.setattr(model, "embed", slow_embed)
    monkeypatch.setattr(model, "logits", slow_logits)


def test_every_leg_of_two_turns_has_the_value_worked_out_by_hand(
    tiny_model_dir, hand, monkeypatch
):
    clk = hand

    async def run():
        server, model, stop = await _swarm(tiny_model_dir, session_lease_s=30)
        try:
            update = model.manager.update

            async def slow_update(*a, **kw):
                clk.ms(OPEN)
                return await update(*a, **kw)

            monkeypatch.setattr(model.manager, "update", slow_update)
            session = model.inference_session(32)
            await session.__aenter__()
            await asyncio.sleep(0.05)  # the server reads the open frame
            _slow_wire(clk, session, server, model, monkeypatch)

            # ---- turn 1: the prompt
            clk.ms(FIRST_OTHER)
            hidden = model._embed_for(session, PROMPT)
            out = await session.step(hidden, ids=PROMPT, reply_tail=1)
            got = (await _info(server))["turn"]
            away = FIRST_OTHER + EMBED + ENCODE + TO_SERVER
            assert got["prefill"] == {
                "n": 1, "away_ms": away, "c_recv_ms": 0.0, "c_head_ms": 0.0,
                "c_other_ms": FIRST_OTHER, "c_embed_ms": EMBED,
                "c_send_ms": ENCODE, "open_ms": OPEN, "wire_ms": TO_SERVER,
                "ingest_ms": DECODE, "served_ms": FETCH, "reply_ms": ENCODE,
                "negative_wire": 0,
            }
            assert got["decode"]["n"] == 0

            # ---- turn 2: one token
            clk.ms(OTHER)
            nxt = np.argmax(
                model._logits_for(session, out[:, -1:])[:, 0], -1)[:, None]
            hidden = model._embed_for(session, nxt)
            await session.step(hidden, ids=nxt)
            got = (await _info(server))["turn"]
            away = (STALL + TO_CLIENT + DECODE + OTHER + HEAD + EMBED
                    + ENCODE + TO_SERVER)
            assert got["decode"] == {
                "n": 1, "away_ms": away, "c_recv_ms": DECODE,
                "c_head_ms": HEAD, "c_other_ms": OTHER, "c_embed_ms": EMBED,
                "c_send_ms": ENCODE, "open_ms": 0.0,
                "wire_ms": STALL + TO_CLIENT + TO_SERVER, "ingest_ms": DECODE,
                "served_ms": FETCH, "reply_ms": ENCODE, "negative_wire": 0,
            }
            assert got["prefill"]["n"] == 1  # and nothing moved there
            assert got["prefill"]["away_ms"] == (
                FIRST_OTHER + EMBED + ENCODE + TO_SERVER)

            # ---- a retry answered from the record counts once
            await session._step_once(
                hidden, True, None, step_id=session.timings[-1]["step"])
            info = await _info(server)
            assert info["steps_deduped"] == 1
            assert info["turn"]["decode"] == got["decode"]

            # ---- a request without the client's entry (an older client)
            # is served, counted, and leaves `away` unsplit
            send = session._spans[0].stream.send

            async def older_client(meta, tensors):
                meta.pop(turn.META_KEY)
                return await send(meta, tensors)

            session._spans[0].stream.send = older_client
            clk.ms(OTHER)
            await session.step(hidden, ids=nxt)
            third = (await _info(server))["turn"]["decode"]
            assert third["n"] == 2
            assert third["away_ms"] > got["decode"]["away_ms"] + OTHER
            for leg in (*turn.CLIENT_LEGS, "wire"):
                assert third[leg + "_ms"] == got["decode"][leg + "_ms"]
            assert third["ingest_ms"] == 2 * DECODE
            await session.__aexit__(None, None, None)
        finally:
            await stop()

    asyncio.run(run())


def test_client_legs_longer_than_away_are_a_wrong_stamp_except_on_a_first_turn(
    hand,
):
    """The server's half alone, on the hand clock. A first turn's `wire` is
    the request's transit less the open frame's: below zero it is cut and
    not counted; on any later turn it is counted."""
    account = turn.TurnAccount()
    turns = turn.ServerTurns(account, "s", hand.now())
    hand.ms(2)
    turns.noted(hand.now())
    turns.arrive(0, "prefill", [0, 0, 1000, 2000, 3000, 500])
    turns.replied(0, hand.now())
    hand.ms(1)
    turns.noted(hand.now())
    turns.arrive(1, "decode", [0, 3000, 0, 0, 0])
    got = account.stats_ms()
    assert got["prefill"]["away_ms"] == 2 and got["prefill"]["wire_ms"] == 0
    assert got["prefill"]["open_ms"] == 0.5
    assert got["prefill"]["negative_wire"] == 0
    assert got["decode"]["away_ms"] == 1 and got["decode"]["wire_ms"] == 0
    assert got["decode"]["negative_wire"] == 1


class _ServerStream:
    """The server's end of the session's stream, with a `send` of the
    test's own; everything else is the real stream's."""

    def __init__(self, stream, send):
        self._stream, self.send = stream, send

    def __getattr__(self, name):
        return getattr(self._stream, name)


@pytest.mark.parametrize("fault", ["send_raises", "typed_error"])
def test_a_step_that_ends_without_its_turns_reply_keeps_the_account_whole(
    tiny_model_dir, hand, monkeypatch, fault
):
    """Under faults, a decode step replayed into the server's handler by
    hand. A send that raises after the turn arrived leaves no turn open, so
    the same step sent again (no lease: nothing is answered from a record)
    is a turn of its own and no later frame of the lost one; a typed error sent
    before the turn arrived (the arena was rebuilt) is where the next
    turn's `away` starts, not the last served reply."""
    clk = hand

    async def run():
        server, model, stop = await _swarm(tiny_model_dir)
        seen = []
        handle = BlockServer._handle_item

        async def keep(self, session, stream, meta, tensors):
            seen.append((session, stream, dict(meta), tensors))
            return await handle(self, session, stream, meta, tensors)

        monkeypatch.setattr(BlockServer, "_handle_item", keep)
        try:
            session = model.inference_session(32)
            await session.__aenter__()
            out = await session.step(
                model._embed_for(session, PROMPT), ids=PROMPT, reply_tail=1)
            nxt = np.argmax(
                model._logits_for(session, out[:, -1:])[:, 0], -1)[:, None]
            await session.step(model._embed_for(session, nxt), ids=nxt)
            srv, stream, meta, tensors = seen[-1]
            before = (await _info(server))["turn"]["decode"]
            assert before["n"] == 1
            sent = []

            async def send(resp, out_t=None):
                sent.append(resp)
                stream.write_ns = clk.now()

            async def broken(resp, out_t=None):
                raise ConnectionResetError("connection lost")

            again = {**meta, turn.META_KEY: [0, 0, 1000, 0, 0]}
            if fault == "send_raises":
                with pytest.raises(ConnectionResetError):
                    await handle(server, srv, _ServerStream(stream, broken),
                                 {**again, "step": meta["step"] + 1}, tensors)
                got = (await _info(server))["turn"]["decode"]
                assert got["n"] == 2 and got["reply_ms"] == before["reply_ms"]
                # the same step again is a turn, not a frame of the lost one
                await handle(server, srv, _ServerStream(stream, send),
                             {**again, "step": meta["step"] + 1}, tensors)
                assert (await _info(server))["turn"]["decode"]["n"] == 3
                assert not sent[-1].get("session_lost")
            else:
                clk.ms(50)  # the client was long away before the error
                monkeypatch.setattr(
                    server.manager, "epoch_valid", lambda handle: False)
                await handle(server, srv, _ServerStream(stream, send),
                             {**again, "step": meta["step"] + 1}, tensors)
                assert sent[-1]["session_lost"]
                assert (await _info(server))["turn"]["decode"] == before
                monkeypatch.undo()
                clk.ms(3)  # error handed to the socket -> next request read
                srv.turns.noted(clk.now())
                await handle(server, srv, _ServerStream(stream, send),
                             {**again, "step": meta["step"] + 2}, tensors)
                got = (await _info(server))["turn"]["decode"]
                assert got["n"] == 2
                assert got["away_ms"] == before["away_ms"] + 3
                assert got["wire_ms"] == before["wire_ms"] + 2
                assert got["negative_wire"] == 0
            await session.close()
        finally:
            await stop()

    asyncio.run(run())


def test_with_the_witness_on_each_turn_is_two_zero_length_spans(
    tiny_model_dir, monkeypatch
):
    """With the witness on each turn is two zero-length spans; with it off
    the sums are kept all the same (the test above)."""
    monkeypatch.setenv("BBTPU_JITWATCH", "1")
    jitwatch.reset()

    async def run():
        server, model, stop = await _swarm(tiny_model_dir)
        try:
            await model.generate(PROMPT, max_new_tokens=4)
            info = await _info(server)
            spans = info["host_spans"]
            turns = info["turn"]
            assert turns["prefill"]["n"] == 1 and turns["decode"]["n"] == 3
            assert spans["bbtpu.turn.arrive"]["n"] == 4
            assert spans["bbtpu.turn.reply"]["n"] == 4
            # (client and server share one event loop here: the client's
            # embed runs before the server has read the open frame. A first
            # turn's `wire` is less the open frame's transit, so it is cut
            # at 0 and never counted as a wrong stamp)
            assert turns["decode"]["negative_wire"] == 0
            assert turns["prefill"]["negative_wire"] == 0
            assert turns["prefill"]["open_ms"] > 0
            assert turns["decode"]["c_head_ms"] > 0
            assert all(rec["served_ms"] > 0 for rec in turns.values())
        finally:
            await stop()

    asyncio.run(run())
    jitwatch.reset()


@pytest.mark.parametrize("older", ["server", "client"])
def test_an_older_peer_on_either_side_still_serves(
    tiny_model_dir, monkeypatch, older
):
    """An older server ignores the entry; an older client sends no entry.
    Both give the tokens of the pair that knows the account."""

    async def tokens():
        server, model, stop = await _swarm(tiny_model_dir)
        try:
            session = model.inference_session(32)
            async with session:
                ids = await model.generate(
                    PROMPT, max_new_tokens=4, session=session)
            return ids, (await _info(server))["turn"]
        finally:
            await stop()

    want, _ = asyncio.run(tokens())
    if older == "server":
        real = BlockServer._run_step

        async def old_run_step(self, session, stream, meta, tensors):
            meta.pop(turn.META_KEY, None)
            return await real(self, session, stream, meta, tensors)

        monkeypatch.setattr(BlockServer, "_run_step", old_run_step)
    else:
        monkeypatch.setattr(
            turn.ClientLegs, "ride", lambda self, meta, stream, start_ns: None)
    got, account = asyncio.run(tokens())
    np.testing.assert_array_equal(got, want)
    # either way `away` stays unsplit: no client leg and no `wire` is made up
    assert account["decode"]["n"] == 3
    assert account["decode"]["away_ms"] > 0
    assert account["decode"]["c_head_ms"] == 0
    assert account["decode"]["wire_ms"] == 0
