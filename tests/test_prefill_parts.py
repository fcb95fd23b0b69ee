"""A prompt's rows reach the first span in parts (ISSUE 43).

A plain committing prefill to a span that advertises the chunk length it plans
with travels as ONE step in several frames along the sequence axis, and the
span's chunk loop runs each chunk as soon as the part that holds its rows has
arrived. Same chunk plan, same programs, same commit: what a prompt in parts
gives is EQUAL, bit for bit, to what the prompt whole gives, through dense,
recurrent-state and latent-cache spans, with an arbitrary tail and after a
prefix skip; a span that advertises nothing gets the one frame it always
got; a stream cut or a deadline passed between parts rolls the pages back
and the retry yields the parent's tokens; a retry answered from the record
swallows its later parts. Tokens, bits and counts only: no time is measured.
"""

import asyncio
import pathlib
import sys
import types

import numpy as np
import pytest
import torch

import jax.numpy as jnp

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bloombee_tpu.client import session as client_session  # noqa: E402
from bloombee_tpu.client.config import ClientConfig  # noqa: E402
from bloombee_tpu.client.model import DistributedModelForCausalLM  # noqa: E402
from bloombee_tpu.runtime.executor import (  # noqa: E402
    plan_prefill_chunks,
    prefill_chunk_len,
)
from bloombee_tpu.server import block_server  # noqa: E402
from bloombee_tpu.server.block_server import (  # noqa: E402
    BlockServer,
    _StepRows,
)
from bloombee_tpu.server.compute_queue import DeadlineExpired  # noqa: E402
from bloombee_tpu.swarm.data import ServerInfo  # noqa: E402
from bloombee_tpu.swarm.registry import (  # noqa: E402
    RegistryClient,
    RegistryServer,
)
from bloombee_tpu.utils import jitwatch  # noqa: E402
from bloombee_tpu.wire import faults, pipeline, turn  # noqa: E402
from bloombee_tpu.wire.faults import FaultPlan, FaultRule  # noqa: E402
from bloombee_tpu.wire.rpc import RpcError, connect  # noqa: E402

HIDDEN, ITEM, CHUNK = 64, 4, 4  # float32 servers, 4-token chunks
ROW = HIDDEN * ITEM


@pytest.fixture(scope="module")
def tiny_model_dir(tmp_path_factory):
    from transformers import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig(
        hidden_size=HIDDEN, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=3, vocab_size=128,
        max_position_embeddings=256, rms_norm_eps=1e-5,
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    model = LlamaForCausalLM(config).eval().to(torch.float32)
    d = tmp_path_factory.mktemp("tiny_llama_parts")
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model


@pytest.fixture(autouse=True)
def no_leaked_plan():
    yield
    faults.set_plan(None)


@pytest.fixture
def part_of(monkeypatch):
    """Make a part of the tiny prompts `n` chunks of `chunk` rows (the
    constant is sized for prompts of tens of megabytes)."""

    def set_part(n: int, chunk: int = CHUNK, row: int = ROW) -> None:
        monkeypatch.setattr(client_session, "_PART_BYTES", n * chunk * row)

    return set_part


def _hf_greedy(model, input_ids, max_new_tokens):
    with torch.no_grad():
        out = model.generate(
            torch.tensor(input_ids), max_new_tokens=max_new_tokens,
            do_sample=False, use_cache=True,
        )
    return out.numpy()


def _prompt(n: int) -> np.ndarray:
    return (np.arange(n)[None, :] * 5 + 3) % 128


async def _swarm(model_dir, cuts=(0, 3), config=None, **kw):
    """Registry, one server a span of `cuts`, and a client over them."""
    reg = RegistryServer(host="127.0.0.1")
    await reg.start()

    def rc():
        return RegistryClient("127.0.0.1", reg.port)

    kw.setdefault("prefill_chunk", CHUNK)
    servers = [
        BlockServer(
            model_uid="tiny", start=a, end=b, model_dir=model_dir,
            registry=rc(), compute_dtype=jnp.float32, num_pages=64,
            page_size=4, **kw,
        )
        for a, b in zip(cuts, cuts[1:])
    ]
    for s in servers:
        await s.start()
    model = DistributedModelForCausalLM.from_pretrained(
        model_dir, rc(), model_uid="tiny", config=config or ClientConfig(),
    )

    async def stop():
        for s in servers:
            await s.stop()
        await reg.stop()

    return servers, model, stop


def _record_frames(session) -> list:
    """Every frame's meta the session sends to its first span from now on."""
    stream, sent = session._spans[0].stream, []
    send = stream.send

    async def recording(meta, tensors=None, **kw):
        sent.append((dict(meta), [t.shape for t in tensors or []]))
        return await send(meta, tensors, **kw)

    stream.send = recording
    return sent


async def _info(server) -> dict:
    conn = await connect("127.0.0.1", server.port)
    try:
        info, _ = await conn.call("rpc_info", {})
        return info
    finally:
        await conn.close()


def _no_page_held(server) -> None:
    table = server.manager.table
    c = table.counts()
    assert c["free"] + c["referenced"] + c["cached"] == table.num_pages, c
    assert c["referenced"] == 0, c


# ------------------------------------------------ in parts == whole, dense
@pytest.mark.parametrize("chain, rows, mixed", [
    ("one_span", 29, False), ("one_span", 32, False), ("one_span", 29, True),
    ("two_spans_push", 29, False), ("two_spans_relay", 29, False),
])
def test_a_prompt_in_parts_equals_the_prompt_whole(
    tiny_model_dir, part_of, chain, rows, mixed
):
    """Parts of two chunks (8 rows): a prompt of 29 goes as 8 + 8 + 8 + 5 (an
    arbitrary tail), one of 32 as four whole parts. The span's output is
    EQUAL to the whole frame's, row for row and bit for bit, the first
    frame carries the step's meta and `[parts, rows]`, the later ones their
    number alone, only the hop to the FIRST span is cut, and `generate`
    gives HF's greedy tokens."""
    model_dir, hf_model = tiny_model_dir
    prompt = _prompt(rows)
    ref = _hf_greedy(hf_model, prompt, 4)

    async def run():
        cuts = (0, 3) if chain == "one_span" else (0, 2, 3)
        servers, model, stop = await _swarm(
            model_dir, cuts, mixed_batch=mixed,
            config=ClientConfig(use_push=chain != "two_spans_relay"),
        )
        try:
            hidden = model.embed(prompt)
            async with model.inference_session(rows + 4) as sess:
                whole = await sess.step(hidden, ids=prompt)
            assert servers[0].prefill_parts["steps"] == 0
            part_of(2)
            async with model.inference_session(rows + 4) as sess:
                sent = _record_frames(sess)
                parts = await sess.step(hidden, ids=prompt)
            np.testing.assert_array_equal(parts, whole)
            n = -(-rows // 8)
            assert [m.get("part", 0) for m, _ in sent] == list(range(n))
            first = sent[0][0]
            assert first["parts"] == [n, rows] and first["step"] == 0
            assert turn.META_KEY in first and "deadline_s" in first
            assert all(set(m) == {"step", "part"} for m, _ in sent[1:])
            assert [s[0][1] for _, s in sent] == [8] * (n - 1) + [
                rows - 8 * (n - 1)]
            assert servers[0].prefill_parts["steps"] == 1
            assert servers[0].prefill_parts["parts"] == n
            # the next span got the prompt whole, as ever
            assert all(s.prefill_parts["steps"] == 0 for s in servers[1:])
            ids = await model.generate(prompt, max_new_tokens=4)
            np.testing.assert_array_equal(ids, ref)
            assert servers[0].prefill_parts["steps"] == 2
        finally:
            await stop()

    asyncio.run(asyncio.wait_for(run(), 240))


# ------------------------- recurrent state, a latent cache, two cache kinds
def _family(name: str):
    import test_deepseek_v2
    import test_falcon_h1
    import test_qwen3_next

    mod = {"falcon_h1": test_falcon_h1, "deepseek_v2": test_deepseek_v2,
           "qwen3_next": test_qwen3_next}[name]
    return mod.CONFIG, mod._swarm


@pytest.mark.parametrize("family", ["falcon_h1", "deepseek_v2", "qwen3_next"])
def test_state_and_latent_spans_give_the_same_bits_in_parts(
    tmp_path, part_of, family
):
    """A state-space mixer's state, latent attention's page and gated
    DeltaNet's state beside a K/V arena are carried over the chunk
    boundaries of a prompt of 37 (chunks of 16, a tail of 5) whether its
    rows came in one frame or in three: the prefill's rows and three decode
    steps after it are EQUAL."""
    from cellbench import checkpoint

    config, swarm = _family(family)
    checkpoint.write_checkpoint(tmp_path, config, 43)
    ids = np.random.default_rng(43).integers(0, config["vocab_size"], (1, 40))

    async def one(model):
        outs = []
        async with model.inference_session(48, 1) as sess:
            sent = _record_frames(sess)
            outs.append(await sess.step(
                model.embed(ids[:, :37]), ids=ids[:, :37]))
            frames = len(sent)
            for t in range(37, 40):
                outs.append(await sess.step(
                    model.embed(ids[:, t:t + 1]), ids=ids[:, t:t + 1]))
        return outs, frames

    async def run():
        reg, server, model = await swarm(tmp_path, prefill_chunk=16)
        try:
            whole, frames = await one(model)
            assert frames == 1
            part_of(1, chunk=16, row=config["hidden_size"] * 4)
            parts, frames = await one(model)
            assert frames == 3
            for a, b in zip(parts, whole):
                np.testing.assert_array_equal(a, b)
            assert server.prefill_parts["steps"] == 1
            assert server.prefill_parts["parts"] == 3
            assert (await _info(server))["kernel_fallbacks"] == 0
        finally:
            await server.stop()
            await reg.stop()

    asyncio.run(asyncio.wait_for(run(), 280))


# ----------------------------------------------------------- a prefix skip
def test_after_a_prefix_skip_the_suffix_is_what_is_cut(
    tiny_model_dir, part_of
):
    """A warm prefix pool: the probe adopts the prompt's first pages and the
    session sends `hidden[:, skip:]`. That suffix, not the prompt, is cut
    into parts; the adoption settles before the first chunk of the first
    part, and the answer equals the whole suffix's."""
    model_dir, hf_model = tiny_model_dir
    prompt = _prompt(33)
    ref = _hf_greedy(hf_model, prompt, 4)

    async def run(chunks_a_part):
        servers, model, stop = await _swarm(
            model_dir, prefix_cache=True,
            config=ClientConfig(prefix_cache=True),
        )
        server = servers[0]
        try:
            # cold: publishes the first 12 tokens' pages
            await model.generate(prompt[:, :12], max_new_tokens=1)
            part_of(chunks_a_part)
            async with model.inference_session(40) as sess:
                sent = _record_frames(sess)
                out = await sess.step(model.embed(prompt), ids=prompt)
            assert server.manager.prefix_stats()["prefix_hit_tokens"] >= 12
            ids = await model.generate(prompt, max_new_tokens=4)
            np.testing.assert_array_equal(ids, ref)
            await asyncio.sleep(0.2)
            _no_page_held(server)
            return out, sent[1:]  # [0] is the probe
        finally:
            await stop()

    whole, sent = asyncio.run(asyncio.wait_for(run(1 << 20), 240))
    assert len(sent) == 1 and sent[0][0]["prefix_skip"] == 12
    assert "parts" not in sent[0][0] and whole.shape[1] == 21
    parts, sent = asyncio.run(asyncio.wait_for(run(2), 240))
    np.testing.assert_array_equal(parts, whole)
    assert sent[0][0]["prefix_skip"] == 12
    assert sent[0][0]["parts"] == [3, 21]
    assert [m.get("part") for m, _ in sent[1:]] == [1, 2]
    assert [s[0][1] for _, s in sent] == [8, 8, 5]


# ------------------------------------------------------- who gets one frame
@pytest.mark.parametrize("why", [
    "no_chunk_budget", "an_older_server", "speculative", "micro_batches",
    "ragged_replay",
])
def test_no_advert_and_no_plain_prefill_get_one_frame(
    tiny_model_dir, part_of, monkeypatch, why
):
    """The whole frame, as ever: for a span with no chunk budget, for one
    whose advert lacks the field (an older server plans its chunks all the
    same), and to an advertising span for a speculative step, a step in
    batch-axis micro-batches and a ragged replay."""
    model_dir, hf_model = tiny_model_dir
    prompt = _prompt(29)
    part_of(1)
    if why == "an_older_server":
        monkeypatch.setattr(block_server, "prefill_chunk_len", lambda *a: 0)

    async def run():
        servers, model, stop = await _swarm(
            model_dir, prefill_chunk=0 if why == "no_chunk_budget" else CHUNK)
        try:
            info = await _info(servers[0])
            advertised = why in ("speculative", "micro_batches",
                                 "ragged_replay")
            assert info["prefill_chunk"] == (CHUNK if advertised else 0)
            batch = 2 if why == "micro_batches" else 1
            hidden = np.repeat(model.embed(prompt), batch, axis=0)
            sess = model.inference_session(
                40, batch, microbatch=2 if why == "micro_batches" else None)
            async with sess:
                sent = _record_frames(sess)
                if why == "ragged_replay":
                    await sess._step_once(
                        hidden, commit=False, tree_mask=None,
                        commit_lens=[29])
                else:
                    await sess.step(hidden, commit=why != "speculative")
            assert len(sent) == batch
            assert all("parts" not in m and "part" not in m for m, _ in sent)
            assert all(s[0][1] == 29 for _, s in sent)
            assert servers[0].prefill_parts == {
                "steps": 0, "parts": 0, "wait_us": 0}
            if why == "an_older_server":
                assert servers[0].prefill_chunks == 8
                ids = await model.generate(prompt, max_new_tokens=3)
                np.testing.assert_array_equal(
                    ids, _hf_greedy(hf_model, prompt, 3))
        finally:
            await stop()

    asyncio.run(asyncio.wait_for(run(), 240))


def test_parts_that_meet_a_server_that_plans_no_chunks_run_as_one_task(
    tiny_model_dir, part_of, monkeypatch
):
    """An advert can be older than the server's budget: parts that reach a
    span which plans no chunks for the step are collected and computed as
    the one task a whole frame is, with the whole frame's answer."""
    model_dir, _ = tiny_model_dir
    prompt = _prompt(29)

    async def run():
        servers, model, stop = await _swarm(model_dir)
        try:
            hidden = model.embed(prompt)
            async with model.inference_session(40) as sess:
                whole = await sess.step(hidden, ids=prompt)
            part_of(2)
            monkeypatch.setattr(
                BlockServer, "_chunk_spans", lambda self, *a: None)
            before = servers[0].prefill_chunks
            async with model.inference_session(40) as sess:
                sent = _record_frames(sess)
                parts = await sess.step(hidden, ids=prompt)
            assert len(sent) == 4 and servers[0].prefill_parts["parts"] == 4
            assert servers[0].prefill_chunks == before
            np.testing.assert_allclose(parts, whole, rtol=2e-5, atol=2e-6)
        finally:
            await stop()

    asyncio.run(asyncio.wait_for(run(), 240))


def test_an_advert_without_the_field_reads_as_no_parts():
    wire = ServerInfo(prefill_chunk=512).to_wire()
    assert ServerInfo.from_wire(wire).prefill_chunk == 512
    del wire["prefill_chunk"]
    assert ServerInfo.from_wire(wire).prefill_chunk == 0


# ------------------------------------------------------------------- faults
@pytest.mark.chaos
@pytest.mark.parametrize("lease", [30.0, 0.0], ids=["resume", "replay"])
def test_a_stream_cut_between_parts_rolls_back_and_the_retry_gives_the_tokens(
    tiny_model_dir, part_of, lease
):
    """The connection is reset under the client's third part. The span has
    computed the chunks of the first two speculatively: it rolls them back
    (no page stays referenced once the session is gone) and, with a lease,
    parks the session, which the client resumes to send the step again in
    parts under its first id; without one the client rebuilds the chain.
    Either way the tokens are the parent's."""
    model_dir, hf_model = tiny_model_dir
    prompt = _prompt(29)
    ref = _hf_greedy(hf_model, prompt, 5)
    part_of(2)

    async def run():
        servers, model, stop = await _swarm(
            model_dir, session_lease_s=lease, prefix_cache=True,
            config=ClientConfig(resume=True, ban_timeout=0.2, ban_max=0.5),
        )
        server = servers[0]
        try:
            faults.set_plan(FaultPlan(seed=1).add(FaultRule(
                site="send", action="reset", method="sitem",
                port=server.port,
                predicate=lambda h: (h.get("meta") or {}).get("part") == 2,
            )))
            ids = await model.generate(prompt, max_new_tokens=5)
            np.testing.assert_array_equal(ids, ref)
            # the cut step's first parts, then the whole step again
            assert server.prefill_parts["steps"] == 2
            assert 5 <= server.prefill_parts["parts"] <= 6
            assert server.sessions_resumed == (1 if lease else 0)
            assert server.steps_deduped == 0
            await asyncio.sleep(0.3)
            _no_page_held(server)
        finally:
            await stop()

    asyncio.run(asyncio.wait_for(run(), 240))


@pytest.mark.chaos
def test_a_deadline_passed_between_parts_drops_the_step_and_frees_its_pages(
    tiny_model_dir, part_of
):
    """The client's third part is held back past the step's deadline: the
    span stops waiting, counts an expired deadline, rolls the first parts'
    chunks back and answers nothing; the parts that still come are
    swallowed, and the client's retry yields the parent's tokens."""
    model_dir, hf_model = tiny_model_dir
    prompt = _prompt(29)
    ref = _hf_greedy(hf_model, prompt, 4)
    part_of(2)

    async def run():
        servers, model, stop = await _swarm(
            model_dir,
            config=ClientConfig(step_timeout=3.0, ban_timeout=0.2,
                                ban_max=0.5),
        )
        server = servers[0]
        try:
            # every program compiled before the deadline is short
            await model.generate(prompt, max_new_tokens=4)
            before = dict(server.prefill_parts)
            faults.set_plan(FaultPlan(seed=1).add(FaultRule(
                site="send", action="delay", delay_s=6.0, method="sitem",
                port=server.port,
                predicate=lambda h: (h.get("meta") or {}).get("part") == 2,
            )))
            ids = await model.generate(prompt, max_new_tokens=4)
            np.testing.assert_array_equal(ids, ref)
            assert server.deadlines_expired >= 1
            assert server.prefill_parts["steps"] >= before["steps"] + 2
            # two parts of the dropped step, four of its retry
            assert server.prefill_parts["parts"] >= before["parts"] + 6
            await asyncio.sleep(0.3)
            _no_page_held(server)
        finally:
            await stop()

    asyncio.run(asyncio.wait_for(run(), 240))


@pytest.mark.chaos
def test_a_retry_answered_from_the_record_swallows_its_parts(
    tiny_model_dir, part_of
):
    """The prefill is applied and its reply lost in a partition. The
    resumed client sends the step again, in parts, under its first id: the
    span answers the first part from the record, computes nothing twice,
    lets the later parts pass, and the decode steps that follow on the same
    stream give the parent's tokens."""
    model_dir, hf_model = tiny_model_dir
    prompt = _prompt(29)
    ref = _hf_greedy(hf_model, prompt, 5)
    part_of(2)

    async def run():
        servers, model, stop = await _swarm(
            model_dir, session_lease_s=30.0, prefix_cache=True,
            config=ClientConfig(resume=True, step_timeout=3.0),
        )
        server = servers[0]
        try:
            # every program compiled before a reply is waited for
            await model.generate(prompt, max_new_tokens=5)
            was = (dict(server.prefill_parts), server.prefill_chunks,
                   server.step_tokens)
            faults.set_plan(FaultPlan(seed=1).add(FaultRule(
                site="read", action="partition", method="sitem",
                port=server.port, nth=1,
            )))
            ids = await model.generate(prompt, max_new_tokens=5)
            np.testing.assert_array_equal(ids, ref)
            assert server.steps_deduped == 1
            assert server.sessions_resumed == 1
            # computed once: the retry's parts never reached a chunk loop
            assert server.prefill_parts["steps"] == was[0]["steps"] + 1
            assert server.prefill_parts["parts"] == was[0]["parts"] + 4
            assert server.prefill_chunks == was[1] + 8
            assert server.step_tokens == was[2] + 29 + 4
        finally:
            await stop()

    asyncio.run(asyncio.wait_for(run(), 240))


# ------------------------------------------------- the account, the counter
def test_the_turn_account_stays_whole_on_a_step_in_parts(
    tiny_model_dir, part_of, monkeypatch
):
    """One turn, one entry, on the first part: `c_send` is the FIRST part's
    cast and encode (here 5 ms of a hand clock a part, four parts), the
    later parts lie under `served`, no stamp goes wrong and `away` is still
    the client's legs plus the wire."""
    model_dir, _ = tiny_model_dir
    part_of(2)
    ns = [5_000_000_000]
    monkeypatch.setattr(turn, "now_ns", lambda: ns[0])
    monkeypatch.setattr(jitwatch, "time", types.SimpleNamespace(
        perf_counter_ns=lambda: ns[0]))  # the stopwatch of embed and head
    encode = pipeline.encode_now

    def slow_encode(tensors, *args, **kw):
        if tensors:
            ns[0] += 5_000_000
        return encode(tensors, *args, **kw)

    monkeypatch.setattr(pipeline, "encode_now", slow_encode)

    async def run():
        servers, model, stop = await _swarm(model_dir)
        try:
            prompt = _prompt(29)
            async with model.inference_session(40) as sess:
                await asyncio.sleep(0.05)  # the server reads the open frame
                out = await sess.step(
                    model._embed_for(sess, prompt), ids=prompt, reply_tail=1)
                nxt = np.argmax(
                    model._logits_for(sess, out[:, -1:])[:, 0], -1)[:, None]
                await sess.step(model._embed_for(sess, nxt), ids=nxt)
            info = await _info(servers[0])
            got = info["turn"]["prefill"]
            assert info["prefill_parts"]["parts"] == 4
            assert got["n"] == 1 and got["negative_wire"] == 0
            assert got["c_send_ms"] == 5.0
            legs = sum(got[k + "_ms"] for k in turn.CLIENT_LEGS)
            assert got["away_ms"] >= 5.0
            assert got["away_ms"] == pytest.approx(legs + got["wire_ms"])
            # the decode turn's `away` starts at the ONE reply of the step
            # in parts, and its `c_send` is its own frame's
            decode = info["turn"]["decode"]
            assert decode["n"] == 1 and decode["negative_wire"] == 0
            assert decode["c_send_ms"] == 5.0
            assert decode["away_ms"] == pytest.approx(
                sum(decode[k + "_ms"] for k in turn.CLIENT_LEGS)
                + decode["wire_ms"])
        finally:
            await stop()

    asyncio.run(asyncio.wait_for(run(), 240))


def test_rpc_info_counts_what_was_sent_and_the_witness_names_each_part(
    tiny_model_dir, part_of, monkeypatch, capsys
):
    """`rpc_info["prefill_parts"]`: steps that came in more than one part,
    their parts, and the chunk loops' wait for rows still on their way;
    `health --probe` prints the group; with the witness on every part after
    a step's first is one zero-length span `bbtpu.prefill.part`."""
    model_dir, _ = tiny_model_dir
    part_of(2)
    monkeypatch.setenv("BBTPU_JITWATCH", "1")
    jitwatch.reset()

    async def run():
        servers, model, stop = await _swarm(model_dir)
        try:
            await model.generate(_prompt(29), max_new_tokens=2)  # 4 parts
            await model.generate(_prompt(8), max_new_tokens=2)  # one frame
            await model.generate(_prompt(17), max_new_tokens=2)  # 3 parts
            info = await _info(servers[0])
            parts = info["prefill_parts"]
            assert set(parts) == {"steps", "parts", "wait_ms"}
            assert parts["steps"] == 2 and parts["parts"] == 7
            assert parts["wait_ms"] >= 0
            assert info["host_spans"]["bbtpu.prefill.part"]["n"] == 5
            assert info["prefill_chunks"] == 8 + 2 + 5
            proc = await asyncio.create_subprocess_exec(
                sys.executable, "-m", "bloombee_tpu.cli.health", "tiny",
                "--num-blocks", "3", "--registry",
                f"127.0.0.1:{servers[0].registry.port}", "--probe",
                stdout=asyncio.subprocess.PIPE, cwd=str(ROOT),
            )
            out, _ = await proc.communicate()
            assert "prefill_parts steps=2 parts=7 wait_ms=" in out.decode()
        finally:
            await stop()

    asyncio.run(asyncio.wait_for(run(), 240))
    jitwatch.reset()


# ------------------------------------------------------------ the part rule
@pytest.mark.parametrize("cell, chunk, hidden, rows", [
    ("deepseekv2-longctx", 512, 5120, 512),
    ("qwen3next-longctx", 512, 2048, 2048),
    ("falconh1-longdoc", 128, 5120, 768),
    ("mistral7b-longdoc", 128, 4096, 1024),
    ("qwen3moe-longdoc", 128, 2048, 2048),
])
def test_a_part_is_whole_chunks_of_about_the_constants_bytes(
    cell, chunk, hidden, rows
):
    """The rule, at the five cells' widths in bfloat16: the largest multiple
    of the span's chunk that fits the constant, and one chunk where a chunk
    alone is larger. Nothing of it reads a model's name."""
    got = client_session._part_rows(chunk, hidden * 2)
    assert got == rows and got % chunk == 0
    assert got * hidden * 2 <= max(client_session._PART_BYTES,
                                   chunk * hidden * 2)


@pytest.mark.parametrize("budget, cap, want", [
    (0, None, 0), (-1, 8, 0), (1, None, 1), (4, None, 4), (100, None, 64),
    (512, 128, 128), (128, 512, 128),
])
def test_the_advertised_length_is_the_one_the_plan_cuts_with(
    budget, cap, want
):
    assert prefill_chunk_len(budget, cap) == want
    if want:
        spans = plan_prefill_chunks(10 * want + 3, budget, cap)
        assert {e - s for s, e in spans[:-1]} == {want}


# ------------------------------------------------------ the server's reader
class _Parts:
    """A stream that holds what the test put there."""

    def __init__(self, *items):
        self.items = list(items)

    async def recv(self):
        if not self.items:
            await asyncio.sleep(3600)
        return self.items.pop(0)


def _rows(lo: int, hi: int) -> np.ndarray:
    return np.arange(lo, hi, dtype=np.float32)[None, :, None] * np.ones(
        (1, 1, 2), np.float32)


def _step_rows(stream, first=6, total=14, deadline=None):
    counts = {"steps": 0, "parts": 0, "wait_us": 0}
    seq = _StepRows(_rows(0, first), {"step": 7, "parts": [3, total]},
                    stream, deadline, "s", counts)
    return seq, counts


def test_chunks_are_cut_from_the_parts_wherever_the_client_cut_them():
    """A client that cut off the chunk grid is served all the same: a chunk
    inside one part is a view of it, one across parts is joined, and parts
    are read only when a chunk needs them."""

    async def run():
        stream = _Parts(({"step": 7, "part": 1}, [_rows(6, 9)]),
                        ({"step": 7, "part": 2}, [_rows(9, 14)]))
        seq, counts = _step_rows(stream)
        assert (seq.tokens, seq.batch, counts["parts"]) == (14, 1, 1)
        first = await seq.take(0, 4)
        assert first.base is not None and len(stream.items) == 2
        np.testing.assert_array_equal(first, _rows(0, 4))
        np.testing.assert_array_equal(await seq.take(4, 8), _rows(4, 8))
        assert len(stream.items) == 1
        np.testing.assert_array_equal(await seq.take(8, 14), _rows(8, 14))
        np.testing.assert_array_equal(await seq.take(0, 14), _rows(0, 14))
        assert counts == {"steps": 1, "parts": 3,
                          "wait_us": counts["wait_us"]}

    asyncio.run(run())


@pytest.mark.parametrize("item, error", [
    (None, "stream closed between the parts"),
    (({"step": 7, "part": 2}, [_rows(6, 9)]), "expected part 1 of step 7"),
    (({"step": 8, "part": 1}, [_rows(6, 9)]), "expected part 1 of step 7"),
    (({"step": 7, "part": 1}, []), "expected part 1 of step 7"),
    (({"step": 7, "part": 1}, [_rows(6, 16)]), "8 rows to come"),
    (({"step": 7, "part": 1}, [_rows(6, 9).astype(np.float16)]),
     "expected part 1 of step 7"),
])
def test_a_part_that_is_not_the_next_one_fails_the_step(item, error):
    async def run():
        seq, _ = _step_rows(_Parts(item))
        with pytest.raises(RpcError, match=error):
            await seq.take(4, 8)

    asyncio.run(run())


def test_a_part_that_does_not_come_in_time_is_an_expired_deadline():
    from bloombee_tpu.utils import clock

    async def run():
        seq, _ = _step_rows(_Parts(), deadline=clock.monotonic() + 0.05)
        with pytest.raises(DeadlineExpired, match="between the parts"):
            await seq.take(4, 8)

    asyncio.run(run())


@pytest.mark.parametrize("bad", [
    [1, 14], [3, 6], [3], [3.0, 14], "3,14", [True, 14], [3, 14, 1],
])
def test_a_parts_field_that_cannot_be_true_is_refused(bad):
    with pytest.raises(ValueError, match="parts must be"):
        _StepRows(_rows(0, 6), {"step": 1, "parts": bad}, _Parts(), None,
                  "s", {"steps": 0, "parts": 0, "wait_us": 0})
