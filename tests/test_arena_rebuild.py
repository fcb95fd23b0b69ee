"""Arena rebuild after a kernel failure consumed the donated buffers.

The recovery path can only ever fire after a real kernel failure, so it is
never exercised incidentally — these tests force one (round-4 verdict):

- manager level: epoch bookkeeping, parked sequences surviving a rebuild
  and unparking into the fresh arena with their data intact
- e2e: an injected kernel failure mid-generation consumes the arena; the
  pre-rebuild session's next step gets the typed `session_lost` reply, the
  client replays its token history onto the same (healthy, UNBANNED)
  server and the generation completes token-exact.
- a Pallas kernel failure BEFORE donation (what a compile error is) gives
  way to the dense path once, and is counted where operators look:
  `kernel_fallbacks` next to `warmup_failures` in rpc_info.

Reference analog: a CUDA error kills the reference's runtime process and
its supervisor restarts the whole container (server.py:524-541); here the
server survives and only the affected sessions replay.
"""

import asyncio

import numpy as np
import pytest
import torch

import jax
import jax.numpy as jnp

from bloombee_tpu.client.model import DistributedModelForCausalLM
from bloombee_tpu.kv.cache_manager import CacheManager
from bloombee_tpu.server.block_server import BlockServer
from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer


def make_manager(**kw):
    defaults = dict(
        num_layers=2, num_pages=8, page_size=4, n_kv_heads=1, head_dim=4,
        dtype=jnp.float32,
    )
    defaults.update(kw)
    return CacheManager(**defaults)


def test_rebuild_invalidates_resident_preserves_parked():
    async def run():
        m = make_manager()
        async with m.allocate(1, 8) as h_res, m.allocate(1, 8) as h_park:
            # write 3 tokens into each and commit
            for h in (h_res, h_park):
                slots = m.write_slots(h, 3, commit=True)
                val = float(h.handle_id + 1)
                m.arena["k"] = m.arena["k"].at[:, slots].set(val)
                m.arena["v"] = m.arena["v"].at[:, slots].set(val)
            m.park_sequence(h_park.seq_ids[0])
            epoch0 = m.arena_epoch

            m.rebuild_arena()

            assert m.arena_epoch == epoch0 + 1
            # resident handle: KV gone, epoch stale, table reset
            assert not m.epoch_valid(h_res)
            assert m.table.seq(h_res.seq_ids[0]).l_seq == 0
            # parked handle: survives, re-stamped to the new epoch
            assert m.epoch_valid(h_park)
            # unpark into the FRESH arena restores length and data
            m.ensure_resident(h_park)
            assert m.table.seq(h_park.seq_ids[0]).l_seq == 3
            lens = m.context_lens(h_park)
            assert int(lens[0]) == 3
            val = float(h_park.handle_id + 1)
            pt = m.page_table(h_park, 4)[0]
            page = int(pt[0])
            got = np.asarray(
                m.arena["k"][0, page * m.page_size : page * m.page_size + 3]
            )
            np.testing.assert_allclose(got, val)

    asyncio.run(run())


def test_rebuild_stale_across_two_epochs():
    """A seq parked through rebuild 1 but resident during rebuild 2 goes
    stale; the per-seq stamp must not resurrect it."""

    async def run():
        m = make_manager()
        async with m.allocate(1, 8) as h:
            m.write_slots(h, 2, commit=True)
            m.park_sequence(h.seq_ids[0])
            m.rebuild_arena()
            assert m.epoch_valid(h)
            m.ensure_resident(h)  # back on device
            m.rebuild_arena()
            assert not m.epoch_valid(h)

    asyncio.run(run())


@pytest.fixture(scope="module")
def tiny_model_dir(tmp_path_factory):
    from transformers import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig(
        hidden_size=64,
        intermediate_size=128,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_hidden_layers=2,
        vocab_size=128,
        max_position_embeddings=256,
        rms_norm_eps=1e-5,
        tie_word_embeddings=False,
    )
    torch.manual_seed(5)
    model = LlamaForCausalLM(config).eval().to(torch.float32)
    d = tmp_path_factory.mktemp("tiny_llama_rebuild")
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model, config


def test_e2e_kernel_failure_rebuild_replay_no_ban(
    tiny_model_dir, monkeypatch
):
    """Inject a kernel failure that consumes the arena mid-generation:
    the server must rebuild, the session's next step must get the typed
    session_lost reply, and the client must replay WITHOUT banning the
    healthy server (single-server swarm: a ban would strand recovery
    until ban_timeout) — then finish with the same tokens as a clean run.
    """
    model_dir, hf_model, config = tiny_model_dir

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()
        s1 = BlockServer(
            model_uid="tiny", start=0, end=2, model_dir=model_dir,
            registry=RegistryClient("127.0.0.1", reg.port),
            compute_dtype=jnp.float32, num_pages=64, page_size=4,
        )
        await s1.start()
        model = DistributedModelForCausalLM.from_pretrained(
            model_dir, RegistryClient("127.0.0.1", reg.port),
            model_uid="tiny",
        )
        rng = np.random.default_rng(2)
        input_ids = rng.integers(0, config.vocab_size, size=(1, 4))

        # clean reference run first
        ref_ids = await model.generate(
            input_ids, max_new_tokens=6, server_decode=False
        )

        # arm the failure: the NEXT span step deletes the arena buffers
        # (as a mid-chain donation failure would) and raises — the
        # executor's except path must detect the consumed arena, rebuild,
        # and re-raise; the session's retry then sees session_lost
        from bloombee_tpu.runtime import executor as exec_mod

        real_step = exec_mod.span_step_packed
        state = {"armed": False, "fired": False}

        def exploding_step(*args, **kw):
            if state["armed"]:
                state["armed"] = False
                state["fired"] = True
                for a in jax.tree.leaves(
                    (s1.manager.arena["k"], s1.manager.arena["v"])
                ):
                    a.delete()
                raise RuntimeError("injected kernel failure (test)")
            return real_step(*args, **kw)

        monkeypatch.setattr(exec_mod, "span_step_packed", exploding_step)

        epoch0 = s1.manager.arena_epoch
        async with model.inference_session(16, 1) as sess:
            out = await sess.step(model.embed(input_ids), ids=input_ids)
            cur = np.argmax(model.logits(out[:, -1:])[:, 0], axis=-1)
            toks = [cur]
            state["armed"] = True  # next step blows up mid-chain
            for _ in range(5):
                out = await sess.step(
                    model.embed(cur[:, None]), ids=cur[:, None]
                )
                cur = np.argmax(model.logits(out[:, -1:])[:, 0], axis=-1)
                toks.append(cur)

        assert state["fired"], "injected failure never fired"
        assert s1.manager.arena_epoch == epoch0 + 1, "arena was not rebuilt"
        # the healthy server must NOT have been banned during recovery
        assert not model.manager._bans, (
            f"client banned a healthy server: {model.manager._bans}"
        )
        got = np.concatenate(
            [input_ids, np.stack(toks, axis=1)], axis=1
        )
        np.testing.assert_array_equal(got, ref_ids)

        await s1.stop()
        await reg.stop()

    asyncio.run(run())


def test_kernel_failure_before_donation_falls_back_and_is_counted(
    tiny_model_dir, monkeypatch
):
    """A paged kernel the device refuses raises at call time, before the
    arena is donated: the step reruns on the dense path (same tokens as a
    server that never had the kernel), the kernel path stays off, and the
    swallowed failure shows as kernel_fallbacks in rpc_info — it is a
    bug to report (fatal in chip_smoke.py), never a silent condition."""
    from bloombee_tpu.runtime import executor as exec_mod
    from bloombee_tpu.wire.rpc import connect

    model_dir, _, config = tiny_model_dir
    # the kernel path is open on the CPU only through the test switch
    monkeypatch.setenv("BBTPU_PAGED_INTERPRET", "1")
    monkeypatch.setenv("BBTPU_PAGED_MIN_CONTEXT", "0")
    real_step = exec_mod.span_step_packed
    refused = []

    def refusing_step(*args, **kw):
        if kw.get("use_paged"):
            refused.append(kw["t"])
            raise NotImplementedError("Unsupported cast (injected)")
        return real_step(*args, **kw)

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()
        server = BlockServer(
            model_uid="tiny", start=0, end=2, model_dir=model_dir,
            registry=RegistryClient("127.0.0.1", reg.port),
            compute_dtype=jnp.float32, num_pages=64, page_size=4,
        )
        await server.start()
        model = DistributedModelForCausalLM.from_pretrained(
            model_dir, RegistryClient("127.0.0.1", reg.port),
            model_uid="tiny",
        )
        input_ids = np.random.default_rng(4).integers(
            0, config.vocab_size, size=(1, 4)
        )
        monkeypatch.setenv("BBTPU_PAGED_ATTENTION", "0")
        dense_ids = await model.generate(
            input_ids, max_new_tokens=5, server_decode=False
        )
        assert server.executor.attn_dispatches["paged"] == 0

        monkeypatch.setenv("BBTPU_PAGED_ATTENTION", "1")
        monkeypatch.setattr(exec_mod, "span_step_packed", refusing_step)
        ids = await model.generate(
            input_ids, max_new_tokens=5, server_decode=False
        )
        np.testing.assert_array_equal(ids, dense_ids)
        assert len(refused) == 1  # tried once, then the path stays off
        assert server.executor.kernel_fallbacks == 1
        assert server.executor.attn_dispatches["paged"] == 0

        conn = await connect("127.0.0.1", server.port)
        info, _ = await conn.call("rpc_info", {})
        await conn.close()
        assert info["kernel_fallbacks"] == 1
        assert info["warmup_failures"] == 0
        assert info["warmup_done"] is True  # no start-up task was started
        assert info["attn_dispatches"]["dense"] > 0
        assert info["device"]["platform"] == "cpu"
        assert set(info["native"]) == {"paged_table", "byte_split"}
        await server.stop()
        await reg.stop()

    asyncio.run(run())
