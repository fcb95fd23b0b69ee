"""Universal ragged dispatch (ISSUE 17): decode rows + tree-verify rows +
one prefill chunk fused into ONE device step.

Covers the tentpole claims end to end: fused super-batches are numerically
identical to the members dispatched solo (property test over explicit and
randomized kind mixes), a TP-mesh span — previously on the unsupported
list — executes `ragged_group` with parity against the single-chip
executor, per-kind rollback survives a fault injected AFTER the device
step wrote every member's KV (decodes roll back, the chunk truncates, tree
members truncate — then solo replays reproduce the exact pre-fault
outputs), e2e universal traffic (concurrent decode + spec-decode + long
chunked prefill) stays HF-greedy-exact while cross-kind dispatches
actually happen, warmup pre-compiles the unified buckets so steady-state
fused traffic incurs ZERO recompiles (jitwatch --require), declined
ragged configs surface per-reason in rpc_info (BB006), and the kind-aware
group_hint bounds tree gathers by the speculating-session count.
"""

import asyncio
import types

import numpy as np
import pytest
import torch

import jax
import jax.numpy as jnp
import jax.random as jr

from bloombee_tpu.kv.cache_manager import CacheManager
from bloombee_tpu.models.llama.block import init_block_params
from bloombee_tpu.models.spec import ModelSpec
from bloombee_tpu.parallel.serving import make_serving_mesh
from bloombee_tpu.runtime.executor import SpanExecutor
from bloombee_tpu.server.block_server import (
    BlockServer,
    _BatchMember,
    _ChunkMember,
    _Session,
    _TreeMember,
)
from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer
from bloombee_tpu.utils import jitwatch
from bloombee_tpu.utils.tree import stack_params
from bloombee_tpu.wire import faults
from bloombee_tpu.wire.rpc import connect

SPEC = ModelSpec(
    family="llama", hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_hidden_layers=3, vocab_size=64,
)


@pytest.fixture(autouse=True)
def no_leaked_plan():
    yield
    faults.set_plan(None)


def _params():
    return stack_params([
        init_block_params(jr.PRNGKey(i), SPEC)
        for i in range(SPEC.num_hidden_layers)
    ])


def _rand_tree(rng, t):
    """A random linearized speculative tree: node j's parent has a lower
    index, the mask row is ancestors-or-self, depths are rotary offsets."""
    mask = np.zeros((t, t), dtype=bool)
    depth = np.zeros((t,), dtype=np.int32)
    mask[0, 0] = True
    for j in range(1, t):
        p = int(rng.integers(0, j))
        mask[j] = mask[p]
        mask[j, j] = True
        depth[j] = depth[p] + 1
    return mask[None], depth[None]


def _make_member(rng, kind):
    """(hidden, tree_mask, depths) for one member of the given kind."""
    d = SPEC.hidden_size

    def h(t):
        return (rng.standard_normal((1, t, d)) * 0.1).astype(np.float32)

    if kind == "decode":
        return h(1), None, None
    if kind == "tree":
        t = int(rng.choice([3, 5, 7]))
        mask, depth = _rand_tree(rng, t)
        return h(t), mask, depth
    assert kind == "chunk"
    return h(int(rng.integers(3, 7))), None, None


async def _fused_vs_solo(mix, seed, mesh=None, return_fused=False):
    """Allocate one session per member, prefill random contexts, dispatch
    each member SOLO (single-member ragged group — the legacy per-kind
    program), rewind, then dispatch them all FUSED; returns the per-member
    (solo, fused) output pairs."""
    rng = np.random.default_rng(seed)
    manager = CacheManager(
        num_layers=SPEC.num_hidden_layers, num_pages=64, page_size=4,
        n_kv_heads=SPEC.num_key_value_heads, head_dim=SPEC.head_dim,
        dtype=jnp.float32,
    )
    ex = SpanExecutor(
        _params(), SPEC, manager, compute_dtype=jnp.float32, mesh=mesh
    )
    from contextlib import AsyncExitStack

    async with AsyncExitStack() as stack:
        handles = []
        for _ in mix:
            handles.append(await stack.enter_async_context(
                manager.allocate(1, 32, timeout=5.0)
            ))
        hiddens, masks, depths = [], [], []
        for h, kind in zip(handles, mix):
            ctx = int(rng.integers(4, 10))
            ex.prefill(
                h,
                (rng.standard_normal((1, ctx, SPEC.hidden_size)) * 0.1)
                .astype(np.float32),
            )
            hid, tm, dp = _make_member(rng, kind)
            hiddens.append(hid)
            masks.append(tm)
            depths.append(dp)
        snaps = [
            [int(x) for x in manager.context_lens(h)] for h in handles
        ]

        solo = []
        for h, hid, tm, dp, snap in zip(
            handles, hiddens, masks, depths, snaps
        ):
            out, _ = ex.ragged_group(
                [h], [hid], tree_masks=[tm], depths_list=[dp]
            )
            solo.append(np.asarray(out))
            manager.truncate_speculative(h, snap)

        out, _ = ex.ragged_group(
            handles, hiddens, tree_masks=masks, depths_list=depths
        )
        out = np.asarray(out)
        fused = []
        off = 0
        for hid in hiddens:
            t = int(hid.shape[1])
            fused.append(out[off:off + t])
            off += t
        for h, snap in zip(handles, snaps):
            manager.truncate_speculative(h, snap)
        if return_fused:
            return fused
        return list(zip(solo, fused))


# ------------------------------------------------ fused == solo, per kind
@pytest.mark.parametrize("mix", [
    ["decode", "decode", "decode"],        # pure-decode fast path
    ["decode", "chunk"],                   # Sarathi fused iteration
    ["decode", "tree"],                    # cross-kind: NEW to ISSUE 17
    ["tree", "tree", "chunk"],             # trees + chunk: NEW
    ["decode", "decode", "tree", "chunk"], # the full universal mix
], ids=lambda m: "+".join(m))
def test_fused_matches_solo(mix):
    """ONE ragged dispatch over mixed row kinds is numerically identical
    to each member dispatched alone (causal rows ride the tree-mask
    variant as lower-triangular rows — exactly causality)."""
    pairs = asyncio.run(_fused_vs_solo(mix, seed=7))
    for solo, fused in pairs:
        np.testing.assert_allclose(solo, fused, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("seed", [11, 23, 41])
def test_fused_matches_solo_fuzz(seed):
    """Property fuzz: random member-kind mixes (always >= 2 members, at
    most one chunk) stay solo-identical under fusion."""
    rng = np.random.default_rng(seed)
    mix = (
        ["decode"] * int(rng.integers(0, 3))
        + ["tree"] * int(rng.integers(0, 3))
        + (["chunk"] if rng.integers(0, 2) else [])
    )
    while len(mix) < 2:
        mix.append("decode")
    pairs = asyncio.run(_fused_vs_solo(mix, seed=seed))
    for solo, fused in pairs:
        np.testing.assert_allclose(solo, fused, atol=1e-5, rtol=1e-5)


# ------------------------------------------------------ TP-mesh burn-down
def test_tp_mesh_ragged_group_parity():
    """The first unsupported-list entry burned down: a TP-mesh span runs
    the universal ragged dispatch (replicated payload, GSPMD-sharded dense
    attend) with parity against the single-chip executor — including the
    cross-kind decode+tree+chunk mix."""
    mix = ["decode", "tree", "chunk"]
    ref = asyncio.run(_fused_vs_solo(mix, seed=13, return_fused=True))
    tp2 = asyncio.run(_fused_vs_solo(
        mix, seed=13, mesh=make_serving_mesh(2), return_fused=True
    ))
    for a, b in zip(ref, tp2):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_tp_mesh_not_on_unsupported_list():
    manager = CacheManager(
        num_layers=SPEC.num_hidden_layers, num_pages=16, page_size=4,
        n_kv_heads=SPEC.num_key_value_heads, head_dim=SPEC.head_dim,
        dtype=jnp.float32,
    )
    ex = SpanExecutor(
        _params(), SPEC, manager, compute_dtype=jnp.float32,
        mesh=make_serving_mesh(2),
    )
    assert ex.ragged_unsupported(has_tree=False) is None
    assert ex.ragged_unsupported(has_tree=True) is None


# ---------------------------------------------------------- server fixture
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
    d = tmp_path_factory.mktemp("tiny_llama_uniragged")
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model, config


def _hf_greedy(model, input_ids, max_new_tokens):
    with torch.no_grad():
        out = model.generate(
            torch.tensor(input_ids), max_new_tokens=max_new_tokens,
            do_sample=False, use_cache=True,
        )
    return out.numpy()


async def _uni_server(model_dir, reg_port, **kw):
    kw.setdefault("compute_dtype", jnp.float32)
    kw.setdefault("num_pages", 64)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_batch", 8)
    s = BlockServer(
        model_uid="tiny", start=0, end=3, model_dir=model_dir,
        registry=RegistryClient("127.0.0.1", reg_port), **kw,
    )
    await s.start()
    return s


# ------------------------------------------- per-kind rollback, post-write
@pytest.mark.chaos
def test_fault_after_device_write_rolls_back_per_kind(
    tiny_model_dir, monkeypatch
):
    """Inject a fault AFTER the fused device step wrote every member's KV:
    the decode member must roll back, the chunk member truncate to its
    pre-dispatch snapshot, the tree member truncate its rows — and the
    per-kind solo replays must then reproduce EXACTLY the outputs of solo
    dispatches taken from the clean pre-fault state (a rollback that
    leaked one ghost token would shift every replayed position)."""
    model_dir, _, config = tiny_model_dir
    d = config.hidden_size

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()
        s = await _uni_server(
            model_dir, reg.port, mixed_batch=True, spec_batch=True,
            prefill_chunk=4,
        )
        try:
            rng = np.random.default_rng(3)
            async with s.manager.allocate(1, 32, timeout=5.0) as h_dec, \
                    s.manager.allocate(1, 32, timeout=5.0) as h_tree, \
                    s.manager.allocate(1, 32, timeout=5.0) as h_chunk:
                handles = (h_dec, h_tree, h_chunk)
                for h in handles:
                    s.executor.prefill(
                        h,
                        (rng.standard_normal((1, 6, d)) * 0.1)
                        .astype(np.float32),
                    )
                sessions = [
                    _Session(f"rb-{i}", h, 1)
                    for i, h in enumerate(handles)
                ]
                for sess in sessions:
                    sess.adoption_settled = True
                dec_hid = (rng.standard_normal((1, 1, d)) * 0.1).astype(
                    np.float32
                )
                mask, depth = _rand_tree(rng, 5)
                tree_hid = (rng.standard_normal((1, 5, d)) * 0.1).astype(
                    np.float32
                )
                chunk_hid = (rng.standard_normal((1, 4, d)) * 0.1).astype(
                    np.float32
                )
                snaps = [
                    [int(x) for x in s.manager.context_lens(h)]
                    for h in handles
                ]

                # clean-state solo references, state rewound after each
                ref_dec, _ = s._compute_step(
                    sessions[0], h_dec, dec_hid, False, None
                )
                ref_dec = np.asarray(ref_dec)
                s.manager.truncate_speculative(h_dec, snaps[0])
                ref_tree, _ = s._compute_step(
                    sessions[1], h_tree, tree_hid, False, mask, depth
                )
                ref_tree = np.asarray(ref_tree)
                s.manager.truncate_speculative(h_tree, snaps[1])
                ref_chunk, _ = s._compute_prefill_chunk(
                    sessions[2], h_chunk, chunk_hid, True, False
                )
                ref_chunk = np.asarray(ref_chunk)
                s.manager.truncate_speculative(h_chunk, snaps[2])

                # the fused dispatch faults AFTER its device write landed
                orig = s.executor.ragged_group
                calls = {"n": 0}

                def flaky(*a, **kw):
                    out = orig(*a, **kw)
                    calls["n"] += 1
                    raise RuntimeError("injected post-write fault")

                monkeypatch.setattr(s.executor, "ragged_group", flaky)
                members = [
                    _BatchMember(sessions[0], h_dec, dec_hid),
                    _TreeMember(sessions[1], h_tree, tree_hid, mask, depth),
                    _ChunkMember(
                        sessions[2], h_chunk, chunk_hid, True, False
                    ),
                ]
                outs = s._compute_ragged_group(members)
                assert calls["n"] == 1
                assert not any(isinstance(o, Exception) for o in outs)
                got_dec = np.asarray(outs[0][0])
                got_tree = np.asarray(outs[1][0])
                got_chunk = np.asarray(outs[2][0])
                np.testing.assert_allclose(
                    got_dec, ref_dec, atol=1e-5, rtol=1e-5
                )
                np.testing.assert_allclose(
                    got_tree, ref_tree, atol=1e-5, rtol=1e-5
                )
                np.testing.assert_allclose(
                    got_chunk, ref_chunk, atol=1e-5, rtol=1e-5
                )
        finally:
            await s.stop()
            await reg.stop()

    asyncio.run(run())


# --------------------------------------------- e2e universal traffic, HF
def test_e2e_universal_traffic_hf_exact(tiny_model_dir, monkeypatch):
    """Concurrent decode + spec-decode + long chunked prefill on a server
    with BOTH flags on: cross-kind fused dispatches actually happen
    (ragged_cross_kind_dispatches > 0), every stream stays HF-greedy
    exact, and the unified counters ride rpc_info."""
    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.client.speculative import generate_speculative
    from bloombee_tpu.spec.drafter import GreedyTreeDrafter, LocalJaxDraftModel

    model_dir, hf_model, config = tiny_model_dir
    # three continuously-stepping streams co-arrive within ms; a modest
    # window fuses them without long tail stalls when one stream finishes
    monkeypatch.setenv("BBTPU_BATCH_WINDOW_MS", "300")

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()
        s = await _uni_server(
            model_dir, reg.port, mixed_batch=True, spec_batch=True,
            prefill_chunk=4,
        )
        model = DistributedModelForCausalLM.from_pretrained(
            model_dir, RegistryClient("127.0.0.1", reg.port),
            model_uid="tiny",
        )
        rng = np.random.default_rng(29)
        dec_prompt = rng.integers(0, config.vocab_size, size=(1, 5))
        spec_prompt = rng.integers(0, config.vocab_size, size=(1, 6))
        long_ids = (np.arange(24)[None, :] * 5 + 3) % config.vocab_size
        info = None
        try:
            generated = []

            async def decode_loop():
                async with model.inference_session(40, 1) as sess:
                    out = await sess.step(model.embed(dec_prompt))
                    tok = np.argmax(model.logits(out)[:, -1], axis=-1)
                    generated.append(tok)
                    for _ in range(11):
                        out = await sess.step(
                            model.embed(generated[-1][:, None])
                        )
                        generated.append(
                            np.argmax(model.logits(out)[:, -1], axis=-1)
                        )

            async def spec_loop():
                return await generate_speculative(
                    model,
                    GreedyTreeDrafter(
                        LocalJaxDraftModel.from_dir(model_dir),
                        branching=(2, 1),
                    ),
                    spec_prompt, max_new_tokens=8,
                )

            async def long_prefill():
                async with model.inference_session(40, 1) as sess:
                    out = await sess.step(model.embed(long_ids))
                    t = np.argmax(model.logits(out)[:, -1], axis=-1)
                    got = [t]
                    for _ in range(2):
                        out = await sess.step(model.embed(t[:, None]))
                        t = np.argmax(model.logits(out)[:, -1], axis=-1)
                        got.append(t)
                    return np.concatenate(got)

            _, spec_ids, long_tail = await asyncio.gather(
                decode_loop(), spec_loop(), long_prefill()
            )

            # fused dispatches crossed row kinds at least once
            assert s.ragged_group_dispatches > 0
            assert s.ragged_cross_kind_dispatches > 0
            assert s.step_dispatches > 0

            # every stream HF-exact
            ref = _hf_greedy(hf_model, dec_prompt, len(generated))
            np.testing.assert_array_equal(
                np.concatenate(generated), ref[0, dec_prompt.shape[1]:]
            )
            ref = _hf_greedy(
                hf_model, spec_prompt,
                np.asarray(spec_ids).shape[1] - spec_prompt.shape[1],
            )
            np.testing.assert_array_equal(np.asarray(spec_ids), ref)
            ref = _hf_greedy(hf_model, long_ids, 3)
            np.testing.assert_array_equal(
                long_tail, ref[0, long_ids.shape[1]:]
            )

            conn = await connect("127.0.0.1", s.port)
            info, _ = await conn.call("rpc_info", {})
            await conn.close()
        finally:
            await s.stop()
            await reg.stop()
        assert info["ragged_group_dispatches"] == s.ragged_group_dispatches
        assert (
            info["ragged_cross_kind_dispatches"]
            == s.ragged_cross_kind_dispatches
        )
        assert info["ragged_declines"] == {}

    asyncio.run(run())


# --------------------------------------------------- jitwatch steady gate
@pytest.mark.chaos
def test_e2e_universal_zero_steady_recompiles(
    tiny_model_dir, monkeypatch, tmp_path
):
    """Warmup pre-compiles the UNIFIED buckets (packed decode pair,
    decode+chunk, tree pair, decode+tree, decode+tree+chunk); steady-state
    fused traffic constrained to those buckets must incur ZERO recompiles
    and the flushed report must pass jitwatch --require."""
    monkeypatch.setenv("BBTPU_JITWATCH", "1")
    model_dir, _, config = tiny_model_dir
    d = config.hidden_size
    report = tmp_path / "uniragged_jitwatch.jsonl"
    jitwatch.reset()
    # earlier tests may have compiled these shapes in-process; drop the
    # executable cache so warmup's compiles actually happen
    jax.clear_caches()

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()
        s = await _uni_server(
            model_dir, reg.port, mixed_batch=True, spec_batch=True,
            prefill_chunk=4,
        )
        try:
            await s.warmup(batch_sizes=(1, 2), prefill_tokens=8)
            snap = jitwatch.snapshot()
            assert snap["fenced"] is True
            assert snap["warmup_compiles"] >= 1, snap

            # steady state: drive the group runners directly with members
            # shaped exactly like the warmed buckets (ctx 8 prefill, tree
            # t=11 — the default-drafter node count — chunk = the 4-token
            # budget); every bucket tag must hit the warm cache
            rng = np.random.default_rng(1)
            async with s.manager.allocate(1, 36, timeout=5.0) as h_a, \
                    s.manager.allocate(1, 36, timeout=5.0) as h_b, \
                    s.manager.allocate(1, 36, timeout=5.0) as h_c:
                handles = (h_a, h_b, h_c)
                for h in handles:
                    s.executor.prefill(
                        h,
                        (rng.standard_normal((1, 8, d)) * 0.1)
                        .astype(np.float32),
                    )
                sessions = [
                    _Session(f"jw-{i}", h, 1)
                    for i, h in enumerate(handles)
                ]
                for sess in sessions:
                    sess.adoption_settled = True

                def dec(sess, h):
                    return _BatchMember(
                        sess, h,
                        (rng.standard_normal((1, 1, d)) * 0.1)
                        .astype(np.float32),
                    )

                def tree(sess, h):
                    t_i = 11
                    mask = np.tril(np.ones((1, t_i, t_i), dtype=bool))
                    depth = np.arange(t_i, dtype=np.int32)[None, :]
                    return _TreeMember(
                        sess, h,
                        (rng.standard_normal((1, t_i, d)) * 0.1)
                        .astype(np.float32),
                        mask, depth,
                    )

                def chunk(sess, h):
                    return _ChunkMember(
                        sess, h,
                        (rng.standard_normal((1, 4, d)) * 0.1)
                        .astype(np.float32),
                        True, False,
                    )

                groups = [
                    [dec(sessions[0], h_a), dec(sessions[1], h_b)],
                    [dec(sessions[0], h_a), chunk(sessions[2], h_c)],
                    [tree(sessions[0], h_a), tree(sessions[1], h_b)],
                    [dec(sessions[0], h_a), tree(sessions[1], h_b)],
                    [
                        dec(sessions[0], h_a), tree(sessions[1], h_b),
                        chunk(sessions[2], h_c),
                    ],
                ]
                for group in groups:
                    snaps = [
                        [int(x) for x in s.manager.context_lens(m.handle)]
                        for m in group
                    ]
                    outs = s._compute_ragged_group(group)
                    assert not any(
                        isinstance(o, Exception) for o in outs
                    ), outs
                    # rewind speculative members so contexts stay in the
                    # warmed page buckets round after round (decode rows
                    # COMMIT on success — their few extra tokens stay
                    # within the same pow2 page bucket)
                    for m, sn in zip(group, snaps):
                        if not isinstance(m, _BatchMember):
                            s.manager.truncate_speculative(m.handle, sn)
                assert s.ragged_cross_kind_dispatches >= 2
        finally:
            await s.stop()
            await reg.stop()

    asyncio.run(run())

    snap = jitwatch.snapshot()
    assert snap["steady_state_recompiles"] == 0, [
        c for c in snap["compiles"] if c["phase"] == "steady"
    ]
    jitwatch.flush(str(report))
    assert jitwatch._main([str(report), "--require"]) == 0
    # under scripts/chaos.sh the same line feeds the UNIRAGGED entry gate
    jitwatch.flush()
    jitwatch.reset()


# ------------------------------------------------ decline surfacing, hint
def test_ragged_declines_surface_in_rpc_info(tiny_model_dir, monkeypatch):
    """BB006: a span that can't run the ragged path records a per-reason
    decline when the operator asked for fusing, visible in rpc_info."""
    model_dir, _, _ = tiny_model_dir
    monkeypatch.setattr(
        SpanExecutor, "ragged_unsupported",
        lambda self, has_tree=False: "weight offload",
    )

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()
        s = await _uni_server(
            model_dir, reg.port, mixed_batch=True, spec_batch=True,
        )
        try:
            assert s.mixed_batch is False
            assert s.spec_batch is False
            assert s.ragged_declines == {"weight offload": 2}
            conn = await connect("127.0.0.1", s.port)
            info, _ = await conn.call("rpc_info", {})
            await conn.close()
            assert info["ragged_declines"] == {"weight offload": 2}
            assert info["ragged_group_dispatches"] == 0
        finally:
            await s.stop()
            await reg.stop()

    asyncio.run(run())


def test_group_hint_is_kind_aware(tiny_model_dir):
    """The PR-13 early-dispatch extension: a tree-only gather is bounded
    by the speculating-session count (non-speculating sessions can't
    contribute tree rows), a causal gather excludes speculating sessions,
    and with both flags on every open session counts."""
    model_dir, _, _ = tiny_model_dir

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()
        s = await _uni_server(model_dir, reg.port, spec_batch=True)
        try:
            sessions = {
                sid: _Session(sid, None, 1)
                for sid in ("a", "b", "c")
            }
            # a revealed itself non-speculating; b, c still could
            sessions["a"].speculating = False
            s._sessions = sessions
            tree_m = types.SimpleNamespace(key=("tree", None, None, "f32"))
            dec_m = types.SimpleNamespace(
                key=("decode1", None, None, "f32")
            )
            assert s._batch_group_hint() == 3  # no members: total
            assert s._batch_group_hint([tree_m]) == 2  # b, c only
            assert s._batch_group_hint([dec_m]) == 1  # a only
            s.mixed_batch = True  # both flags: every kind fuses
            assert s._batch_group_hint([tree_m]) == 3
        finally:
            s._sessions = {}  # fabricated sessions have no real handles
            await s.stop()
            await reg.stop()

    asyncio.run(run())
