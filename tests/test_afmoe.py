"""AFMoE (Arcee Trinity): window layers with rotary positions among full
layers with none in ONE scan, a sigmoid router whose choice a bias corrects
over the experts a server HOLDS, gated attention between four norms, a dense
layer before sparse ones.

Tiny widths on the CPU, seeded: five layers of the benchmark cell's kinds
(dense; window, window, full, window), 8 routed experts of which this
checkpoint holds 4, window 32. The mathematics under test has ONE plain
copy, the benchmark's family file (cellbench/families/afmoe.py: float32, no
cache, no kernels); everything the program serves (chunks, then single steps
through the paged cache, on the dense path and through the interpreted Pallas
kernels) is held to that file.
"""

import asyncio
import dataclasses
import json
import pathlib
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "tests"))

from bloombee_tpu.kv.cache_manager import CacheManager  # noqa: E402
from bloombee_tpu.models.checkpoint import load_span_params  # noqa: E402
from bloombee_tpu.models.layout import LEAD, split_runs  # noqa: E402
from bloombee_tpu.ops.moe import (  # noqa: E402
    bias_moved,
    moe_mlp,
    reach_fields,
    route_topk,
)
from bloombee_tpu.runtime.executor import SpanExecutor  # noqa: E402
from cellbench import checkpoint, families, reference  # noqa: E402
from test_cellbench_afmoe import TINY_AFMOE as CONFIG  # noqa: E402
from test_chip_compile import v5e  # noqa: E402,F401  (the fixture)

D, LAYERS = CONFIG["hidden_size"], CONFIG["num_hidden_layers"]
FAMILY = families.of(CONFIG)
HELD = tuple(CONFIG["experts_held"])
KERNELS = {"BBTPU_PAGED_INTERPRET": "1", "BBTPU_FLASH_INTERPRET": "1",
           "BBTPU_PAGED_MIN_CONTEXT": "0"}
# float32 throughout at `highest`: what is left is the order of the sums
# (the cache's pages against one pass, a kernel's online softmax, a row's
# experts added in another order)
TOL = dict(rtol=2e-4, atol=2e-5)


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    path = tmp_path_factory.mktemp("tiny_afmoe")
    checkpoint.write_checkpoint(path, CONFIG, 47)
    return path


@pytest.fixture(scope="module")
def span(ckpt):
    return load_span_params(
        str(ckpt), 0, LAYERS, dtype=jnp.float32, experts=HELD)


def _reference_hidden(ckpt, hidden, positions=None, layers=range(LAYERS)):
    """The family file's layers over one sequence's hidden states [T, D]."""
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(hidden)
        pos = jnp.arange(h.shape[0]) if positions is None else positions
        for layer in layers:
            h = FAMILY.layer_forward(
                reference.layer_params(ckpt, CONFIG, layer), CONFIG, h, pos)
        return np.asarray(h)


def _executor(span, pages=64, **kw):
    params, spec = span
    manager = CacheManager(
        LAYERS, pages, 16, spec.num_key_value_heads, spec.head_dim,
        dtype=jnp.float32)
    return SpanExecutor(params, spec, manager, compute_dtype=jnp.float32, **kw)


def _hidden(seed, t, b=1):
    return (0.7 * np.random.default_rng(seed).standard_normal(
        (b, t, D))).astype(np.float32)


# ------------------------------------------------------------ the spec
def test_spec_from_the_published_config():
    from bloombee_tpu.models.auto import spec_from_config_dict

    config = json.loads(
        (ROOT / "cellbench/configs/trinity-large-ep8-span5.json").read_text())
    config.pop("cellbench")
    spec = spec_from_config_dict(config)
    assert (spec.family, spec.hidden_size, spec.head_dim) == (
        "afmoe", 3072, 128)
    assert (spec.num_attention_heads, spec.num_key_value_heads) == (48, 8)
    assert spec.layer_types == (
        "sliding", "sliding", "sliding", "full", "sliding")
    assert [spec.window_for_layer(i) for i in range(5)] == [
        4096, 4096, 4096, 0, 4096]
    assert spec.flash_window == 4096 and spec.rope_window_only
    assert [spec.mlp_kind(i) for i in range(5)] == ["dense"] + ["sparse"] * 4
    assert (spec.moe_router, spec.moe_norm_topk, spec.moe_route_scale) == (
        "sigmoid", True, 2.448)
    assert (spec.num_experts_per_tok, spec.moe_intermediate_size,
            spec.moe_shared_intermediate, spec.intermediate_size) == (
        4, 3072, 3072, 12288)
    assert spec.embedding_multiplier == pytest.approx(3072 ** 0.5)
    assert spec.sandwich_norms and spec.attn_gate and spec.qk_norm
    # one kind of cache, the window a value that rides the scan: a span may
    # be cut anywhere
    assert all(spec.span_unsupported(a, b) is None
               for a in range(5) for b in range(a + 1, 6))
    # a family whose layers are all of one kind keeps its chunk path
    from bloombee_tpu.models.mistral import mistral_spec_from_hf
    from types import SimpleNamespace

    mistral = mistral_spec_from_hf(SimpleNamespace(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=2, vocab_size=64,
        rms_norm_eps=1e-5, sliding_window=4096))
    assert mistral.flash_window == 0


def test_the_span_loads_as_two_runs_under_the_layer_bodys_keys(span):
    params, spec = span
    lead, main = split_runs(params)
    assert lead["gate_proj"].shape == (1, D, 256) and "router_t" not in lead
    assert main["router_t"].shape == (4, 8, D)  # the router over ALL experts
    assert main["expert_bias"].shape == (4, 8)
    assert main["expert_bias"].dtype == jnp.float32
    assert float(jnp.abs(main["expert_bias"]).max()) > 0  # no zeros
    assert main["experts_gate"].shape == (4, 4, D, 64)  # the 4 held
    assert main["shared_down"].shape == (4, 64, D)
    # the attention's gate is a tensor of its own, stored output-major
    assert main["q_gate_proj"].shape == (4, 4 * 32, D) == main["q_proj"].shape
    for key in ("pre_feedforward_layernorm", "post_feedforward_layernorm"):
        assert main[key].shape == (4, D) and lead[key].shape == (1, D)
    assert all(k.startswith(LEAD) or k in main for k in params)
    assert (spec.num_experts, spec.moe_held) == (8, HELD)


# ---------------------------------------------------------- the router
def test_router_bias_changes_the_choice_and_not_the_weight():
    """A hand-written case: four experts, top-2. Unbiased, row 0 takes
    experts 0 and 1; the bias lifts expert 3 over expert 1. The weights are
    the UNBIASED scores of the chosen pair over their sum, times the scale."""
    logits = jnp.asarray([[2.0, 1.0, -1.0, 0.5], [0.0, 3.0, 1.0, -2.0]])
    bias = jnp.asarray([0.0, -0.2, 0.0, 0.1])
    s = np.asarray(jax.nn.sigmoid(logits))
    idx0, w0 = route_topk(logits, 2, sigmoid=True, norm_topk=True, scale=2.5)
    idx1, w1 = route_topk(
        logits, 2, sigmoid=True, norm_topk=True, scale=2.5, bias=bias)
    assert np.asarray(idx0).tolist() == [[0, 1], [1, 2]]
    assert np.asarray(idx1).tolist() == [[0, 3], [1, 2]]
    np.testing.assert_allclose(
        w1[0], 2.5 * s[0, [0, 3]] / s[0, [0, 3]].sum(), rtol=1e-6)
    # row 1's choice is the same with and without: so are its weights
    np.testing.assert_allclose(w1[1], w0[1], rtol=1e-6)
    np.testing.assert_allclose(
        w0[1], 2.5 * s[1, [1, 2]] / s[1, [1, 2]].sum(), rtol=1e-6)
    # without route_norm the weights are the scores themselves
    _, raw = route_topk(logits, 2, sigmoid=True, bias=bias)
    np.testing.assert_allclose(raw[0], s[0, [0, 3]], rtol=1e-6)
    # the pair the bias brought in is counted, and no other
    assert np.asarray(bias_moved(logits, idx1)).tolist() == [
        [False, True], [False, False]]
    assert reach_fields(True)[3:] == ("bias_moved_pairs", "routed_pairs")
    # and the family file's plain copy says the same
    config = dict(CONFIG, num_experts_per_tok=2, route_scale=2.5)
    r_idx, r_w = FAMILY.route(logits, bias, config)
    assert np.asarray(r_idx).tolist() == np.asarray(idx1).tolist()
    np.testing.assert_allclose(r_w, w1, rtol=1e-6)


@pytest.mark.parametrize("rows", [2, 300])
def test_the_three_expert_forms_agree_under_the_sigmoid_router(
        ckpt, rows, monkeypatch):
    """dense, list (2 rows x top-2 < 8 experts) and tiled (300 rows), each
    over the HELD stack with the router's bias, against the family file."""
    monkeypatch.setenv("BBTPU_PAGED_INTERPRET", "1")
    tensors = reference.read_safetensors(
        ckpt / checkpoint.file_name(checkpoint.layer_tag(2)))
    x = jnp.asarray(_hidden(5, rows)[0])
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: jnp.asarray(a).astype(jnp.float32),
                         FAMILY.layer_params(tensors, CONFIG, 2))
        routed = {k: v for k, v in p.items() if not k.startswith("s_")}
        want = FAMILY.moe(x, routed, CONFIG)
        stacks = [jnp.swapaxes(p[f"e_{k}"], 1, 2)
                  for k in ("gate", "up", "down")]
        common = dict(
            sigmoid=True, router_bias=p["expert_bias"], norm_topk=True,
            route_scale=CONFIG["route_scale"], held=HELD)
        dense = moe_mlp(x[None], p["router"].T, *stacks, 2, **common)[0]
        kernel = moe_mlp(
            x[None], p["router"].T, *stacks, 2, expert_base=jnp.int32(0),
            interpret=True, **common)[0]
    scale = float(jnp.abs(want).max())
    assert scale > 0
    np.testing.assert_allclose(dense, want, rtol=1e-4, atol=1e-5 * scale)
    np.testing.assert_allclose(kernel, want, rtol=1e-4, atol=1e-5 * scale)


def test_eight_shares_add_up_to_the_uncut_layer(tmp_path):
    """The guide's tie of a share to the model: eight shares of one expert
    each; the shares' routed partial sums plus the shared expert counted
    ONCE add up to the uncut reference's sparse MLP, in the reference and in
    the program's expert layer alike."""
    whole = dict(CONFIG, num_experts=8, experts_held=[0, 8])
    checkpoint.write_checkpoint(tmp_path, whole, 48)
    layer = 1
    tensors = reference.read_safetensors(
        tmp_path / checkpoint.file_name(checkpoint.layer_tag(layer)))
    x = jnp.asarray(_hidden(9, 40)[0])
    with jax.default_matmul_precision("highest"):
        f32 = lambda p: jax.tree.map(  # noqa: E731
            lambda a: jnp.asarray(a).astype(jnp.float32), p)
        p_all = f32(FAMILY.layer_params(tensors, whole, layer))
        want = FAMILY.moe(x, p_all, whole)
        shared = FAMILY._silu_mlp(
            x, p_all["s_gate"], p_all["s_up"], p_all["s_down"])
        total = program = shared
        for first in range(8):
            share = dict(whole, num_experts=1, experts_held=[first, 1])
            p = f32(FAMILY.layer_params(tensors, share, layer))
            total = total + FAMILY.moe(
                x, {k: v for k, v in p.items() if not k.startswith("s_")},
                share)
            program = program + moe_mlp(
                x[None], p["router"].T, *(
                    jnp.swapaxes(p[f"e_{k}"], 1, 2)
                    for k in ("gate", "up", "down")),
                2, sigmoid=True, router_bias=p["expert_bias"],
                norm_topk=True, route_scale=whole["route_scale"],
                held=(first, 1))[0]
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5 * scale)
    np.testing.assert_allclose(program, want, rtol=1e-4, atol=1e-5 * scale)
    # the routed part counts: it is no rounding of the shared expert's output
    assert float(jnp.abs(want - shared).max()) > 0.2 * scale


# ------------------------------------------------ positions by layer kind
def test_a_full_layer_ignores_a_shift_of_all_positions_a_window_layer_not(
        ckpt):
    """Layer 3 (full) has no positional encoding at all: its output is the
    same when every position moves by 1000. Layer 2 (window) ropes its
    queries and keys: RELATIVE positions are what rotary keeps, so a uniform
    shift leaves it too, and a STRETCH (positions doubled) changes it and
    not the full layer's mask order."""
    h = jnp.asarray(_hidden(11, 48)[0])
    pos = jnp.arange(48)
    full = lambda p: _reference_hidden(ckpt, h, p, layers=[3])  # noqa: E731
    window = lambda p: _reference_hidden(ckpt, h, p, layers=[2])  # noqa: E731
    np.testing.assert_allclose(full(pos + 1000), full(pos), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(full(pos * 2), full(pos), rtol=1e-5, atol=1e-6)
    assert np.abs(window(pos * 2) - window(pos)).max() > 1e-2
    # the program's tables: identity in a full layer, rotary in a window one
    from bloombee_tpu.runtime.step import _rope_by_window

    spec = load_span_params(
        str(ckpt), 3, 4, dtype=jnp.float32, experts=HELD)[1]
    pick = _rope_by_window(spec, jnp.arange(8)[None] + 5, jnp.float32)
    cos, sin = pick(jnp.int32(0))
    assert float(jnp.abs(cos - 1).max()) == 0 == float(jnp.abs(sin).max())
    cos, sin = pick(jnp.int32(32))
    assert float(jnp.abs(sin).max()) > 0.5


# ------------------------------------------------------ the served path
@pytest.mark.parametrize("kernels", [False, True], ids=["dense", "kernels"])
@pytest.mark.parametrize("chunk", [128, 40], ids=["chunk128", "chunk40"])
def test_chunked_prefill_then_decode_through_the_cache_match_the_family_file(
        ckpt, span, chunk, kernels, monkeypatch):
    """300 prompt tokens in chunks (128: two full chunks on the flash kernel
    by layer kind and a 44-row tail on the chunk kernel; 40: chunks of 32
    rows, every one under the flash kernel's tile), then 4 single steps, against the family
    file's full forward with no cache. Every prompt outgrows the 32-token
    window nine times."""
    for k, v in (KERNELS if kernels else {}).items():
        monkeypatch.setenv(k, v)
    t = 300
    h = _hidden(3, t + 4)
    want = _reference_hidden(ckpt, h[0])
    ex = _executor(span)

    async def run():
        outs = []
        async with ex.manager.allocate(1, t + 8) as handle:
            outs.append(np.asarray(
                ex.prefill_chunked(handle, h[:, :t], chunk)))
            for i in range(4):
                outs.append(np.asarray(
                    ex.decode(handle, h[:, t + i:t + i + 1])))
        return np.concatenate(outs, axis=1)[0]

    with jax.default_matmul_precision("highest"):
        got = asyncio.run(run())
    np.testing.assert_allclose(got, want, **TOL)
    assert ex.kernel_fallbacks == 0
    flash = 2 if kernels and chunk == 128 else 0
    assert ex.attn_dispatches["flash"] == flash
    assert (ex.attn_dispatches["dense"] == 0) == kernels
    # the experts' forms: a full chunk's 128 rows x top-2 reach all 8 experts
    # (tiled only from 256 rows: dense), a decode row lists its two
    assert (ex.moe_dispatches["grouped"] > 0) == kernels
    # what the steps reached of the held experts, and what the bias moved
    ex._drain_reach()
    reach = ex.moe_reach
    assert reach["rows"] == t + 4
    assert 0 < reach["bias_moved_pairs"] < reach["routed_pairs"]
    assert reach["routed_pairs"] >= (t + 4) * 2 * 4
    assert 0 < reach["rows_with_held_expert"] <= reach["routed_pairs_here"]
    assert len(reach["held_hit_last"]) == LAYERS - 1
    # the window-dead accounting, for this family as for SambaY: four window
    # layers of five, at each step's context before it
    from bloombee_tpu.runtime.executor import plan_prefill_chunks

    starts = [s for s, _ in plan_prefill_chunks(t, chunk)] + [
        t + i for i in range(4)]
    assert ex.kv_held["kv_held_tokens"] == 5 * sum(starts)
    assert ex.kv_held["window_dead_tokens"] == 4 * sum(
        max(s - 32, 0) for s in starts)


def test_a_chunk_steps_span_says_the_tile_its_flash_kernel_multiplied(
        span, monkeypatch):
    """`bbtpu.step` of a chunk that attended through flash carries `flash`,
    the rule's tile by layer kind at the step's shapes; a decode step and a
    chunk on the dense path carry none; `flash_form` keeps the last one for
    `rpc_info` and `health --probe`."""
    from bloombee_tpu.ops.pallas.flash_attention import flash_tiles
    from bloombee_tpu.utils import jitwatch

    monkeypatch.setenv("BBTPU_JITWATCH", "1")
    seen = []
    real = jitwatch.span

    def stamped(name, **ids):
        if name == "bbtpu.step":
            seen.append(ids)
        return real(name, **ids)

    monkeypatch.setattr(jitwatch, "span", stamped)
    h = _hidden(5, 129)

    async def run(ex):
        async with ex.manager.allocate(1, 136) as handle:
            ex.prefill_chunked(handle, h[:, :128], 128)
            ex.decode(handle, h[:, 128:])

    ex = _executor(span)
    asyncio.run(run(ex))
    assert [s["kind"] for s in seen] == ["chunk", "decode"]
    assert not any("flash" in s for s in seen) and ex.flash_form is None
    del seen[:]
    for k, v in KERNELS.items():
        monkeypatch.setenv(k, v)
    ex = _executor(span)
    spec = span[1]
    asyncio.run(run(ex))
    assert [s["kind"] for s in seen] == ["chunk", "decode"]
    n_rep = spec.num_attention_heads // spec.num_key_value_heads
    # the chunk's 128 tokens fill the 8-page bucket, which a 32-token
    # window's run of whole 512-key blocks would pass: both kinds gather the
    # bucket's 128 keys
    want = "x".join(map(str, flash_tiles(128, 128, n_rep, spec.head_dim, 4)))
    assert seen[0]["flash"] == f"window:{want}+full:{want}"
    assert "flash" not in seen[1]
    assert ex.flash_form == seen[0]["flash"]


def test_fused_pack_and_decode_group_match_the_family_file(ckpt, span):
    """The ragged pack (one sequence's chunk beside another's decode row)
    and the packed decode group, each row against its own sequence's
    reference."""
    a, b = _hidden(4, 60), _hidden(5, 70)
    want_a, want_b = (_reference_hidden(ckpt, x[0]) for x in (a, b))
    ex = _executor(span)

    async def run():
        m = ex.manager
        async with m.allocate(1, 96) as ha, m.allocate(1, 96) as hb:
            got_a = [np.asarray(ex.prefill(ha, a[:, :58]))[0]]
            got_b = [np.asarray(ex.prefill(hb, b[:, :45]))[0]]
            out, both = ex.ragged_group(
                [ha, hb], [a[:, 58:59], b[:, 45:68]],
                tree_masks=[None, None], depths_list=[None, None])
            m.commit(both)
            out = np.asarray(out)
            got_a.append(out[:1]), got_b.append(out[1:24])
            out, both = ex.decode_group([ha, hb], [a[:, 59:60], b[:, 68:69]])
            m.commit(both)
            out = np.asarray(out)
            got_a.append(out[0]), got_b.append(out[1])
            got_b.append(np.asarray(ex.decode(hb, b[:, 69:70]))[0])
        return np.concatenate(got_a), np.concatenate(got_b)

    with jax.default_matmul_precision("highest"):
        got_a, got_b = asyncio.run(run())
    np.testing.assert_allclose(got_a, want_a, **TOL)
    np.testing.assert_allclose(got_b, want_b, **TOL)
    assert ex.kernel_fallbacks == 0


def test_a_span_cut_anywhere_serves_the_same_rows(ckpt):
    """Layers [0, 2) then [2, 5) on two executors (a dense layer and a sparse
    one; three sparse layers, the full one among them: its window rides by
    the ABSOLUTE index) give what the whole span gives."""
    h = _hidden(6, 50)
    want = _reference_hidden(ckpt, h[0])
    x = h
    for start, end in ((0, 2), (2, 5)):
        params, spec = load_span_params(
            str(ckpt), start, end, dtype=jnp.float32, experts=HELD)
        manager = CacheManager(
            end - start, 16, 16, spec.num_key_value_heads, spec.head_dim,
            dtype=jnp.float32)
        ex = SpanExecutor(params, spec, manager, compute_dtype=jnp.float32,
                          start_block=start)
        assert ex.windows == tuple(
            0 if i == 3 else 32 for i in range(start, end))

        async def run(ex=ex, x=x):
            async with ex.manager.allocate(1, 64) as handle:
                return np.asarray(ex.prefill(handle, x))

        with jax.default_matmul_precision("highest"):
            x = asyncio.run(run())
    np.testing.assert_allclose(x[0], want, **TOL)


# ----------------------------------------------------------- refusals
@pytest.mark.parametrize("kw,reason", [
    (dict(tp=2), "--tp .tensor-parallel serving. unsupported for afmoe"),
    (dict(sp=2), "--sp .sequence-parallel prefill. unsupported for afmoe"),
], ids=["tp", "sp"])
def test_a_server_the_family_cannot_serve_refuses_at_start_up(
        ckpt, kw, reason):
    from bloombee_tpu.server.block_server import BlockServer

    with pytest.raises(ValueError, match=reason):
        BlockServer(model_uid="x", start=0, end=LAYERS, model_dir=str(ckpt),
                    experts=HELD, compute_dtype=jnp.float32, page_size=16,
                    num_pages=16, **kw)


def test_server_side_refusals_and_counters_reach_the_client(ckpt):
    """At the server: no training stack, decode_n says why not, tree rows on
    window layers are declined with the reason in
    `rpc_info["ragged_declines"]`; `rpc_info` carries the experts held, the
    reach counters with what the bias moved, and the window-dead accounting
    where the benchmark's counter snapshot reads it."""
    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.server.block_server import BlockServer
    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer
    from bloombee_tpu.wire.rpc import connect

    ids = np.random.default_rng(41).integers(0, CONFIG["vocab_size"], (1, 75))

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()
        server = BlockServer(
            model_uid="tiny-afmoe", start=0, end=LAYERS, model_dir=str(ckpt),
            registry=RegistryClient("127.0.0.1", reg.port), experts=HELD,
            compute_dtype=jnp.float32, page_size=16, num_pages=64,
            prefill_chunk=32, mixed_batch=True, spec_batch=True)
        await server.start()
        try:
            model = DistributedModelForCausalLM.from_pretrained(
                str(ckpt), RegistryClient("127.0.0.1", reg.port),
                model_uid="tiny-afmoe", dtype=jnp.float32)
            assert model.spec.embedding_multiplier == pytest.approx(D ** 0.5)
            rows = []
            async with model.inference_session(96, 1) as session:
                out = await session.step(
                    model.embed(ids[:, :70]), ids=ids[:, :70])
                rows.append(model.logits(out[:, -1:])[0, 0])
                for t in range(70, 75):
                    out = await session.step(
                        model.embed(ids[:, t:t + 1]), ids=ids[:, t:t + 1])
                    rows.append(model.logits(out)[0, 0])
            conn = await connect("127.0.0.1", server.port)
            info, _ = await conn.call("rpc_info", {})
            await conn.close()
            return (np.stack(rows), info, server.training,
                    server.spec_batch, server._decode_n_ineligible())
        finally:
            await server.stop()
            await reg.stop()

    with jax.default_matmul_precision("highest"):
        got, info, training, spec_batch, why_not = asyncio.run(
            asyncio.wait_for(run(), 280))
    # the client's logits (its embedding rows times sqrt(D), its final norm
    # and head) against the family file's full forward
    client = reference.read_safetensors(
        ckpt / checkpoint.file_name(checkpoint.CLIENT_SHARD))
    hidden = _reference_hidden(ckpt, FAMILY.embed(client, CONFIG, ids[0]))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(FAMILY.logits_rows(
            client, CONFIG, jnp.asarray(hidden[69:75])))
    np.testing.assert_allclose(got, want, **TOL)
    assert training is None and spec_batch is False
    assert "share of the experts" in why_not
    assert info["ragged_declines"]["sliding-window layers"] == 1
    assert info["experts_held"] == list(HELD)
    reach = info["moe_reach"]
    assert 0 < reach["bias_moved_pairs"] < reach["routed_pairs"]
    kv = info["kv"]
    assert 0 < kv["window_dead_tokens"] < kv["held_tokens"]
    assert info["memory"]["sambay"] == {
        "kv_held_tokens": kv["held_tokens"],
        "window_dead_tokens": kv["window_dead_tokens"]}


# ------------------------------------- the cell's programs, for the chip
def _cell_shapes(one_chip, pages=5376):
    from bloombee_tpu.models.auto import spec_from_config_dict

    config = json.loads(
        (ROOT / "cellbench/configs/trinity-large-ep8-span5.json").read_text())
    held = tuple(config["experts_held"])
    config.pop("cellbench")
    spec = dataclasses.replace(
        spec_from_config_dict(config), num_experts=config["router_experts"],
        moe_held=held)
    d, h, kv, hd = (spec.hidden_size, spec.num_attention_heads,
                    spec.num_key_value_heads, spec.head_dim)

    def s(n, *shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((n, *shape), dtype, sharding=one_chip)

    def attention(n):
        return {
            **{k: s(n, d) for k in (
                "input_layernorm", "post_attention_layernorm",
                "pre_feedforward_layernorm", "post_feedforward_layernorm")},
            "q_norm": s(n, hd), "k_norm": s(n, hd),
            "q_proj": s(n, h * hd, d), "q_gate_proj": s(n, h * hd, d),
            "k_proj": s(n, kv * hd, d), "v_proj": s(n, kv * hd, d),
            "o_proj": s(n, h * hd, d),
        }

    i, e, si = (spec.moe_intermediate_size, held[1],
                spec.moe_shared_intermediate)
    dense = {**attention(1), "gate_proj": s(1, d, spec.intermediate_size),
             "up_proj": s(1, d, spec.intermediate_size),
             "down_proj": s(1, spec.intermediate_size, d)}
    sparse = {**attention(4), "router_t": s(4, spec.num_experts, d),
              "expert_bias": s(4, spec.num_experts, dtype=jnp.float32),
              "experts_gate": s(4, e, d, i), "experts_up": s(4, e, d, i),
              "experts_down": s(4, e, i, d), "shared_gate": s(4, d, si),
              "shared_up": s(4, d, si), "shared_down": s(4, si, d)}
    params = {**{LEAD + k: v for k, v in dense.items()}, **sparse}
    return spec, params, s(5, pages * 16, kv, hd)


@pytest.mark.parametrize("program", ["decode", "chunk", "tail"])
def test_cell_span_step_compiles_for_v5e_and_copies_no_parameter(
        v5e, program):
    """The cell's step programs at the published widths (a decode group
    through the paged kernel and the experts' grouped form, a solo 512-row
    chunk through the flash kernel BY LAYER KIND and the tiled experts, an
    8-row tail through the chunk kernel), 1024-page bucket: ONE program runs
    layer 0's dense MLP and the four sparse layers, window and full, over
    one flat arena; the compiled text holds no copy of a `stacked_params`
    parameter and no copy of a whole arena."""
    from jax.sharding import SingleDeviceSharding

    from bloombee_tpu.runtime.step import span_step_packed
    from test_chip_compile import _cell_payload

    one_chip = SingleDeviceSharding(v5e[0])
    spec, params, arena = _cell_shapes(one_chip)
    layers, pages = 5, 1024
    windows = tuple(spec.window_for_layer(i) for i in range(layers))
    b, t, case = {
        "decode": (4, 1, dict(use_paged=True)),
        # (a chunk's rows come as page groups: written by page, PR 49)
        "chunk": (1, 512, dict(
            use_flash=True, t_real=512, expert_kernels=True,
            page_groups=True)),
        "tail": (1, 8, dict(use_paged=True, t_real=5)),
    }[program]
    plan_len = b * t + b * pages + b * t + b + layers
    compiled = span_step_packed.lower(
        params, arena, arena, _cell_payload(spec, b * t, plan_len, one_chip),
        None, None, None, b=b, t=t, spec=spec, page_size=16,
        max_pages=pages, windows=windows, **case,
    ).compile()
    text = compiled.as_text()
    assert "%stacked_params__lead_q_gate_proj" in text  # the names read below
    assert "%stacked_params__expert_bias" in text
    assert not re.findall(r"copy\([^)\n]*%(stacked_params\w+)", text)
    assert not re.findall(r"copy\([^)\n]*%arena_[kv]", text)
    tiled = program == "chunk"
    assert ("jit(tiled_experts)" in text) == tiled
    assert ("jit(grouped_experts)" in text) == (not tiled)
    if program == "chunk":
        # both kinds on the flash kernel, each under its kind's scope
        for kind in ("window_attn", "full_attn"):
            assert f"{kind}/attention/jit(flash_attention)" in text
            assert f"{kind}/arena_gather" in text
    # no temporary the size of a layer's held stack (1.8 GB)
    assert compiled.memory_analysis().temp_size_in_bytes < 300e6
    # no program moves a slab, and the chunk's K/V go into the arena one
    # index a PAGE (32 a slab, where the row scatter takes 512): PR 49
    from test_chip_compile import _scatter_indices, _slab_moves

    slab = arena.shape[1] * arena.shape[2] * arena.shape[3]
    assert not _slab_moves(text, slab), _slab_moves(text, slab)
    writes = _scatter_indices(text)  # (one more, of 32: the reach counters)
    assert writes.count({"decode": 4, "chunk": 32, "tail": 8}[program]) >= 4
    assert 512 not in writes, writes

