"""Qwen3-Next: gated-DeltaNet linear-attention layers with a gated
full-attention layer every fourth, two cache kinds in ONE span step (a K/V
arena with a row a full layer, a state arena with a row a linear one), a
sparse MLP with one gated shared expert in every layer, over the experts a
server HOLDS.

Tiny widths on the CPU (two periods, 8 experts, hidden 64), seeded. The
mathematics under test has ONE plain copy, the benchmark's family file
(cellbench/families/qwen3_next.py: the recurrence token by token, no
cache); everything the program serves (the chunk form, through both arenas)
is held to that file.
"""

import asyncio
import pathlib
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bloombee_tpu.kv.cache_manager import (  # noqa: E402
    CacheManager,
    state_slots_for,
)
from bloombee_tpu.models.checkpoint import load_span_params  # noqa: E402
from bloombee_tpu.models.layout import split_kinds, stacked_layers  # noqa: E402
from bloombee_tpu.ops.linear_attention import (  # noqa: E402
    gdn_chunk,
    gdn_sequence,
    gdn_step,
    l2_normalize,
)
from bloombee_tpu.runtime.executor import SpanExecutor  # noqa: E402
from cellbench import checkpoint, families, reference  # noqa: E402

# 8 router outputs, top-3; this checkpoint holds experts 2-5
CONFIG = {
    "model_type": "qwen3_next", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "partial_rotary_factor": 0.25,
    "full_attention_interval": 4, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 16, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_value_head_dim": 16,
    "num_experts": 4, "router_experts": 8, "experts_held": [2, 4],
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "intermediate_size": 96, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "num_hidden_layers": 8, "vocab_size": 128, "rms_norm_eps": 1e-6,
    "rope_theta": 10000000, "rope_scaling": None, "hidden_act": "silu",
    "max_position_embeddings": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "torch_dtype": "bfloat16",
}
D, LAYERS = CONFIG["hidden_size"], CONFIG["num_hidden_layers"]
FAMILY = families.of(CONFIG)
HELD = tuple(CONFIG["experts_held"])
KERNELS = {"BBTPU_PAGED_INTERPRET": "1", "BBTPU_PAGED_MIN_CONTEXT": "0",
           "BBTPU_FLASH_INTERPRET": "1"}
# float32 at `highest` on both sides: what is left is the order of sums (the
# chunk form against the token loop, the dense experts against the family
# file's blocks); a layer's update is 0.01 of a residual of 0.2, so these
# tolerances are a thousandth of one layer's update
TOL = dict(rtol=2e-4, atol=2e-6)


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    path = tmp_path_factory.mktemp("tiny_qwen3_next")
    checkpoint.write_checkpoint(path, CONFIG, 39)
    return path


@pytest.fixture(scope="module")
def span(ckpt):
    return load_span_params(
        str(ckpt), 0, LAYERS, dtype=jnp.float32, experts=HELD)


def _reference_hidden(ckpt, hidden, config=CONFIG):
    """The family file's layers over one sequence's hidden states [T, D]."""
    with jax.default_matmul_precision("highest"):
        h, pos = jnp.asarray(hidden), jnp.arange(hidden.shape[0])
        for layer in range(config["num_hidden_layers"]):
            h = FAMILY.layer_forward(
                reference.layer_params(ckpt, config, layer), config, h, pos)
        return np.asarray(h)


def _manager(spec, **kw):
    kw.setdefault("state_slots", 6)
    kw.setdefault("arena_layers", spec.arena_layers(0, LAYERS))
    return CacheManager(
        LAYERS, 96, 4, spec.num_key_value_heads, spec.head_dim,
        dtype=jnp.float32, ssm=spec.recurrent, **kw)


def _executor(span, manager=None, **kw):
    params, spec = span
    return SpanExecutor(params, spec, manager or _manager(spec),
                        compute_dtype=jnp.float32, **kw)


def _hidden(seed, t, b=1):
    return (0.05 * np.random.default_rng(seed).standard_normal(
        (b, t, D))).astype(np.float32)


# ------------------------------------------------ the rule's two forms
def _rule_inputs(seed, t, h=3, k=16, v=8):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    return (l2_normalize(f(t, h, k)) * k ** -0.5, l2_normalize(f(t, h, k)),
            f(t, h, v), -jnp.asarray(rng.uniform(0, 0.3, (t, h)), jnp.float32),
            jnp.asarray(rng.uniform(0, 1, (t, h)), jnp.float32),
            f(h, k, v))


def _token_by_token(q, k, v, g, beta, s):
    outs = []
    for t in range(q.shape[0]):
        o, s = gdn_step(*(x[t:t + 1] for x in (q, k, v, g, beta)), s[None])
        outs.append(o[0])
        s = s[0]
    return jnp.stack(outs), s


@pytest.mark.parametrize("t,chunk", [(200, 64), (64, 64), (37, 64), (128, 32)])
def test_chunk_form_equals_the_token_loop_across_block_boundaries(t, chunk):
    """The triangular chunk form against one rule step a token, from a
    state that is not empty: 200 rows in a 256-row bucket cross three block
    boundaries and end in a padded tail (beta = g = 0 there)."""
    q, k, v, g, beta, s0 = _rule_inputs(t, t)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = _token_by_token(q, k, v, g, beta, s0)
        bucket = 1 << (t - 1).bit_length()
        pad = lambda z: jnp.pad(  # noqa: E731
            z, ((0, bucket - t),) + ((0, 0),) * (z.ndim - 1))
        o, s = gdn_sequence(*map(pad, (q, k, v, g, beta)), s0, chunk)
        one_o, one_s = gdn_chunk(q, k, v, g, beta, s0)
    np.testing.assert_allclose(o[:t], want_o, rtol=1e-5, atol=2e-6)
    np.testing.assert_allclose(s, want_s, rtol=1e-5, atol=2e-6)
    np.testing.assert_allclose(one_o, want_o, rtol=1e-5, atol=2e-6)
    np.testing.assert_allclose(one_s, want_s, rtol=1e-5, atol=2e-6)


def test_rows_with_zero_beta_and_decay_leave_the_state_bit_equal():
    """Padding rows (beta = 0, g = 0) neither decay nor feed S, whatever
    their q, k and v hold: both forms."""
    q, k, v, g, beta, s0 = _rule_inputs(5, 16)
    zero = jnp.zeros_like(g)
    _, s = gdn_chunk(q, k, v * 1e3, zero, zero, s0)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s0))
    rows = jnp.broadcast_to(s0, (16, *s0.shape))
    _, s = gdn_step(q, k, v * 1e3, zero, zero, rows)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(rows))


def test_keys_of_one_direction_do_not_break_the_solve():
    """Every key the same unit vector and beta near 1: the strictly-lower
    part is all ones, where a series in it cancels catastrophically and
    forward substitution does not."""
    q, k, v, g, beta, s0 = _rule_inputs(6, 64)
    k = jnp.broadcast_to(k[:1], k.shape)
    beta = jnp.full_like(beta, 0.999)
    g = jnp.zeros_like(g)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = _token_by_token(q, k, v, g, beta, s0)
        o, s = gdn_chunk(q, k, v, g, beta, s0)
    np.testing.assert_allclose(o, want_o, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s, want_s, rtol=1e-4, atol=1e-5)


# --------------------------------- the system through both arenas
@pytest.mark.parametrize("kernels", [False, True], ids=["dense", "kernels"])
@pytest.mark.parametrize("split", [None, 1, 70, 128])
def test_one_chunk_equals_single_steps_equals_two_chunks(
        ckpt, span, split, kernels, monkeypatch):
    """Prefill then decode through the K/V arena (2 rows) and the state
    arena (6 rows) == the family file's token loop with no cache, however
    the positions are cut: one chunk, single steps, two chunks with a
    padded tail, a 128-row chunk then 22 single steps; on the dense path
    and through the Pallas kernels (interpreted)."""
    for k, v in (KERNELS if kernels else {}).items():
        monkeypatch.setenv(k, v)
    n = 150 if split != 1 else 21
    h = _hidden(3, n)
    want = _reference_hidden(ckpt, h[0])
    ex = _executor(span)
    cuts = {None: [n], 1: [1] * n, 70: [70, 80], 128: [128] + [1] * 22}[split]

    async def run():
        outs, at = [], 0
        async with ex.manager.allocate(1, 160) as handle:
            for c in cuts:
                step = ex.prefill if c > 1 else ex.decode
                outs.append(np.asarray(step(handle, h[:, at:at + c])))
                at += c
        return np.concatenate(outs, axis=1)[0]

    with jax.default_matmul_precision("highest"):
        got = asyncio.run(run())
    np.testing.assert_allclose(got, want, **TOL)
    # the layers are no rounding of the residual they are added to
    assert float(np.abs(want - h[0]).max()) > 1e3 * TOL["atol"]
    assert ex.kernel_fallbacks == 0
    assert (ex.attn_dispatches["paged"] + ex.attn_dispatches["flash"] > 0
            ) == kernels


def test_a_chunk_steps_span_says_the_blocks_its_rule_took_at_once(
        span, step_spans):
    """`bbtpu.step` of a chunk carries `rule_blocks`, the blocks the rule's
    chunk form took in its one batched pass (ops/linear_attention.py
    `sequence_blocks`): 2 for 128 rows, 1 for a 22-row tail in its 32-row
    bucket (the single-block form); a decode step, whose rows take one rule
    step each, carries none."""
    h = _hidden(5, 151)
    ex = _executor(span)

    async def run():
        async with ex.manager.allocate(1, 160) as handle:
            ex.prefill(handle, h[:, :128])
            ex.prefill(handle, h[:, 128:150])
            ex.decode(handle, h[:, 150:])

    asyncio.run(run())
    assert [s["kind"] for s in step_spans] == ["chunk", "chunk", "decode"]
    assert [s.get("rule_blocks") for s in step_spans] == [2, 1, None]


@pytest.mark.parametrize("kernels", [False, True], ids=["dense", "kernels"])
def test_fused_pack_and_decode_group_match_the_family_file(
        ckpt, span, kernels, monkeypatch):
    """The ragged pack (one sequence's chunk beside another's decode row:
    with kernels on, attended sequence by sequence) and the packed decode
    group, each row against its own sequence's reference; the decode group
    takes the experts' grouped form over the HELD stacks of both kinds."""
    for k, v in (KERNELS if kernels else {}).items():
        monkeypatch.setenv(k, v)
    a, b = _hidden(4, 15), _hidden(5, 100)
    want_a, want_b = (_reference_hidden(ckpt, x[0]) for x in (a, b))
    ex = _executor(span)

    async def run():
        m = ex.manager
        async with m.allocate(1, 120) as ha, m.allocate(1, 120) as hb:
            got_a = [np.asarray(ex.prefill(ha, a[:, :13]))[0]]
            got_b = [np.asarray(ex.prefill(hb, b[:, :9]))[0]]
            out, both = ex.ragged_group(
                [ha, hb], [a[:, 13:14], b[:, 9:98]],
                tree_masks=[None, None], depths_list=[None, None])
            m.commit(both)
            out = np.asarray(out)
            got_a.append(out[:1]), got_b.append(out[1:90])
            out, both = ex.decode_group([ha, hb], [a[:, 14:15], b[:, 98:99]])
            m.commit(both)
            out = np.asarray(out)
            got_a.append(out[0]), got_b.append(out[1])
            got_b.append(np.asarray(ex.decode(hb, b[:, 99:100]))[0])
        return np.concatenate(got_a), np.concatenate(got_b)

    with jax.default_matmul_precision("highest"):
        got_a, got_b = asyncio.run(run())
    np.testing.assert_allclose(got_a, want_a, **TOL)
    np.testing.assert_allclose(got_b, want_b, **TOL)
    assert ex.kernel_fallbacks == 0
    assert ex.attn_dispatches["ragged" if kernels else "dense"] >= 1
    assert (ex.moe_dispatches["grouped"] > 0) == kernels
    # the pack's chunk attended through the flash kernel, 128 rows wide
    # (90 in the bucket) over the 32-page bucket's 128 keys; the 13- and
    # 9-row prefills before it took the dense tail
    from bloombee_tpu.ops.pallas.flash_attention import flash_tiles

    tile = "x".join(map(str, flash_tiles(
        128, 128, span[1].gqa_groups, span[1].head_dim, 4)))
    assert ex.flash_form == (f"full:{tile}" if kernels else None)
    # a share of the experts held: every layer is sparse and counted
    ex.fetch(jnp.zeros(()))
    assert len(ex.moe_reach["held_hit_last"]) == LAYERS


@pytest.mark.parametrize("program", ["packed", "ragged"])
def test_padding_rows_leave_state_and_tail_as_they_were(span, program):
    """A 5-row chunk in an 8-row bucket (and, ragged, a 16-row pack whose
    padding rows hold garbage): S and the convolution's tail of every
    linear layer equal the unpadded run's, so a padding row has neither
    advanced S nor entered the tail."""
    h = _hidden(8, 12)

    def run(pad: bool):
        ex = _executor(span)

        async def go():
            m = ex.manager
            async with m.allocate(1, 64) as handle:
                ex.prefill(handle, h[:, :7])
                if not pad:
                    for t in range(7, 12):
                        ex.decode(handle, h[:, t:t + 1])
                elif program == "packed":
                    ex.prefill(handle, h[:, 7:12])  # 5 rows, bucket 8
                else:
                    async with m.allocate(1, 64) as other:
                        ex.prefill(other, _hidden(9, 3))
                        _, both = ex.ragged_group(
                            [other, handle], [_hidden(10, 1), h[:, 7:12]],
                            tree_masks=[None, None], depths_list=[None, None])
                        m.commit(both)
                slot = int(m.state_slots(handle)[0])
                return (np.asarray(m.state["ssm"][:, slot]),
                        np.asarray(m.state["conv"][:, slot]))

        with jax.default_matmul_precision("highest"):
            return asyncio.run(go())

    (s_pad, tail_pad), (s_ref, tail_ref) = run(True), run(False)
    assert s_ref.shape[0] == 6 and float(np.abs(s_ref).max()) > 0
    # (the unpadded run takes single steps, the padded one the chunk form:
    # equal to float32's order of sums; a padding row that entered would
    # move the tail by its own size, 0.1)
    np.testing.assert_allclose(s_pad, s_ref, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(tail_pad, tail_ref, rtol=1e-4, atol=1e-6)


def test_a_session_entering_mid_span_skips_the_leading_period(ckpt, span):
    """`layer_active` gates layer by layer inside the period scan: layers
    [4, 8) alone equal the reference's last period, and the skipped
    period's rows of both arenas stay zero."""
    h = _hidden(11, 20)
    with jax.default_matmul_precision("highest"):
        x, pos = jnp.asarray(h[0]), jnp.arange(20)
        for layer in range(4, 8):
            x = FAMILY.layer_forward(
                reference.layer_params(ckpt, CONFIG, layer), CONFIG, x, pos)
    ex = _executor(span)

    async def run():
        async with ex.manager.allocate(1, 64) as handle:
            return np.asarray(ex.prefill(handle, h, layers=(4, 8)))[0]

    with jax.default_matmul_precision("highest"):
        got = asyncio.run(run())
    np.testing.assert_allclose(got, np.asarray(x), **TOL)
    m = ex.manager
    assert float(jnp.abs(m.arena["k"][0]).max()) == 0.0
    assert float(jnp.abs(m.arena["k"][1]).max()) > 0.0
    assert float(jnp.abs(m.state["ssm"][:3]).max()) == 0.0
    assert float(jnp.abs(m.state["ssm"][3:]).max()) > 0.0


# -------------------------------------------------------- the share test
def test_four_shares_add_up_to_the_uncut_layer(tmp_path):
    """Four shares of two experts each: the shares' routed partial sums
    plus the GATED shared expert counted ONCE add up to the uncut
    reference's sparse MLP, in the reference and in the program's expert
    form alike."""
    from bloombee_tpu.ops.moe import moe_mlp

    whole = dict(CONFIG, num_experts=8, experts_held=[0, 8])
    checkpoint.write_checkpoint(tmp_path, whole, 40)
    layer = 1
    tensors = reference.read_safetensors(
        tmp_path / checkpoint.file_name(checkpoint.layer_tag(layer)))
    x = jnp.asarray(_hidden(9, 40)[0]) * 20
    with jax.default_matmul_precision("highest"):
        f32 = lambda p: jax.tree.map(  # noqa: E731
            lambda a: jnp.asarray(a).astype(jnp.float32), p)
        p_all = f32(FAMILY.layer_params(tensors, whole, layer))
        want = FAMILY.moe(x, p_all, whole)
        shared = jax.nn.sigmoid(x @ p_all["s_w"].T) * FAMILY._silu_mlp(
            x, p_all["s_gate"], p_all["s_up"], p_all["s_down"])
        total = program = shared
        for first in range(0, 8, 2):
            share = dict(whole, num_experts=2, experts_held=[first, 2])
            p = f32(FAMILY.layer_params(tensors, share, layer))
            total = total + FAMILY.moe(x, p, share) - shared
            program = program + moe_mlp(
                x[None], p["router"].T, *(
                    jnp.swapaxes(p[f"e_{k}"], 1, 2)
                    for k in ("gate", "up", "down")),
                3, pre_softmax=True, norm_topk=True, held=(first, 2))[0]
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5 * scale)
    np.testing.assert_allclose(program, want, rtol=1e-4, atol=1e-5 * scale)
    # the routed part counts: it is no rounding of the shared expert's output
    assert float(jnp.abs(want - shared).max()) > 0.2 * scale


# --------------------------------------------- the loader, arenas, accounting
def test_the_span_loads_one_stack_a_position_as_the_steps_read_them(span, ckpt):
    params, spec = span
    linear, full = split_kinds(params)
    assert len(linear) == 3 and stacked_layers(params) == LAYERS
    for stack in linear:
        assert stack["gdn_in_proj"].shape == (2, D, 32 + 32 + 64 + 64)
        assert stack["gdn_ba_proj"].shape == (2, D, 256)
        assert stack["gdn_conv_w"].shape == (2, 4, 128)
        assert stack["experts_gate"].shape == (2, 4, D, 32)  # the 4 held
        assert stack["router"].shape == (2, D, 8)  # over ALL experts
        assert "q_proj" not in stack
    assert full["q_proj"].shape == full["q_gate_proj"].shape == (2, 128, D)
    assert full["shared_gate_w"].shape == (2, D) and "gdn_in_proj" not in full
    assert (spec.num_experts, spec.moe_held) == (8, HELD)
    assert spec.layer_types == ("linear", "linear", "linear", "full")
    assert (spec.rotary_dim, spec.norm_type) == (8, "rms1p")
    # q_proj's rows per head are q then gate; in_proj_qkvz's per KEY head
    # q | k | v | z: layer 3 (full) and layer 0 (linear, key head 1)
    raw = reference.read_safetensors(
        ckpt / checkpoint.file_name(checkpoint.layer_tag(3)))
    q = np.asarray(raw["model.layers.3.self_attn.q_proj.weight"], np.float32)
    np.testing.assert_array_equal(np.asarray(full["q_proj"][0, 32:64]), q[64:96])
    np.testing.assert_array_equal(
        np.asarray(full["q_gate_proj"][0, 32:64]), q[96:128])
    raw = reference.read_safetensors(
        ckpt / checkpoint.file_name(checkpoint.layer_tag(0)))
    w = np.asarray(
        raw["model.layers.0.linear_attn.in_proj_qkvz.weight"], np.float32)
    got = np.asarray(linear[0]["gdn_in_proj"][0]).T  # [q | k | v | z, D]
    head1 = w[96:192]  # key head 1: q 16 | k 16 | v 32 | z 32
    np.testing.assert_array_equal(got[16:32], head1[:16])
    np.testing.assert_array_equal(got[32 + 16:64], head1[16:32])
    np.testing.assert_array_equal(got[64 + 32:128], head1[32:64])
    np.testing.assert_array_equal(got[128 + 32:192], head1[64:96])
    ba = np.asarray(
        raw["model.layers.0.linear_attn.in_proj_ba.weight"], np.float32)
    got = np.asarray(linear[0]["gdn_ba_proj"][0]).T
    np.testing.assert_array_equal(got[2:4], ba[4:6])  # b of value heads 2, 3
    np.testing.assert_array_equal(got[128 + 2:128 + 4], ba[6:8])  # their a
    assert not got[4:128].any() and not got[132:].any()


def test_arena_layer_counts_follow_the_kinds(span):
    """K/V rows = full layers, state rows = linear layers; a layer finds its
    row among its kind; the bytes follow."""
    _, spec = span
    assert spec.arena_layers(0, 8) == (2, 6)
    assert spec.arena_layers(4, 8) == (1, 3)
    assert spec.cache_rows(0, 8) == (
        ("state", 0), ("state", 1), ("state", 2), ("kv", 0),
        ("state", 3), ("state", 4), ("state", 5), ("kv", 1))
    m = _manager(spec)
    assert m.arena["k"].shape == (2, 96 * 4, 2, 32)
    assert m.state["ssm"].shape == (6, 6, 4, 16, 16)
    assert m.state["conv"].shape == (6, 6, 3, 128)
    stats = m.memory_stats()
    assert (stats["kv_arena_layers"], stats["state_arena_layers"]) == (2, 6)
    assert stats["kv_arena_bytes"] == 2 * 2 * 96 * 4 * 2 * 32 * 4
    # twice the batcher's width, whatever the K/V arena's size
    assert state_slots_for(spec, 5376, 16, 8) == 16
    assert state_slots_for(spec, 64, 16, 4) == 8
    from bloombee_tpu.server.block_selection import estimate_block_bytes

    lin, full = (estimate_block_bytes(spec, jnp.float32, i) for i in (0, 3))
    moe = D * 8 + 4 * 3 * D * 32 + 3 * D * 32 + D + 4 * D
    assert full == 4 * (2 * D * 128 + 2 * D * 64 + 128 * D + 64 + moe)
    assert lin == 4 * (D * 192 + D * 256 + 64 * D + 4 * 128 + 16 + 8 + moe)


# ------------------------------------------------------------ refusals
def _tree(t):
    return (np.tril(np.ones((1, t, t), bool)),
            np.arange(t, dtype=np.int32)[None])


async def _refuse_tree_step(ex, m, h):
    mask, depths = _tree(3)
    ex.decode(h, _hidden(0, 3), commit=False, tree_mask=mask, depths=depths)


async def _refuse_tree_group(ex, m, h):
    assert "recurrent state" in ex.ragged_unsupported(has_tree=True)
    assert ex.ragged_unsupported(has_tree=False) is None
    mask, depths = _tree(3)
    ex.ragged_group([h], [_hidden(0, 3)], tree_masks=[mask],
                    depths_list=[depths])


async def _refuse_accept(ex, m, h):
    m.accept_speculative(h, [np.asarray([0])])


async def _refuse_decode_n(ex, m, h):
    ex.decode_n(h, np.zeros((1,), np.int32), 2, {})


async def _refuse_dense_forward(ex, m, h):
    from bloombee_tpu.runtime.layer_body import dense_block_forward

    dense_block_forward({}, ex.spec, jnp.zeros((1, 1, D)), None, None, None)


async def _refuse_two_chunks_in_a_pack(ex, m, h):
    async with m.allocate(1, 64) as other:
        ex.ragged_group([h, other], [_hidden(0, 3), _hidden(1, 2)],
                        tree_masks=[None, None], depths_list=[None, None])


@pytest.mark.parametrize("name,call,error,reason", [
    ("two_chunks_in_a_ragged_pack", _refuse_two_chunks_in_a_pack, ValueError,
     "ONE sequence of more than one row"),
    ("tree_verify_solo", _refuse_tree_step, ValueError, "tree verify"),
    ("tree_verify_group", _refuse_tree_group, ValueError, "recurrent state"),
    ("speculative_accept", _refuse_accept, ValueError, "speculative accept"),
    ("decode_n", _refuse_decode_n, ValueError, "recurrent state"),
    ("drafter_dense_forward", _refuse_dense_forward, NotImplementedError,
     "linear-attention layers"),
])
def test_a_step_that_cannot_carry_recurrent_state_refuses(
        span, name, call, error, reason):
    async def run():
        ex = _executor(span)
        async with ex.manager.allocate(1, 64) as h:
            ex.prefill_chunk(h, _hidden(0, 8), commit=True)
            with pytest.raises(error, match=reason):
                await call(ex, ex.manager, h)

    asyncio.run(run())


@pytest.mark.parametrize("kw,reason", [
    (dict(mesh="tp"), "--tp"),
    (dict(sp_mesh="sp"), "--sp"),
    (dict(host_layers=[{}]), "weight offload"),
    (dict(adapters={"a": {}}), "LoRA adapters unsupported for qwen3_next"),
    (dict(start_block=2), "whole periods of 4 layers"),
])
def test_an_executor_that_cannot_serve_the_family_refuses(span, kw, reason):
    with pytest.raises(ValueError, match=reason):
        _executor(span, **kw)


def test_a_manager_of_the_wrong_arenas_refuses(span, ckpt):
    params, spec = span
    with pytest.raises(ValueError, match="state_slots"):
        _manager(spec, state_slots=0)
    with pytest.raises(ValueError, match="quantized"):
        _manager(spec, quant="int4")
    with pytest.raises(ValueError, match="a row a full layer"):
        _executor(span, _manager(spec, arena_layers=(8, 8)))
    with pytest.raises(ValueError, match="whole periods of 4 layers"):
        load_span_params(str(ckpt), 0, 6, dtype=jnp.float32, experts=HELD)
    with pytest.raises(ValueError, match="whole periods of 4 layers"):
        load_span_params(str(ckpt), 2, 6, dtype=jnp.float32, experts=HELD)
    with pytest.raises(ValueError, match="outside the router's 8"):
        load_span_params(str(ckpt), 0, 8, experts=(6, 4))
    # a later period alone is a span like any other
    tail, _ = load_span_params(str(ckpt), 4, 8, dtype=jnp.float32,
                               experts=HELD)
    assert stacked_layers(tail) == 4


def test_pages_cannot_be_adopted_replicated_or_parked(span):
    async def run():
        m = _manager(span[1], prefix_cache=True)
        ex = _executor(span, m)
        assert m.prefix_cache is False and m.repl_supported is False
        async with m.allocate(1, 64) as h:
            ex.prefill_chunk(h, _hidden(0, 16), commit=True)
            assert m.adopt_prefix(h, [["a", "b"]]) == [0]
            assert m.export_pages(h.seq_ids[0], 0, 1) is None
            assert m.install_replicated(
                ["x"], np.zeros((1,)), np.zeros((1,))) == 0
            free = m.table.free_pages
            m.park_sequence(h.seq_ids[0])
            assert not m.has_parked(h) and m.table.free_pages == free
        assert m.state_refusals == {"prefix cache": 1, "host park": 1}

    asyncio.run(run())


@pytest.mark.parametrize("how", ["truncate", "rollback", "commit_shorter"])
def test_a_cut_to_a_position_above_zero_loses_the_session(span, how):
    async def run():
        ex = _executor(span)
        m = ex.manager
        async with m.allocate(1, 64) as h:
            ex.prefill_chunk(h, _hidden(0, 8), commit=True)
            ex.decode(h, _hidden(1, 1), commit=False)
            ex.decode(h, _hidden(2, 1), commit=False)
            assert m.epoch_valid(h)
            if how == "truncate":
                m.truncate_speculative(h, [9])
            elif how == "rollback":
                m.rollback(h)
            else:
                m.commit(h, lengths=[9])
            assert not m.epoch_valid(h)
        assert m.state_refusals == {"rollback to a position > 0": 1}

    asyncio.run(run())


# ---------------------------------- through a BlockServer and a client
def _family_logits(ckpt, ids, rows):
    client = reference.read_safetensors(
        ckpt / checkpoint.file_name(checkpoint.CLIENT_SHARD))
    hidden = _reference_hidden(ckpt, FAMILY.embed(client, CONFIG, ids))
    with jax.default_matmul_precision("highest"):
        return np.asarray(FAMILY.logits_rows(
            client, CONFIG, jnp.asarray(hidden[rows])))


async def _swarm(ckpt, **server_kw):
    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.server.block_server import BlockServer
    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer

    reg = RegistryServer(host="127.0.0.1")
    await reg.start()
    server_kw.setdefault("num_pages", 64)
    server = BlockServer(
        model_uid="tiny-q3n", start=0, end=LAYERS, model_dir=str(ckpt),
        registry=RegistryClient("127.0.0.1", reg.port), experts=HELD,
        compute_dtype=jnp.float32, page_size=4, **server_kw)
    await server.start()
    model = DistributedModelForCausalLM.from_pretrained(
        str(ckpt), RegistryClient("127.0.0.1", reg.port),
        model_uid="tiny-q3n", dtype=jnp.float32)
    return reg, server, model


@pytest.mark.parametrize("mixed", [False, True], ids=["solo", "mixed-batch"])
def test_client_logits_through_a_block_server_match_the_family_file(
        ckpt, mixed):
    """The normal path: a client, one BlockServer told `--experts 2:4`,
    prefill in chunks of 16 with a tail of 5, then decode through both
    arenas; the client's LOGITS against the family file's full forward.
    The tolerance is float32's: a thousandth of the spread of a row's
    logits (0.02)."""
    ids = np.random.default_rng(40).integers(0, CONFIG["vocab_size"], (1, 43))

    async def run():
        reg, server, model = await _swarm(
            ckpt, prefill_chunk=16, mixed_batch=mixed)
        try:
            rows = []
            async with model.inference_session(64, 1) as session:
                out = await session.step(
                    model.embed(ids[:, :37]), ids=ids[:, :37])
                rows.append(model.logits(out[:, -1:])[0, 0])
                for t in range(37, 43):
                    out = await session.step(
                        model.embed(ids[:, t:t + 1]), ids=ids[:, t:t + 1])
                    rows.append(model.logits(out)[0, 0])
            from bloombee_tpu.wire.rpc import connect

            conn = await connect("127.0.0.1", server.port)
            info, _ = await conn.call("rpc_info", {})
            await conn.close()
            return np.stack(rows), info
        finally:
            await server.stop()
            await reg.stop()

    with jax.default_matmul_precision("highest"):
        got, info = asyncio.run(asyncio.wait_for(run(), 280))
    want = _family_logits(ckpt, ids[0], list(range(36, 43)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert float(want.std()) > 1e3 * 2e-5
    assert info["kernel_fallbacks"] == 0 and info["prefill_chunks"] >= 3
    assert info["experts_held"] == [2, 4]
    assert info["layer_kinds"] == {"linear": 6, "full": 2}
    assert info["memory"]["kv_arena_layers"] == 2
    assert info["memory"]["state_arena_layers"] == 6
    reach = info["moe_reach"]
    assert reach["rows"] == 43 and len(reach["held_hit_last"]) == LAYERS


def test_server_side_refusals_carry_their_reason(ckpt):
    """At the server: kv_put declines, a ragged replay commit is refused
    with its reason, no training stack, decode_n says why not, tree rows are
    declined with the reason in `rpc_info["ragged_declines"]`, and `health
    --probe` prints the layer kinds and each arena's layers."""
    async def run():
        reg, server, model = await _swarm(
            ckpt, prefix_cache=True, mixed_batch=True, spec_batch=True)
        try:
            assert server.training is None
            assert server.spec_batch is False and server.mixed_batch is True
            assert "recurrent state" in server._decode_n_ineligible()
            resp, _ = await server._kv_put(
                {"page_size": 4, "start": 0, "end": 8, "hashes": []}, [])
            assert resp["installed"] == 0
            assert "recurrent state" in resp["reason"]
            async with server.manager.allocate(2, 32) as handle:
                session = type("S", (), dict(
                    last_step_at=0.0, id="s", n_steps=0, layers=None,
                    adapter=None, adoption_settled=False))()
                with pytest.raises(ValueError, match="ragged replay"):
                    server._compute_step(
                        session, handle, np.zeros((2, 4, D), np.float32),
                        False, None, commit_lens=[4, 2])
            from bloombee_tpu.wire.rpc import connect

            conn = await connect("127.0.0.1", server.port)
            info, _ = await conn.call("rpc_info", {})
            await conn.close()
            proc = await asyncio.create_subprocess_exec(
                sys.executable, "-m", "bloombee_tpu.cli.health",
                "tiny-q3n", "--registry", f"127.0.0.1:{reg.port}",
                "--num-blocks", str(LAYERS), "--probe",
                stdout=asyncio.subprocess.PIPE,
                stderr=asyncio.subprocess.STDOUT, cwd=str(ROOT))
            out, _ = await asyncio.wait_for(proc.communicate(), 60)
            return info, out.decode()
        finally:
            await server.stop()
            await reg.stop()

    info, health = asyncio.run(asyncio.wait_for(run(), 280))
    declines = info["ragged_declines"]
    assert declines["prefix cache"] == 1
    assert declines["ragged replay commit"] == 1
    assert declines["recurrent state (tree rows would branch it)"] == 1
    assert "layer_kinds=full:2,linear:6" in health
    assert "memory.kv=layers:2" in health
    assert "memory.state.layers=6" in health


@pytest.mark.parametrize("kw,reason", [
    (dict(tp=2), "--tp .tensor-parallel serving. unsupported for qwen3_next"),
    (dict(kv_quant="int4"), "quantized"),
    (dict(start=1, end=5), "whole periods of 4 layers"),
], ids=["tp", "int4-kv", "half-periods"])
def test_a_server_the_family_cannot_serve_refuses_at_start_up(ckpt, kw, reason):
    from bloombee_tpu.server.block_server import BlockServer

    kw = {"start": 0, "end": LAYERS, **kw}
    with pytest.raises(ValueError, match=reason):
        BlockServer(model_uid="x", model_dir=str(ckpt), experts=HELD,
                    compute_dtype=jnp.float32, page_size=4, num_pages=16,
                    **kw)
