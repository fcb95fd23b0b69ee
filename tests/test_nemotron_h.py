"""Nemotron-H: layers that are ONE sublayer each (a Mamba-2 mixer, an
ungated relu² expert layer or position-free GQA attention ALONE, one norm a
layer) in a pattern whose periods differ in length (6, 7, 7, 7, 7, 9 layers
and a tail of 9 with no attention layer), served as a list of runs of
repeated kinds by the ONE period scan; a K/V arena with a row an attention
layer, a state arena with a row a mixer layer, an expert layer in neither.

Tiny widths on the CPU (the WHOLE published pattern of 52 layers, hidden 64,
8 router outputs of which 4 held, an expert width of 24 that the loader pads
to 128), seeded. The mathematics under test has ONE plain copy, the
benchmark's family file (cellbench/families/nemotron_h.py: the recurrence
token by token, no cache, no kernels); everything the program serves (the
chunked scan, both arenas, the runs, the three expert forms) is held to it.
"""

import asyncio
import pathlib
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bloombee_tpu.kv.cache_manager import CacheManager  # noqa: E402
from bloombee_tpu.models.checkpoint import load_span_params  # noqa: E402
from bloombee_tpu.models.layout import (  # noqa: E402
    lane_padded,
    period_stacks,
    stacked_layers,
)
from bloombee_tpu.ops.moe import expert_form, moe_mlp  # noqa: E402
from bloombee_tpu.runtime.executor import SpanExecutor  # noqa: E402
from cellbench import checkpoint, families, reference  # noqa: E402

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# the deployment's four pipeline stages
STAGES = ((0, 13), (13, 27), (27, 43), (43, 52))
# 8 router outputs, top-3; this checkpoint holds experts 0-3
CONFIG = {
    "model_type": "nemotron_h", "hidden_size": 64, "intermediate_size": 24,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8, "expand": 2,
    "hybrid_override_pattern": PATTERN, "num_hidden_layers": len(PATTERN),
    "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 48,
    "n_shared_experts": 1, "n_routed_experts": 4, "router_experts": 8,
    "experts_held": [0, 4], "num_experts_per_tok": 3, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "layer_norm_epsilon": 1e-5, "norm_eps": 1e-5, "vocab_size": 128,
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
    "use_conv_bias": True, "use_bias": False, "mamba_proj_bias": False,
    "attention_bias": False, "mlp_bias": False, "tie_word_embeddings": False,
    "max_position_embeddings": 512, "rope_theta": 10000,
    "partial_rotary_factor": 1, "sliding_window": None,
    "torch_dtype": "bfloat16",
}
D, LAYERS = CONFIG["hidden_size"], CONFIG["num_hidden_layers"]
FAMILY = families.of(CONFIG)
HELD = tuple(CONFIG["experts_held"])
KERNELS = {"BBTPU_PAGED_INTERPRET": "1", "BBTPU_PAGED_MIN_CONTEXT": "0",
           "BBTPU_FLASH_INTERPRET": "1"}
KINDS = {"M": "mamba", "E": "moe", "*": "full"}


def _counts(lo, hi):
    return {KINDS[c]: PATTERN[lo:hi].count(c) for c in "ME*"
            if c in PATTERN[lo:hi]}


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    path = tmp_path_factory.mktemp("tiny_nemotron_h")
    checkpoint.write_checkpoint(path, CONFIG, 54)
    return path


def _span(ckpt, lo, hi, held=HELD):
    return load_span_params(
        str(ckpt), lo, hi, dtype=jnp.float32, experts=held)


@pytest.fixture(scope="module")
def stage1(ckpt):
    """Layers 13-26, EMEMEM*EMEMEM*: the benchmark's span."""
    return _span(ckpt, 13, 27)


def _reference_hidden(ckpt, hidden, lo, hi, config=CONFIG):
    """The family file's layers [lo, hi) over one sequence's hidden states
    [T, D]."""
    with jax.default_matmul_precision("highest"):
        h, pos = jnp.asarray(hidden), jnp.arange(hidden.shape[0])
        for layer in range(lo, hi):
            h = FAMILY.layer_forward(
                reference.layer_params(ckpt, config, layer), config, h, pos)
        return np.asarray(h)


def _manager(spec, lo, hi, **kw):
    kw.setdefault("state_slots", 6)
    kw.setdefault("arena_layers", spec.arena_layers(lo, hi))
    return CacheManager(
        hi - lo, 96, 4, spec.num_key_value_heads, spec.head_dim,
        dtype=jnp.float32, ssm=spec.recurrent, **kw)


def _executor(span, lo, hi, manager=None, **kw):
    params, spec = span
    kw.setdefault("start_block", lo)
    return SpanExecutor(params, spec, manager or _manager(spec, lo, hi),
                        compute_dtype=jnp.float32, **kw)


def _hidden(seed, t, b=1):
    return (0.05 * np.random.default_rng(seed).standard_normal(
        (b, t, D))).astype(np.float32)


def _close(got, want, h_in, rel=2e-4):
    """`got` against `want`, the tolerance a share of what the layers ADDED
    to their input (every sublayer reads normed rows, so its update has the
    size its weights give it; at these widths that is a hundredth of the
    residual, and a tolerance on the sum would see nothing)."""
    added = float(np.abs(want - h_in).max())
    assert added > 1e-3
    np.testing.assert_allclose(got, want, rtol=0, atol=rel * added)


def _serve(ex, h):
    """Prefill in chunks of 16 with a ragged tail of 11, then two decode
    steps, through both arenas: [T, D]."""
    async def run():
        async with ex.manager.allocate(1, 120) as handle:
            got = [np.asarray(ex.prefill(handle, h[:, a:b]))[0]
                   for a, b in ((0, 16), (16, 32), (32, 43))]
            for t in (43, 44):
                got.append(np.asarray(ex.decode(handle, h[:, t:t + 1]))[0])
        return np.concatenate(got)

    with jax.default_matmul_precision("highest"):
        return asyncio.run(run())


# ------------------------------------------- the four deployment spans
@pytest.mark.parametrize("lo,hi", STAGES)
def test_each_deployment_span_matches_the_family_file(ckpt, lo, hi):
    """A span alone, fed the reference's hidden rows at its first layer:
    periods of 6 + 7, 7 + 7, 7 + 9 layers, and the 9-layer tail with no
    attention layer, whose K/V arena has NO row."""
    span = _span(ckpt, lo, hi)
    params, spec = span
    assert stacked_layers(params) == hi - lo
    assert spec.arena_layers(lo, hi) == (
        PATTERN[lo:hi].count("*"), PATTERN[lo:hi].count("M"))
    ex = _executor(span, lo, hi)
    h = _hidden(lo, 45)
    got = _serve(ex, h)
    _close(got, _reference_hidden(ckpt, h[0], lo, hi), h[0])
    assert ex.kernel_fallbacks == 0
    m = ex.manager
    assert m.arena["k"].shape[0] == PATTERN[lo:hi].count("*")
    assert m.state["ssm"].shape[0] == PATTERN[lo:hi].count("M")
    # a share of the experts held: one reach vector an expert layer
    ex.fetch(jnp.zeros(()))
    assert len(ex.moe_reach["held_hit_last"]) == PATTERN[lo:hi].count("E")
    assert ex.moe_reach["bias_moved_pairs"] >= 0


def test_the_spans_runs_are_the_patterns_repeats(ckpt, stage1):
    """EMEMEM*EMEMEM* is ONE run, the period twice; a period that stands
    alone is factored into pairs that repeat and single layers. One stack a
    (run, position), each [repeats, ...]; an expert layer holds TWO matrices
    an expert, its width padded to whole lanes with zeros."""
    params, spec = stage1
    period = ("moe", "mamba") * 3 + ("full",)
    assert spec.period_runs(13, 27) == ((period, 2),)
    assert spec.period_runs(0, 13) == (
        (("mamba", "moe"), 2), (("mamba",), 1), (("full",), 1),
        (("moe", "mamba"), 3), (("full",), 1))
    assert spec.period_runs(27, 43) == (
        (("moe", "mamba"), 3), (("full",), 1), (("moe", "mamba"), 4),
        (("full",), 1))
    assert spec.period_runs(43, 52) == ((("moe", "mamba"), 4), (("moe",), 1))
    assert spec.period_runs(6, 34) == ((period, 4),)
    runs = period_stacks(params)
    assert [len(r) for r in runs] == [7]
    moe, mamba = runs[0][:2]
    assert "experts_gate" not in moe and "shared_gate" not in moe
    wide = lane_padded(24)
    assert moe["experts_up"].shape == (2, 4, D, wide)
    assert moe["experts_down"].shape == (2, 4, wide, D)
    assert float(jnp.abs(moe["experts_up"][..., 24:]).max()) == 0.0
    assert float(jnp.abs(moe["experts_down"][:, :, 24:]).max()) == 0.0
    assert moe["router_t"].shape == (2, 8, D)  # ALL the router's experts
    assert moe["shared_up"].shape == (2, D, 48)
    proj = 32 + (32 + 2 * 2 * 16) + 4
    assert mamba["ssm_in_proj"].shape == (2, D, lane_padded(proj))
    assert set(runs[0][6]) == {
        "input_layernorm", "q_proj", "k_proj", "v_proj", "o_proj"}
    assert spec.moe_held is None or spec.moe_held == HELD
    assert (spec.num_experts, spec.experts_held) == (8, HELD)
    assert not spec.rope and spec.one_sublayer and spec.mlp_type == "relu2"


@pytest.mark.parametrize("kernels", [False, True], ids=["dense", "kernels"])
def test_fused_pack_and_decode_group_match_the_family_file(
        ckpt, stage1, monkeypatch, kernels):
    """Two sequences: solo chunks, then ONE ragged dispatch (a decode row of
    `a` beside an 89-row chunk of `b`), then a decode group, then a solo
    decode; with the kernels on, through the grouped expert kernel, the
    paged decode kernel and the pack's attention by rows."""
    for k, v in (KERNELS if kernels else {}).items():
        monkeypatch.setenv(k, v)
    a, b = _hidden(4, 15), _hidden(5, 100)
    want_a, want_b = (_reference_hidden(ckpt, x[0], 13, 27) for x in (a, b))
    ex = _executor(stage1, 13, 27)

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
    _close(got_a, want_a, a[0])
    _close(got_b, want_b, b[0])
    assert ex.kernel_fallbacks == 0
    assert ex.attn_dispatches["ragged" if kernels else "dense"] >= 1
    assert (ex.moe_dispatches["grouped"] > 0) == kernels


def test_padding_rows_leave_a_state_slot_as_it_was(stage1):
    """A 5-row chunk in an 8-row bucket: S and the convolution's tail of
    every mixer layer equal the run that took those rows one by one; the
    state arena has a row a MIXER layer (6 of 14) and the K/V arena a row
    an ATTENTION layer (2), an expert layer neither (bytes asserted)."""
    h = _hidden(8, 12)
    _, spec = stage1

    def run(pad: bool):
        ex = _executor(stage1, 13, 27)

        async def go():
            m = ex.manager
            async with m.allocate(1, 64) as handle:
                ex.prefill(handle, h[:, :7])
                if pad:
                    ex.prefill(handle, h[:, 7:12])  # 5 rows, bucket 8
                else:
                    for t in range(7, 12):
                        ex.decode(handle, h[:, t:t + 1])
                slot = int(m.state_slots(handle)[0])
                return (np.asarray(m.state["ssm"][:, slot]),
                        np.asarray(m.state["conv"][:, slot]),
                        np.asarray(m.state["ssm"]), m)

        with jax.default_matmul_precision("highest"):
            return asyncio.run(go())

    s_pad, tail_pad, whole, m = run(True)
    s_ref, tail_ref, _, _ = run(False)
    assert s_ref.shape[0] == 6 and float(np.abs(s_ref).max()) > 0
    np.testing.assert_allclose(s_pad, s_ref, rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(tail_pad, tail_ref, rtol=1e-4, atol=1e-7)
    # one slot was written: every other slot of every row is untouched
    assert int((np.abs(whole).reshape(6, whole.shape[1], -1).max(-1) > 0
                ).sum()) == 6
    ssm = spec.ssm
    assert m.arena["k"].nbytes == 2 * 96 * 4 * 2 * 16 * 4
    assert m.state["ssm"].nbytes == 6 * 6 * ssm.heads * ssm.head_dim * ssm.state * 4
    assert m.state["conv"].shape == (6, 6, ssm.conv - 1, ssm.conv_dim)
    assert (m.kv_layers, m.state_layers) == (2, 6)


def test_a_session_entering_mid_span_skips_the_leading_period(ckpt, stage1):
    """`layer_active` gates layer by layer inside every run's scan: layers
    [20, 27) alone equal the reference's, and the skipped period's rows of
    both arenas stay zero."""
    h = _hidden(11, 20)
    want = _reference_hidden(ckpt, h[0], 20, 27)
    ex = _executor(stage1, 13, 27)

    async def run():
        async with ex.manager.allocate(1, 64) as handle:
            return np.asarray(ex.prefill(handle, h, layers=(7, 14)))[0]

    with jax.default_matmul_precision("highest"):
        got = asyncio.run(run())
    _close(got, want, h[0])
    m = ex.manager
    assert float(jnp.abs(m.arena["k"][0]).max()) == 0.0
    assert float(jnp.abs(m.arena["k"][1]).max()) > 0.0
    assert float(jnp.abs(m.state["ssm"][:3]).max()) == 0.0
    assert float(jnp.abs(m.state["ssm"][3:]).max()) > 0.0


# -------------------------------------------------------- the share test
def _expert_stacks(p):
    """The family file's [E, out, in] leaves as the program stores them."""
    return (jnp.swapaxes(p["e_up"], 1, 2), jnp.swapaxes(p["e_down"], 1, 2))


def test_two_shares_add_up_to_the_uncut_layer(tmp_path):
    """`--experts 0:4` and `4:4`: the halves' routed partial sums plus the
    shared expert counted ONCE add up to the uncut reference's expert layer,
    in the reference and in the program's expert form alike (sigmoid scores,
    the bias on the choice alone, renormalised, times 2.5, relu²)."""
    whole = dict(CONFIG, n_routed_experts=8, experts_held=[0, 8])
    checkpoint.write_checkpoint(tmp_path, whole, 52)
    layer = 1
    tensors = reference.read_safetensors(
        tmp_path / checkpoint.file_name(checkpoint.layer_tag(layer)))
    x = jnp.asarray(_hidden(9, 40)[0]) * 20
    with jax.default_matmul_precision("highest"):
        f32 = lambda p: jax.tree.map(  # noqa: E731
            lambda a: jnp.asarray(a).astype(jnp.float32), p)
        p_all = f32(FAMILY.layer_params(tensors, whole, layer))
        want = FAMILY.moe(x, p_all, whole)
        shared = FAMILY.relu2_mlp(x, p_all["s_up"], p_all["s_down"])
        total = program = shared
        for first in (0, 4):
            share = dict(whole, n_routed_experts=4, experts_held=[first, 4])
            p = f32(FAMILY.layer_params(tensors, share, layer))
            total = total + FAMILY.moe(x, p, share) - shared
            program = program + moe_mlp(
                x[None], None, None, *_expert_stacks(p), 3, norm_topk=True,
                held=(first, 4), sigmoid=True, route_scale=2.5,
                router_logits=(x @ p["router"].T)[None],
                router_bias=p["expert_bias"], activation="relu2")[0]
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5 * scale)
    np.testing.assert_allclose(program, want, rtol=1e-4, atol=1e-5 * scale)
    # the routed part counts: it is no rounding of the shared expert's output
    assert float(jnp.abs(want - shared).max()) > 0.2 * scale


# ------------------------------------------- the ungated form, three ways
@pytest.mark.parametrize("rows,form", [(2, "list"), (256, "tiled"),
                                       (40, "dense")])
def test_the_ungated_relu2_form_is_one_sum_in_every_expert_form(
        ckpt, rows, form):
    """down(relu(up(x)) ** 2) through the dense einsums, the grouped kernel
    (a list of the chosen experts) and the tiled kernel (the chosen pairs in
    row tiles), both in interpret mode, against the family file's sum; and
    the stacks padded from 24 to 128 columns of zeros give what the unpadded
    ones give."""
    layer = 13  # an E layer
    config = dict(CONFIG, experts_held=[0, 4])
    p = jax.tree.map(
        lambda a: jnp.asarray(a).astype(jnp.float32),
        reference.layer_params(ckpt, config, layer))
    x = jnp.asarray(_hidden(rows, rows)[0]) * 20
    up, down = _expert_stacks(p)
    pad = lane_padded(24) - 24
    up_p = jnp.pad(up, ((0, 0), (0, 0), (0, pad)))
    down_p = jnp.pad(down, ((0, 0), (0, pad), (0, 0)))
    kernels = form != "dense"
    assert expert_form(rows, 3, 8, kernels) == form

    def run(up, down):
        return moe_mlp(
            x[None], None, None, up, down, 3, norm_topk=True, held=HELD,
            sigmoid=True, route_scale=2.5,
            router_logits=(x @ p["router"].T)[None],
            router_bias=p["expert_bias"], activation="relu2",
            expert_base=jnp.int32(0) if kernels else None,
            interpret=True)[0]

    with jax.default_matmul_precision("highest"):
        want = FAMILY.moe(x, p, config) - FAMILY.relu2_mlp(
            x, p["s_up"], p["s_down"])
        got, got_padded = run(up, down) if not kernels else None, run(up_p, down_p)
    scale = float(jnp.abs(want).max())
    assert scale > 0
    np.testing.assert_allclose(got_padded, want, rtol=1e-4, atol=1e-5 * scale)
    if got is not None:
        np.testing.assert_allclose(got, got_padded, rtol=0, atol=1e-6 * scale)


def test_the_expert_kernels_weight_blocks_fit_at_the_published_width():
    """[2688, 1920] (1856 padded to whole lanes) in two matrices: a tile of
    640 columns, three tiles, 13.8 MB of double-buffered blocks; unpadded,
    1856 is no whole number of lanes and would go in whole (40 MB)."""
    from bloombee_tpu.ops.pallas import grouped_experts as ge

    assert lane_padded(1856) == 1920
    tile = ge._i_tile(2688, 1920, 2, matrices=2)
    assert (tile, 1920 // tile) == (640, 3)
    assert 2 * 2 * 2688 * tile * 2 <= ge._WEIGHT_BLOCKS_BYTES
    assert ge._i_tile(2688, 1856, 2, matrices=2) == 1856
    # the gated families' tiles are what they were
    assert ge._i_tile(2048, 768, 2) == 768 and ge._i_tile(7168, 2048, 2) == 256


# ------------------------------------------------- where a span may be cut
@pytest.mark.parametrize("start,end,reason", [
    (0, 12, "layer 11 closes no period: .* ends with layer 12"),
    (1, 13, "layer 1 stands inside the period that layer 0 opens"),
    (14, 27, "layer 14 stands inside the period that layer 13 opens"),
    (13, 26, "layer 25 closes no period"),
    (43, 51, "layer 50 closes no period: .* ends with layer 51"),
    (44, 52, "layer 44 stands inside the period that layer 43 opens"),
    (27, 60, "the model has layers 0-51"),
    (13, 13, "the model has layers 0-51"),
])
def test_a_span_is_whole_periods_or_it_is_refused(ckpt, start, end, reason):
    from bloombee_tpu.models.checkpoint import load_spec

    spec = load_spec(str(ckpt), HELD)
    assert spec.period_starts() == (0, 6, 13, 20, 27, 34, 43, 52)
    why = spec.span_unsupported(start, end)
    assert why is not None and "may be cut at layers [0, 6, 13" in why
    import re

    assert re.search(reason, why), why
    with pytest.raises(ValueError, match="whole periods of the pattern"):
        load_span_params(str(ckpt), start, end, experts=HELD)
    for lo, hi in STAGES + ((0, 52), (6, 13), (34, 43), (20, 34)):
        assert spec.span_unsupported(lo, hi) is None


def test_block_selection_cuts_only_where_the_family_allows(ckpt):
    from bloombee_tpu.models.checkpoint import load_spec
    from bloombee_tpu.server.block_selection import (
        choose_best_blocks,
        estimate_span_bytes,
    )
    from bloombee_tpu.swarm.data import ModuleInfo

    spec = load_spec(str(ckpt), HELD)
    infos = [ModuleInfo(uid=f"m.{i}", servers={}) for i in range(LAYERS)]
    assert choose_best_blocks(infos, {}, 14, spec=spec) == (6, 20)
    assert choose_best_blocks(infos, {}, 16, spec=spec) == (27, 43)
    assert choose_best_blocks(infos, {}, 9, spec=spec) == (34, 43)
    with pytest.raises(ValueError, match="no window of 5 blocks"):
        choose_best_blocks(infos, {}, 5, spec=spec)
    # a span's bytes by kind are the loaded stacks' bytes
    params, _ = _span(ckpt, 13, 27)
    assert estimate_span_bytes(spec, jnp.float32, 13, 27) == sum(
        leaf.size * 4 for leaf in jax.tree.leaves(params))


@pytest.mark.parametrize("change,reason", [
    (dict(hybrid_override_pattern="ME-" + PATTERN[3:]), "M, E or \\*"),
    (dict(hybrid_override_pattern=PATTERN[:-1]), "must name each of the 52"),
    (dict(mlp_hidden_act="silu"), "mlp_hidden_act 'silu'"),
    (dict(n_group=2), "group-limited router"),
    (dict(use_conv_bias=False), "conv bias"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(time_step_limit=[0.001, 0.1]), "clamped time step"),
])
def test_a_config_the_family_cannot_run_is_refused(change, reason):
    from bloombee_tpu.models.nemotron_h import nemotron_h_spec_from_hf

    with pytest.raises(NotImplementedError, match=reason):
        nemotron_h_spec_from_hf(
            types.SimpleNamespace(**dict(CONFIG, **change)))


# ------------------------------- what a recurrent state refuses, as ever
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
     "a mixer or an expert layer alone"),
])
def test_a_step_that_cannot_carry_recurrent_state_refuses(
        ckpt, name, call, error, reason):
    # the tail [43, 52): the smallest span, and the one with no K/V row
    span = _span(ckpt, 43, 52)

    async def run():
        ex = _executor(span, 43, 52)
        async with ex.manager.allocate(1, 64) as h:
            ex.prefill_chunk(h, _hidden(0, 8), commit=True)
            with pytest.raises(error, match=reason):
                await call(ex, ex.manager, h)

    asyncio.run(run())


@pytest.mark.parametrize("kw,reason", [
    (dict(mesh="tp"), "--tp"),
    (dict(sp_mesh="sp"), "--sp"),
    (dict(host_layers=[{}]), "weight offload"),
    (dict(adapters={"a": {}}), "LoRA adapters unsupported for nemotron_h"),
    (dict(start_block=44), "whole periods of the pattern"),
])
def test_an_executor_that_cannot_serve_the_family_refuses(ckpt, kw, reason):
    span = _span(ckpt, 43, 52)
    with pytest.raises(ValueError, match=reason):
        _executor(span, 43, 52, **kw)


def test_a_manager_of_the_wrong_arenas_refuses(ckpt):
    span = _span(ckpt, 43, 52)
    _, spec = span
    with pytest.raises(ValueError, match="state_slots"):
        _manager(spec, 43, 52, state_slots=0)
    with pytest.raises(ValueError, match="a row a full layer"):
        _executor(span, 43, 52, _manager(spec, 43, 52, arena_layers=(9, 9)))
    with pytest.raises(ValueError, match="outside the router's 8"):
        load_span_params(str(ckpt), 43, 52, experts=(6, 4))


def test_what_pages_alone_could_carry_this_family_refuses(ckpt):
    """Prefix adoption, export to a standby and host parking are refused as
    for every family with recurrent state (`ModelSpec.recurrent`)."""
    span = _span(ckpt, 43, 52)
    _, spec = span
    assert spec.recurrent is spec.ssm

    async def run():
        m = _manager(spec, 43, 52, prefix_cache=True)
        ex = _executor(span, 43, 52, m)
        assert m.prefix_cache is False and m.repl_supported is False
        async with m.allocate(1, 64) as h:
            ex.prefill_chunk(h, _hidden(0, 16), commit=True)
            assert m.adopt_prefix(h, [["a", "b"]]) == [0]
            assert m.export_pages(h.seq_ids[0], 0, 1) is None
            free = m.table.free_pages
            m.park_sequence(h.seq_ids[0])
            assert not m.has_parked(h) and m.table.free_pages == free
        assert m.state_refusals == {"prefix cache": 1, "host park": 1}

    asyncio.run(run())


@pytest.mark.parametrize("how", ["truncate", "rollback", "commit_shorter"])
def test_a_cut_to_a_position_above_zero_loses_the_session(ckpt, how):
    span = _span(ckpt, 43, 52)

    async def run():
        ex = _executor(span, 43, 52)
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


# ---------------------------------- through BlockServers and a client
def _family_logits(ckpt, ids, rows):
    client = reference.read_safetensors(
        ckpt / checkpoint.file_name(checkpoint.CLIENT_SHARD))
    hidden = _reference_hidden(
        ckpt, FAMILY.embed(client, CONFIG, ids), 0, LAYERS)
    with jax.default_matmul_precision("highest"):
        return np.asarray(FAMILY.logits_rows(
            client, CONFIG, jnp.asarray(hidden[rows])))


async def _swarm(ckpt, spans=STAGES, **server_kw):
    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.server.block_server import BlockServer
    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer

    reg = RegistryServer(host="127.0.0.1")
    await reg.start()
    server_kw.setdefault("num_pages", 64)
    servers = []
    for start, end in spans:
        servers.append(BlockServer(
            model_uid="tiny-nemotron", start=start, end=end,
            model_dir=str(ckpt), experts=HELD,
            registry=RegistryClient("127.0.0.1", reg.port),
            compute_dtype=jnp.float32, page_size=4, **server_kw))
        await servers[-1].start()
    model = DistributedModelForCausalLM.from_pretrained(
        str(ckpt), RegistryClient("127.0.0.1", reg.port),
        model_uid="tiny-nemotron", dtype=jnp.float32)
    return reg, servers, model


async def _rpc_info(server):
    from bloombee_tpu.wire.rpc import connect

    conn = await connect("127.0.0.1", server.port)
    info, _ = await conn.call("rpc_info", {})
    await conn.close()
    return info


def test_the_four_stages_chained_match_the_whole_published_pattern(ckpt):
    """The normal path: a client and FOUR BlockServers told `--experts 0:4`,
    the deployment's stages [0, 13), [13, 27), [27, 43), [43, 52); prefill
    in chunks of 16 with a ragged tail of 5, then decode through both
    arenas; the client's LOGITS against the family file's one full forward
    over all 52 layers. Every server names PUBLISHED layer indices."""
    ids = np.random.default_rng(54).integers(0, CONFIG["vocab_size"], (1, 43))

    async def run():
        reg, servers, model = await _swarm(
            ckpt, prefill_chunk=16, mixed_batch=True)
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
            return np.stack(rows), [await _rpc_info(s) for s in servers]
        finally:
            for server in servers:
                await server.stop()
            await reg.stop()

    with jax.default_matmul_precision("highest"):
        got, infos = asyncio.run(asyncio.wait_for(run(), 560))
    want = _family_logits(ckpt, ids[0], list(range(36, 43)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert float(want.std()) > 1e3 * 2e-5
    for (start, end), info in zip(STAGES, infos):
        kinds = _counts(start, end)
        assert info["kernel_fallbacks"] == 0 and info["prefill_chunks"] >= 3
        assert info["experts_held"] == list(HELD)
        assert info["layer_kinds"] == kinds
        assert info["memory"]["kv_arena_layers"] == kinds.get("full", 0)
        assert info["memory"]["state_arena_layers"] == kinds["mamba"]
        reach = info["moe_reach"]
        assert reach["rows"] == 43
        assert len(reach["held_hit_last"]) == kinds["moe"]
        assert "bias_moved_pairs" in reach
        assert set(info["moe"]) == {
            "grouped_dispatches", "tiled_dispatches", "dense_dispatches"}


def test_server_side_refusals_carry_their_reason(ckpt):
    """At the server: kv_put declines, a ragged replay commit is refused
    with its reason, no training stack, no fused decode_n, tree rows are
    declined with the reason in `rpc_info["ragged_declines"]`, and `health
    --probe` prints the layer kinds and each arena's layers."""
    async def run():
        reg, (server,), model = await _swarm(
            ckpt, ((43, 52),), prefix_cache=True, mixed_batch=True,
            spec_batch=True)
        try:
            assert server.training is None
            assert server.spec_batch is False and server.mixed_batch is True
            # (a stage is not the whole model: decode_n is off before it
            # asks about the state; the executor's own refusal is above)
            assert server._decode_n_ineligible() is not None
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
            info = await _rpc_info(server)
            proc = await asyncio.create_subprocess_exec(
                sys.executable, "-m", "bloombee_tpu.cli.health",
                "tiny-nemotron", "--registry", f"127.0.0.1:{reg.port}",
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
    assert "layer_kinds=mamba:4,moe:5" in health
    assert "memory.kv=layers:0" in health
    assert "memory.state.layers=4" in health


@pytest.mark.parametrize("kw,reason", [
    (dict(tp=2), "--tp .tensor-parallel serving. unsupported for nemotron_h"),
    (dict(start=14, end=27), "layer 14 stands inside the period"),
    (dict(start=43, end=50), "layer 49 closes no period"),
], ids=["tp", "inside-a-period", "cut-tail"])
def test_a_server_the_family_cannot_serve_refuses_at_start_up(ckpt, kw, reason):
    from bloombee_tpu.server.block_server import BlockServer

    kw = {"start": 43, "end": LAYERS, **kw}
    with pytest.raises(ValueError, match=reason):
        BlockServer(model_uid="x", model_dir=str(ckpt), experts=HELD,
                    compute_dtype=jnp.float32, page_size=4, num_pages=16,
                    **kw)
