"""Kimi Linear: Kimi delta attention (a delta rule whose decay is a vector a
key channel) in three layers of four, latent attention without positions in
the fourth, a LATENT page arena beside a state arena in one span step, the
model's leading dense layer inside the first period and a short last period,
a sigmoid router with a bias over the experts a server HOLDS.

Tiny widths on the CPU (7 layers L L L F L L F, 8 router outputs of which 4
held, hidden 64), seeded. The mathematics under test has ONE plain copy, the
benchmark's family file (cellbench/families/kimi_linear.py: the recurrence
token by token, attention expanded, no cache); everything the program serves
(the chunk form in sub-blocks, absorbed attention, both arenas, two runs of
periods) is held to that file.
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
from bloombee_tpu.models.layout import (  # noqa: E402
    LEAD,
    split_kinds,
    split_runs,
    stacked_layers,
)
from bloombee_tpu.ops.linear_attention import (  # noqa: E402
    gdn_sequence,
    kda_chunk,
    kda_sequence,
    kda_step,
    l2_normalize,
)
from bloombee_tpu.runtime.executor import SpanExecutor  # noqa: E402
from cellbench import checkpoint, families, reference  # noqa: E402

# 8 router outputs, top-3; this checkpoint holds experts 2-5
CONFIG = {
    "model_type": "kimi_linear", "hidden_size": 64, "intermediate_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_use_nope": True,
    "linear_attn_config": {
        "full_attn_layers": [4, 7], "kda_layers": [1, 2, 3, 5, 6],
        "head_dim": 16, "num_heads": 4, "short_conv_kernel_size": 4},
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "moe_intermediate_size": 32, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_expert_group": 1,
    "topk_group": 1, "use_grouped_topk": True, "num_experts": 4,
    "router_experts": 8, "experts_held": [2, 4], "num_experts_per_token": 3,
    "num_shared_experts": 1, "routed_scaling_factor": 2.446,
    "num_hidden_layers": 7, "vocab_size": 128, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "rope_scaling": None, "hidden_act": "silu",
    "model_max_length": 512, "num_nextn_predict_layers": 0,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
}
D, LAYERS = CONFIG["hidden_size"], CONFIG["num_hidden_layers"]
FAMILY = families.of(CONFIG)
HELD = tuple(CONFIG["experts_held"])
KERNELS = {"BBTPU_PAGED_INTERPRET": "1", "BBTPU_PAGED_MIN_CONTEXT": "0",
           "BBTPU_FLASH_INTERPRET": "1"}
# float32 at `highest` on both sides: what is left is the order of sums (the
# chunk form's sub-blocks and triangular solve against the token loop, the
# absorbed products against the expanded ones, the dense experts against the
# family file's blocks). A KDA layer's update is 0.05 of a residual that
# grows to 0.2, so these tolerances are a thousandth of one layer's update;
# `test_the_tolerance_sees_a_scalar_decay_and_a_bfloat16_state` shows what
# they catch
TOL = dict(rtol=2e-4, atol=2e-6)


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    path = tmp_path_factory.mktemp("tiny_kimi_linear")
    checkpoint.write_checkpoint(path, CONFIG, 51)
    return path


@pytest.fixture(scope="module")
def span(ckpt):
    return load_span_params(
        str(ckpt), 0, LAYERS, dtype=jnp.float32, experts=HELD)


def _reference_hidden(ckpt, hidden, lo=0, hi=LAYERS, config=CONFIG):
    """The family file's layers [lo, hi) over one sequence's hidden states
    [T, D]."""
    with jax.default_matmul_precision("highest"):
        h, pos = jnp.asarray(hidden), jnp.arange(hidden.shape[0])
        for layer in range(lo, hi):
            h = FAMILY.layer_forward(
                reference.layer_params(ckpt, config, layer), config, h, pos)
        return np.asarray(h)


def _manager(spec, lo=0, hi=LAYERS, **kw):
    kw.setdefault("state_slots", 6)
    kw.setdefault("arena_layers", spec.arena_layers(lo, hi))
    kw.setdefault("payload", spec.mla.page_payload)
    return CacheManager(
        hi - lo, 96, 4, spec.num_key_value_heads, spec.head_dim,
        dtype=jnp.float32, ssm=spec.recurrent, **kw)


def _executor(span, manager=None, **kw):
    params, spec = span
    return SpanExecutor(params, spec, manager or _manager(spec),
                        compute_dtype=jnp.float32, **kw)


def _hidden(seed, t, b=1):
    return (0.05 * np.random.default_rng(seed).standard_normal(
        (b, t, D))).astype(np.float32)


# ------------------------------------------------ the rule's two forms
def _rule_inputs(seed, t, h=3, k=16, v=8, decay=1.0):
    """q, k normalised; g a VECTOR a key channel, from forgetting in a token
    (-decay) to never; a state that is not empty."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    return (l2_normalize(f(t, h, k)) * k ** -0.5, l2_normalize(f(t, h, k)),
            f(t, h, v),
            -jnp.asarray(rng.uniform(0, decay, (t, h, k)), jnp.float32),
            jnp.asarray(rng.uniform(0, 1, (t, h)), jnp.float32),
            f(h, k, v))


def _scan(q, k, v, g, beta, s0):
    """The reference's recurrence: the family file's four lines over rows."""
    def step(s, row):
        q_t, k_t, v_t, g_t, beta_t = row
        s = s * jnp.exp(g_t)[:, :, None]
        u = (v_t - jnp.einsum("hkv,hk->hv", s, k_t)) * beta_t[:, None]
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    s, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
    return o, s


def _token_by_token(q, k, v, g, beta, s):
    outs = []
    for t in range(q.shape[0]):
        o, s = kda_step(*(x[t:t + 1] for x in (q, k, v, g, beta)), s[None])
        outs.append(o[0])
        s = s[0]
    return jnp.stack(outs), s


@pytest.mark.parametrize("t,chunk,sub", [
    (200, 64, 16), (64, 64, 16), (37, 64, 16), (128, 16, 16), (150, 64, 64),
    (7, 64, 16)])
def test_chunk_form_equals_step_form_equals_the_reference_scan(t, chunk, sub):
    """The sub-blocked chunk form against one rule step a token against the
    reference's scan, from a state that is not empty: blocks of 64 in
    sub-blocks of 16, blocks of ONE sub-block (16; 64 taken exactly), and
    ragged tails (200 = 3 x 64 + 8, 37, 7) padded with rows of beta = g = 0."""
    q, k, v, g, beta, s0 = _rule_inputs(t, t)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = _scan(q, k, v, g, beta, s0)
        step_o, step_s = _token_by_token(q, k, v, g, beta, s0)
        got_o, got_s = kda_sequence(q, k, v, g, beta, s0, chunk, sub)
    assert got_o.shape == want_o.shape
    for got, want in ((step_o, want_o), (step_s, want_s), (got_o, want_o),
                      (got_s, want_s)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-6)


def test_forgetting_within_a_token_stays_finite_and_equals_the_scan():
    """The stability case: g of -20 a token a channel over 256 rows (the
    published initialisation reaches -16). exp(G_i - G_j) between rows of a
    block is at most 1 everywhere; a form that factors it into
    (k_i exp(G_i)) . (k_j exp(-G_j)) takes exp(+1280) over a 64-row block
    and fails, shown beside it."""
    q, k, v, _, beta, s0 = _rule_inputs(3, 256)
    g = jnp.full((256, 3, 16), -20.0, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = _scan(q, k, v, g, beta, s0)
        got_o, got_s = kda_sequence(q, k, v, g, beta, s0, 64)
    assert bool(jnp.isfinite(got_o).all() and jnp.isfinite(got_s).all())
    np.testing.assert_allclose(got_o, want_o, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(got_s, want_s, rtol=1e-5, atol=1e-7)
    assert float(jnp.abs(want_o).max()) > 1e-3  # each row reads its own write
    cum = jnp.cumsum(g[:64], axis=0)
    factored = jnp.einsum(
        "ihc,jhc->hij", k[:64] * jnp.exp(cum), k[:64] * jnp.exp(-cum))
    assert not bool(jnp.isfinite(jnp.tril(factored.transpose(1, 2, 0))).all())


def test_no_exponent_of_the_chunk_form_is_positive(monkeypatch):
    """Every `exp` the chunk form takes has an argument <= 0 (or -inf under
    a mask), whatever the decay: read off the calls themselves."""
    from bloombee_tpu.ops import linear_attention

    largest = []
    real_exp = jnp.exp

    def watched(x):
        largest.append(float(jnp.max(x)))
        return real_exp(x)

    monkeypatch.setattr(linear_attention.jnp, "exp", watched)
    q, k, v, g, beta, s0 = _rule_inputs(5, 128, decay=20.0)
    kda_chunk(q, k, v, g, beta, s0)
    assert len(largest) >= 6 and max(largest) <= 0.0


def test_a_decay_constant_over_a_heads_channels_is_gated_deltanet():
    """The tie to the parent rule: with g the same for a head's channels the
    vector rule equals `gdn_sequence` to rounding, outputs and state."""
    q, k, v, g, beta, s0 = _rule_inputs(9, 192, decay=0.3)
    scalar = g[..., 0]
    with jax.default_matmul_precision("highest"):
        want_o, want_s = gdn_sequence(q, k, v, scalar, beta, s0, 64)
        got_o, got_s = kda_sequence(
            q, k, v, jnp.broadcast_to(scalar[..., None], g.shape), beta, s0,
            64)
    np.testing.assert_allclose(got_o, want_o, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_s, want_s, rtol=1e-5, atol=1e-6)


def test_the_tolerance_sees_a_scalar_decay_and_a_bfloat16_state(ckpt):
    """What TOL catches, in the reference's own arithmetic on the tiny
    model's layer 1: the decay replaced by its mean over a head's channels
    (the program computes Gated DeltaNet) and the state S rounded to
    bfloat16 after every token both move the mixer's output by far more
    than TOL allows."""
    p = jax.tree.map(
        lambda a: jnp.asarray(a).astype(jnp.float32),
        reference.layer_params(ckpt, CONFIG, 1))
    h = jnp.asarray(_hidden(21, 96)[0]) * 20
    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta = FAMILY.kda_inputs(p, CONFIG, h)
        s0 = jnp.zeros((4, 16, 16), jnp.float32)
        want, _ = _scan(q, k, v, g, beta, s0)
        mean = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
        scalar, _ = _scan(q, k, v, mean, beta, s0)

        def rounded(s, row):
            q_t, k_t, v_t, g_t, beta_t = row
            s = s * jnp.exp(g_t)[:, :, None]
            u = (v_t - jnp.einsum("hkv,hk->hv", s, k_t)) * beta_t[:, None]
            s = (s + k_t[:, :, None] * u[:, None, :]).astype(
                jnp.bfloat16).astype(jnp.float32)
            return s, jnp.einsum("hkv,hk->hv", s, q_t)

        _, low = jax.lax.scan(rounded, s0, (q, k, v, g, beta))
    allowed = TOL["atol"] + TOL["rtol"] * np.abs(np.asarray(want))
    for other in (scalar, low):
        over = np.abs(np.asarray(other) - np.asarray(want)) / allowed
        assert float(np.median(over)) > 5, float(np.median(over))
    # the fills make the decay differ inside a head: no constant
    assert float((g.max(-1) / g.min(-1)).mean()) < 0.5


# ----------------------------- the program's steps against the family file
@pytest.mark.parametrize("kernels", [False, True], ids=["dense", "kernels"])
def test_prefill_in_chunks_then_decode_matches_the_family_file(
        ckpt, span, kernels, monkeypatch):
    """Chunks of 21 and 16 rows (a 32-row bucket's padded tail; the chunk
    form) then decode rows (the step form) through the latent arena and the
    state arena, against the reference's FULL forward; the span is two runs
    of periods (the first with the dense layer, the short tail). With
    kernels on, the chunks attend through the latent flash form and the
    decode rows through the paged latent kernel (interpreted)."""
    for k, v in (KERNELS if kernels else {}).items():
        monkeypatch.setenv(k, v)
    h = _hidden(3, 43)
    want = _reference_hidden(ckpt, h[0])
    ex = _executor(span)

    async def run():
        m = ex.manager
        async with m.allocate(1, 64) as handle:
            got = [np.asarray(ex.prefill(handle, h[:, :21]))[0],
                   np.asarray(ex.prefill(handle, h[:, 21:37]))[0]]
            for t in range(37, 43):
                got.append(np.asarray(ex.decode(handle, h[:, t:t + 1]))[0])
            return np.concatenate(got)

    with jax.default_matmul_precision("highest"):
        got = asyncio.run(run())
    np.testing.assert_allclose(got, want, **TOL)
    assert ex.kernel_fallbacks == 0
    # the layers did something: the output is no rounding of the input
    assert float(np.abs(want - h[0]).max()) > 0.05


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
    """The mixed batch: a ragged pack (one sequence's 89-row chunk beside
    another's decode row, in a 128-row bucket whose padding rows belong to
    no one) and the packed decode group (two rows in a bucket), each row
    against its own sequence's reference."""
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
    # a share of the experts held: the six sparse layers are counted, the
    # dense one is not; the router's bias brings its two counters
    ex.fetch(jnp.zeros(()))
    assert len(ex.moe_reach["held_hit_last"]) == LAYERS - 1


def test_padding_rows_leave_state_and_tail_as_they_were(span):
    """A 5-row chunk in an 8-row bucket: S and the convolution's tail of
    every KDA layer equal the run that took those rows one by one."""
    h = _hidden(8, 12)

    def run(pad: bool):
        ex = _executor(span)

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
                        np.asarray(m.state["conv"][:, slot]))

        with jax.default_matmul_precision("highest"):
            return asyncio.run(go())

    (s_pad, tail_pad), (s_ref, tail_ref) = run(True), run(False)
    assert s_ref.shape[0] == 5 and float(np.abs(s_ref).max()) > 0
    np.testing.assert_allclose(s_pad, s_ref, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(tail_pad, tail_ref, rtol=1e-4, atol=1e-6)


def test_a_session_entering_mid_span_skips_the_leading_period(ckpt, span):
    """`layer_active` gates layer by layer inside both runs' scans: layers
    [4, 7) alone equal the reference's tail, and the skipped period's rows
    of both arenas stay zero."""
    h = _hidden(11, 20)
    want = _reference_hidden(ckpt, h[0], 4, 7)
    ex = _executor(span)

    async def run():
        async with ex.manager.allocate(1, 64) as handle:
            return np.asarray(ex.prefill(handle, h, layers=(4, 7)))[0]

    with jax.default_matmul_precision("highest"):
        got = asyncio.run(run())
    np.testing.assert_allclose(got, want, **TOL)
    m = ex.manager
    assert float(jnp.abs(m.arena["k"][0]).max()) == 0.0
    assert float(jnp.abs(m.arena["k"][1]).max()) > 0.0
    assert float(jnp.abs(m.state["ssm"][:3]).max()) == 0.0
    assert float(jnp.abs(m.state["ssm"][3:]).max()) > 0.0


# -------------------------------------------------------- the share test
def test_four_shares_add_up_to_the_uncut_layer(tmp_path):
    """Four shares of two experts each: the shares' routed partial sums plus
    the shared expert counted ONCE add up to the uncut reference's sparse
    MLP, in the reference and in the program's expert form alike (sigmoid
    scores, the bias on the choice alone, renormalised, times 2.446)."""
    from bloombee_tpu.ops.moe import moe_mlp

    whole = dict(CONFIG, num_experts=8, experts_held=[0, 8])
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
        shared = FAMILY._silu_mlp(
            x, p_all["s_gate"], p_all["s_up"], p_all["s_down"])
        total = program = shared
        for first in range(0, 8, 2):
            share = dict(whole, num_experts=2, experts_held=[first, 2])
            p = f32(FAMILY.layer_params(tensors, share, layer))
            total = total + FAMILY.moe(x, p, share) - shared
            program = program + moe_mlp(
                x[None], None, *(
                    jnp.swapaxes(p[f"e_{k}"], 1, 2)
                    for k in ("gate", "up", "down")),
                3, norm_topk=True, held=(first, 2), sigmoid=True,
                route_scale=2.446, router_logits=(x @ p["router"].T)[None],
                router_bias=p["expert_bias"])[0]
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5 * scale)
    np.testing.assert_allclose(program, want, rtol=1e-4, atol=1e-5 * scale)
    # the routed part counts: it is no rounding of the shared expert's output
    assert float(jnp.abs(want - shared).max()) > 0.2 * scale


# --------------------------------------------- the loader, arenas, accounting
def test_the_span_loads_two_runs_of_periods_as_the_steps_read_them(span, ckpt):
    params, spec = span
    assert spec.layer_types == (
        "linear", "linear", "linear", "full", "linear", "linear", "full")
    assert spec.period_runs(0, 7) == (
        (("linear+dense", "linear", "linear", "full"), 1),
        (("linear", "linear", "full"), 1))
    assert stacked_layers(params) == LAYERS
    lead, main = split_runs(params)
    assert all(k.startswith(LEAD) or k in main for k in params)
    (lin_lead, full_lead), (lin_main, full_main) = (
        split_kinds(lead), split_kinds(main))
    assert (len(lin_lead), len(lin_main)) == (3, 2)
    # layer 0: the dense MLP, no router; every other layer: the 4 held
    assert lin_lead[0]["gate_proj"].shape == (1, D, 96)
    assert "router_t" not in lin_lead[0] and "gate_proj" not in lin_lead[1]
    for stack in (*lin_lead[1:], *lin_main, full_lead, full_main):
        assert stack["experts_gate"].shape == (1, 4, D, 32)
        assert stack["router_t"].shape == (1, 8, D)  # over ALL experts
        assert stack["expert_bias"].dtype == jnp.float32
    for stack in (*lin_lead, *lin_main):
        assert stack["gdn_in_proj"].shape == (1, D, 3 * 64)
        assert stack["gdn_conv_w"].shape == (1, 4, 3 * 64)
        assert stack["gdn_low_proj"].shape == (1, D, 3 * 128)
        assert stack["gdn_f_b_proj"].shape == (1, 16, 64)
        assert stack["gdn_a_log"].shape == (1, 4)
        assert stack["gdn_dt_bias"].shape == (1, 64)
        assert "q_b_nope" not in stack
    for stack in (full_lead, full_main):
        assert stack["q_b_nope"].shape == (1, 4 * 16, D)
        assert stack["q_b_rope"].shape == (1, 4 * 8, D)
        assert stack["kv_a_proj"].shape == (1, 32 + 8, D)
        assert "q_a_proj" not in stack and "gdn_in_proj" not in stack
    assert (spec.num_experts, spec.moe_held) == (8, HELD)
    assert (spec.mla.q_rank, spec.mla.rope, spec.gdn.channel_decay,
            spec.gdn.gate_rank, spec.gdn.scope) == (0, False, True, 16, "kda")
    # the three projections side by side, the narrow ones in their lanes
    raw = reference.read_safetensors(
        ckpt / checkpoint.file_name(checkpoint.layer_tag(1)))
    a = "model.layers.1.self_attn."
    got = np.asarray(lin_lead[1]["gdn_in_proj"][0]).T
    np.testing.assert_array_equal(
        got[64:128], np.asarray(raw[a + "k_proj.weight"], np.float32))
    low = np.asarray(lin_lead[1]["gdn_low_proj"][0]).T
    np.testing.assert_array_equal(
        low[128:144], np.asarray(raw[a + "g_a_proj.weight"], np.float32))
    np.testing.assert_array_equal(
        low[256:260], np.asarray(raw[a + "b_proj.weight"], np.float32))
    assert not low[16:128].any() and not low[260:].any()
    # a head's query rows: its nope dims, then its shared-key dims
    raw = reference.read_safetensors(
        ckpt / checkpoint.file_name(checkpoint.layer_tag(3)))
    q = np.asarray(
        raw["model.layers.3.self_attn.q_proj.weight"], np.float32)
    np.testing.assert_array_equal(
        np.asarray(full_lead["q_b_nope"][0, 16:32]), q[24:40])
    np.testing.assert_array_equal(
        np.asarray(full_lead["q_b_rope"][0, 8:16]), q[40:48])


def test_arena_layer_counts_follow_the_kinds(span):
    """Latent rows = full layers, state rows = KDA layers; a layer finds its
    row among its kind; the bytes follow."""
    _, spec = span
    assert spec.arena_layers(0, 7) == (2, 5)
    assert spec.arena_layers(4, 7) == (1, 2)
    assert spec.cache_rows(0, 7) == (
        ("state", 0), ("state", 1), ("state", 2), ("kv", 0),
        ("state", 3), ("state", 4), ("kv", 1))
    m = _manager(spec)
    # a latent page: the latent, and the shared key in whole lanes
    assert m.arena["k"].shape == (2, 96 * 4, 32)
    assert m.arena["v"].shape == (2, 96 * 4, 128)
    assert m.state["ssm"].shape == (5, 6, 4, 16, 16)
    assert m.state["conv"].shape == (5, 6, 3, 3 * 64)
    stats = m.memory_stats()
    assert (stats["kv_arena_layers"], stats["state_arena_layers"]) == (2, 5)
    # twice the batcher's width, whatever the latent arena's size
    assert state_slots_for(spec, 5376, 16, 8) == 16
    from bloombee_tpu.server.block_selection import (
        estimate_block_bytes,
        kv_token_bytes,
    )

    assert kv_token_bytes(spec, 2) == (32 + 128) * 2
    moe = 8 * D + 4 * 3 * D * 32 + 3 * D * 32
    kda = (D * 192 + D * 384 + 2 * 16 * 64 + 64 * D + 4 * 192 + 16 + 4 + 64)
    mla = D * 4 * 24 + D * 40 + 32 * 4 * 32 + 64 * D + 32
    assert [estimate_block_bytes(spec, jnp.float32, i) for i in (0, 1, 3)] == [
        4 * (kda + 3 * D * 96 + 4 * D), 4 * (kda + moe + 4 * D),
        4 * (mla + moe + 4 * D)]


# ------------------------------------------------------------ refusals
@pytest.mark.parametrize("start,end,reason", [
    (0, 4, None), (4, 7, None), (0, 7, None),
    (1, 4, "whole periods of 4 layers"), (0, 3, "whole periods of 4 layers"),
    (0, 6, "the model's last period has 3"), (5, 7, "from a period's first"),
    (0, 8, "whole periods"),
])
def test_a_span_is_whole_periods_or_it_is_refused(span, start, end, reason):
    got = span[1].span_unsupported(start, end)
    assert (got is None) if reason is None else (reason in got), got


def test_a_span_of_three_kinds_of_period_is_refused(ckpt):
    """The published depth's shape in small: the period with the dense
    layer, whole periods, the short tail: three runs, one more than a span
    may hold (a server takes [0, 8) or [8, 11))."""
    import types

    from bloombee_tpu.models.kimi_linear import kimi_linear_spec_from_hf

    config = dict(CONFIG, num_hidden_layers=11, linear_attn_config=dict(
        CONFIG["linear_attn_config"], full_attn_layers=[4, 8, 11],
        kda_layers=[1, 2, 3, 5, 6, 7, 9, 10]))
    spec = kimi_linear_spec_from_hf(types.SimpleNamespace(**config))
    assert [n for _, n in spec.period_runs(0, 11)] == [1, 1, 1]
    assert "two runs of like periods" in spec.span_unsupported(0, 11)
    assert spec.span_unsupported(0, 8) is None
    assert spec.span_unsupported(4, 11) is None
    assert spec.span_unsupported(8, 11) is None


@pytest.mark.parametrize("change,reason", [
    (dict(mla_use_nope=False), "mla_use_nope"),
    (dict(q_lora_rank=24), "low-rank queries"),
    (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
    (dict(moe_router_activation_func="softmax"), "moe_router_activation"),
    (dict(num_expert_group=2, topk_group=1), "group-limited"),
    (dict(linear_attn_config=dict(
        CONFIG["linear_attn_config"], kda_layers=[1, 2, 3, 5])),
     "name each of the 7 layers once"),
])
def test_a_config_the_family_cannot_run_is_refused(change, reason):
    import types

    from bloombee_tpu.models.kimi_linear import kimi_linear_spec_from_hf

    with pytest.raises(NotImplementedError, match=reason):
        kimi_linear_spec_from_hf(
            types.SimpleNamespace(**dict(CONFIG, **change)))


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
    (dict(adapters={"a": {}}), "LoRA adapters unsupported for kimi_linear"),
    (dict(start_block=2), "whole periods of 4 layers"),
])
def test_an_executor_that_cannot_serve_the_family_refuses(span, kw, reason):
    with pytest.raises(ValueError, match=reason):
        _executor(span, **kw)


def test_a_manager_of_the_wrong_arenas_refuses(span, ckpt):
    params, spec = span
    with pytest.raises(ValueError, match="state_slots"):
        _manager(spec, state_slots=0)
    with pytest.raises(ValueError, match="no int4 form"):
        _manager(spec, quant="int4")
    with pytest.raises(ValueError, match="a row a full layer"):
        _executor(span, _manager(spec, arena_layers=(7, 7)))
    with pytest.raises(ValueError, match="whole periods of 4 layers"):
        load_span_params(str(ckpt), 0, 6, dtype=jnp.float32, experts=HELD)
    with pytest.raises(ValueError, match="outside the router's 8"):
        load_span_params(str(ckpt), 0, 7, experts=(6, 4))
    # the model's tail alone is a span like any other
    tail, _ = load_span_params(str(ckpt), 4, 7, dtype=jnp.float32,
                               experts=HELD)
    assert stacked_layers(tail) == 3 and split_runs(tail)[0] is None


def test_what_a_latent_family_carries_this_one_refuses(span):
    """A latent page can be adopted from a prefix, exported to a standby and
    parked on the host (deepseek_v2 does all three); a sequence here also
    owns recurrent state no page holds, so the refusals ask
    `ModelSpec.recurrent` FIRST and this family takes the recurrent
    families' side of each."""
    _, spec = span
    assert spec.mla is not None and spec.recurrent is spec.gdn

    async def run():
        m = _manager(spec, prefix_cache=True)
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


# ---------------------------------- through BlockServers and a client
def _family_logits(ckpt, ids, rows):
    client = reference.read_safetensors(
        ckpt / checkpoint.file_name(checkpoint.CLIENT_SHARD))
    hidden = _reference_hidden(ckpt, FAMILY.embed(client, CONFIG, ids))
    with jax.default_matmul_precision("highest"):
        return np.asarray(FAMILY.logits_rows(
            client, CONFIG, jnp.asarray(hidden[rows])))


async def _swarm(ckpt, spans=((0, LAYERS),), **server_kw):
    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.server.block_server import BlockServer
    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer

    reg = RegistryServer(host="127.0.0.1")
    await reg.start()
    server_kw.setdefault("num_pages", 64)
    servers = []
    for start, end in spans:
        servers.append(BlockServer(
            model_uid="tiny-kimi", start=start, end=end, model_dir=str(ckpt),
            registry=RegistryClient("127.0.0.1", reg.port), experts=HELD,
            compute_dtype=jnp.float32, page_size=4, **server_kw))
        await servers[-1].start()
    model = DistributedModelForCausalLM.from_pretrained(
        str(ckpt), RegistryClient("127.0.0.1", reg.port),
        model_uid="tiny-kimi", dtype=jnp.float32)
    return reg, servers, model


async def _rpc_info(server):
    from bloombee_tpu.wire.rpc import connect

    conn = await connect("127.0.0.1", server.port)
    info, _ = await conn.call("rpc_info", {})
    await conn.close()
    return info


@pytest.mark.parametrize("spans,mixed", [
    (((0, 7),), False), (((0, 7),), True), (((0, 4), (4, 7)), True)],
    ids=["solo", "mixed-batch", "two-servers"])
def test_client_logits_through_block_servers_match_the_family_file(
        ckpt, spans, mixed):
    """The normal path: a client, BlockServers told `--experts 2:4` (one
    holding the model; two holding [0, 4) and the tail [4, 7)), prefill in
    chunks of 16 with a tail of 5, then decode through both arenas; the
    client's LOGITS against the family file's full forward. The tolerance
    is float32's: a thousandth of the spread of a row's logits."""
    ids = np.random.default_rng(51).integers(0, CONFIG["vocab_size"], (1, 43))

    async def run():
        reg, servers, model = await _swarm(
            ckpt, spans, prefill_chunk=16, mixed_batch=mixed)
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
        got, infos = asyncio.run(asyncio.wait_for(run(), 280))
    want = _family_logits(ckpt, ids[0], list(range(36, 43)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert float(want.std()) > 1e3 * 2e-5
    kinds = {(0, 7): {"linear": 5, "full": 2}, (0, 4): {"linear": 3, "full": 1},
             (4, 7): {"linear": 2, "full": 1}}
    for (start, end), info in zip(spans, infos):
        assert info["kernel_fallbacks"] == 0 and info["prefill_chunks"] >= 3
        assert info["experts_held"] == [2, 4]
        assert info["layer_kinds"] == kinds[start, end]
        assert info["memory"]["kv_arena_layers"] == kinds[start, end]["full"]
        assert info["memory"]["state_arena_layers"] == kinds[
            start, end]["linear"]
        assert info["latent_bytes_per_token"] == 2 * (32 + 128)
        reach = info["moe_reach"]
        sparse = end - start - (start == 0)
        assert reach["rows"] == 43 and len(reach["held_hit_last"]) == sparse


def test_server_side_refusals_carry_their_reason(ckpt):
    """At the server: kv_put declines, a ragged replay commit is refused
    with its reason, no training stack, decode_n says why not, tree rows are
    declined with the reason in `rpc_info["ragged_declines"]`, and `health
    --probe` prints the layer kinds, each arena's layers and a cached
    token's latent bytes."""
    async def run():
        reg, (server,), model = await _swarm(
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
            info = await _rpc_info(server)
            proc = await asyncio.create_subprocess_exec(
                sys.executable, "-m", "bloombee_tpu.cli.health",
                "tiny-kimi", "--registry", f"127.0.0.1:{reg.port}",
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
    assert "layer_kinds=full:2,linear:5" in health
    assert "memory.kv=layers:2" in health
    assert "memory.state.layers=5" in health


@pytest.mark.parametrize("kw,reason", [
    (dict(tp=2), "--tp .tensor-parallel serving. unsupported for kimi_linear"),
    (dict(kv_quant="int4"), "a latent page .* has no int4 form"),
    (dict(start=1, end=5), "whole periods of 4 layers"),
    (dict(start=4, end=6), "the model's last period has 3"),
], ids=["tp", "int4-kv", "half-periods", "cut-tail"])
def test_a_server_the_family_cannot_serve_refuses_at_start_up(ckpt, kw, reason):
    from bloombee_tpu.server.block_server import BlockServer

    kw = {"start": 0, "end": LAYERS, **kw}
    with pytest.raises(ValueError, match=reason):
        BlockServer(model_uid="x", model_dir=str(ckpt), experts=HELD,
                    compute_dtype=jnp.float32, page_size=4, num_pages=16,
                    **kw)


def test_full_rank_latent_queries_are_one_code_for_deepseek_v2_too():
    """`q_lora_rank: null` (DeepSeek-V2-Lite) was refused until this family
    brought the full-rank query: now the spec says `q_rank` 0, the loader
    cuts `q_proj`'s rows as it cuts `q_b_proj`'s (the rotary rows
    de-interleaved), and the layer body feeds them the hidden rows."""
    import types

    from bloombee_tpu.models.checkpoint import split_query_rows
    from bloombee_tpu.models.deepseek_v2 import deepseek_v2_spec_from_hf
    from bloombee_tpu.ops.rotary import deinterleave

    lite = dict(
        hidden_size=64, intermediate_size=96, num_attention_heads=4,
        num_hidden_layers=2, vocab_size=128, rms_norm_eps=1e-6,
        q_lora_rank=None, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, n_routed_experts=None)
    spec = deepseek_v2_spec_from_hf(types.SimpleNamespace(**lite))
    assert (spec.mla.q_rank, spec.mla.rope) == (0, True)
    q = np.arange(4 * 12 * 64, dtype=np.float32).reshape(4 * 12, 64)
    got = split_query_rows(q, 4, 8, 4, perm=deinterleave(4))
    assert got["q_b_nope"].shape == (32, 64) and got["q_b_rope"].shape == (16, 64)
    np.testing.assert_array_equal(got["q_b_nope"][8:16], q[12:20])
    # head 1's rotary rows 20..23, evens then odds
    np.testing.assert_array_equal(got["q_b_rope"][4:8], q[[20, 22, 21, 23]])
