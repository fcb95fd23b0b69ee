"""Falcon-H1: a state-space mixer beside attention in every layer, served
through the paged span step with a recurrent-state arena beside the KV arena.

Tiny widths on the CPU, seeded. The mathematics under test has ONE plain
copy, the benchmark's family file (cellbench/families/falcon_h1.py), which
(b) ties to the published implementation (`transformers`'
FalconH1ForCausalLM); everything the program serves is held to that file.
"""

import asyncio
import json
import pathlib
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bloombee_tpu.kv.cache_manager import (  # noqa: E402
    AllocationTimeout,
    CacheManager,
    state_slots_for,
)
from bloombee_tpu.models.checkpoint import load_span_params  # noqa: E402
from bloombee_tpu.ops.ssm import (  # noqa: E402
    conv_taps,
    ssd_chunk,
    ssd_sequence,
    ssm_step,
)
from bloombee_tpu.runtime.executor import SpanExecutor  # noqa: E402
from cellbench import checkpoint, families, reference  # noqa: E402

CONFIG = {
    "model_type": "falcon_h1", "architectures": ["FalconH1ForCausalLM"],
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 128, "rms_norm_eps": 1e-05, "rope_theta": 100000000000,
    "rope_scaling": None, "hidden_act": "silu", "attention_bias": False,
    "mlp_bias": False, "projectors_bias": False, "mamba_proj_bias": False,
    "mamba_conv_bias": True, "mamba_rms_norm": True,
    "mamba_norm_before_gate": False, "mamba_use_mlp": True,
    "mamba_d_ssm": 48, "mamba_n_heads": 6, "mamba_d_head": 8,
    "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 8, "mamba_expand": 2,
    # multipliers far from 1, each different: a misplaced one shows
    "attention_in_multiplier": 1.25, "attention_out_multiplier": 0.5,
    "embedding_multiplier": 5.65, "key_multiplier": 0.5,
    "lm_head_multiplier": 0.0078125, "mlp_multipliers": [0.7, 0.5],
    "ssm_in_multiplier": 1.5, "ssm_multipliers": [1.3, 2.0, 3.0, 2.5, 1.1],
    "ssm_out_multiplier": 0.9, "max_position_embeddings": 4096,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
}
D = CONFIG["hidden_size"]
FAMILY = families.of(CONFIG)


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    path = tmp_path_factory.mktemp("tiny_falcon_h1")
    checkpoint.write_checkpoint(path, CONFIG, 30)
    return path


@pytest.fixture(scope="module")
def span(ckpt):
    return load_span_params(str(ckpt), 0, 2, dtype=jnp.float32)


def _reference_hidden(ckpt, hidden):
    """The family file's layers over one sequence's hidden states [T, D]."""
    with jax.default_matmul_precision("highest"):
        h, pos = jnp.asarray(hidden), jnp.arange(hidden.shape[0])
        for layer in range(CONFIG["num_hidden_layers"]):
            h = FAMILY.layer_forward(
                reference.layer_params(ckpt, CONFIG, layer), CONFIG, h, pos)
        return np.asarray(h)


def _manager(spec, **kw):
    kw.setdefault("state_slots", 8)
    return CacheManager(
        2, 64, 16, spec.num_key_value_heads, spec.head_dim,
        dtype=jnp.float32, ssm=spec.ssm, **kw)


def _executor(span, manager=None, **kw):
    params, spec = span
    manager = manager or _manager(spec)
    return SpanExecutor(params, spec, manager, compute_dtype=jnp.float32, **kw)


def _hidden(seed, t):
    return np.random.default_rng(seed).standard_normal((1, t, D)).astype(
        np.float32)


# ---------------------------------------------------------------- the ops
def _scan_inputs(t, seed=0):
    rng = np.random.default_rng(seed)
    h, p, g, n = 6, 8, 2, 16
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    return dict(
        x=f(t, h, p), dt=jax.nn.softplus(f(t, h)), a=-jnp.exp(f(h)),
        b=f(t, g, n), c=f(t, g, n), d=f(h), s0=f(h, p, n))


def _by_steps(v):
    s, ys = v["s0"], []
    for i in range(v["x"].shape[0]):
        y, s = ssm_step(v["x"][i:i + 1], v["dt"][i:i + 1], v["a"],
                        v["b"][i:i + 1], v["c"][i:i + 1], v["d"], s[None])
        ys.append(y[0])
        s = s[0]
    return jnp.stack(ys), s


@pytest.mark.parametrize("split", [None, 1, 7, 12])
def test_one_chunk_equals_single_steps_equals_two_chunks(split):
    """(c) T tokens in one chunk == T single steps == two chunks split
    anywhere, the second starting from the first's state."""
    v = _scan_inputs(13)
    y_steps, s_steps = _by_steps(v)
    if split is None:
        y, s = ssd_chunk(v["x"], v["dt"], v["a"], v["b"], v["c"], v["d"], v["s0"])
    else:
        cut = lambda z, lo, hi: z[lo:hi]  # noqa: E731
        parts, s = [], v["s0"]
        for lo, hi in ((0, split), (split, 13)):
            y_part, s = ssd_chunk(
                cut(v["x"], lo, hi), cut(v["dt"], lo, hi), v["a"],
                cut(v["b"], lo, hi), cut(v["c"], lo, hi), v["d"], s)
            parts.append(y_part)
        y = jnp.concatenate(parts)
    np.testing.assert_allclose(y, y_steps, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(s, s_steps, rtol=2e-5, atol=2e-5)


def test_sequence_walks_chunks_of_the_configured_length():
    v = _scan_inputs(32, seed=1)
    y_one, s_one = ssd_chunk(v["x"], v["dt"], v["a"], v["b"], v["c"], v["d"], v["s0"])
    y, s = ssd_sequence(v["x"], v["dt"], v["a"], v["b"], v["c"], v["d"],
                        v["s0"], chunk=8)
    np.testing.assert_allclose(y, y_one, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(s, s_one, rtol=2e-5, atol=2e-5)


def test_rows_with_zero_dt_leave_the_state_bit_equal():
    """(c) padding rows and bucket tails: dt == 0 on the rows past the real
    ones. WHATEVER those rows hold, the state after the chunk is the same
    bit for bit; against the run without them it agrees to the last ulp or
    two (a matmul over 5 rows and one over 8 sum in another order: the
    padding contributes exact zeros, the order of the rest is the
    compiler's). One step with dt == 0 returns its state bit for bit."""
    v = _scan_inputs(8, seed=2)
    real = 5
    _, s_unpadded = ssd_chunk(
        v["x"][:real], v["dt"][:real], v["a"], v["b"][:real], v["c"][:real],
        v["d"], v["s0"])
    dt = v["dt"].at[real:].set(0.0)
    states = []
    for garbage in (0.0, 1e3):
        x = v["x"].at[real:].set(garbage)
        b = v["b"].at[real:].set(garbage)
        y, s = ssd_chunk(x, dt, v["a"], b, v["c"], v["d"], v["s0"])
        states.append(np.asarray(s))
    assert np.array_equal(states[0], states[1])
    np.testing.assert_allclose(states[0], s_unpadded, rtol=1e-6, atol=1e-6)
    y_step, s_step = ssm_step(
        v["x"][:1], jnp.zeros_like(v["dt"][:1]), v["a"], v["b"][:1],
        v["c"][:1], v["d"], v["s0"][None])
    assert np.array_equal(np.asarray(s_step[0]), np.asarray(v["s0"]))


def test_conv_taps_reach_into_the_tail_and_keep_it_for_idle_sequences():
    rng = np.random.default_rng(3)
    c, k = 5, 4
    tails = jnp.asarray(rng.standard_normal((3, k - 1, c)), jnp.float32)
    xbc = jnp.asarray(rng.standard_normal((8, c)), jnp.float32)
    # seq 0: rows 0..4 (5 real of a bucket of 6), seq 1: row 6, seq 2: idle
    q_seq = jnp.asarray([0, 0, 0, 0, 0, 0, 1, 3], jnp.int32)
    row0 = jnp.asarray([0, 6, 7], jnp.int32)
    nt = jnp.asarray([5, 1, 0], jnp.int32)
    taps, new = conv_taps(xbc, tails, q_seq, row0, nt)
    ext0 = np.concatenate([tails[0], xbc[:6]])
    for i in range(5):
        np.testing.assert_array_equal(taps[i], ext0[i:i + k])
    np.testing.assert_array_equal(
        taps[6], np.concatenate([tails[1], xbc[6:7]]))
    np.testing.assert_array_equal(new[0], ext0[5:8])  # after 5 REAL rows
    np.testing.assert_array_equal(
        new[1], np.concatenate([tails[1][1:], xbc[6:7]]))
    np.testing.assert_array_equal(new[2], tails[2])  # untouched


# -------------------------------------------- (b) the reference and the code
def test_family_reference_matches_transformers():
    """(b) cellbench/families/falcon_h1.py against transformers'
    FalconH1ForCausalLM (torch path, float32) on the same weights."""
    torch = pytest.importorskip("torch")
    from transformers import FalconH1Config, FalconH1ForCausalLM

    hf_config = FalconH1Config(
        **{k: v for k, v in CONFIG.items()
           if k not in ("model_type", "architectures", "torch_dtype")})
    torch.manual_seed(0)
    model = FalconH1ForCausalLM(hf_config).float().eval()
    with torch.no_grad():
        for name, p in model.named_parameters():
            if "A_log" in name:
                p.copy_(torch.log(torch.rand_like(p) * 15 + 1))
            elif "dt_bias" in name:
                p.copy_(torch.rand_like(p) * 0.1)
            elif "norm" in name or name.endswith(".D"):
                p.copy_(1 + 0.1 * torch.randn_like(p))
            else:
                p.copy_(torch.randn_like(p) * 0.3)
    tensors = {k: v.detach().numpy() for k, v in model.state_dict().items()}
    ids = np.random.default_rng(0).integers(0, CONFIG["vocab_size"], (1, 21))
    with torch.no_grad():
        want = model(torch.tensor(ids)).logits[0].numpy()
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(FAMILY.embed(tensors, CONFIG, ids[0]))
        for layer in range(CONFIG["num_hidden_layers"]):
            h = FAMILY.layer_forward(
                FAMILY.layer_params(tensors, CONFIG, layer), CONFIG, h,
                jnp.arange(ids.shape[1]))
        got = np.asarray(FAMILY.logits_rows(tensors, CONFIG, h))
    # float32 on both sides, the same operations in another order (torch's
    # chunked SSD against a scan over positions, another matmul blocking):
    # agreement to a few ulps of the largest logit (0.5 here)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


# --------------------------------- (c) through the packed and ragged programs
def test_chunked_prefill_with_a_tail_then_decode_matches_the_reference(
        ckpt, span):
    hidden = _hidden(1, 45)
    want = _reference_hidden(ckpt, hidden[0])

    async def run():
        ex = _executor(span)
        async with ex.manager.allocate(1, 128) as handle:
            # chunks of 16, 16 and a tail of 5 in a bucket of 8, then decode
            outs = [ex.prefill_chunked(handle, hidden[:, :37], 16)]
            for t in range(37, 45):
                outs.append(ex.decode(handle, hidden[:, t:t + 1]))
            return np.concatenate(outs, 1)[0]

    with jax.default_matmul_precision("highest"):
        got = asyncio.run(run())
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def _state_of(manager, handle):
    slot = int(manager.state_slots(handle)[0])
    return (np.asarray(manager.state["ssm"][:, slot]),
            np.asarray(manager.state["conv"][:, slot]))


@pytest.mark.parametrize("program", ["packed", "ragged"])
def test_a_padded_bucket_leaves_state_and_tail_as_the_unpadded_run(
        span, program):
    """(c) 5 tokens run as ONE chunk in a bucket of 8 (packed) or packed
    with another session's decode row into a bucket of 8 rows (ragged) leave
    the state and the convolution's tail where 5 single steps leave them."""
    hidden = _hidden(2, 16)
    other = _hidden(3, 9)

    async def run(as_chunk):
        ex = _executor(span)
        m = ex.manager
        async with m.allocate(1, 64) as a, m.allocate(1, 64) as b:
            ex.prefill_chunk(a, hidden[:, :8])
            ex.prefill_chunk(b, other[:, :8])
            if not as_chunk:
                for t in range(8, 13):
                    ex.decode(a, hidden[:, t:t + 1], commit=False)
            elif program == "packed":
                ex.prefill_chunk(a, hidden[:, 8:13])
            else:
                ex.ragged_group([b, a], [other[:, 8:9], hidden[:, 8:13]])
            return _state_of(m, a)

    with jax.default_matmul_precision("highest"):
        (s_chunk, tail_chunk), (s_steps, tail_steps) = (
            asyncio.run(run(True)), asyncio.run(run(False)))
    # (the tail's rows are projection outputs: another row bucket, another
    # matmul blocking, so an ulp and not a bit)
    np.testing.assert_allclose(tail_chunk, tail_steps, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(s_chunk, s_steps, rtol=1e-5, atol=1e-6)


def test_a_bucket_tail_holding_garbage_changes_no_bit(span):
    """(c) the packed program's bucket tail: a 5-token chunk as 8 rows of
    which the plan says 5 are real. Rows 5..7 holding zeros or garbage give
    the same state, tail and real outputs bit for bit; the same chunk as 5
    rows gives the same tail bit for bit and the same state to an ulp."""
    from bloombee_tpu.runtime.layer_body import _ssm_mixer, packed_ssm_rows

    params, spec = span
    layer0 = jax.tree.map(lambda x: x[0], params)
    ssm = spec.ssm
    state = {
        "ssm": jnp.asarray(np.random.default_rng(5).standard_normal(
            (4, ssm.heads, ssm.head_dim, ssm.state)), jnp.float32),
        "conv": jnp.asarray(np.random.default_rng(6).standard_normal(
            (4, ssm.conv - 1, ssm.conv_dim)), jnp.float32),
    }
    x = jnp.asarray(_hidden(7, 8)[0])
    slots = jnp.asarray([2], jnp.int32)

    def run(rows_held, garbage):
        xs = x[:rows_held]
        if garbage:
            xs = xs.at[5:].set(1e3)
        rows = packed_ssm_rows(
            1, rows_held, jnp.asarray([[9] * rows_held]), slots, 4, 5)
        return _ssm_mixer(spec, layer0, xs, state, slots, rows)

    (m5, s5), (m8, s8), (m8g, s8g) = run(5, False), run(8, False), run(8, True)
    assert np.array_equal(np.asarray(s8["ssm"]), np.asarray(s8g["ssm"]))
    assert np.array_equal(np.asarray(s8["conv"]), np.asarray(s8g["conv"]))
    assert np.array_equal(np.asarray(m8[:5]), np.asarray(m8g[:5]))
    assert np.array_equal(np.asarray(s5["conv"]), np.asarray(s8g["conv"]))
    np.testing.assert_allclose(s5["ssm"], s8g["ssm"], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(m5, m8g[:5], rtol=1e-5, atol=1e-6)
    # and the untouched slots are untouched
    assert np.array_equal(np.asarray(s8g["ssm"][:2]), np.asarray(state["ssm"][:2]))


# ------------------------------------- (d) two sessions in one ragged pack
def test_two_sessions_in_one_ragged_pack_never_read_each_others_slot(
        ckpt, span):
    lens = (20, 33, 40)
    hs = [_hidden(10 + i, n) for i, n in enumerate(lens)]
    wants = [_reference_hidden(ckpt, h[0]) for h in hs]

    async def run():
        ex = _executor(span)
        m = ex.manager
        async with m.allocate(1, 128) as a, m.allocate(1, 128) as b, \
                m.allocate(1, 128) as c:
            ex.prefill_chunked(a, hs[0][:, :19], 16)
            ex.prefill_chunked(b, hs[1][:, :32], 16)
            ex.prefill_chunk(c, hs[2][:, :16])
            # one pack: a decodes, c's next chunk (14 tokens, a tail),
            # b decodes; slots 0, 2, 1 in that order
            out, _ = ex.ragged_group(
                [a, c, b],
                [hs[0][:, 19:20], hs[2][:, 16:30], hs[1][:, 32:33]])
            rest = ex.prefill_chunk(c, hs[2][:, 30:40], fetch=True)
            return np.asarray(out), rest[0]

    with jax.default_matmul_precision("highest"):
        out, rest = asyncio.run(run())
    np.testing.assert_allclose(out[0], wants[0][19], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out[1:15], wants[2][16:30], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out[15], wants[1][32], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(rest, wants[2][30:40], rtol=1e-4, atol=1e-4)


def test_a_reopened_slot_starts_from_zeros(ckpt, span):
    """A slot is handed on without being zeroed: the next session's first
    step stands at position 0 and reads zeros whatever the slot holds."""
    hidden = _hidden(20, 12)
    want = _reference_hidden(ckpt, hidden[0])

    async def run():
        ex = _executor(span, _manager(span[1], state_slots=1))
        async with ex.manager.allocate(1, 64) as first:
            ex.prefill_chunk(first, _hidden(21, 16))
        assert float(jnp.abs(ex.manager.state["ssm"]).max()) > 0
        async with ex.manager.allocate(1, 64) as again:
            return ex.prefill(again, hidden)[0]

    with jax.default_matmul_precision("highest"):
        got = asyncio.run(run())
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------ (e) refusals
def _tree(t):
    return (np.tril(np.ones((1, t, t), bool)), np.arange(t, dtype=np.int32)[None])


async def _refuse_tree_step(ex, m, h):
    mask, depths = _tree(3)
    ex.decode(h, _hidden(0, 3), commit=False, tree_mask=mask, depths=depths)


async def _refuse_tree_group(ex, m, h):
    assert "recurrent state" in ex.ragged_unsupported(has_tree=True)
    assert ex.ragged_unsupported(has_tree=False) is None
    mask, depths = _tree(3)
    ex.ragged_group(
        [h], [_hidden(0, 3)], tree_masks=[mask], depths_list=[depths]
    )


async def _refuse_accept(ex, m, h):
    m.accept_speculative(h, [np.asarray([0])])


async def _refuse_decode_n(ex, m, h):
    ex.decode_n(h, np.zeros((1,), np.int32), 2, {})


async def _refuse_dense_forward(ex, m, h):
    from bloombee_tpu.runtime.layer_body import dense_block_forward

    dense_block_forward({}, ex.spec, jnp.zeros((1, 1, D)), None, None, None)


async def _refuse_two_chunks_in_a_pack(ex, m, h):
    """A ragged pack runs the chunk form on ONE sequence (one program for
    every pack of a bucket): two sequences of several rows are refused
    before a key is written."""
    async with m.allocate(1, 64) as other:
        before = m.context_lens(m.combine_handles([h, other])).tolist()
        try:
            ex.ragged_group([h, other], [_hidden(0, 3), _hidden(1, 2)])
        finally:
            after = m.context_lens(m.combine_handles([h, other])).tolist()
            assert after == before


@pytest.mark.parametrize("name,call,error,reason", [
    ("two_chunks_in_a_ragged_pack", _refuse_two_chunks_in_a_pack, ValueError,
     "ONE sequence of more than one row"),
    ("tree_verify_solo", _refuse_tree_step, ValueError, "tree verify"),
    ("tree_verify_group", _refuse_tree_group, ValueError, "recurrent state"),
    ("speculative_accept", _refuse_accept, ValueError, "speculative accept"),
    ("decode_n", _refuse_decode_n, ValueError, "recurrent state"),
    ("drafter_dense_forward", _refuse_dense_forward, NotImplementedError,
     "state-space mixer"),
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
])
def test_an_executor_that_cannot_carry_recurrent_state_refuses(span, kw, reason):
    with pytest.raises(ValueError, match=reason):
        _executor(span, **kw)


def test_a_manager_without_slots_or_with_a_quantized_arena_refuses(span):
    spec = span[1]
    with pytest.raises(ValueError, match="state_slots"):
        _manager(spec, state_slots=0)
    with pytest.raises(ValueError, match="quantized"):
        _manager(spec, quant="int4")
    params, _ = span
    plain = CacheManager(2, 64, 16, spec.num_key_value_heads, spec.head_dim,
                         dtype=jnp.float32)
    with pytest.raises(ValueError, match="state slots"):
        SpanExecutor(params, spec, plain, compute_dtype=jnp.float32)


def test_pages_cannot_be_adopted_replicated_or_parked(span):
    """Everything that copies or moves pages: the prefix cache is held off
    (so adoption, export and kv_put find nothing to do), host parking skips
    the sequence; each is counted by reason."""
    async def run():
        m = _manager(span[1], prefix_cache=True)
        ex = _executor(span, m)
        assert m.prefix_cache is False and m.repl_supported is False
        async with m.allocate(1, 64) as h:
            ex.prefill_chunk(h, _hidden(0, 16), commit=True)
            assert m.adopt_prefix(h, [["a", "b"]]) == [0]
            assert m.export_pages(h.seq_ids[0], 0, 1) is None
            assert m.install_replicated(["x"], np.zeros((1,)), np.zeros((1,))) == 0
            free = m.table.free_pages
            m.park_sequence(h.seq_ids[0])
            assert not m.has_parked(h) and m.table.free_pages == free
        assert m.state_refusals == {"prefix cache": 1, "host park": 1}

    asyncio.run(run())


@pytest.mark.parametrize("how", ["truncate", "rollback", "commit_shorter"])
def test_a_cut_to_a_position_above_zero_loses_the_session(span, how):
    """A recurrent state can be kept or reset to zero, never cut to the
    middle: after such a cut the session is not servable (`epoch_valid`
    false: the server answers `session_lost` and the client replays from
    0), and the refusal is counted once."""
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
            assert not m.epoch_valid(h)
        assert m.state_refusals == {"rollback to a position > 0": 1}

    asyncio.run(run())


def test_a_cut_to_zero_resets_and_a_cut_of_nothing_keeps(ckpt, span):
    hidden = _hidden(30, 10)
    want = _reference_hidden(ckpt, hidden[0])

    async def run():
        ex = _executor(span)
        m = ex.manager
        async with m.allocate(1, 64) as h:
            ex.prefill_chunk(h, _hidden(31, 8))  # speculative: rolls back to 0
            m.rollback(h)
            assert m.epoch_valid(h)
            out = ex.prefill(h, hidden)  # from position 0: zeros again
            m.truncate_speculative(h, [10])  # cuts nothing
            assert m.epoch_valid(h)
            return out[0]

    with jax.default_matmul_precision("highest"):
        got = asyncio.run(run())
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert True


# -------------------------------------------------- (f) the pool of slots
def test_slots_are_freed_on_close_and_counted(span):
    async def run():
        m = _manager(span[1], state_slots=3)
        assert m.state_stats() == {
            "slots": 3, "live": 0, "bytes": m.state_stats()["bytes"]}
        async with m.allocate(2, 16) as h:
            assert sorted(m.state_slots(h)) == [0, 1]
            assert m.state_stats()["live"] == 2
            async with m.allocate(1, 16) as g:
                assert list(m.state_slots(g)) == [2]
        assert m.state_stats()["live"] == 0
        stats = m.memory_stats()["state"]
        ssm = span[1].ssm
        assert stats["bytes"] == 2 * 3 * (
            ssm.heads * ssm.head_dim * ssm.state * 4
            + (ssm.conv - 1) * ssm.conv_dim * 4)

    asyncio.run(run())


def test_an_exhausted_slot_pool_waits_or_refuses_as_pages_do(span):
    async def run():
        m = _manager(span[1], state_slots=2)
        with pytest.raises(AllocationTimeout, match="state slots"):
            async with m.allocate(3, 16):
                pass
        order = []

        async def first():
            async with m.allocate(2, 16):
                order.append("first-in")
                await asyncio.sleep(0.05)
            order.append("first-out")

        async def second():
            await asyncio.sleep(0.01)
            async with m.allocate(1, 16, timeout=2.0):
                order.append("second-in")

        await asyncio.gather(first(), second())
        assert order == ["first-in", "first-out", "second-in"]
        async with m.allocate(2, 16):
            with pytest.raises(AllocationTimeout):
                async with m.allocate(1, 16, timeout=0.05):
                    pass

    asyncio.run(run())


def test_slot_count_is_derived_from_what_the_server_is_told(span):
    spec = span[1]
    assert state_slots_for(spec, 64, 16, 8) >= 16
    assert state_slots_for(spec, 64, 16, 1) >= 2
    published = json.loads(
        (ROOT / "cellbench/configs/falcon-h1-34b-span8.json").read_text())
    published.pop("cellbench")
    from bloombee_tpu.models.auto import spec_from_config_dict

    big = spec_from_config_dict(published)
    # the cell: 1280 pages of 16 tokens, --max-batch 8: a sequence's state
    # costs what 2052 tokens of K/V cost, so the floor (16) decides
    assert state_slots_for(big, 1280, 16, 8) == 16
    assert state_slots_for(big, 16384, 16, 8) == 127
    import dataclasses

    assert state_slots_for(dataclasses.replace(big, ssm=None), 1280, 16, 8) == 0


# ------------------------------------- (a) through a BlockServer and a client
def _family_logits(ckpt, ids, rows):
    """The family file's full forward: float32 logits at positions `rows`."""
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
        model_uid="tiny-fh1", start=0, end=2, model_dir=str(ckpt),
        registry=RegistryClient("127.0.0.1", reg.port),
        compute_dtype=jnp.float32, page_size=4, **server_kw)
    await server.start()
    model = DistributedModelForCausalLM.from_pretrained(
        str(ckpt), RegistryClient("127.0.0.1", reg.port),
        model_uid="tiny-fh1", dtype=jnp.float32)
    return reg, server, model


@pytest.mark.parametrize("mixed", [False, True], ids=["solo", "mixed-batch"])
def test_client_logits_through_a_block_server_match_the_family_file(
        ckpt, mixed):
    """(a) the normal path: a client, one BlockServer, prefill in chunks of 16
    with a tail of 5, then decode through both caches; the client's logits
    (final_layernorm, lm_head_multiplier) against the family file's full
    forward."""
    ids = np.random.default_rng(40).integers(0, CONFIG["vocab_size"], (1, 43))
    task_ids = []

    async def run():
        reg, server, model = await _swarm(
            ckpt, prefill_chunk=16, mixed_batch=mixed)
        try:
            rows = []
            wrap = server.compute._account.wrap  # what `bbtpu.task` is given
            server.compute._account.wrap = lambda fn, at, **ids_: (
                task_ids.append(ids_), wrap(fn, at, **ids_))[1]
            async with model.inference_session(64, 1) as session:
                out = await session.step(model.embed(ids[:, :37]), ids=ids[:, :37])
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
        got, info = asyncio.run(run())
    want = _family_logits(ckpt, ids[0], list(range(36, 43)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert info["kernel_fallbacks"] == 0
    state = info["memory"]["state"]
    assert state["slots"] >= 16 and state["live"] == 0 and state["bytes"] > 0
    assert info["prefill_chunks"] >= 3
    # every group task (the decode steps; with --mixed-batch the chunks too)
    # says how many sequences touched a state slot in it
    groups = [t for t in task_ids if "members" in t]
    assert len(groups) >= 6 and all(t["state_rows"] == 1 for t in groups)


def test_a_session_of_two_sequences_is_served_under_mixed_batch(ckpt):
    """A ragged pack runs the chunk form on one sequence, so the server sends
    a chunk of SEVERAL sequences on its own (the packed program takes any
    number): both rows' logits match the family file's forward."""
    ids = np.random.default_rng(41).integers(0, CONFIG["vocab_size"], (2, 24))

    async def run():
        reg, server, model = await _swarm(ckpt, prefill_chunk=16, mixed_batch=True)
        try:
            async with model.inference_session(64, 2) as session:
                out = await session.step(model.embed(ids[:, :21]), ids=ids[:, :21])
                rows = [model.logits(out[:, -1:])[:, 0]]
                for t in range(21, 24):
                    out = await session.step(
                        model.embed(ids[:, t:t + 1]), ids=ids[:, t:t + 1])
                    rows.append(model.logits(out)[:, 0])
            return np.stack(rows, 1), server.prefill_chunks
        finally:
            await server.stop()
            await reg.stop()

    with jax.default_matmul_precision("highest"):
        got, chunks = asyncio.run(run())
    assert chunks == 2
    for row in range(2):
        want = _family_logits(ckpt, ids[row], list(range(20, 24)))
        np.testing.assert_allclose(got[row], want, rtol=2e-4, atol=2e-5)


def test_slots_come_back_on_close_and_on_lease_expiry(ckpt):
    """(f) a session that closes gives its slot back at once; a client that
    vanishes keeps pages, slot and reservation (nothing of a recurrent state
    can be handed to the pool) until its lease runs out, then all come back."""
    from bloombee_tpu.wire.faults import FaultPlan

    ids = np.arange(8)[None, :] % CONFIG["vocab_size"]

    async def run():
        reg, server, model = await _swarm(
            ckpt, session_lease_s=1.0, keepalive_s=0.2)
        m = server.manager
        try:
            free0 = m.table.free_pages
            async with model.inference_session(24, 1) as session:
                await model.generate(ids, max_new_tokens=2, session=session)
                assert m.state_stats()["live"] == 1
            for _ in range(50):
                if m.state_stats()["live"] == 0:
                    break
                await asyncio.sleep(0.05)
            assert m.state_stats()["live"] == 0
            session = model.inference_session(24, 1)
            await session.__aenter__()
            await model.generate(ids, max_new_tokens=2, session=session)
            assert m.state_stats()["live"] == 1
            for sp in session._spans:  # the client goes silent, no FIN
                sp.conn.fault_plan = FaultPlan()
                sp.conn._bbtpu_partitioned = True
            deadline = asyncio.get_event_loop().time() + 8.0
            while asyncio.get_event_loop().time() < deadline:
                if not server._sessions and m.state_stats()["live"] == 0:
                    break
                await asyncio.sleep(0.1)
            assert m.state_stats()["live"] == 0 and not server._sessions
            assert m.table.free_pages >= free0
            assert server.sessions_reaped == 1
        finally:
            await server.stop()
            await reg.stop()

    asyncio.run(run())


def test_server_side_refusals_carry_their_reason(ckpt):
    """(e) at the server: kv_put declines, a ragged replay commit is refused
    with its reason, no training stack, decode_n and rebalancing to another
    span keep working or say why not; rpc_info counts refusals by reason
    where the other ragged declines are, and `health --probe` prints them."""
    async def run():
        reg, server, model = await _swarm(
            ckpt, prefix_cache=True, mixed_batch=True, spec_batch=True)
        try:
            assert server.training is None
            assert server.spec_batch is False and server.mixed_batch is True
            assert "recurrent state" in server._decode_n_ineligible()
            resp, _ = await server._kv_put(
                {"page_size": 4, "start": 0, "end": 2, "hashes": []}, [])
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
                "tiny-fh1", "--registry", f"127.0.0.1:{reg.port}",
                "--num-blocks", "2", "--probe",
                stdout=asyncio.subprocess.PIPE, stderr=asyncio.subprocess.STDOUT,
                cwd=str(ROOT))
            out, _ = await asyncio.wait_for(proc.communicate(), 60)
            return info, out.decode()
        finally:
            await server.stop()
            await reg.stop()

    info, health = asyncio.run(run())
    declines = info["ragged_declines"]
    assert declines["prefix cache"] == 1
    assert declines["ragged replay commit"] == 1
    assert declines["recurrent state (tree rows would branch it)"] == 1
    assert "memory.state=slots:" in health
    assert "ragged_decline[prefix cache]=1" in health
