"""DeepSeek-V2: latent attention (MLA) with a latent page in the paged arena,
a dense layer before sparse ones in ONE span step, group-limited routing
over the experts a server HOLDS, shared experts.

Tiny widths on the CPU, seeded. The mathematics under test has ONE plain
copy, the benchmark's family file (cellbench/families/deepseek_v2.py:
expanded form, no cache), which (a) ties to the published implementation
(`transformers`' DeepseekV2ForCausalLM); everything the program serves
(absorbed form, through the cache) is held to that file.
"""

import asyncio
import dataclasses
import pathlib
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bloombee_tpu.kv.cache_manager import CacheManager  # noqa: E402
from bloombee_tpu.models.checkpoint import load_span_params  # noqa: E402
from bloombee_tpu.models.layout import LEAD, split_runs  # noqa: E402
from bloombee_tpu.runtime.executor import SpanExecutor  # noqa: E402
from cellbench import checkpoint, families, reference  # noqa: E402

YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
        "mscale_all_dim": 0.707}
# 16 experts in 4 groups of 4, 2 groups kept, top-3; this checkpoint holds
# group 1 (experts 4-7) and its router scores all 16
CONFIG = {
    "model_type": "deepseek_v2", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 4, "router_experts": 16, "experts_held": [4, 4],
    "n_group": 4, "topk_group": 2, "num_experts_per_tok": 3,
    "n_shared_experts": 2, "moe_intermediate_size": 32,
    "intermediate_size": 96, "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "num_hidden_layers": 3, "routed_scaling_factor": 2.5, "vocab_size": 128,
    "rope_scaling": YARN, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "topk_method": "group_limited_greedy", "norm_topk_prob": False,
    "scoring_func": "softmax", "max_position_embeddings": 512,
    "tie_word_embeddings": False, "attention_bias": False,
    "hidden_act": "silu", "torch_dtype": "bfloat16",
}
D, LAYERS = CONFIG["hidden_size"], CONFIG["num_hidden_layers"]
FAMILY = families.of(CONFIG)
HELD = tuple(CONFIG["experts_held"])
KERNELS = {"BBTPU_PAGED_INTERPRET": "1", "BBTPU_PAGED_MIN_CONTEXT": "0"}


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    path = tmp_path_factory.mktemp("tiny_deepseek_v2")
    checkpoint.write_checkpoint(path, CONFIG, 35)
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
    return CacheManager(
        LAYERS, 64, 4, spec.num_key_value_heads, spec.head_dim,
        dtype=jnp.float32, payload=spec.mla.page_payload, **kw)


def _executor(span, manager=None, **kw):
    params, spec = span
    return SpanExecutor(params, spec, manager or _manager(spec),
                        compute_dtype=jnp.float32, **kw)


def _hidden(seed, t, b=1):
    return (0.05 * np.random.default_rng(seed).standard_normal(
        (b, t, D))).astype(np.float32)


# ------------------------------------------------ (a) the published model
def _hf(config):
    import torch
    from transformers import DeepseekV2Config, DeepseekV2ForCausalLM

    cfg = {k: v for k, v in config.items()
           if k not in ("router_experts", "experts_held", "torch_dtype")}
    cfg["n_routed_experts"] = 16  # the installed port holds every expert
    torch.manual_seed(35)
    model = DeepseekV2ForCausalLM(DeepseekV2Config(**cfg)).float().eval()
    return cfg, model, {k: v.detach().numpy()
                        for k, v in model.state_dict().items()}


@pytest.mark.parametrize("rope", ["plain", "yarn"])
def test_family_reference_matches_transformers(rope, monkeypatch):
    """(a) the whole model, all experts held, `group_limited_greedy`: at
    `rope_scaling: null` as it stands; with YaRN after the one known
    difference is divided out: the installed port leaves mscale**2 out of
    the softmax scale (`modeling_deepseek_v2.py`: `self.scaling =
    qk_head_dim ** -0.5`), where the model's own published code multiplies
    it in. The family file follows the published code."""
    import torch

    cfg, model, tensors = _hf(
        dict(CONFIG, rope_scaling=YARN if rope == "yarn" else None))
    if rope == "yarn":
        assert FAMILY.softmax_scale(cfg) == pytest.approx(
            24 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2)
        monkeypatch.setattr(
            FAMILY, "softmax_scale",
            lambda c: (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5)
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (1, 37))
    with torch.no_grad():
        want = model(torch.tensor(ids)).logits[0].numpy()
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(FAMILY.embed(tensors, cfg, ids[0]))
        for layer in range(LAYERS):
            p = jax.tree.map(
                jnp.asarray, FAMILY.layer_params(tensors, cfg, layer))
            h = FAMILY.layer_forward(p, cfg, h, jnp.arange(37))
        got = np.asarray(FAMILY.logits_rows(tensors, cfg, h))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)


def test_yarn_frequencies_match_transformers():
    """The family file's and the program's YaRN frequencies against
    `ROPE_INIT_FUNCTIONS["yarn"]` at the published constants."""
    from transformers import DeepseekV2Config
    from transformers.modeling_rope_utils import ROPE_INIT_FUNCTIONS

    from bloombee_tpu.ops.rotary import yarn_inv_freq

    rs = dict(YARN, factor=40.0, original_max_position_embeddings=4096,
              beta_fast=32.0, beta_slow=1.0)
    want, factor = ROPE_INIT_FUNCTIONS["yarn"](DeepseekV2Config(
        rope_scaling=rs, qk_rope_head_dim=64,
        max_position_embeddings=163840), "cpu")
    got, scale = FAMILY.rotary_frequencies(
        {"qk_rope_head_dim": 64, "rope_theta": 10000, "rope_scaling": rs})
    np.testing.assert_allclose(got, want.numpy(), rtol=1e-6)
    np.testing.assert_allclose(
        yarn_inv_freq(64, 10000.0, 40.0, 4096, 32, 1), want.numpy(), rtol=1e-6)
    assert scale == factor == 1.0
    # the correction range of the issue: dims 10 and 23
    np.testing.assert_allclose(
        got[:11], 1 / 10000 ** (np.arange(0, 22, 2) / 64), rtol=1e-6)
    np.testing.assert_allclose(
        got[23:], (1 / 10000 ** (np.arange(46, 64, 2) / 64)) / 40, rtol=1e-6)


def test_spec_from_the_published_config():
    import json

    from bloombee_tpu.models.auto import spec_from_config_dict

    config = json.loads((
        ROOT / "cellbench/configs/deepseek-v2-ep8-span5.json").read_text())
    spec = spec_from_config_dict(config)
    m = spec.mla
    assert (m.q_rank, m.kv_rank, m.nope_dim, m.rope_dim, m.v_dim) == (
        1536, 512, 128, 64, 128)
    assert m.softmax_scale == pytest.approx(0.114721, rel=1e-5)
    # the rotary key's row is stored in whole lanes: 512 + 128 values
    assert m.page_payload == ((512,), (128,)) and m.token_bytes == 1280
    assert (spec.moe_groups, spec.moe_topk_groups, spec.moe_route_scale) == (
        8, 3, 16.0)
    assert spec.moe_shared_intermediate == 3072
    assert [spec.mlp_kind(i) for i in range(3)] == ["dense", "sparse", "sparse"]


# ------------------------------------------------- the router, held experts
def test_group_limited_router_matches_the_family_file():
    from bloombee_tpu.ops.moe import route_topk

    logits = jnp.asarray(
        np.random.default_rng(1).standard_normal((50, 16)), jnp.float32)
    idx, w = route_topk(logits, 3, groups=4, topk_groups=2, scale=2.5)
    want_idx, want_w = FAMILY.route(jax.nn.softmax(logits, -1), CONFIG)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_allclose(np.asarray(w), np.asarray(want_w), rtol=1e-6)
    # every chosen expert lies in one of the row's two best groups
    best = np.asarray(jax.nn.softmax(logits, -1)).reshape(50, 4, 4).max(-1)
    kept = np.argsort(-best, axis=1)[:, :2]
    assert all(set(np.asarray(idx)[r] // 4) <= set(kept[r]) for r in range(50))


@pytest.mark.parametrize("rows", [2, 8])
def test_grouped_and_dense_forms_agree_on_the_held_experts(rows, monkeypatch):
    """Both expert forms take the router's choices over all 16, keep the
    pairs whose expert is held and index the held stack; a row with no held
    expert gets zero from the routed experts."""
    from bloombee_tpu.ops.moe import _held_local, moe_mlp, route_topk

    rng = np.random.default_rng(rows)
    f = lambda *s: jnp.asarray(0.2 * rng.standard_normal(s), jnp.float32)  # noqa: E731
    x, router = f(1, rows, 32), f(32, 16)
    gate, up, down = f(4, 32, 24), f(4, 32, 24), f(4, 24, 32)
    kw = dict(groups=4, topk_groups=2, route_scale=2.5, held=(4, 4))
    dense = moe_mlp(x, router, gate, up, down, 3, **kw)
    grouped = moe_mlp(x, router, gate, up, down, 3, **kw, interpret=True,
                      expert_base=jnp.int32(0))
    np.testing.assert_allclose(grouped, dense, rtol=1e-5, atol=1e-6)
    idx, w = route_topk(x @ router, 3, groups=4, topk_groups=2, scale=2.5)
    _, _, here = _held_local(idx, w, (4, 4))
    none = ~np.asarray(here[0]).any(-1)
    assert np.all(np.asarray(dense)[0][none] == 0)


# --------------------------------- (c) the program against the family file
@pytest.mark.parametrize("kernels", [False, True], ids=["dense", "kernels"])
@pytest.mark.parametrize("split", [None, 1, 7, 12])
def test_one_chunk_equals_single_steps_equals_two_chunks(
        ckpt, span, split, kernels, monkeypatch):
    """(c) ABSORBED through the cache == the family file's EXPANDED form with
    no cache, however the 19 positions are cut: one chunk, single steps, two
    chunks; on the dense path and through the Pallas kernels (interpreted)."""
    for k, v in (KERNELS if kernels else {}).items():
        monkeypatch.setenv(k, v)
    h = _hidden(3, 19)
    want = _reference_hidden(ckpt, h[0])
    ex = _executor(span)
    cuts = {None: [19], 1: [1] * 19, 7: [7, 12], 12: [12, 7]}[split]

    async def run():
        outs, at = [], 0
        async with ex.manager.allocate(1, 64) as handle:
            for n in cuts:
                step = ex.prefill if n > 1 else ex.decode
                outs.append(np.asarray(step(handle, h[:, at:at + n])))
                at += n
        return np.concatenate(outs, axis=1)[0]

    with jax.default_matmul_precision("highest"):
        got = asyncio.run(run())
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
    assert ex.kernel_fallbacks == 0
    assert (ex.attn_dispatches["paged"] > 0) == kernels


@pytest.mark.parametrize("kernels", [False, True], ids=["dense", "kernels"])
def test_fused_pack_and_decode_group_match_the_family_file(
        ckpt, span, kernels, monkeypatch):
    """The ragged pack (one sequence's chunk beside another's decode row)
    and the packed decode group, each row against its own sequence's
    reference; with kernels on, the decode group takes the experts' grouped
    form over the HELD stack."""
    for k, v in (KERNELS if kernels else {}).items():
        monkeypatch.setenv(k, v)
    a, b = _hidden(4, 15), _hidden(5, 22)
    want_a, want_b = (_reference_hidden(ckpt, x[0]) for x in (a, b))
    ex = _executor(span)

    async def run():
        m = ex.manager
        async with m.allocate(1, 64) as ha, m.allocate(1, 64) as hb:
            got_a = [np.asarray(ex.prefill(ha, a[:, :13]))[0]]
            got_b = [np.asarray(ex.prefill(hb, b[:, :9]))[0]]
            out, both = ex.ragged_group(
                [ha, hb], [a[:, 13:14], b[:, 9:20]],
                tree_masks=[None, None], depths_list=[None, None])
            m.commit(both)
            out = np.asarray(out)
            got_a.append(out[:1]), got_b.append(out[1:12])
            out, both = ex.decode_group([ha, hb], [a[:, 14:15], b[:, 20:21]])
            m.commit(both)
            out = np.asarray(out)
            got_a.append(out[0]), got_b.append(out[1])
            got_b.append(np.asarray(ex.decode(hb, b[:, 21:22]))[0])
        return np.concatenate(got_a), np.concatenate(got_b)

    with jax.default_matmul_precision("highest"):
        got_a, got_b = asyncio.run(run())
    np.testing.assert_allclose(got_a, want_a, rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(got_b, want_b, rtol=2e-4, atol=2e-6)
    assert ex.kernel_fallbacks == 0
    assert ex.attn_dispatches["ragged" if kernels else "dense"] >= 1
    assert (ex.moe_dispatches["grouped"] > 0) == kernels


def _reference_reach(ckpt, hidden):
    """Per sparse layer what the family file's router sends to the HELD
    experts: [distinct held experts chosen, pairs here, rows with one]."""
    from cellbench.reference import _rms

    eps, out = CONFIG["rms_norm_eps"], []
    with jax.default_matmul_precision("highest"):
        h, pos = jnp.asarray(hidden), jnp.arange(hidden.shape[0])
        for layer in range(LAYERS):
            raw = reference.layer_params(ckpt, CONFIG, layer)
            if "router" in raw:
                p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), raw)
                x = h + FAMILY.mla_attention(
                    p, CONFIG, _rms(h, p["ln1"], eps), pos) @ p["o"].T
                scores = jax.nn.softmax(
                    _rms(x, p["ln2"], eps) @ p["router"].T, -1)
                local = np.asarray(FAMILY.route(scores, CONFIG)[0]) - HELD[0]
                here = (local >= 0) & (local < HELD[1])
                out.append([len(set(local[here])), int(here.sum()),
                            int(here.any(-1).sum())])
            h = FAMILY.layer_forward(raw, CONFIG, h, pos)
    return np.array(out)


@pytest.mark.parametrize("kernels", [False, True], ids=["dense", "kernels"])
def test_what_a_steps_rows_reach_of_the_held_experts_is_counted(
        ckpt, span, kernels, monkeypatch):
    """Item 4's counters: a server that holds a share of the experts hands
    out of every step, per sparse layer, the distinct held experts its rows
    chose, the pairs whose expert is held and the rows with one; read at the
    next fetch (no wait on the compute thread), a bucket's padding rows
    counted nowhere, equal to the family file's router on the same rows."""
    for k, v in (KERNELS if kernels else {}).items():
        monkeypatch.setenv(k, v)
    h = _hidden(6, 19)  # 19 rows in the bucket of 32
    want = _reference_reach(ckpt, h[0])
    assert want.shape == (LAYERS - 1, 3) and want[:, 1].sum() > 0
    ex = _executor(span)

    async def run():
        async with ex.manager.allocate(1, 64) as handle:
            ex.prefill(handle, h)
            first = dict(ex.moe_reach)
            ex.decode(handle, _hidden(7, 1))
        return first

    with jax.default_matmul_precision("highest"):
        first = asyncio.run(run())
    assert first == {
        "steps": 1, "rows": 19, "held_hit_last": want[:, 0].tolist(),
        "routed_pairs_here": want[:, 1].sum(),
        "rows_with_held_expert": want[:, 2].sum(),
    }
    assert ex.moe_reach["steps"] == 2 and ex.moe_reach["rows"] == 20
    assert not ex._reach_pending
    whole = dataclasses.replace(span[1], moe_held=None)
    assert whole.experts_held == (0, 16)  # every expert held: nothing to say


@pytest.mark.parametrize("program", ["packed", "ragged"])
def test_a_padded_bucket_is_bit_equal_to_the_unpadded_run(span, program):
    """11 rows in the 16 bucket against the same rows in a bucket of their
    own (8 + 3 single steps would be another cut): the written latent pages
    and the outputs hold the same bits, packed and ragged."""
    h = _hidden(6, 11)

    def run(pad):
        ex = _executor(span)

        async def go():
            m = ex.manager
            async with m.allocate(1, 64) as handle, m.allocate(1, 64) as other:
                if program == "packed":
                    out = ex.prefill(handle, h)
                else:
                    ex.prefill(other, _hidden(7, 5))
                    members = [handle, other] if pad else [other, handle]
                    hs = {id(handle): h, id(other): _hidden(8, 1)}
                    out, both = ex.ragged_group(
                        members, [hs[id(x)] for x in members],
                        tree_masks=[None, None], depths_list=[None, None])
                    m.commit(both)
                    out = out[:11] if pad else out[1:12]
                pages = m.page_table(handle, 4)[0]
                slots = (pages[:, None] * 4 + np.arange(4)).reshape(-1)[:11]
                return np.asarray(out), [
                    np.asarray(m.arena[k][:, slots]) for k in "kv"]
        return asyncio.run(go())

    out_a, pages_a = run(True)
    if program == "packed":
        # the same 11 rows as a chunk of 8 and a chunk of 3: other buckets
        ex = _executor(span)

        async def go():
            async with ex.manager.allocate(1, 64) as handle:
                outs = [np.asarray(ex.prefill(handle, h[:, :8])),
                        np.asarray(ex.prefill(handle, h[:, 8:]))]
                return np.concatenate(outs, 1)
        np.testing.assert_allclose(
            asyncio.run(go()), out_a, rtol=1e-5, atol=1e-7)
        return
    out_b, pages_b = run(False)
    np.testing.assert_array_equal(out_a.reshape(11, D), out_b.reshape(11, D))
    for x, y in zip(pages_a, pages_b):
        np.testing.assert_array_equal(x, y)


def test_latent_kernels_match_the_dense_form():
    """The two Pallas kernels (interpreted) against `latent_attend_dense` on
    pages scattered over an arena, lengths that end inside a page, a chunk
    whose bucket tail is padding."""
    from bloombee_tpu.ops.pallas.latent_attention import (
        latent_attend_dense,
        latent_flash_attention,
        paged_decode_attention_latent,
    )

    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    h, c, r, ps, b, npg = 8, 32, 8, 4, 3, 8
    ql, qp, cs, pes = f(b, h, c), f(b, h, r), f(64 * ps, c), f(64 * ps, r)
    pt = jnp.asarray(rng.permutation(64)[:b * npg].reshape(b, npg), jnp.int32)
    lens = jnp.asarray([5, 32, 0], jnp.int32)
    got = paged_decode_attention_latent(
        ql, qp, cs, pes, pt, lens, page_size=ps, scale=0.3, pages_per_step=4,
        interpret=True)
    slots = (pt[:, :, None] * ps + jnp.arange(ps)).reshape(b, -1)
    want = latent_attend_dense(
        ql[:, :, None], qp[:, :, None], cs[slots], pes[slots],
        (lens - 1)[:, None], lens, 0.3)[:, :, 0]
    np.testing.assert_allclose(got[:2], want[:2], rtol=1e-5, atol=1e-6)
    assert np.all(np.asarray(got[2]) == 0)  # a padding row: no key, zeros
    t, s, start, n_real = 20, 48, 9, 13
    ql, qp, cc, pc = f(h, t, c), f(h, t, r), f(s, c), f(s, r)
    got = latent_flash_attention(
        ql, qp, cc, pc, start, start + n_real, n_real, scale=0.3,
        block_rows=64, block_k=8, interpret=True)
    want = latent_attend_dense(
        ql[None], qp[None], cc[None], pc[None],
        (start + jnp.arange(t))[None], jnp.asarray([start + n_real]), 0.3)[0]
    np.testing.assert_allclose(
        got[:, :n_real], want[:, :n_real], rtol=1e-5, atol=1e-6)


# -------------------------------------------------------- (d) the share test
def test_eight_shares_add_up_to_the_uncut_layer(tmp_path):
    """(d) four shares of four experts each (the tiny preset's groups): the
    shares' routed partial sums plus the shared experts counted ONCE add up
    to the uncut reference's layer output, in the reference and in the
    program's expert forms alike."""
    whole = dict(CONFIG, n_routed_experts=16, experts_held=[0, 16])
    checkpoint.write_checkpoint(tmp_path, whole, 36)
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
        total = shared
        from bloombee_tpu.ops.moe import moe_mlp

        program = shared
        for first in range(0, 16, 4):
            share = dict(whole, n_routed_experts=4, experts_held=[first, 4])
            p = f32(FAMILY.layer_params(tensors, share, layer))
            routed = FAMILY.moe(
                x, {k: v for k, v in p.items() if not k.startswith("s_")},
                share)
            total = total + routed
            program = program + moe_mlp(
                x[None], p["router"].T, *(
                    jnp.swapaxes(p[f"e_{k}"], 1, 2)
                    for k in ("gate", "up", "down")),
                3, groups=4, topk_groups=2, route_scale=2.5,
                held=(first, 4))[0]
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5 * scale)
    np.testing.assert_allclose(program, want, rtol=1e-4, atol=1e-5 * scale)
    # the routed part counts: it is no rounding of the shared experts' output
    assert float(jnp.abs(want - shared).max()) > 0.2 * scale


# --------------------------------------------- the loader, arena, accounting
def test_the_span_loads_as_two_runs_stored_as_the_steps_read_them(span, ckpt):
    params, spec = span
    lead, main = split_runs(params)
    assert lead["gate_proj"].shape == (1, D, 96) and "router_t" not in lead
    assert main["router_t"].shape == (2, 16, D)  # the router over ALL experts
    assert main["experts_gate"].shape == (2, 4, D, 32)  # the 4 held
    assert main["shared_down"].shape == (2, 64, D)
    assert main["kv_b_k"].shape == (2, 4, 16, 16) == main["kv_b_v"].shape
    assert main["q_b_nope"].shape == (2, 64, 24)
    assert main["q_b_rope"].shape == (2, 32, 24)
    assert all(k.startswith(LEAD) or k in main for k in params)
    assert (spec.num_experts, spec.moe_held) == (16, (4, 4))
    # the rotary rows are stored de-interleaved: evens, then odds
    raw = reference.read_safetensors(
        ckpt / checkpoint.file_name(checkpoint.layer_tag(1)))
    kv_a = np.asarray(
        raw["model.layers.1.self_attn.kv_a_proj_with_mqa.weight"], np.float32)
    np.testing.assert_array_equal(
        np.asarray(main["kv_a_proj"][0, 16:]),
        kv_a[16:][[0, 2, 4, 6, 1, 3, 5, 7]])
    # a span of sparse layers only is ONE run under the plain keys
    tail, _ = load_span_params(str(ckpt), 1, 3, dtype=jnp.float32,
                               experts=HELD)
    assert split_runs(tail)[0] is None and tail["router_t"].shape[0] == 2


def test_experts_outside_the_router_or_for_a_dense_family_refuse(ckpt):
    with pytest.raises(ValueError, match="outside the router's 16"):
        load_span_params(str(ckpt), 0, 3, experts=(14, 4))
    from bloombee_tpu.cli.run_server import parse_experts

    assert parse_experts("4:4") == (4, 4) and parse_experts(None) is None
    with pytest.raises(SystemExit, match="FIRST:COUNT"):
        parse_experts("4")


def test_the_arena_holds_the_declared_payload_and_counts_its_bytes(span):
    _, spec = span
    m = _manager(spec)
    assert m.arena["k"].shape == (3, 256, 16)  # the latent
    assert m.arena["v"].shape == (3, 256, 128)  # the rotary key, whole lanes
    assert m.memory_stats()["kv_arena_bytes"] == 3 * 256 * (16 + 128) * 4
    from bloombee_tpu.server.block_selection import (
        estimate_span_bytes,
        kv_token_bytes,
    )

    assert kv_token_bytes(spec) == (16 + 128) * 2
    attn = (64 * 24 + 24 * 4 * 24 + 64 * 24 + 16 * 4 * 32 + 4 * 16 * 64
            + 24 + 16)
    dense = attn + 3 * 64 * 96 + 4 * 64
    sparse = attn + 4 * 3 * 64 * 32 + 64 * 16 + 3 * 64 * 64 + 4 * 64
    assert estimate_span_bytes(spec, jnp.bfloat16, 0, 3) == 2 * (
        dense + 2 * sparse)


# ------------------------------------------------------------ (e) refusals
def _tree(t):
    return np.tril(np.ones((1, t, t), bool))


async def _refuse_tree_step(ex, m, h):
    async with m.allocate(1, 32) as handle:
        ex.decode(handle, h[:, :4], commit=False, tree_mask=_tree(4),
                  depths=np.arange(4, dtype=np.int32)[None])


async def _refuse_tree_group(ex, m, h):
    async with m.allocate(1, 32) as a, m.allocate(1, 32) as b:
        ex.ragged_group([a, b], [h[:, :4], h[:, :1]],
                        tree_masks=[_tree(4), None],
                        depths_list=[np.arange(4)[None], None])


async def _refuse_decode_n(ex, m, h):
    async with m.allocate(1, 32) as handle:
        ex.decode_n(handle, np.zeros((1,), np.int32), 2, {})


async def _refuse_two_chunks_in_a_pack(ex, m, h):
    async with m.allocate(1, 32) as a, m.allocate(1, 32) as b:
        ex.ragged_group([a, b], [h[:, :4], h[:, :3]],
                        tree_masks=[None, None], depths_list=[None, None])


@pytest.mark.parametrize("call,reason", [
    (_refuse_tree_step, "tree verify unsupported: latent attention"),
    (_refuse_tree_group, "latent attention .no tree mask"),
    (_refuse_decode_n, "decode_n . latent attention"),
    (_refuse_two_chunks_in_a_pack, "a latent cache"),
], ids=["tree-step", "tree-group", "decode_n", "two-chunks"])
def test_a_step_the_latent_page_cannot_serve_refuses(span, call, reason):
    ex = _executor(span)
    with pytest.raises(ValueError, match=reason):
        asyncio.run(call(ex, ex.manager, _hidden(1, 8)))


@pytest.mark.parametrize("kw,reason", [
    (dict(host_layers=[{}]), "weight offload unsupported for deepseek_v2"),
    (dict(attn_sparsity=0.5), "--attn-sparsity unsupported"),
], ids=["offload", "sparse-attention"])
def test_an_executor_the_latent_page_cannot_serve_refuses(span, kw, reason):
    with pytest.raises(ValueError, match=reason):
        _executor(span, **kw)


def test_an_int4_arena_and_a_dense_forward_refuse(span):
    _, spec = span
    with pytest.raises(ValueError, match="no int4 form"):
        _manager(spec, quant="int4")
    from bloombee_tpu.runtime.layer_body import dense_unsupported

    assert "latent attention" in dense_unsupported(spec)


@pytest.mark.parametrize("how", ["truncate", "rollback", "commit_shorter"])
def test_a_cut_to_a_position_above_zero_keeps_the_session(ckpt, span, how):
    """(e) a latent page is cut like any page (unlike PR 30's recurrent
    state): after a rollback, a truncation or a shorter commit of five
    speculative rows the session goes on from where it was cut and matches
    the reference."""
    keep = 2 if how == "commit_shorter" else 0
    extra = _hidden(11, 5)
    h = np.concatenate([_hidden(10, 12), extra[:, :keep], _hidden(12, 6)], 1)
    want = _reference_hidden(ckpt, h[0])
    ex = _executor(span)

    async def run():
        m = ex.manager
        async with m.allocate(1, 64) as handle:
            outs = [np.asarray(ex.prefill(handle, h[:, :12]))]
            spec_out = np.asarray(ex.prefill(handle, extra, commit=False))
            if how == "rollback":
                m.rollback(handle)
            elif how == "truncate":
                m.truncate_speculative(handle, [12])
            else:
                m.commit(handle, [14])
                outs.append(spec_out[:, :2])
            assert [int(x) for x in m.context_lens(handle)] == [12 + keep]
            assert m.epoch_valid(handle)
            outs.append(np.asarray(ex.prefill(handle, h[:, 12 + keep:])))
        return np.concatenate(outs, 1)[0]

    with jax.default_matmul_precision("highest"):
        got = asyncio.run(run())
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)
    assert not ex.manager.state_refusals


def test_latent_pages_are_exported_installed_and_parked_as_pages(span):
    """kv_put / export_pages, the prefix pool and host parking carry the
    latent page: the two slabs' rows differ, nothing else does."""
    _, spec = span
    m = _manager(spec, prefix_cache=True)
    ex = _executor(span, m)
    assert m.repl_supported

    async def run():
        async with m.allocate(1, 64) as handle:
            ex.prefill(handle, _hidden(12, 10))
            sid = handle.seq_ids[0]
            k, v, hi = m.export_pages(sid, 0, 2)
            assert k.shape == (3, 8, 16) and v.shape == (3, 8, 128) and hi == 2
            pages = lambda a: np.swapaxes(  # noqa: E731
                np.asarray(a).reshape(3, 2, 4, -1), 0, 1)
            assert m.install_replicated(
                [b"a" * 16, b"b" * 16], pages(k), pages(v)) == 2
            with pytest.raises(ValueError, match="does not match"):
                m.install_replicated([b"c" * 16], pages(k)[:1], pages(k)[:1])
            before = np.asarray(
                ex.decode(handle, _hidden(13, 1), commit=False))
            m.rollback(handle)
            m.park_sequence(sid)
            m.ensure_resident(handle)
            after = np.asarray(ex.decode(handle, _hidden(13, 1)))
            np.testing.assert_array_equal(before, after)

    asyncio.run(run())


# ---------------------------------- (b) through a BlockServer and a client
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
        model_uid="tiny-dsv2", start=0, end=LAYERS, model_dir=str(ckpt),
        registry=RegistryClient("127.0.0.1", reg.port), experts=HELD,
        compute_dtype=jnp.float32, page_size=4, **server_kw)
    await server.start()
    model = DistributedModelForCausalLM.from_pretrained(
        str(ckpt), RegistryClient("127.0.0.1", reg.port),
        model_uid="tiny-dsv2", dtype=jnp.float32)
    return reg, server, model


@pytest.mark.parametrize("mixed", [False, True], ids=["solo", "mixed-batch"])
def test_client_logits_through_a_block_server_match_the_family_file(
        ckpt, mixed):
    """(b) the normal path: a client, one BlockServer told `--experts 4:4`,
    prefill in chunks of 16 with a tail of 5, then decode through the latent
    cache; the client's logits against the family file's full forward."""
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
    assert info["kernel_fallbacks"] == 0 and info["prefill_chunks"] >= 3
    assert info["experts_held"] == [4, 4]
    assert info["latent_bytes_per_token"] == (16 + 128) * 2
    assert set(info["moe"]) == {
        "grouped_dispatches", "tiled_dispatches", "dense_dispatches"}
    reach = info["moe_reach"]
    assert reach["steps"] >= 9 and reach["rows"] == 43
    assert len(reach["held_hit_last"]) == LAYERS - 1
    assert 0 < reach["rows_with_held_expert"] <= reach["routed_pairs_here"]


def test_server_side_refusals_carry_their_reason(ckpt):
    """(e) at the server: no training stack, decode_n says why not, tree
    rows are declined with the reason in `rpc_info["ragged_declines"]`, and
    `health --probe` prints the experts held and the latent's bytes."""
    async def run():
        reg, server, model = await _swarm(
            ckpt, mixed_batch=True, spec_batch=True)
        try:
            assert server.training is None
            assert server.spec_batch is False and server.mixed_batch is True
            assert "latent attention" in server._decode_n_ineligible()
            from bloombee_tpu.wire.rpc import connect

            conn = await connect("127.0.0.1", server.port)
            info, _ = await conn.call("rpc_info", {})
            await conn.close()
            proc = await asyncio.create_subprocess_exec(
                sys.executable, "-m", "bloombee_tpu.cli.health",
                "tiny-dsv2", "--registry", f"127.0.0.1:{reg.port}",
                "--num-blocks", str(LAYERS), "--probe",
                stdout=asyncio.subprocess.PIPE,
                stderr=asyncio.subprocess.STDOUT, cwd=str(ROOT))
            out, _ = await asyncio.wait_for(proc.communicate(), 60)
            return info, out.decode()
        finally:
            await server.stop()
            await reg.stop()

    info, health = asyncio.run(asyncio.wait_for(run(), 280))
    assert info["ragged_declines"][
        "latent attention (no tree mask in its kernels)"] == 1
    assert "experts_held=4:4" in health
    assert "latent_bytes_per_token=288" in health


@pytest.mark.parametrize("kw,reason", [
    (dict(tp=2), "--tp .tensor-parallel serving. unsupported for deepseek_v2"),
    (dict(kv_quant="int4"), "no int4 form"),
], ids=["tp", "int4-kv"])
def test_a_server_the_latent_page_cannot_serve_refuses_at_start_up(
        ckpt, kw, reason):
    from bloombee_tpu.server.block_server import BlockServer

    with pytest.raises(ValueError, match=reason):
        BlockServer(model_uid="x", start=0, end=LAYERS, model_dir=str(ckpt),
                    experts=HELD, compute_dtype=jnp.float32, page_size=4,
                    num_pages=16, **kw)
