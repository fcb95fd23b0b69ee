"""Experts by index (ops/moe.py): a decode group's rows go to their top-k
experts through the grouped Pallas kernel (interpret mode on the CPU), a
chunk above the ridge computes only its chosen pairs through the tiled one,
a chunk under it keeps the dense einsum, and a family without experts
reaches none of them.

The plain copy of the mathematics is the benchmark's family file
(cellbench/families/qwen3_moe.py `_moe`: softmax over all experts, top-k,
renormalised — which is also Mixtral's softmax over the top-k logits).
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

from bloombee_tpu.kv.cache_manager import CacheManager  # noqa: E402
from bloombee_tpu.models.checkpoint import load_span_params  # noqa: E402
from bloombee_tpu.ops import moe  # noqa: E402
from bloombee_tpu.ops.moe import (  # noqa: E402
    TILED_MIN_ROWS,
    expert_form,
    moe_mlp,
    route_topk,
    router_topk_weights,
)
from bloombee_tpu.runtime import step as step_module  # noqa: E402
from bloombee_tpu.runtime.executor import SpanExecutor  # noqa: E402
from cellbench import checkpoint, families, reference  # noqa: E402
from cellbench.families.qwen3_moe import _moe as family_moe  # noqa: E402

D, I = 64, 128
FORMS = {
    # name: (experts, top_k, pre_softmax + norm_topk)
    "qwen3-128-top8": (128, 8, True),
    "mixtral-8-top2": (8, 2, False),
}


def _weights(num_experts, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.2, dtype)  # noqa: E731
    return dict(router=f(D, num_experts), gate=f(num_experts, D, I),
                up=f(num_experts, D, I), down=f(num_experts, I, D))


def _rows(rows, seed=1):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((rows, 1, D)), jnp.float32)


def _both(x, w, top_k, pre, **kw):
    """(dense, grouped) outputs of the same step."""
    call = lambda **more: moe_mlp(  # noqa: E731
        x, w["router"], w["gate"], w["up"], w["down"], top_k,
        pre_softmax=pre, norm_topk=pre, **more)
    return call(), call(expert_base=jnp.int32(0), interpret=True, **kw)


def _family(x, w, top_k):
    p = {"router": w["router"].T, "e_gate": w["gate"].transpose(0, 2, 1),
         "e_up": w["up"].transpose(0, 2, 1),
         "e_down": w["down"].transpose(0, 2, 1)}
    config = {"num_experts_per_tok": top_k, "norm_topk_prob": True}
    return family_moe(x[:, 0], p, config, block=x.shape[0])[:, None]


@pytest.mark.parametrize("rows", [1, 2, 3, 4, 8, 128])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_grouped_equals_dense_equals_the_family_file(form, rows):
    num_experts, top_k, pre = FORMS[form]
    w, x = _weights(num_experts, seed=rows), _rows(rows, seed=rows + 7)
    with jax.default_matmul_precision("highest"):
        dense, grouped = _both(x, w, top_k, pre)
        want = _family(x, w, top_k)
    np.testing.assert_allclose(grouped, dense, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(grouped, want, rtol=1e-5, atol=1e-5)


# every row bucket the five cells' servers compile (a decode group of 1-4
# rows, an 8-row tail, the prefill chunk, the warm-up's fused pack), by the
# router of the cell's configuration; Mistral and Falcon-H1 have no expert
CELL_BUCKETS = {
    # cell: (top_k, experts the router scores, chunk rows, fused rows)
    "qwen3moe-longdoc": (8, 128, 128, 256),
    "deepseekv2-longctx": (6, 160, 512, 1024),
    "qwen3next-longctx": (10, 512, 512, 1024),
}


def _rule_cases():
    cases = []
    for cell, (top_k, experts, chunk, fused) in CELL_BUCKETS.items():
        for rows in (1, 2, 4, 8):
            cases.append((cell, rows, top_k, experts, True, "list"))
        cases.append((cell, 1, top_k, experts, False, "dense"))
        over = "tiled" if chunk >= TILED_MIN_ROWS else "dense"
        cases.append((cell, chunk, top_k, experts, True, over))
        cases.append((cell, chunk, top_k, experts, False, "dense"))
        cases.append((cell, fused, top_k, experts, True, "tiled"))
    # where the list form ends: the rows can hit every expert; Mixtral
    cases += [
        ("qwen3moe-longdoc", 15, 8, 128, True, "list"),
        ("qwen3moe-longdoc", 16, 8, 128, True, "dense"),
        ("deepseekv2-longctx", 26, 6, 160, True, "list"),
        ("deepseekv2-longctx", 32, 6, 160, True, "dense"),
        ("qwen3next-longctx", 64, 10, 512, True, "dense"),
        ("mixtral", 3, 2, 8, True, "list"),
        ("mixtral", 4, 2, 8, True, "dense"),
        ("mixtral", 255, 2, 8, True, "dense"),
        ("mixtral", 256, 2, 8, True, "tiled"),
        ("mixtral", 512, 2, 8, False, "dense"),
    ]
    return cases


@pytest.mark.parametrize("cell,rows,top_k,num_experts,kernels,form", [
    pytest.param(*c, id=f"{c[0]}-r{c[1]}-{'kernels' if c[4] else 'off'}")
    for c in _rule_cases()
])
def test_the_form_follows_the_rows(
        cell, rows, top_k, num_experts, kernels, form):
    """The one rule: the shape the program is compiled for and whether
    kernels may run in it; how many experts the server holds is no part."""
    assert expert_form(rows, top_k, num_experts, kernels) == form


def test_padding_rows_add_no_expert_and_come_out_zero():
    """3 live rows in a bucket of 4: the list holds only what the live rows
    chose (the zero row would have picked experts 0..7, a tie of zeros)."""
    num_experts, top_k, pre = FORMS["qwen3-128-top8"]
    w = _weights(num_experts)
    x = _rows(4).at[3].set(0.0)
    idx, weights = route_topk(x[:, 0] @ w["router"], top_k, pre, pre)
    slot_expert, live, slot_weights = moe._chosen_experts(
        x[:, 0], idx, weights, num_experts)
    chosen_by_live = set(np.asarray(idx[:3]).ravel().tolist())
    assert int(live) == len(chosen_by_live) <= 3 * top_k
    assert set(np.asarray(slot_expert).tolist()) == chosen_by_live
    assert not np.asarray(slot_weights[:, 3]).any()
    assert not np.asarray(slot_weights[int(live):]).any()
    with jax.default_matmul_precision("highest"):
        dense, grouped = _both(x, w, top_k, pre)
    np.testing.assert_allclose(grouped, dense, rtol=1e-5, atol=1e-5)
    assert not np.asarray(grouped[3]).any()


def test_a_step_of_only_padding_rows_lists_nothing():
    num_experts, top_k, pre = FORMS["qwen3-128-top8"]
    w = _weights(num_experts)
    x = jnp.zeros((2, 1, D), jnp.float32)
    _, grouped = _both(x, w, top_k, pre)
    assert not np.asarray(grouped).any()


def test_two_rows_that_chose_the_same_experts_share_the_list():
    num_experts, top_k, pre = FORMS["qwen3-128-top8"]
    w = _weights(num_experts)
    x = jnp.concatenate([_rows(1), 1.5 * _rows(1)])  # same direction
    idx, weights = route_topk(x[:, 0] @ w["router"], top_k, pre, pre)
    _, live, slot_weights = moe._chosen_experts(
        x[:, 0], idx, weights, num_experts)
    shared = len(set(np.asarray(idx).ravel().tolist()))
    assert int(live) == shared < 2 * top_k
    both_rows = np.asarray(slot_weights[: int(live)] > 0).all(axis=1)
    assert both_rows.sum() == 2 * top_k - shared > 0
    with jax.default_matmul_precision("highest"):
        dense, grouped = _both(x, w, top_k, pre)
    np.testing.assert_allclose(grouped, dense, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pre", [True, False], ids=["qwen3", "mixtral"])
def test_a_tie_at_the_kth_place_keeps_exactly_k_lower_index_first(pre):
    """Experts 2 and 5 tie at the second place of a top-2: expert 2 is kept,
    expert 5 is not, and the weights are those of a top-2 (the threshold form
    kept both and renormalised over three)."""
    logits = jnp.asarray([[[0.5, 3.0, 1.0, -1.0, 0.0, 1.0, 0.25, -2.0]]])
    idx, weights = route_topk(logits, 2, pre, pre)
    assert np.asarray(idx).tolist() == [[[1, 2]]]
    spread = np.asarray(router_topk_weights(logits, 2, pre, pre))[0, 0]
    assert (spread > 0).tolist() == [False, True, True] + [False] * 5
    two = np.exp([3.0, 1.0]) / np.exp([3.0, 1.0]).sum()
    np.testing.assert_allclose(spread[[1, 2]], two, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights)[0, 0], two, rtol=1e-6)


def test_a_local_slice_of_the_experts_sums_to_the_whole():
    """parallel/spmd.py's expert shards: the full router's weights sliced to
    the local experts, the dense form on the local stacks, partial sums."""
    num_experts, top_k, pre = FORMS["qwen3-128-top8"]
    w, x = _weights(num_experts), _rows(4)
    full = router_topk_weights(x @ w["router"], top_k, pre, pre)
    with jax.default_matmul_precision("highest"):
        whole = moe_mlp(x, w["router"], w["gate"], w["up"], w["down"], top_k,
                        pre_softmax=pre, norm_topk=pre)
        parts = sum(
            moe_mlp(x, None, w["gate"][s], w["up"][s], w["down"][s], top_k,
                    router_weights=full[..., s])
            for s in (slice(0, 32), slice(32, 64), slice(64, 96),
                      slice(96, 128)))
    np.testing.assert_allclose(parts, whole, rtol=1e-5, atol=1e-5)


def test_the_stacks_of_several_layers_are_addressed_by_base():
    """The span step hands the kernel every layer's experts as one flat
    stack and the row where this layer's start."""
    num_experts, top_k, pre = FORMS["mixtral-8-top2"]
    layers = [_weights(num_experts, seed=s) for s in (3, 4, 5)]
    flat = {k: jnp.concatenate([w[k] for w in layers])
            for k in ("gate", "up", "down")}
    x = _rows(2)
    with jax.default_matmul_precision("highest"):
        for l, w in enumerate(layers):
            dense, _ = _both(x, w, top_k, pre)
            grouped = moe_mlp(
                x, w["router"], flat["gate"], flat["up"], flat["down"], top_k,
                expert_base=jnp.int32(l * num_experts), interpret=True)
            np.testing.assert_allclose(grouped, dense, rtol=1e-5, atol=1e-5)


def test_the_intermediate_dim_is_tiled_when_a_block_would_not_fit(monkeypatch):
    """Mixtral's widths do not fit VMEM whole: tiles of the intermediate
    dim, and a dead slot re-names the last tile fetched."""
    from bloombee_tpu.ops.pallas import grouped_experts as kernel

    assert kernel._i_tile(2048, 768, 2) == 768
    assert kernel._i_tile(4096, 14336, 2) == 512
    monkeypatch.setattr(kernel, "_WEIGHT_BLOCKS_BYTES", 3 * 2 * D * 64 * 4)
    assert kernel._i_tile(D, I, 4) == 128  # the lane tiling's floor
    monkeypatch.setattr(kernel, "_i_tile", lambda d, i, itemsize: 32)
    num_experts, top_k, pre = FORMS["mixtral-8-top2"]
    w, x = _weights(num_experts), _rows(3).at[2].set(0.0)
    with jax.default_matmul_precision("highest"):
        dense = moe_mlp(x, w["router"], w["gate"], w["up"], w["down"], top_k)
        grouped = moe_mlp(x, w["router"], w["gate"], w["up"], w["down"],
                          top_k, expert_base=jnp.int32(0), interpret=True)
    np.testing.assert_allclose(grouped, dense, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------- the tiled form
def _pair_loop(x, idx, weights, w, held=None):
    """The mathematics with nothing shared: one (row, expert) pair at a
    time, float64."""
    x = np.asarray(x, np.float64)[0]
    gate, up, down = (np.asarray(w[k], np.float64)
                      for k in ("gate", "up", "down"))
    out = np.zeros_like(x)
    first, count = held or (0, gate.shape[0])
    for r in range(x.shape[0]):
        for e, p in zip(np.asarray(idx)[0, r], np.asarray(weights)[0, r]):
            if first <= e < first + count:
                g, u = x[r] @ gate[e - first], x[r] @ up[e - first]
                out[r] += p * ((g / (1 + np.exp(-g)) * u) @ down[e - first])
    return out[None]


def _tiled_case(name):
    """name -> (x [1, R, D], weights, top_k, moe_mlp's routing keywords)."""
    rng = np.random.default_rng(sum(map(ord, name)))
    f = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.2, jnp.float32)  # noqa: E731
    experts, top_k, held, kw, rows = 16, 2, None, dict(
        pre_softmax=True, norm_topk=True), 300
    if name == "qwen3next-top10-of-512-held-128":
        experts, top_k, held, rows = 512, 10, (256, 128), 256
    elif name == "deepseekv2-group-limited-i-tiled":
        experts, top_k, held, rows = 32, 6, (8, 8), 256
        kw = dict(groups=8, topk_groups=3, route_scale=16.0)
    elif name == "held-most-pairs-elsewhere":
        experts, top_k, held = 64, 4, (60, 4)
    held_n = held[1] if held else experts
    w = dict(router=f(D, experts), gate=f(held_n, D, I), up=f(held_n, D, I),
             down=f(held_n, I, D))
    x = f(1, rows, D)
    if name == "all-rows-to-one-expert":
        # expert 3 wins every row by far: its run is 300 pairs, five row
        # tiles inside one grid step; the second choice still differs
        w["router"] = w["router"].at[:, 3].set(0.0)
        x = x.at[..., 0].set(9.0)
        w["router"] = w["router"].at[0, 3].set(5.0)
    elif name == "experts-with-no-row":
        x = x.at[..., 0].set(9.0)
        w["router"] = w["router"].at[0, ::2].set(-5.0)  # the even ones lose
    elif name == "padding-rows":
        x = x.at[0, 200:].set(0.0).at[0, 17].set(0.0)
    return x, w, top_k, dict(kw, held=held)


TILED_CASES = [
    "uniform", "all-rows-to-one-expert", "experts-with-no-row",
    "padding-rows", "held-most-pairs-elsewhere",
    "deepseekv2-group-limited-i-tiled", "qwen3next-top10-of-512-held-128",
]


@pytest.mark.parametrize("name", TILED_CASES)
def test_tiled_equals_dense_equals_a_loop_over_the_pairs(name, monkeypatch):
    from bloombee_tpu.ops.pallas import grouped_experts as kernel

    if "i-tiled" in name:  # 128 columns of the intermediate dim in 4 tiles
        monkeypatch.setattr(kernel, "_i_tile", lambda d, i, itemsize: 32)
    x, w, top_k, kw = _tiled_case(name)
    rows = x.shape[1]
    assert moe.expert_form(rows, top_k, w["router"].shape[1], True) == "tiled"
    call = lambda **more: moe_mlp(  # noqa: E731
        x, w["router"], w["gate"], w["up"], w["down"], top_k, **kw, **more)
    with jax.default_matmul_precision("highest"):
        dense = call()
        tiled = call(expert_base=jnp.int32(0), interpret=True)
        idx, weights = route_topk(
            x @ w["router"], top_k,
            **{("scale" if k == "route_scale" else k): v
               for k, v in kw.items() if k != "held"})
    np.testing.assert_allclose(tiled, dense, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        tiled, _pair_loop(x, idx, weights, w, kw["held"]),
        rtol=1e-4, atol=1e-5)
    assert float(np.abs(np.asarray(dense)).max()) > 1e-2  # no empty test
    # the plan: every live pair held here in exactly one expert's run
    held = kw["held"]
    held_n = held[1] if held else w["router"].shape[1]
    here = None
    if held is not None:
        idx, weights, here = moe._held_local(idx, weights, held)
        here = here[0]
    slot_expert, n, start, count, src, _ = (
        np.asarray(a) for a in moe._tiled_plan(
            x[0], idx[0], weights[0], held_n, here))
    live = np.asarray(jnp.any(x[0] != 0, -1))[:, None] & (
        np.ones_like(idx[0], bool) if here is None else np.asarray(here))
    assert count.sum() == live.sum() and (count[n:] == 0).all()
    assert list(slot_expert[:n]) == sorted(set(np.asarray(idx[0])[live]))
    for e, s0, c in zip(slot_expert[:n], start[:n], count[:n]):
        rows_of_e = np.nonzero((np.asarray(idx[0]) == e) & live)[0]
        assert sorted(src[s0:s0 + c]) == sorted(rows_of_e)
    if name == "all-rows-to-one-expert":
        assert count.max() == rows > 4 * kernel.ROW_TILE
    if name == "experts-with-no-row":
        assert 0 < n <= held_n // 2 and not (slot_expert[:n] % 2 == 0).any()
    if name == "padding-rows":
        assert not np.asarray(tiled)[0, 200:].any()
        assert not np.asarray(tiled)[0, 17].any()
        assert not set(src[: count.sum()]) & ({17} | set(range(200, rows)))
    if name == "held-most-pairs-elsewhere":
        assert live.sum() < 0.2 * live.size
        assert (np.asarray(tiled)[0][~live.any(-1)] == 0).all()


def test_tiled_walks_several_layers_stacks_by_base():
    """The stacks of three layers flat over (layer, expert), as
    `lift_expert_stacks` hands them: layer l's experts start at l * E."""
    x, w, top_k, kw = _tiled_case("uniform")
    layers = [_tiled_case("uniform")[1], _tiled_case("padding-rows")[1], w]
    flat = {k: jnp.concatenate([l[k] for l in layers])
            for k in ("gate", "up", "down")}
    with jax.default_matmul_precision("highest"):
        for at, layer in enumerate(layers):
            want = moe_mlp(x, layer["router"], layer["gate"], layer["up"],
                           layer["down"], top_k, **kw)
            got = moe_mlp(x, layer["router"], flat["gate"], flat["up"],
                          flat["down"], top_k, **kw, interpret=True,
                          expert_base=jnp.int32(16 * at))
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ------------------------------------------------ through the span step
def _config(model_type, **more):
    return {
        "model_type": model_type, "hidden_size": 64, "intermediate_size": 96,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_hidden_layers": 2, "vocab_size": 128, "rms_norm_eps": 1e-05,
        "rope_theta": 10000.0, "rope_scaling": None, "hidden_act": "silu",
        "attention_bias": False, "max_position_embeddings": 4096,
        "tie_word_embeddings": False, "torch_dtype": "bfloat16", **more,
    }


CONFIGS = {
    "qwen3_moe": _config(
        "qwen3_moe", architectures=["Qwen3MoeForCausalLM"],
        moe_intermediate_size=32, num_experts=16, num_experts_per_tok=2,
        norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[]),
    "mistral": _config(
        "mistral", architectures=["MistralForCausalLM"], sliding_window=None),
}


def _falcon_h1_config():
    from tests.test_falcon_h1 import CONFIG

    return CONFIG


@pytest.fixture(scope="module")
def spans(tmp_path_factory):
    """model_type -> (checkpoint dir, config, (params, spec))."""
    out = {}
    configs = {**CONFIGS, "falcon_h1": _falcon_h1_config()}
    for name, config in configs.items():
        path = tmp_path_factory.mktemp(f"tiny_{name}")
        checkpoint.write_checkpoint(path, config, 32)
        out[name] = (path, config,
                     load_span_params(str(path), 0, 2, dtype=jnp.float32))
    return out


@pytest.fixture
def kernels_on(monkeypatch):
    """The Pallas paths, interpreted, from the first page on."""
    monkeypatch.setenv("BBTPU_PAGED_INTERPRET", "1")
    monkeypatch.setenv("BBTPU_PAGED_MIN_CONTEXT", "0")


def _executor(span):
    params, spec = span
    manager = CacheManager(
        2, 64, 16, spec.num_key_value_heads, spec.head_dim,
        dtype=jnp.float32, ssm=spec.ssm,
        **({"state_slots": 8} if spec.ssm is not None else {}))
    return SpanExecutor(params, spec, manager, compute_dtype=jnp.float32)


def _hidden(seed, t):
    return np.random.default_rng(seed).standard_normal((1, t, 64)).astype(
        np.float32)


def _reference_hidden(path, config, hidden):
    family = families.of(config)
    with jax.default_matmul_precision("highest"):
        h, pos = jnp.asarray(hidden), jnp.arange(hidden.shape[0])
        for layer in range(config["num_hidden_layers"]):
            h = family.layer_forward(
                reference.layer_params(path, config, layer), config, h, pos)
        return np.asarray(h)


def test_decode_groups_take_the_grouped_form_and_match_the_family_file(
        spans, kernels_on):
    """Two sessions: a 20-token prompt as a chunk (32 rows: dense), then
    decode steps alone (1 row), as a group of two and as a group of three in
    a bucket of four (a padding row): all grouped, all as the family file's
    full forward."""
    path, config, span = spans["qwen3_moe"]
    a, b, c = _hidden(1, 24), _hidden(2, 24), _hidden(3, 24)

    async def run():
        ex = _executor(span)
        m = ex.manager
        got = {}
        async with m.allocate(1, 64) as ha, m.allocate(1, 64) as hb, \
                m.allocate(1, 64) as hc:
            for h, hidden in ((ha, a), (hb, b), (hc, c)):
                ex.prefill_chunk(h, hidden[:, :20])
            assert ex.moe_dispatches == {"grouped": 0, "tiled": 0, "dense": 3}
            got["solo"] = ex.decode(ha, a[:, 20:21], commit=False)
            two, _ = ex.decode_group([ha, hb], [a[:, 21:22], b[:, 20:21]])
            three, _ = ex.decode_group(
                [ha, hb, hc], [a[:, 22:23], b[:, 21:22], c[:, 20:21]])
            assert ex.moe_dispatches == {"grouped": 3, "tiled": 0, "dense": 3}
            assert ex.kernel_fallbacks == 0
        return got, two, three

    with jax.default_matmul_precision("highest"):
        got, two, three = asyncio.run(run())
    want = {k: _reference_hidden(path, config, v[0]) for k, v in
            (("a", a), ("b", b), ("c", c))}
    close = lambda x, y: np.testing.assert_allclose(  # noqa: E731
        np.asarray(x).reshape(-1), y, rtol=2e-4, atol=2e-5)
    close(got["solo"], want["a"][20])
    close(two[0], want["a"][21])
    close(two[1], want["b"][20])
    close(three[0], want["a"][22])
    close(three[1], want["b"][21])
    close(three[2], want["c"][20])


def test_a_chunk_above_the_ridge_takes_the_tiled_form(
        spans, kernels_on, monkeypatch):
    """A 256-row chunk that attends through flash: no paged kernel in its
    program, yet its experts take the tiled form, equal to the family file's
    forward; the 32-row tail after it (32 * 2 >= 16 experts) stays dense.
    Without the flash switch the same chunk attends densely and so do its
    experts."""
    path, config, span = spans["qwen3_moe"]
    a = _hidden(4, 288)

    async def run():
        ex = _executor(span)
        async with ex.manager.allocate(1, 320) as ha:
            first = ex.prefill_chunk(ha, a[:, :256])
            tail = ex.prefill_chunk(ha, a[:, 256:288])
        return ex, np.concatenate([np.asarray(first), np.asarray(tail)], 1)

    with jax.default_matmul_precision("highest"):
        plain, want_dense = asyncio.run(run())
        monkeypatch.setenv("BBTPU_FLASH_INTERPRET", "1")
        ex, got = asyncio.run(run())
    assert plain.moe_dispatches == {"grouped": 0, "tiled": 0, "dense": 2}
    assert ex.moe_dispatches == {"grouped": 0, "tiled": 1, "dense": 1}
    assert ex.attn_dispatches["flash"] == 1 and ex.kernel_fallbacks == 0
    want = _reference_hidden(path, config, a[0])
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(want_dense[0], want, rtol=2e-4, atol=2e-5)


def test_without_the_kernel_switch_every_step_stays_dense(spans):
    """Off the TPU with no interpret switch no Pallas kernel may run: the
    decode step computes the dense form, as under a mesh."""
    path, config, span = spans["qwen3_moe"]
    a = _hidden(1, 24)

    async def run():
        ex = _executor(span)
        async with ex.manager.allocate(1, 64) as ha:
            ex.prefill_chunk(ha, a[:, :20])
            out = ex.decode(ha, a[:, 20:21], commit=False)
            assert ex.moe_dispatches == {"grouped": 0, "tiled": 0, "dense": 2}
        return out

    with jax.default_matmul_precision("highest"):
        out = asyncio.run(run())
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1), _reference_hidden(path, config, a[0])[20],
        rtol=2e-4, atol=2e-5)


def test_quantised_stacks_stay_dense(spans, kernels_on):
    from bloombee_tpu.models.wquant import quantize_span_params

    params, spec = spans["qwen3_moe"][2]
    quantised = quantize_span_params(params, 8)
    assert step_module.experts_form(spec, params, 2, True) == "list"
    assert step_module.experts_form(spec, params, 512, True) == "tiled"
    for stacks, rows, kernels in (
        (quantised, 2, True), (quantised, 512, True), (params, 2, False),
        (params, 512, False), (params, 8, True),
    ):
        assert step_module.experts_form(spec, stacks, rows, kernels) == "dense"
    assert step_module.lift_expert_stacks(spec, quantised, 2, True) == (
        quantised, None)


@pytest.mark.parametrize("family", ["mistral", "falcon_h1"])
def test_a_family_without_experts_never_reaches_the_expert_code(
        spans, kernels_on, monkeypatch, family):
    """Every entry of the expert path replaced by one that raises: a chunk,
    a decode step, a decode group and a ragged pack of a dense family run
    through `span_step_packed` and `span_step_ragged`, and no count moves."""
    from bloombee_tpu.ops.pallas import grouped_experts as kernel
    from bloombee_tpu.runtime import layer_body

    def unreachable(*_a, **_k):
        raise AssertionError("the expert path was reached")

    for module, name in (
        (moe, "moe_mlp"), (moe, "route_topk"), (moe, "expert_form"),
        (layer_body, "moe_mlp"), (step_module, "expert_form"),
        (kernel, "grouped_experts"), (kernel, "tiled_experts"),
    ):
        monkeypatch.setattr(module, name, unreachable)
    a, b = _hidden(1, 24), _hidden(2, 24)

    async def run():
        ex = _executor(spans[family][2])
        m = ex.manager
        async with m.allocate(1, 64) as ha, m.allocate(1, 64) as hb:
            ex.prefill_chunk(ha, a[:, :8])
            ex.prefill_chunk(hb, b[:, :8])
            ex.decode(ha, a[:, 8:9], commit=False)
            ex.decode_group([ha, hb], [a[:, 9:10], b[:, 8:9]])
            ex.ragged_group([ha, hb], [a[:, 10:11], b[:, 9:14]])
            assert ex.attn_dispatches["ragged"] == 1
            assert ex.attn_dispatches["paged"] >= 2
            return dict(ex.moe_dispatches)

    assert asyncio.run(run()) == {"grouped": 0, "tiled": 0, "dense": 0}


async def _served(path, uid, steps):
    """One BlockServer over the checkpoint, `steps` decode steps of one
    session after an 8-token prompt; its rpc_info."""
    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.server.block_server import BlockServer
    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer
    from bloombee_tpu.wire.rpc import connect

    reg = RegistryServer(host="127.0.0.1")
    await reg.start()
    server = BlockServer(
        model_uid=uid, start=0, end=2, model_dir=str(path),
        registry=RegistryClient("127.0.0.1", reg.port),
        compute_dtype=jnp.float32, page_size=4, num_pages=64)
    await server.start()
    try:
        model = DistributedModelForCausalLM.from_pretrained(
            str(path), RegistryClient("127.0.0.1", reg.port),
            model_uid=uid, dtype=jnp.float32)
        ids = np.random.default_rng(5).integers(0, 128, (1, 8 + steps))
        async with model.inference_session(32, 1) as session:
            await session.step(model.embed(ids[:, :8]), ids=ids[:, :8])
            for t in range(8, 8 + steps):
                await session.step(
                    model.embed(ids[:, t:t + 1]), ids=ids[:, t:t + 1])
        conn = await connect("127.0.0.1", server.port)
        info, _ = await conn.call("rpc_info", {})
        await conn.close()
        return info
    finally:
        await server.stop()
        await reg.stop()


def test_rpc_info_counts_the_forms_only_for_a_family_with_experts(
        spans, kernels_on):
    before = asyncio.run(_served(spans["qwen3_moe"][0], "tiny-moe", 0))
    info = asyncio.run(_served(spans["qwen3_moe"][0], "tiny-moe", 3))
    moved = {k: info["moe"][k] - before["moe"][k] for k in info["moe"]}
    assert moved == {
        "grouped_dispatches": 3, "tiled_dispatches": 0, "dense_dispatches": 0}
    assert info["kernel_fallbacks"] == 0
    assert "moe" not in asyncio.run(_served(spans["mistral"][0], "tiny-m", 2))
