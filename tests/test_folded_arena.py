"""The K/V arena's layout is a rule of its shape (kv/arena.py `folds`): a
slab whose heads are no whole tile is stored folded, [tokens * heads,
head_dim], and every path that addresses the arena sees that in its input.

On the CPU the two layouts hold the same numbers, so everything here is
BIT-equal between them: the arena's own helpers, the manager's slot-addressed
paths (speculative compaction, park / unpark, replication export / install)
and whole span steps through a `BlockServer`'s executor, at a tiny attention
spec of 2 KV heads x 256 and a tiny `qwen3_next` of the same heads. The
unfolded side is the same code under a `folds` that says no. What the rule
costs and saves on the device is `tests/test_chip_compile.py`'s.
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

from bloombee_tpu.kv import arena  # noqa: E402
from bloombee_tpu.kv.cache_manager import CacheManager  # noqa: E402
from bloombee_tpu.models.llama.block import init_block_params  # noqa: E402
from bloombee_tpu.models.spec import ModelSpec  # noqa: E402
from bloombee_tpu.utils.tree import stack_params  # noqa: E402
from cellbench import checkpoint  # noqa: E402

KERNELS = {"BBTPU_PAGED_INTERPRET": "1", "BBTPU_PAGED_MIN_CONTEXT": "0",
           "BBTPU_FLASH_INTERPRET": "1"}
KVH, HD, PAGE = 2, 256, 16
LLAMA = ModelSpec(
    family="llama", hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=KVH, head_dim=HD,
    num_hidden_layers=2, vocab_size=64, rope_theta=10000.0,
)
# tests/test_qwen3_next.py's tiny model with the published heads: 2 x 256
Q3N = {
    "model_type": "qwen3_next", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": KVH, "head_dim": HD, "partial_rotary_factor": 0.25,
    "full_attention_interval": 4, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 16, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_value_head_dim": 16,
    "num_experts": 4, "router_experts": 8, "experts_held": [2, 4],
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "intermediate_size": 96, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "num_hidden_layers": 4, "vocab_size": 128, "rms_norm_eps": 1e-6,
    "rope_theta": 10000000, "rope_scaling": None, "hidden_act": "silu",
    "max_position_embeddings": 1024, "tie_word_embeddings": False,
    "use_sliding_window": False, "torch_dtype": "bfloat16",
}


# ------------------------------------------------------------------ the rule
@pytest.mark.parametrize("kvh,hd,dtype,folded", [
    (8, 128, "bfloat16", False),  # Mistral, Falcon-H1
    (4, 128, "bfloat16", False),  # Qwen3-30B-A3B
    (2, 256, "bfloat16", True),  # Qwen3-Next
    (10, 128, "bfloat16", True),  # phi4flash's pairs
    (4, 256, "bfloat16", True), (16, 256, "bfloat16", False),
    (1, 128, "bfloat16", False), (2, 128, "float32", False),
    (3, 128, "bfloat16", True), (12, 128, "float32", True),
    (24, 128, "bfloat16", False), (2, 512, "float32", True),
    (2, 64, "bfloat16", False),  # no whole lanes: re-laid out either way
    (2, 256, "int8", False),  # an 8-bit type tiles otherwise: not claimed
])
def test_the_rule_reads_the_shape(kvh, hd, dtype, folded):
    assert arena.folds(kvh, hd, dtype) is folded
    made = arena.make_arena(2, 4, PAGE, kvh, hd, jnp.dtype(dtype))
    want = (2, 4 * PAGE * kvh, hd) if folded else (2, 4 * PAGE, kvh, hd)
    assert made["k"].shape == made["v"].shape == want
    assert arena.arena_tokens(made["k"], kvh) == 4 * PAGE
    # a mesh shards the head axis, an int4 slab and a latent page have no
    # folded form: unfolded whatever the rule says
    assert arena.make_arena(
        2, 4, PAGE, kvh, hd, jnp.dtype(dtype), sharded=True
    )["k"].shape == (2, 4 * PAGE, kvh, hd)
    if hd % 32 == 0:
        assert arena.make_arena(
            2, 4, PAGE, kvh, hd, quant="int4"
        )["k"].codes.shape == (2, 4 * PAGE, kvh, hd // 2)
    latent = arena.make_arena(2, 4, PAGE, kvh, hd, payload=((hd,), (64,)))
    assert latent["k"].shape == (2, 4 * PAGE, hd)


def test_folded_helpers_address_the_same_tokens():
    """`arena_write`, `gather_pages`, `heads_view` and `slot_rows` on a
    folded slab against the unfolded one: same slots, same pages, an
    out-of-range slot dropped, a page gathered whole."""
    rng = np.random.default_rng(0)
    pages, n = 6, 9
    plain = jnp.zeros((pages * PAGE, KVH, HD), jnp.float32)
    folded = jnp.zeros((pages * PAGE * KVH, HD), jnp.float32)
    slots = jnp.asarray(
        [3, 95, 17, pages * PAGE, 40, 41, 42, -1 % (pages * PAGE), 0],
        jnp.int32)
    k, v = (jnp.asarray(rng.standard_normal((n, KVH, HD)), jnp.float32)
            for _ in range(2))
    pk, pv = arena.arena_write(plain, plain, slots, k, v)
    fk, fv = arena.arena_write(folded, folded, slots, k, v)
    assert fk.shape == folded.shape
    np.testing.assert_array_equal(arena.heads_view(fk, KVH), pk)
    np.testing.assert_array_equal(arena.heads_view(fv, KVH), pv)
    assert arena.heads_view(pk, KVH) is pk
    table = jnp.asarray([[5, 0, 2], [1, 1, 3]], jnp.int32)
    np.testing.assert_array_equal(
        arena.gather_pages(fk, table, PAGE, KVH),
        arena.gather_pages(pk, table, PAGE, KVH))
    np.testing.assert_array_equal(
        arena.gather_pages(pk, table, PAGE),
        arena.gather_pages(pk, table, PAGE, KVH))
    rows = arena.slot_rows(np.asarray([0, 5, 96], np.int32), KVH)
    assert rows.tolist() == [0, 1, 10, 11, 192, 193]
    assert isinstance(rows, np.ndarray)
    assert arena.slot_rows(rows, 1) is rows


# --------------------------------------------- the manager's slot-addressed paths
def _no_fold(*_):
    """A `folds` that says no: the unfolded side of a comparison."""
    return False


def _manager(folded: bool, **kw):
    with pytest.MonkeyPatch.context() as mp:
        if not folded:
            mp.setattr(arena, "folds", _no_fold)
        m = CacheManager(2, 12, 4, KVH, HD, dtype=jnp.float32, **kw)
    assert m.folded is folded
    assert m.arena["k"].ndim == (3 if folded else 4)
    return m


def _tokens(m: CacheManager, leaf: str, slots):
    """The arena's tokens at `slots`, [L, n, heads, head_dim], whatever the
    layout."""
    return np.asarray(m.arena[leaf]).reshape(2, -1, KVH, HD)[:, slots]


def _fill(m: CacheManager, handle, n: int, seed: int, commit=True):
    """`n` more tokens of random K and V for the handle's one sequence,
    layer by layer, through `arena_write`."""
    rng = np.random.default_rng(seed)
    slots = jnp.asarray(m.write_slots(handle, n, commit=commit).reshape(-1))
    for layer in range(2):
        k, v = (jnp.asarray(rng.standard_normal((n, KVH, HD)), jnp.float32)
                for _ in range(2))
        new_k, new_v = arena.arena_write(
            m.arena["k"][layer], m.arena["v"][layer], slots, k, v)
        m.arena["k"] = m.arena["k"].at[layer].set(new_k)
        m.arena["v"] = m.arena["v"].at[layer].set(new_v)


@pytest.mark.parametrize(
    "path", ["park", "park_quant", "rollback", "cow", "replicate"])
def test_manager_paths_round_trip_in_both_layouts(path, monkeypatch):
    """Token slots become a folded arena's rows in ONE helper
    (`CacheManager._rows`); each path that uses it leaves the two layouts
    holding the same tokens, and what goes to the host or over the wire is
    [.., heads, head_dim] in both."""
    if path == "park_quant":
        monkeypatch.setenv("BBTPU_PARK_QUANT", "1")
    kw = dict(prefix_cache=True) if path == "replicate" else {}

    async def run(folded):
        m = _manager(folded, **kw)
        async with m.allocate(1, 24) as h, m.allocate(1, 16) as other:
            sid = h.seq_ids[0]
            _fill(m, other, 5, 7)  # a neighbour whose rows must not move
            _fill(m, h, 9, 1)
            held = m.table.prefix_slots(sid)
            before = [_tokens(m, x, held) for x in "kv"]
            if path in ("park", "park_quant"):
                m.park_sequence(sid)
                _fill(m, other, 6, 8)  # the freed pages are written over
                m.unpark_sequence(sid)
                after = [
                    _tokens(m, x, m.table.prefix_slots(sid)) for x in "kv"]
                if path == "park":  # the round trip is the identity
                    for a, b in zip(after, before):
                        np.testing.assert_array_equal(a, b)
                return after
            if path == "rollback":
                _fill(m, h, 6, 2, commit=False)  # a tree of six rows
                tree = m.table.range_slots(sid, 9, 15)
                want = _tokens(m, "k", tree[[0, 2, 5]])
                m.accept_speculative(h, [np.asarray([0, 2, 5])])
                assert m.context_lens(h).tolist() == [12]
                after = [
                    _tokens(m, x, m.table.prefix_slots(sid)) for x in "kv"]
                np.testing.assert_array_equal(after[0][:, :9], before[0])
                np.testing.assert_array_equal(after[0][:, 9:], want)
                return after
            if path == "cow":  # a copy-on-write page pair, by hand
                m.table.take_pending_copies = lambda: [(held[0] // 4, 11)]
                m._apply_pending_copies()
                del m.table.take_pending_copies
                copied = [
                    _tokens(m, x, np.arange(44, 48, dtype=np.int32))
                    for x in "kv"]
                np.testing.assert_array_equal(copied[0], before[0][:, :4])
                return copied
            m.table.set_seq_hashes(sid, ["a", "b"])
            k_dev, v_dev, hi = m.export_pages(sid, 0, 2)
            assert hi == 2 and k_dev.shape == (2, 8, KVH, HD)
            np.testing.assert_array_equal(np.asarray(k_dev), before[0][:, :8])
            return [np.asarray(k_dev), np.asarray(v_dev)]

    got = {f: asyncio.run(run(f)) for f in (True, False)}
    for a, b in zip(got[True], got[False]):
        assert a.shape[-2:] == (KVH, HD) and np.abs(a).max() > 0
        np.testing.assert_array_equal(a, b)
    if path == "replicate":
        # the exported pages install into a manager of EITHER layout
        k_pages, v_pages = (
            np.swapaxes(x.reshape(2, 2, 4, KVH, HD), 0, 1) for x in got[True])
        for folded in (True, False):
            m = _manager(folded, prefix_cache=True)
            assert m.install_replicated(["a", "b"], k_pages, v_pages) == 2
            pages = [m.table._pool[h] for h in ("a", "b")]
            slots = np.concatenate(
                [np.arange(p * 4, p * 4 + 4) for p in pages])
            np.testing.assert_array_equal(
                _tokens(m, "v", slots), got[True][1])
            with pytest.raises(ValueError, match="arena geometry"):
                m.install_replicated(
                    ["c"], k_pages[:1, :, :, :1], v_pages[:1, :, :, :1])


# ------------------------------------- whole steps through a BlockServer
def _llama_params():
    keys = jax.random.split(jax.random.PRNGKey(46), LLAMA.num_hidden_layers)
    return stack_params([
        init_block_params(k, LLAMA, dtype=jnp.float32) for k in keys])


def _hidden(seed, t, d=64):
    return (0.05 * np.random.default_rng(seed).standard_normal(
        (1, t, d))).astype(np.float32)


def _server(model: str, folded: bool, tmp):
    """A BlockServer over the tiny span, its arena made by the rule (2 x 256
    folds) or under a `folds` that says no."""
    from bloombee_tpu.server.block_server import BlockServer

    kw = dict(compute_dtype=jnp.float32, num_pages=160, page_size=PAGE,
              prefill_chunk=512)
    with pytest.MonkeyPatch.context() as mp:
        if not folded:
            mp.setattr(arena, "folds", _no_fold)
        if model == "llama":
            server = BlockServer(
                model_uid="m", start=0, end=LLAMA.num_hidden_layers,
                params=_llama_params(), spec=LLAMA, **kw)
        else:
            server = BlockServer(
                model_uid="m", start=0, end=Q3N["num_hidden_layers"],
                model_dir=str(tmp), experts=tuple(Q3N["experts_held"]), **kw)
    assert server.manager.folded is folded
    return server


CASES = {
    # case -> the models that can run it (a family with recurrent state
    # has no speculative compaction and is never parked)
    "decode_group": ("llama", "qwen3_next"),
    "chunk512": ("llama", "qwen3_next"),
    "tail": ("llama", "qwen3_next"),
    "fused_pack": ("llama", "qwen3_next"),
    "rollback": ("llama",),
    "park_unpark": ("llama",),
}


async def _drive(server, case: str):
    """One case through the server's executor and manager: the step
    outputs, then the sequences' K and V as [L, tokens, heads, head_dim]."""
    ex, m = server.executor, server.manager
    outs = []

    def kept(*handles):
        layers = m.arena["k"].shape[0]
        for h in handles:
            slots = m.table.prefix_slots(h.seq_ids[0], committed_only=False)
            if not len(slots):
                continue  # the case used one sequence
            outs.extend(
                np.asarray(m.arena[x]).reshape(layers, -1, KVH, HD)[:, slots]
                for x in "kv")

    async with m.allocate(1, 640) as ha, m.allocate(1, 640) as hb:
        if case == "decode_group":
            outs.append(np.asarray(ex.prefill(ha, _hidden(1, 21))))
            outs.append(np.asarray(ex.prefill(hb, _hidden(2, 40))))
            for i in range(3):
                out, both = ex.decode_group(
                    [ha, hb], [_hidden(10 + i, 1), _hidden(20 + i, 1)])
                m.commit(both)
                outs.append(np.asarray(out))
        elif case in ("chunk512", "tail"):
            outs.append(np.asarray(ex.prefill(ha, _hidden(3, 512))))
            if case == "tail":
                outs.append(np.asarray(ex.prefill(ha, _hidden(4, 5))))
            outs.append(np.asarray(ex.decode(ha, _hidden(5, 1))))
        elif case == "fused_pack":
            outs.append(np.asarray(ex.prefill(ha, _hidden(6, 13))))
            outs.append(np.asarray(ex.prefill(hb, _hidden(7, 9))))
            out, both = ex.ragged_group(
                [ha, hb], [_hidden(8, 1), _hidden(9, 89)],
                tree_masks=[None, None], depths_list=[None, None])
            m.commit(both)
            outs.append(np.asarray(out))
            outs.append(np.asarray(ex.decode(hb, _hidden(11, 1))))
        elif case == "rollback":
            outs.append(np.asarray(ex.prefill(ha, _hidden(12, 19))))
            # a tree of five: 0 -> (1, 2), 1 -> 3, 2 -> 4; the path 0, 2, 4
            parent = [-1, 0, 0, 1, 2]
            mask = np.eye(5, dtype=bool)
            for i, p in enumerate(parent):
                while p >= 0:
                    mask[i, p] = True
                    p = parent[p]
            outs.append(np.asarray(ex.decode(
                ha, _hidden(13, 5), commit=False, tree_mask=mask[None],
                depths=np.asarray([[0, 1, 1, 2, 2]], np.int32))))
            m.accept_speculative(ha, [np.asarray([0, 2, 4])])
            outs.append(np.asarray(ex.decode(ha, _hidden(14, 1))))
        elif case == "park_unpark":
            outs.append(np.asarray(ex.prefill(ha, _hidden(15, 37))))
            outs.append(np.asarray(ex.prefill(hb, _hidden(16, 8))))
            m.park_sequence(ha.seq_ids[0])
            outs.append(np.asarray(ex.prefill(hb, _hidden(17, 60))))
            m.ensure_resident(ha)
            outs.append(np.asarray(ex.decode(ha, _hidden(18, 1))))
        kept(ha, hb)
    assert ex.kernel_fallbacks == 0
    return outs


@pytest.fixture(scope="module")
def q3n_dir(tmp_path_factory):
    path = tmp_path_factory.mktemp("tiny_qwen3_next_2x256")
    checkpoint.write_checkpoint(path, Q3N, 46)
    return path


@pytest.mark.parametrize("model,case", [
    (model, case) for case, models in CASES.items() for model in models])
def test_folded_equals_unfolded_through_a_block_server(
        model, case, q3n_dir, monkeypatch):
    """The same rows through a BlockServer whose arena the rule folded and
    through one under a rule that says no, kernels on (interpreted): every
    step's output and the K and V the sequences hold afterwards, bit for
    bit."""
    for k, v in KERNELS.items():
        monkeypatch.setenv(k, v)
    got = {}
    for folded in (True, False):
        server = _server(model, folded, q3n_dir)
        got[folded] = asyncio.run(_drive(server, case))
        ex = server.executor
        assert sum(ex.attn_dispatches[k] for k in (
            "paged", "flash", "ragged")) > 0, ex.attn_dispatches
    assert len(got[True]) == len(got[False]) >= 4
    for a, b in zip(got[True], got[False]):
        assert a.shape == b.shape and np.isfinite(a).all()
        assert np.abs(a).max() > 0
        np.testing.assert_array_equal(a, b)


def test_rpc_info_and_the_step_span_say_the_layout(q3n_dir, monkeypatch):
    """`rpc_info["kv"]["folded"]`, and the same word on `bbtpu.step`."""
    from bloombee_tpu.utils import jitwatch

    monkeypatch.setenv("BBTPU_JITWATCH", "1")
    seen = []
    real = jitwatch.span

    def span(name, **ids):
        if name == "bbtpu.step":
            seen.append(ids)
        return real(name, **ids)

    monkeypatch.setattr(jitwatch, "span", span)
    for folded in (True, False):
        server = _server("qwen3_next", folded, q3n_dir)
        info, _ = asyncio.run(server._rpc_info({}, []))
        assert info["kv"] == {
            "folded": folded, "chunk_page_writes": 0, "chunk_row_writes": 0}

        async def step(server=server):
            async with server.manager.allocate(1, 32) as h:
                server.executor.prefill(h, _hidden(19, 7))
                server.executor.prefill(h, _hidden(20, 19))
            async with server.manager.allocate(1, 32) as h:
                server.executor.prefill(h, _hidden(21, 19))

        asyncio.run(step())
        assert seen[-1]["arena"] == ("folded" if folded else "unfolded")
        # 7 rows are under one page, 19 more start inside it: row by row.
        # 19 rows from a page's first token, in a bucket of 32, are page
        # groups, written by page in both layouts (a slab the server's rule
        # left unfolded has a free page view under that rule)
        assert [ids["write"] for ids in seen[-3:]] == ["rows", "rows", "pages"]
        assert server.executor.kv_writes == {
            "chunk_page_writes": 1, "chunk_row_writes": 2}
    # a shape the rule leaves alone says so too
    plain = CacheManager(2, 4, 4, 8, 128)
    assert plain.folded is False and plain.arena["k"].ndim == 4


def test_an_arena_of_the_other_layout_is_refused(monkeypatch):
    """The executor holds the arena to the rule: a manager made under
    another one, or a folded one handed to a mesh, does not start."""
    from bloombee_tpu.runtime.executor import SpanExecutor

    params = _llama_params()
    with pytest.raises(ValueError, match="take the other layout"):
        SpanExecutor(params, LLAMA, _manager(False), compute_dtype=jnp.float32)
    SpanExecutor(params, LLAMA, _manager(True), compute_dtype=jnp.float32)
    sharded = CacheManager(
        2, 12, 4, KVH, HD, dtype=jnp.float32, sharded=True)
    assert sharded.folded is False
