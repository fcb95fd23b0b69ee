"""Span step parity: paged prefill + decode vs dense HF reference.

The TPU-native analogue of /root/reference/tests/test_block_exact_match.py's
step-wise inference check (atol 1e-3), across a whole span with the paged KV
arena instead of dense concat caches.
"""

import asyncio

import numpy as np
import pytest
import torch

import jax.numpy as jnp

from bloombee_tpu.kv.cache_manager import CacheManager
from bloombee_tpu.models.llama.block import HF_BLOCK_KEYS, convert_hf_block_params
from bloombee_tpu.models.llama.config import llama_spec_from_hf
from bloombee_tpu.runtime.executor import SpanExecutor
from bloombee_tpu.utils.tree import stack_params


@pytest.fixture(scope="module")
def setup():
    from transformers import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig(
        hidden_size=64,
        intermediate_size=128,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_hidden_layers=3,
        vocab_size=256,
        max_position_embeddings=128,
        rms_norm_eps=1e-5,
    )
    torch.manual_seed(0)
    model = LlamaForCausalLM(config).eval().to(torch.float32)
    spec = llama_spec_from_hf(config)
    layers = []
    for layer in model.model.layers:
        sd = layer.state_dict()
        layers.append(
            convert_hf_block_params({k: sd[k].numpy() for k in HF_BLOCK_KEYS})
        )
    params = stack_params(layers)
    return model, config, spec, params


def hf_span_forward(model, hidden_t: torch.Tensor) -> np.ndarray:
    """Dense full-sequence forward through all decoder layers (no norm/head)."""
    t = hidden_t.shape[1]
    position_ids = torch.arange(t).unsqueeze(0).expand(hidden_t.shape[0], -1)
    cos, sin = model.model.rotary_emb(hidden_t, position_ids)
    h = hidden_t
    with torch.no_grad():
        for layer in model.model.layers:
            out = layer(h, position_embeddings=(cos, sin), attention_mask=None)
            h = out[0] if isinstance(out, tuple) else out
    return h.numpy()


def make_executor(spec, params, **kw):
    manager = CacheManager(
        num_layers=spec.num_hidden_layers,
        num_pages=32,
        page_size=4,
        n_kv_heads=spec.num_key_value_heads,
        head_dim=spec.head_dim,
        dtype=jnp.float32,
    )
    ex = SpanExecutor(
        params, spec, manager, compute_dtype=jnp.float32, **kw
    )
    return manager, ex


def test_prefill_then_decode_matches_dense(setup):
    model, config, spec, params = setup
    b, total, prefill = 2, 12, 7
    torch.manual_seed(3)
    hidden = torch.randn(b, total, config.hidden_size)
    ref = hf_span_forward(model, hidden)

    manager, ex = make_executor(spec, params)

    async def run():
        async with manager.allocate(b, 32) as handle:
            out_pre = ex.prefill(handle, hidden[:, :prefill].numpy())
            np.testing.assert_allclose(
                out_pre, ref[:, :prefill], atol=1e-3, rtol=1e-3
            )
            for i in range(prefill, total):
                out_i = ex.decode(handle, hidden[:, i : i + 1].numpy())
                np.testing.assert_allclose(
                    out_i, ref[:, i : i + 1], atol=1e-3, rtol=1e-3,
                    err_msg=f"decode step {i}",
                )
            assert manager.context_lens(handle).tolist() == [total, total]

    asyncio.run(run())


def test_host_path_of_a_served_chunk_and_decode_step(setup, monkeypatch):
    """A chunk and a decode step run as compute-queue tasks with the witness
    on: `host_path` holds one task and ONE launch a kind, the launch said what
    the device was doing (the region carries `device`), the legs sum to the
    task's wall and the unnamed rest is under the sum of the named legs.
    (Task number 0 is one of those read in full: CPU and launches.)"""
    from bloombee_tpu.server.compute_queue import _WorkerAccount
    from bloombee_tpu.utils import jitwatch

    monkeypatch.setenv("BBTPU_JITWATCH", "1")
    jitwatch.reset()
    model, config, spec, params = setup
    torch.manual_seed(5)
    hidden = torch.randn(1, 9, config.hidden_size).numpy()
    manager, ex = make_executor(spec, params)
    account = _WorkerAccount()

    async def run():
        async with manager.allocate(1, 32) as handle:
            account.wrap(
                lambda: ex.prefill_chunk(
                    handle, hidden[:, :8], commit=True, fetch=False),
                0, task=0, **{"class": "prefill"},
            )()
            account.wrap(
                lambda: ex.decode(handle, hidden[:, 8:9], fetch=False),
                0, task=0, kinds="decode1",
            )()

    asyncio.run(run())
    path = account.host_path()
    assert set(path) == {"chunk", "decode"}
    for kind, rec in path.items():
        assert (rec["n"], rec["launches"]) == (1, 1), (kind, rec)
        legs = rec["legs"]
        assert {"bbtpu.pack", "bbtpu.h2d", "jit_call", "bbtpu.counters",
                "bbtpu.slice", "unnamed"} <= set(legs), legs
        walls = {leg: v["wall_ms"] for leg, v in legs.items()}
        assert sum(walls.values()) == pytest.approx(rec["wall_ms"], abs=1e-5)
        assert walls["unnamed"] < sum(walls.values()) - walls["unnamed"]
        assert rec["jit_idle_ms"] + rec["jit_busy_ms"] == pytest.approx(
            walls["jit_call"], abs=1e-5)
        assert rec["full_n"] == 1 and rec["cpu_ms"] <= rec["cpu_wall_ms"]
    assert sum(r["wall_ms"] for r in path.values()) == pytest.approx(
        account.stats_ms()["busy_ms"], abs=2e-3)
    jitwatch.reset()


def test_chunked_prefill_matches(setup):
    model, config, spec, params = setup
    b, total = 1, 11
    torch.manual_seed(4)
    hidden = torch.randn(b, total, config.hidden_size)
    ref = hf_span_forward(model, hidden)

    manager, ex = make_executor(spec, params, max_chunk_tokens=4)

    async def run():
        async with manager.allocate(b, 16) as handle:
            out = ex.prefill(handle, hidden.numpy())
            np.testing.assert_allclose(out, ref, atol=1e-3, rtol=1e-3)

    asyncio.run(run())


def test_non_pow2_batch_padding(setup):
    model, config, spec, params = setup
    b, total = 3, 6
    torch.manual_seed(5)
    hidden = torch.randn(b, total, config.hidden_size)
    ref = hf_span_forward(model, hidden)

    manager, ex = make_executor(spec, params)

    async def run():
        async with manager.allocate(b, 8) as handle:
            out = ex.prefill(handle, hidden.numpy())
            np.testing.assert_allclose(out, ref, atol=1e-3, rtol=1e-3)

    asyncio.run(run())


def test_speculative_decode_rollback(setup):
    """Write speculative tokens uncommitted, roll back, decode the true token —
    result must match the no-speculation path (paged commit/rollback with the
    arena: reference paged_kv spec-dec routing tests)."""
    model, config, spec, params = setup
    b, prefill = 1, 5
    torch.manual_seed(6)
    hidden = torch.randn(b, prefill + 1, config.hidden_size)
    ref = hf_span_forward(model, hidden)

    manager, ex = make_executor(spec, params)

    async def run():
        async with manager.allocate(b, 16) as handle:
            ex.prefill(handle, hidden[:, :prefill].numpy())
            # speculative garbage tokens, uncommitted
            garbage = np.random.default_rng(0).normal(
                size=(b, 3, config.hidden_size)
            ).astype(np.float32)
            ex.decode(handle, garbage, commit=False)
            assert manager.context_lens(handle).tolist() == [prefill + 3]
            manager.rollback(handle)
            assert manager.context_lens(handle).tolist() == [prefill]
            out = ex.decode(handle, hidden[:, prefill:].numpy())
            np.testing.assert_allclose(
                out, ref[:, prefill:], atol=1e-3, rtol=1e-3
            )

    asyncio.run(run())


def test_packed_payload_bitcast_roundtrip_bf16_and_f32():
    """pack_step_payload's single-buffer bitcast must round-trip exactly on
    the device for BOTH lane widths: uint16 (bf16 serving, the production
    wire) and uint32 (fp32 parity serving)."""
    import functools

    import jax
    import ml_dtypes

    from bloombee_tpu.runtime.step import (
        pack_step_payload,
        unpack_step_payload,
    )

    rng = np.random.default_rng(0)
    plan = rng.integers(-(2**31), 2**31 - 1, size=(57,), dtype=np.int32)

    for np_dt in (ml_dtypes.bfloat16, np.float32):
        h = rng.standard_normal((2, 3, 8)).astype(np_dt)
        payload = pack_step_payload(h, plan)
        hid, pl_ = jax.jit(
            functools.partial(unpack_step_payload, b=2, t=3, d=8)
        )(jnp.asarray(payload))
        assert hid.shape == h.shape
        assert np.asarray(hid).view(np.uint8).tobytes() == h.tobytes()
        np.testing.assert_array_equal(np.asarray(pl_), plan)


def test_span_decode_bf16_compute_runs_packed_path():
    """The bf16 (uint16-lane) packed path through the real executor: prefill
    + decode produce finite bf16 outputs."""
    import ml_dtypes

    from bloombee_tpu.models.llama.block import init_block_params
    from bloombee_tpu.models.spec import ModelSpec

    spec = ModelSpec(
        family="llama", hidden_size=32, intermediate_size=64,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        num_hidden_layers=2, vocab_size=64,
    )
    import jax

    params = stack_params(
        [init_block_params(jax.random.PRNGKey(i), spec, dtype=jnp.bfloat16)
         for i in range(2)]
    )

    async def run():
        manager = CacheManager(
            num_layers=2, num_pages=16, page_size=4, n_kv_heads=2,
            head_dim=8, dtype=jnp.bfloat16,
        )
        ex = SpanExecutor(params, spec, manager,
                          compute_dtype=jnp.bfloat16)
        rng = np.random.default_rng(0)
        async with manager.allocate(2, 12) as handle:
            out = ex.prefill(
                handle, rng.standard_normal((2, 6, 32)).astype(np.float32)
            )
            assert out.dtype == ml_dtypes.bfloat16
            assert np.isfinite(out.astype(np.float32)).all()
            out = ex.decode(
                handle, rng.standard_normal((2, 1, 32)).astype(np.float32)
            )
            assert out.dtype == ml_dtypes.bfloat16
            assert np.isfinite(out.astype(np.float32)).all()

    asyncio.run(run())


def test_attn_sparsity_topk():
    """FlexGen Policy.attn_sparsity analog: attend_paged with attn_topk keeps
    only the top-k keys per query (plus the query's own position) and
    renormalizes; sparsity=1 is exactly dense, and a numpy reference pins
    the top-k rule."""
    import jax
    import jax.numpy as jnp

    from bloombee_tpu.models.spec import ModelSpec
    from bloombee_tpu.runtime.layer_body import attend_paged

    spec = ModelSpec(
        family="llama", hidden_size=32, intermediate_size=64,
        num_attention_heads=2, num_key_value_heads=2, head_dim=16,
        num_hidden_layers=1, vocab_size=32,
    )
    rng = np.random.default_rng(0)
    B, T, S, H, hd = 2, 1, 12, 2, 16
    q = jnp.asarray(rng.standard_normal((B, T, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    lens = jnp.asarray([10, 7], jnp.int32)
    q_pos = (lens - 1)[:, None]

    dense = np.asarray(
        attend_paged(spec, q, k, v, q_pos, lens, None, jnp.int32(0))
    )
    same = np.asarray(
        attend_paged(spec, q, k, v, q_pos, lens, None, jnp.int32(0),
                     attn_topk=S)
    )
    np.testing.assert_allclose(same, dense, atol=1e-6)

    topk = 3
    got = np.asarray(
        attend_paged(spec, q, k, v, q_pos, lens, None, jnp.int32(0),
                     attn_topk=topk)
    )
    # numpy reference: mask invalid/future, keep top-k logits + own position
    scale = hd ** -0.5
    qf, kf, vf = (np.asarray(x, np.float32) for x in (q, k, v))
    want = np.zeros_like(got)
    for b in range(B):
        L = int(lens[b])
        own = L - 1
        for h in range(H):
            lg = (qf[b, 0, h] * scale) @ kf[b, :, h].T
            lg[L:] = -np.inf
            kept = np.argsort(lg)[-topk:]
            keep = set(kept.tolist()) | {own}
            lg2 = np.full(S, -np.inf)
            for i in keep:
                lg2[i] = lg[i]
            w = np.exp(lg2 - np.max(lg2))
            w = w / w.sum()
            want[b, 0, h] = w @ vf[b, :, h]
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_attn_sparsity_executor_smoke():
    """attn_sparsity<1 serves finite outputs and differs from dense (it is
    approximate), while sparsity=1.0 is the exact default path."""
    import asyncio

    import jax
    import jax.numpy as jnp

    from bloombee_tpu.kv.cache_manager import CacheManager
    from bloombee_tpu.models.llama.block import init_block_params
    from bloombee_tpu.models.spec import ModelSpec
    from bloombee_tpu.runtime.executor import SpanExecutor
    from bloombee_tpu.utils.tree import stack_params

    spec = ModelSpec(
        family="llama", hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_hidden_layers=2, vocab_size=64,
    )
    params = stack_params(
        [init_block_params(jax.random.PRNGKey(i), spec, dtype=jnp.float32)
         for i in range(2)]
    )
    rng = np.random.default_rng(1)
    prefill = (rng.standard_normal((1, 30, 64)) * 0.1).astype(np.float32)
    step = (rng.standard_normal((1, 1, 64)) * 0.1).astype(np.float32)

    async def run(sparsity):
        manager = CacheManager(
            num_layers=2, num_pages=16, page_size=4, n_kv_heads=2,
            head_dim=16, dtype=jnp.float32,
        )
        ex = SpanExecutor(params, spec, manager, compute_dtype=jnp.float32,
                          attn_sparsity=sparsity)
        async with manager.allocate(1, 40) as handle:
            ex.prefill(handle, prefill)
            return np.asarray(ex.decode(handle, step))

    dense = asyncio.run(run(1.0))
    sparse = asyncio.run(run(0.25))
    assert np.isfinite(sparse).all()
    assert np.abs(sparse - dense).max() > 1e-6  # actually approximated


# --------------------------------------------------------------------------
# The arena's addressing scheme (runtime/step.py `_scan_layers`, kv/arena.py
# `layer_slots` / `layer_pages`): the arena rides the layer scan's carry
# whole, as one flat slab, and a layer reaches its rows by offset. These run
# on the CPU and pin the scheme and its semantics, not a speed.

_L, _PAGE, _NUM_PAGES, _HKV, _HD, _D = 3, 4, 32, 2, 16, 64
_S_TOT = _PAGE * _NUM_PAGES  # 128: no other dimension of these steps is
_MAX_PAGES = 4


def _arena_spec():
    from bloombee_tpu.models.spec import ModelSpec

    return ModelSpec(
        family="llama", hidden_size=_D, intermediate_size=96,
        num_attention_heads=4, num_key_value_heads=_HKV, head_dim=_HD,
        num_hidden_layers=_L, vocab_size=64,
    )


def _arena_params(n_layers=_L):
    import jax

    from bloombee_tpu.models.llama.block import init_block_params

    return stack_params([
        init_block_params(jax.random.PRNGKey(i), _arena_spec(),
                          dtype=jnp.float32)
        for i in range(n_layers)
    ])


def _random_arena(quant=None):
    """An arena with something in EVERY row, so a stray write shows."""
    import jax

    from bloombee_tpu.kv.arena import make_arena

    arena = make_arena(_L, _NUM_PAGES, _PAGE, _HKV, _HD, jnp.float32, quant)
    leaves, tree = jax.tree.flatten(arena)
    rng = np.random.default_rng(7)
    leaves = [
        jnp.asarray(rng.integers(0, 255, a.shape), a.dtype)
        if a.dtype == jnp.uint8
        else jnp.asarray(rng.standard_normal(a.shape), a.dtype)
        for a in leaves
    ]
    return jax.tree.unflatten(tree, leaves)


def _step_case(kind, layer_active=None, n_layers=_L):
    """(fn, args, real_slots, layers_written) of one tiny step of `kind`:
    `fn(*args)` -> (hidden, arena_k, arena_v); rows beyond the real ones
    are bucket padding pointed at slot == S_tot."""
    import functools

    from bloombee_tpu.runtime import step as S

    spec = _arena_spec()
    rng = np.random.default_rng(11)
    oob = _S_TOT
    arena = _random_arena("int4" if kind == "int4" else None)
    active = (
        np.ones(n_layers, np.int32) if layer_active is None
        else np.asarray(layer_active, np.int32)
    )
    written = [l for l in range(n_layers) if active[l]]
    if kind == "ragged":
        # one decode token of sequence 0 + a 4-token chunk of sequence 1
        # + 3 padding rows (q_seq == n_seqs)
        r, n_seqs = 8, 2
        slots = np.array([2 * _PAGE + 1, 20, 21, 22, 23, oob, oob, oob])
        plan = S.pack_ragged_plan(
            slots, np.array([[1, 2, 0, 0], [5, 0, 0, 0]]),
            np.array([5, 0, 1, 2, 3, 0, 0, 0]), np.array([6, 4]),
            np.array([0, 1, 1, 1, 1, 2, 2, 2]), active,
        )
        h = rng.standard_normal((1, r, _D)).astype(np.float32)
        fn = functools.partial(
            S.span_step_ragged_impl, spec=spec, r=r, n_seqs=n_seqs,
            page_size=_PAGE, max_pages=_MAX_PAGES,
        )
        args = (_arena_params(n_layers), arena["k"], arena["v"],
                jnp.asarray(S.pack_step_payload(h, plan)))
        return fn, args, slots[slots < oob], written
    if kind == "chunk":
        # one 8-row chunk bucket holding 5 real tokens of a fresh sequence
        b, t = 1, 8
        slots = np.array([12, 13, 14, 15, 16, oob, oob, oob])
        pages = np.array([[3, 4, 0, 0]])
        positions, lens = np.arange(8)[None], np.array([5])
    else:
        # two decode rows: sequence 0 is real (6 tokens in pages 1, 2: the
        # new token lands at page 2, offset 1), sequence 1 is padding
        b, t = 2, 1
        slots = np.array([2 * _PAGE + 1, oob])
        pages = np.array([[1, 2, 0, 0], [0, 0, 0, 0]])
        positions, lens = np.array([[5], [0]]), np.array([6, 0])
    h = rng.standard_normal((b, t, _D)).astype(np.float32)
    if kind == "layer_step":
        plan = S.pack_plan(slots, pages, positions, lens, np.ones(1))
        params_1 = {k: v[0] for k, v in _arena_params(1).items()}
        fn = functools.partial(
            S.layer_step_impl, spec=spec, page_size=_PAGE,
            max_pages=_MAX_PAGES,
        )
        args = (params_1, arena["k"], arena["v"], jnp.asarray(h),
                jnp.asarray(plan), jnp.int32(1))
        return fn, args, slots[slots < oob], [1]
    plan = S.pack_plan(slots, pages, positions, lens, active)
    fn = functools.partial(
        S.span_step_packed_impl, spec=spec, b=b, t=t, page_size=_PAGE,
        max_pages=_MAX_PAGES,
    )
    args = (_arena_params(n_layers), arena["k"], arena["v"],
            jnp.asarray(S.pack_step_payload(h, plan)))
    return fn, args, slots[slots < oob], written


_STEP_KINDS = ["decode", "chunk", "ragged", "layer_step", "int4"]


def _has_slot_axis(aval) -> bool:
    """An array that spans a whole layer's slots (a slab, the stacked or the
    flat arena, any int4 leaf of them)."""
    return any(d in (_S_TOT, _L * _S_TOT) for d in aval.shape)


def _subjaxprs(eqn):
    from jax.extend import core as jex_core

    for v in eqn.params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            if isinstance(x, jex_core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jex_core.Jaxpr):
                yield x


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn):
            yield from _walk_eqns(sub)


@pytest.mark.parametrize("kind", _STEP_KINDS)
def test_no_step_moves_a_slab(kind):
    """No scan of a step takes or emits a slab as xs / ys (the arena is in
    the CARRY), and no dynamic_slice / dynamic_update_slice yields an array
    with a whole slot axis: a layer's rows are reached by offset ids."""
    import jax

    fn, args, _, _ = _step_case(kind)
    closed = jax.make_jaxpr(fn)(*args)
    scans = 0
    for eqn in _walk_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name == "scan":
            scans += 1
            first_x = eqn.params["num_consts"] + eqn.params["num_carry"]
            xs = [v.aval for v in eqn.invars[first_x:]]
            ys = [v.aval for v in eqn.outvars[eqn.params["num_carry"]:]]
            moved = [a.shape for a in xs + ys if _has_slot_axis(a)]
            assert not moved, f"scan moves slabs as xs/ys: {moved}"
            carry = [v.aval for v in
                     eqn.invars[eqn.params["num_consts"]:first_x]]
            assert any(a.shape[0] == _L * _S_TOT for a in carry), (
                "the flat arena is not in the scan's carry"
            )
        elif name in ("dynamic_slice", "dynamic_update_slice"):
            out = [v.aval.shape for v in eqn.outvars
                   if _has_slot_axis(v.aval)]
            assert not out, f"{name} yields a slab or more: {out}"
    assert scans == (0 if kind == "layer_step" else 1)


def _rows_changed(before, after):
    """[L, S_tot] bool: which rows of an arena side differ, over all of its
    leaves (bitwise: NaN-safe, int4 leaf by leaf)."""
    import jax

    changed = np.zeros((_L, _S_TOT), bool)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        a, b = (np.asarray(x).reshape(_L, _S_TOT, -1) for x in (a, b))
        changed |= (a.view(np.uint8) != b.view(np.uint8)).any(-1)
    return changed


@pytest.mark.parametrize("kind", _STEP_KINDS)
def test_step_writes_its_rows_and_no_others(kind):
    """Padding rows (slot >= S_tot) are dropped at EVERY layer: offset
    naively, layer l's slot S_tot is layer l + 1's slot 0. After a step the
    arena differs from before in exactly (layer, real slot) and nowhere
    else, K and V alike."""
    import jax

    fn, args, real_slots, layers = _step_case(kind)
    _, new_k, new_v = jax.jit(fn)(*args)
    want = np.zeros((_L, _S_TOT), bool)
    want[np.ix_(layers, real_slots)] = True
    assert not want[:, 0].any()  # slot 0 is nobody's: the naive offset's victim
    for side, (old, new) in {"k": (args[1], new_k),
                             "v": (args[2], new_v)}.items():
        got = _rows_changed(old, new)
        np.testing.assert_array_equal(got, want, err_msg=side)


@pytest.mark.parametrize("kind", ["decode", "chunk", "ragged"])
def test_layer_active_suffix_skips_leading_layers(kind):
    """layer_active = [0, 1, 1] (a session entering mid-span): layer 0's part
    of the arena stays bit-identical, and hidden + the other layers' rows
    equal a step of the two active layers alone over their own arena."""
    import jax

    fn, args, real_slots, layers = _step_case(kind, layer_active=[0, 1, 1])
    h, new_k, new_v = jax.jit(fn)(*args)
    assert layers == [1, 2]
    assert not _rows_changed(args[1], new_k)[0].any()
    assert not _rows_changed(args[2], new_v)[0].any()

    fn2, args2, _, _ = _step_case(kind, n_layers=2)
    tail = lambda tree: jax.tree.map(lambda x: x[1:], tree)  # noqa: E731
    h2, k2, v2 = jax.jit(fn2)(
        tail(args[0]), args[1][1:], args[2][1:], *args2[3:]
    )
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h2))
    np.testing.assert_array_equal(np.asarray(new_k[1:]), np.asarray(k2))
    np.testing.assert_array_equal(np.asarray(new_v[1:]), np.asarray(v2))


def test_resident_prefix_leaves_offloaded_layers_alone():
    """Weight-offload mode: a params stack of the first 2 of the arena's 3
    layers scans that prefix over the FULL arena; layer 2's rows are
    untouched and the rest equals a step over a 2-layer arena."""
    import jax

    fn, args, real_slots, _ = _step_case("decode", n_layers=2)
    h, new_k, new_v = jax.jit(fn)(*args)
    changed = _rows_changed(args[1], new_k) | _rows_changed(args[2], new_v)
    assert not changed[2].any()
    assert changed[:2, real_slots].all()
    h2, k2, v2 = jax.jit(fn)(args[0], args[1][:2], args[2][:2], args[3])
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h2))
    np.testing.assert_array_equal(np.asarray(new_k[:2]), np.asarray(k2))
    np.testing.assert_array_equal(np.asarray(new_v[:2]), np.asarray(v2))


@pytest.mark.parametrize("quant", [None, "int4"])
def test_arena_write_all_is_one_offset_scatter(quant):
    """The sp-prefill landing step writes every layer's rows with ONE
    scatter into the flat arena (no scan over slabs), equal to a per-layer
    arena_write; its padding rows drop at every layer too."""
    import jax

    from bloombee_tpu.kv.arena import arena_write
    from bloombee_tpu.runtime.executor import _arena_write_all

    arena = _random_arena(quant)
    rng = np.random.default_rng(3)
    slots = jnp.asarray([9, 40, _S_TOT, 41, _S_TOT], jnp.int32)
    k_new, v_new = (
        jnp.asarray(rng.standard_normal((_L, 5, _HKV, _HD)), jnp.float32)
        for _ in range(2)
    )
    names = {
        e.primitive.name for e in _walk_eqns(
            jax.make_jaxpr(_arena_write_all.__wrapped__)(
                arena["k"], arena["v"], slots, k_new, v_new
            ).jaxpr
        )
    }
    assert "scan" not in names and "dynamic_update_slice" not in names
    layer = lambda tree, l: jax.tree.map(lambda x: x[l], tree)  # noqa: E731
    want = [
        arena_write(layer(arena["k"], l), layer(arena["v"], l), slots,
                    k_new[l], v_new[l])
        for l in range(_L)
    ]
    old_k, old_v = jax.tree.map(np.asarray, (arena["k"], arena["v"]))
    got_k, got_v = _arena_write_all(
        arena["k"], arena["v"], slots, k_new, v_new
    )
    for l in range(_L):
        for got, exp in zip(
            jax.tree.leaves((layer(got_k, l), layer(got_v, l))),
            jax.tree.leaves(want[l]),
        ):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    changed = _rows_changed(old_k, got_k) | _rows_changed(old_v, got_v)
    want_rows = np.zeros((_L, _S_TOT), bool)
    want_rows[:, [9, 40, 41]] = True
    np.testing.assert_array_equal(changed, want_rows)
