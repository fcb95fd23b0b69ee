"""Paged decode attention kernel vs dense reference (interpreter mode)."""

import numpy as np
import pytest

import jax.numpy as jnp

from bloombee_tpu.ops.pallas.paged_attention import paged_decode_attention


def dense_reference(q, k_slab, v_slab, page_table, lens, page_size, window=0):
    """Gather pages then masked softmax — the exact dense-path semantics
    (incl. attend_paged's sliding window: key visible iff pos > q_pos - w)."""
    b, h, hd = q.shape
    hkv = k_slab.shape[1]
    g = h // hkv
    outs = []
    for i in range(b):
        slots = [
            p * page_size + o
            for p in page_table[i]
            for o in range(page_size)
        ]
        k = k_slab[np.asarray(slots)]  # [S, Hkv, hd]
        v = v_slab[np.asarray(slots)]
        s = k.shape[0]
        mask = np.arange(s) < lens[i]
        if window > 0:
            mask &= np.arange(s) > (lens[i] - 1) - window
        row = []
        for head in range(h):
            kv = head // g
            logits = (q[i, head].astype(np.float32) @
                      k[:, kv].astype(np.float32).T) * hd**-0.5
            logits = np.where(mask, logits, -1e30)
            p_att = np.exp(logits - logits.max())
            p_att = p_att / p_att.sum()
            row.append(p_att @ v[:, kv].astype(np.float32))
        outs.append(np.stack(row))
    return np.stack(outs)


@pytest.mark.parametrize("hkv,h", [(2, 8), (4, 4), (1, 6)])
def test_paged_decode_matches_dense(hkv, h):
    rng = np.random.default_rng(0)
    b, hd, page_size, n_phys, n_pages = 3, 64, 16, 12, 4
    q = rng.standard_normal((b, h, hd)).astype(np.float32)
    k_slab = rng.standard_normal(
        (n_phys * page_size, hkv, hd)
    ).astype(np.float32)
    v_slab = rng.standard_normal(
        (n_phys * page_size, hkv, hd)
    ).astype(np.float32)
    # shuffled physical pages; per-seq lens not page-aligned
    page_table = np.array(
        [[7, 2, 9, 0], [1, 4, 0, 0], [11, 3, 5, 8]], np.int32
    )
    lens = np.array([55, 17, 64], np.int32)

    got = np.asarray(
        paged_decode_attention(
            jnp.asarray(q), jnp.asarray(k_slab), jnp.asarray(v_slab),
            jnp.asarray(page_table), jnp.asarray(lens),
            page_size=page_size, interpret=True,
        )
    )
    want = dense_reference(q, k_slab, v_slab, page_table, lens, page_size)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [5, 16, 40])
def test_paged_decode_sliding_window(window):
    """Sliding window masks to [len-w, len) and must match attend_paged's
    semantics; pages wholly below the window are skipped in-kernel."""
    rng = np.random.default_rng(3)
    b, h, hkv, hd, page_size = 2, 4, 2, 64, 16
    n_phys = 10
    q = rng.standard_normal((b, h, hd)).astype(np.float32)
    k_slab = rng.standard_normal(
        (n_phys * page_size, hkv, hd)
    ).astype(np.float32)
    v_slab = rng.standard_normal(
        (n_phys * page_size, hkv, hd)
    ).astype(np.float32)
    page_table = np.array([[7, 2, 9, 0], [1, 4, 3, 6]], np.int32)
    lens = np.array([55, 33], np.int32)

    got = np.asarray(
        paged_decode_attention(
            jnp.asarray(q), jnp.asarray(k_slab), jnp.asarray(v_slab),
            jnp.asarray(page_table), jnp.asarray(lens),
            page_size=page_size, interpret=True, window=window,
        )
    )
    want = dense_reference(
        q, k_slab, v_slab, page_table, lens, page_size, window=window
    )
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_paged_decode_bf16_and_padding_rows():
    """bf16 inputs and zero-length padding rows (executor pads B to a
    bucket): padding rows emit finite garbage that the caller drops."""
    rng = np.random.default_rng(1)
    b, h, hkv, hd, page_size = 4, 8, 2, 64, 16
    n_phys, n_pages = 8, 2
    q = rng.standard_normal((b, h, hd)).astype(np.float32)
    k_slab = rng.standard_normal(
        (n_phys * page_size, hkv, hd)
    ).astype(np.float32)
    v_slab = rng.standard_normal(
        (n_phys * page_size, hkv, hd)
    ).astype(np.float32)
    page_table = np.array(
        [[3, 1], [0, 2], [5, 0], [0, 0]], np.int32
    )
    lens = np.array([20, 9, 32, 0], np.int32)  # row 3 = padding

    got = np.asarray(
        paged_decode_attention(
            jnp.asarray(q, jnp.bfloat16),
            jnp.asarray(k_slab, jnp.bfloat16),
            jnp.asarray(v_slab, jnp.bfloat16),
            jnp.asarray(page_table), jnp.asarray(lens),
            page_size=page_size, interpret=True,
        ).astype(jnp.float32)
    )
    assert np.isfinite(got).all()
    want = dense_reference(
        q[:3].astype(np.float32), k_slab, v_slab, page_table[:3], lens[:3],
        page_size,
    )
    np.testing.assert_allclose(got[:3], want, rtol=2e-2, atol=2e-2)


def test_span_decode_paged_kernel_matches_dense():
    """The serving span step with the paged decode kernel on vs off
    (executor eligibility end-to-end): identical decode outputs."""
    import asyncio
    import os

    import jax
    import jax.numpy as jnp

    from bloombee_tpu.kv.cache_manager import CacheManager
    from bloombee_tpu.models.llama.block import init_block_params
    from bloombee_tpu.models.spec import ModelSpec
    from bloombee_tpu.runtime.executor import SpanExecutor
    from bloombee_tpu.utils.tree import stack_params

    spec = ModelSpec(
        family="llama", hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=2, head_dim=64,
        num_hidden_layers=2, vocab_size=64,
    )
    params = stack_params(
        [init_block_params(jax.random.PRNGKey(i), spec, dtype=jnp.float32)
         for i in range(2)]
    )
    prefill = np.asarray(
        jax.random.normal(jax.random.PRNGKey(7), (2, 21, 64), jnp.float32)
    ) * 0.1
    steps = [
        np.asarray(
            jax.random.normal(jax.random.PRNGKey(50 + i), (2, 1, 64))
        ) * 0.1
        for i in range(3)
    ]

    async def run_one(paged: bool):
        os.environ["BBTPU_PAGED_ATTENTION"] = "1" if paged else "0"
        os.environ["BBTPU_PAGED_INTERPRET"] = "1"
        # tiny test contexts sit below the production paged/dense
        # crossover threshold; force the kernel on
        os.environ["BBTPU_PAGED_MIN_CONTEXT"] = "0"
        try:
            manager = CacheManager(
                num_layers=2, num_pages=16, page_size=16,
                n_kv_heads=2, head_dim=64, dtype=jnp.float32,
            )
            ex = SpanExecutor(params, spec, manager,
                              compute_dtype=jnp.float32)
            async with manager.allocate(2, 64) as handle:
                outs = [ex.prefill(handle, prefill)]
                for s in steps:
                    outs.append(ex.decode(handle, s))
                return outs
        finally:
            del os.environ["BBTPU_PAGED_ATTENTION"]
            del os.environ["BBTPU_PAGED_INTERPRET"]
            del os.environ["BBTPU_PAGED_MIN_CONTEXT"]

    outs_paged = asyncio.run(run_one(True))
    outs_dense = asyncio.run(run_one(False))
    for got, want in zip(outs_paged, outs_dense):
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_span_decode_paged_kernel_sliding_windows():
    """Mistral/gemma-style alternating sliding-window layers run through
    the paged kernel (the per-layer window rides the scan) and match the
    dense path exactly. The executor counts the turns the two decode steps'
    calls walked (`kv_walk`): 2 layers x 2 rows x the 2 pages that hold 20
    and 21 tokens, where the 4-page bucket held 4, all of them live."""
    import asyncio
    import os

    import jax
    import jax.numpy as jnp

    from bloombee_tpu.kv.cache_manager import CacheManager
    from bloombee_tpu.models.llama.block import init_block_params
    from bloombee_tpu.models.spec import ModelSpec
    from bloombee_tpu.runtime.executor import SpanExecutor
    from bloombee_tpu.utils.tree import stack_params

    spec = ModelSpec(
        family="llama", hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=2, head_dim=64,
        num_hidden_layers=2, vocab_size=64,
        layer_types=("sliding", "full"), sliding_window=7,
    )
    params = stack_params(
        [init_block_params(jax.random.PRNGKey(i), spec, dtype=jnp.float32)
         for i in range(2)]
    )
    prefill = np.asarray(
        jax.random.normal(jax.random.PRNGKey(8), (2, 19, 64), jnp.float32)
    ) * 0.1
    steps = [
        np.asarray(
            jax.random.normal(jax.random.PRNGKey(80 + i), (2, 1, 64))
        ) * 0.1
        for i in range(2)
    ]

    async def run_one(paged: bool):
        os.environ["BBTPU_PAGED_ATTENTION"] = "1" if paged else "0"
        os.environ["BBTPU_PAGED_INTERPRET"] = "1"
        # tiny test contexts sit below the production paged/dense
        # crossover threshold; force the kernel on
        os.environ["BBTPU_PAGED_MIN_CONTEXT"] = "0"
        try:
            manager = CacheManager(
                num_layers=2, num_pages=16, page_size=16,
                n_kv_heads=2, head_dim=64, dtype=jnp.float32,
            )
            ex = SpanExecutor(params, spec, manager,
                              compute_dtype=jnp.float32)
            assert ex.windows == (7, 0)
            async with manager.allocate(2, 64) as handle:
                outs = [ex.prefill(handle, prefill)]
                for s in steps:
                    outs.append(ex.decode(handle, s))
                return outs, dict(ex.kv_walk)
        finally:
            del os.environ["BBTPU_PAGED_ATTENTION"]
            del os.environ["BBTPU_PAGED_INTERPRET"]
            del os.environ["BBTPU_PAGED_MIN_CONTEXT"]

    outs_paged, walk = asyncio.run(run_one(True))
    outs_dense, no_walk = asyncio.run(run_one(False))
    for got, want in zip(outs_paged, outs_dense):
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert walk == {"turns": 16, "live_turns": 16}
    assert no_walk == {"turns": 0, "live_turns": 0}


def test_paged_kernel_context_threshold():
    """The executor engages the paged kernel only at/above
    BBTPU_PAGED_MIN_CONTEXT (measured dense/paged crossover): long-context
    decode calls it, short-context decode stays dense."""
    import asyncio
    import os

    import jax
    import jax.numpy as jnp

    from bloombee_tpu.kv.cache_manager import CacheManager
    from bloombee_tpu.models.llama.block import init_block_params
    from bloombee_tpu.models.spec import ModelSpec
    from bloombee_tpu.ops.pallas import paged_attention as pk
    from bloombee_tpu.runtime.executor import SpanExecutor
    from bloombee_tpu.utils.tree import stack_params

    spec = ModelSpec(
        family="llama", hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=2, head_dim=64,
        num_hidden_layers=2, vocab_size=64,
    )
    params = stack_params(
        [init_block_params(jax.random.PRNGKey(i), spec, dtype=jnp.float32)
         for i in range(2)]
    )
    rng = np.random.default_rng(0)
    calls = []
    orig = pk.paged_decode_attention

    def spy(*a, **k):
        calls.append(True)
        return orig(*a, **k)

    async def run(ctx):
        manager = CacheManager(
            num_layers=2, num_pages=80, page_size=16,
            n_kv_heads=2, head_dim=64, dtype=jnp.float32,
        )
        ex = SpanExecutor(params, spec, manager, compute_dtype=jnp.float32,
                          max_chunk_tokens=512)
        async with manager.allocate(1, ctx + 4) as handle:
            h = (rng.standard_normal((1, ctx, 64)) * 0.1).astype(np.float32)
            ex.prefill(handle, h)
            step = (rng.standard_normal((1, 1, 64)) * 0.1).astype(np.float32)
            ex.decode(handle, step)

    os.environ["BBTPU_PAGED_INTERPRET"] = "1"  # CPU backend
    pk.paged_decode_attention = spy
    try:
        # default threshold is 512: a 600-token context buckets above it
        asyncio.run(run(600))
        assert calls, "kernel not engaged at long context"
        calls.clear()
        asyncio.run(run(24))  # buckets to 64 tokens, below 512
        assert not calls, "kernel engaged below the crossover threshold"
    finally:
        pk.paged_decode_attention = orig
        del os.environ["BBTPU_PAGED_INTERPRET"]


def test_int4_paged_kernel_matches_dequantized_reference():
    """paged_decode_attention_int4 dequantizes in-kernel: output must match
    attention computed over the host-dequantized slab (exactly the values
    the dense quantized path sees)."""
    import jax
    import jax.numpy as jnp

    from bloombee_tpu.kv.quant import dequantize, quantize
    from bloombee_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_int4,
    )

    rng = np.random.default_rng(0)
    B, H, HKV, hd = 2, 4, 2, 64
    page_size, n_pages, max_pages = 8, 16, 4
    q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.float32)
    k_dense = jnp.asarray(
        rng.standard_normal((n_pages * page_size, HKV, hd)), jnp.float32
    )
    v_dense = jnp.asarray(
        rng.standard_normal((n_pages * page_size, HKV, hd)), jnp.float32
    )
    kq, vq = quantize(k_dense), quantize(v_dense)
    pt = rng.integers(0, n_pages, (B, max_pages)).astype(np.int32)
    lens = np.asarray([25, 13], np.int32)

    got = np.asarray(
        paged_decode_attention_int4(
            q, kq, vq, jnp.asarray(pt), jnp.asarray(lens),
            page_size=page_size, scale=hd**-0.5, interpret=True,
            window=jnp.int32(0),
        )
    )

    kf = np.asarray(dequantize(kq, jnp.float32), np.float32)
    vf = np.asarray(dequantize(vq, jnp.float32), np.float32)
    qf = np.asarray(q)
    want = np.zeros_like(got)
    for b in range(B):
        toks = np.concatenate(
            [np.arange(p * page_size, (p + 1) * page_size) for p in pt[b]]
        )
        S = len(toks)
        for h in range(H):
            kvh = h // (H // HKV)
            lg = (qf[b, h] * hd**-0.5) @ kf[toks, kvh].T
            lg[np.arange(S) >= lens[b]] = -1e30
            w = np.exp(lg - lg.max())
            w /= w.sum()
            want[b, h] = w @ vf[toks, kvh]
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_int4_arena_uses_paged_kernel_and_matches_dense_path():
    """Executor end-to-end with an int4 KV arena: the paged kernel path
    (in-kernel dequant) matches the dense gather path (host-side dequant)
    on the same quantized values, and the kernel actually runs."""
    import asyncio
    import os

    import jax
    import jax.numpy as jnp

    from bloombee_tpu.kv.cache_manager import CacheManager
    from bloombee_tpu.models.llama.block import init_block_params
    from bloombee_tpu.models.spec import ModelSpec
    from bloombee_tpu.ops.pallas import paged_attention as pk
    from bloombee_tpu.runtime.executor import SpanExecutor
    from bloombee_tpu.utils.tree import stack_params

    spec = ModelSpec(
        family="llama", hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=2, head_dim=64,
        num_hidden_layers=2, vocab_size=64,
    )
    params = stack_params(
        [init_block_params(jax.random.PRNGKey(i), spec, dtype=jnp.float32)
         for i in range(2)]
    )
    rng = np.random.default_rng(1)
    prefill = (rng.standard_normal((2, 21, 64)) * 0.1).astype(np.float32)
    steps = [(rng.standard_normal((2, 1, 64)) * 0.1).astype(np.float32)
             for _ in range(3)]

    calls = []
    orig = pk.paged_decode_attention_int4

    def spy(*a, **k):
        calls.append(True)
        return orig(*a, **k)

    async def run(paged):
        os.environ["BBTPU_PAGED_ATTENTION"] = "1" if paged else "0"
        os.environ["BBTPU_PAGED_INTERPRET"] = "1"
        os.environ["BBTPU_PAGED_MIN_CONTEXT"] = "0"
        try:
            manager = CacheManager(
                num_layers=2, num_pages=16, page_size=16,
                n_kv_heads=2, head_dim=64, dtype=jnp.float32, quant="int4",
            )
            ex = SpanExecutor(params, spec, manager,
                              compute_dtype=jnp.float32)
            async with manager.allocate(2, 64) as handle:
                outs = [ex.prefill(handle, prefill)]
                for s in steps:
                    outs.append(ex.decode(handle, s))
                return outs
        finally:
            for k in ("BBTPU_PAGED_ATTENTION", "BBTPU_PAGED_INTERPRET",
                      "BBTPU_PAGED_MIN_CONTEXT"):
                del os.environ[k]

    pk.paged_decode_attention_int4 = spy
    try:
        outs_paged = asyncio.run(run(True))
    finally:
        pk.paged_decode_attention_int4 = orig
    outs_dense = asyncio.run(run(False))
    assert calls, "int4 paged kernel never ran"
    for got, want in zip(outs_paged, outs_dense):
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("win", [11, 3, 64])
def test_int4_paged_kernel_sliding_window(win):
    """int4 kernel honors the sliding window (shared softmax body and the
    bounded walk: a window inside the last page, one over two pages, one
    longer than the context): match the host-dequantized windowed
    reference."""
    import jax.numpy as jnp

    from bloombee_tpu.kv.quant import dequantize, quantize
    from bloombee_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_int4,
    )

    rng = np.random.default_rng(3)
    B, H, HKV, hd = 2, 4, 2, 64
    page_size, n_pages, max_pages = 8, 8, 4
    q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.float32)
    k_dense = jnp.asarray(
        rng.standard_normal((n_pages * page_size, HKV, hd)), jnp.float32
    )
    v_dense = jnp.asarray(
        rng.standard_normal((n_pages * page_size, HKV, hd)), jnp.float32
    )
    kq, vq = quantize(k_dense), quantize(v_dense)
    pt = rng.integers(0, n_pages, (B, max_pages)).astype(np.int32)
    lens = np.asarray([30, 17], np.int32)

    got = np.asarray(
        paged_decode_attention_int4(
            q, kq, vq, jnp.asarray(pt), jnp.asarray(lens),
            page_size=page_size, scale=hd**-0.5, interpret=True,
            window=jnp.int32(win),
        )
    )
    kf = np.asarray(dequantize(kq, jnp.float32), np.float32)
    vf = np.asarray(dequantize(vq, jnp.float32), np.float32)
    qf = np.asarray(q)
    want = np.zeros_like(got)
    for b in range(B):
        toks = np.concatenate(
            [np.arange(p * page_size, (p + 1) * page_size) for p in pt[b]]
        )
        S = len(toks)
        qpos = lens[b] - 1
        for h in range(H):
            kvh = h // (H // HKV)
            lg = (qf[b, h] * hd**-0.5) @ kf[toks, kvh].T
            pos = np.arange(S)
            lg[(pos >= lens[b]) | (pos <= qpos - win)] = -1e30
            w = np.exp(lg - lg.max())
            w /= w.sum()
            want[b, h] = w @ vf[toks, kvh]
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


from bloombee_tpu.ops.pallas.paged_attention import paged_chunk_attention


def dense_chunk_reference(
    q, k_slab, v_slab, page_table, lens, page_size, tree=None, window=0
):
    """[B, T, H, hd] reference with attend_paged's exact semantics: query
    token t sits at position lens-T+t; causal (or tree) masking over the
    paged context."""
    b, t_q, h, hd = q.shape
    hkv = k_slab.shape[1]
    g = h // hkv
    out = np.zeros((b, t_q, h, hd), np.float32)
    for i in range(b):
        slots = [
            p * page_size + o
            for p in page_table[i]
            for o in range(page_size)
        ]
        k = k_slab[np.asarray(slots)]
        v = v_slab[np.asarray(slots)]
        s = k.shape[0]
        pos = np.arange(s)
        start = lens[i] - t_q
        for t in range(t_q):
            q_pos = start + t
            if tree is None:
                mask = (pos < lens[i]) & (pos <= q_pos)
                if window > 0:
                    mask &= pos > q_pos - window
            else:
                in_step = (pos >= start) & (pos < lens[i])
                rel = np.clip(pos - start, 0, t_q - 1)
                mask = np.where(
                    in_step,
                    tree[i, t, rel] & (pos < lens[i]),
                    (pos < lens[i]) & (pos <= q_pos),
                )
            for head in range(h):
                kv = head // g
                logits = (
                    q[i, t, head].astype(np.float32)
                    @ k[:, kv].astype(np.float32).T
                ) * hd**-0.5
                logits = np.where(mask, logits, -1e30)
                p_att = np.exp(logits - logits.max())
                p_att /= p_att.sum()
                out[i, t, head] = p_att @ v[:, kv].astype(np.float32)
    return out


def _chunk_setup(rng, b, t_q, h, hkv, hd=64, page_size=16, n_phys=12):
    q = rng.standard_normal((b, t_q, h, hd)).astype(np.float32)
    k_slab = rng.standard_normal(
        (n_phys * page_size, hkv, hd)
    ).astype(np.float32)
    v_slab = rng.standard_normal(
        (n_phys * page_size, hkv, hd)
    ).astype(np.float32)
    page_table = np.array([[7, 2, 9, 0], [1, 4, 5, 8]], np.int32)[:b]
    lens = np.array([55, 38], np.int32)[:b]
    return q, k_slab, v_slab, page_table, lens


@pytest.mark.parametrize("hkv,h,t_q", [(2, 8, 4), (4, 4, 7), (1, 6, 3)])
def test_paged_chunk_causal_matches_dense(hkv, h, t_q):
    rng = np.random.default_rng(5)
    q, k_slab, v_slab, pt, lens = _chunk_setup(rng, 2, t_q, h, hkv)
    got = np.asarray(
        paged_chunk_attention(
            jnp.asarray(q), jnp.asarray(k_slab), jnp.asarray(v_slab),
            jnp.asarray(pt), jnp.asarray(lens), page_size=16,
            interpret=True,
        )
    )
    want = dense_chunk_reference(q, k_slab, v_slab, pt, lens, 16)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [5, 20])
def test_paged_chunk_sliding_window(window):
    rng = np.random.default_rng(6)
    q, k_slab, v_slab, pt, lens = _chunk_setup(rng, 2, 4, 8, 2)
    got = np.asarray(
        paged_chunk_attention(
            jnp.asarray(q), jnp.asarray(k_slab), jnp.asarray(v_slab),
            jnp.asarray(pt), jnp.asarray(lens), page_size=16,
            interpret=True, window=window,
        )
    )
    want = dense_chunk_reference(
        q, k_slab, v_slab, pt, lens, 16, window=window
    )
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_paged_chunk_tree_matches_dense():
    """Tree-verify step: the [T, T] mask governs in-step visibility while
    the committed prefix stays fully visible (the speculative hot path the
    dense gather served before)."""
    rng = np.random.default_rng(7)
    t_q = 6
    q, k_slab, v_slab, pt, lens = _chunk_setup(rng, 2, t_q, 8, 2)
    # random lower-triangular-ish tree: node sees itself + its ancestors
    parents = np.array([-1, 0, 0, 1, 2, 3], np.int32)
    tm = np.zeros((t_q, t_q), bool)
    for n in range(t_q):
        node = n
        while node >= 0:
            tm[n, node] = True
            node = parents[node]
    tree = np.broadcast_to(tm, (2, t_q, t_q)).copy()
    got = np.asarray(
        paged_chunk_attention(
            jnp.asarray(q), jnp.asarray(k_slab), jnp.asarray(v_slab),
            jnp.asarray(pt), jnp.asarray(lens), page_size=16,
            tree_mask=jnp.asarray(tree), interpret=True, has_tree=True,
        )
    )
    want = dense_chunk_reference(
        q, k_slab, v_slab, pt, lens, 16, tree=tree
    )
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_executor_tree_step_paged_matches_dense(monkeypatch):
    """Through the real executor: a tree decode step at paged-eligible
    context must produce the same output with the chunk kernel as with the
    dense gather path (lifts the old tb==1 gate)."""
    import asyncio

    from bloombee_tpu.kv.cache_manager import CacheManager
    from bloombee_tpu.models.llama.block import init_block_params
    from bloombee_tpu.models.spec import ModelSpec
    from bloombee_tpu.runtime.executor import SpanExecutor
    from bloombee_tpu.utils.tree import stack_params
    import jax.random as jr

    spec = ModelSpec(
        family="llama", hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_hidden_layers=2, vocab_size=64,
    )
    params = stack_params(
        [init_block_params(jr.PRNGKey(i), spec) for i in range(2)]
    )

    def run(paged: bool):
        monkeypatch.setenv("BBTPU_PAGED_INTERPRET", "1" if paged else "")
        monkeypatch.setenv("BBTPU_PAGED_MIN_CONTEXT", "16")
        monkeypatch.setenv("BBTPU_PAGED_ATTENTION", "1" if paged else "")

        async def go():
            manager = CacheManager(
                num_layers=2, num_pages=32, page_size=4,
                n_kv_heads=2, head_dim=16, dtype=jnp.float32,
            )
            ex = SpanExecutor(
                params, spec, manager, compute_dtype=jnp.float32
            )
            rng = np.random.default_rng(1)
            async with manager.allocate(2, 64) as handle:
                pre = rng.standard_normal((2, 30, 64)).astype(np.float32)
                ex.prefill(handle, pre)
                t_q = 5
                step = rng.standard_normal((2, t_q, 64)).astype(np.float32)
                parents = np.array([-1, 0, 0, 1, 2], np.int32)
                tm = np.zeros((t_q, t_q), bool)
                for n in range(t_q):
                    node = n
                    while node >= 0:
                        tm[n, node] = True
                        node = parents[node]
                depths = np.array(
                    [[0, 1, 1, 2, 2]] * 2, np.int32
                )
                tree = np.broadcast_to(tm, (2, t_q, t_q)).copy()
                return ex.decode(
                    handle, step, commit=False, tree_mask=tree,
                    depths=depths,
                )

        return asyncio.run(go())

    dense = run(False)
    paged = run(True)
    np.testing.assert_allclose(
        np.asarray(paged, np.float32), np.asarray(dense, np.float32),
        rtol=2e-4, atol=2e-4,
    )


# ------------------------------------------------ ragged mixed-batch kernel
def dense_ragged_reference(
    q, k_slab, v_slab, page_table, lens, q_seq, q_pos, page_size, window=0
):
    """Row-by-row gather + masked softmax with the ragged kernel's exact
    semantics: row i belongs to sequence q_seq[i] (>= B = padding, emits
    zeros) and sees keys at positions <= q_pos[i] (within the window)."""
    r, h, hd = q.shape
    hkv = k_slab.shape[1]
    g = h // hkv
    b = page_table.shape[0]
    out = np.zeros((r, h, hd), np.float32)
    for i in range(r):
        sq = int(q_seq[i])
        if sq >= b:
            continue
        slots = [
            p * page_size + o
            for p in page_table[sq]
            for o in range(page_size)
        ]
        k = k_slab[np.asarray(slots)]
        v = v_slab[np.asarray(slots)]
        n = k.shape[0]
        pos = int(q_pos[i])
        mask = np.arange(n) <= pos
        if window > 0:
            mask &= np.arange(n) > pos - window
        for head in range(h):
            kv = head // g
            logits = (q[i, head].astype(np.float32) @
                      k[:, kv].astype(np.float32).T) * hd**-0.5
            logits = np.where(mask, logits, -1e30)
            p_att = np.exp(logits - logits.max())
            p_att = p_att / p_att.sum()
            out[i, head] = p_att @ v[:, kv].astype(np.float32)
    return out


@pytest.mark.parametrize("seed,window", [(0, 0), (1, 0), (2, 9), (3, 0)])
def test_paged_ragged_matches_dense_and_sibling_kernels(seed, window):
    """The parity gate for the mixed-batch kernel on RANDOMIZED ragged
    shapes (N decode rows + one multi-token chunk group + bucket-padding
    rows): paged_ragged_attention must match (a) the dense reference,
    (b) paged_decode_attention on the decode rows, and (c)
    paged_chunk_attention on the chunk member — the three paths a mixed
    group's members would otherwise take. Padding rows emit exact zeros."""
    from bloombee_tpu.ops.pallas.paged_attention import (
        paged_chunk_attention,
        paged_ragged_attention,
    )

    rng = np.random.default_rng(seed)
    page_size = int(rng.choice([8, 16]))
    hkv = int(rng.choice([1, 2]))
    h = hkv * int(rng.choice([2, 4]))
    hd = 64
    b = int(rng.integers(2, 5))
    max_pages = 4
    lens = rng.integers(
        6, page_size * max_pages + 1, size=b
    ).astype(np.int32)
    # disjoint shuffled physical pages per sequence; table padding = 0
    n_phys = b * max_pages + 2
    pool = rng.permutation(n_phys)
    page_table = np.zeros((b, max_pages), np.int32)
    off = 0
    for i in range(b):
        need = -(-int(lens[i]) // page_size)
        page_table[i, :need] = pool[off:off + need]
        off += need
    k_slab = rng.standard_normal(
        (n_phys * page_size, hkv, hd)
    ).astype(np.float32)
    v_slab = rng.standard_normal(
        (n_phys * page_size, hkv, hd)
    ).astype(np.float32)

    # ragged rows: every sequence but one contributes a single decode row
    # (pos = len-1); sequence `c` contributes a t-token chunk; then padding
    c = int(rng.integers(0, b))
    t = int(rng.integers(2, min(6, int(lens[c])) + 1))
    q_seq, q_pos = [], []
    for i in range(b):
        if i == c:
            q_seq.extend([c] * t)
            q_pos.extend(range(int(lens[c]) - t, int(lens[c])))
        else:
            q_seq.append(i)
            q_pos.append(int(lens[i]) - 1)
    n_pad = int(rng.integers(0, 3))
    q_seq.extend([b] * n_pad)
    q_pos.extend([0] * n_pad)
    q_seq = np.asarray(q_seq, np.int32)
    q_pos = np.asarray(q_pos, np.int32)
    r = len(q_seq)
    q = rng.standard_normal((r, h, hd)).astype(np.float32)

    got = np.asarray(
        paged_ragged_attention(
            jnp.asarray(q), jnp.asarray(k_slab), jnp.asarray(v_slab),
            jnp.asarray(page_table), jnp.asarray(lens),
            jnp.asarray(q_seq), jnp.asarray(q_pos),
            page_size=page_size, interpret=True, window=window,
        )
    )
    want = dense_ragged_reference(
        q, k_slab, v_slab, page_table, lens, q_seq, q_pos, page_size,
        window=window,
    )
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    if n_pad:
        np.testing.assert_array_equal(got[r - n_pad:], 0.0)

    # (b) the decode rows match the single-token decode kernel
    dec_rows = [i for i in range(r - n_pad) if int(q_seq[i]) != c]
    dec_seqs = [int(q_seq[i]) for i in dec_rows]
    if dec_rows:
        dec_got = np.asarray(
            paged_decode_attention(
                jnp.asarray(q[dec_rows]), jnp.asarray(k_slab),
                jnp.asarray(v_slab), jnp.asarray(page_table[dec_seqs]),
                jnp.asarray(lens[dec_seqs]), page_size=page_size,
                interpret=True, window=window,
            )
        )
        np.testing.assert_allclose(
            got[dec_rows], dec_got, rtol=2e-5, atol=2e-5
        )

    # (c) the chunk member matches the multi-token chunk kernel
    chunk_rows = [i for i in range(r - n_pad) if int(q_seq[i]) == c]
    chunk_got = np.asarray(
        paged_chunk_attention(
            jnp.asarray(q[chunk_rows])[None], jnp.asarray(k_slab),
            jnp.asarray(v_slab), jnp.asarray(page_table[c:c + 1]),
            jnp.asarray(lens[c:c + 1]), page_size=page_size,
            interpret=True, window=window,
        )
    )
    np.testing.assert_allclose(
        got[chunk_rows], chunk_got[0], rtol=2e-5, atol=2e-5
    )


def dense_tree_ragged_reference(
    q, k_slab, v_slab, page_table, lens, nt, q_seq, q_pos, tree_rows,
    page_size,
):
    """Per-row numpy reference for the ragged TREE-verify mask: committed
    keys (pos < len - nt) are fully visible; in-step slot m of the row's
    own sequence is visible iff tree_rows[i, m]."""
    r, h, hd = q.shape
    hkv = k_slab.shape[1]
    g = h // hkv
    b = page_table.shape[0]
    out = np.zeros((r, h, hd), np.float32)
    for i in range(r):
        sq = int(q_seq[i])
        if sq >= b:
            continue
        slots = [
            p * page_size + o
            for p in page_table[sq]
            for o in range(page_size)
        ]
        k = k_slab[np.asarray(slots)]
        v = v_slab[np.asarray(slots)]
        n = k.shape[0]
        ss = int(lens[sq]) - int(nt[sq])
        pos = np.arange(n)
        mask = pos < ss
        for m in range(int(nt[sq])):
            if tree_rows[i, m]:
                mask |= pos == ss + m
        mask &= pos < int(lens[sq])
        for head in range(h):
            kv = head // g
            logits = (q[i, head].astype(np.float32) @
                      k[:, kv].astype(np.float32).T) * hd**-0.5
            logits = np.where(mask, logits, -1e30)
            p_att = np.exp(logits - logits.max())
            p_att = p_att / p_att.sum()
            out[i, head] = p_att @ v[:, kv].astype(np.float32)
    return out


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_paged_ragged_tree_matches_dense_reference(seed):
    """Parity gate for the ragged TREE-verify kernel variant: N sessions'
    linearized trees (random ancestor-or-self structures, differing sizes,
    zero-padded tree rows) over shuffled disjoint pages must match the
    per-row dense reference, and padding rows emit exact zeros."""
    from bloombee_tpu.ops.pallas.paged_attention import (
        paged_ragged_attention,
    )
    from bloombee_tpu.spec.tree import DraftTree, tree_attention_mask

    rng = np.random.default_rng(seed)
    page_size = int(rng.choice([8, 16]))
    hkv = int(rng.choice([1, 2]))
    h = hkv * int(rng.choice([2, 4]))
    hd = 64
    b = int(rng.integers(2, 5))
    max_pages = 4
    # committed context per sequence, then a tree of t_b in-step tokens
    committed = rng.integers(5, 20, size=b).astype(np.int32)
    t_max = 8
    nts = rng.integers(2, t_max + 1, size=b).astype(np.int32)
    lens = (committed + nts).astype(np.int32)
    assert int(lens.max()) <= page_size * max_pages

    n_phys = b * max_pages + 2
    pool = rng.permutation(n_phys)
    page_table = np.zeros((b, max_pages), np.int32)
    off = 0
    for i in range(b):
        need = -(-int(lens[i]) // page_size)
        page_table[i, :need] = pool[off:off + need]
        off += need
    k_slab = rng.standard_normal(
        (n_phys * page_size, hkv, hd)
    ).astype(np.float32)
    v_slab = rng.standard_normal(
        (n_phys * page_size, hkv, hd)
    ).astype(np.float32)

    q_seq, q_pos = [], []
    tree_rows = []
    for i in range(b):
        t = int(nts[i])
        # random ancestor-or-self tree: node j's parent uniform in [-1, j)
        parents = np.asarray(
            [-1] + [int(rng.integers(-1, j)) for j in range(1, t)],
            np.int64,
        )
        tree = DraftTree(
            tokens=np.zeros(t, np.int64), parents=parents
        )
        tm = tree_attention_mask(tree)
        depths = tree.depths()
        q_seq.extend([i] * t)
        q_pos.extend((int(committed[i]) + depths).tolist())
        for row in range(t):
            tr = np.zeros(t_max, np.int32)
            tr[:t] = tm[row]
            tree_rows.append(tr)
    n_pad = int(rng.integers(0, 3))
    for _ in range(n_pad):
        q_seq.append(b)
        q_pos.append(0)
        tree_rows.append(np.zeros(t_max, np.int32))
    q_seq = np.asarray(q_seq, np.int32)
    q_pos = np.asarray(q_pos, np.int32)
    tree_rows = np.stack(tree_rows)
    r = len(q_seq)
    q = rng.standard_normal((r, h, hd)).astype(np.float32)

    got = np.asarray(
        paged_ragged_attention(
            jnp.asarray(q), jnp.asarray(k_slab), jnp.asarray(v_slab),
            jnp.asarray(page_table), jnp.asarray(lens),
            jnp.asarray(q_seq), jnp.asarray(q_pos),
            page_size=page_size, interpret=True, window=0,
            nt=jnp.asarray(nts), tree_rows=jnp.asarray(tree_rows),
            has_tree=True,
        )
    )
    want = dense_tree_ragged_reference(
        q, k_slab, v_slab, page_table, lens, nts, q_seq, q_pos, tree_rows,
        page_size,
    )
    np.testing.assert_allclose(
        got[: r - n_pad], want[: r - n_pad], rtol=2e-5, atol=2e-5
    )
    if n_pad:
        np.testing.assert_array_equal(got[r - n_pad:], 0.0)


# ----------------------------------------- several pages a grid step
def _walk_case(rng, hkv, n_pages, lens, page_size=16, hd=64, n_phys=40):
    """Queries, slabs and a page table of shuffled physical pages whose
    padding entries are page 0, for rows of the given lengths."""
    b, h = len(lens), 2 * hkv
    q = rng.standard_normal((b, h, hd)).astype(np.float32)
    k_slab = rng.standard_normal(
        (n_phys * page_size, hkv, hd)).astype(np.float32)
    v_slab = rng.standard_normal(
        (n_phys * page_size, hkv, hd)).astype(np.float32)
    page_table = np.zeros((b, n_pages), np.int32)
    for i, n in enumerate(lens):
        live = -(-int(n) // page_size)
        page_table[i, :live] = rng.permutation(n_phys)[:live]
    return q, k_slab, v_slab, page_table, np.asarray(lens, np.int32)


@pytest.mark.parametrize("n_pages, rows, want", [
    (256, 128, 8),    # Mistral-7B in the cell: 16-token pages x 8 KV heads
    (64, 128, 8),
    (4, 128, 4),      # the smallest bucket
    (1280, 128, 8),   # the arena's cap, no power of two
    (100, 128, 4),    # a cap with few factors of two
    (37, 128, 1),
    (256, 256, 4),
    (256, 2048, 1),   # 128-token pages x 16 KV heads: one page fills a step
    (256, 64, 1),     # Qwen3-30B-A3B, Falcon-H1-34B: 4 KV heads, held at
    (16, 8, 1),       # one page a step for the yardstick's sake (docstring)
    (1024, 32, 1),    # Qwen3-Next: 2 KV heads, likewise
    (16, 192, 4),     # 12 KV heads: over 128 rows and no multiple of it
    (1024, 160, 4),   # Phi-4-mini-flash in the cell: 10 K/V pairs, 640 rows
    (768, 160, 4),
    (1346, 160, 2),   # a capped bucket that 4 does not divide
    (16, 136, 4),
    (16, 132, 1),     # rows off the float32 sublane tile
])
def test_pages_a_grid_step(n_pages, rows, want):
    """A step streams as many pages as keep its K block at 1024 rows or
    fewer, a power of two that divides the page bucket."""
    from bloombee_tpu.ops.pallas.paged_attention import _pages_per_step

    got = _pages_per_step(n_pages, rows)
    assert got == want and n_pages % got == 0 and got * rows <= max(rows, 1024)


@pytest.mark.parametrize("hkv", [8, 10])
@pytest.mark.parametrize("n_pages, window", [
    (32, 0), (32, 100), (20, 0), (20, 37), (12, 300),
])
def test_paged_decode_several_steps_of_several_pages(n_pages, window, hkv):
    """Buckets of several grid steps, each of several shuffled physical
    pages (128-row pages of 8 K/V heads: 8 a step at 32 pages, 4 at 20 and
    12; 160-row pages of 10: 4 a step): lengths that end inside a step,
    inside a page and before the last step, and a window that skips whole
    steps, against the dense reference."""
    from bloombee_tpu.ops.pallas.paged_attention import _pages_per_step

    page_size = 16
    assert _pages_per_step(n_pages, page_size * hkv) == (
        4 if hkv == 10 or n_pages < 32 else 8)
    q, k_slab, v_slab, page_table, lens = _walk_case(
        np.random.default_rng(11), hkv, n_pages,
        [n_pages * page_size, 16 * 5 + 3, 1])
    got = np.asarray(
        paged_decode_attention(
            jnp.asarray(q), jnp.asarray(k_slab), jnp.asarray(v_slab),
            jnp.asarray(page_table), jnp.asarray(lens),
            page_size=page_size, interpret=True, window=window,
        )
    )
    want = dense_reference(
        q, k_slab, v_slab, page_table, lens, page_size, window=window)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ------------------------------------------------- the bounded page walk
@pytest.mark.parametrize("hkv, pages", [(8, 8), (10, 4), (4, 1)])
@pytest.mark.parametrize("window", [
    0,     # full attention: every row walks from its first group
    200,   # starts inside a group and inside a page of the longest row
    129,   # one key more than a group of 8 pages holds
    1000,  # longer than every context
])
def test_paged_decode_walk_is_bounded_by_window_and_context(
        hkv, pages, window):
    """Rows of unlike spans in one call (the bucket's full length, a length
    that ends inside a group and inside a page, length 1, a padding row of
    length 0) at 128-, 160- and 64-row pages (8, 4 and 1 a grid step): the
    grid walks the longest row's span from each row's own first live group,
    a shorter row's trailing turns hold no live page, and the row of length
    0 emits zeros. Against the dense reference."""
    from bloombee_tpu.ops.pallas.paged_attention import (
        _pages_per_step,
        walk_bounds,
    )

    n_pages, page_size = 32, 16
    assert _pages_per_step(n_pages, page_size * hkv) == pages
    lens = [n_pages * page_size, 16 * 13 + 5, 1, 0]
    q, k_slab, v_slab, page_table, lens = _walk_case(
        np.random.default_rng(56), hkv, n_pages, lens)
    lo, extent, live = walk_bounds(lens, window, page_size, pages, np)
    groups = n_pages // pages
    assert 1 <= extent <= groups and live <= len(lens) * extent
    if 0 < window < 512 and pages > 1:
        assert lo[0] > 0 and extent < groups  # turns below the window went
    got = np.asarray(
        paged_decode_attention(
            jnp.asarray(q), jnp.asarray(k_slab), jnp.asarray(v_slab),
            jnp.asarray(page_table), jnp.asarray(lens),
            page_size=page_size, interpret=True, window=window,
        )
    )
    want = dense_reference(
        q[:3], k_slab, v_slab, page_table[:3], lens[:3], page_size,
        window=window)
    np.testing.assert_allclose(got[:3], want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(got[3], 0.0)


def test_one_program_serves_a_traced_window_of_0_and_of_512():
    """The window is a traced operand (a layer scan hands each layer its
    own) and so is the grid's extent: ONE jitted function, traced once,
    serves full attention and a 512-token window, each equal to the dense
    reference."""
    import jax

    page_size, n_pages, hkv = 16, 64, 8
    q, k_slab, v_slab, page_table, lens = _walk_case(
        np.random.default_rng(57), hkv, n_pages, [1000, 700, 30], n_phys=64)
    traces = []

    def attend(q, k_slab, v_slab, page_table, lens, window):
        traces.append(window)
        return paged_decode_attention(
            q, k_slab, v_slab, page_table, lens, page_size=page_size,
            interpret=True, window=window)

    attend = jax.jit(attend)
    for window in (0, 512):
        got = np.asarray(attend(
            jnp.asarray(q), jnp.asarray(k_slab), jnp.asarray(v_slab),
            jnp.asarray(page_table), jnp.asarray(lens), jnp.int32(window)))
        want = dense_reference(
            q, k_slab, v_slab, page_table, lens, page_size, window=window)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert len(traces) == 1


@pytest.mark.parametrize("rows, context, before, after", [
    # phi4flash-longctx at its mean context: 8 calls under the 512-token
    # window and 8 full or cross calls over the 1024-page bucket, 4 pages a
    # turn: (turns, live turns) a decode step
    (1, 10100, (4096, 1336), (1336, 1336)),
    (1, 8200, (4096, 1104), (1104, 1104)),
    (4, 12000, (16384, 6304), (6304, 6304)),
])
def test_the_turns_a_decode_step_walks(rows, context, before, after):
    """`walk_bounds` is the one formula of the wrapper's grid and of the
    executor's `kv_walk` counter: the bucket's walk held a live page in a
    third of its turns at the cell's shape, the bounded walk in all of
    them up to the groups' edges."""
    from bloombee_tpu.ops.pallas.paged_attention import walk_bounds

    lens = np.full((rows,), context, np.int32)
    turns = live = 0
    for window in (512, 0):
        lo, extent, n = walk_bounds(lens, window, 16, 4, np)
        jlo, jextent, jn = walk_bounds(
            jnp.asarray(lens), jnp.int32(window), 16, 4)
        assert (np.asarray(jlo) == lo).all()
        assert (int(jextent), int(jn)) == (int(extent), int(n))
        turns += 8 * rows * int(extent)
        live += 8 * int(n)
    assert (16 * rows * (1024 // 4), live) == before
    assert (turns, live) == after


@pytest.mark.parametrize("lens, window, pages, want", [
    ([0, 0], 0, 4, ([0, 0], 1, 0)),         # padding rows alone: one turn
    ([0, 0], 512, 4, ([0, 0], 1, 0)),
    ([1], 0, 4, ([0], 1, 1)),
    ([64], 0, 4, ([0], 1, 1)),              # ends on a group's edge
    ([65], 0, 4, ([0], 2, 2)),
    ([640, 65, 0], 100, 4, ([8, 0, 0], 2, 4)),  # 540 // 64; row 1 whole
    ([640, 65, 0], 100, 1, ([33, 0, 0], 7, 12)),
    ([300], 1000, 8, ([0], 3, 3)),          # a window longer than the context
])
def test_walk_bounds_by_rows(lens, window, pages, want):
    from bloombee_tpu.ops.pallas.paged_attention import walk_bounds

    lo, extent, live = walk_bounds(
        np.asarray(lens, np.int32), window, 16, pages, np)
    assert (list(lo), int(extent), int(live)) == want
