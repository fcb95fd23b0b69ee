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
