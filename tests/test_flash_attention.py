"""Pallas flash attention vs dense reference (interpreter mode on CPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bloombee_tpu.ops.attention import causal_mask, masked_attention
from bloombee_tpu.ops.pallas.flash_attention import flash_attention


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hkv", [4, 2, 1])
def test_flash_matches_dense(causal, hkv):
    b, t, h, hd = 2, 256, 4, 64
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (b, t, h, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, t, hkv, hd), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, t, hkv, hd), jnp.float32)

    if causal:
        mask = causal_mask(t)[None]
    else:
        mask = jnp.ones((1, t, t), bool)
    ref = masked_attention(q, k, v, mask)

    out = flash_attention(
        q, k, v, causal=causal, block_q=64, block_k=64, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_flash_prefix_offset_matches_dense():
    """S > T: queries attend to a committed prefix plus themselves, with
    absolute positions offset by s - t (chunked-prefill shape)."""
    b, t, s, h, hkv, hd = 1, 64, 192, 4, 2, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, t, h, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, hkv, hd), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, hd), jnp.float32)
    ref = masked_attention(q, k, v, causal_mask(t, offset=s - t, s=s)[None])
    out = flash_attention(
        q, k, v, causal=True, block_q=32, block_k=32, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_flash_explicit_offset_masks_padded_tail():
    """offset=0 with S > T (fresh prefill over a page-padded context): keys
    beyond the causal horizon — including the garbage tail — are masked."""
    b, t, s, h, hkv, hd = 1, 64, 128, 4, 2, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, t, h, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, hkv, hd), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, hd), jnp.float32)
    # dense reference sees only the first t keys (the real ones)
    ref = masked_attention(
        q, k[:, :t], v[:, :t], causal_mask(t)[None]
    )
    # poison the tail: if the kernel ever attends there, outputs explode
    k = k.at[:, t:].set(100.0)
    v = v.at[:, t:].set(100.0)
    out = flash_attention(
        q, k, v, causal=True, block_q=32, block_k=32, interpret=True,
        offset=0,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_span_prefill_flash_matches_dense():
    """The serving span step with the flash path on vs off (executor
    heuristic end-to-end): identical prefill outputs."""
    import ml_dtypes

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
    hidden = np.asarray(
        jax.random.normal(jax.random.PRNGKey(7), (2, 128, 64), jnp.float32)
    )

    import asyncio
    import os

    async def run_one(flag):
        os.environ["BBTPU_FLASH_ATTENTION"] = flag
        os.environ["BBTPU_FLASH_INTERPRET"] = "1"  # non-TPU backend gate
        try:
            manager = CacheManager(
                num_layers=2, num_pages=64, page_size=16,
                n_kv_heads=2, head_dim=16, dtype=jnp.float32,
            )
            ex = SpanExecutor(params, spec, manager,
                              compute_dtype=jnp.float32)
            async with manager.allocate(2, 256) as handle:
                return ex.prefill(handle, hidden)
        finally:
            del os.environ["BBTPU_FLASH_ATTENTION"]
            del os.environ["BBTPU_FLASH_INTERPRET"]

    out_flash = asyncio.run(run_one("1"))
    out_dense = asyncio.run(run_one("0"))
    np.testing.assert_allclose(out_flash, out_dense, atol=2e-5, rtol=2e-5)


def test_flash_rejects_bad_shapes():
    q = jnp.zeros((1, 100, 2, 16))
    k = v = jnp.zeros((1, 100, 2, 16))
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    q = jnp.zeros((1, 64, 4, 16))
    k = v = jnp.zeros((1, 64, 3, 16))
    with pytest.raises(ValueError):  # H not a multiple of Hkv
        flash_attention(q, k, v, interpret=True)
    q = jnp.zeros((1, 128, 4, 16))
    k = v = jnp.zeros((1, 64, 2, 16))
    with pytest.raises(ValueError):  # S < T
        flash_attention(q, k, v, interpret=True)


def test_flash_ragged_starts_lens_matches_dense():
    """Per-row starts/lens (mixed-length batch): each row's queries sit at
    its own offset and see only its own keys — the case that previously
    fell back to the dense gather (round-4 verdict #10)."""
    b, t, s, h, hkv, hd = 3, 64, 192, 4, 2, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, t, h, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, hkv, hd), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, hd), jnp.float32)
    starts = np.array([0, 37, 100], np.int32)
    lens = starts + t

    # dense per-row reference: row r attends keys [0, lens[r]) causally
    # from its own offset
    refs = []
    for r in range(b):
        mask = causal_mask(t, offset=int(starts[r]), s=s)[None]
        mask = mask & (jnp.arange(s)[None, None, :] < int(lens[r]))
        refs.append(masked_attention(q[r:r+1], k[r:r+1], v[r:r+1], mask))
    ref = jnp.concatenate(refs, axis=0)

    # poison keys beyond each row's lens: attending there must explode
    k_p, v_p = np.array(k), np.array(v)
    for r in range(b):
        k_p[r, int(lens[r]):] = 100.0
        v_p[r, int(lens[r]):] = 100.0
    out = flash_attention(
        jnp.asarray(q), jnp.asarray(k_p), jnp.asarray(v_p), causal=True,
        block_q=32, block_k=32, interpret=True,
        starts=jnp.asarray(starts), lens=jnp.asarray(lens),
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_span_prefill_flash_mixed_length_batch(monkeypatch):
    """Executor-level: a second-turn prefill over rows with DIFFERENT
    committed context lengths must engage flash and match the dense path
    (previously the uniform-starts gate forced dense)."""
    import asyncio

    from bloombee_tpu.kv.cache_manager import CacheManager
    from bloombee_tpu.models.llama.block import init_block_params
    from bloombee_tpu.models.spec import ModelSpec
    from bloombee_tpu.runtime.executor import SpanExecutor
    from bloombee_tpu.utils.tree import stack_params

    spec = ModelSpec(
        family="llama", hidden_size=32, intermediate_size=64,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        num_hidden_layers=2, vocab_size=64,
    )
    params = stack_params(
        [init_block_params(jax.random.PRNGKey(i), spec, dtype=jnp.float32)
         for i in range(2)]
    )
    rng = np.random.default_rng(0)
    turn1 = rng.standard_normal((2, 40, 32)).astype(np.float32) * 0.1
    lens1 = [17, 40]  # ragged first-turn lengths
    turn2 = rng.standard_normal((2, 128, 32)).astype(np.float32) * 0.1

    def run(flash: bool):
        monkeypatch.setenv("BBTPU_FLASH_ATTENTION", "1" if flash else "0")
        monkeypatch.setenv("BBTPU_FLASH_INTERPRET", "1" if flash else "")
        monkeypatch.setenv("BBTPU_PAGED_ATTENTION", "0")

        async def go():
            manager = CacheManager(
                num_layers=2, num_pages=64, page_size=16,
                n_kv_heads=2, head_dim=8, dtype=jnp.float32,
            )
            ex = SpanExecutor(
                params, spec, manager, compute_dtype=jnp.float32,
                max_chunk_tokens=512,
            )
            async with manager.allocate(2, 256) as handle:
                # ragged turn 1: padded rectangle, per-row commit
                ex.prefill(handle, turn1, commit=False)
                manager.commit(handle, lengths=lens1)
                assert sorted(manager.context_lens(handle)) == sorted(lens1)
                # turn 2: T=128 over rows with different starts
                return ex.prefill(handle, turn2)

        return asyncio.run(go())

    dense = run(False)
    flash = run(True)
    np.testing.assert_allclose(
        np.asarray(flash, np.float32), np.asarray(dense, np.float32),
        rtol=2e-4, atol=2e-4,
    )


# ---------------------------------------------------- the cells' geometries
# name: (t, s, n_rep, hkv, hd, window, dtype, out_dtype, starts, tile).
# A tile depends on n_rep, never on the head COUNT, so each case runs two
# K/V heads (one where the bucket is long) of the cell's group size; `tile`
# is what `flash_tiles` chooses there (pinned: a change of the rule shows)
_BF16, _F32 = jnp.bfloat16, jnp.float32
_CELL_CASES = {
    # Trinity 48/8/128: a window layer's gathered 4,736-key run (37 x 128:
    # only 128 divides it), a window edge inside a tile (start 4200: query
    # 0's first key is 105), a lens that ends inside one (4712 = 36 x 128 +
    # 104), a second row still inside its first window
    "trinity_window_4736": (512, 4736, 6, 2, 128, 4096, _BF16, None,
                            (4200, 100), (512, 128, 6)),
    # ... and the run `_chunk_pages` gathers since PR 48, whole 512-key
    # blocks (5,120): window edge at key 305, lens 4912 = 9 x 512 + 304
    "trinity_window": (512, 5120, 6, 2, 128, 4096, _BF16, None, (4400, 100),
                       (256, 512, 6)),
    # ... and its full layer at the 16k bucket
    "trinity_full": (512, 16384, 6, 1, 128, 0, _BF16, None, (10037,),
                     (256, 512, 6)),
    # Qwen3-Next 16/2/256
    "qwen3next_full": (512, 8192, 8, 1, 256, 0, _BF16, None, (5003,),
                       (128, 512, 8)),
    # Phi-4-mini-flash 40/10/128 halves, window 512 over 1,152 keys, the
    # differential subtraction's float32 output on bfloat16 operands
    "phi4flash_window_1152": (512, 1152, 4, 2, 128, 512, _BF16, _F32,
                              (620, 3), (512, 384, 4)),
    "phi4flash_window": (512, 1536, 4, 2, 128, 512, _BF16, _F32, (900, 3),
                         (512, 512, 4)),
    # the two 128-row cells: Falcon-H1 20/4/128, Qwen3 32/4/128
    "falconh1_t128": (128, 4096, 5, 2, 128, 0, _BF16, None, (2907, 0),
                      (128, 512, 5)),
    "qwen3moe_t128": (128, 4096, 8, 2, 128, 0, _BF16, None, (3840, 1000),
                      (128, 512, 8)),
    # float32 operands: the path that must not narrow (bfloat16 products
    # would read 1e-2 off)
    "float32_operands": (128, 512, 4, 2, 128, 0, _F32, None, (300, 17),
                         (128, 512, 4)),
    # float32 out of bfloat16 operands without a window
    "bf16_in_f32_out": (256, 1024, 4, 1, 128, 0, _BF16, _F32, (700,),
                        (256, 512, 4)),
}


@pytest.mark.parametrize("name", sorted(_CELL_CASES))
def test_flash_at_cell_geometry_matches_dense(name):
    """The kernel at each cell's head geometry and the tile the rule gives
    it, against dense float32 attention over the same (bfloat16) inputs:
    ragged starts / lens, keys past each row's lens poisoned."""
    from bloombee_tpu.ops.pallas.flash_attention import flash_tiles

    t, s, n_rep, hkv, hd, window, dtype, out_dtype, starts, tile = (
        _CELL_CASES[name]
    )
    assert flash_tiles(t, s, n_rep, hd, jnp.dtype(dtype).itemsize) == tile
    b, h = len(starts), n_rep * hkv
    keys = jax.random.split(jax.random.PRNGKey(len(name)), 3)
    q = jax.random.normal(keys[0], (b, t, h, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(keys[1], (b, s, hkv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(keys[2], (b, s, hkv, hd), jnp.float32).astype(dtype)
    starts = np.asarray(starts, np.int32)
    lens = starts + t

    pos = starts[:, None, None] + np.arange(t)[None, :, None]
    key = np.arange(s)[None, None, :]
    mask = (key <= pos) & (key < lens[:, None, None])
    if window:
        mask &= key > pos - window
    ref = masked_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        jnp.asarray(mask),
    )

    k_p, v_p = np.array(k.astype(jnp.float32)), np.array(v.astype(jnp.float32))
    for r in range(b):
        k_p[r, int(lens[r]):] = 100.0
        v_p[r, int(lens[r]):] = 100.0
    out = flash_attention(
        q, jnp.asarray(k_p).astype(dtype), jnp.asarray(v_p).astype(dtype),
        causal=True, interpret=True, starts=jnp.asarray(starts),
        lens=jnp.asarray(lens), window=window, out_dtype=out_dtype,
    )
    assert out.dtype == (out_dtype or dtype)
    # a bfloat16 output is rounded to 8 bits; a float32 one carries the
    # two-term p @ v (a single bfloat16 cast of p would read 1e-3 off)
    tol = 1e-2 if out.dtype == _BF16 else 5e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=tol, rtol=tol
    )


def test_flash_row_without_a_visible_key_is_zero():
    """A row whose lens is 0 (a padding sequence) reads zeros, not the mean
    of the keys its masked logits would weigh equally."""
    b, t, s, h, hkv, hd = 2, 64, 128, 4, 2, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (b, t, h, hd), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, hkv, hd), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, hd), jnp.float32)
    out = flash_attention(
        q, k, v, causal=True, block_q=32, block_k=32, interpret=True,
        starts=jnp.array([0, 0], jnp.int32), lens=jnp.array([64, 0], jnp.int32),
    )
    assert np.all(np.asarray(out[1]) == 0.0)
    assert np.all(np.isfinite(np.asarray(out[0])))


# what the cells' calls look like to the rule: (t, n_rep, hd, window)
_CELL_CALLS = {
    "trinity_window": (512, 6, 128, 4096), "trinity_full": (512, 6, 128, 0),
    "qwen3next": (512, 8, 256, 0), "phi4flash_window": (512, 4, 128, 512),
    "phi4flash_full": (512, 4, 128, 0), "falconh1": (128, 5, 128, 0),
    "qwen3moe": (128, 8, 128, 0),
}


@pytest.mark.parametrize("name", sorted(_CELL_CALLS))
def test_flash_tiles_divide_every_gathered_length(name):
    """At a cell's shapes, every length a caller can hand the kernel (a
    window layer's `_chunk_pages` run, every power-of-two page bucket from
    the chunk up, an arena's own page count where it cuts the bucket) is a
    whole number of the rule's K blocks, the chunk of its query blocks and
    the group of its heads; the tile fits the kernel's VMEM budget."""
    import importlib

    # (the package exports the FUNCTION under the module's name)
    fa = importlib.import_module("bloombee_tpu.ops.pallas.flash_attention")
    from bloombee_tpu.runtime.layer_body import chunk_run_pages

    t, n_rep, hd, window = _CELL_CALLS[name]
    page = 16
    buckets = [2 ** i for i in range(3, 12) if 2 ** i * page >= t]
    buckets += [1280, 1536]  # arenas that cut the last bucket
    lengths = {
        page * chunk_run_pages(t, window, page, pages) for pages in buckets
    }
    assert len(lengths) > 1 or window
    for s in sorted(lengths):
        bq, bk, g = fa.flash_tiles(t, s, n_rep, hd, 2)
        assert t % bq == 0 and s % bk == 0 and n_rep % g == 0, (s, bq, bk, g)
        assert bq % 128 == 0 and bk % 128 == 0
        assert fa._tile_bytes(bq, bk, g, hd, 2) <= fa._VMEM_BUDGET
        # fat in rows whatever the chunk: a 128-row chunk still stacks its
        # group's heads
        assert g * bq >= min(512, n_rep * t), (s, bq, bk, g)


def test_flash_tiles_overrides_pin_a_test_tile():
    from bloombee_tpu.ops.pallas.flash_attention import flash_tiles

    assert flash_tiles(256, 256, 4, 64, 4, block_q=64, block_k=64)[:2] == (
        64, 64)
    # clipped to the axis, as the kernel's old defaults were
    assert flash_tiles(64, 192, 2, 32, 4, block_q=128, block_k=128)[:2] == (
        64, 128)


@pytest.mark.parametrize("t,s,takes", [
    (128, 128, True), (512, 5120, True), (128, 1280 * 16, True),
    (64, 128, False),  # a chunk under the block: the callers' dense tail
    (128, 192, False),  # a bucket that is no whole block
    (256, 128, False),  # a pack wider than its bucket's keys
    (0, 128, False),
])
def test_flash_takes_is_the_callers_one_test(t, s, takes):
    """`flash_takes` is what `_diff_attend`, `_attend_by_rows` and the
    executor ask before a call (and before saying a step's tile): every
    call it admits, `flash_tiles` has a tile for and the kernel accepts."""
    from bloombee_tpu.ops.pallas.flash_attention import (
        flash_takes,
        flash_tiles,
    )

    assert flash_takes(t, s) is takes
    if takes:
        bq, bk, _ = flash_tiles(t, s, 4, 128, 2)
        assert t % bq == 0 and s % bk == 0
