"""The benchmark's `phi4flash` family file, in tier 1.

The plan at the published size, a tiny checkpoint's files, every key of the
needs, and the SambaY metric readers on a synthetic trace and without one.
The pinned values were produced by this file's own code when the family was
added (PR 45): a later edit that moves one has to say so here. The CPU
rehearsal of the cell `phi4flash-longctx` is a row of
`tests/test_cell_rehearsal.py`, which takes its tiny configurations from
here. PR 49 added the second one, the same at hidden size 256
(`WIDE_PHI4FLASH`), whose K/V pair is 128 wide (whole lanes, as the published
pair), so that the sound rehearsal writes its chunks into the arena by page
(`page_write_share`); the controls keep the narrow one.
"""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from cellbench import checkpoint, families  # noqa: E402
from cellbench.tests.test_families import SEED, _sha  # noqa: E402

TINY_PHI4FLASH = {
    "model_type": "phi4flash", "hidden_size": 128, "intermediate_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 8, "sliding_window": 64, "vocab_size": 512,
    "layer_norm_eps": 1e-05, "mb_per_layer": 2, "tie_word_embeddings": True,
    "mlp_bias": False, "lm_head_bias": False, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 8,
    "attention_bias": True, "max_position_embeddings": 8192,
    "hidden_act": "silu", "embd_pdrop": 0, "resid_pdrop": 0,
    "torch_dtype": "bfloat16",
}

# a K/V pair of 128 lanes: its slab has a free page view (kv/arena.py
# `page_view_free`), so its chunks go into the arena by page
WIDE_PHI4FLASH = dict(TINY_PHI4FLASH, hidden_size=256)

PHI_PINS = {
    "plan": (
        "c85ac2bbb8eb2f17d06fd8fd50f4399b2ce7f020010a53b5c6288b99d2dfda5b", 33, 434),
    "files": {
        "config.json": "8ca06cbbef8f75830bc3e8501175cd04760ca9b639787fedf944ba49586a84ae",
        "model-client.safetensors": "796642dd0be4deabe0ce8db793d6ddc61fd43ee7ef95f8d8adcf06c3eae59a7a",
        "model-layer000.safetensors": "ee4792b6ce371a500c788f3a8825cb3495007898a559acdac9f418a22244aa03",
        "model-layer001.safetensors": "979d8f292ef5190472051309b58233e6aaa3422d656c38c64262e8f74a20e108",
        "model-layer002.safetensors": "69a38532db81bf993d6c984e79eaa623c93ad41b6b0d69a15222dd94a194c555",
        "model-layer003.safetensors": "99394ba86c6d9653344c9514fe736ae1ca5a3b5d6a6d2d850691ffd79235c925",
        "model-layer004.safetensors": "61fe6de057dcbbcf887bf3ac5b9ada718f0961be761daca234689200050f0b0d",
        "model-layer005.safetensors": "b7251bdac2a50855846e3034b7514ccb050eeabee827675e7493d6d5d21420bc",
        "model-layer006.safetensors": "c073fabedb08b01e6f4f10a5bf99fb60f41dcd6bd4f8602db5ece39d3a720e2d",
        "model-layer007.safetensors": "75fe05da7bc355907bd8ce6af36b2c8027642e0c2b6be75c46e36a928616e2b6",
        "model.safetensors.index.json": "0da3daf05269478c12d9461579d1a92e5d86b9fa366042304c3fc09fa4500e7c",
    },
    "needs": [
        ("decode_step_needs", 4.0, 10000.0,
         {"bytes": 8426250240.0, "flops": 33618001920.0, "weight_bytes": 6677790720, "kv_bytes": 1722613760.0, "state_bytes": 25804800.0}),
        ("chunk_needs", 512, 5120.0,
         {"bytes": 4007751680.0, "flops": 2111328747520.0, "weight_bytes": 3925278720, "kv_bytes": 70778880.0, "state_bytes": 6451200}),
        ("mamba1_scan_needs", 512, "chunk",
         {"bytes": 292737024, "flops": 2264924160}),
        ("mamba1_scan_needs", 4.0, "decode",
         {"bytes": 28942848.0, "flops": 17694720.0}),
    ],
}


def _published() -> dict:
    config = json.loads(
        (ROOT / "cellbench/configs/phi4-mini-flash-full32.json").read_text())
    config.pop("cellbench")
    return config


def test_phi4flash_plan_at_the_published_size():
    import numpy as np

    plan = checkpoint.tensor_plan(_published())
    listed = [[tag, [[n, list(shape), fill] for n, shape, fill in tensors]]
              for tag, tensors in plan]
    digest = _sha(json.dumps(listed).encode())
    assert (digest, len(plan), sum(len(ts) for _, ts in plan)
            ) == PHI_PINS["plan"]
    size = lambda tensors: sum(  # noqa: E731
        int(np.prod(shape)) for _, shape, _ in tensors)
    # a Mamba layer, a window or full layer, a GMU layer, a cross layer
    # (ISSUE 45: 119.9, 98.3, 104.9, 91.8 M), and all 32: 9 Mamba + 8 window
    # + 1 full + 7 GMU + 7 cross (the issue's table counts 7 window layers;
    # 1, 3, .., 15 are 8)
    assert [round(size(plan[i][1]) / 1e6, 1) for i in (0, 1, 17, 18, 19)] == [
        119.9, 98.3, 98.3, 104.9, 91.8]
    assert round(sum(size(ts) for _, ts in plan[:-1]) / 1e6) == 3340
    names = dict((n, s) for n, s, _ in plan[16][1])
    assert names["model.layers.16.attn.A_log"] == (5120, 16)
    assert names["model.layers.16.attn.x_proj.weight"] == (192, 5120)
    names = dict((n, s) for n, s, _ in plan[19][1])
    assert names["model.layers.19.attn.Wqkv.weight"] == (2560, 2560)
    assert dict((n, s) for n, s, _ in plan[17][1])[
        "model.layers.17.attn.Wqkv.weight"] == (5120, 2560)
    # the head is tied: the client's file has no lm_head
    assert [n for n, _, _ in plan[-1][1]] == [
        "model.embed_tokens.weight", "model.final_layernorm.weight",
        "model.final_layernorm.bias"]


def test_phi4flash_tiny_checkpoint_files(tmp_path):
    checkpoint.write_checkpoint(tmp_path, TINY_PHI4FLASH, SEED)
    got = {p.name: _sha(p.read_bytes()) for p in sorted(tmp_path.iterdir())}
    assert got == PHI_PINS["files"]


def test_phi4flash_needs_every_key():
    family, config = families.of(_published()), _published()
    got = [
        (fn, *args, getattr(family, fn)(config, *args))
        for fn, args in (
            ("decode_step_needs", (4.0, 10000.0)),
            ("chunk_needs", (512, 5120.0)),
            ("mamba1_scan_needs", (512, "chunk")),
            ("mamba1_scan_needs", (4.0, "decode")),
        )
    ]
    assert repr(got) == repr(PHI_PINS["needs"])
    assert family._kinds(config) == {
        "mamba": 9, "sliding": 8, "full": 1, "gmu": 7, "cross": 7}
    # a short chunk runs 18 of the 32 layers: their weights and no others
    short = sum(family._weights(config, k) * n for k, n in
                (("mamba", 9), ("sliding", 8), ("full", 1)))
    assert family.chunk_needs(config, 512, 5120.0)["weight_bytes"] == 2 * short
    # a decode row reads its window in 8 layers, its context in the full one
    # and AGAIN in each of the 7 cross layers (the same pages)
    step = family.decode_step_needs(config, 1.0, 10000.0)
    assert step["kv_bytes"] == 2 * 2560 * (
        8 * (512 + 1) + (10000 + 1) + 7 * 10001)


def test_sambay_metrics_read_nothing_where_there_is_no_trace(tmp_path):
    from cellbench import run as cell_run

    ctx = {"config": _published(), "prefill_chunk": 512,
           "device_kind": "TPU v5 lite", "work_dir": str(tmp_path),
           "info0": {}, "info1": {}}
    for name in ("chunk_mamba_ms_p50", "step_mamba_ms_p50",
                 "mamba_scan_roofline", "step_cross_ms_p50",
                 "cross_rows_share", "window_dead_share",
                 "page_write_share"):  # (a program without the counter)
        assert cell_run.read_metric(name, dict(ctx)) is None


def test_sambay_metrics_on_a_synthetic_reduction_and_counters():
    from cellbench import run as cell_run

    run = lambda **ms: {  # noqa: E731
        **dict.fromkeys(("mamba_proj", "mamba_conv", "mamba_scan",
                         "state_io", "gmu", "cross_attention", "norm",
                         "arena_write", "mlp"), 0.0), **ms}
    got = {"busy_s": 1.0, "move_s": 0.0, "seconds_by_scope": {}, "runs": {
        "chunk": [run(mamba_proj=2.0, mamba_conv=0.5, mamba_scan=4.0,
                      state_io=0.5)] * 3,
        "decode": [run(mamba_proj=1.0, mamba_scan=0.25, gmu=0.5,
                       cross_attention=1.5)] * 3,
        "fused": []}}
    ctx = {"config": _published(), "prefill_chunk": 512,
           "device_kind": "TPU v5 lite", "_scopetrace_sambay": got,
           "info0": {"memory": {"sambay": {
               "cross_rows": 10, "self_rows": 100, "kv_held_tokens": 0,
               "window_dead_tokens": 0}, "kv_writes": {
               "chunk_page_writes": 40, "chunk_row_writes": 7},
               "kv_walk": {"turns": 4096, "live_turns": 1336}}},
           "info1": {"memory": {"sambay": {
               "cross_rows": 110, "self_rows": 10100, "kv_held_tokens": 900,
               "window_dead_tokens": 720}, "kv_writes": {
               "chunk_page_writes": 115, "chunk_row_writes": 32},
               "kv_walk": {"turns": 6096, "live_turns": 3236}}}}
    read = lambda name: cell_run.read_metric(name, ctx)  # noqa: E731
    assert read("chunk_mamba_ms_p50") == 7.0
    assert read("step_mamba_ms_p50") == 1.25
    assert read("step_cross_ms_p50") == 2.0
    assert read("cross_rows_share") == pytest.approx(1.0)
    assert read("window_dead_share") == pytest.approx(80.0)
    assert read("page_write_share") == pytest.approx(75.0)  # 75 of 100
    assert read("decode_walk_live_share") == pytest.approx(95.0)  # of 2000
    # a program without the counter (the parent of PR 56), and a window
    # with no decode dispatch through the kernel: nothing to read
    for info in ctx["info0"], ctx["info1"]:
        info["memory"]["kv_walk"]["turns"] = 4096
    assert read("decode_walk_live_share") is None
    for info in ctx["info0"], ctx["info1"]:
        del info["memory"]["kv_walk"]
    assert read("decode_walk_live_share") is None
    needs = families.of(_published()).mamba1_scan_needs(
        _published(), 512, "chunk")
    least = max(needs["bytes"] / 819e9, needs["flops"] / 197e12)
    assert read("mamba_scan_roofline") == pytest.approx(100 * least / 4e-3)
    assert 0 < read("mamba_scan_roofline") < 100


def test_a_prompt_by_page_decode_rows_and_a_prompt_inside_a_page(tmp_path):
    """Executor level (PR 49): a prompt's chunk from a page's first token
    goes into the arena by page, decode rows and a second prompt that starts
    inside a page row by row (`chunk_row_writes` counts it), and every
    step's output and both arenas are those of the parent's path, the row
    scatter throughout, bit for bit."""
    import asyncio

    import jax.numpy as jnp
    import numpy as np

    from bloombee_tpu.kv.cache_manager import CacheManager
    from bloombee_tpu.models.checkpoint import load_span_params
    from bloombee_tpu.runtime.executor import SpanExecutor

    checkpoint.write_checkpoint(tmp_path, WIDE_PHI4FLASH, SEED)
    layers = WIDE_PHI4FLASH["num_hidden_layers"]
    params, spec = load_span_params(str(tmp_path), 0, layers, dtype=jnp.float32)
    rng = np.random.default_rng(49)
    hidden = (0.05 * rng.standard_normal(
        (1, 55, spec.hidden_size))).astype(np.float32)

    def drive(by_page: bool):
        manager = CacheManager(
            layers, 16, 16, spec.num_key_value_heads, spec.head_dim,
            dtype=jnp.float32, ssm=spec.recurrent, state_slots=4,
            arena_layers=spec.arena_layers(0, layers))
        assert manager.arena["k"].shape[1:] == (16 * 16, 1, 128)
        ex = SpanExecutor(params, spec, manager, compute_dtype=jnp.float32)
        if not by_page:
            ex._page_groups = lambda slots_pad: False

        async def go():
            async with manager.allocate(1, 64) as h:
                outs = [ex.prefill_chunk(
                    h, hidden[:, :32], commit=True, fetch=True)]
                outs += [ex.decode(h, hidden[:, t:t + 1])
                         for t in range(32, 35)]
                outs.append(ex.prefill_chunk(
                    h, hidden[:, 35:], commit=True, fetch=True))
                return [np.asarray(o) for o in outs], [
                    np.asarray(manager.arena[key]) for key in ("k", "v")]

        return asyncio.run(go()), dict(ex.kv_writes)

    (outs, arenas), writes = drive(True)
    (want_outs, want_arenas), parent_writes = drive(False)
    assert writes == {"chunk_page_writes": 1, "chunk_row_writes": 1}
    assert parent_writes == {"chunk_page_writes": 0, "chunk_row_writes": 2}
    for got, want in zip(outs + arenas, want_outs + want_arenas):
        assert np.isfinite(got).all() and np.abs(got).max() > 0
        np.testing.assert_array_equal(got, want)
