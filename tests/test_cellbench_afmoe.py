"""The benchmark's `afmoe` family file, in tier 1.

The plan at the published size, every key of the needs, and the family's
metric readers on synthetic reductions and without a trace. The CPU rehearsal
of the cell `trinity-longctx` is a row of `tests/test_cell_rehearsal.py`,
which takes its tiny configuration from here (as `tests/test_afmoe.py` does).
"""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from cellbench import checkpoint, families  # noqa: E402

# five layers of the cell's kinds (dense; window, window, full, window), 8
# routed experts of which this share holds 4, window 32
TINY_AFMOE = {
    "model_type": "afmoe", "hidden_size": 128, "intermediate_size": 256,
    "moe_intermediate_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "num_hidden_layers": 5,
    "num_dense_layers": 1, "num_experts": 4, "router_experts": 8,
    "experts_held": [2, 4], "num_experts_per_tok": 2,
    "num_shared_experts": 1, "sliding_window": 32,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention",
                    "sliding_attention"],
    "global_attn_every_n_layers": 4, "vocab_size": 512,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "rope_scaling": None,
    "route_norm": True, "route_scale": 2.448, "score_func": "sigmoid",
    "n_group": 1, "topk_group": 1, "mup_enabled": True,
    "max_position_embeddings": 4096, "tie_word_embeddings": False,
    "hidden_act": "silu", "torch_dtype": "bfloat16",
}


def _published() -> dict:
    config = json.loads(
        (ROOT / "cellbench/configs/trinity-large-ep8-span5.json").read_text())
    config.pop("cellbench")
    return config


def test_afmoe_plan_at_the_published_size():
    import numpy as np

    config = _published()
    plan = checkpoint.tensor_plan(config)
    size = lambda tensors: sum(  # noqa: E731
        int(np.prod(shape)) for _, shape, _ in tensors)
    # ISSUE 47's arithmetic: a dense layer 176M, a sparse layer with 32 of
    # 256 experts 998M (attention 62.9M with its gate, shared 28.3M, router
    # 0.79M, an expert 28.31M), an eighth of the vocabulary twice
    assert [round(size(ts) / 1e6, 1) for _, ts in plan] == [
        176.2, 998.0, 998.0, 998.0, 998.0, 153.8]
    names = {n: s for n, s, _ in plan[1][1]}
    assert names["model.layers.1.mlp.router.gate.weight"] == (256, 3072)
    assert names["model.layers.1.mlp.expert_bias"] == (256,)
    assert names["model.layers.1.self_attn.gate_proj.weight"] == (6144, 3072)
    assert names["model.layers.1.mlp.experts.31.down_proj.weight"] == (
        3072, 3072)
    assert "model.layers.1.mlp.experts.32.down_proj.weight" not in names
    assert "model.layers.0.mlp.router.gate.weight" not in {
        n for n, _, _ in plan[0][1]}
    # expert_bias is no zeros, and the post norms' gains are a fill
    fills = {n: f for n, _, f in plan[1][1]}
    assert fills["model.layers.1.mlp.expert_bias"]["low"] < 0 < fills[
        "model.layers.1.mlp.expert_bias"]["high"]
    assert fills["model.layers.1.post_mlp_layernorm.weight"] != "ones"
    # every width as published (the catalog's numbers, pinned here)
    for key, want in (("hidden_size", 3072), ("intermediate_size", 12288),
                      ("moe_intermediate_size", 3072), ("head_dim", 128),
                      ("num_attention_heads", 48), ("num_key_value_heads", 8),
                      ("num_experts_per_tok", 4), ("sliding_window", 4096),
                      ("route_scale", 2.448), ("router_experts", 256)):
        assert config[key] == want


def test_afmoe_needs_every_key():
    family, config = families.of(_published()), _published()
    step = family.decode_step_needs(config, 4.0, 10000.0)
    chunk = family.chunk_needs(config, 512, 5120.0)
    for needs in (step, chunk):
        assert set(needs) == {"bytes", "flops", "weight_bytes", "kv_bytes"}
        assert needs["bytes"] > needs["weight_bytes"] > 0
    row = 2 * 8 * 128 * 2  # K and V of a token in one layer, bfloat16
    # a decode row reads its window in the four window layers and its whole
    # context in the full one, and writes its own row in each
    assert step["kv_bytes"] == 4 * row * (4 * (4096 + 1) + (10001 + 1))
    # a chunk's rows between them see the window plus the chunk in a window
    # layer, the whole context in the full one
    assert chunk["kv_bytes"] == row * (
        4 * (4096 + 512 + 1024) + (5120 + 1024))
    # a 512-row chunk reaches every held expert: all 32 stacks are counted
    d = 3072
    held = 32 * 3 * d * d
    assert chunk["weight_bytes"] > 2 * 4 * held
    assert chunk["weight_bytes"] < 2 * (4 * held + 5 * 63e6 + 4 * 30e6 + 114e6)
    # of what five full layers would read of 10k cached tokens, the four
    # window layers skip more than 40%
    assert family.window_skip_share(config, 512, 10000.0) == pytest.approx(
        100 * (1 - (4 * (4096 + 512) + 10512) / (5 * 10512)))


def test_afmoe_metrics_read_nothing_where_there_is_no_trace(tmp_path):
    from cellbench import run as cell_run

    ctx = {"config": _published(), "prefill_chunk": 512,
           "device_kind": "TPU v5 lite", "work_dir": str(tmp_path),
           "trace_dir": str(tmp_path / "none"), "info0": {}, "info1": {}}
    for name in ("chunk_router_ms_p50", "chunk_window_attn_ms_p50",
                 "chunk_full_attn_ms_p50", "router_bias_moved_share",
                 "held_experts_reached_share", "window_dead_share"):
        assert cell_run.read_metric(name, dict(ctx)) is None


def test_afmoe_metrics_on_synthetic_reductions():
    from cellbench import reachtrace
    from cellbench import run as cell_run

    kinds = {"busy_s": 1.0, "move_s": 0.0, "seconds_by_scope": {}, "runs": {
        "chunk": [{"window_attn": 1.5, "full_attn": 2.5}] * 3,
        "decode": [], "fused": []}}
    moe = {"busy_s": 1.0, "move_s": 0.0, "seconds_by_scope": {}, "runs": {
        "chunk": [{"moe_router": 0.75, "moe_shared": 1.0,
                   "moe_experts": 9.0}] * 3, "decode": [], "fused": []}}
    # the spans as the program stamps them: a full chunk, a tail, a decode
    raw = {"host": [{"line": "x", "events": [
        ("bbtpu.moe_reach", 0.0, 0.0, {
            "kind": "chunk", "rows": "512", "held_hit": "32;30;32;31",
            "routed_pairs_here": "250;260;255;259",
            "rows_with_held_expert": "200;210;205;207",
            "bias_moved_pairs": "400;500;450;450",
            "routed_pairs": "2048;2048;2048;2048"}),
        ("bbtpu.moe_reach", 0.0, 0.0, {
            "kind": "chunk", "rows": "100", "held_hit": "9;9;9;9",
            "bias_moved_pairs": "1;1;1;1", "routed_pairs": "400;400;400;400"}),
        ("bbtpu.moe_reach", 0.0, 0.0, {
            "kind": "decode", "rows": "4", "held_hit": "2;2;2;2"}),
        ("bbtpu.step", 0.0, 0.0, {
            "kind": "chunk", "rows": "512", "context": "5120"}),
        ("bbtpu.other", 0.0, 0.0, {}),
    ]}]}
    got = reachtrace.reduce(raw)
    assert len(got["reach"]) == 3 and got["steps"] == [
        {"kind": "chunk", "rows": 512, "context": 5120}]
    assert reachtrace.reduce({"host": []}) is None
    ctx = {"config": _published(), "prefill_chunk": 512,
           "_scopetrace_attnkind": kinds, "_scopetrace_moe": moe,
           "_reachtrace": got,
           "info0": {"memory": {"sambay": {
               "kv_held_tokens": 0, "window_dead_tokens": 0}}},
           "info1": {"memory": {"sambay": {
               "kv_held_tokens": 1000, "window_dead_tokens": 470}}}}
    read = lambda name: cell_run.read_metric(name, ctx)  # noqa: E731
    assert read("chunk_window_attn_ms_p50") == 1.5
    assert read("chunk_full_attn_ms_p50") == 2.5
    assert read("chunk_router_ms_p50") == 0.75
    assert read("chunk_experts_ms_p50") == 10.75
    assert read("router_bias_moved_share") == pytest.approx(
        100 * 1800 / 8192)
    assert read("held_experts_reached_share") == pytest.approx(
        100 * 31.5 / 32)
    assert read("window_dead_share") == pytest.approx(47.0)
    # the readers of latent attention's reduction read nothing here
    ctx["_mlatrace"] = None
    assert read("held_experts_hit_share") is None
    assert read("chunk_moe_ms_p50") is None
