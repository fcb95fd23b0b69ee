"""The benchmark's `kimi_linear` family file, in tier 1.

The plan at the published size, a tiny checkpoint's files, every key of the
needs, the KDA readers on a synthetic trace. The pinned values were produced
by this file's own code when the family was added (PR 51): a later edit that
moves one has to say so here. The CPU rehearsal of the cell
`kimilinear-longctx` is a row of `tests/test_cell_rehearsal.py`, which takes
its tiny configuration from here.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from cellbench import checkpoint, families  # noqa: E402
from cellbench.tests.test_families import SEED, _sha  # noqa: E402

# 7 layers L L L F L L F (a whole period, then the model's short tail), the
# first layer's MLP dense; 8 router outputs of which experts 2-5 are held
TINY_KIMI_LINEAR = {
    "model_type": "kimi_linear", "hidden_size": 128, "intermediate_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 32,
    "kv_lora_rank": 64, "q_lora_rank": None, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 16, "v_head_dim": 32, "mla_use_nope": True,
    "linear_attn_config": {
        "full_attn_layers": [4, 7], "kda_layers": [1, 2, 3, 5, 6],
        "head_dim": 32, "num_heads": 4, "short_conv_kernel_size": 4},
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "moe_intermediate_size": 64, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_expert_group": 1,
    "topk_group": 1, "use_grouped_topk": True, "num_experts": 4,
    "router_experts": 8, "experts_held": [2, 4], "num_experts_per_token": 3,
    "num_shared_experts": 1, "routed_scaling_factor": 2.446,
    "num_hidden_layers": 7, "vocab_size": 512, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "rope_scaling": None, "hidden_act": "silu",
    "model_max_length": 8192, "num_nextn_predict_layers": 0,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
}

KIMI_PINS = {
    "plan": (
        "99e386df2dc58f0080517ae7b7f47829c2f4c1a47403bd04bcb0364b6bf840cb", 9, 1501),
    "files": {
        "config.json": "e903832a7d1a424e43139564207c377f614d6a14dc2831ff2485f1a853e2e827",
        "model-client.safetensors": "401a37180d1e6d104dfcd95877079ddd03839281615366a3c6ec48926312860d",
        "model-layer000.safetensors": "8cda0e671925817c3b257fda0a24c98f6f8365d542fa58f9474861804f440954",
        "model-layer001.safetensors": "47f9a33d05b1a733c8b29685693f131a68316e151694bbf517b168c2769f604f",
        "model-layer002.safetensors": "347372f31b8d63a94efcddc9907f67583f98b6572844401c93057ed126c0ba6b",
        "model-layer003.safetensors": "7884abdce04ebc053657e5690520542505870cf867729460210b02c223fbb852",
        "model-layer004.safetensors": "bc2693d11d0a902f70fff5e95a2cd7e6700b6ce9521af942f446016cf4b7b13f",
        "model-layer005.safetensors": "6de5bf5057b2ca5ef61265e4bc00a3954dc2c5c8a6378f1a0689310359da2a69",
        "model-layer006.safetensors": "245223b9dc270fdf593cc3f68c16009ae6a716ed4965b27251bf9af4934022d3",
        "model.safetensors.index.json": "a3be1bc491c9bcb12df955aa328bfbda96550bc7e7da029df580b2b251df93a8",
    },
    "needs": [
        ("decode_step_needs", 2.0, 10000.0,
         {"bytes": 1313389056.0, "flops": 4875436032.0, "weight_bytes": 1214906368.0, "kv_bytes": 46363136.0, "state_bytes": 52101120.0}),
        ("chunk_needs", 512, 5120.0,
         {"bytes": 7282752983.146531, "flops": 918334472192.0, "weight_bytes": 7166524887.146531, "kv_bytes": 85458944.0, "state_bytes": 26050560}),
        ("kda_rule_needs", 512, "chunk",
         {"bytes": 203293440, "flops": 11576279040}),
        ("kda_rule_needs", 2.0, "decode",
         {"bytes": 53479680.0, "flops": 45219840.0}),
        ("mla_attention_needs", 512, 5120.0, "chunk",
         {"bytes": 85458944.0, "flops": 383325831168.0}),
        ("mla_attention_needs", 2.0, 10000.0, "decode",
         {"bytes": 46363136.0, "flops": 2785558528.0}),
    ],
}


def _published_kimi() -> dict:
    config = json.loads(
        (ROOT / "cellbench/configs/kimi-linear-48b-ep4-span8.json").read_text())
    config.pop("cellbench")
    return config


def test_kimi_linear_configuration_keeps_every_published_number():
    """The catalog row's `config`, key for key: only the keys `reduced`
    names differ, and no width is among them."""
    whole = json.loads(
        (ROOT / "cellbench/configs/kimi-linear-48b-ep4-span8.json").read_text())
    cb = whole.pop("cellbench")
    assert set(cb["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size", "linear_attn_config"}
    assert cb["published"] == {
        "num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4}}
    lin = whole["linear_attn_config"]
    assert (lin["head_dim"], lin["num_heads"], lin["short_conv_kernel_size"]
            ) == (128, 32, 4)
    assert (lin["full_attn_layers"], lin["kda_layers"]) == (
        [4, 8], [1, 2, 3, 5, 6, 7])
    assert (whole["hidden_size"], whole["intermediate_size"],
            whole["moe_intermediate_size"], whole["kv_lora_rank"],
            whole["qk_nope_head_dim"], whole["qk_rope_head_dim"],
            whole["v_head_dim"], whole["num_experts_per_token"],
            whole["q_lora_rank"], whole["mla_use_nope"]) == (
        2304, 9216, 1024, 512, 128, 64, 128, 8, None, True)
    assert (whole["router_experts"], whole["experts_held"]) == (256, [0, 64])
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == "kimi-linear-48b-ep4-span8")
    assert sorted(entry["reduced"]) == sorted(cb["reduced"])
    assert entry["source"] == cb["source"]


def test_kimi_linear_plan_at_the_published_size():
    import numpy as np

    plan = checkpoint.tensor_plan(_published_kimi())
    listed = [[tag, [[n, list(shape), fill] for n, shape, fill in tensors]]
              for tag, tensors in plan]
    digest = _sha(json.dumps(listed).encode())
    assert (digest, len(plan), sum(len(ts) for _, ts in plan)
            ) == KIMI_PINS["plan"]
    size = lambda tensors: sum(  # noqa: E731
        int(np.prod(shape)) for _, shape, _ in tensors)
    # layer 0: a KDA mixer (39.5 M) and the dense MLP (63.7 M); a sparse KDA
    # layer and a sparse latent layer with the 64 HELD experts, the router
    # over all 256 and the shared expert (460.7 M); the span (ISSUE 51:
    # 3,584 M parameters, 7.17 GB)
    assert [round(size(ts) / 1e6, 1) for _, ts in plan[:4]] == [
        103.2, 500.2, 500.2, 489.8]
    assert round(sum(size(ts) for _, ts in plan[:-1]) / 1e6) == 3584
    names = dict((n, s) for n, s, _ in plan[3][1])
    a, s = "model.layers.3.self_attn.", "model.layers.3.block_sparse_moe."
    assert s + "experts.63.w2.weight" in names
    assert s + "experts.64.w2.weight" not in names
    assert names[s + "gate.weight"] == (256, 2304)
    assert names[s + "gate.e_score_correction_bias"] == (256,)
    assert names[a + "q_proj.weight"] == (32 * 192, 2304)
    assert names[a + "kv_a_proj_with_mqa.weight"] == (512 + 64, 2304)
    assert a + "q_a_proj.weight" not in names
    names = dict((n, s) for n, s, _ in plan[0][1])
    a = "model.layers.0.self_attn."
    assert names[a + "k_conv1d.weight"] == (4096, 1, 4)
    assert names[a + "A_log"] == (1, 1, 32, 1)
    assert names[a + "f_b_proj.weight"] == (4096, 128)
    assert names["model.layers.0.mlp.gate_proj.weight"] == (9216, 2304)
    assert not any("block_sparse_moe" in n for n in names)


def test_kimi_linear_tiny_checkpoint_files(tmp_path):
    checkpoint.write_checkpoint(tmp_path, TINY_KIMI_LINEAR, SEED)
    got = {p.name: _sha(p.read_bytes()) for p in sorted(tmp_path.iterdir())}
    assert got == KIMI_PINS["files"]


def test_kimi_linear_needs_every_key():
    family, config = families.of(_published_kimi()), _published_kimi()
    got = [
        (fn, *args, getattr(family, fn)(config, *args))
        for fn, args in (
            ("decode_step_needs", (2.0, 10000.0)),
            ("chunk_needs", (512, 5120.0)),
            ("kda_rule_needs", (512, "chunk")),
            ("kda_rule_needs", (2.0, "decode")),
            ("mla_attention_needs", (512, 5120.0, "chunk")),
            ("mla_attention_needs", (2.0, 10000.0, "decode")),
        )
    ]
    assert repr(got) == repr(KIMI_PINS["needs"])
    # a cached token is 1,152 B a LATENT layer (the latent and the shared
    # key), a session's state 2.17 MB a KDA one; a 512-row chunk reaches all
    # 64 held experts, 2 pairs a row; two decode rows about four
    assert family.latent_row_bytes(config) == (512 + 64) * 2
    assert family.state_bytes(config) == 32 * 128 * 128 * 4 + 3 * 12288 * 2
    assert family._expert_reach(config, 512) == pytest.approx((2.0, 64), abs=0.01)
    assert family._expert_reach(config, 2)[1] == pytest.approx(3.94, abs=0.01)
    assert family._kinds(config) == (6, 2)
    # ISSUE 51's two mixers: about 39.5 M and 29.1 M parameters
    assert family._mixer_weights(config) == (39460864, 29114368)


def test_kda_readers_reduce_a_synthetic_trace(monkeypatch):
    """The four KDA metrics on a trace whose answers are worked out by hand:
    five runs of the packed program (the first and the last are the trace's
    edges), one a decode run; ops under the KDA mixer's scopes, one of them
    a move under `state_io`; a program with `state_io` alone (another
    recurrent family, the parent) reads nothing."""
    from cellbench import scopetrace
    from cellbench.metrics import (
        chunk_kda_ms_p50,
        kda_rule_roofline,
        kda_state_move_share,
        step_kda_ms_p50,
    )

    ms = 1e-3
    step = "jit(span_step_packed_impl)/jit(main)/while/body/cond/branch_1_fun/"

    def op(name, start, dur, op_name):
        return (f"%{name} = f32[8]{{0}} {name.split('.')[0]}()", start * ms,
                dur * ms, op_name)

    prog = "jit_span_step_packed_impl(1)"
    raw = {"device": [{"name": "/device:TPU:0", "modules": [
        (prog, 0.0, 5 * ms), (prog, 10 * ms, 10 * ms), (prog, 30 * ms, 10 * ms),
        (prog, 50 * ms, 10 * ms), (prog, 70 * ms, 5 * ms),
    ], "ops": [
        op("fusion.0", 0, 5, step + "moe_experts/dot_general:"),
        op("paged_decode_attention_latent.1", 10, 3,
           step + "attention/mla_attention/pallas_call:"),
        op("fusion.2", 13, 2, step + "kda_proj/dot_general:"),
        op("fusion.3", 15, 0.5, step + "kda_rule/mul:"),
        op("fusion.4", 15.5, 0.6, step + "state_io/gather:"),
        op("copy.5", 16.1, 0.4, step + "state_io/scatter:"),
        op("fusion.6", 30, 3, step + "kda_proj/dot_general:"),
        op("fusion.7", 33, 4, step + "kda_rule/triangular_solve:"),
        op("fusion.12", 37, 0.5, step + "kda_conv/dot_general:"),
        op("fusion.11", 37.5, 1, step + "state_io/gather:"),
        op("fusion.8", 50, 3, step + "kda_proj/dot_general:"),
        op("fusion.9", 53, 2, step + "kda_rule/dot_general:"),
        op("fusion.10", 70, 5, step + "moe_experts/dot_general:"),
    ]}]}
    monkeypatch.setattr(
        scopetrace, "reduced",
        lambda ctx, name, scopes, move: scopetrace.reduce(raw, scopes, move))
    ctx = {"config": _published_kimi(), "prefill_chunk": 512,
           "device_kind": "TPU v5 lite"}
    assert step_kda_ms_p50.read(dict(ctx)) == pytest.approx(3.5)
    # chunk runs: 8.5 and 5 -> 6.75; the rule's three scopes: 5.5 and 2
    assert chunk_kda_ms_p50.read(dict(ctx)) == pytest.approx(6.75)
    assert kda_state_move_share.read(dict(ctx)) == pytest.approx(
        100 * 0.4 / 30)
    family = families.of(ctx["config"])
    from cellbench import roofline

    least, _ = roofline.least_seconds(
        family.kda_rule_needs(ctx["config"], 512, "chunk"), "TPU v5 lite")
    assert kda_rule_roofline.read(dict(ctx)) == pytest.approx(
        100 * least / 3.75e-3)
    # no KDA scope in the program: `state_io` alone is another family's
    raw["device"][0]["ops"] = [
        o for o in raw["device"][0]["ops"] if "kda_" not in o[3]]
    for metric in (step_kda_ms_p50, chunk_kda_ms_p50, kda_state_move_share,
                   kda_rule_roofline):
        assert metric.read(dict(ctx)) is None


@pytest.mark.parametrize("name", [
    "step_kda_ms_p50", "chunk_kda_ms_p50", "kda_rule_roofline",
    "kda_state_move_share"])
def test_a_kda_metric_reads_nothing_where_there_is_no_trace(tmp_path, name):
    """An untraced run, or the parent's program: None, not a made-up number."""
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "cellbench" / "metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    ctx = {"trace_dir": str(tmp_path / "trace"), "config": _published_kimi(),
           "prefill_chunk": 512, "device_kind": "TPU v5 lite"}
    assert module.read(ctx) is None


def test_the_new_cell_joins_the_metrics_it_reports_and_adds_four():
    """BENCHMARK.json: the cell was an ADDITION (the eighth one-chip cell;
    later PRs add theirs after it), every list it joined had the seven or is
    one ISSUE 51 names, and the four KDA metrics list it alone."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = "kimilinear-longctx"
    assert [w["name"] for w in bench["workloads"]][7] == cell
    assert len(bench["workloads"]) == len(bench["configs"]) >= 8
    assert all(w["chips"] == 1 for w in bench["workloads"])
    joined = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if cell in m.get("workloads", ())}
    assert {"tokens_per_s", "ttft_long_ms_p50", "gap_long_ms_p50",
            "step_roofline", "chunk_roofline", "chunk_mla_ms_p50",
            "step_mla_ms_p50", "mla_attention_roofline",
            "mla_decode_roofline", "latent_io_move_share",
            "held_experts_hit_share", "router_bias_moved_share",
            "chunk_router_ms_p50", "chunk_experts_ms_p50",
            "page_write_share"} <= joined
    assert "gap_ms_p50" not in joined
    assert "held_experts_reached_share" not in joined
    for name in ("chunk_kda_ms_p50", "step_kda_ms_p50", "kda_rule_roofline",
                 "kda_state_move_share"):
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [cell]
        assert (metric["moves"], metric["layer"]) == ("tokens_per_s", "kernels")
        assert (ROOT / "cellbench" / "metrics" / f"{name}.py").exists()
