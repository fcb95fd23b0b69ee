"""The benchmark's `qwen3_next` family file, in tier 1.

The plan at the published size, a tiny checkpoint's files, every key of the
needs, and the generic scope reducer on a synthetic trace. The pinned values
were produced by this file's own code when the family was added (PR 39): a
later edit that moves one has to say so here. The CPU rehearsal of the cell
`qwen3next-longctx` is a row of `tests/test_cell_rehearsal.py`, which takes
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

TINY_QWEN3_NEXT = {
    "model_type": "qwen3_next", "hidden_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "partial_rotary_factor": 0.25,
    "full_attention_interval": 4, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 16, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_value_head_dim": 16,
    "num_experts": 4, "router_experts": 8, "experts_held": [2, 4],
    "num_experts_per_tok": 3, "norm_topk_prob": True,
    "moe_intermediate_size": 64, "shared_expert_intermediate_size": 64,
    "intermediate_size": 256, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "num_hidden_layers": 8, "vocab_size": 512, "rms_norm_eps": 1e-06,
    "rope_theta": 10000000, "rope_scaling": None, "hidden_act": "silu",
    "max_position_embeddings": 8192, "tie_word_embeddings": False,
    "use_sliding_window": False, "torch_dtype": "bfloat16",
}

Q3N_PINS = {
    "plan": (
        "37dc9dff90f948d71a52449c82f7853f390c21075f40aa4975cccb1a82b90096", 9, 3185),
    "files": {
        "config.json": "b101ee5b427e9d3c69b5fa77150877651f7a955ed733e6933e7e6baa5eab31d5",
        "model-client.safetensors": "9da2d2899807cbeffbbf413242211bab068f5024acf8ff23ed5ba54cb156686b",
        "model-layer000.safetensors": "891a04e45c6740228597512528b702fe1803a8e2256ee3741a141ebf0078471d",
        "model-layer001.safetensors": "d332b451e7e89f8468e227a75eadf32af3335f1b1030552ada3162b7eccfc705",
        "model-layer002.safetensors": "3c55f909fe3107056d5632d3414db0d03ce6edec74578eeb9580a3d4a844805a",
        "model-layer003.safetensors": "3d89d5fbc45469ab34c339fa6f41185750f023f2a799fd7fc577b84a6034477c",
        "model-layer004.safetensors": "8d39b8ea3fb7331d72490876be6fcd336d25f3ebb0543893aaa4f744b15cd345",
        "model-layer005.safetensors": "856ca014d8785a383e956f0c6b308b5b3c79726e0ecb37f386b9f730900ec3bc",
        "model-layer006.safetensors": "8ebb974c10090ecc426f6c2eb1149a9048774490ebb8ec6333f4f86d73e693f7",
        "model-layer007.safetensors": "9f46edd716e774c6812dab4e84c3292f243da12508707913efea581d29fbd67b",
        "model.safetensors.index.json": "f7a021e694379eda408356eb1ece90383771aeb6eb774d72fa66e4e681f00d2f",
    },
    "needs": [
        ("decode_step_needs", 2.0, 10000.0,
         {"bytes": 963076096.0, "flops": 2111897600.0, "weight_bytes": 829620224.0, "kv_bytes": 81928192.0, "state_bytes": 51511296.0}),
        ("chunk_needs", 512, 5120.0,
         {"bytes": 7075624220.084858, "flops": 463067938816.0, "weight_bytes": 7022605596.084858, "kv_bytes": 23068672.0, "state_bytes": 25755648}),
        ("gdn_rule_needs", 512, "chunk",
         {"bytes": 127600128, "flops": 11475615744}),
        ("gdn_rule_needs", 2.0, "decode",
         {"bytes": 52302336.0, "flops": 44826624.0}),
    ],
}


def _published_q3n() -> dict:
    config = json.loads(
        (ROOT / "cellbench/configs/qwen3-next-80b-ep4-span8.json").read_text())
    config.pop("cellbench")
    return config


def test_qwen3_next_plan_at_the_published_size():
    import numpy as np

    plan = checkpoint.tensor_plan(_published_q3n())
    listed = [[tag, [[n, list(shape), fill] for n, shape, fill in tensors]]
              for tag, tensors in plan]
    digest = _sha(json.dumps(listed).encode())
    assert (digest, len(plan), sum(len(ts) for _, ts in plan)
            ) == Q3N_PINS["plan"]
    size = lambda tensors: sum(  # noqa: E731
        int(np.prod(shape)) for _, shape, _ in tensors)
    # a linear layer and a full one with the 128 HELD experts, the router
    # over all 512 and the shared expert; the span (ISSUE 39: 402.7 M of
    # experts + 37.9 M / 31.5 M outside them, 3,512 M in eight layers)
    assert [round(size(ts) / 1e6, 1) for _, ts in plan[:4]] == [
        440.6, 440.6, 440.6, 434.1]
    assert round(sum(size(ts) for _, ts in plan[:-1]) / 1e6) == 3512
    names = dict((n, s) for n, s, _ in plan[3][1])
    assert "model.layers.3.mlp.experts.127.down_proj.weight" in names
    assert "model.layers.3.mlp.experts.128.down_proj.weight" not in names
    assert names["model.layers.3.mlp.gate.weight"] == (512, 2048)
    assert names["model.layers.3.self_attn.q_proj.weight"] == (8192, 2048)
    assert dict((n, s) for n, s, _ in plan[0][1])[
        "model.layers.0.linear_attn.in_proj_qkvz.weight"] == (12288, 2048)


def test_qwen3_next_tiny_checkpoint_files(tmp_path):
    checkpoint.write_checkpoint(tmp_path, TINY_QWEN3_NEXT, SEED)
    got = {p.name: _sha(p.read_bytes()) for p in sorted(tmp_path.iterdir())}
    assert got == Q3N_PINS["files"]


def test_qwen3_next_needs_every_key():
    family, config = families.of(_published_q3n()), _published_q3n()
    got = [
        (fn, *args, getattr(family, fn)(config, *args))
        for fn, args in (
            ("decode_step_needs", (2.0, 10000.0)),
            ("chunk_needs", (512, 5120.0)),
            ("gdn_rule_needs", (512, "chunk")),
            ("gdn_rule_needs", (2.0, "decode")),
        )
    ]
    assert repr(got) == repr(Q3N_PINS["needs"])
    # a cached token is 2 KB a FULL layer, a session's state 2.15 MB a
    # LINEAR one; a 512-row chunk reaches all 128 held experts, 2.5 pairs a
    # row; two decode rows about five
    assert family.kv_row_bytes(config) == 2048
    assert family.state_bytes(config) == 32 * 128 * 128 * 4 + 3 * 8192 * 2
    assert family._expert_reach(config, 512) == pytest.approx((2.5, 128), abs=0.01)
    assert family._expert_reach(config, 2)[1] == pytest.approx(4.95, abs=0.01)
    assert family._kinds(config) == (6, 2)


def test_scopetrace_reduces_a_synthetic_trace_by_the_scopes_it_is_given():
    """`cellbench/scopetrace.py`, the ONE reducer the linear mixer's four
    metrics share, on a trace whose answers are worked out by hand: five
    runs of the packed program (the first and the last are the trace's edges
    and are left out), one a decode run; ops under the mixer's scopes, one
    of them a move under `state_io`; another family's scopes are ignored."""
    from cellbench import scopetrace
    from cellbench.metrics import gdn_rule_roofline

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
        op("paged_decode_attention.1", 10, 3, step + "attention/pallas_call:"),
        op("fusion.2", 13, 2, step + "gdn_proj/dot_general:"),
        op("fusion.3", 15, 0.5, step + "gdn_rule/mul:"),
        op("fusion.4", 15.5, 0.6, step + "state_io/gather:"),
        op("copy.5", 16.1, 0.4, step + "state_io/scatter:"),
        op("fusion.6", 30, 3, step + "gdn_proj/dot_general:"),
        op("fusion.7", 33, 4, step + "gdn_rule/triangular_solve:"),
        op("fusion.12", 37, 0.5, step + "gdn_conv/dot_general:"),
        op("fusion.11", 37.5, 1, step + "state_io/gather:"),
        op("fusion.8", 50, 3, step + "gdn_proj/dot_general:"),
        op("fusion.9", 53, 2, step + "gdn_rule/dot_general:"),
        op("fusion.10", 70, 5, step + "moe_experts/dot_general:"),
    ]}]}
    scopes = gdn_rule_roofline.GDN_SCOPES
    got = scopetrace.reduce(raw, scopes, "state_io")
    assert {k: len(v) for k, v in got["runs"].items()} == {
        "decode": 1, "chunk": 2, "fused": 0}
    assert scopetrace.median_ms(got, "decode", *scopes) == pytest.approx(3.5)
    # chunk runs: 8.5 and 5 -> 6.75; the rule's three scopes: 5.5 and 2
    assert scopetrace.median_ms(got, "chunk", *scopes) == pytest.approx(6.75)
    assert scopetrace.median_ms(
        got, "chunk", "gdn_conv", "gdn_rule", "state_io") == pytest.approx(3.75)
    assert scopetrace.median_ms(got, "fused", *scopes) is None
    assert got["move_s"] == pytest.approx(0.4 * ms)
    assert got["busy_s"] == pytest.approx(30 * ms)
    # the same walk for another list of scopes; none of them: nothing
    assert scopetrace.median_ms(
        scopetrace.reduce(raw, ("moe_experts",), "x"), "chunk", "moe_experts"
    ) == pytest.approx(0.0)
    assert scopetrace.reduce(raw, ("ssm_scan",), "state_io") is None
    assert scopetrace.median_ms(None, "chunk", "gdn_rule") is None


def test_chunk_experts_ms_reads_the_sparse_layer_of_a_chunk_run(monkeypatch):
    """`chunk_experts_ms_p50` (PR 40): per chunk run the router (with a
    kernel form's plan: the sort), the shared experts and the experts, the
    tiled kernel's call under `moe_experts` like the dense form's fusions;
    a decode run and the trace's edge runs are left out; a program without
    the scopes reads nothing."""
    from cellbench import scopetrace
    from cellbench.metrics import chunk_experts_ms_p50 as metric

    ms = 1e-3
    step = "jit(span_step_packed_impl)/jit(main)/while/body/"
    prog = "jit_span_step_packed_impl(1)"

    def op(name, start, dur, op_name):
        return (f"%{name} = f32[8]{{0}} {name.split('.')[0]}()", start * ms,
                dur * ms, op_name)

    raw = {"device": [{"name": "/device:TPU:0", "modules": [
        (prog, 0.0, 5 * ms), (prog, 10 * ms, 10 * ms), (prog, 30 * ms, 10 * ms),
        (prog, 50 * ms, 10 * ms), (prog, 70 * ms, 5 * ms),
    ], "ops": [
        op("fusion.0", 0, 5, step + "moe_experts/dot_general:"),
        op("paged_decode_attention.1", 10, 3, step + "attention/pallas_call:"),
        op("grouped_experts.2", 13, 2, step + "moe_experts/jit(grouped_experts)/pallas_call:"),
        op("sort.3", 30, 0.5, step + "moe_router/sort:"),
        op("fusion.4", 30.5, 0.25, step + "moe_shared/dot_general:"),
        op("tiled_experts.5", 31, 4, step + "moe_experts/jit(tiled_experts)/pallas_call:"),
        op("fusion.6", 35, 3, step + "gdn_rule/dot_general:"),
        op("fusion.7", 50, 1, step + "moe_router/dot_general:"),
        op("fusion.8", 51, 8, step + "moe_experts/dot_general:"),
        op("fusion.9", 70, 5, step + "moe_experts/dot_general:"),
    ]}]}
    monkeypatch.setattr(
        scopetrace, "reduced",
        lambda ctx, name, scopes, move: scopetrace.reduce(raw, scopes, move))
    assert metric.read({}) == pytest.approx((4.75 + 9.0) / 2)
    raw["device"][0]["ops"] = [
        o for o in raw["device"][0]["ops"] if "moe_" not in o[3]]
    assert metric.read({}) is None


@pytest.mark.parametrize("name", [
    "step_gdn_ms_p50", "chunk_gdn_ms_p50", "gdn_rule_roofline",
    "gdn_state_move_share", "chunk_experts_ms_p50"])
def test_a_linear_mixer_metric_reads_nothing_where_there_is_no_trace(
        tmp_path, name):
    """An untraced run, or the parent's program: None, not a made-up number."""
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "cellbench" / "metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    ctx = {"trace_dir": str(tmp_path / "trace"), "config": _published_q3n(),
           "prefill_chunk": 512, "device_kind": "TPU v5 lite"}
    assert module.read(ctx) is None
