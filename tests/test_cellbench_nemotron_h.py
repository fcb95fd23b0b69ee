"""The benchmark's `nemotron_h` family file, in tier 1.

The configuration's file against the catalog row, the plan at the published
size, a tiny checkpoint's files, every key of the needs, the new reader on a
synthetic trace. The pinned values were produced by this file's own code
when the family was added (PR 54): a later edit that moves one has to say so
here. The CPU rehearsal of the cell `nemotron3nano-longctx` is a row of
`tests/test_cell_rehearsal.py`, which takes its tiny configuration from
here.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from cellbench import checkpoint, families, roofline  # noqa: E402
from cellbench.tests.test_families import SEED, _sha  # noqa: E402

CONFIG_FILE = ROOT / "cellbench/configs/nemotron3-nano-30b-ep2-span14.json"
CELL = "nemotron3nano-longctx"
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

# 13 layers MEMEM*EMEMEM* (the published pattern's first two periods, of 6
# and 7 layers); 8 router outputs of which experts 2-5 are held; an expert
# width of 72, which the loader pads to 128
TINY_NEMOTRON_H = {
    "model_type": "nemotron_h", "hidden_size": 128, "intermediate_size": 72,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 32, "expand": 2,
    "hybrid_override_pattern": "MEMEM*EMEMEM*", "num_hidden_layers": 13,
    "moe_intermediate_size": 72, "moe_shared_expert_intermediate_size": 144,
    "n_shared_experts": 1, "n_routed_experts": 4, "router_experts": 8,
    "experts_held": [2, 4], "num_experts_per_tok": 3, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "layer_norm_epsilon": 1e-05, "norm_eps": 1e-05, "vocab_size": 512,
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
    "use_conv_bias": True, "use_bias": False, "mamba_proj_bias": False,
    "attention_bias": False, "mlp_bias": False, "tie_word_embeddings": False,
    "max_position_embeddings": 8192, "rope_theta": 10000,
    "partial_rotary_factor": 1, "sliding_window": None,
    "torch_dtype": "bfloat16",
}


def _config() -> dict:
    config = json.loads(CONFIG_FILE.read_text())
    config.pop("cellbench")
    return config


def test_the_configuration_keeps_every_published_number():
    """The catalog row's `config`, key for key: only the keys `reduced`
    names differ, and no width is among them."""
    whole = json.loads(CONFIG_FILE.read_text())
    cb = whole.pop("cellbench")
    assert set(cb["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"}
    assert cb["published"] == {
        "num_hidden_layers": 52, "hybrid_override_pattern": PUBLISHED_PATTERN,
        "n_routed_experts": 128, "vocab_size": 131072}
    assert whole["hybrid_override_pattern"] == PUBLISHED_PATTERN[13:27]
    assert whole["num_hidden_layers"] == 14
    assert (whole["hidden_size"], whole["moe_intermediate_size"],
            whole["moe_shared_expert_intermediate_size"],
            whole["mamba_num_heads"], whole["mamba_head_dim"],
            whole["ssm_state_size"], whole["n_groups"], whole["conv_kernel"],
            whole["num_attention_heads"], whole["num_key_value_heads"],
            whole["head_dim"], whole["num_experts_per_tok"],
            whole["routed_scaling_factor"], whole["chunk_size"]) == (
        2688, 1856, 3712, 64, 64, 128, 8, 4, 32, 2, 128, 6, 2.5, 128)
    assert (whole["router_experts"], whole["experts_held"]) == (128, [0, 64])
    assert {"deployment", "assumed", "arithmetic"} <= set(cb)
    assert "8 v5e chips" in cb["deployment"] and "TWO chips" in cb["deployment"]
    catalog = pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(r for r in map(json.loads, catalog.read_text().splitlines())
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert cb["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if whole.get(k) != v
                } == set(cb["reduced"])
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == "nemotron3-nano-30b-ep2-span14")
    assert sorted(entry["reduced"]) == sorted(cb["reduced"])
    assert entry["source"] == cb["source"]
    cell = json.loads(
        (ROOT / "cellbench/cells" / f"{CELL}.json").read_text())
    assert cell["num_pages"] == 5376
    assert cb["server_flags"] == [
        "--mixed-batch", "--prefill-chunk", "512", "--experts", "0:64"]


def test_the_plan_at_the_published_size():
    import numpy as np

    plan = checkpoint.tensor_plan(_config())
    size = lambda tensors: sum(  # noqa: E731
        int(np.prod(shape)) for _, shape, _ in tensors)
    assert len(plan) == 15
    # E (64 held experts of two matrices, the shared expert, the router over
    # all 128), M, and after three such pairs a * layer (ISSUE 54: 658.9 M,
    # 38.7 M, 23.4 M; the span 4,232.6 M parameters, 8.47 GB)
    assert [round(size(ts) / 1e6, 1) for _, ts in plan[:2] + plan[6:7]] == [
        658.9, 38.7, 23.4]
    assert sum(size(ts) for _, ts in plan[:-1]) == 4232579712
    names = dict((n, s) for n, s, _ in plan[0][1])
    m = "backbone.layers.0.mixer."
    assert names[m + "experts.63.up_proj.weight"] == (1856, 2688)
    assert names[m + "experts.63.down_proj.weight"] == (2688, 1856)
    assert m + "experts.64.up_proj.weight" not in names
    assert not any("gate_proj" in n for n in names)  # two matrices an expert
    assert names[m + "gate.weight"] == (128, 2688)
    assert names[m + "gate.e_score_correction_bias"] == (128,)
    assert names[m + "shared_experts.up_proj.weight"] == (3712, 2688)
    names = dict((n, s) for n, s, _ in plan[1][1])
    m = "backbone.layers.1.mixer."
    assert names[m + "in_proj.weight"] == (4096 + 6144 + 64, 2688)
    assert names[m + "conv1d.weight"] == (6144, 1, 4)
    assert names[m + "A_log"] == (64,) and names[m + "norm.weight"] == (4096,)
    assert names[m + "out_proj.weight"] == (2688, 4096)
    names = dict((n, s) for n, s, _ in plan[6][1])
    m = "backbone.layers.6.mixer."
    assert names[m + "q_proj.weight"] == (32 * 128, 2688)
    assert names[m + "k_proj.weight"] == (2 * 128, 2688)
    assert set(names) == {
        "backbone.layers.6.norm.weight", m + "q_proj.weight",
        m + "k_proj.weight", m + "v_proj.weight", m + "o_proj.weight"}
    assert dict((n, s) for n, s, _ in plan[-1][1]) == {
        "backbone.embeddings.weight": (16384, 2688),
        "backbone.norm_f.weight": (2688,), "lm_head.weight": (16384, 2688)}


def test_a_tiny_checkpoint_is_what_its_seed_says(tmp_path):
    """The same seed writes the same files, and the reference reads a layer's
    kind off its leaves."""
    from cellbench import reference

    checkpoint.write_checkpoint(tmp_path / "a", TINY_NEMOTRON_H, SEED)
    checkpoint.write_checkpoint(tmp_path / "b", TINY_NEMOTRON_H, SEED)
    a, b = ({p.name: _sha(p.read_bytes()) for p in sorted(d.iterdir())}
            for d in (tmp_path / "a", tmp_path / "b"))
    assert a == b and len(a) == 13 + 1 + 2
    kinds = []
    for layer in range(13):
        p = reference.layer_params(tmp_path / "a", TINY_NEMOTRON_H, layer)
        kinds.append("M" if "in" in p else "E" if "router" in p else "*")
        assert "ln" in p and ("q" in p) == (kinds[-1] == "*")
    assert "".join(kinds) == "MEMEM*EMEMEM*"


NEEDS = [
    ("decode_step_needs", (2.0, 10000.0),
     {"bytes": 1595722752.0, "flops": 3015507968.0,
      "weight_bytes": 1503237120.0, "kv_bytes": 40968192.0,
      "state_bytes": 51216384.0}),
    ("chunk_needs", (512, 5120.0),
     {"bytes": 8579923967.838144, "flops": 694375415808.0,
      "weight_bytes": 8464662527.838144, "kv_bytes": 12582912.0,
      "state_bytes": 25608192}),
    ("experts_needs", (512, "chunk"),
     {"bytes": 7968522239.838144, "flops": 306519736320.0}),
    ("ssm_scan_needs", (512, "chunk"),
     {"bytes": 152203776, "flops": 9814671360}),
]


def test_the_needs_count_the_published_width_and_each_kind_once():
    family, config = families.of(_config()), _config()
    got = [(fn, args, getattr(family, fn)(config, *args))
           for fn, args, _ in NEEDS]
    assert repr(got) == repr(NEEDS)
    # a cached token is 1,024 B a * layer, a session's state 2.13 MB an M
    # layer; a 512-row chunk reaches all 64 held experts, 3 held pairs a row
    assert family._kv_row_bytes(config) == 2 * 2 * 128 * 2
    assert family._state_bytes(config) == 64 * 64 * 128 * 4 + 3 * 6144 * 2
    assert family._expert_reach(config, 512) == pytest.approx(
        (3.0, 64), abs=0.01)
    assert family._kinds(config).count("mamba") == 6
    # the experts' bytes are the PUBLISHED width's: 64 x 2 x 2688 x 1856 x 2
    # a layer, not the 1920 columns the loader stores
    experts = family.experts_needs(config, 512, "chunk")
    per_layer = experts["bytes"] / 6
    assert per_layer == pytest.approx(
        (64 * 2 * 2688 * 1856 + 2 * 2688 * 3712) * 2 + 4 * 512 * 2688 * 2,
        rel=1e-6)
    # memory-bound at the chip's ridge: 24 rows an expert
    least, bound = roofline.least_seconds(experts, "TPU v5 lite")
    assert bound == "memory" and 0.009 < least < 0.010


def test_chunk_experts_roofline_reads_a_synthetic_trace(monkeypatch):
    """The new metric on a trace whose answer is worked out by hand: four
    runs of the packed program (the first and the last are the trace's
    edges); the chunk runs' `moe_shared` + `moe_experts` time is 12 and 14
    ms, the router's is not counted; a family without `experts_needs` and a
    program without the scopes read nothing."""
    from cellbench import scopetrace
    from cellbench.metrics import chunk_experts_ms_p50, chunk_experts_roofline

    ms = 1e-3
    step = "jit(span_step_packed_impl)/jit(main)/while/body/cond/branch_1_fun/"

    def op(name, start, dur, op_name):
        return (f"%{name} = f32[8]{{0}} {name.split('.')[0]}()", start * ms,
                dur * ms, op_name)

    prog = "jit_span_step_packed_impl(1)"
    raw = {"device": [{"name": "/device:TPU:0", "modules": [
        (prog, 0.0, 5 * ms), (prog, 10 * ms, 20 * ms),
        (prog, 40 * ms, 20 * ms), (prog, 70 * ms, 5 * ms),
    ], "ops": [
        op("fusion.0", 0, 5, step + "moe_experts/dot_general:"),
        op("fusion.1", 10, 1, step + "moe_router/dot_general:"),
        op("fusion.2", 11, 2, step + "moe_shared/dot_general:"),
        op("tiled_experts.3", 13, 10, step + "moe_experts/pallas_call:"),
        op("fusion.4", 23, 3, step + "ssm_scan/dot_general:"),
        op("fusion.5", 40, 1, step + "moe_router/dot_general:"),
        op("fusion.6", 41, 2, step + "moe_shared/dot_general:"),
        op("tiled_experts.7", 43, 12, step + "moe_experts/pallas_call:"),
        op("fusion.8", 70, 5, step + "moe_experts/dot_general:"),
    ]}]}
    monkeypatch.setattr(
        scopetrace, "reduced",
        lambda ctx, name, scopes, move: scopetrace.reduce(raw, scopes, move))
    ctx = {"config": _config(), "prefill_chunk": 512,
           "device_kind": "TPU v5 lite"}
    assert chunk_experts_ms_p50.read(dict(ctx)) == pytest.approx(14.0)
    family = families.of(ctx["config"])
    least, _ = roofline.least_seconds(
        family.experts_needs(ctx["config"], 512, "chunk"), "TPU v5 lite")
    got = chunk_experts_roofline.read(dict(ctx))
    assert got == pytest.approx(100 * least / 13e-3) and got < 100
    kimi = json.loads((ROOT / "cellbench/configs/"
                       "kimi-linear-48b-ep4-span8.json").read_text())
    assert chunk_experts_roofline.read(dict(ctx, config=kimi)) is None
    raw["device"][0]["ops"] = [
        o for o in raw["device"][0]["ops"] if "moe_" not in o[3]]
    assert chunk_experts_roofline.read(dict(ctx)) is None


def test_chunk_experts_roofline_reads_nothing_where_there_is_no_trace(tmp_path):
    """An untraced run, or the parent's program: None, not a made-up number."""
    spec = importlib.util.spec_from_file_location(
        "chunk_experts_roofline",
        ROOT / "cellbench" / "metrics" / "chunk_experts_roofline.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    ctx = {"trace_dir": str(tmp_path / "trace"), "config": _config(),
           "prefill_chunk": 512, "device_kind": "TPU v5 lite"}
    assert module.read(ctx) is None


def test_the_new_cell_joins_the_metrics_it_reports_and_adds_one():
    """BENCHMARK.json: the cell is an ADDITION (the ninth one-chip cell),
    every list it joined is one ISSUE 54 names or one every cell is on, and
    `chunk_experts_roofline` lists it alone."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert [w["name"] for w in bench["workloads"]][8] == CELL
    assert len(bench["workloads"]) == len(bench["configs"]) >= 9
    assert all(w["chips"] == 1 for w in bench["workloads"])
    cell = bench["workloads"][8]
    assert (cell["traffic"], cell["config"]) == (
        "longctx", "nemotron3-nano-30b-ep2-span14")
    joined = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"tokens_per_s", "gap_long_ms_p50", "batch_width_mean",
            "step_roofline", "chunk_roofline", "step_ssm_ms_p50",
            "chunk_ssm_ms_p50", "ssm_scan_roofline", "state_io_move_share",
            "chunk_experts_ms_p50", "chunk_router_ms_p50",
            "router_bias_moved_share", "held_experts_reached_share",
            "chunk_experts_roofline", "page_write_share"} <= joined
    assert "gap_ms_p50" not in joined
    # `held_experts_hit_share` reads through latent attention's reducer and
    # finds nothing beside any other attention: the cell reports the reach
    # through `held_experts_reached_share`, as trinity-longctx does
    assert "held_experts_hit_share" not in joined
    metric = next(m for m in bench["per_layer"]
                  if m["name"] == "chunk_experts_roofline")
    assert metric == {
        "name": "chunk_experts_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "tokens_per_s", "workloads": [CELL]}
    assert (ROOT / "cellbench/metrics/chunk_experts_roofline.py").exists()
