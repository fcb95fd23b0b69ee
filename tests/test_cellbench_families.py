"""The benchmark's family files, in tier 1.

1. `cellbench/tests/test_families.py`'s identity tests (a family is a file
   found by `model_type`; the two families PR 29 moved changed no number),
   collected here because tier 1 collects `tests/` only. One of them stands
   red since PR 30 and is marked an expected failure, with its reason
   (`cellbench/tests/conftest.py`).
2. The same pins for `falcon_h1` (cellbench/families/falcon_h1.py): the plan
   at the published size, a tiny checkpoint's files, every key of the needs.
   The values were produced by this file's own code when the family was added
   (PR 30, after the review moved the fills of in_proj, the taps and D): a
   later edit that moves one has to say so here.
3. The same pins for `deepseek_v2`, and the trace readers both families
   brought, on synthetic traces and without one.

The CPU rehearsal of each family's cell through `cellbench/run.py` is
`tests/test_cell_rehearsal.py`, which takes its tiny configurations from here.
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
from cellbench.tests.test_families import *  # noqa: E402,F401,F403
from cellbench.tests.conftest import EXPECTED_FAILURES  # noqa: E402
from cellbench.tests.test_families import SEED, _sha  # noqa: E402

TINY_FALCON_H1 = {
    "model_type": "falcon_h1", "architectures": ["FalconH1ForCausalLM"],
    "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "num_hidden_layers": 2,
    "vocab_size": 512, "rms_norm_eps": 1e-05, "rope_theta": 100000000000,
    "rope_scaling": None, "hidden_act": "silu", "attention_bias": False,
    "mlp_bias": False, "projectors_bias": False, "mamba_proj_bias": False,
    "mamba_conv_bias": True, "mamba_rms_norm": True,
    "mamba_norm_before_gate": False, "mamba_use_mlp": True,
    "mamba_d_ssm": 64, "mamba_n_heads": 4, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 128, "mamba_expand": 2,
    "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
    "embedding_multiplier": 5.656854249492381,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845,
    "max_position_embeddings": 8192, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
}

FH1_PINS = {
    "plan": (
        "58cb1e1be8a8f777850a8203589510cd21c28c5e2a14e3cf9ce374e8d17f2a5b", 9, 139),
    "files": {
        "config.json": "8c61322af6fafebf3f504c60dd44acf16c19bcc696fa2b681f21f50f17f61e95",
        "model-client.safetensors": "55c9148ea551b4b3a66ba3a6939464d6d13fe33b73780d7e5e351b0ad741b25c",
        "model-layer000.safetensors": "565fcdc0aefb51fda3aa0cbb69646efb8fae5ca0f17d547366441ab76ae843b4",
        "model-layer001.safetensors": "c47ea3608b9fabc72ba9566d065202349889112e35b4706103cee255af619e19",
        "model.safetensors.index.json": "52f964ec49de8bd2404c94e0e0ec5065eb5e4cafc0ad735daee285cd48fec820",
    },
    "needs": [
        ("decode_step_needs", 2.0, 3000.0,
         {"bytes": 7114858496.0, "flops": 14354743296.0,
          "weight_bytes": 6881280000, "kv_bytes": 98336768.0,
          "state_bytes": 135200768.0}),
        ("chunk_needs", 128, 1536.0,
         {"bytes": 6978764800.0, "flops": 904023506944.0,
          "weight_bytes": 6881280000, "kv_bytes": 27262976.0,
          "state_bytes": 67600384}),
        ("ssm_scan_needs", 2.0, "decode",
         {"bytes": 136204288.0, "flops": 101318656.0}),
        ("ssm_scan_needs", 128, "chunk",
         {"bytes": 105827328, "flops": 6484393984}),
    ],
}


def _published() -> dict:
    config = json.loads(
        (ROOT / "cellbench/configs/falcon-h1-34b-span8.json").read_text())
    config.pop("cellbench")
    return config


# `cellbench/tests/test_families.py::test_unknown_model_type_names_the_missing_
# file` takes `falcon_h1` as the type no family file exists for; since PR 30
# one does, and that file may only be edited by a `benchmark` PR. The collected
# test stays under its name as an EXPECTED failure (strict: it says so the day
# it passes), and the same check with a name no family has stands below it.
test_unknown_model_type_names_the_missing_file = pytest.mark.xfail(  # noqa: F811
    strict=True, reason=EXPECTED_FAILURES[
        "test_unknown_model_type_names_the_missing_file"],
)(test_unknown_model_type_names_the_missing_file)  # noqa: F405


def test_a_model_type_no_family_has_names_the_missing_file():
    config = dict(TINY_FALCON_H1, model_type="no_such_family")
    with pytest.raises(LookupError, match=r"cellbench/families/no_such_family\.py"):
        checkpoint.tensor_plan(config)
    with pytest.raises(LookupError, match=r"cellbench/families/no_such_family\.py"):
        families.of(config)


# ------------------------------------------------------- falcon_h1's pins
def test_falcon_h1_plan_at_the_published_size():
    plan = checkpoint.tensor_plan(_published())
    listed = [[tag, [[n, list(shape), fill] for n, shape, fill in tensors]]
              for tag, tensors in plan]
    digest = _sha(json.dumps(listed).encode())
    tensors = sum(len(ts) for _, ts in plan)
    assert (digest, len(plan), tensors) == FH1_PINS["plan"]
    # one layer at the published widths: 430.1 M parameters (ISSUE 30)
    layer = sum(int(__import__("numpy").prod(shape))
                for _, shape, _ in plan[0][1])
    assert round(layer / 1e6, 1) == 430.1


def test_falcon_h1_tiny_checkpoint_files(tmp_path):
    checkpoint.write_checkpoint(tmp_path, TINY_FALCON_H1, SEED)
    got = {p.name: _sha(p.read_bytes()) for p in sorted(tmp_path.iterdir())}
    assert got == FH1_PINS["files"]


def test_falcon_h1_needs_every_key():
    family, config = families.of(_published()), _published()
    got = [
        (fn, *args, getattr(family, fn)(config, *args))
        for fn, args in (
            ("decode_step_needs", (2.0, 3000.0)),
            ("chunk_needs", (128, 1536.0)),
            ("ssm_scan_needs", (2.0, "decode")),
            ("ssm_scan_needs", (128, "chunk")),
        )
    ]
    assert repr(got) == repr(FH1_PINS["needs"])


# ------------------------------------------ the mixer's scopes in a trace
def test_ssmtrace_reduces_a_synthetic_trace_to_known_numbers():
    """`cellbench/ssmtrace.py` on a trace whose answers are worked out by
    hand: five runs of the packed program (the first and the last are the
    trace's edges and are left out), of which one executed the decode kernel;
    ops under the mixer's three scopes, one of them a move under `state_io`."""
    from cellbench import ssmtrace

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
        op("fusion.0", 0, 5, step + "mlp/dot_general:"),
        # a decode run: 2 ms projections, 0.5 ms one step, 1 ms slot traffic
        # of which 0.4 ms is a copy
        op("paged_decode_attention.1", 10, 3, step + "attention/pallas_call:"),
        op("fusion.2", 13, 2, step + "ssm_proj/dot_general:"),
        op("fusion.3", 15, 0.5, step + "ssm_scan/mul:"),
        op("fusion.4", 15.5, 0.6, step + "state_io/gather:"),
        op("copy.5", 16.1, 0.4, step + "state_io/scatter:"),
        # two chunk runs: ssm_scan 4 ms and 2 ms, ssm_proj 3 ms each, and in
        # the first 1 ms of slot traffic
        op("fusion.6", 30, 3, step + "ssm_proj/dot_general:"),
        op("fusion.7", 33, 4, step + "ssm_scan/dot_general:"),
        op("fusion.11", 37, 1, step + "state_io/gather:"),
        op("fusion.8", 50, 3, step + "ssm_proj/dot_general:"),
        op("fusion.9", 53, 2, step + "ssm_scan/dot_general:"),
        op("fusion.10", 70, 5, step + "mlp/dot_general:"),
    ]}]}
    got = ssmtrace.reduce(raw)
    assert got["runs"] == {"decode": 1, "chunk": 2, "fused": 0}
    assert got["step_ssm_ms_p50"] == pytest.approx(3.5)
    assert got["chunk_ssm_ms_p50"] == pytest.approx(6.5)  # median of 8 and 5
    assert got["chunk_ssm_scan_ms_p50"] == pytest.approx(3.0)
    # what `ssm_scan_roofline` divides by: the scan AND the slot traffic
    assert got["chunk_scan_and_state_ms_p50"] == pytest.approx(3.5)
    assert got["state_io_move_s"] == pytest.approx(0.4 * ms)
    assert got["busy_s"] == pytest.approx(29.5 * ms)
    assert got["ops_ms_mean"]["decode"][0] == ["ssm_proj: fusion", pytest.approx(2.0)]
    # a program without the scopes (the parent of the PR that brought them)
    bare = {"device": [{"name": "d", "modules": raw["device"][0]["modules"],
                        "ops": [o for o in raw["device"][0]["ops"]
                                if "ssm" not in o[3] and "state_io" not in o[3]]}]}
    assert ssmtrace.reduce(bare) is None


@pytest.mark.parametrize("name", [
    "step_ssm_ms_p50", "chunk_ssm_ms_p50", "ssm_scan_roofline",
    "state_io_move_share"])
def test_a_mixer_metric_reads_nothing_where_there_is_no_trace(tmp_path, name):
    """An untraced run, or the parent's program: None, not a made-up number."""
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "cellbench" / "metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    ctx = {"trace_dir": str(tmp_path / "trace"), "config": _published(),
           "prefill_chunk": 128, "device_kind": "TPU v5 lite"}
    assert module.read(ctx) is None


# ---------------------------------------------------- deepseek_v2's pins
# (PR 35) the plan at the published size, a tiny checkpoint's files, every
# key of the needs. The values were produced by this file's own code when
# the family was added: a later edit that moves one has to say so here.
TINY_DEEPSEEK_V2 = {
    "model_type": "deepseek_v2", "hidden_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 48,
    "kv_lora_rank": 32, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
    "v_head_dim": 32, "n_routed_experts": 4, "router_experts": 16,
    "experts_held": [4, 4], "n_group": 4, "topk_group": 2,
    "num_experts_per_tok": 3, "n_shared_experts": 2,
    "moe_intermediate_size": 64, "intermediate_size": 256,
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "num_hidden_layers": 3,
    "routed_scaling_factor": 16, "vocab_size": 512, "rms_norm_eps": 1e-06,
    "rope_theta": 10000, "topk_method": "group_limited_greedy",
    "norm_topk_prob": False, "scoring_func": "softmax",
    "max_position_embeddings": 8192, "tie_word_embeddings": False,
    "attention_bias": False, "hidden_act": "silu",
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 64, "type": "yarn"},
    "torch_dtype": "bfloat16",
}

DSV2_PINS = {
    "plan": (
        "ff573df00defc23b7455b9ecc8b8dadbd9b1655e4b6695d9ffb96c118f8c372c", 6, 307),
    "files": {
        "config.json": "eed7d68020f23677feb7b8160aaaee15e7f3a950e113540a3ab14e2b16ab817e",
        "model-client.safetensors": "d05fc500f703d51048bf0d41104e0ca37c5bc093addb6d0bdd1af7b36bcd4910",
        "model-layer000.safetensors": "426d16dfb9467636e66829417935d107c923df773fbd8287f85db612866f6af2",
        "model-layer001.safetensors": "8f6f4510810c7d22f79163eb2ed4078e38a0366fa287796057cf02bec9e04ea2",
        "model-layer002.safetensors": "648e29b95cf02d533182db4d270cb6b025927a6c602ebfabda0c6f48d602cd2a",
        "model.safetensors.index.json": "6db50bcf4249ddf72852c4950c19729152d1e20afb8ee5c55472faa9073cfb19",
    },
    "needs": [
        ("decode_step_needs", 2.0, 10000.0,
         {"bytes": 2649627903.9999995, "flops": 32646266880.0,
          "weight_bytes": 2531590143.9999995, "kv_bytes": 117996800.0}),
        ("chunk_needs", 512, 5120.0,
         {"bytes": 6787563508.03038, "flops": 5059672801280.0,
          "weight_bytes": 6028656628.03038, "kv_bytes": 748421120.0}),
        ("mla_attention_needs", 512, 5120.0, "chunk",
         {"bytes": 748421120.0, "flops": 3833258311680.0}),
        ("mla_attention_needs", 2.0, 10000.0, "decode",
         {"bytes": 117996800.0, "flops": 27855585280.0}),
    ],
}


def _published_dsv2() -> dict:
    config = json.loads(
        (ROOT / "cellbench/configs/deepseek-v2-ep8-span5.json").read_text())
    config.pop("cellbench")
    return config


def test_deepseek_v2_plan_at_the_published_size():
    import numpy as np

    plan = checkpoint.tensor_plan(_published_dsv2())
    listed = [[tag, [[n, list(shape), fill] for n, shape, fill in tensors]]
              for tag, tensors in plan]
    digest = _sha(json.dumps(listed).encode())
    assert (digest, len(plan), sum(len(ts) for _, ts in plan)
            ) == DSV2_PINS["plan"]
    size = lambda tensors: sum(  # noqa: E731
        int(np.prod(shape)) for _, shape, _ in tensors)
    # layer 0 with its dense MLP; a sparse layer with the 20 HELD experts,
    # the router over all 160 and the shared experts; the span (ISSUE 35)
    assert [size(ts) for _, ts in plan[:2]] == [337981440, 669102080]
    assert sum(size(ts) for _, ts in plan[:-1]) == 3014389760
    names = [n for n, _, _ in plan[1][1]]
    assert "model.layers.1.mlp.experts.19.down_proj.weight" in names
    assert "model.layers.1.mlp.experts.20.down_proj.weight" not in names
    assert dict((n, s) for n, s, _ in plan[1][1])[
        "model.layers.1.mlp.gate.weight"] == (160, 5120)


def test_deepseek_v2_tiny_checkpoint_files(tmp_path):
    checkpoint.write_checkpoint(tmp_path, TINY_DEEPSEEK_V2, SEED)
    got = {p.name: _sha(p.read_bytes()) for p in sorted(tmp_path.iterdir())}
    assert got == DSV2_PINS["files"]


def test_deepseek_v2_needs_every_key():
    family, config = families.of(_published_dsv2()), _published_dsv2()
    got = [
        (fn, *args, getattr(family, fn)(config, *args))
        for fn, args in (
            ("decode_step_needs", (2.0, 10000.0)),
            ("chunk_needs", (512, 5120.0)),
            ("mla_attention_needs", (512, 5120.0, "chunk")),
            ("mla_attention_needs", (2.0, 10000.0, "decode")),
        )
    ]
    assert repr(got) == repr(DSV2_PINS["needs"])
    # a cached token is 1,152 B a layer; 20 held experts are all reached by
    # a 512-row chunk and about one and a half by two decode rows
    assert family.latent_row_bytes(config) == 1152
    assert family._expert_reach(config, 512)[1] == pytest.approx(20, abs=1e-6)
    assert family._expert_reach(config, 2)[1] == pytest.approx(1.47, abs=0.01)


def test_a_latent_metric_reads_nothing_where_there_is_no_trace(tmp_path):
    """The new readers on a run without a trace (and so on the parent of the
    PR that brought the scopes): None, no exception."""
    from cellbench import mlatrace
    from cellbench.metrics import (  # noqa: F401
        chunk_mla_ms_p50,
        chunk_moe_ms_p50,
        latent_io_move_share,
        step_mla_ms_p50,
    )

    from cellbench.metrics import held_experts_hit_share

    ctx = {"work_dir": str(tmp_path), "trace": None,
           "config": {"experts_held": [0, 20]}, "prefill_chunk": 512}
    for module in (chunk_mla_ms_p50, step_mla_ms_p50, chunk_moe_ms_p50,
                   latent_io_move_share, held_experts_hit_share):
        assert module.read(dict(ctx)) is None
    assert mlatrace.reduce({"device": [], "host": []}) is None
    # ops of another family (no latent scope): nothing to read either
    ops = [("fusion.1", 0.0, 1e-3, "jit(f)/while/body/moe_experts/dot")]
    mods = [("jit_span_step_packed_impl(1)", 0.0, 1e-3)]
    assert mlatrace.reduce(
        {"device": [{"name": "/device:TPU:0", "ops": ops, "modules": mods}],
         "host": []}) is None


def test_held_experts_hit_share_reads_the_full_chunks_of_the_reach_spans():
    """`bbtpu.moe_reach` spans as the program stamps them (one a step; per
    sparse layer `;`-separated lists) -> the median over full chunks' sparse layers
    of distinct held experts reached, as a share of the experts held; a
    decode step's and a tail's spans are left out."""
    from cellbench import mlatrace
    from cellbench.metrics import held_experts_hit_share

    def span(rows, hit, kind="chunk"):
        return ("bbtpu.moe_reach", 0.0, 0.0, {
            "kind": kind, "rows": rows, "held_hit": hit,
            "routed_pairs_here": "9;9;9;9",
            "rows_with_held_expert": "7;7;7;7"})

    host = [{"line": 1, "events": [
        span(512, "20;19;18;20"), span(2, "1;2;1;1", "decode"),
        ("bbtpu.task", 0, 1, {}), span(137, "12;11;13;12")]}, {"line": 2, "events": [
            span(512, "17;20;20;16")]}]
    reach = mlatrace.reach_spans(host)
    assert [r["rows"] for r in reach] == [512, 2, 137, 512]
    assert [r["kind"] for r in reach] == ["chunk", "decode", "chunk", "chunk"]
    got = {"reach": reach}
    assert len(mlatrace.traced_steps(got, "chunk", spans="reach")) == 3
    assert len(mlatrace.traced_steps(got, "chunk", 512, "reach")) == 2
    # the steps as they were DISPATCHED are another span (`bbtpu.step`)
    host[0]["events"].append(
        ("bbtpu.step", 0.0, 0.0, {"kind": "decode", "rows": 2,
                                  "context": 9000}))
    host[0]["events"].append(
        ("bbtpu.step", 0.0, 0.0, {"kind": "chunk", "rows": 512}))
    assert mlatrace.step_spans(host) == [
        {"kind": "decode", "rows": 2, "context": 9000},
        {"kind": "chunk", "rows": 512, "context": 0}]
    assert reach[0]["held_hit"] == [20, 19, 18, 20]
    assert reach[1]["rows_with_held_expert"] == [7, 7, 7, 7]
    ctx = {"_mlatrace": got, "prefill_chunk": 512,
           "config": {"experts_held": [0, 20]}}
    # full chunks: 20 19 18 20 17 20 20 16 -> median 19.5 of 20
    assert held_experts_hit_share.read(ctx) == pytest.approx(97.5)
    assert held_experts_hit_share.read(dict(ctx, config={})) is None
    assert held_experts_hit_share.read(
        dict(ctx, _mlatrace={"reach": reach[1:3]})) is None


def test_the_core_roofline_takes_the_traced_steps_own_context():
    """The core's time follows the context, and a 5 s trace holds the chunks
    of four requests: where the program stamped its steps, the needs are
    taken at THEIR median context (a traced sample 1,000 tokens under the
    window's mean read 100.5 on the chip before this, and spans stamped when
    the counters were read, up to a prefill late, 68.5); with no span the
    window's means stand in."""
    from cellbench import families, mlatrace, roofline

    config = json.loads((ROOT / "cellbench" / "configs"
                         / "deepseek-v2-ep8-span5.json").read_text())
    config.pop("cellbench")
    needs = families.of(config).mla_attention_needs

    def share(context, ms, kind="chunk", rows=512):
        least, _ = roofline.least_seconds(
            needs(config, rows, context, kind), "TPU v5 lite")
        return 100.0 * least / (ms * 1e-3)

    def step(kind, rows, context):
        return {"kind": kind, "rows": rows, "context": context}

    steps = [step("chunk", 512, c) for c in (1024, 4096, 3072)] + [
        step("chunk", 137, 11776), step("decode", 2, 9000),
        step("decode", 3, 11000), step("fused", 513, 8000)]
    ctx = {"_mlatrace": {"chunk_core_ms_p50": 16.0, "step_core_ms_p50": 0.7,
                         "steps": steps},
           "config": config, "prefill_chunk": 512,
           "device_kind": "TPU v5 lite"}
    got = mlatrace.core_roofline(ctx, "chunk", 512, 5200.0)
    assert got == pytest.approx(share(3072, 16.0))  # the full chunks' median
    assert got < share(5200.0, 16.0)
    assert ctx["notes"]["mla_chunk_traced_steps"] == [3, 512, 3072]
    got = mlatrace.core_roofline(ctx, "decode", 2.0, 5000.0)
    assert got == pytest.approx(share(10000, 0.7, "decode", 2.5))
    bare = dict(ctx, _mlatrace={"chunk_core_ms_p50": 16.0, "steps": []})
    assert mlatrace.core_roofline(bare, "chunk", 512, 5200.0) == (
        pytest.approx(share(5200.0, 16.0)))
