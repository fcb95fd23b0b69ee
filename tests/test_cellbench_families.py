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
3. A CPU rehearsal of the new cell's path through `cellbench/run.py` on a tiny
   falcon_h1 preset, added to a copy of the benchmark by files only: `correct`
   true, and false when the server holds int8 weights (the control).
"""

from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from cellbench import checkpoint, families  # noqa: E402
from cellbench.tests.test_families import *  # noqa: E402,F401,F403
from cellbench.tests.conftest import EXPECTED_FAILURES  # noqa: E402
from cellbench.tests.test_families import SEED, _sha  # noqa: E402

TREE = ROOT / ".cache" / "cellbench_rehearsal_falcon_h1"

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


# ------------------------------------------------- the cell's CPU rehearsal
@pytest.fixture(scope="module")
def tree() -> pathlib.Path:
    """A copy of the benchmark with a tiny falcon_h1 configuration and cell
    ADDED (the family file is already there), no file edited."""
    shutil.rmtree(TREE, ignore_errors=True)
    TREE.mkdir(parents=True)
    shutil.copytree(ROOT / "cellbench", TREE / "cellbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (TREE / "bloombee_tpu").symlink_to(ROOT / "bloombee_tpu")
    cb = TREE / "cellbench"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (cb / "configs" / "tiny-falcon-h1.json").write_text(json.dumps(dict(
        TINY_FALCON_H1, cellbench={
            "source": "none: a rehearsal preset", "uid": "tiny-falcon-h1",
            "reduced": {"everything": "tiny"},
            # a float32 server: at these widths bfloat16's own rounding
            # (0.003) is twenty times the int8-weight reference's distance
            # from the reference (0.00016), and the projection on it is noise
            "server_flags": ["--mixed-batch", "--prefill-chunk", "128",
                             "--dtype", "float32"],
            # sound 2.2e-7; padding fed to the state 2.0e-5 (at these widths
            # the state carries little); an int8-weight server 1.6e-4
            "prefill_chunk": 128, "logit_error_limit": 2e-6,
            "int8_projection_limit": 0.5})))
    (cb / "traffic" / "tiny-doc.json").write_text(json.dumps({
        "loop": "closed", "sessions": 3, "stagger_s": 0.1,
        "prompt_tokens": [300, 136, 261, 200], "new_tokens": [5, 4, 6, 4],
        "judge": {"requests": 2, "new_tokens": 4}}))
    (cb / "cells" / "tiny-falcon-h1-doc.json").write_text('{"num_pages": 128}')
    bench["configs"].append(
        {"name": "tiny-falcon-h1", "source": "none", "reduced": [],
         "file": "cellbench/configs/tiny-falcon-h1.json", "why": "rehearsal"})
    bench["workloads"].append(
        {"name": "tiny-falcon-h1-doc", "config": "tiny-falcon-h1",
         "traffic": "tiny-doc", "chips": 1, "why": "rehearsal"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "falconh1-longdoc" in metric.get("workloads", ()):
            metric["workloads"].append("tiny-falcon-h1-doc")
    (TREE / "BENCHMARK.json").write_text(json.dumps(bench))
    return TREE


def _run(tree: pathlib.Path, *argv: str):
    env = dict(os.environ, CELLBENCH_REHEARSAL="1", JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("XLA_FLAGS", None)  # conftest's 8 virtual devices: one is the cell's
    proc = subprocess.run(
        [sys.executable, "cellbench/run.py", *argv], cwd=tree, env=env,
        capture_output=True, text=True, timeout=900)
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1]) if lines else None
    return proc.returncode, last, proc.stdout + proc.stderr


def _compared(out: str) -> dict:
    """Each number the run compared, beside its limit."""
    line = next(ln for ln in out.splitlines() if '"phase": "correctness"' in ln)
    return json.loads(line)["compared"]


def test_falcon_h1_cell_rehearsal_is_correct(tree):
    rc, last, out = _run(tree, "--workload", "tiny-falcon-h1-doc", "--seed",
                         str(2**31 + 30), "--seconds", "4", "--trace", "1")
    assert last is not None and rc == 0, out[-3000:]
    assert last["correct"] is True and last["failed"] == 0, out[-3000:]
    assert last["attempted"] >= 3
    # a CPU run reports no device metric under a device metric's name
    for name in ("step_ssm_ms_p50", "chunk_ssm_ms_p50", "ssm_scan_roofline",
                 "state_io_move_share", "device_idle_share"):
        assert name not in last["metrics"]


def test_falcon_h1_cell_rehearsal_int8_server_is_not_correct(tree):
    rc, last, out = _run(
        tree, "--workload", "tiny-falcon-h1-doc", "--seed", "17", "--seconds",
        "2", "--trace", "0", "--server-arg=--weight-quant",
        "--server-arg=int8")
    assert last is not None and last["correct"] is False, out[-3000:]
    assert rc != 0
    got = _compared(out)
    assert got["int8_projection_median"][0] == pytest.approx(1.0, abs=0.05)


def test_falcon_h1_cell_rehearsal_sees_padding_fed_to_the_state(tree, tmp_path):
    """The timed path BROKEN underneath the harness: in a copy of the program
    a chunk's bucket tail advances the recurrent state (the mask on `dt`
    taken off: `scripts/plant_state_fault.py`, which planted it on the chip
    too). The served tokens still come, no request fails, and `correct` is
    false by the logit error: the judge sees the state."""
    broken = tmp_path / "tree"
    shutil.copytree(tree, broken, symlinks=True)
    (broken / "bloombee_tpu").unlink()
    shutil.copytree(ROOT / "bloombee_tpu", broken / "bloombee_tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = importlib.util.spec_from_file_location(
        "plant_state_fault", ROOT / "scripts" / "plant_state_fault.py")
    planter = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(planter)
    planter.plant(broken, "pad")  # the fault the chip run planted too
    rc, last, out = _run(broken, "--workload", "tiny-falcon-h1-doc", "--seed",
                         "23", "--seconds", "2", "--trace", "0")
    assert last is not None and last["correct"] is False, out[-3000:]
    assert last["failed"] == 0 and rc != 0
    err, limit = _compared(out)["logit_err_median"]
    assert err > 5 * limit, (err, limit)


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
