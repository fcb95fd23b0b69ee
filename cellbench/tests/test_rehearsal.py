"""CPU rehearsal of the benchmark (tiny presets, Pallas in interpret mode).

    JAX_PLATFORMS=cpu python -m pytest cellbench/tests/test_rehearsal.py -q

It is the benchmark's own test, so it lives here and not under tests/. It
builds a copy of the benchmark in a git-ignored directory and ADDS files to it
-- three tiny configurations, two tiny traffic mixes, three cells, a per-layer
metric and a FAMILY FILE (`families/falcon.py`, from tests/family_falcon.py:
a family the program serves and the harness has no file for) -- without
editing a file that is there: what a later PR has to be able to do. Then: the
command runs, its last line has exactly the contract's keys, the added metric
is found by name, the added family's cell is `correct`, and a server in a
lower precision than the configuration states (the control, --weight-quant
int8) makes `correct` false in a built-in family's cell and in the added
one's. No time or rate read here is a device number.
"""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
TREE = ROOT / ".cache" / "cellbench_rehearsal"

TINY_DENSE = {
    "model_type": "mistral", "hidden_size": 256, "intermediate_size": 512,
    "num_attention_heads": 8, "num_key_value_heads": 4,
    "num_hidden_layers": 2, "vocab_size": 1024, "rms_norm_eps": 1e-05,
    "rope_theta": 10000.0, "sliding_window": 4096,
    "max_position_embeddings": 8192, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
}
TINY_MOE = {
    "model_type": "qwen3_moe", "hidden_size": 128, "intermediate_size": 256,
    "moe_intermediate_size": 64, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "head_dim": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "vocab_size": 512, "rms_norm_eps": 1e-06,
    "rope_theta": 1000000.0, "max_position_embeddings": 8192,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
}
# the falcon-7b shape: one key/value head, parallel residual, tied head
TINY_FALCON = {
    "model_type": "falcon", "architectures": ["FalconForCausalLM"],
    "hidden_size": 256, "num_attention_heads": 8, "num_hidden_layers": 2,
    "vocab_size": 1024, "layer_norm_epsilon": 1e-05, "rope_theta": 10000.0,
    "multi_query": True, "parallel_attn": True, "alibi": False, "bias": False,
    "new_decoder_architecture": False, "max_position_embeddings": 8192,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16",
}


def _harness(uid: str, limit: float) -> dict:
    return {"source": "none: a rehearsal preset, not a model anyone serves",
            "reduced": {"everything": "tiny"}, "uid": uid,
            "server_flags": ["--mixed-batch", "--prefill-chunk", "128"],
            "prefill_chunk": 128, "logit_error_limit": limit,
            "int8_projection_limit": 0.5}


@pytest.fixture(scope="module")
def tree() -> pathlib.Path:
    shutil.rmtree(TREE, ignore_errors=True)
    TREE.mkdir(parents=True)
    shutil.copytree(ROOT / "cellbench", TREE / "cellbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (TREE / "bloombee_tpu").symlink_to(ROOT / "bloombee_tpu")
    before = {p: p.read_bytes() for p in (TREE / "cellbench").rglob("*")
              if p.is_file()}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cb = TREE / "cellbench"
    # --- added files only -------------------------------------------------
    (cb / "configs" / "tiny-dense.json").write_text(json.dumps(
        dict(TINY_DENSE, cellbench=_harness("tiny-dense", 0.05))))
    (cb / "configs" / "tiny-moe.json").write_text(json.dumps(
        dict(TINY_MOE, cellbench=_harness("tiny-moe", 0.05))))
    (cb / "configs" / "tiny-falcon.json").write_text(json.dumps(
        dict(TINY_FALCON, cellbench=_harness("tiny-falcon", 0.05))))
    shutil.copy(cb / "tests" / "family_falcon.py", cb / "families" / "falcon.py")
    (cb / "traffic" / "tiny-chat.json").write_text(json.dumps({
        "loop": "closed", "sessions": 3, "stagger_s": 0.1,
        "prompt_tokens": [72, 136, 100, 200], "new_tokens": [5, 4, 6, 4],
        "judge": {"requests": 2, "new_tokens": 4}}))
    (cb / "traffic" / "tiny-long.json").write_text(json.dumps({
        "loop": "closed", "sessions": 2, "stagger_s": 0.1,
        "prompt_tokens": [600, 520], "new_tokens": [4, 5],
        "judge": {"requests": 1, "new_tokens": 4}}))
    for cell in ("tiny-moe-chat", "tiny-falcon-chat"):
        (cb / "cells" / f"{cell}.json").write_text('{"num_pages": 128}')
    (cb / "metrics" / "requests_in_window.py").write_text(
        '"""Added by the rehearsal: requests due inside the window."""\n\n\n'
        "def read(ctx):\n"
        '    return float(sum(0.0 <= r["due"] < ctx["window_s"]\n'
        '                     for r in ctx["records"]))\n')
    bench["configs"] += [
        {"name": "tiny-dense", "source": "none", "reduced": [],
         "file": "cellbench/configs/tiny-dense.json", "why": "rehearsal"},
        {"name": "tiny-moe", "source": "none", "reduced": [],
         "file": "cellbench/configs/tiny-moe.json", "why": "rehearsal"},
        {"name": "tiny-falcon", "source": "none", "reduced": [],
         "file": "cellbench/configs/tiny-falcon.json", "why": "rehearsal"}]
    bench["workloads"] += [
        {"name": "tiny-moe-chat", "config": "tiny-moe",
         "traffic": "tiny-chat", "chips": 1, "why": "rehearsal"},
        {"name": "tiny-dense-long", "config": "tiny-dense",
         "traffic": "tiny-long", "chips": 1, "why": "rehearsal"},
        {"name": "tiny-falcon-chat", "config": "tiny-falcon",
         "traffic": "tiny-chat", "chips": 1, "why": "rehearsal"}]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] += ["tiny-moe-chat", "tiny-dense-long",
                                    "tiny-falcon-chat"]
    bench["per_layer"].append(
        {"name": "requests_in_window", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "client",
         "moves": "tokens_per_s", "workloads": ["tiny-moe-chat"]})
    (TREE / "BENCHMARK.json").write_text(json.dumps(bench))
    # nothing that was there has been edited
    assert all(p.read_bytes() == data for p, data in before.items())
    return TREE


def _run(tree: pathlib.Path, *argv: str) -> tuple[int, dict | None, str]:
    env = dict(os.environ, CELLBENCH_REHEARSAL="1", JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "cellbench/run.py", *argv], cwd=tree, env=env,
        capture_output=True, text=True, timeout=900)
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1]) if lines else None
    return proc.returncode, last, proc.stdout + proc.stderr


CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_chat_cell_runs_and_added_files_are_found(tree):
    rc, last, out = _run(tree, "--workload", "tiny-moe-chat", "--seed",
                         str(2**31 + 11), "--seconds", "4", "--trace", "1")
    assert last is not None and set(last) == CONTRACT_KEYS, out[-3000:]
    assert rc == 0 and last["correct"] is True, out[-3000:]
    assert last["failed"] == 0 and last["attempted"] >= 3
    assert last["metrics"]["requests_in_window"]["value"] == last["attempted"]
    assert last["metrics"]["compiles_in_window"]["value"] >= 0
    # a CPU run reports no device metric under a device metric's name
    assert "device_idle_share" not in last["metrics"]
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}


def test_longdoc_cell_end_to_end_line(tree):
    rc, last, out = _run(tree, "--workload", "tiny-dense-long", "--seed", "5",
                         "--seconds", "4", "--trace", "0")
    assert rc == 0 and set(last) == CONTRACT_KEYS, out[-3000:]
    assert {"gap_ms_p50", "tokens_per_s", "setup_s"} <= set(last["metrics"])
    assert "device_idle_share" not in last["metrics"]  # --trace 0
    for metric in last["metrics"].values():
        assert metric["value"] > 0


def test_lower_precision_server_is_not_correct(tree):
    """The control: the program's own int8 weight path in the server's
    place. The comparison has to come out as not correct."""
    rc, last, out = _run(
        tree, "--workload", "tiny-moe-chat", "--seed", "7", "--seconds", "2",
        "--trace", "0", "--server-arg=--weight-quant", "--server-arg=int8")
    assert last is not None and last["correct"] is False, out[-3000:]
    assert rc != 0


def test_added_family_serves_and_is_correct(tree):
    """A third family, added by files only: other tensor names, fills from a
    range, a tied head; through the same run.py as the built-in two."""
    assert not (ROOT / "cellbench" / "families" / "falcon.py").exists()
    rc, last, out = _run(tree, "--workload", "tiny-falcon-chat", "--seed",
                         str(2**31 + 29), "--seconds", "4", "--trace", "1")
    assert last is not None and set(last) == CONTRACT_KEYS, out[-3000:]
    assert rc == 0 and last["correct"] is True, out[-3000:]
    assert last["failed"] == 0 and last["attempted"] >= 3


def test_added_family_lower_precision_server_is_not_correct(tree):
    rc, last, out = _run(
        tree, "--workload", "tiny-falcon-chat", "--seed", "13", "--seconds",
        "2", "--trace", "0", "--server-arg=--weight-quant",
        "--server-arg=int8")
    assert last is not None and last["correct"] is False, out[-3000:]
    assert rc != 0


def test_no_program_beside_the_benchmark_is_an_error(tmp_path):
    shutil.copytree(ROOT / "cellbench", tmp_path / "cellbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = subprocess.run(
        [sys.executable, "cellbench/run.py", "--workload", "mistral7b-longdoc",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and not proc.stdout.strip()
