"""chip_smoke.py rehearsed on the CPU: the tiny model, Pallas kernels through
their interpret switches, every phase run — and, because the server's
platform is not `tpu`, a final `"ok": false` and a non-zero exit code.

The real run happens on the chip (`python chip_smoke.py`, no arguments);
this keeps its control flow, its child-process hygiene and its failure
contract from rotting between chip runs."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke_run():
    env = {
        k: v for k, v in os.environ.items()
        # one CPU device, and the compile cache at its default place
        if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")
    }
    env.update(
        JAX_PLATFORMS="cpu",
        BBTPU_PAGED_INTERPRET="1",
        BBTPU_FLASH_INTERPRET="1",
        # the tiny prompts reach this context; the real crossover is 512
        BBTPU_PAGED_MIN_CONTEXT="128",
    )
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--tiny"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    lines = [json.loads(line) for line in proc.stdout.splitlines()]
    return proc, lines


def test_tiny_cpu_run_does_every_phase_and_fails_on_platform(smoke_run):
    proc, lines = smoke_run
    assert proc.returncode != 0, proc.stdout
    assert lines[-1] == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    phases = {line["phase"]: line for line in lines[:-1]}
    assert list(phases) == [
        "setup", "checkpoint", "server_one", "tokens_vs_reference",
        "summary",
    ], proc.stdout + proc.stderr
    # the platform is the ONLY thing wrong: every other gate passed
    assert phases["summary"]["faults"] == [
        "server platform is cpu, not tpu"
    ]
    assert phases["setup"]["compile_cache"] == str(REPO / ".cache" / "xla")
    server = phases["server_one"]
    assert server["warmup_failures"] == 0
    assert server["kernel_fallbacks"] == 0
    assert all(
        server["attn_dispatches"][k] > 0 for k in ("flash", "paged", "ragged")
    ), server["attn_dispatches"]
    assert server["dispatch"]["batch_dispatches"] >= 1
    assert server["dispatch"]["ragged_group_dispatches"] >= 1
    assert set(server["native"]) == {"paged_table", "byte_split"}
    verdict = phases["tokens_vs_reference"]
    assert verdict["faults"] == []
    assert sorted(verdict["requests"]) == ["a", "b", "chat", "long"]
    assert all(r["outside"] == 0 for r in verdict["requests"].values())
    # no registry, server, client or judge outlives the run
    started = phases["summary"]["children"]
    assert sorted(started) == [
        "client_one", "judge", "registry", "server_one"
    ]
    alive = {
        name: pid for name, pid in started.items()
        if pathlib.Path(f"/proc/{pid}").exists()
    }
    assert alive == {}


def test_parent_never_imports_jax(smoke_run):
    _, lines = smoke_run
    summary = next(line for line in lines if line.get("phase") == "summary")
    assert summary["parent_imported_jax"] is False
    # and importing the script (what the parent does before it picks a
    # role) pulls in neither jax nor anything of the package
    probe = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; "
         "bad = [m for m in sys.modules "
         "if m.split('.')[0] in ('jax', 'jaxlib', 'bloombee_tpu')]; "
         "sys.exit(repr(bad) if bad else 0)"],
        cwd=REPO, capture_output=True, text=True, timeout=60,
    )
    assert probe.returncode == 0, probe.stderr
