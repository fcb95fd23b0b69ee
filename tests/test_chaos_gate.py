"""Chaos gate: what scripts/chaos.sh promises, checked in tier 1 from its
text; the seed matrix itself runs at its entry point, `bash scripts/chaos.sh`.

The script replays every chaos-marked test under a fixed BBTPU_CHAOS_*
seed matrix (ambient wire jitter on top of the tests' own seeded fault
plans), so fault-recovery paths are exercised with injected noise — not
only when an operator remembers to soak them. It exits 0 when pytest is
unavailable, mirroring the scripts/lint.sh contract.
"""

import pathlib
import re
import subprocess

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_matrix_entries_are_keyval_tokens():
    """The matrix format is KEY=VAL tokens with per-entry defaults — not
    the old positional colon strings, which silently misassigned every
    column to the right of an insertion. Also pins that the Byzantine
    corruption entry exists and forces the integrity layer on (corruption
    is invisible to the transport; without BBTPU_INTEGRITY=1 the entry
    would test nothing)."""
    src = (REPO / "scripts" / "chaos.sh").read_text()
    entries = re.findall(r'^\s+"([^"]+)"$', src, flags=re.M)
    assert len(entries) >= 5, f"matrix lost entries: {entries}"
    known = {
        "SEED", "DELAY_P", "ADMIT", "PARTITION_P", "MIXED", "SPEC",
        "REBALANCE", "CORRUPT", "LOCKWATCH", "JITWATCH", "ARTIFACT",
        "UNIRAGGED", "CODEC", "SIM", "TESTS",
    }
    for entry in entries:
        for tok in entry.split():
            key, sep, val = tok.partition("=")
            assert sep == "=" and key in known and val, (
                f"matrix entry {entry!r} has non-KEY=VAL token {tok!r}"
            )
    assert any("CORRUPT=" in e for e in entries), (
        "no Byzantine corruption entry in the chaos matrix"
    )
    # the swarm-simulator entry replays the metastable-convergence gate
    # (python -m bloombee_tpu.sim --require --smoke) on every chaos run
    assert any("SIM=" in e for e in entries), (
        "no swarm-simulator entry in the chaos matrix"
    )
    # at least one BROAD entry must replay the whole chaos-marked suite:
    # targeted feature entries (TESTS=...) keep the gate inside its wall
    # budget, but whole-suite ambient coverage must never disappear
    assert any("TESTS=" not in e for e in entries), (
        "every matrix entry is targeted; no broad whole-suite entry left"
    )
    # targeted entries must name real files (a typo would silently select
    # nothing and the ledger gate would flag it only at run time)
    for entry in entries:
        for tok in entry.split():
            if tok.startswith("TESTS="):
                for f in tok[len("TESTS="):].split(","):
                    assert (REPO / f).is_file(), (
                        f"matrix entry {entry!r} targets missing file {f}"
                    )
    assert "BBTPU_INTEGRITY=${integrity}" in src
    assert "BBTPU_CHAOS_CORRUPT_P=${CORRUPT}" in src


def test_gate_requires_nonvacuous_ledger():
    """Every matrix entry must run under a recovery-coverage ledger and
    fail when the merged ledger shows zero faults or zero recoveries: a
    probabilistic plan that happened to inject nothing (or whose
    injections never reached recovery machinery) is a vacuous green, and
    the gate's whole point is that green means 'recovery ran'."""
    src = (REPO / "scripts" / "chaos.sh").read_text()
    assert "BBTPU_CHAOS_LEDGER=" in src, "entries run without a ledger"
    assert "bbtpu-chaos-ledger" in src and "mktemp" in src, (
        "ledger file is not per-entry (entries would bleed coverage "
        "into each other)"
    )
    assert re.search(
        r"python -m bloombee_tpu\.utils\.ledger .*--require", src
    ), "gate never checks the ledger with --require"


def test_gate_requires_nonvacuous_lockwatch():
    """The lock-witness entry follows the same no-vacuous-green contract
    as the ledger: at least one matrix entry runs with BBTPU_LOCKWATCH=1
    and its report is gated with --require, which fails on zero observed
    cross-lock edges or any hierarchy violation/cycle."""
    src = (REPO / "scripts" / "chaos.sh").read_text()
    entries = re.findall(r'^\s+"([^"]+)"$', src, flags=re.M)
    assert any("LOCKWATCH=1" in e for e in entries), (
        "no lock-witness entry in the chaos matrix"
    )
    assert "BBTPU_LOCKWATCH_REPORT=" in src, (
        "witness runs without a report file; nothing to gate on"
    )
    assert re.search(
        r"python -m bloombee_tpu\.utils\.lockwatch .*\\\n\s*--require", src
    ) or re.search(
        r"python -m bloombee_tpu\.utils\.lockwatch .*--require", src
    ), "gate never checks the lock-witness report with --require"


def test_gate_requires_nonvacuous_jitwatch():
    """The compile-witness entry follows the same contract: at least one
    matrix entry runs with BBTPU_JITWATCH=1 and its report is gated with
    --require, which fails on zero observed compiles (vacuous green), a
    missing warmup fence, or any steady-state recompile."""
    src = (REPO / "scripts" / "chaos.sh").read_text()
    entries = re.findall(r'^\s+"([^"]+)"$', src, flags=re.M)
    assert any("JITWATCH=1" in e for e in entries), (
        "no compile-witness entry in the chaos matrix"
    )
    assert "BBTPU_JITWATCH_REPORT=" in src, (
        "witness runs without a report file; nothing to gate on"
    )
    assert re.search(
        r"python -m bloombee_tpu\.utils\.jitwatch .*\\\n\s*--require", src
    ) or re.search(
        r"python -m bloombee_tpu\.utils\.jitwatch .*--require", src
    ), "gate never checks the compile-witness report with --require"


def test_gate_pins_artifact_entry():
    """The compile-artifact entry must exist and be held to BOTH
    strengthened gates: the merged ledger must show the
    server.artifact_fallback_compile recovery point (the corrupt/declined
    fallback path actually ran, not just clean pre-install), and the
    compile witness must pass --preinstalled mode (the pre-installed
    standby warmed up from persistent-cache hits alone — any real warmup
    compile for a pre-installed bucket is a red)."""
    src = (REPO / "scripts" / "chaos.sh").read_text()
    entries = re.findall(r'^\s+"([^"]+)"$', src, flags=re.M)
    artifact = [e for e in entries if "ARTIFACT=1" in e]
    assert artifact, "no compile-artifact entry in the chaos matrix"
    # the jitwatch --preinstalled gate needs the witness on in the same
    # entry, or there is no report to strengthen
    assert all("JITWATCH=1" in e for e in artifact), (
        "ARTIFACT entry runs without the compile witness"
    )
    assert "--require-recovery" in src and (
        "server.artifact_fallback_compile" in src
    ), "ARTIFACT entry is not pinned to the fallback-compile recovery"
    assert "--preinstalled" in src, (
        "ARTIFACT entry never strengthens the jitwatch gate to "
        "--preinstalled mode"
    )
    assert 'artifact_jitwatch_args="--preinstalled"' in src, (
        "--preinstalled is not derived from the ARTIFACT key"
    )


def test_gate_pins_universal_ragged_entry():
    """The universal-ragged entry must exist and force the whole fused
    path: UNIRAGGED=1 derives BOTH fusion flags inside the script (decode
    + tree + chunk rows share one gather only when mixed AND spec
    batching are on), replays the files whose traffic exercises every row
    kind, and carries the compile witness in the same entry so the
    'unified buckets pre-compiled, zero steady recompiles' claim is gated
    — not just asserted in a unit test."""
    src = (REPO / "scripts" / "chaos.sh").read_text()
    entries = re.findall(r'^\s+"([^"]+)"$', src, flags=re.M)
    uni = [e for e in entries if "UNIRAGGED=1" in e]
    assert uni, "no universal-ragged entry in the chaos matrix"
    assert all("JITWATCH=1" in e for e in uni), (
        "UNIRAGGED entry runs without the compile witness"
    )
    assert any("tests/test_universal_ragged.py" in e for e in uni), (
        "UNIRAGGED entry does not replay the universal-ragged tests"
    )
    # the derivation lives in the script, not the matrix line: setting
    # only one fusion flag would silently degrade the entry to PR-8/PR-10
    # behavior and the 'one dispatch' claim would go untested
    assert re.search(
        r'if \[ "\$\{UNIRAGGED\}" != "0" \]; then\s*\n\s*MIXED=1\s*\n'
        r"\s*SPEC=1", src,
    ), "UNIRAGGED does not derive MIXED=1 SPEC=1"


def test_gate_pins_codec_entry():
    """The streaming wire-path entry must exist and force every frame
    through the off-loop codec pool: CODEC=1 derives an inline threshold
    of 0 inside the script (otherwise tiny chaos-sized frames take the
    inline fast path and the ordered-drain/backpressure machinery under
    test never runs), replays the wire-pipeline tests, and pairs with
    CORRUPT so in-flight corruption of pooled decodes is caught by the
    integrity layer and ledgered as a recovery."""
    src = (REPO / "scripts" / "chaos.sh").read_text()
    entries = re.findall(r'^\s+"([^"]+)"$', src, flags=re.M)
    codec = [e for e in entries if "CODEC=1" in e]
    assert codec, "no streaming wire-path entry in the chaos matrix"
    assert any("tests/test_wire_pipeline.py" in e for e in codec), (
        "CODEC entry does not replay the wire-pipeline tests"
    )
    assert all("CORRUPT=" in e for e in codec), (
        "CODEC entry runs without Byzantine corruption; pooled-decode "
        "integrity goes untested"
    )
    # the derivation lives in the script: without inline=0 the pipeline
    # silently short-circuits for small frames and the entry is vacuous
    assert re.search(
        r'if \[ "\$\{CODEC\}" != "0" \]; then\s*\n\s*wire_inline=0', src,
    ), "CODEC does not derive BBTPU_WIRE_PIPELINE_INLINE=0"
    assert "BBTPU_WIRE_PIPELINE_INLINE=${wire_inline}" in src, (
        "derived inline threshold never reaches the test environment"
    )
    assert "BBTPU_WIRE_PIPELINE=1" in src, (
        "chaos entries run without the wire pipeline pinned on"
    )


def test_red_entry_prints_full_reproduction_line():
    """A red entry must print a single copy-pasteable reproduction line:
    the complete derived environment (not just the matrix tokens — those
    hide keepalive/integrity/promotion knobs derived from them) plus the
    exact pytest invocation, and the per-entry wall time."""
    src = (REPO / "scripts" / "chaos.sh").read_text()
    assert "reproduce with:" in src
    # the repro line reuses the same env_line the run used — it cannot
    # drift from reality
    assert src.count("env_line=") == 1
    assert re.search(r"echo\s+\"\s+\$\{env_line\} python -m pytest", src), (
        "repro line does not print the derived environment"
    )
    assert "${elapsed}s" in src, "per-entry wall time missing from gate log"


# `slow` since PR 50: a pytest inside pytest that replays the whole matrix
# with 0.5 s keep-alives in real time (243 s alone) was red under tier 1's
# six workers in most runs (PRs 40, 43, 44, 45, 48, 49: a keep-alive that
# timed out on a loaded machine, never the program), so it guarded nothing
# there. The chaos-marked tests themselves run in tier 1 once, without the
# ambient noise; the matrix is `bash scripts/chaos.sh` or `-m slow`, alone.
@pytest.mark.slow
def test_chaos_suite_under_seed_matrix():
    proc = subprocess.run(
        ["bash", str(REPO / "scripts" / "chaos.sh")],
        cwd=REPO, capture_output=True, text=True, timeout=580,
    )
    assert proc.returncode == 0, (
        f"chaos regressions:\n{proc.stdout[-8000:]}\n{proc.stderr[-4000:]}"
    )
