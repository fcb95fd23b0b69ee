"""The CPU rehearsal of every family's cell through `cellbench/run.py`, in
tier 1: ONE harness, a row a family (PR 50; until then a copy of all this
stood in each of `tests/test_cellbench_{families,qwen3_next,phi4flash,
afmoe}.py`, which keep what is about a family's readers and plan).

A row's tiny configuration, traffic mix and cell are ADDED to a copy of the
benchmark by files only (no file of `cellbench/` edited), and the copy is run
three ways: sound (`correct` true, with the family's own assertions on the
last line), with an int8-weight server (the control: `correct` false by the
projection), and with each of the family's faults planted in a copy of the
program (`scripts/plant_fault.py`, which plants the same on the chip: the
served tokens still come, no request fails, `correct` is false by the logit
error).

Every run of a session, whichever worker makes it, shares ONE compile cache
(it keys a program by its text, without source locations, so a copy of the
program at another path hits too). It is worth 18 s of a 52 s run on the same
program and 1-4 s on a control: an int8 server or a planted fault changes all
twelve step programs, each of which traces through every layer (`PERF.md`
section 6, PR 50). A tree is the case's own, under pytest's temporary
directory: nothing is written under the checkout, and nothing a worker builds
is another worker's to delete. `tests/conftest.py` deals these cases through
the collection, so that no worker is handed them in a row.

A new family's rehearsal is a row here and a tiny configuration beside the
family's pins.
"""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "tests"))
sys.path.insert(0, str(ROOT / "scripts"))

import plant_fault  # noqa: E402
from test_cellbench_afmoe import TINY_AFMOE  # noqa: E402
from test_cellbench_families import (  # noqa: E402
    TINY_DEEPSEEK_V2,
    TINY_FALCON_H1,
)
from test_cellbench_kimi_linear import TINY_KIMI_LINEAR  # noqa: E402
from test_cellbench_nemotron_h import TINY_NEMOTRON_H  # noqa: E402
from test_cellbench_phi4flash import (  # noqa: E402
    TINY_PHI4FLASH,
    WIDE_PHI4FLASH,
)
from test_cellbench_qwen3_next import TINY_QWEN3_NEXT  # noqa: E402

# two sessions, three prompts that each end inside a chunk
TINY_CTX = {
    "loop": "closed", "sessions": 2, "stagger_s": 0.1,
    "prompt_tokens": [300, 171, 260], "new_tokens": [4, 5, 4],
    "judge": {"requests": 2, "new_tokens": 4}}


def _shares_in(**bounds):
    """last["metrics"][name]["value"] strictly between (low, high)."""
    def check(last, work):
        for name, (low, high) in bounds.items():
            assert low < last["metrics"][name]["value"] < high, name
    return check


def _reach_metrics_read(last, work):
    """The reducer of the `bbtpu.step` / `bbtpu.moe_reach` spans ended (from
    PR 48 to PR 49 it died of the step span's `flash` id, which names two
    kinds of layer here and joined them with a comma), and where the traced
    second held a full chunk's reach span (under six workers' load it may
    hold none: the span is stamped a step later, when the counters are
    read) both metrics that read it are on the last line."""
    reach = json.loads((work / "reachtrace.json").read_text())["reach"]
    if any(s["kind"] == "chunk" and s["rows"] == 128 for s in reach):
        assert {"router_bias_moved_share", "held_experts_reached_share"
                } <= set(last["metrics"]), sorted(last["metrics"])


@dataclasses.dataclass(frozen=True)
class Row:
    """One family's rehearsal. Every server is float32 (at these widths
    bfloat16's own rounding, 0.003, is twenty times an int8-weight server's
    distance from the reference, and the projection on it is noise), mixes
    batches and cuts prompts into 128-token chunks."""

    presets: dict  # name -> tiny configuration; the cell is `<name>-<mix>`
    joins: str  # the accepted cell whose metric lists the tiny cells join
    limit: float  # `logit_error_limit`: above every sound reading (float32's
    # order of sums), far below every planted fault's
    sound_seed: int
    faults: tuple  # planted in tier 1: what this family brought
    slow_faults: tuple = ()  # those other rows plant in the same shared code
    server_flags: tuple = ()
    mix: str = "ctx"
    traffic: dict = dataclasses.field(default_factory=lambda: TINY_CTX)
    cell_extras: dict = dataclasses.field(
        default_factory=lambda: {"num_pages": 128})
    sound_seconds: int = 3
    # the sessions' first requests: a further one starts only once one has
    # finished, which a window of seconds does not promise on a machine six
    # test workers share (the qwen3_next rehearsal read 2 there, 12 alone)
    attempted: int = 2
    int8_abs: float = 0.05  # the int8 projection's distance from 1
    fault_factor: float = 10  # a planted fault's error over the limit
    # a CPU run reports no device metric under a device metric's name
    device_metrics: tuple = ()
    # the family's own assertions on the sound run: (last line, work dir)
    also: tuple = ()

    @property
    def sound(self) -> str:  # the preset the sound run serves
        return list(self.presets)[-1]

    @property
    def control(self) -> str:  # the preset the controls serve
        return list(self.presets)[0]


ROWS = {
    "falcon_h1": Row(
        presets={"tiny-falcon-h1": TINY_FALCON_H1}, joins="falconh1-longdoc",
        # sound 2.2e-7; padding fed to the state 2.0e-5 (at these widths the
        # state carries little); an int8-weight server 1.6e-4
        limit=2e-6, fault_factor=5, sound_seed=2**31 + 30, sound_seconds=4,
        attempted=3, faults=("pad",), mix="doc",
        traffic={
            "loop": "closed", "sessions": 3, "stagger_s": 0.1,
            "prompt_tokens": [300, 136, 261, 200], "new_tokens": [5, 4, 6, 4],
            "judge": {"requests": 2, "new_tokens": 4}},
        device_metrics=(
            "step_ssm_ms_p50", "chunk_ssm_ms_p50", "ssm_scan_roofline",
            "state_io_move_share", "device_idle_share")),
    "deepseek_v2": Row(
        presets={"tiny-deepseek-v2": TINY_DEEPSEEK_V2},
        joins="deepseekv2-longctx", server_flags=("--experts", "4:4"),
        # sound 2.1e-7; the rotary key zeroed in the cache write 5.2e-4, the
        # route scale left out 0.028, the routed sum dropped 0.030; an
        # int8-weight server 6.5e-4 with projection 1.0000
        limit=2e-5, sound_seed=2**31 + 35, sound_seconds=4,
        faults=("rope_key", "route_scale", "routed_sum"),
        device_metrics=(
            "chunk_mla_ms_p50", "step_mla_ms_p50", "chunk_moe_ms_p50",
            "mla_attention_roofline", "mla_decode_roofline",
            "latent_io_move_share", "device_idle_share")),
    "qwen3_next": Row(
        presets={"tiny-qwen3-next": TINY_QWEN3_NEXT},
        joins="qwen3next-longctx", server_flags=("--experts", "2:4"),
        # sound 3.4e-7 .. 4.0e-7; each fault reads thousands of times that
        limit=4e-6, sound_seed=2**31 + 39,
        faults=("state_reset", "beta", "attn_gate"),
        device_metrics=(
            "chunk_gdn_ms_p50", "step_gdn_ms_p50", "gdn_rule_roofline",
            "gdn_state_move_share", "device_idle_share")),
    "phi4flash": Row(
        # the wide preset's K/V pair is 128 lanes, as the published one, so
        # that its slab has a free page view (kv/arena.py `page_view_free`)
        # and the sound run writes its chunks into the arena by page; the
        # controls keep the narrow one
        presets={"tiny-phi4flash": TINY_PHI4FLASH,
                 "tiny-phi4flash-wide": WIDE_PHI4FLASH},
        joins="phi4flash-longctx", limit=2e-5, sound_seed=2**31 + 45,
        int8_abs=0.1,
        traffic=dict(TINY_CTX, prompt_tokens=[300, 171, 276]),
        faults=("cross_row", "memory", "lambda", "state_reset"),
        device_metrics=(
            "chunk_mamba_ms_p50", "step_mamba_ms_p50", "mamba_scan_roofline",
            "step_cross_ms_p50", "device_idle_share"),
        also=(_shares_in(
            # the second exit is taken: a prompt's rows stop at the shared
            # layer
            cross_rows_share=(0, 20), window_dead_share=(0, 100),
            # every chunk starts on a page boundary and no tail is under a
            # page; 100 where no pack formed in the window: a chunk that
            # rides a fused pack behind decode rows writes by row (PR 49)
            page_write_share=(50, 101)),)),
    "afmoe": Row(
        presets={"tiny-afmoe": TINY_AFMOE}, joins="trinity-longctx",
        server_flags=("--experts", "2:4"),
        # a router near-tie aside (the program's float32 product and the
        # reference's differ in the last bits), sound readings are float32's
        # order of sums; each fault reads hundreds of times the limit
        limit=1e-4, sound_seed=2**31 + 47, int8_abs=0.15,
        sound_seconds=6,  # a traced 1.8 s: room for a full chunk's reach span
        faults=("bias_choice", "biased_weights", "full_rope", "window"),
        slow_faults=("route_scale", "attn_gate", "routed_sum", "embed_scale"),
        device_metrics=(
            "chunk_router_ms_p50", "chunk_window_attn_ms_p50",
            "chunk_full_attn_ms_p50", "device_idle_share"),
        also=(
            # prompts of 171-300 tokens outgrow the 32-token window many
            # times over: four of the five layers hold mostly dead tokens
            _shares_in(window_dead_share=(50, 80)), _reach_metrics_read)),
    "kimi_linear": Row(
        presets={"tiny-kimi-linear": TINY_KIMI_LINEAR},
        joins="kimilinear-longctx", server_flags=("--experts", "2:4"),
        # sound 1.3e-6 (float32's order of sums; a router near-tie aside,
        # as afmoe's); each fault reads hundreds of times the limit
        limit=1e-4, sound_seed=2**31 + 51, int8_abs=0.15,
        sound_seconds=6,  # a traced 1.8 s: room for a full chunk's reach span
        faults=("scalar_decay", "rope_in_full", "gate_out"),
        # the lines qwen3_next's and afmoe's rows plant
        slow_faults=("beta_out", "state_reset", "route_scale_out", "bias_out"),
        device_metrics=(
            "chunk_kda_ms_p50", "step_kda_ms_p50", "kda_rule_roofline",
            "kda_state_move_share", "chunk_mla_ms_p50", "device_idle_share")),
    "nemotron_h": Row(
        presets={"tiny-nemotron-h": TINY_NEMOTRON_H},
        joins="nemotron3nano-longctx", server_flags=("--experts", "2:4"),
        # sound 2.5e-7 on two seeds (float32's order of sums); the state
        # emptied at a prompt's last chunk boundary 1.3e-4 (at these widths
        # the state carries little), the other faults hundreds of times that
        limit=1e-5, sound_seed=2**31 + 54, int8_abs=0.15,
        sound_seconds=6,  # a traced 1.8 s: room for a full chunk's reach span
        faults=("state_reset",),
        # the ungated form's line, and those kimi_linear's and afmoe's rows
        # plant
        slow_faults=("relu2_silu", "route_scale_out", "bias_out"),
        device_metrics=(
            "chunk_ssm_ms_p50", "step_ssm_ms_p50", "ssm_scan_roofline",
            "state_io_move_share", "chunk_experts_roofline",
            "chunk_experts_ms_p50", "device_idle_share"),
        also=(_reach_metrics_read,)),
}


def _build(tree: pathlib.Path, row: Row) -> pathlib.Path:
    """A copy of the benchmark with the row's configurations, traffic mix
    and cells ADDED (the family file and the metric readers are already
    there), the program a link to the checkout's."""
    shutil.copytree(ROOT / "cellbench", tree / "cellbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tree / "bloombee_tpu").symlink_to(ROOT / "bloombee_tpu")
    cb = tree / "cellbench"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (cb / "traffic" / f"tiny-{row.mix}.json").write_text(
        json.dumps(row.traffic))
    for name, config in row.presets.items():
        (cb / "configs" / f"{name}.json").write_text(json.dumps(dict(
            config, cellbench={
                "source": "none: a rehearsal preset", "uid": name,
                "reduced": {"everything": "tiny"},
                "server_flags": ["--mixed-batch", "--prefill-chunk", "128",
                                 "--dtype", "float32", *row.server_flags],
                "prefill_chunk": 128, "logit_error_limit": row.limit,
                "int8_projection_limit": 0.5})))
        (cb / "cells" / f"{name}-{row.mix}.json").write_text(
            json.dumps(row.cell_extras))
        bench["configs"].append(
            {"name": name, "source": "none", "reduced": [],
             "file": f"cellbench/configs/{name}.json", "why": "rehearsal"})
        bench["workloads"].append(
            {"name": f"{name}-{row.mix}", "config": name,
             "traffic": f"tiny-{row.mix}", "chips": 1, "why": "rehearsal"})
        for metric in bench["end_to_end"] + bench["per_layer"]:
            if row.joins in metric.get("workloads", ()):
                metric["workloads"].append(f"{name}-{row.mix}")
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))
    return tree


@pytest.fixture
def tree(family, tmp_path) -> pathlib.Path:
    """The tree of the row a case names, the case's own."""
    return _build(tmp_path / "tree", ROWS[family])


@pytest.fixture(scope="session")
def compile_cache(tmp_path_factory) -> str:
    """The session's ONE compile cache, the workers' too: under xdist a
    worker's base temp is a directory of the session's. `run.py` hands it to
    its server, load generator and judge at once, so concurrent writers are
    what it is used with. Where the variable is set, that directory is it."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    base = tmp_path_factory.getbasetemp()
    if os.environ.get("PYTEST_XDIST_WORKER"):
        base = base.parent
    return str(base / "cell_rehearsal_xla")


def _cores() -> list[int]:
    """The cores this worker's swarm may use: under xdist a window of half
    the machine's, its start moved by the worker's number. `run.py` pins its
    server to the lowest two fifths of the cores it finds itself allowed and
    its load generator to the rest, so the swarms of six workers would all
    compile on cores 0-2 of eight while the others idle (25 cases at once:
    3811 worker-seconds so, 1997 with windows; my runs, PR 50)."""
    cores = sorted(os.sched_getaffinity(0))
    worker = os.environ.get("PYTEST_XDIST_WORKER")
    if worker is None or len(cores) < 4:
        return cores
    start = int(worker[2:]) * len(cores) // int(
        os.environ["PYTEST_XDIST_WORKER_COUNT"])
    return sorted(cores[(start + i) % len(cores)]
                  for i in range(len(cores) // 2))


def _run(tree: pathlib.Path, cache: str, *argv: str):
    """(exit code, the last line where it holds a verdict, all output)."""
    env = dict(os.environ, CELLBENCH_REHEARSAL="1", JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache)
    env.pop("XLA_FLAGS", None)  # conftest's 8 virtual devices: one is the cell's
    allowed = os.sched_getaffinity(0)
    os.sched_setaffinity(0, _cores())  # this thread's, which the child inherits
    try:
        proc = subprocess.run(
            [sys.executable, "cellbench/run.py", *argv], cwd=tree, env=env,
            capture_output=True, text=True, timeout=600)
    finally:
        os.sched_setaffinity(0, allowed)
    lines = proc.stdout.strip().splitlines()
    try:
        last = json.loads(lines[-1]) if lines else None
    except ValueError:
        last = None
    if last is not None and "correct" not in last:
        last = None  # the summary line of a run without a verdict
    return proc.returncode, last, proc.stdout + proc.stderr


def _compared(out: str) -> dict:
    """Each number the run compared, beside its limit."""
    line = next(ln for ln in out.splitlines() if '"phase": "correctness"' in ln)
    return json.loads(line)["compared"]


FAULT_CASES = [
    pytest.param(family, fault, id=f"{family}-{fault}",
                 marks=[pytest.mark.slow] if fault in row.slow_faults else [])
    for family, row in ROWS.items() for fault in row.faults + row.slow_faults]


@pytest.mark.parametrize("family", list(ROWS))
def test_cell_rehearsal_is_correct(family, tree, compile_cache):
    row = ROWS[family]
    rc, last, out = _run(
        tree, compile_cache, "--workload", f"{row.sound}-{row.mix}", "--seed",
        str(row.sound_seed), "--seconds", str(row.sound_seconds),
        "--trace", "1")
    assert last is not None and rc == 0, out[-3000:]
    assert last["correct"] is True and last["failed"] == 0, out[-3000:]
    assert last["attempted"] >= row.attempted
    for name in row.device_metrics:
        assert name not in last["metrics"]
    for check in row.also:
        check(last, tree / ".cache" / "cellbench" / f"{row.sound}-{row.mix}")


@pytest.mark.parametrize("family", list(ROWS))
def test_cell_rehearsal_int8_server_is_not_correct(
        family, tree, compile_cache):
    row = ROWS[family]
    rc, last, out = _run(
        tree, compile_cache, "--workload", f"{row.control}-{row.mix}",
        "--seed", "17", "--seconds", "2", "--trace", "0",
        "--server-arg=--weight-quant", "--server-arg=int8")
    assert last is not None and last["correct"] is False, out[-3000:]
    assert rc != 0
    got = _compared(out)
    assert got["int8_projection_median"][0] == pytest.approx(
        1.0, abs=row.int8_abs)


@pytest.mark.parametrize("family,fault", FAULT_CASES)
def test_cell_rehearsal_sees_a_planted_fault(
        family, fault, tree, compile_cache):
    """The timed path BROKEN underneath the harness: the tree's program is a
    copy with the fault planted."""
    row = ROWS[family]
    (tree / "bloombee_tpu").unlink()
    shutil.copytree(ROOT / "bloombee_tpu", tree / "bloombee_tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    plant_fault.plant(tree, family, fault)
    rc, last, out = _run(
        tree, compile_cache, "--workload", f"{row.control}-{row.mix}",
        "--seed", "23", "--seconds", "2", "--trace", "0")
    assert last is not None and last["correct"] is False, out[-3000:]
    assert last["failed"] == 0 and rc != 0
    err, limit = _compared(out)["logit_err_median"]
    assert err > row.fault_factor * limit, (err, limit)


@pytest.mark.parametrize("family,fault", [
    (family, fault) for family, faults in plant_fault.FAULTS.items()
    for fault in faults])
def test_a_planters_sound_line_stands_once_in_the_program(family, fault):
    """A planter whose target line has drifted fails here in a second, not
    after a rehearsal; and every fault a row names is in the table."""
    path, _text, sound, broken, found = plant_fault.target(ROOT, family, fault)
    assert found == 1, (str(path), sound)
    assert broken != sound
    row = ROWS[family]
    assert set(row.faults + row.slow_faults) <= set(plant_fault.FAULTS[family])
