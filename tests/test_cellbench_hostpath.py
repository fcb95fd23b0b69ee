"""cellbench/hostpath.py and the eight metric files of PR 57, in tier 1.

A hand-made pair of counter snapshots (`rpc_info["memory"]["host_path"]` at
the window's start and end): every metric's value is worked out by hand, the
parent's `rpc_info` (no account) and a window with no task of a kind read
None and not 0, and every new `per_layer` entry has its file and lists the
nine cells."""

from __future__ import annotations

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from cellbench import hostpath  # noqa: E402
from cellbench.run import read_metric  # noqa: E402

COUNTER_METRICS = {
    # decode: 10 tasks of 40 ms in the window
    "host_decode_task_ms_mean": 4.0,
    # chunk: 5 tasks of 60 ms
    "host_chunk_task_ms_mean": 12.0,
    # unnamed 4 + 6 of 100 ms
    "host_task_unnamed_share": 10.0,
    # CPU 30 + 45 of 100 ms
    "host_task_offcpu_share": 25.0,
    # 6 + 1 of 10 + 5 launches
    "launch_on_idle_share": 100.0 * 7 / 15,
    # 3 + 2 ms over 7 idle launches
    "jit_call_on_idle_ms_mean": 5.0 / 7,
}
SPAN_METRICS = {
    "idle_unnamed_share": 100.0 * (0.2 + 0.1) / 2.0,
    "idle_pack_share": 100.0 * 0.5 / 2.0,
}


def _kind(n, wall, cpu, launches, on_idle, jit_idle, jit_busy, legs):
    return {
        "n": n, "wall_ms": wall, "cpu_ms": cpu / 2, "launches": launches,
        "launches_on_idle": on_idle, "jit_idle_ms": jit_idle,
        "jit_busy_ms": jit_busy,
        # the CPU is read in half of these tasks, beside half their wall
        "full_n": n // 2, "cpu_wall_ms": wall / 2,
        "legs": {leg: {"wall_ms": w, "cpu_ms": c / 2, "cpu_wall_ms": w / 2}
                 for leg, (w, c) in legs.items()},
    }


def snapshots():
    """Before the window: warm-up's tasks. In the window: 10 decode tasks
    (40 ms wall, 30 CPU), 5 chunk tasks (60, 45), no fused one; `other` met
    only before it."""
    before = {
        "decode": _kind(4, 20.0, 18.0, 4, 4, 8.0, 0.0, {
            "bbtpu.pack": (6.0, 6.0), "jit_call": (8.0, 7.0),
            "unnamed": (6.0, 5.0)}),
        "other": _kind(30, 900.0, 400.0, 0, 0, 0.0, 0.0, {
            "unnamed": (900.0, 400.0)}),
    }
    after = {
        "decode": _kind(14, 60.0, 48.0, 14, 10, 11.0, 9.0, {
            "bbtpu.pack": (16.0, 16.0), "jit_call": (20.0, 9.0),
            "bbtpu.counters": (14.0, 14.0), "unnamed": (10.0, 9.0)}),
        "chunk": _kind(5, 60.0, 45.0, 5, 1, 2.0, 18.0, {
            "bbtpu.pack": (30.0, 29.0), "jit_call": (20.0, 6.0),
            "bbtpu.h2d": (4.0, 4.0), "unnamed": (6.0, 6.0)}),
        "other": before["other"],
    }
    return before, after


def ctx_of(before, after, tmp_path=None):
    ctx = {
        "info0": {"memory": {"host_path": before}},
        "info1": {"memory": {"host_path": after}},
    }
    if tmp_path is not None:
        (tmp_path / "trace").mkdir()
        ctx["trace_dir"] = str(tmp_path / "trace")
    return ctx


def test_window_is_the_difference_by_kind_and_leg(tmp_path):
    ctx = ctx_of(*snapshots(), tmp_path)
    got = hostpath.reduced(ctx)
    decode, chunk = got["kinds"]["decode"], got["kinds"]["chunk"]
    assert (decode["n"], decode["wall_ms"]) == (10, 40.0)
    assert (decode["cpu_ms"], decode["cpu_wall_ms"]) == (15.0, 20.0)
    assert decode["legs"]["bbtpu.pack"] == {
        "wall_ms": 10.0, "cpu_ms": 5.0, "cpu_wall_ms": 5.0}
    # a leg (and a kind) first met inside the window counts from zero
    assert decode["legs"]["bbtpu.counters"] == {
        "wall_ms": 14.0, "cpu_ms": 7.0, "cpu_wall_ms": 7.0}
    assert decode["full_n"] == 5
    assert decode["mean"]["on_cpu_share"] == 75.0
    assert (chunk["n"], chunk["launches_on_idle"]) == (5, 1)
    assert decode["mean"]["task_wall_ms"] == 4.0
    # jit_call: 12 ms of wall, 2 of CPU in the window; read in half of it
    assert decode["mean"]["legs_ms"]["jit_call"] == {
        "wall_ms": 1.2, "on_cpu_share": pytest.approx(100.0 * 1.0 / 6.0)}
    # a kind that ran before the window and not in it: sums 0, no mean
    assert got["kinds"]["other"]["n"] == 0
    assert "mean" not in got["kinds"]["other"]
    assert hostpath.kind(ctx, "other") is None
    total = got["all"]
    assert (total["n"], total["wall_ms"], total["launches"]) == (15, 100.0, 15)
    assert total["legs"]["unnamed"]["wall_ms"] == 10.0
    for rec in (decode, chunk, total):
        assert sum(v["wall_ms"] for v in rec["legs"].values()) == (
            pytest.approx(rec["wall_ms"]))
    # what PERF.md quotes is kept beside the trace
    assert json.loads((tmp_path / hostpath.CACHE_NAME).read_text()) == got


@pytest.mark.parametrize("name, want", sorted(COUNTER_METRICS.items()))
def test_counter_metric_value_and_none_without_the_account(name, want):
    before, after = snapshots()
    assert read_metric(name, ctx_of(before, after)) == pytest.approx(want)
    # the parent's rpc_info: `memory` without the account
    parent = {"info0": {"memory": {"kv_walk": {}}},
              "info1": {"memory": {"kv_walk": {}}}}
    assert read_metric(name, parent) is None
    # a server that ran with the witness off: an empty account
    assert read_metric(name, ctx_of({}, {})) is None
    # a window that held no task at all
    assert read_metric(name, ctx_of(after, after)) is None


def test_a_window_with_no_task_of_the_kind_reads_none():
    before, after = snapshots()
    no_chunk = {k: v for k, v in after.items() if k != "chunk"}
    ctx = ctx_of(before, no_chunk)
    assert read_metric("host_chunk_task_ms_mean", ctx) is None
    assert read_metric("host_decode_task_ms_mean", ctx) == pytest.approx(4.0)
    # no launch found the device idle: no mean of nothing
    busy = json.loads(json.dumps(after))
    for kind in ("decode", "chunk"):
        busy[kind]["launches_on_idle"] = before.get(kind, {}).get(
            "launches_on_idle", 0)
    ctx = ctx_of(before, busy)
    assert read_metric("jit_call_on_idle_ms_mean", ctx) is None
    assert read_metric("launch_on_idle_share", ctx) == 0.0


@pytest.mark.parametrize("name, want", sorted(SPAN_METRICS.items()))
def test_idle_by_span_metric(name, want):
    idle = {"total_s": 2.0, "by_span_s": {
        "bbtpu.pack": 0.5, "bbtpu.task": 0.2, "bbtpu.dispatch": 0.1,
        "bbtpu.jit.span_step_packed": 0.4}}
    assert read_metric(name, {"_hosttrace": {"idle": idle}}) == (
        pytest.approx(want))
    assert read_metric(name, {"_hosttrace": None}) is None
    assert read_metric(name, {"_hosttrace": {"idle": None}}) is None
    idle = {"total_s": 2.0, "by_span_s": {"bbtpu.fetch": 0.3}}
    assert read_metric(name, {"_hosttrace": {"idle": idle}}) == 0.0


def test_cli_reduces_an_untraced_runs_snapshots(tmp_path):
    before, after = snapshots()
    src, out = tmp_path / "loadgen.json", tmp_path / "hostpath.json"
    src.write_text(json.dumps(ctx_of(before, after)))
    assert hostpath.main([str(src), str(out)]) == 0
    assert json.loads(out.read_text())["all"]["n"] == 15
    src.write_text(json.dumps({"info0": {"memory": {}}, "info1": {"memory": {}}}))
    assert hostpath.main([str(src), str(out)]) == 3


def test_every_new_per_layer_entry_has_its_file_and_lists_nine_cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in bench["workloads"]]
    assert len(cells) == 9
    new = list(COUNTER_METRICS) + list(SPAN_METRICS)
    entries = bench["per_layer"][-len(new):]
    assert sorted(e["name"] for e in entries) == sorted(new)
    for entry in entries:
        assert (ROOT / "cellbench" / "metrics"
                / f"{entry['name']}.py").exists(), entry["name"]
        assert entry["workloads"] == cells, entry["name"]
        assert entry["moves"] == "tokens_per_s"
        assert entry["layer"] == "executor and jitted step"
        assert entry["better"] == "lower"
        assert entry["source"] == (
            "program_span" if entry["name"] in SPAN_METRICS
            else "program_counter")
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
