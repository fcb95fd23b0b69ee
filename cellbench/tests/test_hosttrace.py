"""cellbench/hosttrace.py on synthetic traces whose answers are known in advance.

    JAX_PLATFORMS=cpu python -m pytest cellbench/tests/test_hosttrace.py -q

One device plane with known idle gaps, a host plane with `bbtpu.task` spans
holding `pack` / `jit` / `slice` children, an `enqueue` before the second
task and a stretch with no task and no enqueue: every idle class and
`idle_attributed_share` has a value worked out by hand. An op list with and
without a layer scope for the two move shares. And the way a metric file is
found by name and returns None, not a made-up number, where the trace has no
device plane (the CPU rehearsal's case).
"""

from __future__ import annotations

import importlib.util
import json
import pathlib

import pytest

from cellbench import hosttrace

ROOT = pathlib.Path(__file__).resolve().parents[2]
MS = 1e-3

NEW_METRICS = (
    "worker_starved_share", "worker_hop_ms_mean", "host_dispatch_path_ms_mean",
    "idle_attributed_share", "idle_starved_share", "idle_hop_share",
    "idle_pre_dispatch_share", "idle_jit_call_share",
    "idle_post_dispatch_share", "scan_slab_move_share", "arena_io_move_share",
    "step_attention_ms_p50", "step_mlp_ms_p50",
)


def _op(name, start_ms, dur_ms, op_name=""):
    return (f"%{name} = bf16[8]{{0}} {name.split('.')[0]}()", start_ms * MS,
            dur_ms * MS, op_name)


def _span(name, start_ms, dur_ms, **ids):
    return (name, start_ms * MS, dur_ms * MS, ids)


STEP = "jit(span_step_packed_impl)/jit(main)/while/body/cond/branch_1_fun/"


def synthetic() -> dict:
    """Times in ms. Device busy [0,10) [14,20) [30,40) [41,50): gaps
    [10,14) [20,30) [40,41), 15 ms idle in all.

    compute thread: task 1 [9,16): pack [9,11), jit [11,13), slice [13,15);
                    task 2 [26,34): pack [26,29), jit [29,31);
                    task 3 [45,47): no jit, no enqueue in the trace.
    event loop:     enqueue of task 2 at 22.

    gap [10,14): pack 1 (pre_dispatch), jit 2, slice 1 (post_dispatch)
    gap [20,30): 2 starved [20,22), 4 hop [22,26), 3 pack, 1 jit
    gap [40,41): between task 2's end (34) and task 3's start (45), no
                 enqueue and no hop_us stamped: starved."""
    device = {"name": "/device:TPU:0", "modules": [
        ("jit_span_step_packed_impl(1)", 0.0, 10 * MS),
        ("jit_span_step_packed_impl(1)", 14 * MS, 6 * MS),
        ("jit_span_step_packed_impl(1)", 30 * MS, 10 * MS),
        ("jit_span_step_packed_impl(1)", 41 * MS, 9 * MS),
    ], "ops": [
        _op("fusion.1", 0, 10, STEP + "mlp/dot_general:"),
        # second run, a decode step: 1 ms of scan slab, 1 ms arena write,
        # 2 ms attention kernel, 1.5 ms MLP, 0.5 ms of a copy inside attention
        _op("dynamic-slice_bitcast_fusion.2", 14, 1,
            "jit(span_step_packed_impl)/jit(main)/while/body/squeeze:"),
        _op("bitcast_dynamic-update-slice_fusion.3", 15, 1,
            STEP + "arena_write/dynamic_update_slice:"),
        _op("paged_decode_attention.4", 16, 2, STEP + "attention/pallas_call:"),
        _op("copy.5", 18, 0.5, STEP + "attention/transpose:"),
        _op("fusion.6", 18.5, 1.5, STEP + "moe_experts/dot_general:"),
        # third run: a `while` that contains its body's ops
        _op("while.7", 30, 10, "jit(span_step_packed_impl)/jit(main)/while:"),
        _op("paged_decode_attention.8", 31, 3, STEP + "attention/pallas_call:"),
        _op("copy.9", 34, 2, "jit(span_step_packed_impl)/jit(main)/while/body/copy:"),
        _op("fusion.10", 36, 4, STEP + "mlp/dot_general:"),
        _op("fusion.11", 41, 9, STEP + "mlp/dot_general:"),
    ]}
    compute = {"line": 2, "events": [
        _span("bbtpu.task", 9, 7, task=1, members=2, kinds="decode1"),
        _span("bbtpu.pack", 9, 2, task=1),
        _span("bbtpu.jit.span_step_packed", 11, 2, task=1, bucket="b2;t1;p64"),
        _span("bbtpu.slice", 13, 2, task=1),
        _span("bbtpu.task", 26, 8, task=2, starved_us=6000, hop_us=4000,
              **{"class": "decode"}),
        _span("bbtpu.pack", 26, 3, task=2),
        _span("bbtpu.jit.span_step_packed", 29, 2, task=2),
        _span("bbtpu.task", 45, 2, task=3, starved_us=11000, hop_us=0),
    ]}
    loop = {"line": 1, "events": [
        _span("bbtpu.enqueue", 22, 0.002, task=2, **{"class": "decode"}),
        _span("bbtpu.fetch", 16, 3, session="s", step=4),
    ]}
    return {"device": [device], "host": [loop, compute]}


def test_every_idle_class_has_the_value_worked_out_by_hand():
    idle = hosttrace.reduce(synthetic())["idle"]
    assert idle["total_s"] == pytest.approx(15 * MS)
    want = {"starved": 3, "hop": 4, "pre_dispatch": 4, "jit_call": 3,
            "post_dispatch": 1}
    for name, ms in want.items():
        assert idle[name] == pytest.approx(ms * MS), name
    assert idle["unattributed"] == pytest.approx(0.0, abs=1e-12)
    assert idle["by_span_s"]["bbtpu.pack"] == pytest.approx(4 * MS)
    assert idle["by_span_s"]["bbtpu.slice"] == pytest.approx(1 * MS)


def test_idle_before_the_first_task_is_unattributed():
    raw = synthetic()
    # the trace caught nothing of task 1: the first gap has no host span
    raw["host"][1]["events"] = raw["host"][1]["events"][4:]
    idle = hosttrace.reduce(raw)["idle"]
    assert idle["unattributed"] == pytest.approx(4 * MS)
    attributed = sum(idle[c] for c in hosttrace.IDLE_CLASSES)
    assert attributed + idle["unattributed"] == pytest.approx(idle["total_s"])


def test_hop_falls_back_to_what_the_worker_stamped_on_the_task():
    raw = synthetic()
    raw["host"][0]["events"] = raw["host"][0]["events"][1:]  # no enqueue
    idle = hosttrace.reduce(raw)["idle"]
    assert idle["hop"] == pytest.approx(4 * MS)
    assert idle["starved"] == pytest.approx(3 * MS)


def test_move_shares_by_scope_and_decode_step_parts():
    device = hosttrace.reduce(synthetic())["device"]
    assert device["busy_s"] == pytest.approx(35 * MS)
    # no layer scope: the slab slice of run 2 and the copy under run 3's while
    assert device["scan_slab_move_s"] == pytest.approx(3 * MS)
    assert device["arena_io_move_s"] == pytest.approx(1 * MS)
    # a move op inside another scope belongs to neither
    assert device["other_move_s"] == pytest.approx(0.5 * MS)
    owners = dict(device["moves_by_owner"])
    assert owners["attention: copy"] == pytest.approx(0.5 * MS)
    assert owners["scan body: copy"] == pytest.approx(2 * MS)
    assert owners["scan body: dynamic-slice_bitcast_fusion"] == (
        pytest.approx(1 * MS))
    # decode runs are told by the paged kernel; the first and the last run of
    # the plane are cut by the trace's edges and left out (trace.py's rule)
    assert device["decode_runs"] == 2
    assert device["step_attention_ms_p50"] == pytest.approx((2.5 + 3.0) / 2)
    assert device["step_mlp_ms_p50"] == pytest.approx((1.5 + 4.0) / 2)
    # the while keeps only what its body leaves
    scopes = dict(device["step_seconds_by_scope"])
    assert scopes["scan body"] == pytest.approx((1 + 2) * MS)
    assert scopes["step, outside the scan"] == pytest.approx(1 * MS)


def test_a_program_without_scopes_gives_no_device_reading():
    """The parent's trace: the same ops, no layer scope on any of them.
    Reading every move as the scan's would be a made-up number."""
    raw = synthetic()
    raw["device"][0]["ops"] = [
        op[:3] + ("jit(span_step_packed_impl)/jit(main)/while/body/mul:",)
        for op in raw["device"][0]["ops"]]
    got = hosttrace.reduce(raw)
    assert got["device"] is None
    assert got["idle"] is not None  # the host's spans do not need them


def test_scope_is_the_innermost_known_part_of_the_op_name():
    assert hosttrace.scope_of(STEP + "arena_write/dynamic_update_slice:") == (
        "arena_write")
    assert hosttrace.scope_of(STEP + "mlp/norm/mul:") == "norm"
    assert hosttrace.scope_of("jit(f)/while/body/squeeze:") is None
    assert hosttrace.scope_of("") is None


def test_worker_account_from_the_task_spans():
    worker = hosttrace.reduce(synthetic())["worker"]
    # from task 1's start (9) to task 3's end (47); tasks 2 and 3 bring their
    # stamped waits: 6 + 11 starved, 4 hop; busy 7 + 8 + 2
    assert worker["tasks"] == 3
    assert worker["wall_s"] == pytest.approx(38 * MS)
    assert worker["starved_s"] == pytest.approx(17 * MS)
    assert worker["hop_s"] == pytest.approx(4 * MS)
    assert worker["busy_s"] == pytest.approx(17 * MS)
    assert worker["accounted_share"] == pytest.approx(100.0)


def _metric(name: str):
    path = ROOT / "cellbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("m_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_metric_files_are_found_by_name_and_read_the_cached_reduction(tmp_path):
    """What cellbench/run.py does with a name from BENCHMARK.json: the file
    cellbench/metrics/<name>.py, its read(ctx). The reduction is read back
    from the JSON beside the trace, where the first metric file left it."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    listed = {m["name"]: m for m in bench["per_layer"]}
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    (tmp_path / hosttrace.CACHE_NAME).write_text(
        json.dumps(hosttrace.reduce(synthetic())))
    ctx = {"trace_dir": str(trace_dir)}
    got = {name: _metric(name)(ctx) for name in NEW_METRICS}
    for name in NEW_METRICS:
        assert name in listed and listed[name]["moves"] == "tokens_per_s"
        assert got[name] is not None, name
    classes = ("idle_starved_share", "idle_hop_share", "idle_pre_dispatch_share",
               "idle_jit_call_share", "idle_post_dispatch_share")
    assert sum(got[c] for c in classes) == pytest.approx(
        got["idle_attributed_share"])
    assert got["idle_attributed_share"] == pytest.approx(100.0)
    assert got["idle_hop_share"] == pytest.approx(100.0 * 4 / 15)
    assert got["scan_slab_move_share"] == pytest.approx(100.0 * 3 / 35)
    assert got["arena_io_move_share"] == pytest.approx(100.0 * 1 / 35)
    assert got["worker_starved_share"] == pytest.approx(100.0 * 17 / 38)
    assert got["worker_hop_ms_mean"] == pytest.approx(2.0)
    assert got["host_dispatch_path_ms_mean"] == pytest.approx(17 / 3)


def test_no_device_plane_gives_none_and_no_trace_gives_none(tmp_path):
    """The CPU rehearsal's trace has host planes only, and the parent's
    program has no spans at all: a device metric is None, never a number."""
    raw = synthetic()
    host_only = hosttrace.reduce({"device": [], "host": raw["host"]})
    assert host_only["idle"] is None and host_only["device"] is None
    spanless = hosttrace.reduce({"device": raw["device"], "host": []})
    assert spanless["idle"] is None and spanless["worker"] is None
    # ... while the device's own scopes are simply absent: every move op of a
    # span-step program then reads as the scan's, which is why the parent's
    # side of a comparison reports these two metrics without meaning
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    (tmp_path / hosttrace.CACHE_NAME).write_text(json.dumps(host_only))
    ctx = {"trace_dir": str(trace_dir)}
    for name in NEW_METRICS:
        value = _metric(name)(ctx)
        if name.startswith(("worker_", "host_")):
            assert value is not None  # host spans need no device
        else:
            assert value is None, name
    # no trace directory at all (an untraced run): nothing to read
    for name in NEW_METRICS:
        assert _metric(name)(
            {"trace_dir": str(tmp_path / "other" / "trace")}) is None


def test_parse_reads_a_real_xplane_file(tmp_path):
    """The protobuf schema built in hosttrace.py against a file written with
    it: a device plane whose op carries `tf_op` on its METADATA, a host line
    with a `bbtpu.task` and its ids."""
    space = hosttrace._xspace_class()()
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[1].name = "tf_op"
    dev.event_metadata[7].name = "%copy.1 = bf16[8]{0} copy(%p)"
    stat = dev.event_metadata[7].stats.add(metadata_id=1)
    stat.str_value = STEP + "arena_gather/gather:"
    dev.event_metadata[8].name = "jit_span_step_packed_impl(1)"
    ops = dev.lines.add(name="XLA Ops")
    ops.events.add(metadata_id=7, offset_ps=2_000_000, duration_ps=500_000)
    mods = dev.lines.add(name="XLA Modules")
    mods.events.add(metadata_id=8, offset_ps=1_000_000, duration_ps=3_000_000)
    host = space.planes.add(name="/host:CPU")
    host.stat_metadata[1].name = "task"
    host.stat_metadata[2].name = "kinds"
    host.stat_metadata[3].name = "decode1"
    host.event_metadata[1].name = "bbtpu.task"
    host.event_metadata[2].name = "PjitFunction(f)"
    line = host.lines.add(id=42, timestamp_ns=1000)
    ev = line.events.add(metadata_id=1, offset_ps=5_000, duration_ps=7_000)
    ev.stats.add(metadata_id=1).int64_value = 9
    ev.stats.add(metadata_id=2).ref_value = 3
    line.events.add(metadata_id=2, offset_ps=0, duration_ps=1)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    raw = hosttrace.parse(path)
    (name, start, dur, op_name), = raw["device"][0]["ops"]
    assert name.startswith("%copy.1") and hosttrace.is_move(name)
    assert (start, dur) == (pytest.approx(2e-6), pytest.approx(5e-7))
    assert hosttrace.scope_of(op_name) == "arena_gather"
    assert raw["device"][0]["modules"][0][0] == "jit_span_step_packed_impl(1)"
    (only,) = raw["host"]
    assert only["line"] == 42 and len(only["events"]) == 1
    name, start, dur, ids = only["events"][0]
    assert name == "bbtpu.task" and ids == {"task": 9, "kinds": "decode1"}
    assert start == pytest.approx(1000e-9 + 5e-9) and dur == pytest.approx(7e-9)
