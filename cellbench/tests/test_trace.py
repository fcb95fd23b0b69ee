"""The trace reduction and the roofline arithmetic, on a synthetic trace with
a KNOWN idle gap and a KNOWN kernel, so that an idle share of 0.01% beside a
lockstep batch is either confirmed or corrected by the numbers, not by trust.

    JAX_PLATFORMS=cpu python -m pytest cellbench/tests/test_trace.py -q
"""

from __future__ import annotations

import math
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from cellbench import checkpoint, families, roofline, stats, trace  # noqa: E402

MS = 1_000_000_000  # picoseconds in a millisecond

# One device plane. Two runs of the packed program as a decode
# step (30 ms each, the paged decode kernel inside) with a 10 ms idle gap
# between them, one run of the SAME jitted function as a solo prefill chunk
# (20 ms, no decode kernel), then one fused program (30 ms).
# Inside each decode step: a `while` (30 ms) that contains a 12 ms fusion, a
# 9 ms copy, a 6 ms dynamic-update-slice fusion and a 2 ms decode kernel;
# 1 ms of the while is its own.
_OPS = [
    # name, start_ms, dur_ms
    ("while.1", 0, 30), ("fusion.7", 0, 12), ("copy.3", 12, 9),
    ("bitcast_dynamic-update-slice_fusion.2", 21, 6),
    # the chip's trace names an op by its whole HLO line
    ("%paged_decode_attention.4 = bf16[8,2048]{1,0} custom-call(bf16[4,128]"
     " %copy.9), kind=kOutput", 27, 2),
    ("while.1", 40, 30), ("fusion.7", 40, 12), ("copy.3", 52, 9),
    ("bitcast_dynamic-update-slice_fusion.2", 61, 6),
    ("%paged_decode_attention.4 = bf16[8,2048]{1,0} custom-call(bf16[4,128]"
     " %copy.9), kind=kOutput", 67, 2),
    ("fusion.9", 70, 20),
    ("paged_ragged_attention.2", 90, 30),
]
# ... all of it 4 ms later, between two runs that the trace's edges cut: of
# each only a 4 ms copy is left (the first would read as a chunk, the last as
# a very short decode-less step: the reduction leaves both out of the medians)
_OPS = ([("copy.3", 0, 4)] + [(n, s + 4, d) for n, s, d in _OPS]
        + [("copy.3", 124, 4)])
_MODULES = [
    # (the first and the last span-step run of a plane are left out as
    # possibly cut by the trace's edges: only with more than two runs)
    ("jit_span_step_packed_impl(111)", 0, 30),
    ("jit_span_step_packed_impl(111)", 40, 30),
    ("jit_span_step_packed_impl(333)", 70, 20),
    ("jit_span_step_ragged_impl(222)", 90, 30),
]
_MODULES = ([("jit_span_step_packed_impl(111)", 0, 4)]
            + [(n, s + 4, d) for n, s, d in _MODULES]
            + [("jit_span_step_packed_impl(111)", 124, 4)])


def _text_proto() -> str:
    names = sorted({n for n, _, _ in _OPS + _MODULES})
    ids = {n: i + 1 for i, n in enumerate(names)}

    def line(line_id, name, events):
        body = "".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {int(s * MS)} "
            f"duration_ps: {int(d * MS)} }}\n" for n, s, d in events)
        return (f'lines {{ id: {line_id} name: "{name}" timestamp_ns: 5000\n'
                f"{body}}}\n")

    meta = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
        for n, i in ids.items())
    host = 'planes { id: 2 name: "/host:CPU" }\n'
    return ('planes { id: 1 name: "/device:TPU:0"\n'
            + line(1, trace.OPS_LINE, _OPS)
            + line(2, trace.MODULES_LINE, _MODULES) + meta + "}\n" + host)


@pytest.fixture(scope="module")
def planes() -> list:
    from jax.profiler import ProfileData

    data = ProfileData.from_text_proto(_text_proto())
    planes = trace.load(pathlib.Path("unused"), data=data)
    assert [p["name"] for p in planes] == ["/device:TPU:0"]  # host left out
    return planes


@pytest.fixture(scope="module")
def reduced(planes) -> dict:
    return trace.reduce(planes)


def test_known_idle_gap_is_found(reduced):
    assert reduced["window_s"] == pytest.approx(0.128)
    assert reduced["busy_s"] == pytest.approx(0.118)  # nested ops once
    assert reduced["device_idle_share"] == pytest.approx(100 * 10 / 128)
    assert reduced["breakdown"]["idle_gaps"] == [
        ["after jit_span_step_packed_impl", pytest.approx(0.010)]]


def test_idle_share_is_taken_over_the_traced_interval(planes):
    """The profiler ran 200 ms and the ops span 128 ms of it: the 72 ms
    before the first and after the last op are idle time too."""
    got = trace.reduce(planes, traced_s=0.200)
    assert got["window_s"] == pytest.approx(0.200)
    assert got["ops_span_s"] == pytest.approx(0.128)
    assert got["device_idle_share"] == pytest.approx(100 * 82 / 200)
    gaps = dict(got["breakdown"]["idle_gaps"])
    assert gaps["before the first and after the last op"] == pytest.approx(0.072)
    # a traced interval shorter than what the ops span cannot make busy > window
    assert trace.reduce(planes, traced_s=0.050)["window_s"] == pytest.approx(0.128)


def test_known_kernel_and_programs(reduced):
    ops = dict(reduced["breakdown"]["device_ops"])
    assert ops["paged_decode_attention"] == pytest.approx(0.004)
    assert ops["paged_ragged_attention"] == pytest.approx(0.030)
    assert ops["fusion"] == pytest.approx(0.044)
    assert ops["while"] == pytest.approx(0.002)  # self time only
    # copy 26 ms + dynamic-update-slice 12 ms of 118 ms busy
    assert reduced["arena_move_share"] == pytest.approx(100 * 38 / 118)


def test_decode_steps_and_prefill_chunks_are_reduced_apart(reduced):
    """Both run `span_step_packed`; only a decode step executes the paged
    decode kernel. A chunk's time never enters the decode step's median."""
    assert reduced["programs_run"] == {"decode": 2, "chunk": 1, "fused": 1}
    assert reduced["server_step_ms_p50"] == pytest.approx(30.0)
    assert reduced["server_prefill_ms_p50"] == pytest.approx(20.0)
    assert reduced["server_fused_ms_p50"] == pytest.approx(30.0)
    kinds = {name: p["kind"] for name, p in reduced["programs"].items()}
    assert kinds == {"jit_span_step_packed_impl(111)": "decode",
                     "jit_span_step_packed_impl(333)": "chunk",
                     "jit_span_step_ragged_impl(222)": "fused"}
    assert trace.program_kind("jit_dynamic_slice(5)", {"fusion"}) is None


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        trace.reduce([])


def _configs():
    import json

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for entry in bench["configs"]:
        full = json.loads((ROOT / entry["file"]).read_text())
        yield entry["name"], {
            k: v for k, v in full.items() if k != "cellbench"}


@pytest.mark.parametrize("name,config", list(_configs()))
def test_roofline_stays_under_100_for_both_configurations(name, config):
    """least_seconds counts only needed bytes and FLOPs, so no step can run
    faster: against the time the chip needs merely to READ what the step
    reads (the weights it touches once, the live KV), the share is <= 100%
    for every batch width and context the cells reach."""
    family = families.of(config)
    # every tensor of every layer file the checkpoint holds, norms included
    all_weight_bytes = roofline.BF16 * sum(
        math.prod(shape) for tag, tensors in checkpoint.tensor_plan(config)
        if tag != checkpoint.CLIENT_SHARD for _name, shape, _fill in tensors)
    for rows in (1, 2, 3.5, 8):
        for context in (72, 512, 2120, 4096):
            needs = family.decode_step_needs(config, rows, context)
            assert needs["weight_bytes"] <= all_weight_bytes
            least, bound = roofline.least_seconds(needs, "TPU v5 lite")
            assert bound in ("memory", "compute")
            # a step that did nothing but stream its needed bytes at the peak
            # rate and its FLOPs at the peak rate, one after the other
            honest_floor = (needs["bytes"] / 819e9 + needs["flops"] / 197e12)
            assert 0 < least <= honest_floor
            assert 100.0 * least / honest_floor <= 100.0
    # a full prefill chunk: the same rule, at every context a chunk can start
    for context in (0, 1024, 3968):
        needs = family.chunk_needs(config, 128, context)
        assert needs["weight_bytes"] <= all_weight_bytes
        least, _ = roofline.least_seconds(needs, "TPU v5 lite")
        assert 0 < least <= needs["bytes"] / 819e9 + needs["flops"] / 197e12
    # the decode step's median device time since PR 28 (ledger, PR 28)
    fastest = {"qwen3-30b-a3b-span4": 0.0070731,
               "mistral-7b-span16": 0.010938}[name]
    needs = family.decode_step_needs(config, 3.0, 400)
    least, _ = roofline.least_seconds(needs, "TPU v5 lite")
    assert 100.0 * least / fastest < 100.0


def test_distinct_experts_expectation():
    assert roofline.expected_distinct_experts(128, 8, 1) == pytest.approx(8.0)
    assert 50 < roofline.expected_distinct_experts(128, 8, 8) < 64
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")


def test_driver_spread_drops_the_farthest_run():
    runs = [100.0, 100.5, 99.5, 100.2, 99.8, 110.0]
    s = stats.driver_spread(runs)
    assert s["trimmed"] < s["wide"]
    assert s["trimmed"] == pytest.approx(stats.iqr_share(runs[:5]))


def test_prorated_tokens_do_not_jump_at_the_edges():
    req = {"start": -1.0, "token_times": [1.0, 2.0, 3.0], "prompt_tokens": 100}
    # half of the prefill lies inside the window: half of the prompt counts
    assert stats.prorated_tokens(req, 0.0, 2.5) == pytest.approx(50 + 2)
