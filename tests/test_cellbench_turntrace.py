"""cellbench/turntrace.py on hand-made `hosttrace.parse()` lists, in tier 1.

Two sessions' `bbtpu.turn.*` stamps over a device with three idle gaps:
every leg's starved seconds have the value worked out by hand (two sessions
in different legs at one instant split it in halves), a second no leg covers
reads `uncovered`, the five shares sum to hosttrace's own `starved` share,
and each new metric file is found by name and returns None, not 0, on a
trace without the stamps (the parent's program)."""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from cellbench import hosttrace, turntrace  # noqa: E402
from cellbench.tests.test_hosttrace import _op, _span  # noqa: E402
from cellbench.tests.test_hosttrace import synthetic as spanless  # noqa: E402

MS = 1e-3
SHARE_METRICS = {
    "idle_starved_client_share": 13, "idle_starved_wire_share": 7,
    "idle_starved_ingest_share": 4, "idle_starved_reply_share": 5,
    "idle_starved_uncovered_share": 7,
}
P50_METRICS = {
    "prefill_client_ms_p50": 21, "prefill_upload_ms_p50": 15,
    "turn_client_ms_p50": 10, "turn_wire_ms_p50": 6,
    "turn_server_edge_ms_p50": 4,
}
IDLE_MS, STARVED_MS = 40, 36


def _us(ms: float) -> int:
    return int(ms * 1000)


def traced() -> dict:
    """Times in ms. Device busy [0,10) [30,40) [50,60) [70,80): idle 40.

    compute thread: task 1 [8,10); task 2 [29,31), enqueued at 28; task 3
    [49,51) and task 4 [69,71) with no enqueue in the trace. `starved`:
    [10,28) [31,49) [51,69); under the gaps [10,28) [40,49) [60,69): 36.

    session A, a decode turn (step 5): the reply before left at 10, the
    request's last byte was read at 26, submitted at 28 (`arrive`): away 16 =
    wire 6 + c_recv 1 + c_head 4 + c_other 1 + c_embed 2 + c_send 2, laid out
    wire [10,13) c_recv [13,14) c_head [14,18) c_other [18,19) c_embed [19,21)
    c_send [21,23) wire [23,26) ingest [26,28); served [30,42), reply
    [42,44) (`reply` at 44).
    session B, a first turn (step 0): open frame read at 25, request read at
    45, submitted at 48: open [20,25), away 20 = c_other 2 + c_embed 6 +
    c_send 8 + wire 4 (all on the request's side): c_other [25,27) c_embed
    [27,33) c_send [33,41) wire [41,45) ingest [45,48); served [48,60), reply
    [60,62) (`reply` at 62).

    gap [10,28): A alone until 20 (wire 3, client 1+4+1+1), then B's open /
      c_other / c_embed beside A's c_embed, c_send (client 3), wire [23,26)
      (halves: wire 1.5, client 1.5) and ingest [26,28) (ingest 1, client 1):
      wire 4.5, client 12.5, ingest 1
    gap [40,49): B c_send | A served (client .5, reply .5), B wire | A served
      (.5, .5), B wire | A reply [42,44) (1, 1), B wire alone 1, B ingest 3,
      B served [48,49) 1 (the fetch's share): client .5, wire 2.5, reply 3,
      ingest 3
    gap [60,69): B reply [60,62) 2, then nobody: uncovered 7."""
    step = "jit(span_step_packed_impl)/jit(main)/while/body/mlp/dot_general:"
    device = {"name": "/device:TPU:0", "modules": [], "ops": [
        _op(f"fusion.{i}", start, 10, step)
        for i, start in enumerate((0, 30, 50, 70))]}
    compute = {"line": 2, "events": [
        _span("bbtpu.task", 8, 2, task=1),
        _span("bbtpu.task", 29, 2, task=2),
        _span("bbtpu.task", 49, 2, task=3),
        _span("bbtpu.task", 69, 2, task=4),
    ]}
    loop = {"line": 1, "events": [
        _span("bbtpu.enqueue", 28, 0.002, task=2),
        _span(turntrace.ARRIVE, 28, 0, session="A", step=5,
              away_us=_us(16), ingest_us=_us(2), client=";".join(map(str, (
                  _us(1), _us(4), _us(1), _us(2), _us(2), 0))),
              **{"class": "decode"}),
        # the span made a millisecond after the write it stamps
        _span(turntrace.REPLY, 45, 0, session="A", step=5,
              served_us=_us(12), reply_us=_us(2), lag_us=_us(1)),
        # step 0 and first=1: the profiler's reader drops an id that is 0
        _span(turntrace.ARRIVE, 48, 0, session="B", first=1,
              away_us=_us(20), ingest_us=_us(3), client=";".join(map(str, (
                  0, 0, _us(2), _us(6), _us(8), _us(5)))),
              **{"class": "prefill"}),
        _span(turntrace.REPLY, 62, 0, session="B", served_us=_us(12),
              reply_us=_us(2)),
        # the codec's spans: 1.5 of A's 2 ms ingest, 1 of B's 2 ms reply
        _span("bbtpu.codec.decode", 26, 1.5, bytes=8192),
        _span("bbtpu.codec.encode", 60.5, 1, bytes=8192),
    ]}
    return {"device": [device], "host": [loop, compute]}


def test_every_legs_starved_seconds_have_the_value_worked_out_by_hand():
    got = turntrace.reduce(traced())
    idle = got["idle"]
    assert idle["total_s"] == pytest.approx(IDLE_MS * MS)
    assert idle["starved_s"] == pytest.approx(STARVED_MS * MS)
    want = {"wire": 7, "c_recv": 1, "c_head": 4, "c_other": 1 + 1,
            "c_embed": 1.5 + .5, "c_send": 1 + .5, "open": 1.5 + 1,
            "ingest": 4, "served": 1 + 1, "reply": 1 + 2, "uncovered": 7}
    for leg, ms in want.items():
        assert idle["starved_by_leg_s"][leg] == pytest.approx(ms * MS), leg
    assert sum(idle["starved_by_leg_s"].values()) == pytest.approx(
        STARVED_MS * MS)


def test_two_sessions_in_different_legs_split_an_instant_in_halves():
    """[23,25): A on the wire, B opening: a millisecond each."""
    pieces = [(23 * MS, 25 * MS)]
    legs = [leg for turn in turntrace.turns_of(traced()["host"])
            for leg in turntrace.legs_of(turn)]
    got = turntrace.divide(pieces, legs)
    assert got.pop("wire") == pytest.approx(1 * MS)
    assert got.pop("open") == pytest.approx(1 * MS)
    assert sum(got.values()) == pytest.approx(0.0, abs=1e-12)


def test_the_five_shares_sum_to_the_starved_share():
    raw = traced()
    idle = turntrace.reduce(raw)["idle"]
    for name, ms in SHARE_METRICS.items():
        share = name[len("idle_starved_"):-len("_share")]
        assert idle["starved_shares"][share] == pytest.approx(
            100.0 * ms / IDLE_MS), name
    starved = hosttrace.reduce(raw)["idle"]["starved"]
    assert idle["five_sum"] == pytest.approx(
        100.0 * starved / (IDLE_MS * MS))
    assert idle["starved_share"] == pytest.approx(100.0 * 36 / 40)


def test_an_unsplit_away_counts_as_uncovered_and_a_wrong_stamp_is_counted():
    raw = traced()
    events = raw["host"][0]["events"]
    ids = dict(events[1][3])
    del ids["client"]  # session A's client is an older one
    events[1] = events[1][:3] + (ids,)
    got = turntrace.reduce(raw)
    # A's away [10,26) was 16 ms of the first gap, 3 of them shared with B
    assert got["idle"]["starved_by_leg_s"]["away"] == pytest.approx(
        (10 + 6 / 2) * MS)
    assert got["idle"]["starved_shares"]["uncovered"] == pytest.approx(
        100.0 * (7 + 13) / IDLE_MS)
    assert got["counts"]["unsplit"] == 1
    assert got["p50_ms"]["turn_client"] is None
    # client legs that outlast `away`: counted, cut, never negative seconds
    raw = traced()
    ids = dict(raw["host"][0]["events"][1][3], away_us=_us(8))
    raw["host"][0]["events"][1] = raw["host"][0]["events"][1][:3] + (ids,)
    got = turntrace.reduce(raw)
    assert got["counts"]["negative_wire"] == 1
    assert all(v >= 0 for v in got["idle"]["starved_by_leg_s"].values())
    # on a first turn it says the open frame waited longer than the request,
    # not that a stamp is wrong: cut all the same, not counted
    raw = traced()
    ids = dict(raw["host"][0]["events"][3][3], away_us=_us(8))
    raw["host"][0]["events"][3] = raw["host"][0]["events"][3][:3] + (ids,)
    got = turntrace.reduce(raw)
    assert got["counts"]["negative_wire"] == 0
    assert all(v >= 0 for v in got["idle"]["starved_by_leg_s"].values())


def test_the_turns_medians_and_the_codecs_share_of_the_servers_edges():
    got = turntrace.reduce(traced())
    for name, ms in P50_METRICS.items():
        assert got["p50_ms"][name[:-len("_ms_p50")]] == pytest.approx(ms)
    p50 = got["p50_ms"]
    # a decode turn's legs sum to its whole: away 16 + ingest 2 + served 12
    # + reply 2
    assert p50["turn_legs_sum"] == pytest.approx(32)
    assert p50["turn_served"] == pytest.approx(12)
    assert got["counts"] == {"first_turns": 1, "decode_turns": 1,
                             "unsplit": 0, "negative_wire": 0}
    assert got["codec_share"]["decode_of_ingest"] == pytest.approx(
        100.0 * 1.5 / (2 + 3))
    assert got["codec_share"]["encode_of_reply"] == pytest.approx(
        100.0 * 1 / (2 + 2))


def test_a_turn_is_read_off_the_stamp_the_trace_caught():
    """A long prompt's two stamps lie seconds apart: with B's `reply` after
    the trace's end its first turn still gives the legs its `arrive`
    carries, and nothing that needs the other stamp."""
    raw = traced()
    raw["host"][0]["events"] = [
        ev for ev in raw["host"][0]["events"]
        if not (ev[0] == turntrace.REPLY and ev[3]["session"] == "B")]
    got = turntrace.reduce(raw)
    assert got["counts"]["first_turns"] == 1
    for name in ("prefill_client_ms_p50", "prefill_upload_ms_p50"):
        assert got["p50_ms"][name[:-len("_ms_p50")]] == pytest.approx(
            P50_METRICS[name])
    assert got["p50_ms"]["first_served"] is None
    assert got["p50_ms"]["turn_server_edge"] == pytest.approx(4)  # A's


def _metric(name: str):
    path = ROOT / "cellbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("m_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.mark.parametrize("name", [*SHARE_METRICS, *P50_METRICS])
def test_a_metric_file_is_found_by_name_and_makes_no_number_up(
    tmp_path, name
):
    """What cellbench/run.py does with a name from BENCHMARK.json. On a
    trace with the stamps the file reads the cached reduction; on the
    parent's program (spans, no `bbtpu.turn.*`) and on an untraced run it
    returns None, not 0."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    listed = {m["name"]: m for m in bench["per_layer"]}[name]
    cells = [w["name"] for w in bench["workloads"]]
    assert listed["source"] == "program_span"
    if name.startswith("turn_"):
        assert (listed["moves"], listed["workloads"]) == (
            "gap_ms_p50", ["mistral7b-longdoc"])
    else:
        assert (listed["moves"], listed["workloads"]) == (
            "tokens_per_s", cells)
    read = _metric(name)
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    cache = tmp_path / turntrace.CACHE_NAME
    cache.write_text(json.dumps(turntrace.reduce(traced())))
    want = (100.0 * SHARE_METRICS[name] / IDLE_MS if name in SHARE_METRICS
            else P50_METRICS[name])
    assert read({"trace_dir": str(trace_dir)}) == pytest.approx(want)
    assert turntrace.reduce(spanless()) is None
    cache.write_text(json.dumps(turntrace.reduce(spanless())))
    assert read({"trace_dir": str(trace_dir)}) is None
    assert read({"trace_dir": str(tmp_path / "other" / "trace")}) is None


def test_a_trace_with_no_device_plane_reads_the_turns_and_no_share():
    """The CPU rehearsal's trace: host planes only."""
    got = turntrace.reduce({"device": [], "host": traced()["host"]})
    assert got["idle"] is None
    assert got["p50_ms"]["turn_client"] == pytest.approx(10)


def test_shares_that_do_not_sum_to_the_starved_share_read_none_and_say_why(
    tmp_path, monkeypatch
):
    """A division that does not add up to hosttrace's own `starved` is an
    error the file carries: the five shares read None, the run's other
    numbers are kept, and the child does not crash."""
    real = hosttrace._attribute_idle

    def other_walk(planes, timeline):
        return {**real(planes, timeline), "starved": 30 * MS}

    monkeypatch.setattr(hosttrace, "_attribute_idle", other_walk)
    got = turntrace.reduce(traced())
    assert "90.000" in got["idle"]["error"] and "75.000" in got["idle"]["error"]
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    (tmp_path / turntrace.CACHE_NAME).write_text(json.dumps(got))
    ctx = {"trace_dir": str(trace_dir)}
    for name in SHARE_METRICS:
        assert _metric(name)(ctx) is None, name
    assert _metric("turn_client_ms_p50")(ctx) == pytest.approx(10)
