"""What the sessions were doing while the device starved: a trace's
`bbtpu.turn.*` stamps laid out as each session's legs, and the device's
`starved` idle divided among them.

The server stamps every turn of a session twice, zero-length, on the
profiler's clock (`bloombee_tpu/wire/turn.py`): `bbtpu.turn.arrive` just
before a step is submitted (`away_us`, `ingest_us` and the client's own legs
as durations) and `bbtpu.turn.reply` when the reply has been handed to the
socket (`served_us`, `reply_us`; `lag_us`: how long before the stamp
the write was). A turn's legs are laid out BACKWARDS from
each stamp:

    ... | wire | c_recv | c_head | c_other | c_embed | c_send | wire | ingest |A
        ^ the reply before left                 the request's last byte ^
                                           | served | reply |P

(a session's first turn: `open` before the point `away` starts from, no reply
to receive, and all of `wire` on the request's side). Through
`hosttrace.parse` and `hosttrace.host_timeline`: the SAME gaps between merged
device-busy intervals and the SAME `starved` segments of the compute thread
as `hosttrace._attribute_idle` takes. Each instant of a starved gap is
divided equally among the legs that cover it; an instant none covers is
`uncovered` (between a request's last reply and the next session's first
arrival less `open`; a turn whose stamp fell after the trace stopped) and is
reported, never spread over the others. A turn of a client that sent no
entry has one leg, `away`, which counts as uncovered too.

    python cellbench/turntrace.py <trace dir> <out.json>

A metric file calls `reduced(ctx)`: parsed once in a CHILD process, kept as
`turntrace.json` beside the trace. A trace without the stamps (the parent of
the PR that brought them) reads as None: no number is made up.

What the numbers are good for. The traced window is 5 s and holds 3-8 first
turns a run, so the five `idle_starved_*_share` and the two `prefill_*_ms_p50`
swing up to twofold between runs of one tree (as `idle_starved_share`, their
base, does): they RANK the legs inside one run and are no yardstick between
runs. The per-turn medians over decode turns (`turn_*_ms_p50`: hundreds of
turns a window) are the numbers to compare.
"""

from __future__ import annotations

import json
import pathlib
import statistics
import subprocess
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from cellbench import hosttrace  # noqa: E402
from cellbench import trace as xla  # noqa: E402

ARRIVE, REPLY = "bbtpu.turn.arrive", "bbtpu.turn.reply"
CLIENT_LEGS = ("c_recv", "c_head", "c_other", "c_embed", "c_send")
# which of the five shares a leg's starved seconds count under
SHARE_OF = {**dict.fromkeys((*CLIENT_LEGS, "open"), "client"), "wire": "wire",
            "ingest": "ingest", "served": "reply", "reply": "reply",
            "away": "uncovered", "uncovered": "uncovered"}
SHARES = ("client", "wire", "ingest", "reply", "uncovered")
CACHE_NAME = "turntrace.json"
US = 1e-6


def turns_of(host: list[dict]) -> list[dict]:
    """One record a traced turn, from its two stamps joined by (session,
    step); a stamp whose twin the trace did not catch stands alone."""
    turns: dict[tuple, dict] = {}
    for line in host:
        for name, at, _, ids in line["events"]:
            if name not in (ARRIVE, REPLY):
                continue
            key = (str(ids.get("session", "")), int(ids.get("step", 0)))
            turn = turns.setdefault(key, {"session": key[0], "step": key[1]})
            if name == ARRIVE:
                legs = [int(v) for v in str(ids.get("client", "")).split(";")
                        if v != ""]
                turn.update(
                    arrive_at=at, cls=str(ids.get("class", "")),
                    first=bool(ids.get("first")),
                    away_us=int(ids.get("away_us", 0)),
                    ingest_us=int(ids.get("ingest_us", 0)),
                    client_us=legs[:5] if len(legs) >= 5 else None,
                    open_us=legs[5] if len(legs) > 5 else 0)
            else:
                turn.update(reply_at=at - int(ids.get("lag_us", 0)) * US,
                            served_us=int(ids.get("served_us", 0)),
                            reply_us=int(ids.get("reply_us", 0)))
    return sorted(turns.values(), key=lambda t: t.get(
        "arrive_at", t.get("reply_at", 0.0)))


def wire_us(turn: dict) -> int | None:
    """`away` less the client's own legs; None where it sent none."""
    if turn.get("client_us") is None:
        return None
    return turn["away_us"] - sum(turn["client_us"])


def legs_of(turn: dict) -> list[tuple[float, float, str]]:
    """The turn's legs as (start, end, name) intervals on the trace's clock."""
    out = []
    if "arrive_at" in turn:
        read = turn["arrive_at"] - turn["ingest_us"] * US
        out.append((read, turn["arrive_at"], "ingest"))
        left = read - turn["away_us"] * US
        wire = wire_us(turn)
        if wire is None:
            out.append((left, read, "away"))
        else:
            wire = max(0, wire) * US
            back = wire if turn["first"] else wire / 2
            if turn["open_us"]:
                out.append((left - turn["open_us"] * US, left, "open"))
            at = left + wire - back
            out.append((left, at, "wire"))
            for name, us in zip(CLIENT_LEGS, turn["client_us"]):
                # client legs that outlast `away` (a wrong stamp) are cut
                end = min(at + us * US, read - back)
                out.append((at, end, name))
                at = end
            out.append((read - back, read, "wire"))
    if "reply_at" in turn:
        fetched = turn["reply_at"] - turn["reply_us"] * US
        out.append((fetched, turn["reply_at"], "reply"))
        out.append((fetched - turn["served_us"] * US, fetched, "served"))
    return [(a, b, name) for a, b, name in out if b > a]


def starved_idle(planes, timeline) -> tuple[list, float]:
    """Per plane the (start, end) pieces of device idle under the compute
    thread's `starved` class, and all idle seconds (a plane's mean): the
    gaps and the segments of `hosttrace._attribute_idle`."""
    starved = [(a, b) for a, b, label in timeline["classes"]
               if label == "starved"]
    pieces, total = [], 0.0
    for plane in planes:
        _, merged = xla.union_seconds([e[:3] for e in plane["ops"]])
        got, i = [], 0
        for (_, a), (b, _) in zip(merged, merged[1:]):
            total += b - a
            while i < len(starved) and starved[i][1] <= a:
                i += 1
            j = i
            while j < len(starved) and starved[j][0] < b:
                lo, hi = max(a, starved[j][0]), min(b, starved[j][1])
                if hi > lo:
                    got.append((lo, hi))
                j += 1
        pieces.append(got)
    return pieces, total / len(planes)


def divide(pieces: list[tuple[float, float]],
           legs: list[tuple[float, float, str]]) -> dict[str, float]:
    """Seconds of `pieces` by leg name: each instant divided equally among
    the legs open at it, `uncovered` where none is. One sweep over the
    pieces' and the legs' edges."""
    edges = []
    for a, b in pieces:
        edges += [(a, 0, None), (b, 1, None)]
    for a, b, name in legs:
        edges += [(a, 2, name), (b, 3, name)]
    edges.sort(key=lambda e: e[0])
    by_leg: dict[str, float] = {}
    open_legs: dict[str, int] = {}
    inside, n_open, prev = 0, 0, 0.0
    for at, kind, name in edges:
        if inside and at > prev:
            if n_open:
                for leg, n in open_legs.items():
                    if n:
                        by_leg[leg] = by_leg.get(leg, 0.0) + (
                            at - prev) * n / n_open
            else:
                by_leg["uncovered"] = by_leg.get("uncovered", 0.0) + at - prev
        prev = at
        if kind == 0:
            inside += 1
        elif kind == 1:
            inside -= 1
        else:
            step = 1 if kind == 2 else -1
            open_legs[name] = open_legs.get(name, 0) + step
            n_open += step
    return by_leg


def _codec_share(host, legs, span: str, leg: str):
    """Seconds of the `span` events inside the union of the `leg` intervals
    over those intervals' summed length, in %."""
    whole = [(leg, a, b - a) for a, b, name in legs if name == leg]
    total, merged = xla.union_seconds(whole)
    if not total:
        return None
    segments = [(a, b, leg) for a, b in merged]
    starts = [s[0] for s in segments]
    got: dict[str, float] = {}
    for line in host:
        for name, at, dur, _ in line["events"]:
            if name == span:
                hosttrace._overlap(segments, starts, at, at + dur, got)
    return 100.0 * got.get(leg, 0.0) / sum(d for _, _, d in whole)


def _median(values):
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None


def reduce(raw: dict) -> dict | None:
    """From `hosttrace.parse()`'s plain lists; None where the trace holds no
    `bbtpu.turn.*` stamp."""
    turns = turns_of(raw["host"])
    if not turns:
        return None
    legs = [leg for turn in turns for leg in legs_of(turn)]
    out: dict = {"turns": len(turns)}

    timeline = hosttrace.host_timeline(raw["host"])
    planes = raw["device"]
    if planes and timeline:
        pieces, idle_s = starved_idle(planes, timeline)
        by_leg: dict[str, float] = {}
        for got in pieces:
            for leg, sec in divide(got, legs).items():
                by_leg[leg] = by_leg.get(leg, 0.0) + sec / len(planes)
        by_share = dict.fromkeys(SHARES, 0.0)
        for leg, sec in by_leg.items():
            by_share[SHARE_OF[leg]] += sec
        starved_s = hosttrace._attribute_idle(planes, timeline)["starved"]
        shares = {k: hosttrace.share(v, idle_s) for k, v in by_share.items()}
        starved_share = hosttrace.share(starved_s, idle_s)
        out["idle"] = {
            "total_s": idle_s, "starved_s": starved_s,
            "starved_share": starved_share,
            "starved_by_leg_s": dict(sorted(by_leg.items(),
                                            key=lambda kv: -kv[1])),
            "starved_shares": shares,
            "five_sum": sum(shares.values()) if idle_s else None,
        }
        # the five are a division of what hosttrace calls starved: where
        # they are not, the file says so, the shares read None and every
        # other number of the run is kept
        if idle_s and abs(sum(shares.values()) - starved_share) >= 0.5:
            out["idle"]["error"] = (
                f"the five shares sum to {sum(shares.values()):.3f}, "
                f"idle_starved_share is {starved_share:.3f}")
    else:
        out["idle"] = None

    # a turn is read off the stamps the trace caught: the client's legs,
    # `wire` and `ingest` off its `arrive`, `served` and `reply` off its
    # `reply` (a long prompt's two stamps lie seconds apart)
    split = [t for t in turns if t.get("client_us") is not None]
    first = [t for t in split if t["first"]]
    decode = [t for t in split if t["cls"] == "decode" and not t["first"]]

    def each(rows, fn, needs=()):
        return _median([fn(t) * 1e-3 for t in rows
                        if all(key in t for key in needs)])

    replied = ("reply_at",)
    client = lambda t: sum(t["client_us"])  # noqa: E731
    edge = lambda t: t["ingest_us"] + t["reply_us"]  # noqa: E731
    whole_turn = lambda t: (  # noqa: E731
        t["away_us"] + t["ingest_us"] + t["served_us"] + t["reply_us"])
    # a session's consecutive reply stamps: what the legs sum to by
    # construction, printed beside them
    by_session: dict[str, list[float]] = {}
    for t in sorted((t for t in turns if "reply_at" in t),
                    key=lambda t: t["reply_at"]):
        by_session.setdefault(t["session"], []).append(
            (t["reply_at"], t.get("cls"), t.get("first")))
    between = [(b[0] - a[0]) * 1e3 for rows in by_session.values()
               for a, b in zip(rows, rows[1:])
               if b[1] == "decode" and not b[2]]
    out["p50_ms"] = {
        "prefill_client": each(first, lambda t: t["open_us"] + sum(
            t["client_us"])),
        "prefill_upload": each(first, lambda t: t["client_us"][4]
                               + max(0, wire_us(t)) + t["ingest_us"]),
        "turn_client": each(decode, client),
        "turn_wire": each(decode, lambda t: max(0, wire_us(t))),
        "turn_server_edge": each(decode, edge, replied),
        "turn_served": each(decode, lambda t: t["served_us"], replied),
        "turn_legs_sum": each(decode, whole_turn, replied),
        "turn_between_reply_stamps": _median(between),
        **{"first_" + k: each(first, fn, needs) for k, fn, needs in (
            ("open", lambda t: t["open_us"], ()), ("wire", wire_us, ()),
            ("ingest", lambda t: t["ingest_us"], ()),
            ("served", lambda t: t["served_us"], replied),
            ("reply", lambda t: t["reply_us"], replied),
            *((leg, lambda t, i=i: t["client_us"][i], ())
              for i, leg in enumerate(CLIENT_LEGS)))},
        **{"turn_" + leg: each(decode, lambda t, i=i: t["client_us"][i])
           for i, leg in enumerate(CLIENT_LEGS)},
        "turn_ingest": each(decode, lambda t: t["ingest_us"]),
        "turn_reply": each(decode, lambda t: t["reply_us"], replied),
    }
    out["counts"] = {
        "first_turns": len(first), "decode_turns": len(decode),
        "unsplit": sum(1 for t in turns if "arrive_at" in t
                       and t["client_us"] is None),
        # (a first turn's `wire` is less the open frame's transit)
        "negative_wire": sum(1 for t in turns if (wire_us(t) or 0) < 0
                             and not t.get("first")),
    }
    out["codec_share"] = {
        "decode_of_ingest": _codec_share(
            raw["host"], legs, "bbtpu.codec.decode", "ingest"),
        "encode_of_reply": _codec_share(
            raw["host"], legs, "bbtpu.codec.encode", "reply"),
    }
    return out


# --------------------------------------------------- what a metric file calls
def reduced(ctx: dict) -> dict | None:
    """This run's reduction, parsed once in a child process and read back
    from `<work dir>/turntrace.json`; None where there is nothing to read."""
    if "_turntrace" not in ctx:
        got = None
        trace_dir = hosttrace._trace_dir(ctx)
        if trace_dir is not None:
            cache = trace_dir.parent / CACHE_NAME
            if not cache.exists() and trace_dir.exists():
                subprocess.run(
                    [sys.executable, str(HERE / "turntrace.py"),
                     str(trace_dir), str(cache)], timeout=600, check=False)
            if cache.exists():
                got = json.loads(cache.read_text())
        ctx["_turntrace"] = got
    return ctx["_turntrace"]


def starved_share(ctx: dict, share: str):
    """Starved idle under the legs of `share`, over all idle seconds, in %;
    None where the five do not sum to `idle_starved_share` (`idle.error`)."""
    got = reduced(ctx)
    idle = got and got.get("idle")
    if not idle or idle.get("error"):
        return None
    return idle["starved_shares"][share]


def p50_ms(ctx: dict, name: str):
    got = reduced(ctx)
    return got["p50_ms"][name] if got else None


def main(argv: list[str]) -> int:
    trace_dir, out = pathlib.Path(argv[0]), pathlib.Path(argv[1])
    try:
        path = xla.find_xplane(trace_dir)
    except FileNotFoundError:
        return 3
    got = reduce(hosttrace.parse(path))
    print(json.dumps({"turntrace": got and {
        k: got[k] for k in ("idle", "counts", "codec_share")}}),
        file=sys.stderr)
    tmp = out.with_suffix(".tmp")
    tmp.write_text(json.dumps(got))
    tmp.replace(out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
