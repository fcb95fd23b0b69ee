"""One account of a session's turn, on the server's clock.

A turn runs from one reply of a session leaving the server to the next one
leaving. The server stamps, on its own clock, the moment a reply was handed
to the socket and the moment the next request's last byte was read
(`wire/rpc.py` `_read_loop`); everything between is `away`. The client knows
how long each of ITS parts of that took, as durations, and says so in the
request it is sending anyway (`META_KEY`: integer microseconds). Durations
from one process and stamps from the other need no clock offset:

    away = wire + c_recv + c_head + c_other + c_embed + c_send

with `wire` (both directions together) the one unknown. The legs, in order:

    wire      reply handed to the socket -> the client read the frame; the
              request's header packed -> the server read its last byte
    c_recv    reply frame read -> tensors decoded and handed to the session
    c_head    final norm + LM head (`client/model.py`)
    c_other   the rest of the client's interval (selection, numpy, the
              event loop; in a chain, the other servers)
    c_embed   embedding of the next step's ids
    c_send    the request's tensors cast and encoded, up to the moment its
              header is packed. A frame cannot carry how long its own write
              took: the write overlaps the server's read and is `wire`. Of a
              prompt sent in PARTS (`client/session.py` `_part_rows`) the
              entry rides the first, so `c_send`, `wire` and `ingest` are
              the first part's; the later parts are made, sent and read
              while the span computes, under its `served`
    open      first turn only: `__aenter__` -> the stream's open frame
              written. It lies BEFORE the first turn's `away`, which the
              server starts where it read that open frame
    ingest    request's last byte read -> the step submitted to the
              compute queue (decode, rx queue, event loop)
    served    submitted -> fetch done (queue wait, tasks, d2h)
    reply     fetch done -> reply frame handed to the socket (the clock is
              read just before the write: `Stream.write_ns`)

The client half is `ClientLegs` (`client/session.py`), the server half
`ServerTurns` (one a session) feeding `TurnAccount` (`rpc_info["turn"]`) and
the zero-length spans `bbtpu.turn.arrive` / `bbtpu.turn.reply`.
"""

from __future__ import annotations

import time

from bloombee_tpu.utils import jitwatch

now_ns = time.perf_counter_ns  # read as turn.now_ns(): tests step it by hand

META_KEY = "turn"
CLIENT_LEGS = ("c_recv", "c_head", "c_other", "c_embed", "c_send")
CLASSES = ("prefill", "decode")
SUMS = ("away", *CLIENT_LEGS, "open", "wire", "ingest", "served", "reply")


def _us(ns: int) -> int:
    return max(0, int(ns)) // 1000


class ClientLegs:
    """The client's own parts of the interval between a reply and the next
    request, as durations. A caller that reports no head and no embed gets
    `c_other` for all of it."""

    def __init__(self):
        self._enter_ns = self._opened_ns = now_ns()
        self._first = True
        self._read_ns = 0  # the last reply's last byte read
        self._recv_ns = self._head_ns = self._embed_ns = 0

    def entering(self) -> None:
        """A chain is about to be opened (`__aenter__`, a rebuild)."""
        self._enter_ns = now_ns()

    def opened(self) -> None:
        """Every span's open frame is written: the next step is a first turn."""
        self._opened_ns = now_ns()
        self._first = True

    def note_head_ms(self, ms: float) -> None:
        self._head_ns += int(ms * 1e6)

    def note_embed_ms(self, ms: float) -> None:
        self._embed_ns += int(ms * 1e6)

    def replied(self, read_ns: int | None) -> None:
        """A reply reached the session; `read_ns` is where `_read_loop` read
        its last byte, and `c_recv` runs from there to now."""
        got = now_ns()
        self._read_ns = got if read_ns is None else read_ns
        self._recv_ns = got - self._read_ns

    def ride(self, meta: dict, stream, start_ns: int) -> None:
        """Put this turn's entry in `meta`, the request about to go out on
        `stream`, whose making began at `start_ns`: `[c_recv, c_head,
        c_other, c_embed, c_send]` and `open` on a first turn, in
        microseconds. `c_send` is filled in by the stream's `before_write`
        hook, where the frame's header is packed."""
        if self._first:
            whole = start_ns - self._opened_ns
            legs = [0, 0, whole - self._embed_ns, self._embed_ns, 0,
                    self._opened_ns - self._enter_ns]
        else:
            named = self._recv_ns + self._head_ns + self._embed_ns
            whole = start_ns - self._read_ns
            legs = [self._recv_ns, self._head_ns, whole - named,
                    self._embed_ns, 0]
        self._first = False
        self._head_ns = self._embed_ns = 0
        entry = meta[META_KEY] = [_us(x) for x in legs]

        def fill() -> None:
            entry[4] = _us(now_ns() - start_ns)

        stream.before_write = fill


def parse_entry(entry) -> list[int] | None:
    """A request's entry as `[five legs..., open]` (`open` 0 where absent),
    or None where an older client sent none or something else."""
    if (
        not isinstance(entry, (list, tuple)) or len(entry) not in (5, 6)
        or not all(isinstance(x, int) and not isinstance(x, bool) and x >= 0
                   for x in entry)
    ):
        return None
    return list(entry) + [0] * (6 - len(entry))


class TurnAccount:
    """`rpc_info["turn"]`: the legs' sums by the step's class, kept with the
    witness on or off (a clock read a leg)."""

    def __init__(self):
        self._us = {c: dict.fromkeys(("n", "negative_wire", *SUMS), 0)
                    for c in CLASSES}

    def add(self, cls: str, **us: int) -> None:
        rec = self._us[cls]
        for key, value in us.items():
            rec[key] += value

    def stats_ms(self) -> dict:
        return {
            cls: {
                "n": rec["n"],
                **{k + "_ms": round(rec[k] / 1e3, 3) for k in SUMS},
                "negative_wire": rec["negative_wire"],
            }
            for cls, rec in self._us.items()
        }


class ServerTurns:
    """One session's stamps on the server's clock. The session loop `noted`
    where each item's last byte was read; `arrive` is called on the event
    loop just before a step is submitted (in the same slice of the loop, so
    the note is the step's own), `fetched` when its output is on the host,
    `replied` when the reply has been handed to the socket. A step sent as
    several micro-batch frames is one turn: the first frame arrives, the
    last reply leaves."""

    def __init__(self, account: TurnAccount, session_id: str,
                 opened_ns: int | None):
        self._account = account
        self._session = session_id
        # where `away` starts: the stream's open frame read, then each reply
        self._left_ns = opened_ns
        self._read_ns: int | None = None  # the item in hand
        self._open: dict = {}  # step id -> the turn being served
        self.n = 0
        self.sum_away_us = self.sum_ingest_us = self.sum_reply_us = 0

    def noted(self, read_ns: int | None) -> None:
        """The item now handled was read at `read_ns` (None: it was pushed by
        another server, and its `ingest` starts where it arrives)."""
        self._read_ns = read_ns

    def arrive(self, step, cls: str, entry, frames: int = 1) -> None:
        if step in self._open:
            return  # a later micro-batch frame of a turn already arrived
        at = now_ns()
        read_ns = at if self._read_ns is None else self._read_ns
        away = (_us(read_ns - self._left_ns)
                if self._left_ns is not None else 0)
        ingest = _us(at - read_ns)
        legs = parse_entry(entry)
        sums = {"n": 1, "away": away, "ingest": ingest}
        # a session's first turn: its `away` began where the server read
        # the open frame and the client's interval where it had written it,
        # so its `wire` is the request's transit LESS the open frame's, and
        # one below zero says the open frame waited longer, not that a stamp
        # is wrong
        ids = {} if self.n else {"first": 1}
        if legs is not None:
            wire = away - sum(legs[:5])
            sums.update(zip(CLIENT_LEGS, legs), open=legs[5],
                        wire=max(0, wire),
                        negative_wire=int(wire < 0 and not ids))
            ids["client"] = ";".join(map(str, legs))
        self._account.add(cls, **sums)
        self.n += 1
        self.sum_away_us += away
        self.sum_ingest_us += ingest
        self._open[step] = {"cls": cls, "at": at, "frames": frames,
                            "fetched": at}
        with jitwatch.span("bbtpu.turn.arrive", session=self._session,
                           step=step, away_us=away, ingest_us=ingest,
                           **{"class": cls}, **ids):
            pass

    def fetched(self, step) -> None:
        turn = self._open.get(step)
        if turn is not None:
            turn["fetched"] = now_ns()

    def replied(self, step, wrote_ns: int | None) -> None:
        """The reply to `step` was handed to the socket at `wrote_ns`
        (`Stream.write_ns`, read just BEFORE the write; None for a frame a
        fault plan dropped, which left now. Read after the write, the
        stamp can wait for the interpreter behind another thread while the
        client already reads the frame, and `wire` goes negative). A reply
        that ends no turn (a retry answered from the record, a typed error,
        a decline) moves only the point `away` is taken from, and only once
        the session has had a turn: before it, `away` starts at the open
        frame and the client's interval where it wrote that frame."""
        turn = self._open.get(step)
        if turn is None:
            if wrote_ns is not None and self._left_ns is not None and self.n:
                self._left_ns = wrote_ns
            return
        at = now_ns() if wrote_ns is None else wrote_ns
        turn["frames"] -= 1
        if turn["frames"] > 0:
            return
        del self._open[step]
        self._left_ns = at
        served = _us(turn["fetched"] - turn["at"])
        reply = _us(at - turn["fetched"])
        self._account.add(turn["cls"], served=served, reply=reply)
        self.sum_reply_us += reply
        # the span is made now, `lag_us` after the write it stamps
        with jitwatch.span("bbtpu.turn.reply", session=self._session,
                           step=step, served_us=served, reply_us=reply,
                           lag_us=_us(now_ns() - at)):
            pass

    def dropped(self, step) -> None:
        """The step ended with no reply of its own (an expired deadline, a
        lost session, a send that raised): its turn is forgotten."""
        self._open.pop(step, None)
