"""The compute thread's tasks over the window, by kind and by leg.

The server keeps one account of every task its compute thread ran
(bloombee_tpu/server/compute_queue.py `_WorkerAccount`, fed by the span stack
of bloombee_tpu/utils/jitwatch.py): per kind of dispatch (`decode` | `chunk` |
`fused` | `other`) the tasks, their wall and thread-CPU milliseconds, each
leg's self time (`bbtpu.pack`, `bbtpu.h2d`, `jit_call`, ..., `unnamed` for
what no span covers), and, in the one task of 32 it reads in full, the
thread's CPU beside the wall (`cpu_ms` against `cpu_wall_ms`) and what each
launch found the device doing. It is
summed over the server's life in `rpc_info["host_path"]`, and once more under
`rpc_info["memory"]["host_path"]`, which the counter snapshots keep. This file
takes the WINDOW's share, `info1 - info0`: all 51 s, the profiler off but for
the traced 5 s of a `--trace 1` run.

A metric file calls `reduced(ctx)`; the first call also writes
`<work dir>/hostpath.json` (every kind, every leg, wall and CPU, totals and
per-task means: what PERF.md quotes). None for a program without the account
(the parent of the PR that brought it) or a server that ran with the witness
off. From an untraced run, whose per-layer metrics nobody reads:

    python cellbench/hostpath.py .cache/cellbench/<cell>/loadgen.json <out.json>
"""

from __future__ import annotations

import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from cellbench import hosttrace  # noqa: E402
from cellbench.hosttrace import share  # noqa: E402,F401  (the metric files')

CACHE_NAME = "hostpath.json"
_SUMS = ("n", "wall_ms", "full_n", "cpu_ms", "cpu_wall_ms", "launches",
         "launches_on_idle", "jit_idle_ms", "jit_busy_ms")


def _account(info: dict | None) -> dict | None:
    return ((info or {}).get("memory") or {}).get("host_path")


def _minus(after: dict, before: dict) -> dict:
    """One kind's record over the window (a kind or a leg the server had not
    met when the window opened counts from zero)."""
    out = {k: after.get(k, 0) - before.get(k, 0) for k in _SUMS}
    legs0 = before.get("legs") or {}
    out["legs"] = {
        leg: {k: v - (legs0.get(leg) or {}).get(k, 0.0)
              for k, v in rec.items()}
        for leg, rec in after["legs"].items()
    }
    return out


def _plus(a: dict, b: dict) -> dict:
    out = {k: a.get(k, 0) + b.get(k, 0) for k in _SUMS}
    legs = {leg: dict(rec) for leg, rec in (a.get("legs") or {}).items()}
    for leg, rec in b["legs"].items():
        into = legs.setdefault(leg, {})
        for k, v in rec.items():
            into[k] = into.get(k, 0.0) + v
    out["legs"] = legs
    return out


def _means(rec: dict) -> dict:
    """What one task of the kind cost, in ms of wall, and each leg's, with
    the share of it the thread was ON the CPU, % (the CPU is read in the
    `full_n` tasks read in full and held against the wall of those,
    `cpu_wall_ms`; None where the window held none)."""
    n = rec["n"]
    return {
        "task_wall_ms": rec["wall_ms"] / n,
        "on_cpu_share": share(rec["cpu_ms"], rec["cpu_wall_ms"]),
        "legs_ms": {
            leg: {"wall_ms": leg_rec["wall_ms"] / n,
                  "on_cpu_share": share(leg_rec.get("cpu_ms"),
                                        leg_rec.get("cpu_wall_ms"))}
            for leg, leg_rec in sorted(
                rec["legs"].items(), key=lambda kv: -kv[1]["wall_ms"])
        },
    }


def window(info0: dict | None, info1: dict | None) -> dict | None:
    """{"kinds": {kind: record}, "all": record}, each record the window's
    sums plus `mean` (per task) where the window held a task of the kind."""
    before, after = _account(info0), _account(info1)
    if not after or before is None:
        return None
    kinds = {kind: _minus(rec, before.get(kind) or {})
             for kind, rec in sorted(after.items())}
    total: dict = {}
    for rec in kinds.values():
        total = _plus(total, rec)
    for rec in [*kinds.values(), total]:
        if rec["n"]:
            rec["mean"] = _means(rec)
    return {"kinds": kinds, "all": total}


def reduced(ctx: dict) -> dict | None:
    if "_hostpath" not in ctx:
        got = window(ctx.get("info0"), ctx.get("info1"))
        ctx["_hostpath"] = got
        trace_dir = hosttrace._trace_dir(ctx)
        if got is not None and trace_dir is not None \
                and trace_dir.parent.is_dir():
            (trace_dir.parent / CACHE_NAME).write_text(json.dumps(got))
    return ctx["_hostpath"]


def kind(ctx: dict, name: str) -> dict | None:
    """The window's record of one kind; None without the account or where
    the window held no task of the kind."""
    got = reduced(ctx)
    rec = got and got["kinds"].get(name)
    return rec if rec and rec["n"] else None


def total(ctx: dict) -> dict | None:
    got = reduced(ctx)
    return got["all"] if got and got["all"].get("n") else None


def idle_by_span_share(ctx: dict, *spans: str):
    """Of the traced idle seconds, those during which the innermost span
    open on the compute thread was one of `spans`, % (the traced 5 s, on the
    device's clock: `hosttrace.json` `idle.by_span_s`)."""
    idle = (hosttrace.reduced(ctx) or {}).get("idle")
    if not idle or "by_span_s" not in idle:
        return None
    return share(sum(idle["by_span_s"].get(s, 0.0) for s in spans),
                 idle["total_s"])


def main(argv: list[str]) -> int:
    got = json.loads(pathlib.Path(argv[0]).read_text())
    out = window(got.get("info0"), got.get("info1"))
    pathlib.Path(argv[1]).write_text(json.dumps(out))
    return 0 if out is not None else 3


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
