"""A family's own device scopes in a profiler trace, reduced: ONE reducer
that takes the scopes as arguments.

`cellbench/hosttrace.py` knows the attention and MLP scopes of a layer;
`ssmtrace.py` and `mlatrace.py` each read one family's further scopes with a
copy of the same walk. This file is that walk once, for any list of scopes
(`jax.named_scope` names on an op's `op_name` path, the innermost listed one
wins), so that the next family with scopes of its own adds metric readers and
no reducer. Through `hosttrace.parse` (the only part that touches the
`.xplane.pb`) and `trace.program_kind` (decode | chunk | fused):

    runs     per kind, one {scope: self ms} a run of `span_step_packed` /
             `span_step_ragged` (the trace's first and last run, cut by its
             edges, are left out): a metric takes the median over runs of
             the scopes it sums (`median_ms`)
    move_s   copy / dynamic-slice / dynamic-update-slice ops under the
             `move_scope`, and `busy_s`, the device's busy time

A metric file calls `reduced(ctx, name, scopes, move_scope)`: the first call
parses in a CHILD process and keeps the JSON beside the trace under `name`,
as hosttrace does. A program without the scopes (the parent of the PR that
brought them, another family) or a trace without a device plane reads as
None: no number is made up.

    python cellbench/scopetrace.py <trace dir> <out.json> <move scope> <scope>...
"""

from __future__ import annotations

import bisect
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

KINDS = ("decode", "chunk", "fused")


def scope_of(op_name: str, scopes: tuple[str, ...]) -> str | None:
    """The innermost of `scopes` on an HLO op_name path."""
    for part in reversed(op_name.split("/")):
        if part in scopes:
            return part
    return None


def reduce(raw: dict, scopes: tuple[str, ...], move_scope: str) -> dict | None:
    """From `hosttrace.parse()`'s plain lists; None where no op carries one
    of the scopes."""
    planes = raw["device"]
    busy = moves = 0.0
    by_scope: dict[str, float] = {}
    runs: dict[str, list[dict]] = {k: [] for k in KINDS}
    for plane in planes:
        ops = sorted(plane["ops"], key=lambda e: (e[1], -e[2]))
        own = hosttrace.self_seconds(ops)
        busy += xla.union_seconds([e[:3] for e in ops])[0]
        found = [scope_of(op[3], scopes) for op in ops]
        for op, scope, sec in zip(ops, found, own):
            if scope is None:
                continue
            by_scope[scope] = by_scope.get(scope, 0.0) + sec
            if scope == move_scope and hosttrace.is_move(op[0]):
                moves += sec
        mods = sorted(plane["modules"], key=lambda e: e[1])
        steps = [m for m in mods
                 if xla.PACKED_PROGRAM in m[0] or xla.FUSED_PROGRAM in m[0]]
        cut = ({id(m) for m in steps[:1] + steps[-1:]}
               if len(steps) > 2 else set())
        starts = [e[1] for e in ops]
        for mod in steps:
            if id(mod) in cut:
                continue
            name, start, dur = mod
            lo = bisect.bisect_left(starts, start - xla._EPS)
            hi = bisect.bisect_right(starts, start + dur + xla._EPS)
            kind = xla.program_kind(
                name, {xla.group_name(o[0]) for o in ops[lo:hi]})
            if kind is None:
                continue
            got = dict.fromkeys(scopes, 0.0)
            for i in range(lo, hi):
                if found[i] is not None:
                    got[found[i]] += own[i] * 1e3
            runs[kind].append(got)
    if not by_scope:
        return None
    n = len(planes)
    return {
        "busy_s": busy / n,
        "seconds_by_scope": {k: v / n for k, v in sorted(by_scope.items())},
        "move_s": moves / n,
        "runs": runs,
    }


def median_ms(got: dict | None, kind: str, *scopes: str) -> float | None:
    """Median over the runs of `kind` of the summed self time under
    `scopes`, in ms; None where there is no such run."""
    rows = [sum(run[s] for s in scopes) for run in (got or {"runs": {}})[
        "runs"].get(kind, ())]
    return statistics.median(rows) if rows else None


def reduced(ctx: dict, name: str, scopes: tuple[str, ...],
            move_scope: str) -> dict | None:
    """This run's reduction under `name`, parsed once in a child process and
    read back from `<work dir>/scopetrace.<name>.json`; None where there is
    nothing to read."""
    key = f"_scopetrace_{name}"
    if key not in ctx:
        got = None
        trace_dir = hosttrace._trace_dir(ctx)
        if trace_dir is not None:
            cache = trace_dir.parent / f"scopetrace.{name}.json"
            if not cache.exists() and trace_dir.exists():
                subprocess.run(
                    [sys.executable, str(HERE / "scopetrace.py"),
                     str(trace_dir), str(cache), move_scope, *scopes],
                    timeout=600, check=False)
            if cache.exists():
                got = json.loads(cache.read_text())
        ctx[key] = got
    return ctx[key]


def main(argv: list[str]) -> int:
    trace_dir, out = pathlib.Path(argv[0]), pathlib.Path(argv[1])
    move_scope, scopes = argv[2], tuple(argv[3:])
    try:
        path = xla.find_xplane(trace_dir)
    except FileNotFoundError:
        return 3
    got = reduce(hosttrace.parse(path), scopes, move_scope)
    tmp = out.with_suffix(".tmp")
    tmp.write_text(json.dumps(got))
    tmp.replace(out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
