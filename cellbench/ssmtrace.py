"""The state-space mixer's scopes in a profiler trace, reduced.

`cellbench/hosttrace.py` knows the attention and MLP scopes of a layer
(`SCOPES`); a family with a state-space mixer (falcon_h1) runs it under three
more: `ssm_proj` (in_proj, gate, grouped norm, out_proj), `ssm_scan`
(convolution, dt, the recurrence: one step a decode row, the chunk form for a
prefill chunk) and `state_io` (a sequence's slot read out of and written into
the recurrent-state arena). This file reads those three from the same
`.xplane.pb`, through `hosttrace.parse` (the only part that touches the file)
and `trace.program_kind` (decode | chunk | fused), for the four metrics

    step_ssm_ms_p50      per DECODE run of `span_step_packed`: summed self
                         time of the ops under the three scopes; median
    chunk_ssm_ms_p50     the same per solo CHUNK run
    ssm_scan_roofline    the family's `ssm_scan_needs(config, chunk, "chunk")`
                         at the chip's peaks over the median time of
                         `ssm_scan` + `state_io` in a chunk run: the needs
                         count the state's bytes, so the time counts the
                         scope that moves them
    state_io_move_share  copy / dynamic-slice / dynamic-update-slice ops
                         under `state_io`, over device busy time

A metric file calls `reduced(ctx)`: the first call parses in a CHILD process
and keeps the JSON beside the trace, as hosttrace does. A program without the
scopes (the parent of the PR that brought them, a family without a mixer) or
a trace without a device plane reads as None: no number is made up.

    python cellbench/ssmtrace.py <trace dir> <out.json>
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

SSM_SCOPES = ("ssm_proj", "ssm_scan", "state_io")
CACHE_NAME = "ssmtrace.json"


def scope_of(op_name: str) -> str | None:
    """The innermost of the mixer's scopes on an HLO op_name path."""
    for part in reversed(op_name.split("/")):
        if part in SSM_SCOPES:
            return part
    return None


def reduce(raw: dict) -> dict | None:
    """From `hosttrace.parse()`'s plain lists; None where no op carries one
    of the scopes."""
    planes = raw["device"]
    busy = state_moves = 0.0
    by_scope: dict[str, float] = {}
    runs: dict[str, list[dict]] = {"decode": [], "chunk": [], "fused": []}
    by_op: dict[str, dict[str, float]] = {k: {} for k in runs}
    for plane in planes:
        ops = sorted(plane["ops"], key=lambda e: (e[1], -e[2]))
        own = hosttrace.self_seconds(ops)
        busy += xla.union_seconds([e[:3] for e in ops])[0]
        scopes = [scope_of(op[3]) for op in ops]
        for op, scope, sec in zip(ops, scopes, own):
            if scope is None:
                continue
            by_scope[scope] = by_scope.get(scope, 0.0) + sec
            if scope == "state_io" and hosttrace.is_move(op[0]):
                state_moves += sec
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
            got = dict.fromkeys(SSM_SCOPES, 0.0)
            for i in range(lo, hi):
                if scopes[i] is not None:
                    got[scopes[i]] += own[i]
                    op = f"{scopes[i]}: {xla.group_name(ops[i][0])}"
                    by_op[kind][op] = by_op[kind].get(op, 0.0) + own[i]
            runs[kind].append(got)
    if not by_scope:
        return None
    n = len(planes)

    def median_ms(kind: str, *which: str):
        rows = [sum(r[s] for s in which) * 1e3 for r in runs[kind]]
        return statistics.median(rows) if rows else None

    return {
        "busy_s": busy / n,
        "seconds_by_scope": {k: v / n for k, v in sorted(by_scope.items())},
        "state_io_move_s": state_moves / n,
        "runs": {k: len(v) for k, v in runs.items()},
        "step_ssm_ms_p50": median_ms("decode", *SSM_SCOPES),
        "chunk_ssm_ms_p50": median_ms("chunk", *SSM_SCOPES),
        "fused_ssm_ms_p50": median_ms("fused", *SSM_SCOPES),
        "chunk_ssm_scan_ms_p50": median_ms("chunk", "ssm_scan"),
        "chunk_scan_and_state_ms_p50": median_ms(
            "chunk", "ssm_scan", "state_io"),
        "step_ssm_scan_ms_p50": median_ms("decode", "ssm_scan"),
        "by_scope_ms_p50": {
            kind: {s: median_ms(kind, s) for s in SSM_SCOPES}
            for kind in runs},
        # mean ms a run of each kind, by scope and op: the six largest
        "ops_ms_mean": {
            kind: [[op, 1e3 * sec / len(runs[kind])] for op, sec in sorted(
                ops_.items(), key=lambda kv: -kv[1])[:6]]
            for kind, ops_ in by_op.items() if runs[kind]},
    }


def reduced(ctx: dict) -> dict | None:
    """This run's reduction, parsed once in a child process and read back
    from `<work dir>/ssmtrace.json`; None where there is nothing to read."""
    if "_ssmtrace" not in ctx:
        got = None
        trace_dir = hosttrace._trace_dir(ctx)
        if trace_dir is not None:
            cache = trace_dir.parent / CACHE_NAME
            if not cache.exists() and trace_dir.exists():
                subprocess.run(
                    [sys.executable, str(HERE / "ssmtrace.py"),
                     str(trace_dir), str(cache)], timeout=600, check=False)
            if cache.exists():
                got = json.loads(cache.read_text())
        ctx["_ssmtrace"] = got
    return ctx["_ssmtrace"]


def main(argv: list[str]) -> int:
    trace_dir, out = pathlib.Path(argv[0]), pathlib.Path(argv[1])
    try:
        path = xla.find_xplane(trace_dir)
    except FileNotFoundError:
        return 3
    got = reduce(hosttrace.parse(path))
    tmp = out.with_suffix(".tmp")
    tmp.write_text(json.dumps(got))
    tmp.replace(out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
