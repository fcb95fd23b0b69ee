"""Latent attention's and the sparse layer's scopes in a profiler trace, reduced.

`cellbench/hosttrace.py` knows a layer's `attn_proj`, `attention`,
`arena_write`, `arena_gather`, `moe_router` and `moe_experts` (`SCOPES`). A
latent-attention family (deepseek_v2) nests five more inside them: `mla_q` and
`mla_kv` (the low-rank projections and their norms), `mla_absorb` (queries
through W_kvb's key half, the output through its value half), `mla_attention`
(the core: the paged decode kernel or the flash form) and `latent_io` (the
latent page's arena write and gather); its sparse layers run `moe_shared`
beside the routed experts. This file reads them from the same `.xplane.pb`,
through `hosttrace.parse` and `trace.program_kind`, the way `ssmtrace.py`
reads the mixer's, for the metrics

    chunk_mla_ms_p50 / step_mla_ms_p50   per solo CHUNK / DECODE run of
        `span_step_packed`: summed self time of the ops under the five
        latent-attention scopes; median
    chunk_moe_ms_p50     per chunk run: `moe_router` + `moe_shared` +
        `moe_experts`
    mla_attention_roofline / mla_decode_roofline   the family's
        `mla_attention_needs` at the chip's peaks over the median time of
        `mla_attention` + `latent_io` in a chunk / decode run (the scope
        that moves the counted bytes stands under the line)
    latent_io_move_share  copy / dynamic-slice / dynamic-update-slice ops
        under `latent_io`, over device busy time
    held_experts_hit_share  of the experts the server holds, the share a
        full chunk's rows reached, from the host spans `bbtpu.moe_reach`
        (one a step, stamped when the step's counters are read: per sparse
        layer `held_hit`, `routed_pairs_here`, `rows_with_held_expert`)

A program without the scopes (the parent of the PR that brought them, any
other family) or a trace without a device plane reads as None.

    python cellbench/mlatrace.py <trace dir> <out.json>
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

MLA_SCOPES = ("mla_q", "mla_kv", "mla_absorb", "mla_attention", "latent_io")
MOE_SCOPES = ("moe_router", "moe_shared", "moe_experts")
CORE = ("mla_attention", "latent_io")
CACHE_NAME = "mlatrace.json"


def scope_of(op_name: str) -> str | None:
    """The innermost of the scopes above on an HLO op_name path."""
    for part in reversed(op_name.split("/")):
        if part in MLA_SCOPES or part in MOE_SCOPES:
            return part
    return None


def _counts(text) -> list[int]:
    """A span's per-layer counts, `;`-separated (the profiler cuts an id at
    a comma, so the program's spans carry none)."""
    return [int(v) for v in str(text).split(";") if v != ""]


def step_spans(host: list[dict]) -> list[dict]:
    """Every `bbtpu.step` span, stamped as a latent-attention family's step
    is DISPATCHED: its kind ("decode" | "chunk" | "fused"), rows and context
    (its sequences' mean cached tokens before it; the profiler drops a 0)."""
    return [
        {"kind": str(ids.get("kind", "")), "rows": int(ids.get("rows", 0)),
         "context": int(ids.get("context", 0))}
        for line in host for name, _, _, ids in line["events"]
        if name == "bbtpu.step"]


def reach_spans(host: list[dict]) -> list[dict]:
    """Every `bbtpu.moe_reach` span, stamped when a finished step's counters
    are READ (up to a prefill later): the step's kind and rows and, per
    sparse layer, what the rows reached of the held experts."""
    return [
        {"kind": str(ids.get("kind", "")), "rows": int(ids.get("rows", 0)),
         **{k: _counts(ids.get(k, "")) for k in (
             "held_hit", "routed_pairs_here", "rows_with_held_expert")}}
        for line in host for name, _, _, ids in line["events"]
        if name == "bbtpu.moe_reach"]


def reduce(raw: dict) -> dict | None:
    """From `hosttrace.parse()`'s plain lists; None where no op carries one
    of latent attention's scopes."""
    planes = raw["device"]
    busy = io_moves = 0.0
    by_scope: dict[str, float] = {}
    runs: dict[str, list[dict]] = {k: [] for k in xla.KINDS}
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
            if scope == "latent_io" and hosttrace.is_move(op[0]):
                io_moves += sec
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
            got = dict.fromkeys(MLA_SCOPES + MOE_SCOPES, 0.0)
            for i in range(lo, hi):
                if scopes[i] is not None:
                    got[scopes[i]] += own[i]
                    op = f"{scopes[i]}: {xla.group_name(ops[i][0])}"
                    by_op[kind][op] = by_op[kind].get(op, 0.0) + own[i]
            runs[kind].append(got)
    if not any(s in by_scope for s in MLA_SCOPES):
        return None
    n = len(planes)

    def median_ms(kind: str, *which: str):
        rows = [sum(r[s] for s in which) * 1e3 for r in runs[kind]]
        return statistics.median(rows) if rows else None

    return {
        "steps": step_spans(raw["host"]),
        "reach": reach_spans(raw["host"]),
        "busy_s": busy / n,
        "seconds_by_scope": {k: v / n for k, v in sorted(by_scope.items())},
        "latent_io_move_s": io_moves / n,
        "runs": {k: len(v) for k, v in runs.items()},
        "chunk_mla_ms_p50": median_ms("chunk", *MLA_SCOPES),
        "step_mla_ms_p50": median_ms("decode", *MLA_SCOPES),
        "fused_mla_ms_p50": median_ms("fused", *MLA_SCOPES),
        "chunk_moe_ms_p50": median_ms("chunk", *MOE_SCOPES),
        "step_moe_ms_p50": median_ms("decode", *MOE_SCOPES),
        "chunk_core_ms_p50": median_ms("chunk", *CORE),
        "step_core_ms_p50": median_ms("decode", *CORE),
        "by_scope_ms_p50": {
            kind: {s: median_ms(kind, s) for s in MLA_SCOPES + MOE_SCOPES}
            for kind in runs},
        # mean ms a run of each kind, by scope and op: the eight largest
        "ops_ms_mean": {
            kind: [[op, 1e3 * sec / len(runs[kind])] for op, sec in sorted(
                ops_.items(), key=lambda kv: -kv[1])[:8]]
            for kind, ops_ in by_op.items() if runs[kind]},
    }


def reduced(ctx: dict) -> dict | None:
    """This run's reduction, parsed once in a child process and read back
    from `<work dir>/mlatrace.json`; None where there is nothing to read."""
    if "_mlatrace" not in ctx:
        got = None
        trace_dir = hosttrace._trace_dir(ctx)
        if trace_dir is not None:
            cache = trace_dir.parent / CACHE_NAME
            if not cache.exists() and trace_dir.exists():
                subprocess.run(
                    [sys.executable, str(HERE / "mlatrace.py"),
                     str(trace_dir), str(cache)], timeout=600, check=False)
            if cache.exists():
                got = json.loads(cache.read_text())
        ctx["_mlatrace"] = got
    return ctx["_mlatrace"]


def traced_steps(got: dict, kind: str, rows: int | None = None,
                 spans: str = "steps") -> list:
    """The steps of `kind` (with `rows`: of exactly that many rows) whose
    span fell into the trace: `spans` = "steps" as they were dispatched,
    "reach" as their counters were read."""
    return [s for s in got.get(spans, ()) if s["kind"] == kind
            and (rows is None or s["rows"] == rows)]


def core_roofline(ctx: dict, kind: str, rows: float, context: float):
    """`mla_attention_needs(config, rows, context, kind)` at the chip's
    peaks over the median time of the core scopes in a run of that kind, in
    percent; None where the trace, the family or the scopes are missing.

    The core's time follows the context, and a 5 s trace holds some forty
    chunk runs of four requests: THEIR contexts, not the window's. Where the
    program stamped its steps as it dispatched them (`bbtpu.step`: kind,
    rows, context), the needs are taken at the MEDIAN context (and mean
    rows) of the traced steps of this kind, the sample the median time is
    of; `rows` and `context` (the window's means) only stand in where no
    span says."""
    from cellbench import families, roofline

    got = reduced(ctx)
    core_ms = got and got.get(
        "chunk_core_ms_p50" if kind == "chunk" else "step_core_ms_p50")
    needs = getattr(families.of(ctx["config"]), "mla_attention_needs", None)
    if not core_ms or needs is None:
        return None
    steps = traced_steps(
        got, kind, ctx["prefill_chunk"] if kind == "chunk" else None)
    if steps:
        rows = statistics.mean(s["rows"] for s in steps)
        context = statistics.median(s["context"] for s in steps)
        ctx.setdefault("notes", {})[f"mla_{kind}_traced_steps"] = [
            len(steps), rows, context]
    least_s, bound = roofline.least_seconds(
        needs(ctx["config"], rows, context, kind), ctx["device_kind"])
    ctx.setdefault("notes", {})[f"mla_{kind}_roofline_bound"] = bound
    return 100.0 * least_s / (core_ms * 1e-3)


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
