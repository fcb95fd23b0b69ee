"""Reduction of a JAX profiler trace (.xplane.pb) to device metrics.

The program has no profiler spans or named scopes (ROADMAP S2), so everything
here is read from what XLA itself writes: on every `/device:TPU:n` plane the
line "XLA Ops" (one event per executed HLO op, nested where an op such as a
`while` contains others) and the line "XLA Modules" (one event per executed
program, named after the jitted function). `reduce()` is pure Python over
plain event lists so that it can be tested on a synthetic trace
(tests/test_trace.py); `load()` is the only part that touches the file.
"""

from __future__ import annotations

import bisect
import pathlib
import re
import statistics

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_EPS = 5e-10  # half a nanosecond: event times are whole nanoseconds
# ops that move KV-arena or activation bytes without computing on them
MOVE_PATTERNS = (re.compile(r"^copy"), re.compile(r"^dynamic-slice"),
                 re.compile(r"dynamic-update-slice"))
# programs, by the jitted function's name (bloombee_tpu/runtime/step.py).
# `span_step_packed` runs a decode group step (rows of one token each) AND a
# solo prefill chunk: the two are told apart by the attention kernel a run
# executes (the paged decode kernel only ever serves one-token rows).
PACKED_PROGRAM = "span_step_packed"
FUSED_PROGRAM = "span_step_ragged"  # prefill chunk fused with decode rows
DECODE_KERNEL = re.compile(r"^paged_decode_attention")
KINDS = ("decode", "chunk", "fused")


def find_xplane(trace_dir: pathlib.Path) -> pathlib.Path:
    files = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: pathlib.Path, data=None) -> list[dict]:
    """One dict per device plane: {"name", "ops": [(name, start_s, dur_s)],
    "modules": [...]}. Host planes are left out."""
    if data is None:
        from jax.profiler import ProfileData

        data = ProfileData.from_file(str(path))
    planes = []
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        got = {"name": plane.name, "ops": [], "modules": []}
        for line in plane.lines:
            key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
            if key is None:
                continue
            for ev in line.events:
                got[key].append(
                    (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
        if got["ops"]:
            planes.append(got)
    return planes


def group_name(op: str) -> str:
    """`fusion.123` -> `fusion`, `copy.4` -> `copy`: XLA's own names with the
    instance number taken off. The chip's trace names an op by its whole HLO
    line (`%fusion.3 = (bf16[...]) fusion(...)`): the name before ` = `."""
    op = op.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", op) or op


def union_seconds(events) -> tuple[float, list[tuple[float, float]]]:
    """Total length of the union of (name, start, dur) intervals, and the
    merged intervals themselves."""
    merged: list[list[float]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1] + _EPS:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return sum(b - a for a, b in merged), [(a, b) for a, b in merged]


def self_times(events) -> dict[str, float]:
    """Seconds per op group, counting each instant once: an op that contains
    others (a `while` and its body) keeps only the time its children leave."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [name, end, self_seconds]

    def close(until: float) -> None:
        while stack and stack[-1][1] <= until + _EPS:
            name, _, own = stack.pop()
            out[group_name(name)] = out.get(group_name(name), 0.0) + own

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def _median(xs):
    return statistics.median(xs) if xs else None


def program_kind(name: str, op_groups) -> str | None:
    """decode | chunk | fused for a run of a span-step program, None for any
    other program. `op_groups` are the group names of the ops it executed."""
    if FUSED_PROGRAM in name:
        return "fused"
    if PACKED_PROGRAM not in name:
        return None
    if any(DECODE_KERNEL.search(g) for g in op_groups):
        return "decode"
    return "chunk"


def _ops_by_module(mods, events):
    """For each program run (sorted by start), the group names of the ops
    that started inside it."""
    ops = sorted(events, key=lambda e: e[1])
    starts = [e[1] for e in ops]
    for name, start, dur in mods:
        lo = bisect.bisect_left(starts, start - _EPS)
        hi = bisect.bisect_right(starts, start + dur + _EPS)
        yield {group_name(o[0]) for o in ops[lo:hi]}


def reduce(planes: list[dict], traced_s: float | None = None) -> dict:
    """Device metrics of a traced window, averaged over the chips used.
    `traced_s` is how long the profiler ran (the server's own clock around
    start_trace / stop_trace): the idle share is taken over THAT interval.
    Without it (a synthetic trace) the window is first op to last op."""
    if not planes:
        raise ValueError("the trace holds no device plane with XLA ops")
    busy, span, idle_gaps, ops, moves = [], [], {}, {}, []
    programs: dict[str, dict] = {}
    by_kind: dict[str, list[float]] = {k: [] for k in KINDS}
    for plane in planes:
        events = plane["ops"]
        total, merged = union_seconds(events)
        busy.append(total)
        span.append(merged[-1][1] - merged[0][0])
        per_op = self_times(events)
        for name, sec in per_op.items():
            ops[name] = ops.get(name, 0.0) + sec / len(planes)
        moves.append(sum(
            sec for name, sec in per_op.items()
            if any(p.search(name) for p in MOVE_PATTERNS)))
        mods = sorted(plane["modules"], key=lambda e: e[1])
        steps = [m for m in mods if PACKED_PROGRAM in m[0] or FUSED_PROGRAM in m[0]]
        # the trace's edges may cut the first and the last span-step run of a
        # plane (such a run holds only some of its ops): left out
        cut = {id(m) for m in steps[:1] + steps[-1:]} if len(steps) > 2 else set()
        for mod, groups in zip(mods, _ops_by_module(mods, events)):
            if id(mod) in cut:
                continue
            name, _, dur = mod
            kind = program_kind(name, groups)
            rec = programs.setdefault(
                name, {"durs": [], "kinds": [], "kernels": set()})
            rec["durs"].append(dur)
            rec["kinds"].append(kind)
            rec["kernels"] |= {g for g in groups if "attention" in g}
            if kind:
                by_kind[kind].append(dur)
        # each idle gap is named after the program that ran before it
        starts = [m[1] for m in mods]
        for (_, gap_start), (gap_end, _) in zip(merged, merged[1:]):
            at = bisect.bisect_right(starts, gap_start)
            label = "after " + (group_program(mods[at - 1][0]) if at
                                else "trace start")
            idle_gaps[label] = idle_gaps.get(label, 0.0) + (
                gap_end - gap_start) / len(planes)
    n = len(planes)
    busy_s, span_s = sum(busy) / n, sum(span) / n
    # never shorter than what the ops themselves span: a profiler that kept
    # recording a little past stop_trace must not push busy over the window
    window_s = max(traced_s or 0.0, span_s)
    if traced_s:
        idle_gaps["before the first and after the last op"] = (
            window_s - span_s)
    top = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": busy_s, "window_s": window_s, "ops_span_s": span_s,
        "device_idle_share": 100.0 * (1.0 - busy_s / window_s),
        "arena_move_share": 100.0 * (sum(moves) / n) / busy_s,
        "server_step_ms_p50": _ms(_median(by_kind["decode"])),
        "server_prefill_ms_p50": _ms(_median(by_kind["chunk"])),
        "server_fused_ms_p50": _ms(_median(by_kind["fused"])),
        "programs_run": {k: len(v) for k, v in by_kind.items()},
        "programs": {
            # a program's kind: what most of its runs were (the run the
            # trace cut at its start holds only its last ops)
            name: {"runs": len(r["durs"]),
                   "kind": max(set(r["kinds"]), key=r["kinds"].count),
                   "median_ms": _ms(_median(r["durs"])),
                   "attention": sorted(r["kernels"]),
                   }
            for name, r in sorted(programs.items())},
        "breakdown": {"device_ops": top(ops), "idle_gaps": top(idle_gaps)},
    }


def group_program(name: str) -> str:
    """`jit_span_step_packed_impl(1234)` -> `jit_span_step_packed_impl`."""
    return re.sub(r"\(.*$", "", name)


def _ms(seconds):
    return None if seconds is None else seconds * 1e3
