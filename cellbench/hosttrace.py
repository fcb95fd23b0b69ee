"""Host spans and device scopes of a profiler trace (.xplane.pb), reduced.

`cellbench/trace.py` reads XLA's own names. This file reads what the PROGRAM
wrote into the same trace (bloombee_tpu/utils/jitwatch.py, runtime/layer_body.py):

- on the host plane, the `bbtpu.*` spans with their ids: `bbtpu.task` on the
  compute thread's line (ids `task`, `starved_us`, `hop_us`, for a group also
  `members`, `rows`, `kinds`) holding `bbtpu.dispatch`, `bbtpu.pack`,
  `bbtpu.h2d`, `bbtpu.jit.<function>`, `bbtpu.commit`, `bbtpu.slice`;
  `bbtpu.enqueue` on the event loop's line; `bbtpu.fetch` and `bbtpu.codec.*`
  on pool threads;
- on a device plane, every op event's `tf_op` stat: the HLO `op_name`, whose
  path holds the layer's `jax.named_scope` (`.../while/body/.../arena_write/
  dynamic_update_slice`). The chip's trace keeps it on the event's METADATA,
  which `jax.profiler.ProfileData` does not hand out, so the file is parsed
  as the protobuf it is (schema below; google.protobuf only, no JAX).

`reduce()` is pure Python over plain lists (tests/test_hosttrace.py holds a
synthetic trace); `parse()` is the only part that touches the file. A metric
file calls `reduced(ctx)`: the first call parses in a CHILD process (the
parent of a run never holds a 100 MB protobuf, and a parse that dies costs a
metric, not the run) and keeps the JSON beside the trace, in the run's work
directory, where the next metric file finds it.

    python cellbench/hosttrace.py <trace dir> <out.json>
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

from cellbench import trace as xla  # noqa: E402  (names and predicates shared)

# the layer's named scopes (bloombee_tpu/runtime/layer_body.py, ops/moe.py)
SCOPES = ("norm", "attn_proj", "arena_write", "arena_gather", "attention",
          "mlp", "moe_router", "moe_experts")
ARENA_IO = ("arena_write", "arena_gather")
MLP = ("mlp", "moe_router", "moe_experts")
IDLE_CLASSES = ("starved", "hop", "pre_dispatch", "jit_call", "post_dispatch")
TASK, ENQUEUE, JIT = "bbtpu.task", "bbtpu.enqueue", "bbtpu.jit."
CACHE_NAME = "hosttrace.json"


# ------------------------------------------------------------------ parsing
def _xspace_class():
    """The XSpace message of tsl/profiler/protobuf/xplane.proto, built here
    (own pool, own package name) so that nothing but google.protobuf is
    imported: fields by number, as the profiler writes them."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="cellbench_xplane.proto", package="cellbench.xplane",
        syntax="proto3")

    def message(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, number, ftype, repeated, type_name in fields:
            m.field.add(
                name=fname, number=number, type=ftype,
                label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL,
                type_name=type_name and f".cellbench.xplane.{type_name}")
        return m

    I64, U64, STR, BYT, DBL, MSG = (F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING,
                                    F.TYPE_BYTES, F.TYPE_DOUBLE, F.TYPE_MESSAGE)
    message("XStat", ("metadata_id", 1, I64, 0, None),
            ("double_value", 2, DBL, 0, None), ("uint64_value", 3, U64, 0, None),
            ("int64_value", 4, I64, 0, None), ("str_value", 5, STR, 0, None),
            ("bytes_value", 6, BYT, 0, None), ("ref_value", 7, U64, 0, None))
    message("XEvent", ("metadata_id", 1, I64, 0, None),
            ("offset_ps", 2, I64, 0, None), ("duration_ps", 3, I64, 0, None),
            ("stats", 4, MSG, 1, "XStat"), ("num_occurrences", 5, I64, 0, None))
    message("XLine", ("id", 1, I64, 0, None), ("name", 2, STR, 0, None),
            ("timestamp_ns", 3, I64, 0, None), ("events", 4, MSG, 1, "XEvent"))
    message("XEventMetadata", ("id", 1, I64, 0, None), ("name", 2, STR, 0, None),
            ("stats", 5, MSG, 1, "XStat"))
    message("XStatMetadata", ("id", 1, I64, 0, None), ("name", 2, STR, 0, None))
    for entry, value in (("EventEntry", "XEventMetadata"),
                         ("StatEntry", "XStatMetadata")):
        e = message(entry, ("key", 1, I64, 0, None), ("value", 2, MSG, 0, value))
        e.options.map_entry = True
    message("XPlane", ("id", 1, I64, 0, None), ("name", 2, STR, 0, None),
            ("lines", 3, MSG, 1, "XLine"),
            ("event_metadata", 4, MSG, 1, "EventEntry"),
            ("stat_metadata", 5, MSG, 1, "StatEntry"))
    message("XSpace", ("planes", 1, MSG, 1, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("cellbench.xplane.XSpace"))


def _stat_values(stats, stat_names) -> dict:
    out = {}
    for st in stats:
        name = stat_names.get(st.metadata_id)
        if name is None:
            continue
        if st.str_value:
            out[name] = st.str_value
        elif st.ref_value:
            out[name] = stat_names.get(st.ref_value, "")
        elif st.int64_value:
            out[name] = st.int64_value
        elif st.uint64_value:
            out[name] = st.uint64_value
        elif st.double_value:
            out[name] = st.double_value
    return out


def parse(path: pathlib.Path) -> dict:
    """{"device": [{"name", "ops": [(name, start_s, dur_s, op_name)],
    "modules": [(name, start_s, dur_s)]}], "host": [{"line", "events":
    [(name, start_s, dur_s, ids)]}]}: device planes with XLA ops, and of the
    host plane only the lines and events of `bbtpu.*` spans."""
    space = _xspace_class()()
    space.ParseFromString(pathlib.Path(path).read_bytes())
    device, host = [], []
    for plane in space.planes:
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        if plane.name.startswith("/device:"):
            meta = {}
            for key, value in plane.event_metadata.items():
                stats = _stat_values(value.stats, stat_names)
                meta[key] = (value.name, str(stats.get("tf_op", "")))
            got = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {xla.OPS_LINE: "ops", xla.MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                base = line.timestamp_ns * 1e-9
                for ev in line.events:
                    name, op_name = meta.get(ev.metadata_id, ("", ""))
                    row = (name, base + ev.offset_ps * 1e-12,
                           ev.duration_ps * 1e-12)
                    got[key].append(row + (op_name,) if key == "ops" else row)
            if got["ops"]:
                device.append(got)
        elif plane.name.startswith("/host:"):
            names = {k: v.name for k, v in plane.event_metadata.items()
                     if v.name.startswith("bbtpu.")}
            if not names:
                continue
            for line in plane.lines:
                base = line.timestamp_ns * 1e-9
                events = [
                    (names[ev.metadata_id], base + ev.offset_ps * 1e-12,
                     ev.duration_ps * 1e-12,
                     _stat_values(ev.stats, stat_names))
                    for ev in line.events if ev.metadata_id in names]
                if events:
                    host.append({"line": line.id, "events": events})
    return {"device": device, "host": host}


# ---------------------------------------------------------------- reduction
def scope_of(op_name: str) -> str | None:
    """The innermost layer scope on an HLO op_name path, or None:
    `jit(f)/while/body/cond/branch_1_fun/arena_write/dynamic_update_slice:`
    -> `arena_write`; `jit(f)/while/body/squeeze:` -> None."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return None


def _unscoped(op_name: str) -> str:
    """Whose an op of a span-step program is when no layer scope is on its
    op_name: the scan's own body (slicing a layer's slab out of the stacked
    arena, stacking it back), the step outside the scan, or an op the
    compiler put in and gave no op_name at all (copies of loop state)."""
    if not op_name:
        return "(no op_name)"
    return "scan body" if "/while/body" in op_name else "step, outside the scan"


def is_move(name: str) -> bool:
    """cellbench/trace.py's own predicate for `arena_move_share`."""
    group = xla.group_name(name)
    return any(p.search(group) for p in xla.MOVE_PATTERNS)


def self_seconds(events) -> list[float]:
    """For events sorted by (start, -duration): each event's own seconds,
    an op that contains others (a `while` and its body) keeping only what
    its children leave. Same rule as trace.self_times, kept per event."""
    own = [e[2] for e in events]
    stack: list[tuple[int, float]] = []  # (index, end)
    for i, e in enumerate(events):
        start, dur = e[1], e[2]
        while stack and stack[-1][1] <= start + xla._EPS:
            stack.pop()
        if stack:
            parent, parent_end = stack[-1]
            own[parent] -= min(dur, parent_end - start)
        stack.append((i, start + dur))
    return own


def _overlap(segments, starts, a: float, b: float, into: dict) -> None:
    """Add the overlap of [a, b) with each (start, end, label) of the sorted,
    disjoint `segments` to into[label]."""
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(segments) and segments[i][0] < b:
        s, e, label = segments[i]
        got = min(b, e) - max(a, s)
        if got > 0:
            into[label] = into.get(label, 0.0) + got
        i += 1


def host_timeline(host: list[dict]) -> dict | None:
    """The compute thread's time, cut into labelled segments.

    The compute thread's line is the one that holds `bbtpu.task`. Inside a
    task: `jit_call` while a `bbtpu.jit.*` span is open, `pre_dispatch`
    before the first, `post_dispatch` after it (a task with none is all
    `pre_dispatch`). Between a task's end and the next task's start:
    `starved` until work exists, `hop` from then on. Work exists from the
    enqueue of the next task's own number (its `bbtpu.enqueue`, or, where
    the trace did not catch it, the `hop_us` the worker stamped on the
    task), or from an earlier enqueue that falls in between. Before the
    first task's own wait and after the last task the host planes say
    nothing."""
    lines = [ln["events"] for ln in host
             if any(e[0] == TASK for e in ln["events"])]
    if not lines:
        return None
    events = sorted((e for ln in lines for e in ln), key=lambda e: (e[1], -e[2]))
    enqueues = sorted((e for ln in host for e in ln["events"] if e[0] == ENQUEUE),
                      key=lambda e: e[1])
    enqueue_at = {str(e[3].get("task")): e[1] for e in enqueues}
    enqueue_times = [e[1] for e in enqueues]
    tasks = [e for e in events if e[0] == TASK]
    classes: list[tuple[float, float, str]] = []
    prev_end = None
    for name, start, dur, ids in tasks:
        end = start + dur
        if prev_end is None and "hop_us" in ids:
            # the first task of the trace: its own stamps say since when
            # the worker had been waiting for it
            prev_end = start - 1e-6 * (
                float(ids["hop_us"]) + float(ids.get("starved_us", 0)))
        if prev_end is not None and start > prev_end:
            ready = enqueue_at.get(str(ids.get("task")))
            if ready is None and "hop_us" in ids:
                ready = start - float(ids["hop_us"]) * 1e-6
            at = bisect.bisect_right(enqueue_times, prev_end)
            if at < len(enqueue_times) and enqueue_times[at] < start:
                ready = min(enqueue_times[at],
                            start if ready is None else ready)
            ready = start if ready is None else min(max(ready, prev_end), start)
            if ready > prev_end:
                classes.append((prev_end, ready, "starved"))
            if start > ready:
                classes.append((ready, start, "hop"))
        jits = [(e[1], e[1] + e[2]) for e in events
                if e[0].startswith(JIT) and start <= e[1] < end]
        at, label = start, "pre_dispatch"
        for js, je in jits:
            if js > at:
                classes.append((at, js, label))
            classes.append((max(js, at), min(je, end), "jit_call"))
            at, label = min(je, end), "post_dispatch"
        if end > at:
            classes.append((at, end, label))
        prev_end = end if prev_end is None else max(prev_end, end)
    return {"classes": classes, "inner": innermost(events), "tasks": tasks,
            "enqueues": len(enqueues)}


def innermost(events) -> list[tuple[float, float, str]]:
    """For one line's spans sorted by (start, -duration): disjoint
    (start, end, name) segments naming the innermost span open."""
    segments: list[tuple[float, float, str]] = []
    stack: list[tuple[str, float]] = []
    cursor = float("-inf")

    def advance(to: float) -> None:
        nonlocal cursor
        if stack and to > cursor:
            segments.append((cursor, to, stack[-1][0]))
        cursor = max(cursor, to)

    for name, start, dur, _ in events:
        while stack and stack[-1][1] <= start:
            advance(stack[-1][1])
            stack.pop()
        advance(start)
        stack.append((name, start + dur))
    while stack:
        advance(stack[-1][1])
        stack.pop()
    return segments


def _attribute_idle(planes, timeline) -> dict:
    """Every gap between merged device-busy intervals, by overlap with the
    host timeline; averaged over the planes."""
    by_class: dict[str, float] = {}
    by_span: dict[str, float] = {}
    total = 0.0
    classes, inner = timeline["classes"], timeline["inner"]
    class_starts = [s[0] for s in classes]
    inner_starts = [s[0] for s in inner]
    for plane in planes:
        _, merged = xla.union_seconds([e[:3] for e in plane["ops"]])
        for (_, a), (b, _) in zip(merged, merged[1:]):
            total += b - a
            _overlap(classes, class_starts, a, b, by_class)
            _overlap(inner, inner_starts, a, b, by_span)
    n = len(planes)
    out = {k: by_class.get(k, 0.0) / n for k in IDLE_CLASSES}
    out["total_s"] = total / n
    out["unattributed"] = max(0.0, out["total_s"] - sum(
        out[k] for k in IDLE_CLASSES))
    out["by_span_s"] = {k: v / n for k, v in sorted(
        by_span.items(), key=lambda kv: -kv[1])}
    return out


def _device(planes) -> dict:
    """Move ops by owner, and the attention / MLP seconds of each decode
    run, from the ops' scopes."""
    busy = slab = arena_io = other_moves = 0.0
    moves: dict[str, float] = {}
    scopes: dict[str, float] = {}
    by_op: dict[str, float] = {}
    attention_ms, mlp_ms = [], []
    for plane in planes:
        ops = sorted(plane["ops"], key=lambda e: (e[1], -e[2]))
        own = self_seconds(ops)
        busy += xla.union_seconds([e[:3] for e in ops])[0]
        mods = sorted(plane["modules"], key=lambda e: e[1])
        steps = [m for m in mods
                 if xla.PACKED_PROGRAM in m[0] or xla.FUSED_PROGRAM in m[0]]
        cut = ({id(m) for m in steps[:1] + steps[-1:]}
               if len(steps) > 2 else set())
        starts = [e[1] for e in ops]
        in_step = [False] * len(ops)
        step_ids = {id(m) for m in steps}
        for mod in mods:
            name, start, dur = mod
            lo = bisect.bisect_left(starts, start - xla._EPS)
            hi = bisect.bisect_right(starts, start + dur + xla._EPS)
            if id(mod) in step_ids:
                for i in range(lo, hi):
                    in_step[i] = True
            if id(mod) in cut:
                continue
            kind = xla.program_kind(
                name, {xla.group_name(o[0]) for o in ops[lo:hi]})
            if kind == "decode":
                att = mlp = 0.0
                for i in range(lo, hi):
                    scope = scope_of(ops[i][3])
                    if scope == "attention":
                        att += own[i]
                    elif scope in MLP:
                        mlp += own[i]
                attention_ms.append(att * 1e3)
                mlp_ms.append(mlp * 1e3)
        for i, op in enumerate(ops):
            scope = scope_of(op[3])
            if in_step[i]:
                key = scope or _unscoped(op[3])
                scopes[key] = scopes.get(key, 0.0) + own[i]
                key = f"{key}: {xla.group_name(op[0])}"
                by_op[key] = by_op.get(key, 0.0) + own[i]
            if not is_move(op[0]):
                continue
            if not in_step[i]:
                other_moves += own[i]
                owner = "(other program)"
            elif scope is None:
                slab += own[i]
                owner = _unscoped(op[3])
            elif scope in ARENA_IO:
                arena_io += own[i]
                owner = scope
            else:
                other_moves += own[i]
                owner = scope
            key = f"{owner}: {xla.group_name(op[0])}"
            moves[key] = moves.get(key, 0.0) + own[i]
    n = len(planes)
    top = lambda d: [[k, v / n] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:16]]
    if not any(k in SCOPES for k in scopes):
        # a program without the named scopes (the parent of the PR that
        # brought them): every op would read as the scan's, so nothing is
        return None
    return {
        "busy_s": busy / n,
        "scan_slab_move_s": slab / n, "arena_io_move_s": arena_io / n,
        "other_move_s": other_moves / n, "moves_by_owner": top(moves),
        "step_seconds_by_scope": top(scopes),
        "step_seconds_by_scope_and_op": top(by_op),
        "decode_runs": len(attention_ms),
        "step_attention_ms_p50": (statistics.median(attention_ms)
                                  if attention_ms else None),
        "step_mlp_ms_p50": statistics.median(mlp_ms) if mlp_ms else None,
    }


def _worker(tasks) -> dict | None:
    """The worker's account over the traced tasks, from what the worker
    stamped on each `bbtpu.task` (the per-task increments of
    rpc_info["worker"]). The first task's wait reaches back before the
    trace: the account runs from its START to the last task's end."""
    if len(tasks) < 2:
        return None
    wall = (tasks[-1][1] + tasks[-1][2]) - tasks[0][1]
    starved = sum(float(t[3].get("starved_us", 0)) for t in tasks[1:]) * 1e-6
    hop = sum(float(t[3].get("hop_us", 0)) for t in tasks[1:]) * 1e-6
    busy = sum(t[2] for t in tasks)
    return {"tasks": len(tasks), "wall_s": wall, "starved_s": starved,
            "hop_s": hop, "busy_s": busy,
            "accounted_share": 100.0 * (starved + hop + busy) / wall}


def _example_step(timeline) -> list | None:
    """One decode group's task near the middle of the trace, its spans in
    order with their durations: what PERF.md quotes."""
    tasks = timeline["tasks"]
    decode = [t for t in tasks if "decode" in str(t[3].get("kinds", ""))
              or t[3].get("class") == "decode"]
    if not decode:
        return None
    name, start, dur, ids = decode[len(decode) // 2]
    rows = [[name, 0.0, round(dur * 1e3, 4), ids]]
    for s, e, label in timeline["inner"]:
        if start <= s < start + dur and label != TASK:
            rows.append([label, round((s - start) * 1e3, 4),
                         round((e - s) * 1e3, 4)])
    return rows


def reduce(raw: dict) -> dict:
    """Everything the metric files read, from `parse()`'s plain lists. A
    part whose plane the trace lacks is None: no number is made up."""
    timeline = host_timeline(raw["host"])
    planes = raw["device"]
    spans: dict[str, list[float]] = {}
    for line in raw["host"]:
        for name, _, dur, _ in line["events"]:
            rec = spans.setdefault(name, [0, 0.0])
            rec[0] += 1
            rec[1] += dur
    return {
        "idle": (_attribute_idle(planes, timeline)
                 if planes and timeline else None),
        "device": _device(planes) if planes else None,
        "worker": _worker(timeline["tasks"]) if timeline else None,
        "example_step": _example_step(timeline) if timeline else None,
        "host_spans": {k: {"n": n, "total_ms": ms * 1e3}
                       for k, (n, ms) in sorted(spans.items())},
        "enqueues": timeline["enqueues"] if timeline else 0,
    }


# --------------------------------------------------- what a metric file calls
def _trace_dir(ctx: dict) -> pathlib.Path | None:
    """The run's trace directory: `ctx["trace_dir"]` where a caller gives
    one, else the work directory of the cell `cellbench/run.py` was started
    for (run.py removes the trace only after the metrics are read)."""
    if ctx.get("trace_dir"):
        return pathlib.Path(ctx["trace_dir"])
    argv = sys.argv
    for i, arg in enumerate(argv):
        if arg == "--workload" and i + 1 < len(argv):
            return ROOT / ".cache" / "cellbench" / argv[i + 1] / "trace"
        if arg.startswith("--workload="):
            return ROOT / ".cache" / "cellbench" / arg.split("=", 1)[1] / "trace"
    return None


def reduced(ctx: dict) -> dict | None:
    """The reduction of this run's trace, parsed once (in a child process)
    and then read back from `<work dir>/hosttrace.json`. None where there
    is no trace to read. A trace without a device plane (a CPU rehearsal)
    or without spans (a program older than the spans) is not that case:
    the parts it cannot fill are None one by one."""
    if "_hosttrace" not in ctx:
        got = None
        trace_dir = _trace_dir(ctx)
        if trace_dir is not None:
            cache = trace_dir.parent / CACHE_NAME
            if not cache.exists() and trace_dir.exists():
                subprocess.run(
                    [sys.executable, str(HERE / "hosttrace.py"),
                     str(trace_dir), str(cache)], timeout=600, check=False)
            if cache.exists():
                got = json.loads(cache.read_text())
        ctx["_hosttrace"] = got
    return ctx["_hosttrace"]


def share(part, whole):
    """100 * part / whole, or None where either is missing or whole is 0."""
    if part is None or not whole:
        return None
    return 100.0 * part / whole


def idle_share(ctx: dict, *classes: str):
    """Idle seconds of the given classes over all idle seconds, in %."""
    got = reduced(ctx)
    idle = got and got.get("idle")
    if not idle:
        return None
    return share(sum(idle[c] for c in classes), idle["total_s"])


def main(argv: list[str]) -> int:
    trace_dir, out = pathlib.Path(argv[0]), pathlib.Path(argv[1])
    try:
        path = xla.find_xplane(trace_dir)
    except FileNotFoundError:
        return 3
    got = reduce(parse(path))
    tmp = out.with_suffix(".tmp")
    tmp.write_text(json.dumps(got))
    tmp.replace(out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
