#!/usr/bin/env python3
"""What the host-path account costs a task, with the witness on.

A thousand synthetic compute-queue tasks of twelve nested spans each (the
spans a served step opens: bbtpu.task > bbtpu.dispatch > pack, h2d, the jit
region, counters > step, commit, slice, ...), their bodies empty, run through
`_WorkerAccount.wrap` with BBTPU_JITWATCH=1: microseconds a task, the median
of several rounds. Each tree named on the command line is measured in a child
process of its own (its `bloombee_tpu` imported from that tree), this tree
first:

    python scripts/host_path_overhead.py [--tasks 1000] [OTHER_TREE ...]

e.g. with the parent commit unpacked by `git archive` under /root/scratch.
Prints one JSON line a tree; the difference between two trees is what the
account added to every task (the spans and the worker's account were there
before it). Not a tier-1 test: a loaded machine moves the numbers."""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import statistics
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
ROUNDS = 7


def measure(tasks: int) -> dict:
    from bloombee_tpu.server.compute_queue import _WorkerAccount
    from bloombee_tpu.utils import jitwatch

    launch = getattr(jitwatch, "launch", None)  # the parent has none

    def step():
        with jitwatch.stopwatch("bbtpu.dispatch", session="s", step=1):
            with jitwatch.span("bbtpu.pack"):
                pass
            with jitwatch.span("bbtpu.pack"):
                pass
            with jitwatch.span("bbtpu.h2d"):
                pass
            if launch is not None:
                launch(bool, 0)
            with jitwatch.region("span_step_packed", "b8,t1,p64"):
                pass
            with jitwatch.span("bbtpu.counters"):
                with jitwatch.span("bbtpu.step", kind="decode", rows=8):
                    pass
            with jitwatch.span("bbtpu.commit"):
                pass
            with jitwatch.span("bbtpu.slice"):
                pass
        with jitwatch.span("bbtpu.group"):
            pass
        with jitwatch.span("bbtpu.slice", members=2):
            pass

    account = _WorkerAccount()
    per_task = []
    for _ in range(ROUNDS):
        t0 = time.perf_counter_ns()
        for i in range(tasks):
            account.wrap(step, time.perf_counter_ns(), task=i,
                         kinds="decode1")()
        per_task.append((time.perf_counter_ns() - t0) / tasks / 1e3)
    spans = jitwatch.host_spans()
    return {
        "tree": os.getcwd(), "tasks": tasks, "rounds": ROUNDS,
        "spans_a_task": sum(v["n"] for v in spans.values())
        // (tasks * ROUNDS),
        "us_a_task": round(statistics.median(per_task), 3),
        "us_a_task_min": round(min(per_task), 3),
        "accounted": bool(getattr(account, "host_path", dict)()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trees", nargs="*", help="other trees (the parent's)")
    ap.add_argument("--tasks", type=int, default=1000)
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        sys.path.insert(0, os.getcwd())
        print(json.dumps(measure(args.tasks)), flush=True)
        return 0
    for tree in [str(ROOT), *args.trees]:
        subprocess.run(
            [sys.executable, str(pathlib.Path(__file__).resolve()),
             "--child", "--tasks", str(args.tasks)],
            cwd=tree, check=True,
            env=dict(os.environ, BBTPU_JITWATCH="1", JAX_PLATFORMS="cpu"),
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
