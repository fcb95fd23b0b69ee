"""Server child: the program's own CLI (`bloombee_tpu.cli.run_server`) on the
cores the benchmark leaves it, with a profiler switch the load generator can
flip from outside.

    python cellbench/serverproc.py <control dir> <cpu list> <run_server args...>

Before JAX is imported the process pins itself to the given cores, away
from the load generator's CPU work (a vocabulary-wide matmul per token); in a
swarm they are different machines. Where the kernel stores the mask without
enforcing it (the chip machine, PERF.md section 6) this only sizes the thread
pools, and cellbench/run.py prints the CPU each side really used. With BENCH_TRACE_DIR set, a watcher thread starts
`jax.profiler` when `<control dir>/trace.start` appears and stops it on
`trace.stop`, then writes `trace.done`: only the process that holds the chip
can trace it. Nothing else differs from `python -m bloombee_tpu.cli.run_server`.
"""

from __future__ import annotations

import os
import pathlib
import sys
import threading
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent


def pin(cpu_list: str) -> list[int]:
    cpus = sorted(int(c) for c in cpu_list.split(",") if c)
    if cpus:
        os.sched_setaffinity(0, cpus)
    return sorted(os.sched_getaffinity(0))


def _watch(control: pathlib.Path, trace_dir: str) -> None:
    start, stop = control / "trace.start", control / "trace.stop"
    while not start.exists():
        time.sleep(0.05)
    # only now: the main thread imported jax long ago (two threads importing
    # it at once trip over its circular imports)
    import jax

    t0 = time.time()
    jax.profiler.start_trace(trace_dir)
    t1 = time.time()
    while not stop.exists():
        time.sleep(0.05)
    t2 = time.time()
    jax.profiler.stop_trace()
    (control / "trace.done").write_text(
        f'{{"start_began": {t0}, "start_done": {t1}, "stop_began": {t2}, '
        f'"stop_done": {time.time()}}}'
    )


def main(argv: list[str]) -> None:
    control, cpu_list, server_args = pathlib.Path(argv[0]), argv[1], argv[2:]
    print(f"[serverproc] cores {pin(cpu_list)}", flush=True)
    sys.path.insert(0, str(ROOT))
    trace_dir = os.environ.get("BENCH_TRACE_DIR")
    if trace_dir:
        threading.Thread(
            target=_watch, args=(control, trace_dir), daemon=True
        ).start()
    from bloombee_tpu.cli.run_server import main as run_server

    run_server(server_args)


if __name__ == "__main__":
    main(sys.argv[1:])
