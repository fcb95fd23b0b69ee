#!/usr/bin/env python3
"""cellbench: one run of one cell of BENCHMARK.json through the served path.

    python3 cellbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. This process (the parent) never imports JAX:
it reads BENCHMARK.json, finds the cell's configuration, traffic mix and
metric readers BY NAME under cellbench/ (a later PR adds a cell by adding
files, never by editing one), writes the seeded checkpoint, and starts the
programs a swarm operator and a swarm user would start, each a child in its
own process group logging to a file (copied from chip_smoke.py, which stays):

  registry  python -m bloombee_tpu.cli.run_registry              (no JAX)
  server    cellbench/serverproc.py -> bloombee_tpu.cli.run_server (the chip)
  loadgen   cellbench/loadgen.py: the client, CPU JAX, on its own cores
  judge     cellbench/judge.py: the plain float32 reference, on the chip
            after the server has exited (one process per chip), and the
            reduction of the server's profiler trace

Every earlier line of standard output is one JSON object; the last line is
the result: {"correct", "attempted", "failed", "metrics", "device"} and, with
--trace 1, "breakdown". --trace 0 reports the cell's end-to-end metrics,
--trace 1 its per-layer metrics. No accelerator, fewer chips than the cell
asks for, or no program beside cellbench/: non-zero exit and no result line.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import pathlib
import shutil
import signal
import socket
import subprocess
import sys
import time

T_START = time.time()
HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))

from cellbench import checkpoint, schedule, stats  # noqa: E402

# CELLBENCH_REHEARSAL=1: the CPU rehearsal (cellbench/tests): the server runs
# on the CPU with the Pallas kernels in interpret mode and reports no device
# number anyone may quote. Never set on the chip.
REHEARSAL = os.environ.get("CELLBENCH_REHEARSAL") == "1"


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


class RunFailure(Exception):
    """The run could not be completed; the message is the reason."""


class NoAccelerator(RunFailure):
    """No result line may be printed."""


# ----------------------------------------------------------------- children
class Children:
    """The processes the parent started, each in its own process group and
    logging to a file (an undrained pipe blocks a chatty child)."""

    def __init__(self, log_dir: pathlib.Path):
        self.log_dir = log_dir
        self.procs: dict[str, subprocess.Popen] = {}
        self.pids: dict[str, int] = {}

    def spawn(self, name: str, argv: list[str], env: dict) -> None:
        with open(self.log_dir / f"{name}.log", "w") as log:
            self.procs[name] = subprocess.Popen(
                argv, cwd=ROOT, env=env, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True,
            )
        self.pids[name] = self.procs[name].pid

    def tail(self, name: str, nbytes: int = 1500) -> str:
        try:
            data = (self.log_dir / f"{name}.log").read_bytes()[-nbytes:]
        except OSError:
            return ""
        return data.decode(errors="replace")

    def require_alive(self, *names: str) -> None:
        for name in names:
            rc = self.procs[name].poll()
            if rc is not None:
                raise RunFailure(
                    f"{name} exited with code {rc}: {self.tail(name)}")

    def wait(self, name: str, limit_s: float, watch: tuple[str, ...]) -> None:
        deadline = time.monotonic() + limit_s
        proc = self.procs[name]
        while proc.poll() is None:
            self.require_alive(*watch)
            if time.monotonic() > deadline:
                raise RunFailure(
                    f"{name} not done within {limit_s:.0f}s: {self.tail(name)}")
            time.sleep(0.1)
        if proc.returncode != 0:
            raise RunFailure(
                f"{name} exited with code {proc.returncode}: {self.tail(name)}")

    def stop(self, name: str, grace_s: float = 20.0) -> None:
        """SIGTERM (run_server drains), then SIGKILL for the whole group."""
        proc = self.procs.pop(name, None)
        if proc is None:
            return
        for sig, wait_s in ((signal.SIGTERM, grace_s), (signal.SIGKILL, 10)):
            try:
                os.killpg(proc.pid, sig)
            except ProcessLookupError:
                pass
            try:
                proc.wait(timeout=wait_s)
                break
            except subprocess.TimeoutExpired:
                continue
        if proc.poll() is None:
            raise RunFailure(f"{name} survived SIGKILL")

    def stop_all(self) -> None:
        for name in list(self.procs):
            self.stop(name, grace_s=3.0)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_port(port: int, limit_s: float, children: Children, name: str):
    deadline = time.monotonic() + limit_s
    while time.monotonic() < deadline:
        children.require_alive(name)
        try:
            with socket.create_connection(("127.0.0.1", port), timeout=1):
                return
        except OSError:
            time.sleep(0.1)
    raise RunFailure(f"{name} not listening on {port} after {limit_s:.0f}s")


# ------------------------------------------------------- finding by name
def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> tuple[dict, dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise RunFailure(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    config_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / config_entry["file"]).read_text())
    cell_file = HERE / "cells" / f"{name}.json"
    extra = json.loads(cell_file.read_text()) if cell_file.exists() else {}
    return cell, config, extra


def metric_names(bench: dict, cell: str, group: str) -> list[str]:
    return [m["name"] for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def read_metric(name: str, ctx: dict):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "cellbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(ctx)


def _cpu_seconds(pid: int) -> float:
    """CPU seconds (user + system, all threads) a process has used."""
    fields = pathlib.Path(f"/proc/{pid}/stat").read_text().rsplit(")", 1)[1].split()
    return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")


def core_sets() -> tuple[list[int], list[int]]:
    """Disjoint cores: the load generator's own, and the rest for the server
    and the registry. Three fifths go to the generator: it stands for N
    client machines, each doing a vocabulary-wide head matmul per token,
    while the server kept under 1.6 cores busy in every cell (my chip runs,
    PR 26). At least one core each. Each child pins itself before it
    imports JAX, which sizes its thread pools; the chip machine's kernel
    does not enforce the mask (PERF.md section 6), so the run also prints
    the CPU seconds per second each side really used."""
    cores = sorted(os.sched_getaffinity(0))
    k = min(max(1, round(0.6 * len(cores))), len(cores) - 1) if len(cores) > 1 else 0
    return cores[len(cores) - k:], cores[: len(cores) - k] or cores


# --------------------------------------------------------------------- run
def run(args, children: Children, work: pathlib.Path) -> dict:
    bench = load_benchmark()
    cell, config_file, extra = find_cell(bench, args.workload)
    harness = config_file["cellbench"]
    config = {k: v for k, v in config_file.items() if k != "cellbench"}
    traffic = schedule.load_traffic(cell["traffic"])
    unit = {m["name"]: m["unit"]
            for m in bench["end_to_end"] + bench["per_layer"]}
    loadgen_cores, server_cores = core_sets()
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".cache" / "xla")
    num_pages = extra.get("num_pages") or 2 * (
        -(-schedule.peak_live_tokens(traffic) // schedule.PAGE_TOKENS))
    cover = schedule.cover_plan(traffic, harness["prefill_chunk"])
    emit(phase="plan", workload=args.workload, config=cell["config"],
         traffic=cell["traffic"], seed=args.seed, seconds=args.seconds,
         trace=args.trace, reduced=harness["reduced"],
         cpu_count=os.cpu_count(), loadgen_cores=loadgen_cores,
         server_cores=server_cores, compile_cache=cache_dir,
         num_pages=num_pages, cover=cover, rehearsal=REHEARSAL,
         server_args=harness["server_flags"] + args.server_arg)

    env = dict(os.environ, PYTHONUNBUFFERED="1",
               JAX_COMPILATION_CACHE_DIR=cache_dir)
    env.pop("BENCH_RUN", None)  # the driver's own; nothing here reads it
    ckpt = work / "ckpt"
    control = work / "control"
    trace_dir = work / "trace"
    control.mkdir(parents=True)

    # checkpoint: the layer files first (the server reads only those), the
    # client's trio while the server loads
    t0 = time.time()
    layers = checkpoint.write_checkpoint(
        ckpt, config, args.seed, only="layers", workers=len(server_cores))
    t_layers = time.time() - t0

    reg_port, port = _free_port(), _free_port()
    children.spawn(
        "registry",
        [sys.executable, "-m", "bloombee_tpu.cli.run_registry",
         "--host", "127.0.0.1", "--port", str(reg_port)],
        env,
    )
    server_env = dict(env, BBTPU_JITWATCH="1",
                      BBTPU_JITWATCH_REPORT=str(work / "jitwatch.jsonl"))
    if args.trace:
        server_env["BENCH_TRACE_DIR"] = str(trace_dir)
    if REHEARSAL:
        server_env.update(JAX_PLATFORMS="cpu", BBTPU_PAGED_INTERPRET="1",
                          BBTPU_FLASH_INTERPRET="1")
    _wait_port(reg_port, 30.0, children, "registry")
    server_spawned_at = time.time()
    children.spawn(
        "server",
        [sys.executable, str(HERE / "serverproc.py"), str(control),
         ",".join(map(str, server_cores)), str(ckpt),
         "--model-uid", harness["uid"],
         "--registry", f"127.0.0.1:{reg_port}",
         "--blocks", f"0:{config['num_hidden_layers']}",
         "--host", "127.0.0.1", "--public-host", "127.0.0.1",
         "--port", str(port), "--num-pages", str(num_pages),
         "--drain-timeout", "2",
         *harness["server_flags"], *args.server_arg],
        server_env,
    )
    plan = {
        "ckpt": str(ckpt), "uid": harness["uid"], "config": config,
        "traffic": traffic, "cover": cover, "seed": args.seed,
        "seconds": args.seconds, "trace": bool(args.trace),
        "trace_at_s": 0.35 * args.seconds,
        "trace_len_s": min(5.0, max(0.3 * args.seconds, 0.5)),
        "registry_port": reg_port, "server_port": port,
        "server_spawned_at": server_spawned_at,
        "control_dir": str(control), "work_dir": str(work),
        "loadgen_cpus": loadgen_cores,
        "judged_entries": schedule.judged_entries(traffic, args.seed),
        "out": str(work / "loadgen.json"),
    }
    (work / "plan.json").write_text(json.dumps(plan))
    children.spawn(
        "loadgen",
        [sys.executable, str(HERE / "loadgen.py"), str(work / "plan.json")],
        dict(env, JAX_PLATFORMS="cpu"),
    )
    t0 = time.time()
    trio = checkpoint.write_checkpoint(
        ckpt, config, args.seed, only="client", workers=2)
    (control / "client_shard.ready").touch()
    t_trio = time.time() - t0

    started = control / "window.started"
    deadline = time.monotonic() + 1000.0
    while not started.exists():
        children.require_alive("registry", "server", "loadgen")
        if time.monotonic() > deadline:
            raise RunFailure(
                "the window never started: " + children.tail("loadgen"))
        time.sleep(0.05)
    setup_s = float(started.read_text()) - T_START
    cpu_a, wall_a = _cpu_seconds(children.pids["server"]), time.time()
    time.sleep(max(0.0, args.seconds - (time.time() - float(started.read_text()))))
    server_cores_used = (
        _cpu_seconds(children.pids["server"]) - cpu_a) / (time.time() - wall_a)
    children.wait("loadgen", args.seconds + 400.0,
                  watch=("registry", "server"))
    got = json.loads((work / "loadgen.json").read_text())
    children.stop("server")
    children.stop("registry", grace_s=3.0)

    device = dict(got["info_end"]["device"])
    if device["platform"] != "tpu" and not REHEARSAL:
        raise NoAccelerator(f"the server ran on {device['platform']}, not tpu")
    if device["count"] < cell["chips"]:
        raise NoAccelerator(
            f"the server saw {device['count']} devices, the cell asks for "
            f"{cell['chips']}")

    # judge (and trace reduction): the chip is free now. The profiler ran
    # from start_trace's return to stop_trace's call on the server's clock:
    # the idle share is taken over that interval.
    traced_s = None
    if args.trace and (control / "trace.done").exists():
        done = json.loads((control / "trace.done").read_text())
        traced_s = done["stop_began"] - done["start_done"]
    judge_plan = {
        "ckpt": str(ckpt), "config": config, "requests": got["judged"],
        "seed": args.seed, "uid": harness["uid"], "traced_s": traced_s,
        "reference_cache": str(ROOT / ".cache" / "cellbench_reference"),
        "width": -(-(max(traffic["prompt_tokens"])
                     + traffic["judge"]["new_tokens"]) // 128) * 128,
        "trace_dir": str(trace_dir) if args.trace else None,
        "out": str(work / "judge.json"),
    }
    (work / "judge_plan.json").write_text(json.dumps(judge_plan))
    t0 = time.time()
    judge_env = dict(env, JAX_PLATFORMS="cpu") if REHEARSAL else env
    children.spawn(
        "judge",
        [sys.executable, str(HERE / "judge.py"), str(work / "judge_plan.json")],
        judge_env,
    )
    children.wait("judge", 600.0, watch=())
    verdict = json.loads((work / "judge.json").read_text())
    t_judge = time.time() - t0
    trace = verdict.pop("trace", None)
    if args.trace and trace is None and not REHEARSAL:
        raise RunFailure("traced run, and no operation ran on the device")

    info0, info1, end = got["info0"], got["info1"], got["info_end"]
    mem = (end["memory"] or {}).get("device") or {}
    emit(phase="setup", setup_s=setup_s, checkpoint_layers_s=t_layers,
         checkpoint_client_s=t_trio, checkpoint_bytes=layers["bytes"] + trio["bytes"],
         **got["setup"], reference_s=t_judge,
         compiles_warmup={k: got["info_warm"][k] for k in (
             "xla_compiles", "compile_ms_total", "compile_cache_hits")})
    emit(phase="memory", bytes_in_use=mem.get("bytes_in_use"),
         peak_bytes_in_use=mem.get("peak_bytes_in_use"),
         bytes_limit=mem.get("bytes_limit"),
         span_params_bytes=end["memory"].get("span_params_bytes"),
         kv_arena_bytes=end["memory"].get("kv_arena_bytes"))
    late = sorted(got["loadgen"]["late_ms"])
    emit(phase="loadgen", cpus=got["loadgen"]["cpus"],
         server_cores_used_in_window=server_cores_used,
         loadgen_cores_busy_in_window=got["loadgen"]["cores_busy"],
         late_ms_max=late[-1] if late else None,
         late_ms_p50=late[len(late) // 2] if late else None,
         jax_platform=got["loadgen"]["jax_platform"])

    records = got["records"]
    in_window = [r for r in records if 0.0 <= r["due"] < args.seconds]
    failed = [r for r in in_window
              if r["error"] and not r["error"].startswith("cancelled")]
    ctx = {
        "records": records, "window_s": float(args.seconds), "info0": info0,
        "info1": info1, "info_end": end, "trace": trace, "config": config,
        "traffic": traffic, "device_kind": device["kind"],
        "chips": cell["chips"], "setup_s": setup_s,
        "prefill_chunk": harness["prefill_chunk"],
    }
    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for name in metric_names(bench, args.workload, group):
        # --seconds 0 (a control run: only the judged requests) has no window
        value = read_metric(name, ctx) if args.seconds > 0 else None
        if value is not None:
            metrics[name] = {"value": value, "unit": unit[name]}
    if args.seconds > 0:
        # every host-clock candidate, admitted in this cell or not: the
        # builder's sets decide admission from these lines (PERF.md section 4)
        gaps = stats.window_gaps_ms(records, args.seconds)
        ttfts = stats.window_ttfts_ms(records, args.seconds)
        emit(phase="candidates", gap_ms_p50=stats.percentile(gaps, 50),
             gap_ms_p95=stats.percentile(gaps, 95),
             ttft_ms_p50=stats.percentile(ttfts, 50),
             tokens_per_s=stats.window_tokens(records, args.seconds)
             / args.seconds / cell["chips"],
             gaps=len(gaps), requests=len(ttfts))
    if args.trace and trace:
        emit(phase="trace", programs=trace["programs"],
             programs_run=trace["programs_run"],
             ops_span_s=trace["ops_span_s"], traced_s=traced_s,
             notes=ctx.get("notes"))

    # ---- correctness: every number compared, beside its limit
    limit = harness["logit_error_limit"]
    int8_limit = harness["int8_projection_limit"]
    compiles = (info1["steady_state_recompiles"]
                - info0["steady_state_recompiles"])
    compared = {
        "logit_err_median": [verdict["err_median"], limit],
        "int8_projection_median": [verdict["int8_projection_median"],
                                   int8_limit],
        "kernel_fallbacks": [end["kernel_fallbacks"], 0],
        "warmup_failures": [end["warmup_failures"], 0],
    }
    counters = ("xla_compiles", "steady_state_recompiles",
                "compile_cache_hits")
    # a span-step program compiled inside the window is the program's cost
    # under this traffic (its buckets depend on arrival times), reported as
    # `compiles_in_window`: it does not make the outputs wrong
    emit(phase="correctness", compared=compared, judge=verdict,
         compiles_in_window=compiles,
         compile_events={
             "server_warmup": {k: got["info_before_warm"][k] for k in counters},
             "cover": {k: got["info_swept"][k] - got["info_before_warm"][k]
                        for k in counters},
             "schedule_pass": {k: got["info_warm"][k] - got["info_swept"][k]
                               for k in counters},
             "window": {k: info1[k] - info0[k] for k in counters},
             "after_window": {k: end[k] - info1[k] for k in counters}})
    correct = None not in (limit, int8_limit) and all(
        v <= lim for v, lim in compared.values())
    result = {
        "correct": bool(correct), "attempted": len(in_window),
        "failed": len(failed), "metrics": metrics,
        "device": {"platform": device["platform"], "kind": device["kind"],
                   "count": device["count"],
                   "memory_peak_bytes": mem.get("peak_bytes_in_use")},
    }
    if args.trace and trace:
        result["device"].update(busy_s=trace["busy_s"],
                                window_s=trace["window_s"])
        result["breakdown"] = trace["breakdown"]
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=51)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--server-arg", action="append", default=[],
                        help="extra run_server argument (the control: "
                             "--server-arg=--weight-quant --server-arg=int8)")
    args = parser.parse_args(argv)
    if not (ROOT / "bloombee_tpu" / "cli" / "run_server.py").exists():
        print(f"no bloombee_tpu checkout next to {HERE}", file=sys.stderr)
        return 2
    work = ROOT / ".cache" / "cellbench" / args.workload
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    children = Children(work)

    def bail(signum, _frame):
        raise RunFailure(f"signal {signal.Signals(signum).name}")

    signal.signal(signal.SIGTERM, bail)
    signal.signal(signal.SIGINT, bail)
    result, fault = None, None
    try:
        result = run(args, children, work)
    except RunFailure as e:
        fault = e
    finally:
        try:
            children.stop_all()
        except RunFailure as e:
            fault = fault or e
        # 7-10 GB of weights and the trace: not kept between runs
        shutil.rmtree(work / "ckpt", ignore_errors=True)
        shutil.rmtree(work / "trace", ignore_errors=True)
    emit(phase="summary", fault=str(fault) if fault else None,
         children=children.pids, parent_imported_jax="jax" in sys.modules,
         wall_s=round(time.time() - T_START, 1))
    if isinstance(fault, NoAccelerator) or result is None:
        print(f"cellbench: no result: {fault}", file=sys.stderr)
        return 4
    if fault is not None:
        result["correct"] = False
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
