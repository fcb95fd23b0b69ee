#!/usr/bin/env python3
"""chip_smoke.py: the served path, end to end, on the chip.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # only the --tp 4 path and its baseline
    python chip_smoke.py --tiny     # CPU rehearsal size (tests)

Run from the root of a checkout; nothing is installed and nothing is fetched.
The process you start (the parent) never initialises a JAX backend. It writes
a Llama-3-8B-width checkpoint from --seed (depth cut, widths untouched), then
starts the programs a user would start, each logging to a file:

  registry   python -m bloombee_tpu.cli.run_registry          (JAX-free)
  server     python -m bloombee_tpu.cli.run_server <ckpt> ...  (owns the chip)
  client     DistributedModelForCausalLM.from_pretrained(...).generate on the
             CPU (it holds only embed / norm / head; in a swarm it is another
             machine)
  judge      the repo's plain float32 jax.numpy reference on the CPU for the
             same weights, and the dtype-aware token comparison

One process holds the chip at any time: with --chips 4 the one-device server
runs first and is stopped before the --tp 4 server starts.

Every earlier line of standard output is one JSON object (versions, the
`reduced` map, start-up and compile facts, which kernels engaged, memory,
smoke timings that are NOT benchmark numbers, the token comparison). The last
line is exactly {"ok": ..., "device": {...}} with the device as the server
process reported it. Anything wrong -- platform not tpu, a child dead or late,
a warm-up failure, a kernel fallback, a kernel that should have engaged and
did not, tokens outside bf16 noise of the reference -- ends "ok": false and a
non-zero exit code. Children are killed on every exit path.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib.metadata
import json
import os
import pathlib
import signal
import socket
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
WORK = ROOT / ".cache" / "chip_smoke"  # git-ignored; checkpoint, logs, plans

# meta-llama/Meta-Llama-3-8B config.json; only num_hidden_layers is cut
LLAMA3_8B = {
    "architectures": ["LlamaForCausalLM"],
    "model_type": "llama",
    "hidden_size": 4096,
    "intermediate_size": 14336,
    "num_attention_heads": 32,
    "num_key_value_heads": 8,
    "num_hidden_layers": 32,
    "vocab_size": 128256,
    "max_position_embeddings": 8192,
    "rms_norm_eps": 1e-05,
    "rope_theta": 500000.0,
    "rope_scaling": None,
    "hidden_act": "silu",
    "attention_bias": False,
    "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
    "bos_token_id": 128000,
    "eos_token_id": 128001,
}
SPAN_LAYERS = 8  # one server's span of the 32
# the same family at rehearsal size: runs on the CPU in a test's time
TINY = dict(
    LLAMA3_8B, hidden_size=256, intermediate_size=512,
    num_attention_heads=8, num_key_value_heads=4, num_hidden_layers=2,
    vocab_size=1024,
)
PREFILL_CHUNK = 128  # --prefill-chunk: flash takes 128-token chunks
TOL_EPS = 8.0  # token rule: margins inside TOL_EPS * eps(bf16) * std(logits)


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


class SmokeFailure(Exception):
    """A phase could not finish; the message is the reason reported."""


# ------------------------------------------------------------- checkpoint
def _tensor_plan(config: dict) -> list[list[tuple[str, tuple[int, ...]]]]:
    """HF Llama tensor names and torch-layout shapes, grouped into the
    files they are written to (one per layer, one for the client's trio)."""
    d, i = config["hidden_size"], config["intermediate_size"]
    hd = d // config["num_attention_heads"]
    kv = config["num_key_value_heads"] * hd
    shards = []
    for layer in range(config["num_hidden_layers"]):
        p = f"model.layers.{layer}"
        shards.append([
            (f"{p}.input_layernorm.weight", (d,)),
            (f"{p}.post_attention_layernorm.weight", (d,)),
            (f"{p}.self_attn.q_proj.weight", (d, d)),
            (f"{p}.self_attn.k_proj.weight", (kv, d)),
            (f"{p}.self_attn.v_proj.weight", (kv, d)),
            (f"{p}.self_attn.o_proj.weight", (d, d)),
            (f"{p}.mlp.gate_proj.weight", (i, d)),
            (f"{p}.mlp.up_proj.weight", (i, d)),
            (f"{p}.mlp.down_proj.weight", (d, i)),
        ])
    shards.append([
        ("model.embed_tokens.weight", (config["vocab_size"], d)),
        ("model.norm.weight", (d,)),
        ("lm_head.weight", (config["vocab_size"], d)),
    ])
    return shards


def _write_shard(path: pathlib.Path, tensors, seed_seq) -> None:
    """One safetensors file, streamed: header, then each tensor's bf16 bytes
    made straight from the generator's bits (sign and mantissa random,
    magnitude over four octaves up to 2**-5, so std ~0.014 -- HF's 0.02
    init in spirit), a few MiB at a time. Norm weights are ones."""
    header, offset = {}, 0
    for name, shape in tensors:
        nbytes = 2 * int(np.prod(shape))
        header[name] = {
            "dtype": "BF16", "shape": list(shape),
            "data_offsets": [offset, offset + nbytes],
        }
        offset += nbytes
    head = json.dumps(header, separators=(",", ":")).encode()
    head += b" " * (-len(head) % 8)
    gen = np.random.PCG64(seed_seq)
    step = 1 << 22  # bf16 values per slice
    tmp = path.with_suffix(".tmp")
    with open(tmp, "wb") as f:
        f.write(len(head).to_bytes(8, "little"))
        f.write(head)
        for name, shape in tensors:
            n = int(np.prod(shape))
            if name.endswith("norm.weight"):
                f.write(np.full(n, 0x3F80, np.uint16).tobytes())  # 1.0
                continue
            for start in range(0, n, step):
                m = min(step, n - start)
                bits = gen.random_raw(-(-m // 4)).view(np.uint16)[:m]
                exp = ((bits >> 7) & 3) + 118  # 2**-9 .. 2**-6
                f.write(((bits & 0x807F) | (exp << 7)).tobytes())
    os.replace(tmp, path)


def write_checkpoint(path: pathlib.Path, config: dict, seed: int) -> dict:
    """An HF-layout directory (config.json + safetensors + index) under the
    names bloombee_tpu/models/checkpoint.py reads. Reused when it is
    already there for the same seed and shape."""
    manifest = {"seed": seed, "config": config, "format": 1}
    stamp = path / "chip_smoke_manifest.json"
    if stamp.exists() and json.loads(stamp.read_text()) == manifest:
        return {"reused": True, "seconds": 0.0}
    t0 = time.time()
    path.mkdir(parents=True, exist_ok=True)
    stamp.unlink(missing_ok=True)
    shards = _tensor_plan(config)
    names = [f"model-{i + 1:05d}-of-{len(shards):05d}.safetensors"
             for i in range(len(shards))]
    seeds = np.random.SeedSequence(seed).spawn(len(shards))
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
        jobs = [pool.submit(_write_shard, path / n, t, s)
                for n, t, s in zip(names, shards, seeds)]
        for job in jobs:
            job.result()
    weight_map = {t[0]: n for n, ts in zip(names, shards) for t in ts}
    (path / "model.safetensors.index.json").write_text(
        json.dumps({"metadata": {}, "weight_map": weight_map})
    )
    (path / "config.json").write_text(json.dumps(config, indent=1))
    stamp.write_text(json.dumps(manifest))
    nbytes = sum(f.stat().st_size for f in path.glob("*.safetensors"))
    return {"reused": False, "seconds": round(time.time() - t0, 1),
            "bytes": nbytes}


# ----------------------------------------------------------------- parent
class Children:
    """The processes the parent started, each in its own process group and
    logging to a file (an undrained pipe blocks a chatty child)."""

    def __init__(self):
        self.procs: dict[str, subprocess.Popen] = {}
        self.pids: dict[str, int] = {}  # everything ever started

    def spawn(self, name: str, argv: list[str], env: dict) -> None:
        log = open(WORK / f"{name}.log", "w")
        self.procs[name] = subprocess.Popen(
            argv, cwd=ROOT, env=env, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        self.pids[name] = self.procs[name].pid
        log.close()

    def require_alive(self, *names: str) -> None:
        for name in names:
            rc = self.procs[name].poll()
            if rc is not None:
                raise SmokeFailure(
                    f"{name} exited with code {rc}: {self.tail(name)}"
                )

    def tail(self, name: str, nbytes: int = 1500) -> str:
        try:
            data = (WORK / f"{name}.log").read_bytes()[-nbytes:]
        except OSError:
            return ""
        return data.decode(errors="replace")

    def wait(self, name: str, limit_s: float, watch: tuple[str, ...]) -> None:
        """Wait for `name` to exit 0 within its own limit, failing early if
        a process it depends on dies."""
        deadline = time.monotonic() + limit_s
        proc = self.procs[name]
        while proc.poll() is None:
            self.require_alive(*watch)
            if time.monotonic() > deadline:
                raise SmokeFailure(
                    f"{name} not done within {limit_s:.0f}s: "
                    f"{self.tail(name)}"
                )
            time.sleep(0.25)
        if proc.returncode != 0:
            raise SmokeFailure(
                f"{name} exited with code {proc.returncode}: "
                f"{self.tail(name)}"
            )

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
            except subprocess.TimeoutExpired:
                continue
        if proc.poll() is None:
            raise SmokeFailure(f"{name} survived SIGKILL")

    def stop_all(self) -> None:
        for name in list(self.procs):
            self.stop(name, grace_s=5.0)


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
            time.sleep(0.2)
    raise SmokeFailure(f"{name} not listening on {port} after {limit_s:.0f}s")


def _requests(config: dict, seed: int, tiny: bool) -> dict:
    """The traffic, from the seed: a chat-sized request, a long one whose
    bucketed context crosses the paged crossover (flash prefill chunks, then
    paged decode), and two concurrent sessions (`a` decodes while `b`
    prefills, then both decode) so a mixed ragged dispatch and a grouped
    decode happen."""
    rng = np.random.default_rng(seed + 1)
    sizes = (
        {"chat": (12, 6), "long": (140, 4), "a": (135, 8), "b": (140, 4)}
        if tiny else
        {"chat": (12, 12), "long": (300, 12), "a": (280, 24), "b": (300, 12)}
    )
    return {
        name: {
            "prompt": rng.integers(
                0, config["vocab_size"], size=n_prompt
            ).tolist(),
            "new_tokens": n_new,
        }
        for name, (n_prompt, n_new) in sizes.items()
    }


def _child_env(**extra: str) -> dict:
    env = dict(os.environ)
    env["PYTHONUNBUFFERED"] = "1"
    env.update(extra)
    return env


def _serve_leg(
    children: Children, plan: dict, tp: int, tag: str, deadline: float
) -> dict:
    """Start one run_server process for the whole (cut) model, let the
    client child answer the requests through it, stop it. Returns what the
    client wrote: tokens, timings, and the server's own account of itself."""
    port = _free_port()
    leg = dict(plan, tag=tag, tp=tp, server_port=port,
               out=str(WORK / f"client_{tag}.json"),
               server_spawned_at=time.time())
    leg_path = WORK / f"plan_{tag}.json"
    leg_path.write_text(json.dumps(leg))
    pathlib.Path(leg["out"]).unlink(missing_ok=True)
    server = f"server_{tag}"
    children.spawn(
        server,
        [sys.executable, "-m", "bloombee_tpu.cli.run_server", plan["ckpt"],
         "--model-uid", plan["uid"],
         "--registry", f"127.0.0.1:{plan['registry_port']}",
         "--blocks", f"0:{plan['layers']}",
         "--host", "127.0.0.1", "--public-host", "127.0.0.1",
         "--port", str(port), "--num-pages", str(plan["num_pages"]),
         # what a multi-tenant deployment runs: default --max-batch and
         # warm-up, prefix cache off, chunked prefill fused with decodes
         "--mixed-batch", "--prefill-chunk", str(PREFILL_CHUNK),
         "--tp", str(tp)],
        # the compile witness counts compiles and persistent-cache hits;
        # the gather window lets the other session's step join a dispatch
        _child_env(BBTPU_JITWATCH="1", BBTPU_BATCH_WINDOW_MS="500"),
    )
    client = f"client_{tag}"
    children.spawn(
        client,
        [sys.executable, str(ROOT / "chip_smoke.py"), "--role", "client",
         "--plan", str(leg_path)],
        _child_env(JAX_PLATFORMS="cpu"),
    )
    try:
        children.wait(
            client, min(900.0, deadline - time.monotonic()),
            watch=("registry", server),
        )
    finally:
        children.stop(client, grace_s=2.0)
        children.stop(server)
    return json.loads(pathlib.Path(leg["out"]).read_text())


def _server_facts(tag: str, got: dict) -> dict:
    """The earlier-line account of one server leg (smoke timings only)."""
    ready, end = got["info_ready"], got["info_end"]
    return {
        "phase": f"server_{tag}",
        "device": end["device"],
        "native": end["native"],
        "seconds_to_first_announce": got["announce_s"],
        "seconds_to_warm": got["ready_s"],
        "compile": {
            "warmup": {k: ready[k] for k in (
                "xla_compiles", "compile_ms_total", "compile_cache_hits")},
            "end": {k: end[k] for k in (
                "xla_compiles", "compile_ms_total", "compile_cache_hits",
                "steady_state_recompiles")},
        },
        "attn_dispatches": end["attn_dispatches"],
        "kernel_fallbacks": end["kernel_fallbacks"],
        "warmup_failures": end["warmup_failures"],
        "dispatch": {k: end[k] for k in (
            "step_dispatches", "step_tokens", "batch_dispatches",
            "batched_steps", "mixed_dispatches", "ragged_group_dispatches",
            "prefill_chunks")},
        "inference_rps_announced": end.get("inference_rps"),
        "memory": end["memory"],
        "smoke_timings_not_benchmark": {
            name: {"tokens": len(r["tokens"]),
                   "wall_s": round(r["wall_s"], 3)}
            for name, r in got["results"].items()
        },
    }


def _server_faults(tag: str, got: dict, want_kernels: bool) -> list[str]:
    end = got["info_end"]
    faults = []
    if end["warmup_failures"]:
        faults.append(f"{tag}: warmup_failures={end['warmup_failures']}")
    if end["kernel_fallbacks"]:
        faults.append(f"{tag}: kernel_fallbacks={end['kernel_fallbacks']}")
    if want_kernels:
        # one-device serving: every Pallas kernel of the path must have run
        for path in ("flash", "paged", "ragged"):
            if not end["attn_dispatches"].get(path):
                faults.append(f"{tag}: {path} kernel never engaged")
        for counter in ("batch_dispatches", "ragged_group_dispatches"):
            if not end[counter]:
                faults.append(f"{tag}: {counter}=0 (no grouped dispatch)")
    return faults


def parent_main(args) -> int:
    t_start = time.monotonic()
    deadline = t_start + 1150.0  # the driver allows 1200 s, compiles included
    children = Children()
    device = None
    faults: list[str] = []

    def bail(signum, _frame):
        raise SmokeFailure(f"signal {signal.Signals(signum).name}")

    signal.signal(signal.SIGTERM, bail)
    signal.signal(signal.SIGINT, bail)
    try:
        if not (ROOT / "bloombee_tpu" / "cli" / "run_server.py").exists():
            raise SmokeFailure(
                f"no bloombee_tpu checkout next to {ROOT / 'chip_smoke.py'}"
            )
        WORK.mkdir(parents=True, exist_ok=True)
        full = TINY if args.tiny else LLAMA3_8B
        layers = full["num_hidden_layers"] if args.tiny else SPAN_LAYERS
        config = dict(full, num_hidden_layers=layers)
        emit(
            phase="setup",
            python=sys.version.split()[0],
            **{p: importlib.metadata.version(p)
               for p in ("jax", "jaxlib", "libtpu")},
            model="tiny rehearsal" if args.tiny else "Llama-3-8B widths",
            widths={k: config[k] for k in (
                "hidden_size", "num_attention_heads", "num_key_value_heads",
                "intermediate_size", "vocab_size", "torch_dtype")},
            reduced=(
                {"everything": "tiny rehearsal, not a model anyone serves"}
                if args.tiny else
                {"layers": f"{LLAMA3_8B['num_hidden_layers']}->{layers}"}
            ),
            chips=args.chips, seed=args.seed,
            compile_cache=os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(ROOT / ".cache" / "xla"),
        )
        ckpt = WORK / (
            f"{'tiny' if args.tiny else 'llama3-8b'}-l{layers}-s{args.seed}"
        )
        emit(phase="checkpoint", path=str(ckpt.relative_to(ROOT)),
             **write_checkpoint(ckpt, config, args.seed))

        reg_port = _free_port()
        children.spawn(
            "registry",
            [sys.executable, "-m", "bloombee_tpu.cli.run_registry",
             "--host", "127.0.0.1", "--port", str(reg_port)],
            _child_env(),
        )
        _wait_port(reg_port, 30.0, children, "registry")
        plan = {
            "ckpt": str(ckpt), "uid": "chip-smoke", "layers": layers,
            "registry_port": reg_port, "seed": args.seed,
            "num_pages": 128 if args.tiny else 1024,
            "requests": _requests(config, args.seed, args.tiny),
        }
        # --chips 4: ONLY the tp path and the one-device run it is compared
        # with, one after the other so each has the chips to itself
        legs = [("one", 1)] + ([("tp4", 4)] if args.chips == 4 else [])
        outs = {}
        for tag, tp in legs:
            outs[tag] = _serve_leg(children, plan, tp, tag, deadline)
            emit(**_server_facts(tag, outs[tag]))
            faults += _server_faults(tag, outs[tag], want_kernels=tp == 1)
        children.stop("registry", grace_s=5.0)
        served = outs[legs[-1][0]]
        device = served["info_end"]["device"]
        if device["platform"] != "tpu":
            faults.append(f"server platform is {device['platform']}, not tpu")
        if device["count"] != args.chips:
            faults.append(
                f"server saw {device['count']} devices, wanted {args.chips}"
            )
        if args.chips == 4:
            mem = served["info_end"]["memory"]
            for key in ("span_params_bytes_by_device",
                        "kv_arena_bytes_by_device"):
                by_dev = mem[key]
                emit(phase="placement", what=key, bytes_by_device=by_dev)
                if len(by_dev) != 4 or min(by_dev) == 0 or (
                    max(by_dev) > 0.5 * sum(by_dev)
                ):
                    faults.append(f"{key} not spread over 4 chips: {by_dev}")

        judge_plan = dict(
            plan, out=str(WORK / "judge.json"),
            runs=[str(WORK / f"client_{tag}.json") for tag, _ in legs],
        )
        (WORK / "plan_judge.json").write_text(json.dumps(judge_plan))
        children.spawn(
            "judge",
            [sys.executable, str(ROOT / "chip_smoke.py"), "--role", "judge",
             "--plan", str(WORK / "plan_judge.json")],
            _child_env(JAX_PLATFORMS="cpu"),
        )
        children.wait(
            "judge", min(420.0, deadline - time.monotonic()), watch=()
        )
        verdict = json.loads((WORK / "judge.json").read_text())
        emit(phase="tokens_vs_reference", **verdict)
        faults += verdict["faults"]
    except SmokeFailure as e:
        faults.append(str(e))
    finally:
        try:
            children.stop_all()
        except SmokeFailure as e:
            faults.append(str(e))
    ok = not faults
    emit(phase="summary", faults=faults, children=children.pids,
         parent_imported_jax="jax" in sys.modules,
         wall_s=round(time.monotonic() - t_start, 1))
    emit(ok=ok, device=device)
    return 0 if ok else 1


# ----------------------------------------------------------- client child
_INFO_KEYS = (
    "device", "native", "memory", "attn_dispatches", "kernel_fallbacks",
    "warmup_failures", "warmup_done", "xla_compiles", "compile_ms_total",
    "warmup_compiles", "steady_state_recompiles", "compile_cache_hits",
    "step_dispatches", "step_tokens", "batch_dispatches", "batched_steps",
    "mixed_dispatches", "ragged_group_dispatches", "prefill_chunks",
    "inference_rps",
)


async def _client(plan: dict) -> dict:
    import jax.numpy as jnp

    from bloombee_tpu.client.model import DistributedModelForCausalLM
    from bloombee_tpu.swarm.registry import RegistryClient
    from bloombee_tpu.wire.rpc import connect

    t0 = plan["server_spawned_at"]

    async def rpc_info() -> dict:
        conn = await connect("127.0.0.1", plan["server_port"])
        try:
            info, _ = await asyncio.wait_for(conn.call("rpc_info", {}), 30.0)
        finally:
            await conn.close()
        return {k: info.get(k) for k in _INFO_KEYS}

    # wait 1: the server announces its span (own limit)
    registry = RegistryClient("127.0.0.1", plan["registry_port"])
    deadline = time.time() + 420.0
    while True:
        infos = await registry.get_module_infos(
            plan["uid"], range(plan["layers"])
        )
        if infos and all(mi.servers for mi in infos):
            break
        if time.time() > deadline:
            raise TimeoutError("server never announced its span")
        await asyncio.sleep(0.5)
    announce_s = round(time.time() - t0, 1)
    # wait 2: warm-up and the throughput measurement are through
    deadline = time.time() + 600.0
    while True:
        info_ready = await rpc_info()
        if info_ready["warmup_done"]:
            break
        if time.time() > deadline:
            raise TimeoutError("server warm-up not done within 600s")
        await asyncio.sleep(1.0)
    ready_s = round(time.time() - t0, 1)

    model = DistributedModelForCausalLM.from_pretrained(
        plan["ckpt"], registry, model_uid=plan["uid"], dtype=jnp.float32
    )
    reqs = plan["requests"]
    results: dict[str, dict] = {}

    async def generate(prompt, n, session=None):
        # each request has its own limit (first use of a bucket compiles)
        return await asyncio.wait_for(
            model.generate(
                np.asarray([prompt]), max_new_tokens=n, session=session
            ),
            timeout=300.0,
        )

    for name in ("chat", "long"):
        t = time.perf_counter()
        ids = await generate(reqs[name]["prompt"], reqs[name]["new_tokens"])
        results[name] = {
            "tokens": ids[0, len(reqs[name]["prompt"]):].tolist(),
            "wall_s": time.perf_counter() - t,
        }

    # two concurrent sessions: `a` prefills and decodes a few tokens alone,
    # then keeps decoding while `b` prefills (chunk + decode fuse into one
    # ragged dispatch) and both decode together (grouped decode)
    pa, na = reqs["a"]["prompt"], reqs["a"]["new_tokens"]
    pb, nb = reqs["b"]["prompt"], reqs["b"]["new_tokens"]
    head = 4 if na > 4 else 1
    t = time.perf_counter()
    async with model.inference_session(max_length=len(pa) + na + 2) as sess:
        first = await generate(pa, head, session=sess)

        async def timed_b():
            tb = time.perf_counter()
            ids_b = await generate(pb, nb)
            return ids_b, time.perf_counter() - tb

        rest, (ids_b, wall_b) = await asyncio.gather(
            generate(first[0, -1:].tolist(), na - head, session=sess),
            timed_b(),
        )
    results["a"] = {
        "tokens": first[0, len(pa):].tolist() + rest[0, 1:].tolist(),
        "wall_s": time.perf_counter() - t,
    }
    results["b"] = {"tokens": ids_b[0, len(pb):].tolist(), "wall_s": wall_b}
    info_end = await rpc_info()
    await registry.close()
    return {"announce_s": announce_s, "ready_s": ready_s,
            "info_ready": info_ready, "info_end": info_end,
            "results": results}


def client_main(args) -> int:
    plan = json.loads(pathlib.Path(args.plan).read_text())
    got = asyncio.run(_client(plan))
    pathlib.Path(plan["out"]).write_text(json.dumps(got))
    return 0


# ------------------------------------------------------------ judge child
def _reference_logits(plan: dict, seqs: dict[str, list[int]],
                      n_new: dict[str, int]) -> dict:
    """Teacher-forced float32 logits from the repo's plain reference
    (block_forward + dense_attend, the path the parity tests use) for the
    same checkpoint: for each sequence, the rows that predicted its last
    n_new tokens. Sequences are right-padded into one batch; causal
    attention keeps the padding out of every real position."""
    import jax
    import jax.numpy as jnp

    from bloombee_tpu.models.auto import get_family
    from bloombee_tpu.models.checkpoint import (
        CheckpointReader,
        load_client_params,
        load_spec,
    )
    from bloombee_tpu.models.head import embed_impl, norm_head_impl
    from bloombee_tpu.models.llama.block import block_forward, dense_attend
    from bloombee_tpu.ops.rotary import rotary_cos_sin

    spec = load_spec(plan["ckpt"])
    reader = CheckpointReader(plan["ckpt"])
    family = get_family(reader.model_type())
    client = load_client_params(plan["ckpt"], dtype=jnp.float32)
    names = list(seqs)
    width = max(len(seqs[n]) for n in names)
    ids = np.zeros((len(names), width), np.int32)
    for row, name in enumerate(names):
        ids[row, : len(seqs[name])] = seqs[name]
    positions = jnp.broadcast_to(jnp.arange(width)[None], ids.shape)
    cos, sin = rotary_cos_sin(positions, spec.head_dim, spec.rope_theta)
    layer = jax.jit(
        lambda p, h: block_forward(p, spec, h, cos, sin, dense_attend())[0]
    )
    hidden = embed_impl(client, jnp.asarray(ids)).astype(jnp.float32)
    for i in range(spec.num_hidden_layers):
        hidden = layer(
            family.load_block_params(reader, i, dtype=jnp.float32), hidden
        )
    out = {}
    for row, name in enumerate(names):
        end = len(seqs[name]) - 1  # row t predicts token t + 1
        rows = hidden[row, end - n_new[name]: end]
        out[name] = np.asarray(
            norm_head_impl(client, rows, spec.rms_norm_eps), np.float32
        )
    return out


def _noise_margins(logits: np.ndarray, tokens: list[int]):
    """How far below the reference's top logit each chosen token sits, and
    the bf16-noise allowance per position (in logit units)."""
    import ml_dtypes

    chosen = logits[np.arange(len(tokens)), np.asarray(tokens)]
    margin = logits.max(axis=-1) - chosen
    tol = (
        TOL_EPS * float(ml_dtypes.finfo(ml_dtypes.bfloat16).eps)
        * logits.std(axis=-1)
    )
    return margin, tol


def judge_main(args) -> int:
    plan = json.loads(pathlib.Path(args.plan).read_text())
    runs = [json.loads(pathlib.Path(p).read_text())["results"]
            for p in plan["runs"]]
    base = runs[0]  # the one-device server's tokens
    reqs = plan["requests"]
    t0 = time.time()
    logits = _reference_logits(
        plan,
        {n: reqs[n]["prompt"] + base[n]["tokens"] for n in reqs},
        {n: len(base[n]["tokens"]) for n in reqs},
    )
    faults, per_request = [], {}
    for name in reqs:
        # rule: the served token is the reference's greedy token, unless the
        # reference's own margin at that position is inside bf16 noise
        margin, tol = _noise_margins(logits[name], base[name]["tokens"])
        bad = np.flatnonzero(margin > tol)
        per_request[name] = {
            "tokens": len(margin),
            "exact": int((margin == 0).sum()),
            "inside_bf16_noise": int(((margin > 0) & (margin <= tol)).sum()),
            "outside": int(bad.size),
            "worst_margin_over_allowance": float((margin / tol).max()),
        }
        if bad.size:
            faults.append(
                f"{name}: served token {int(bad[0])} sits "
                f"{float(margin[bad[0]]):.4f} below the reference's top "
                f"logit, allowance {float(tol[bad[0]]):.4f}"
            )
        if len(runs) == 2:
            # --tp 4 against one device, same rule: equal tokens up to the
            # first position, if any, where the reference cannot tell the
            # two choices apart
            other = runs[1][name]["tokens"]
            diff = [i for i, (x, y) in enumerate(
                zip(base[name]["tokens"], other)) if x != y]
            per_request[name]["tp4_first_divergence"] = (
                diff[0] if diff else None
            )
            if len(other) != len(base[name]["tokens"]):
                faults.append(f"{name}: tp4 returned {len(other)} tokens")
            elif diff:
                i = diff[0]
                m_other, _ = _noise_margins(
                    logits[name][i: i + 1], other[i: i + 1]
                )
                if max(float(margin[i]), float(m_other[0])) > tol[i]:
                    faults.append(
                        f"{name}: tp4 diverges from one device at token "
                        f"{i} outside bf16 noise"
                    )
    pathlib.Path(plan["out"]).write_text(json.dumps({
        "rule": f"margin <= {TOL_EPS} * eps(bf16) * std(reference logits)",
        "reference": "block_forward + dense_attend, float32, CPU",
        "reference_seconds": round(time.time() - t0, 1),
        "requests": per_request,
        "faults": faults,
    }))
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the --tp 4 path and the "
                             "one-device run it is compared with")
    parser.add_argument("--seed", type=int, default=0,
                        help="weights and prompts are made from it")
    parser.add_argument("--tiny", action="store_true",
                        help="rehearsal size for the CPU (tests)")
    parser.add_argument("--role", choices=("parent", "client", "judge"),
                        default="parent", help=argparse.SUPPRESS)
    parser.add_argument("--plan", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    return {"parent": parent_main, "client": client_main,
            "judge": judge_main}[args.role](args)


if __name__ == "__main__":
    sys.exit(main())
