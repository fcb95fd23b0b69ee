"""Load generator child: the swarm client, on its own CPU cores.

    python cellbench/loadgen.py <plan.json>

It pins itself to the plan's cores BEFORE importing JAX, waits for the server
to announce and finish its own warm-up, loads the client trio, drives every
program bucket of the cell once (set-up), then replays the fixed schedule for the
window through `DistributedModelForCausalLM.generate` -- the user's call --
and writes every timestamp, the server's `rpc_info` at both edges of the
window and the judged requests' logits to the plan's output files.

Spans are recorded here, around the calls into each layer (TimedModel): the
program has no spans of its own yet.
"""

from __future__ import annotations

import asyncio
import contextvars
import gc
import json
import os
import pathlib
import sys
import threading
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

_REQ: contextvars.ContextVar = contextvars.ContextVar("cellbench_req", default=None)
now = time.perf_counter


def _timed_model_class():
    from bloombee_tpu.client.model import DistributedModelForCausalLM

    class TimedModel(DistributedModelForCausalLM):
        """The client with a span around embed, head and session creation;
        which request a call belongs to rides in a context variable (each
        request is its own asyncio task)."""

        def embed(self, input_ids):
            t = now()
            out = super().embed(input_ids)
            rec = _REQ.get()
            if rec is not None:
                rec["embed_ms"].append((now() - t) * 1e3)
            return out

        def logits(self, hidden):
            t = now()
            out = super().logits(hidden)
            t1 = now()
            rec = _REQ.get()
            if rec is not None:
                rec["token_times"].append(t1)
                rec["head_ms"].append((t1 - t) * 1e3)
                if rec.get("keep_logits") is not None:
                    rec["keep_logits"].append(out[0, 0].copy())
            return out

        def inference_session(self, *args, **kwargs):
            session = super().inference_session(*args, **kwargs)
            rec = _REQ.get()
            if rec is not None:
                rec["session"] = session
            return session

    return TimedModel


def _new_record(**fields) -> dict:
    return dict(fields, token_times=[], head_ms=[], embed_ms=[], start=None,
                end=None, error=None, session=None, wire_ms=[], step_ms=[])


def _harvest(rec: dict) -> None:
    """Per decode step: client round trip minus the server's own time for
    that step (session.timings), then drop the session object."""
    session = rec.pop("session", None)
    if session is None:
        return
    for t in session.timings:
        spans = [m for m in t["span_compute_ms"] if m is not None]
        if t["tokens"] == 1 and spans:
            rec["wire_ms"].append(t["total_ms"] - sum(spans))
            rec["step_ms"].append(t["total_ms"])


_INFO_KEYS = (
    "device", "memory", "attn_dispatches", "kernel_fallbacks",
    "warmup_failures", "warmup_done", "xla_compiles", "compile_ms_total",
    "warmup_compiles", "steady_state_recompiles", "compile_cache_hits",
    "step_dispatches", "step_tokens", "batch_dispatches", "batched_steps",
    "mixed_dispatches", "mixed_tokens", "ragged_group_dispatches",
    "prefill_chunks", "prefill_chunk_tokens", "queue_wait_ms", "transport",
    "inference_rps",
)


async def _rpc_info(port: int) -> dict:
    """The server's counters. Under load the call can fail with "Array has
    been deleted": its memory report reads the KV arena while a dispatch has
    donated it (a race in the program, PERF.md section 7), so it is retried."""
    from bloombee_tpu.wire.rpc import RpcError, connect

    for attempt in range(8):
        conn = await connect("127.0.0.1", port)
        try:
            info, _ = await asyncio.wait_for(conn.call("rpc_info", {}), 30.0)
            return {k: info.get(k) for k in _INFO_KEYS}
        except RpcError as e:
            if "deleted" not in str(e) or attempt == 7:
                raise
            await asyncio.sleep(0.01)
        finally:
            await conn.close()
    raise AssertionError("unreachable")


async def _wait_ready(plan: dict, registry) -> dict:
    t0 = plan["server_spawned_at"]
    deadline = time.time() + 900.0
    while True:
        infos = await registry.get_module_infos(
            plan["uid"], range(plan["config"]["num_hidden_layers"])
        )
        if infos and all(mi.servers for mi in infos):
            break
        if time.time() > deadline:
            raise TimeoutError("server never announced its span")
        await asyncio.sleep(0.25)
    announce_s = time.time() - t0
    while True:
        info = await _rpc_info(plan["server_port"])
        if info["warmup_done"]:
            break
        if time.time() > deadline:
            raise TimeoutError("server warm-up not done in time")
        await asyncio.sleep(0.5)
    return {"announce_s": announce_s, "ready_s": time.time() - t0, "info": info}


class Lane:
    """One session slot: a thread with its own event loop, its own routing
    manager and connections, sharing only the (read-only) client weights.
    In a swarm every user is another machine; one event loop for all N
    sessions would serialise their head matmuls (17 ms each at a 152k
    vocabulary) and the generator, not the server, would set the pace."""

    def __init__(self, index: int):
        self.index = index
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(
            target=self._main, name=f"lane{index}", daemon=True)
        self.model = None
        self.thread.start()

    def _main(self) -> None:
        asyncio.set_event_loop(self.loop)
        self.loop.run_forever()

    def submit(self, coro):
        return asyncio.run_coroutine_threadsafe(coro, self.loop)

    def stop(self) -> None:
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(timeout=10)


class WarmSession:
    """A session on a lane that the warm-up drives one call at a time (the
    calls `generate` makes, one by one)."""

    def __init__(self, lane: Lane, plan: dict, tag: int, prompt_len: int,
                 room: int):
        from cellbench import schedule

        self.lane, self.model, self.made = lane, lane.model, 0
        self.ctx, self.limit = prompt_len, prompt_len + room
        self.seed, self.vocab = plan["seed"], plan["config"]["vocab_size"]
        self.ids = [schedule.token_ids(
            plan["seed"], 10_000 + tag, prompt_len,
            plan["config"]["vocab_size"])]
        self._cm = self._sess = self._next = None

    def on_lane(self, coro):
        return asyncio.wrap_future(self.lane.submit(coro))

    async def enter(self) -> None:
        self._cm = self.model.inference_session(self.limit + 2)
        self._sess = await self._cm.__aenter__()

    async def prefill(self) -> None:
        import numpy as np

        ids = np.asarray(self.ids)
        out = await self._sess.step(self.model.embed(ids), ids=ids)
        self._prepare(out)

    def _prepare(self, out) -> None:
        """Head, select and embed now, so that the next step() is only the
        send: a shot needs its sends within a millisecond of each other, and
        a vocabulary-wide head matmul takes tens."""
        import numpy as np

        nxt = np.argmax(self.model.logits(out[:, -1:])[:, 0], -1)[:, None]
        self._next = (self.model.embed(nxt), nxt)

    async def step(self) -> None:
        if self.ctx >= self.limit:
            return  # stay inside this page bucket
        hidden, ids = self._next
        out = await self._sess.step(hidden, ids=ids)
        self.ctx, self.made = self.ctx + 1, self.made + 1
        self._prepare(out)

    async def extend(self, length: int) -> None:
        """A further chunk of `length` tokens on this session: what the last
        chunk of a longer prompt is to the server."""
        import numpy as np

        from cellbench import schedule

        if self.ctx + length > self.limit:
            return
        ids = np.asarray([schedule.token_ids(
            self.seed, 20_000 + self.ctx, length, self.vocab)])
        out = await self._sess.step(self.model.embed(ids), ids=ids)
        self.ctx += length
        self._prepare(out)

    async def close(self) -> None:
        await self._cm.__aexit__(None, None, None)


async def _cover(lanes: list, plan: dict, entry: dict, no: int) -> int:
    """Drive every span-step program of one page bucket (schedule.cover_plan).

    N decoder sessions are opened at a context inside the bucket. The first
    steps each of the entry's `solo_tails` alone (a prompt's last chunk with
    nothing beside it). Then `shots` (see shot below): decode groups of
    exactly k rows, and k decode rows fused with a chunk. Returns steps made."""
    from cellbench import schedule

    n = len(lanes)
    widths = [k for k in (1, 2, 4, 8, 16) if k <= n]
    rounds = schedule.WARM_SHOTS
    shots = rounds * (len(widths) + 2 * len(entry["shots"]))
    # a decoder reserves only the pages its steps need, inside the bucket
    room = min(entry["top"] - entry["decoder_prompt"] - 2,
               shots + 2 + sum(entry["solo_tails"]))
    team = [WarmSession(lane, plan, 100 * no + i, entry["decoder_prompt"],
                        room) for i, lane in enumerate(lanes)]
    await asyncio.gather(*(t.on_lane(t.enter()) for t in team))
    await asyncio.gather(*(t.on_lane(t.prefill()) for t in team))
    for length in entry["solo_tails"]:
        await team[0].on_lane(team[0].extend(length))
    tag = 100 * no + 50

    async def shot(k: int, follower_len: int) -> None:
        """With follower_len 0 a single blocker (a fresh one-chunk prompt)
        goes first and keeps the compute thread busy; the k decode steps
        sent a few ms later wait in the queue and leave it as one decode
        group of exactly k. Otherwise three fresh TWO-chunk prompts (a full
        chunk, then follower_len tokens) go first, at the same instant: the
        server fuses decode rows only with a chunk of a chunked prefill,
        never with a whole one-chunk prompt. While their chunks take turns
        on the device one of them is always waiting in the queue, so the k
        decode steps that arrive leave it fused with it (k rows and a full
        chunk); each decoder steps twice, and the second step meets the
        later chunks, the follower_len-token tails among them."""
        nonlocal tag
        tag += 3
        chunk = entry["blocker"]
        fresh = [WarmSession(lanes[(n - 1 - j) % n], plan, tag + j,
                             chunk + follower_len if follower_len else chunk,
                             1)
                 for j in range(3 if follower_len else 1)]
        await asyncio.gather(*(f.on_lane(f.enter()) for f in fresh))
        chunks = [f.on_lane(f.prefill()) for f in fresh]
        await asyncio.sleep(0.006 if follower_len else 0.003)

        async def steps(member) -> None:
            for _ in range(2 if follower_len else 1):
                await member.on_lane(member.step())

        await asyncio.gather(*chunks, *(steps(team[i]) for i in range(k)))
        await asyncio.gather(*(f.on_lane(f.close()) for f in fresh))

    for _ in range(rounds):
        for k in widths:  # decode groups of exactly k rows
            await shot(k, 0)
        for k, follower_len in entry["shots"]:  # chunk + k decode rows
            await shot(k, follower_len)
    made = sum(t.made for t in team)
    await asyncio.gather(*(t.on_lane(t.close()) for t in team))
    return made


async def _slot_loop(lane: Lane, plan: dict, prompts: list, records: list,
                     w0: float, stop: threading.Event,
                     new_tokens_cap: int | None, turns: int | None) -> None:
    """The closed loop of one slot: entries slot, slot+N, ... of the fixed
    schedule, each one `generate` call that opens its own session; the next
    request is due the moment the last one ended."""
    import numpy as np

    from cellbench import schedule

    traffic, slot, model = plan["traffic"], lane.index, lane.model
    due = w0 + slot * traffic["stagger_s"]
    await asyncio.sleep(max(0.0, due - now()))
    turn = 0
    while not stop.is_set() and (turns is None or turn < turns):
        j = schedule.entry(traffic, slot, turn)
        new = traffic["new_tokens"][j]
        if new_tokens_cap is not None:
            new = min(new, new_tokens_cap)
        rec = _new_record(slot=slot, turn=turn, entry=j, due=due,
                          prompt_tokens=len(prompts[j]), new_tokens=new)
        records.append(rec)
        token = _REQ.set(rec)
        rec["start"] = now()
        try:
            await model.generate(np.asarray([prompts[j]]), max_new_tokens=new)
            rec["end"] = now()
        except asyncio.CancelledError:
            rec["error"] = "cancelled at the end of the run"
            raise
        except Exception as e:  # a failed request is counted, not fatal
            rec["error"] = repr(e)
            rec["end"] = now()
            await asyncio.sleep(0.2)
        finally:
            _REQ.reset(token)
            _harvest(rec)
        due, turn = rec["end"], turn + 1


async def _judge_one(lane: Lane, plan: dict, prompts: list, j: int) -> dict:
    import numpy as np

    rec = _new_record(entry=j, prompt_tokens=len(prompts[j]), keep_logits=[])
    token = _REQ.set(rec)
    try:
        ids = await lane.model.generate(
            np.asarray([prompts[j]]),
            max_new_tokens=plan["traffic"]["judge"]["new_tokens"],
        )
    finally:
        _REQ.reset(token)
    path = pathlib.Path(plan["work_dir"]) / f"judged_{j}.npy"
    np.save(path, np.stack(rec["keep_logits"]).astype(np.float32))
    return {"entry": j, "ids": ids[0].tolist(),
            "prompt_tokens": len(prompts[j]), "logits_file": str(path)}


async def _run(plan: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bloombee_tpu.client.sequence_manager import RemoteSequenceManager
    from bloombee_tpu.swarm.registry import RegistryClient
    from cellbench import schedule

    traffic, config = plan["traffic"], plan["config"]
    control = pathlib.Path(plan["control_dir"])
    registry = RegistryClient("127.0.0.1", plan["registry_port"])
    ready = await _wait_ready(plan, registry)
    while not (control / "client_shard.ready").exists():
        await asyncio.sleep(0.1)
    t_load = time.time()
    timed = _timed_model_class()
    model = timed.from_pretrained(
        plan["ckpt"], registry, model_uid=plan["uid"], dtype=jnp.float32
    )
    vocab, n = config["vocab_size"], traffic["sessions"]
    entries = len(traffic["prompt_tokens"])
    prompts = [schedule.token_ids(plan["seed"], j, traffic["prompt_tokens"][j],
                                  vocab) for j in range(entries)]
    # the client's own programs: one embed per distinct prompt length, the
    # single-token embed and the head
    for length in sorted({1, *traffic["prompt_tokens"],
                          *(p for c in plan["cover"] for p in (
                              c["decoder_prompt"], c["blocker"],
                              *c["solo_tails"],
                              *(c["blocker"] + shot[1]
                                for shot in c["shots"])))}):
        model.embed(np.zeros((1, length), np.int64))
    model.logits(np.zeros((1, 1, config["hidden_size"]), np.float32))
    lanes = [Lane(i) for i in range(n)]
    for lane in lanes:
        lane.model = timed(
            model.spec, model.params,
            RemoteSequenceManager(
                RegistryClient("127.0.0.1", plan["registry_port"]),
                plan["uid"], model.spec.num_hidden_layers),
            config=model.config)
    client_load_s = time.time() - t_load

    # ---- warm-up (set-up, not measured): cover the cell's program buckets,
    # then one pass over the whole schedule with the answers cut short, in
    # the window's own slots and stagger, so every prompt length has met the
    # server the way it will in the window. --seconds 0 (the controls: only
    # the judged requests matter) skips warm-up and window.
    measured = plan["seconds"] > 0
    t_warm = time.time()
    info_before_warm = await _rpc_info(plan["server_port"])
    warm_steps = 0
    for k, entry in enumerate(plan["cover"] if measured else ()):
        warm_steps += await _cover(lanes, plan, entry, k)
    info_swept = await _rpc_info(plan["server_port"])
    warm_records: list[dict] = []
    never = threading.Event()
    t_pass = now()
    if measured:
        await asyncio.gather(*(
            asyncio.wrap_future(lane.submit(_slot_loop(
                lane, plan, prompts, warm_records, t_pass, never,
                schedule.WARM_NEW_TOKENS, -(-entries // n))))
            for lane in lanes))
    info_warm = await _rpc_info(plan["server_port"])
    warm_s = time.time() - t_warm
    warm_failed = [r["error"] for r in warm_records if r["error"]]
    gc.collect()
    gc.freeze()

    # ---- the window: a closed loop of N slots replaying the fixed schedule.
    # The loop starts RAMP_S before the window (set-up): the first requests'
    # session opens and whatever the first seconds of the replay still need
    # lie before the window.
    records: list[dict] = []
    stop = threading.Event()
    loop_start = now() + 0.02
    w0 = loop_start + (schedule.RAMP_S if measured else 0.0)
    w1 = w0 + plan["seconds"]
    futures = [lane.submit(_slot_loop(
        lane, plan, prompts, records, loop_start, stop, None, None))
        for lane in lanes] if measured else []
    await asyncio.sleep(max(0.0, w0 - now() - 0.01))
    info0 = await _rpc_info(plan["server_port"])
    cpu0, wall0 = time.process_time(), now()

    async def trace_switch() -> None:
        await asyncio.sleep(max(0.0, w0 + plan["trace_at_s"] - now()))
        (control / "trace.start").touch()
        await asyncio.sleep(plan["trace_len_s"])
        (control / "trace.stop").touch()

    (control / "window.started").write_text(str(time.time() + (w0 - now())))
    tracer = (asyncio.create_task(trace_switch())
              if plan["trace"] and measured else None)
    await asyncio.sleep(max(0.0, w1 - now()))
    info1 = await _rpc_info(plan["server_port"])
    cpu1, wall1 = time.process_time(), now()
    # drain: no slot starts another request, and each finishes the one it is
    # in (a request due inside the window gets its first token, whose TTFT
    # belongs to the window; and no session is torn down under the server)
    stop.set()
    if futures:
        done, late = await asyncio.wait(
            [asyncio.ensure_future(asyncio.wrap_future(f)) for f in futures],
            timeout=schedule.DRAIN_S)
        for f in futures:
            f.cancel()
        await asyncio.gather(*late, return_exceptions=True)
        for d in done:
            d.result()
    if tracer is not None:
        await tracer
        deadline = time.time() + 120.0
        while not (control / "trace.done").exists():
            if time.time() > deadline:
                raise TimeoutError("the server never finished its trace")
            await asyncio.sleep(0.1)

    # ---- judged requests: outside the window, concurrently, logits kept
    t_judged = time.time()
    judged = await asyncio.gather(*(
        asyncio.wrap_future(lanes[k % n].submit(
            _judge_one(lanes[k % n], plan, prompts, j)))
        for k, j in enumerate(plan["judged_entries"])))
    info_end = await _rpc_info(plan["server_port"])
    await registry.close()
    for lane in lanes:
        lane.stop()

    for r in records:
        r.pop("session", None)
        for key in ("due", "start", "end"):
            if r[key] is not None:
                r[key] -= w0
        r["token_times"] = [t - w0 for t in r["token_times"]]
    cpus = sorted(os.sched_getaffinity(0))
    return {
        "window_s": plan["seconds"],
        "records": records,
        "info_ready": ready["info"], "info_before_warm": info_before_warm,
        "info_swept": info_swept, "info_warm": info_warm, "info0": info0,
        "info1": info1, "info_end": info_end,
        "judged": list(judged),
        "setup": {"server_announce_s": ready["announce_s"],
                  "server_ready_s": ready["ready_s"],
                  "client_load_s": client_load_s, "warm_s": warm_s,
                  "warm_steps": warm_steps,
                  "warm_requests": len(warm_records),
                  "warm_failed": warm_failed,
                  "judged_s": time.time() - t_judged},
        "loadgen": {
            "cpus": cpus, "jax_platform": jax.devices()[0].platform,
            "threads": n + 1,
            # CPU seconds per second of the window, all threads: against the
            # number of cores it was given, how near the generator came to
            # setting the pace itself. (The chip machine's kernel stores an
            # affinity mask and does not enforce it: XLA's CPU threads were
            # measured on 7.4 cores while pinned to 4, PERF.md section 6. So
            # this can pass len(cpus); it sizes thread pools all the same.)
            "cores_busy": (cpu1 - cpu0) / max(wall1 - wall0, 1e-9),
            "late_ms": [(r["start"] - r["due"]) * 1e3 for r in records
                        if r["start"] is not None],
        },
    }


def main(plan_path: str) -> int:
    plan = json.loads(pathlib.Path(plan_path).read_text())
    if plan["loadgen_cpus"]:
        os.sched_setaffinity(0, plan["loadgen_cpus"])
    got = asyncio.run(_run(plan))
    pathlib.Path(plan["out"]).write_text(json.dumps(got))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
