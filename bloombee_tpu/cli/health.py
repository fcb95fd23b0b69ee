"""Swarm health check: which blocks are covered, by whom, with what state.

Port of the reference's `bloombee.cli.health`-style checks
(tests/test_aux_functions.py) reading registry records + rpc_info.

    python -m bloombee_tpu.cli.health MODEL_UID --num-blocks 32 \\
        --registry 127.0.0.1:7700
"""

from __future__ import annotations

import argparse
import asyncio


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("model_uid", nargs="?", default=None)
    parser.add_argument("--num-blocks", type=int)
    parser.add_argument("--registry", default="127.0.0.1:7700",
                        help="registry address or comma-separated replicas")
    parser.add_argument("--probe", action="store_true",
                        help="also call rpc_info on every server")
    parser.add_argument("--switches", action="store_true",
                        help="print the BBTPU_* env switch table and exit "
                        "(reference README.environment-switches.md)")
    args = parser.parse_args(argv)
    if args.switches:
        from bloombee_tpu.utils import env

        env.import_declaring_modules()
        print(env.describe())
        return
    if args.model_uid is None or args.num_blocks is None:
        parser.error("model_uid and --num-blocks are required")

    async def run():
        from bloombee_tpu.swarm.registry import make_registry
        from bloombee_tpu.swarm.spans import compute_spans
        from bloombee_tpu.wire.rpc import connect

        from bloombee_tpu.swarm.data import ServerState

        reg = make_registry(args.registry)
        if args.probe:
            # the discovery plane is a server too: surface its audited
            # error swallows (registry_swallowed_errors) the same way
            for part in args.registry.split(","):
                part = part.strip()
                if not part:
                    continue
                rhost, rport = part.rsplit(":", 1)
                rline = f"  registry {part}"
                conn = None
                try:
                    conn = await connect(rhost, int(rport))
                    probe, _ = await asyncio.wait_for(
                        conn.call("rpc_info", {}), 5
                    )
                    rline += "  [reachable]"
                    for k in ("keys", "registry_swallowed_errors"):
                        if probe.get(k):
                            rline += f"  {k}={probe[k]}"
                except Exception as e:
                    rline += f"  [UNREACHABLE: {type(e).__name__}]"
                finally:
                    if conn is not None:
                        await conn.close()
                print(rline)
        infos = await reg.get_module_infos(
            args.model_uid, range(args.num_blocks)
        )
        # JOINING included so warm standbys are operator-visible; coverage
        # counts only servers routing can actually use (ONLINE/DRAINING)
        spans = compute_spans(infos, min_state=ServerState.JOINING)
        covered = {
            b
            for s in spans.values()
            if s.server_info.state >= ServerState.ONLINE
            for b in range(s.start, s.end)
        }
        missing = [b for b in range(args.num_blocks) if b not in covered]

        print(f"model {args.model_uid}: {len(spans)} server(s)")
        for sid, span in sorted(spans.items(), key=lambda kv: kv[1].start):
            info = span.server_info
            line = (
                f"  {sid}  blocks [{span.start}:{span.end})  "
                f"{info.host}:{info.port}  throughput={info.throughput:.2f}"
            )
            if info.state == ServerState.JOINING:
                line += "  STANDBY"
            if getattr(info, "promoted_standby", False):
                line += "  PROMOTED"
            if info.cache_tokens_left is not None:
                line += f"  cache_tokens_left={info.cache_tokens_left}"
            if getattr(info, "kv_repl", False):
                line += "  kv_repl"
            path_lines: list[str] = []
            if args.probe:
                conn = None
                try:
                    conn = await connect(info.host, info.port)
                    probe, _ = await asyncio.wait_for(
                        conn.call("rpc_info", {}), 5
                    )
                    line += "  [reachable]"
                    # failover/replication counters: lets an operator see
                    # replication running (or lagging) without log access
                    repl = {
                        k: probe[k]
                        for k in (
                            "repl_pages_sent",
                            "repl_pages_installed",
                            "repl_lag_pages",
                            "failover_replayed_tokens",
                        )
                        if probe.get(k)
                    }
                    if repl:
                        line += "  " + " ".join(
                            f"{k}={v}" for k, v in sorted(repl.items())
                        )
                    # nonzero arena_epoch = donated-arena self-heal
                    # events; sessions lost KV and had to replay
                    if probe.get("arena_epoch"):
                        line += f"  arena_epoch={probe['arena_epoch']}"
                    # stall-free scheduling counters: is chunked prefill
                    # firing, and are decode steps actually landing
                    # between chunks
                    sched = {
                        k: probe[k]
                        for k in (
                            "prefill_chunks",
                            "prefill_chunk_tokens",
                            "decode_steps_interleaved",
                        )
                        if probe.get(k)
                    }
                    if sched:
                        line += "  " + " ".join(
                            f"{k}={v}" for k, v in sorted(sched.items())
                        )
                    # mixed-batch dispatch counters: are decodes actually
                    # fusing into prefill-chunk device steps, and what the
                    # per-token dispatch amortization works out to
                    mixed = {
                        k: probe[k]
                        for k in (
                            "mixed_dispatches",
                            "mixed_tokens",
                            "step_dispatches",
                            "step_tokens",
                        )
                        if probe.get(k)
                    }
                    if mixed:
                        line += "  " + " ".join(
                            f"{k}={v}" for k, v in sorted(mixed.items())
                        )
                        dpt = probe.get("dispatches_per_token")
                        if dpt:
                            line += f"  dispatches_per_token={dpt:.3f}"
                    # speculative-decode counters: are tree-verify steps
                    # flowing, are they coalescing into group dispatches
                    # (--spec-batch), and what the swarm-measured draft
                    # acceptance works out to
                    spec = {
                        k: probe[k]
                        for k in (
                            "tree_steps",
                            "tree_rows",
                            "spec_tokens_drafted",
                            "spec_tokens_accepted",
                            "tree_group_dispatches",
                        )
                        if probe.get(k)
                    }
                    if spec:
                        line += "  " + " ".join(
                            f"{k}={v}" for k, v in sorted(spec.items())
                        )
                        rate = probe.get("spec_accept_rate")
                        if rate:
                            line += f"  spec_accept_rate={rate:.3f}"
                        width = probe.get("mean_tree_batch_width")
                        if width:
                            line += f"  mean_tree_batch_width={width:.2f}"
                    # universal ragged dispatch: fused dispatches, how
                    # many crossed row kinds, packs that met no compiled
                    # program and went as their parts, and any per-reason
                    # declines (an operator asked for fusing on a span
                    # that can't)
                    ragged = {
                        k: probe[k]
                        for k in (
                            "ragged_group_dispatches",
                            "ragged_cross_kind_dispatches",
                            "ragged_cold_splits",
                        )
                        if probe.get(k)
                    }
                    if ragged:
                        line += "  " + " ".join(
                            f"{k}={v}" for k, v in sorted(ragged.items())
                        )
                    declines = probe.get("ragged_declines") or {}
                    for reason, n in sorted(declines.items()):
                        line += f"  ragged_decline[{reason}]={n}"
                    # recurrent state beside the KV arena (a family with a
                    # state-space mixer): the slot pool, slots held, bytes
                    state = (probe.get("memory") or {}).get("state")
                    if state:
                        line += (
                            f"  memory.state=slots:{state['slots']}"
                            f",live:{state['live']},bytes:{state['bytes']}"
                        )
                    # layer kinds that differ in their cache: how many of
                    # each, and the rows and bytes of each kind's arena
                    kinds = probe.get("layer_kinds")
                    if kinds:
                        mem = probe.get("memory") or {}
                        line += (
                            "  layer_kinds="
                            + ",".join(f"{k}:{n}" for k, n in sorted(kinds.items()))
                            + f"  memory.kv=layers:{mem.get('kv_arena_layers')}"
                            f",bytes:{mem.get('kv_arena_bytes')}"
                            f"  memory.state.layers="
                            f"{mem.get('state_arena_layers')}"
                        )
                    # elastic self-healing counters: standby promotions /
                    # drain-backs and measured-load rebalance outcomes —
                    # the control loop's every decision, probeable without
                    # log access
                    elastic = {
                        k: probe[k]
                        for k in (
                            "promotions",
                            "demotions",
                            "promotions_yielded",
                            "demotions_aborted",
                            "rebalances_moved",
                            "rebalances_failed",
                            "rebalance_skipped_hysteresis",
                        )
                        if probe.get(k)
                    }
                    if elastic:
                        line += "  " + " ".join(
                            f"{k}={v}" for k, v in sorted(elastic.items())
                        )
                    # integrity counters: digest stamps emitted, audit
                    # re-executions served, liar-hook lies injected (test
                    # swarms only — nonzero here in production is an
                    # incident), and silent prefix hash-chain failures
                    integ = {
                        k: probe[k]
                        for k in (
                            "out_digests_sent",
                            "audit_forwards",
                            "liar_steps",
                            "seq_hash_extend_failures",
                        )
                        if probe.get(k)
                    }
                    if integ:
                        line += "  " + " ".join(
                            f"{k}={v}" for k, v in sorted(integ.items())
                        )
                    # lock-witness counters (BBTPU_LOCKWATCH=1 runs):
                    # observed acquisition-order edges and hierarchy
                    # violations — ANY nonzero lock_violations is a
                    # deadlock setup waiting for the right interleaving
                    watch = {
                        k: probe[k]
                        for k in (
                            "lock_order_edges",
                            "lock_violations",
                        )
                        if probe.get(k)
                    }
                    if watch:
                        line += "  " + " ".join(
                            f"{k}={v}" for k, v in sorted(watch.items())
                        )
                    # compile-witness counters (BBTPU_JITWATCH=1 runs):
                    # ANY nonzero steady_state_recompiles means a decode
                    # bucket escaped warmup — a first-token compile stall
                    # some session actually paid; ANY kernel_fallbacks is a
                    # Pallas kernel the device refused (witness on or off)
                    jit = {
                        k: probe[k]
                        for k in (
                            "xla_compiles",
                            "compile_ms_total",
                            "warmup_compiles",
                            "warmup_failures",
                            "kernel_fallbacks",
                            "steady_state_recompiles",
                            "compile_cache_hits",
                            "preinstalled_warmup_misses",
                            "host_syncs_hot_path",
                        )
                        if probe.get(k)
                    }
                    if jit:
                        line += "  " + " ".join(
                            f"{k}={v}" for k, v in sorted(jit.items())
                        )
                    # a family with experts: dispatches whose experts took
                    # the grouped form (a decode group reads only the
                    # experts its rows chose), the tiled one (a chunk above
                    # the ridge computes only its chosen pairs) or the dense
                    moe = probe.get("moe") or {}
                    if any(moe.values()):
                        line += "  moe " + " ".join(
                            f"{k}={v}" for k, v in sorted(moe.items())
                        )
                    # the tile a chunk's flash kernel multiplies at the
                    # span's shapes, by layer kind (block_q x block_k x
                    # query heads a tile: ops/pallas/flash_attention.py)
                    if probe.get("flash"):
                        line += f"  flash={probe['flash']}"
                    # a server that holds a share of the routed experts,
                    # and what a cached token costs where the page is a
                    # latent (deepseek_v2)
                    if probe.get("experts_held"):
                        first, count = probe["experts_held"]
                        line += f"  experts_held={first}:{count}"
                    if probe.get("latent_bytes_per_token"):
                        line += (
                            "  latent_bytes_per_token="
                            f"{probe['latent_bytes_per_token']}"
                        )
                    # where the host's time went (BBTPU_JITWATCH=1
                    # runs): the compute worker's wall time by cause —
                    # starved = no task existed, hop = one was queued and
                    # nobody ran it, busy = inside bbtpu.task — then the
                    # mean of every host span of the served step
                    worker = probe.get("worker") or {}
                    if worker.get("tasks"):
                        line += "  worker " + " ".join(
                            f"{k}={worker.get(k)}"
                            for k in ("tasks", "starved_ms", "hop_ms",
                                      "busy_ms")
                        )
                    spans = probe.get("host_spans") or {}
                    if spans:
                        line += "  host_spans " + " ".join(
                            f"{name}={v['total_ms'] / v['n']:.3f}msx{v['n']}"
                            for name, v in sorted(spans.items()) if v["n"]
                        )
                    # the worker's busy time by kind of dispatch, a line a
                    # kind: a task's wall, then each leg's (the self time
                    # of its span; `unnamed` is what no span covers), each
                    # with the share of it the thread was on the CPU in the
                    # tasks read in full, then of THEIR launches those that found
                    # the device idle and the jit call's mean on an idle and
                    # on a busy device (cost alone, cost + back-pressure)
                    for kind, rec in sorted(
                        (probe.get("host_path") or {}).items()
                    ):
                        n, on_idle = rec["n"], rec["launches_on_idle"]
                        busy = rec["launches"] - on_idle
                        path_lines.append(
                            f"    host_path.{kind} n={n} "
                            f"wall={rec['wall_ms'] / n:.3f}ms "
                            + ("(%.0f%%cpu of %d read in full)  " % (
                                100 * rec["cpu_ms"] / rec["cpu_wall_ms"],
                                rec["full_n"],
                            ) if rec["cpu_wall_ms"] else " ") + " ".join(
                                f"{leg.removeprefix('bbtpu.')}="
                                f"{v['wall_ms'] / n:.3f}" + (
                                    "(%.0f%%cpu)" % (
                                        100 * v["cpu_ms"] / v["cpu_wall_ms"]
                                    ) if v["cpu_wall_ms"] else ""
                                ) for leg, v in rec["legs"].items()
                            ) + f"  launches={rec['launches']} "
                            f"on_idle={on_idle}"
                            + (f" jit_idle={rec['jit_idle_ms'] / on_idle:.3f}ms"
                               if on_idle else "")
                            + (f" jit_busy={rec['jit_busy_ms'] / busy:.3f}ms"
                               if busy else "")
                        )
                    # a session's turn, reply to reply, by the step's
                    # class: the mean of each leg on the server's clock
                    # (wire/turn.py; negative_wire says a stamp is wrong)
                    for cls, rec in sorted((probe.get("turn") or {}).items()):
                        if rec.get("n"):
                            line += f"  turn.{cls} n={rec['n']} " + " ".join(
                                f"{k[:-3]}={v / rec['n']:.3f}ms"
                                for k, v in rec.items() if k.endswith("_ms")
                            ) + f" negative_wire={rec['negative_wire']}"
                    # prefills that reached this span in parts along the
                    # sequence, and how long their chunk loops stood
                    # waiting for rows still on their way (near 0: the
                    # device sets the pace; large: the upload still does)
                    parts = probe.get("prefill_parts") or {}
                    if parts.get("steps"):
                        line += "  prefill_parts " + " ".join(
                            f"{k}={parts.get(k)}"
                            for k in ("steps", "parts", "wait_ms")
                        )
                    # compile-artifact counters (BBTPU_ARTIFACT_DIR runs):
                    # fallback_compiles > 0 means a server abandoned
                    # pre-installed artifacts and paid local compiles;
                    # declines/evictions show the store defending itself
                    art = {
                        k: probe[k]
                        for k in (
                            "artifact_preinstalled",
                            "artifact_fallback_compiles",
                            "artifact_gets_served",
                            "artifact_puts_installed",
                            "artifact_puts_declined",
                            "artifact_blobs_fetched",
                            "artifact_fetch_retries",
                            "artifact_store_bytes",
                            "artifact_evictions",
                            "artifact_store_declined",
                        )
                        if probe.get(k)
                    }
                    if art:
                        line += "  " + " ".join(
                            f"{k}={v}" for k, v in sorted(art.items())
                        )
                    # wire transport: bytes actually shipped vs raw tensor
                    # bytes (compression working or not), codec seconds,
                    # and the off-loop pipeline's depth/backpressure — the
                    # bytes/token floor under every multi-span latency
                    # number, probeable without log access (BB006)
                    tr = probe.get("transport") or {}
                    for dr in ("tx", "rx"):
                        d = tr.get(dr) or {}
                        if d.get("n"):
                            line += (
                                f"  {dr}_wire_bytes={d['wire_bytes']}"
                                f"  {dr}_ratio={d['ratio']:.3f}"
                                f"  {dr}_codec_s={d['s']:.3f}"
                            )
                    pipe = probe.get("wire_pipeline") or {}
                    if pipe.get("tx_jobs") or pipe.get("rx_jobs"):
                        line += (
                            "  pipeline="
                            + ("on" if pipe.get("enabled") else "off")
                        )
                        for k in (
                            "tx_jobs",
                            "rx_jobs",
                            "rx_depth_max",
                            "rx_backpressure_waits",
                            "tx_limit",
                        ):
                            if pipe.get(k):
                                line += f"  {k}={pipe[k]}"
                    # session lease counters: are leases reaping abandoned
                    # sessions, are clients resuming instead of replaying,
                    # and is keepalive traffic flowing on idle conns
                    lease = {
                        k: probe[k]
                        for k in (
                            "sessions_reaped",
                            "sessions_resumed",
                            "steps_deduped",
                            "keepalives_sent",
                            "pushes_dropped",
                        )
                        if probe.get(k)
                    }
                    if lease:
                        line += "  " + " ".join(
                            f"{k}={v}" for k, v in sorted(lease.items())
                        )
                    # live session ages: a large oldest-idle with leases
                    # off (session_lease_s=0) is exactly the wedged-session
                    # leak this server would never clean up
                    if probe.get("sessions_parked"):
                        line += f"  sessions_parked={probe['sessions_parked']}"
                    for k in ("session_oldest_s", "session_oldest_idle_s"):
                        v = probe.get(k)
                        if v:
                            line += f"  {k}={v:.1f}"
                    waits = probe.get("queue_wait_ms") or {}
                    for cls in ("prefill", "decode"):
                        w = waits.get(cls) or {}
                        if w.get("p95"):
                            line += (
                                f"  {cls}_wait_p95={w['p95']:.1f}ms"
                            )
                    # live load snapshot: the same numbers the server
                    # adverts for load-aware routing
                    load = probe.get("load") or {}
                    for k in (
                        "delay_ms",
                        "queue_depth",
                        "mean_batch_width",
                        "chunk_streams",
                        "pages_free",
                        "active_sessions",
                    ):
                        v = load.get(k)
                        if v:
                            line += f"  {k}={v}"
                    if load.get("shedding"):
                        line += "  SHEDDING"
                    # admission counters: what got shed, with what retry
                    # hints, and which clients are over their fair share
                    adm = probe.get("admission") or {}
                    for k in (
                        "shed_requests",
                        "shed_sessions",
                        "admitted_new",
                    ):
                        if adm.get(k):
                            line += f"  {k}={adm[k]}"
                    hist = adm.get("retry_after_ms_hist") or {}
                    if any(hist.values()):
                        # keys look like "<=250ms" / ">10000ms": sort by
                        # the numeric bound, overflow bucket last
                        def _bound(k):
                            digits = "".join(c for c in k if c.isdigit())
                            return (
                                k.startswith(">"),
                                int(digits) if digits else 0,
                            )

                        line += "  retry_after_ms_hist=" + ",".join(
                            f"{b}:{n}"
                            for b, n in sorted(
                                hist.items(), key=lambda kv: _bound(kv[0])
                            )
                            if n
                        )
                    debts = adm.get("client_debts") or {}
                    over = {
                        c: d for c, d in debts.items() if d > 0
                    }
                    if over:
                        line += "  over_share=" + ",".join(
                            f"{c}:{d:+.2f}"
                            for c, d in sorted(
                                over.items(), key=lambda kv: -kv[1]
                            )
                        )
                except Exception as e:
                    line += f"  [UNREACHABLE: {type(e).__name__}]"
                finally:
                    if conn is not None:
                        await conn.close()
            print("\n".join([line, *path_lines]))
        if missing:
            print(f"  MISSING blocks: {missing}")
            raise SystemExit(1)
        print("  swarm is COMPLETE")

    asyncio.run(run())


if __name__ == "__main__":
    main()
