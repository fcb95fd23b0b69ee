"""Run a worker server hosting a span of blocks.

Reference: /root/reference/src/bloombee/cli/run_server.py:18-231. Block
selection is automatic when --blocks is omitted: the server measures its
compute throughput, fetches the swarm's current coverage from the registry,
and picks the least-served window (reference block_selection.py).

    python -m bloombee_tpu.cli.run_server /path/to/model \\
        --registry 10.0.0.1:7700 --blocks 0:16 --port 7800
"""

from __future__ import annotations

import argparse
import asyncio
import logging


def parse_experts(text):
    """'FIRST:COUNT' -> (first, count), None for no --experts."""
    if not text:
        return None
    first, sep, count = text.partition(":")
    if not sep or not first.isdigit() or not count.isdigit() or not int(count):
        raise SystemExit(f"bad --experts {text!r}: need FIRST:COUNT")
    return int(first), int(count)


def parse_adapters(items):
    """NAME=DIR pairs (or bare DIRs, named by basename) -> {name: dir}."""
    if not items:
        return None
    import os

    out = {}
    for item in items:
        name, sep, path = item.partition("=")
        if not sep or os.sep in name or (os.altsep and os.altsep in name):
            # bare DIR (possibly containing '='): name = basename
            name, path = os.path.basename(os.path.normpath(item)), item
        if not name or not path:
            raise SystemExit(f"bad --adapters entry {item!r}: need NAME=DIR")
        if name in out:
            raise SystemExit(f"duplicate adapter name {name!r} in --adapters")
        out[name] = path
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("model_dir", help="local HF model directory")
    parser.add_argument("--model-uid", default=None,
                        help="swarm uid (default: model dir name)")
    parser.add_argument("--registry", default="127.0.0.1:7700",
                        help="registry address, or a comma-separated "
                             "replica list host:port,host:port (announces "
                             "go to every replica)")
    parser.add_argument("--blocks", default=None,
                        help="'start:end' or omit for automatic selection")
    parser.add_argument("--num-blocks", type=int, default=None,
                        help="how many blocks to serve when auto-selecting")
    parser.add_argument("--experts", default=None, metavar="FIRST:COUNT",
                        help="hold only COUNT of each sparse layer's routed "
                             "experts, from FIRST of the router's numbering "
                             "(one chip's share of an expert-parallel "
                             "deployment: the router still scores all of "
                             "them, a pair whose expert is not held adds "
                             "nothing here). Default: every expert the "
                             "checkpoint has. Announced in rpc_info "
                             "(experts_held)")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--public-host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--num-pages", type=int, default=256)
    parser.add_argument("--page-size", type=int, default=16)
    parser.add_argument("--max-chunk-tokens", type=int, default=512)
    parser.add_argument("--max-batch", type=int, default=8,
                        help="continuous batching: coalesce up to this many "
                             "concurrent sessions' single-token decode "
                             "steps into one span dispatch (1 disables; "
                             "gather window via BBTPU_BATCH_WINDOW_MS)")
    parser.add_argument("--mixed-batch", action="store_true", default=None,
                        help="mixed-batch dispatch: fuse a prefill chunk "
                             "and compatible queued decode steps into ONE "
                             "ragged span dispatch (Sarathi-Serve fused "
                             "iterations) instead of a dispatch each; "
                             "needs --prefill-chunk to produce chunks. "
                             "Default follows BBTPU_MIXED_BATCH")
    parser.add_argument("--prefill-chunk", type=int, default=None,
                        help="stall-free scheduling: split prefills into "
                             "chunks of at most this many tokens, each its "
                             "own compute-queue task, so concurrent "
                             "sessions' decode steps interleave between "
                             "chunks (0 = monolithic prefill; default "
                             "follows BBTPU_PREFILL_CHUNK; aging via "
                             "BBTPU_CHUNK_AGE_S)")
    parser.add_argument("--dtype", default="bfloat16",
                        choices=["bfloat16", "float32"])
    parser.add_argument("--adapter-dirs", nargs="*", default=None,
                        help="LoRA adapter directories to merge into blocks")
    parser.add_argument("--adapters", nargs="*", default=None,
                        metavar="NAME=DIR",
                        help="per-request switchable LoRA adapters "
                             "(clients pick one via active_adapter; "
                             "bare DIR uses its basename as the name)")
    parser.add_argument("--announce-period", type=float, default=5.0)
    parser.add_argument("--rebalance-period", type=float, default=None,
                        help="seconds between swarm-balance checks; the "
                             "server drains and moves its span when the "
                             "least-served window beats the hysteresis "
                             "(0 disables; default 300, or 0 when --blocks "
                             "pins the span; reference server.py:479-542)")
    parser.add_argument("--drain-timeout", type=float, default=30.0,
                        help="how long a drain (SIGTERM/SIGINT shutdown or "
                             "a rebalance) waits for live sessions before "
                             "exiting / swapping the span under them")
    parser.add_argument("--weight-quant", default=None,
                        choices=["none", "int8", "int4"],
                        help="weight-only quantization for the served span "
                             "(int8 halves / int4 quarters weight HBM "
                             "bytes per decode step; compute stays bf16)")
    parser.add_argument("--attn-sparsity", type=float, default=1.0,
                        help="<1.0: approximate decode attention keeping "
                             "only the top fraction of past keys per query "
                             "(FlexGen Policy.attn_sparsity)")
    parser.add_argument("--offload-layers", type=int, default=0,
                        help="stream the span's last N layers' weights from "
                             "host memory per step (serve spans larger than "
                             "HBM; pair with --weight-quant to shrink the "
                             "streamed bytes)")
    parser.add_argument("--kv-quant", default=None,
                        choices=["none", "int4"],
                        help="KV cache quantization (int4 = ~3.2x capacity)")
    parser.add_argument("--prefix-cache", action="store_true", default=None,
                        help="share KV pages of common prompt prefixes "
                             "across sessions (refcounted hash pool with "
                             "copy-on-write; clients probe before prefill "
                             "and ship only the uncached suffix). Default "
                             "follows BBTPU_PREFIX_CACHE")
    parser.add_argument("--oversubscribe", type=float, default=1.0,
                        help="admit up to this x KV capacity; idle "
                        "sessions' KV parks to host under pressure")
    parser.add_argument("--idle-park-s", type=float, default=5.0,
                        help="a session idle this long may be parked")
    parser.add_argument("--tp", type=int, default=1,
                        help="tensor-parallel degree over local chips "
                        "(reference --tensor_parallel_devices)")
    parser.add_argument("--sp", type=int, default=1,
                        help="sequence-parallel degree: prefills of >= "
                        "BBTPU_SP_MIN_TOKENS spread over this many local "
                        "chips via ring attention; decode stays "
                        "single-chip paged")
    parser.add_argument("--admit", action="store_true", default=None,
                        help="admission control: past the queue-delay high "
                             "watermark, shed NEW sessions/prefills with a "
                             "retriable `overloaded` error (established "
                             "sessions' decode steps are always admitted; "
                             "heavy clients shed first via per-client "
                             "fair-share accounting). Default follows "
                             "BBTPU_ADMIT")
    parser.add_argument("--admit-high-ms", type=float, default=None,
                        help="queue-delay high watermark in ms before the "
                             "admission controller starts shedding (default "
                             "follows BBTPU_ADMIT_HIGH_MS)")
    parser.add_argument("--session-lease-s", type=float, default=None,
                        help="session lease: a session whose client goes "
                             "silent (no step, no keepalive) this long is "
                             "reaped — its KV pages become evictable cached "
                             "prefix-pool entries, then free. Disconnected "
                             "clients may reconnect-resume a parked session "
                             "within the lease, token-identical and with "
                             "zero prompt replay (0 disables; default "
                             "follows BBTPU_SESSION_LEASE_S)")
    parser.add_argument("--keepalive-s", type=float, default=None,
                        help="wire keepalive interval: ping idle "
                             "connections, declare them dead after ~2.5x "
                             "silence, so half-open TCP (partition, silent "
                             "peer death) is detected instead of hanging "
                             "(0 disables; default follows "
                             "BBTPU_KEEPALIVE_S)")
    parser.add_argument("--standby", action="store_true",
                        help="start as a WARM STANDBY for the span: load "
                             "weights and accept kv_put replication but "
                             "announce JOINING (no routed traffic), then "
                             "self-promote to a serving replica on "
                             "sustained span overload or server loss and "
                             "drain back when the span cools (watermarks "
                             "via --promote-high-ms/--promote-low-ms; "
                             "requires --blocks or --num-blocks matching "
                             "the primary's span)")
    parser.add_argument("--promote-high-ms", type=float, default=None,
                        help="standby promotion high watermark: promote "
                             "when the span's best serving server sustains "
                             "this much predicted queue delay in ms "
                             "(default follows BBTPU_PROMOTE_HIGH_MS)")
    parser.add_argument("--promote-low-ms", type=float, default=None,
                        help="demotion low watermark: a promoted standby "
                             "drains back once other coverage sustains "
                             "below this (default follows "
                             "BBTPU_PROMOTE_LOW_MS)")
    parser.add_argument("--promote-sustain-s", type=float, default=None,
                        help="how long the hot/cool condition must hold "
                             "before promoting/demoting (default follows "
                             "BBTPU_PROMOTE_SUSTAIN_S)")
    parser.add_argument("--promote-jitter-s", type=float, default=None,
                        help="promotion-storm guard: random pre-promotion "
                             "delay bound + re-check so N standbys don't "
                             "all promote at once (default follows "
                             "BBTPU_PROMOTE_JITTER_S)")
    parser.add_argument("--load-advert-s", type=float, default=None,
                        help="republish the live load snapshot at this "
                             "cadence (seconds) when faster than "
                             "--announce-period; 0 keeps the announce "
                             "cadence (default follows BBTPU_LOAD_ADVERT_S)")
    parser.add_argument("--warmup-batches", default="1",
                        help="comma-separated batch buckets to pre-compile "
                        "at startup ('' = skip)")
    parser.add_argument("--artifact-dir", default=None,
                        help="directory for the swarm-shared compile-"
                             "artifact store: persistent JAX compilation "
                             "cache served to peers over artifact_get and "
                             "pre-fetched from covering peers before "
                             "warmup compiles anything (default follows "
                             "BBTPU_ARTIFACT_DIR; unset = no store; where "
                             "JAX_COMPILATION_CACHE_DIR is set the store "
                             "serves that directory)")
    parser.add_argument("--log-level", default="INFO")
    args = parser.parse_args(argv)
    logging.basicConfig(level=args.log_level)

    import jax.numpy as jnp

    from bloombee_tpu.models.checkpoint import load_spec
    from bloombee_tpu.server.block_selection import (
        choose_best_blocks,
        choose_num_blocks,
    )
    from bloombee_tpu.server.block_server import BlockServer
    from bloombee_tpu.swarm.registry import make_registry
    from bloombee_tpu.swarm.spans import compute_spans

    from bloombee_tpu.server import artifacts
    from bloombee_tpu.utils import env

    # one persistent compile cache per server process, placed by
    # JAX_COMPILATION_CACHE_DIR, else the artifact store's directory, else
    # the checkout's fixed path
    cache_dir = artifacts.enable_persistent_cache(
        args.artifact_dir or env.get("BBTPU_ARTIFACT_DIR") or None
    )
    logging.info("persistent compile cache: %s", cache_dir)
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    # parse the registry spec BEFORE model resolution: a typo'd --registry
    # must fail fast, not after a multi-GB hub download
    registry = make_registry(args.registry)
    from bloombee_tpu.models.hub import resolve_model_dir

    args.model_dir = resolve_model_dir(args.model_dir)
    spec = load_spec(args.model_dir, parse_experts(args.experts))
    model_uid = args.model_uid or args.model_dir.rstrip("/").split("/")[-1]

    async def run():
        if args.blocks:
            start, end = (int(x) for x in args.blocks.split(":"))
            reason = spec.span_unsupported(start, end)
            if reason is not None:
                raise SystemExit(f"--blocks {args.blocks}: {reason}")
            if args.rebalance_period is None:
                # operator pinned the span: do not auto-move it out from
                # under them unless they ALSO asked for rebalancing
                args.rebalance_period = 0.0
        else:
            infos = await registry.get_module_infos(
                model_uid, range(spec.num_hidden_layers)
            )
            n = args.num_blocks or choose_num_blocks(
                spec, dtype, args.num_pages, args.page_size,
                max_batch=args.max_batch,
            )
            start, end = choose_best_blocks(
                # departing (DRAINING) servers are not coverage
                infos, compute_spans(infos, include_draining=False), n,
                spec=spec,
            )
            logging.info(
                "auto-selected blocks [%d:%d) (%d blocks)", start, end, n
            )

        server = BlockServer(
            model_uid=model_uid, start=start, end=end,
            model_dir=args.model_dir, registry=registry,
            host=args.host, port=args.port, public_host=args.public_host,
            num_pages=args.num_pages, page_size=args.page_size,
            compute_dtype=dtype, max_chunk_tokens=args.max_chunk_tokens,
            max_batch=args.max_batch,
            mixed_batch=args.mixed_batch,
            prefill_chunk=args.prefill_chunk,
            announce_period=args.announce_period,
            adapter_dirs=args.adapter_dirs,
            adapters=parse_adapters(args.adapters),
            tp=args.tp,
            sp=args.sp,
            kv_quant=args.kv_quant,
            experts=parse_experts(args.experts),
            weight_quant=args.weight_quant,
            oversubscribe=args.oversubscribe,
            idle_park_s=args.idle_park_s,
            prefix_cache=args.prefix_cache,
            offload_layers=args.offload_layers,
            attn_sparsity=args.attn_sparsity,
            rebalance_period=(
                300.0 if args.rebalance_period is None
                else args.rebalance_period
            ),
            drain_timeout=args.drain_timeout,
            admit=args.admit,
            admit_high_ms=args.admit_high_ms,
            load_advert_s=args.load_advert_s,
            session_lease_s=args.session_lease_s,
            keepalive_s=args.keepalive_s,
            standby=args.standby,
            promote_high_ms=args.promote_high_ms,
            promote_low_ms=args.promote_low_ms,
            promote_sustain_s=args.promote_sustain_s,
            promote_jitter_s=args.promote_jitter_s,
            artifact_dir=args.artifact_dir,
        )
        # the warm-up's task stands BEFORE the server announces itself:
        # `rpc_info`'s `warmup_done` is "no start-up task is left", and
        # `start()` still awaits after its announcement (the native
        # components are built on a machine's first look), so a client that
        # met the record in the registry read "done" before the task was
        # made and sent its first steps into the queue beside the warm-up's
        # compiles, where a cold cache outlasts their deadline
        started = asyncio.Event()
        warm = None
        if args.warmup_batches:
            batches = tuple(
                int(x) for x in args.warmup_batches.split(",") if x
            )

            async def warm_once_started():
                await started.wait()
                await server.warmup(batches)

            warm = server._warmup_task = asyncio.create_task(
                warm_once_started()
            )
        await server.start()
        started.set()
        from bloombee_tpu.server.throughput import measure_and_announce

        async def measure_when_warm():
            # after the warm-up, not beside it: both feed the one compute
            # queue, and decode steps timed between warm-up compiles
            # announce the compiler's speed as the device's
            if warm is not None:
                await asyncio.wait([warm])
            return await measure_and_announce(server)

        # keep a strong reference: the loop holds tasks only weakly
        server._throughput_task = asyncio.create_task(measure_when_warm())
        logging.info(
            "server %s serving %s[%d:%d) on port %d",
            server.server_id, model_uid, start, end, server.port,
        )
        # graceful shutdown: SIGTERM/SIGINT announce DRAINING (routing
        # stops sending new sessions), pending session-KV replication is
        # flushed to standbys (so surviving sessions fail over with at
        # most the unsealed tail to replay), in-flight sessions finish up
        # to --drain-timeout, then the span is revoked and the process
        # exits
        import signal

        stop_requested = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                loop.add_signal_handler(
                    sig,
                    lambda s=sig: (
                        logging.info(
                            "received %s: draining before exit",
                            signal.Signals(s).name,
                        ),
                        stop_requested.set(),
                    ),
                )
            except NotImplementedError:
                pass  # platform without signal handler support
        await stop_requested.wait()
        await server.drain()

    asyncio.run(run())


if __name__ == "__main__":
    main()
