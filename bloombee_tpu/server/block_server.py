"""BlockServer: one worker hosting blocks [start, end) of a model.

Maps the reference worker topology (SURVEY.md sections 3.1/3.3) onto one
asyncio process:

- `rpc_inference` stream == the per-session decode loop
  (reference handler.py:798-1257 + block_functions.py:629). Each step arrives
  either from the client stream or from an upstream server's `rpc_push`
  (server-to-server pipeline, handler.py:1850-2151); the session races both
  sources like the reference's `_iterate_inference_steps`.
- `rpc_push` == upstream activation push; the step metadata carries the
  remaining route so each hop forwards to the next
  (reference `_collect_next_servers`, client/inference_session.py:388-396).
- `rpc_forward` == training-style span forward without a decode session.
- `rpc_info` == ServerInfo snapshot (handler.py:3256 rpc_info).
- A background announcer re-declares the span in the registry every
  `announce_period` with expiration as the liveness signal
  (reference ModuleAnnouncerThread, server.py:914-1007).
"""

from __future__ import annotations

import asyncio
import collections
import dataclasses
import logging
import random
import uuid
from collections import OrderedDict

import numpy as np

import jax.numpy as jnp

from bloombee_tpu.kv.cache_manager import (
    CacheManager,
    ParkedKVLost,
    SessionKVLost,
    state_slots_for,
)
from bloombee_tpu.models.spec import ModelSpec
from bloombee_tpu.runtime.executor import (
    SpanExecutor,
    plan_prefill_chunks,
    prefill_chunk_len,
)
from bloombee_tpu.server import artifacts
from bloombee_tpu.server.promotion import PromotionLoopMixin
from bloombee_tpu.server.compute_queue import (
    PRIORITY_INFERENCE,
    PRIORITY_TRAINING,
    ComputeQueue,
    DeadlineExpired,
    aged_chunk_priority,
)
from bloombee_tpu.swarm.data import ServerInfo, ServerState
from bloombee_tpu.utils import clock, env, jitwatch, ledger, lockwatch
from bloombee_tpu.wire import turn
from bloombee_tpu.wire.flow import FlowLimiter
from bloombee_tpu.wire.rpc import (
    Connection,
    ConnectionClosed,
    OverloadedError,
    RpcError,
    RpcServer,
    Stream,
    connect,
)
from bloombee_tpu.wire.tensor_codec import name_for_dtype

logger = logging.getLogger(__name__)

env.declare(
    "BBTPU_DUMP_ACTIVATIONS", str, "",
    "directory to dump per-step hidden in/out as .npz (reference "
    "real_activation_dumper); empty = off",
)
env.declare(
    "BBTPU_DUMP_LIMIT", int, 100,
    "max activation dumps per server process",
)
env.declare(
    "BBTPU_PRUNER_TRAIN", bool, False,
    "train the MidLMHead online from accepted speculative paths (reference "
    "lm_head_trainer)",
)
env.declare(
    "BBTPU_PRUNER_CKPT", str, "",
    "pruner-head checkpoint path: loaded at init if present, saved every "
    "50 train steps (the neural scorer uses a '.net' sidecar)",
)
env.declare(
    "BBTPU_PRUNER_METHOD", str, "simple",
    "draft-tree pruning strategy: 'simple' (probability threshold, "
    "reference simple_probability_pruner) or 'neural' (learned MLP over "
    "probability features, reference adaptive_neural_pruner)",
)
env.declare(
    "BBTPU_WEIGHT_QUANT", str, "none",
    "weight-only quantization for served spans: none | int8 (per-column "
    "symmetric, ~2x decode-bandwidth headroom) | int4 (group-wise "
    "asymmetric, ~4x); compute stays bf16 (reference compression.py "
    "weight compression)",
)
env.declare(
    "BBTPU_REPL_INFLIGHT", int, 2,
    "max concurrent standby-replication sweeps per server (the kv_put "
    "sender side of session-KV replication; each sweep holds one export "
    "+ one wire push at a time)",
)
env.declare(
    "BBTPU_LOAD_ADVERT_S", float, 0.0,
    "load-advert cadence: refresh and announce the ServerInfo.load "
    "snapshot (queue waits, depth, batch width, pages free) this often; "
    "the effective announce period becomes min(announce_period, this), so "
    "load telemetry can be fresher than liveness announces (0 = piggyback "
    "on every regular announce only)",
)
env.declare(
    "BBTPU_SESSION_LEASE_S", float, 0.0,
    "session lease: a session whose client stream died (or went silent past "
    "this long with keepalives off) is PARKED — its KV pages are handed to "
    "the prefix pool as evictable refcount-0 cached entries, so a wedged or "
    "partitioned client can never pin memory — and stays resumable "
    "(resume: session_id on a fresh stream) for one more lease period "
    "before final reclaim. 0 disables leases: a dead stream frees the "
    "session immediately (seed behavior). Pair with BBTPU_KEEPALIVE_S so "
    "half-open streams are detected promptly; a lease alone only fences a "
    "session after a full silent lease period",
)
env.declare(
    "BBTPU_MIXED_BATCH", bool, False,
    "mixed-batch dispatch (Sarathi-Serve fused iterations): let a popped "
    "prefill chunk absorb compatible queued single-token decode steps "
    "(and vice versa) into ONE ragged span dispatch "
    "(executor.ragged_group; with --spec-batch also on, tree-verify rows "
    "join the same dispatch), so a mid-stream prefill no longer costs "
    "decodes a whole dispatch each. Falls back to separate dispatches on "
    "configs the ragged step doesn't cover (weight offload, hetero "
    "spans, top-k attention; TP meshes run the fused path via the dense "
    "sharded attend), surfacing each declined reason in rpc_info "
    "ragged_declines. Off = the decode-only batcher and per-chunk "
    "prefill tasks, byte-for-byte",
)
env.declare(
    "BBTPU_PROMOTE_HIGH_MS", float, 1500.0,
    "standby promotion high watermark: a standby promotes itself to a "
    "serving replica when its span's best serving server has sustained "
    "this much predicted queue delay (ms) — or immediately when the "
    "span has NO live serving server (advert silence past the lease)",
)
env.declare(
    "BBTPU_PROMOTE_LOW_MS", float, 200.0,
    "standby demotion low watermark: a promoted standby drains back to "
    "standby once the span's OTHER serving servers have sustained "
    "predicted queue delay below this (ms) and cover every block — "
    "the high/low gap is the promotion hysteresis band",
)
env.declare(
    "BBTPU_PROMOTE_SUSTAIN_S", float, 10.0,
    "how long the hot (cool) condition must hold before a standby "
    "promotes (a promoted replica demotes); one flappy advert window "
    "must not churn replicas",
)
env.declare(
    "BBTPU_PROMOTE_JITTER_S", float, 2.0,
    "promotion-storm guard: a standby sleeps uniform(0, this) seconds "
    "and RE-CHECKS the trigger before declaring itself serving, so N "
    "standbys watching one hot span don't all promote at once (a "
    "peer's promotion clears the trigger for the rest)",
)
env.declare(
    "BBTPU_SPEC_BATCH", bool, False,
    "batched tree-speculative verification: let concurrent sessions' "
    "tree-verify steps that share (layers, adapter, dtype) pad/stack into "
    "ONE ragged span dispatch (executor.ragged_group; with --mixed-batch "
    "also on, tree rows fuse with decode rows and a prefill chunk in the "
    "same dispatch) instead of a solo dispatch per speculating session; "
    "per-session speculative KV still commits/rolls back row-by-row and "
    "the accept-rides-next-step protocol is unchanged. Falls back to solo "
    "tree steps on configs the ragged tree step doesn't cover (weight "
    "offload, hetero spans, top-k attention, sliding-window layers; TP "
    "meshes run the fused path via the dense sharded attend). Off = "
    "every tree-verify step dispatches solo, byte-for-byte",
)
env.declare(
    "BBTPU_LIAR_P", float, 0.0,
    "TEST HOOK (Byzantine fault injection): per-step probability this "
    "server perturbs its span-output hidden states BEFORE serialization "
    "— a well-formed reply carrying wrong numbers, the lie the client "
    "integrity layer (BBTPU_INTEGRITY / BBTPU_AUDIT_P) exists to catch. "
    "Seeded by BBTPU_LIAR_SEED for reproducible chaos runs; never enable "
    "in real serving",
)
env.declare(
    "BBTPU_LIAR_SEED", int, 0,
    "seed for the BBTPU_LIAR_P perturbation RNG (which steps lie and "
    "how), so integrity chaos runs are reproducible",
)


def _tail_rows(out, n: int):
    """The last `n` positions of each sequence of a step's lazy output
    `[B, T, D]`, or of a chunked prefill's list of per-chunk outputs: a
    device slice of the last chunk(s), nothing of the others."""
    if not isinstance(out, (list, tuple)):
        return out[:, -n:]
    kept, have = [], 0
    for chunk in reversed(out):
        kept.append(chunk[:, -(n - have):])
        have += kept[-1].shape[1]
        if have >= n:
            break
    return kept[0] if len(kept) == 1 else kept[::-1]


class _StepRows:
    """A prefill step's rows along the sequence axis: whole in the step's
    one frame, or still arriving in PARTS. A client whose first span
    advertises the chunk length it plans with (`ServerInfo.prefill_chunk`)
    sends a plain committing prefill as ONE step in several frames cut at
    multiples of it: the first carries the step's meta and `parts: [count,
    rows of the whole step]`, each later one `{"step", "part"}` and its
    rows. `take` hands the chunk loop one chunk's rows and reads the next
    part off the session's stream where they are not there yet (no other
    `recv` is armed while a step is handled; the connection reads and
    decodes ahead), so the device starts on the first part while the client
    is still encoding the rest. A whole frame is the same with one part."""

    def __init__(self, first: np.ndarray, meta: dict, stream: Stream,
                 deadline: float | None, session_id: str, counts: dict):
        self._parts = [(0, first)]  # (first row, [B, t, D]) in order
        self._have = self.tokens = int(first.shape[1])
        self.batch = int(first.shape[0])
        self._stream, self._deadline = stream, deadline
        self._session, self._step = session_id, meta.get("step")
        self._counts = counts
        parts = meta.get("parts")
        if parts is None:
            return
        if (
            not isinstance(parts, (list, tuple)) or len(parts) != 2
            or not all(isinstance(x, int) and not isinstance(x, bool)
                       for x in parts)
            or parts[0] < 2 or parts[1] <= self._have
        ):
            raise ValueError(
                f"parts must be [count >= 2, rows beyond the first part's "
                f"{self._have}], got {parts!r}"
            )
        self.tokens = parts[1]
        counts["steps"] += 1
        counts["parts"] += 1

    async def take(self, s: int, e: int) -> np.ndarray:
        """Rows [s, e) of every sequence, in the sender's dtype."""
        while self._have < e:
            await self._next_part()
        held = [
            rows[:, max(s - at, 0):e - at] for at, rows in self._parts
            if at < e and at + rows.shape[1] > s
        ]
        return held[0] if len(held) == 1 else np.concatenate(held, axis=1)

    async def _next_part(self) -> None:
        t0 = turn.now_ns()
        try:
            item = await asyncio.wait_for(
                self._stream.recv(), clock.remaining(self._deadline)
            )
        except asyncio.TimeoutError:
            raise DeadlineExpired(
                "client deadline expired between the parts of a prefill"
            ) from None
        if item is None:
            raise RpcError("stream closed between the parts of a prefill")
        meta, tensors = item
        no, first = len(self._parts), self._parts[0][1]
        rows = np.asarray(tensors[0]) if tensors else None
        if (
            meta.get("step") != self._step or meta.get("part") != no
            or rows is None or rows.dtype != first.dtype or rows.ndim != 3
            or (rows.shape[0], rows.shape[2]) != (first.shape[0],
                                                  first.shape[2])
            or not 0 < rows.shape[1] <= self.tokens - self._have
        ):
            raise RpcError(
                f"expected part {no} of step {self._step} "
                f"({self.tokens - self._have} rows to come), got {meta}"
            )
        self._parts.append((self._have, rows))
        self._have += int(rows.shape[1])
        wait_us = max(0, turn.now_ns() - t0) // 1000
        self._counts["parts"] += 1
        self._counts["wait_us"] += wait_us
        with jitwatch.span("bbtpu.prefill.part", session=self._session,
                           step=self._step, part=no,
                           rows=int(rows.shape[1]), wait_us=wait_us):
            pass


class _ChainError(RuntimeError):
    """A downstream span of a chained decode_n reported failure (pushed
    back as `chain_error`). `permanent` distinguishes capability declines
    (tail has no head params / dtype mismatch — retrying the same route
    can never work, the client should fall back to per-step) from
    transient route failures (a span died mid-chain — the client should
    rebuild, replay, and RETRY chained decode on the fresh route)."""

    def __init__(self, msg: str, permanent: bool = False):
        super().__init__(msg)
        self.permanent = permanent


class _Member:
    """What the compute queue reads off a group member for its
    `bbtpu.task` span."""

    @property
    def rows(self) -> int:
        return int(self.hidden.shape[0]) * int(self.hidden.shape[1])

    @property
    def state_rows(self) -> int:
        """Sequences of this member that read and write a recurrent-state
        slot in the step (0 for a family without a state-space mixer)."""
        return self.handle.batch_size if self.session.has_state else 0


@dataclasses.dataclass
class _BatchMember(_Member):
    """One session's single-token decode step inside a merged dispatch
    (continuous batching). `handle` is the session's cache handle or a row
    slice of it (micro-batch chunks batch like any other member)."""

    session: "_Session"
    handle: object
    hidden: np.ndarray  # [b, 1, D] in the wire dtype


@dataclasses.dataclass
class _ChunkMember(_Member):
    """One prefill chunk inside a MIXED dispatch (--mixed-batch): the
    multi-token member that rides a ragged span step alongside other
    sessions' single-token decodes. `first`/`last` carry the chunk
    stream's settle/commit duties into whichever dispatch runs it."""

    session: "_Session"
    handle: object
    hidden: np.ndarray  # [b, t, D] in the wire dtype
    first: bool
    last: bool
    prefix_skip: object = None
    reply_rows: int | None = None  # of this chunk's rows, the last n are
    # among those the client reads (`reply_tail`); None: every row


@dataclasses.dataclass
class _TreeMember(_Member):
    """One session's tree-verify step inside a batched ragged dispatch
    (--spec-batch): the linearized draft tree's rows verify alongside
    other sessions' trees in one executor.ragged_group call. `handle` may be
    a row slice of the session handle (the client shrinks the step to its
    live-row window as rows finish)."""

    session: "_Session"
    handle: object
    hidden: np.ndarray  # [b, t, D] in the wire dtype
    tree_mask: np.ndarray  # [b, t, t] bool ancestor-or-self visibility
    depths: np.ndarray  # [b, t] i32 node depths (rotary offsets)


class _Session:
    def __init__(self, session_id: str, handle, batch_size: int,
                 layers: tuple[int, int] | None = None,
                 adapter: str | None = None,
                 client_id: str | None = None):
        self.id = session_id
        self.handle = handle
        self.batch_size = batch_size
        self.layers = layers  # relative (l0, l1) within this server's span
        self.has_state = False  # the family keeps a recurrent-state slot a row
        self.adapter = adapter  # per-request LoRA adapter name (or base)
        # admission-control identity: the client's self-declared id (one
        # per client process) or the session id when an old client sends
        # none — fair-share accounting then degrades to per-session
        self.client_id = client_id or session_id
        self.push_inbox: asyncio.Queue = asyncio.Queue()
        # chained decode_n control messages (the tail span's selected ids /
        # errors) land here directly from rpc_push — NOT via push_inbox,
        # whose consumer (the session loop) is blocked inside the
        # coordinator while it waits for exactly these messages
        self.chain_inbox: asyncio.Queue = asyncio.Queue()
        self.step_tasks: set[asyncio.Task] = set()  # in-flight mb chunks
        self.last_step_at = 0.0  # idle measure for the parking reclaimer
        # per-session timing accumulators (server half of the reference's
        # [TIMING_TABLE] decomposition, handler.py:1276-1605)
        self.n_steps = 0
        self.sum_tokens = 0
        self.sum_dispatch_ms = 0.0
        self.sum_fetch_ms = 0.0
        self.opened_at = 0.0
        # this session's stamps of the turn's legs; a session a server
        # opens sums into the server's account (_rpc_inference)
        self.turns = turn.ServerTurns(turn.TurnAccount(), session_id, None)
        # last pruned tree step's (hidden, tokens, parents) for online
        # pruner-head training when its accept arrives
        self.last_tree = None
        # per-session measured speculation: drafted tree tokens this
        # session verified and how many its accepts kept (the server half
        # of the drafter's feedback loop — surfaced via rpc_info so an
        # operator can see which streams speculate productively)
        self.spec_drafted = 0
        self.spec_accepted = 0
        # session-KV replication to a standby (client-directed kv_repl
        # items): standby (host, port), the client's full-history hash
        # chains per row, pages already shipped per row, and a lock so
        # only one sweep drains the backlog at a time
        self.repl_standby: tuple[str, int] | None = None
        self.repl_chains: list[list[str]] | None = None
        self.repl_sent: list[int] | None = None
        self.repl_lock = lockwatch.async_lock("server.repl")
        # session lease / reconnect-resume state. The stream-opening RPC
        # handler OWNS the KV pages (allocate context) and survives stream
        # death: it parks, then waits on resume_waiter for either a resume
        # handler (which hands over its fresh stream) or the lease reaper.
        self.parked = False
        self.reaped = False  # lease expired / resume impossible
        self.lease_deadline = 0.0  # monotonic; meaningful while parked
        self.cur_stream = None  # stream the session loop is serving now
        self.resume_waiter: asyncio.Event | None = None
        self.resume_stream = None  # set by the resume handler before wake
        self.detach_event: asyncio.Event | None = None  # releases the
        # resume handler whose stream the session loop currently serves
        # fencing: bumped per adopted stream so anything captured against
        # an older stream can be recognized as stale
        self.stream_epoch = 0
        # at-most-once step application: replies are recorded (keyed
        # (step, mb)) BEFORE first delivery, so a step retried after a
        # lost ack resends the recorded reply instead of re-applying KV
        self.last_step_id = -1
        self.applied_steps: dict[tuple[int, int], tuple[dict, list]] = {}
        # a stepped decode_n chain died after committing KV the client was
        # never told about: resuming would desync — force full replay
        self.kv_dirty = False
        # prefix-cache adoption is SETTLED once a step has trimmed the
        # adopted prefix to the client's declared skip. Until then the
        # session must step solo (the settle mutates the table); after,
        # it batches like any other session instead of being carved out
        # of merged dispatches for the rest of its life
        self.adoption_settled = False
        # speculation-mode gauge for the kind-aware group_hint: True
        # while the session could contribute a tree-verify row. A gather
        # that can only admit tree rows is bounded by the sessions
        # currently speculating — without this, tree groups sleep the
        # full window whenever any non-speculating session is open.
        # OPTIMISTIC start (True): until a session reveals its kind with
        # a plain decode step it might speculate, and the first tree
        # gathers must wait for it or concurrent spec sessions that start
        # milliseconds apart never pair up
        self.speculating = True


class _PeerPool:
    """Cached outbound connections for server-to-server push.

    Locking is per-peer so one unreachable peer's connect timeout cannot
    stall pushes to healthy peers."""

    def __init__(self):
        self._conns: dict[tuple[str, int], Connection] = {}
        self._locks: dict[tuple[str, int], asyncio.Lock] = {}
        self._limiters: dict[tuple[str, int], FlowLimiter] = {}

    def limiter(self, host: str, port: int) -> FlowLimiter:
        """Per-peer adaptive push limiter (reference handler.py:255-370
        AdaptivePushConcurrency role)."""
        key = (host, port)
        lim = self._limiters.get(key)
        if lim is None:
            lim = self._limiters[key] = FlowLimiter(name=f"{host}:{port}")
        return lim

    async def get(self, host: str, port: int) -> Connection:
        key = (host, port)
        lock = self._locks.get(key)
        if lock is None:
            lock = self._locks[key] = lockwatch.async_lock("server.peer_pool")
        async with lock:
            conn = self._conns.get(key)
            if conn is None or conn.is_closing():
                conn = await connect(host, port)
                self._conns[key] = conn
            return conn

    async def close(self):
        for c in self._conns.values():
            await c.close()
        self._conns.clear()


class BlockServer(PromotionLoopMixin):
    def __init__(
        self,
        *,
        model_uid: str,
        start: int,
        end: int,
        params=None,
        spec: ModelSpec | None = None,
        model_dir: str | None = None,
        registry=None,
        host: str = "127.0.0.1",
        port: int = 0,
        public_host: str | None = None,
        num_pages: int = 256,
        page_size: int = 16,
        compute_dtype=jnp.bfloat16,
        max_chunk_tokens: int = 512,
        max_batch: int = 8,  # continuous batching: coalesce up to this
        # many compatible single-token decode steps (across sessions) into
        # one span dispatch; 1 disables the batcher. The gather window is
        # BBTPU_BATCH_WINDOW_MS (default 0: only already-queued steps
        # coalesce, so idle-server latency is untouched)
        announce_period: float = 5.0,
        alloc_timeout: float = 60.0,
        throughput: float = 1.0,
        adapter_dirs: list[str] | None = None,  # merged into base at load
        adapters: dict[str, str] | None = None,  # name -> dir, per-request
        tp: int = 1,
        sp: int = 1,  # >1: long prefills spread over this many local
        # chips via ring attention (parallel/sp_serving.py); decode stays
        # single-chip paged
        kv_quant: str | None = None,  # "int4" -> quantized KV arena
        experts: tuple[int, int] | None = None,  # (first, count) of the
        # router's experts this server holds (--experts); None = all
        weight_quant: str | None = None,  # "int8"/"int4" -> quantized weights
        oversubscribe: float = 1.0,  # admit > capacity; park idle sessions
        idle_park_s: float = 5.0,  # a session this idle may be parked
        attn_sparsity: float = 1.0,  # <1: top-k sparse decode attention
        client_params: dict | None = None,  # embed/norm/lm_head for the
        # server-side multi-step decode loop (decode_n); lazy-loaded from
        # model_dir when omitted
        decode_n_max: int = 256,  # largest decode_n accepted per RPC (a
        # bigger n eagerly commits n KV slots per row before compute, so an
        # unbounded request could exhaust the arena in one call)
        rebalance_period: float = 0.0,  # >0: periodically check whether
        # moving this span to the swarm's least-served window beats the
        # hysteresis margin, and MOVE if so (reference server.py:479-542
        # module_container restart loop); 0 disables
        drain_timeout: float = 30.0,  # how long a rebalance waits for live
        # sessions to finish before swapping the span under them (their
        # next step then gets the typed session_lost and replays elsewhere)
        offload_layers: int = 0,  # stream the span's last N layers' weights
        # from host per step (FlexGen weight-offload: serve spans larger
        # than HBM; combine with --weight-quant to shrink the streamed
        # bytes 2-4x)
        prefix_cache: bool | None = None,  # cross-session shared-prefix KV
        # cache: pool committed prompt pages under content hashes, adopt
        # them into matching sessions, prefill only the suffix
        # (None -> BBTPU_PREFIX_CACHE env; forces the Python paged table)
        prefill_chunk: int | None = None,  # stall-free scheduling
        # (Sarathi-Serve): split each prefill into chunks of at most this
        # many tokens, each its own compute-queue task, so concurrent
        # sessions' decode steps run between chunks instead of stalling
        # behind the whole prompt (0 = monolithic prefill; None ->
        # BBTPU_PREFILL_CHUNK env)
        admit: bool | None = None,  # overload admission control: past
        # admit_high_ms of measured queue delay, shed NEW sessions/prefills
        # with a retriable overloaded(retry_after_ms) instead of letting
        # queue-time deadline aborts kill them; established sessions'
        # decode steps are always admitted (None -> BBTPU_ADMIT env)
        admit_high_ms: float | None = None,  # admission high watermark in
        # ms of live queue delay (None -> BBTPU_ADMIT_HIGH_MS env)
        load_advert_s: float | None = None,  # refresh/announce the
        # ServerInfo.load snapshot this often; effective cadence is
        # min(announce_period, load_advert_s) so load telemetry can be
        # fresher than liveness announces (None -> BBTPU_LOAD_ADVERT_S
        # env; 0 = every announce_period)
        session_lease_s: float | None = None,  # session lifecycle
        # hardening: a session whose stream died is PARKED (pages become
        # evictable cached pool entries) and resumable for this long
        # before final reclaim; also the silence bound past which the
        # reaper fences a live-but-wedged client (None ->
        # BBTPU_SESSION_LEASE_S env; 0 disables)
        keepalive_s: float | None = None,  # wire keepalive interval for
        # accepted connections so half-open clients (partition, no
        # FIN/RST) are detected instead of hanging recv() forever
        # (None -> BBTPU_KEEPALIVE_S env; 0 disables)
        mixed_batch: bool | None = None,  # fuse a prefill chunk and
        # compatible queued decode steps into ONE ragged span dispatch
        # (Sarathi-Serve fused iterations) instead of a dispatch each;
        # falls back to separate dispatches on configs the ragged step
        # doesn't cover (weight offload, hetero spans, top-k attention —
        # TP meshes run the fused path). None -> BBTPU_MIXED_BATCH env;
        # off = current decode-only batching, byte-for-byte
        spec_batch: bool | None = None,  # batched tree-speculative
        # verification: pad/stack concurrent sessions' compatible
        # tree-verify steps into ONE ragged span dispatch
        # (executor.ragged_group — with mixed_batch also on, tree rows
        # fuse with decode rows and a chunk) instead of one solo dispatch
        # per speculating session; falls back to solo tree steps on
        # configs the ragged tree step doesn't cover. None ->
        # BBTPU_SPEC_BATCH env; off = solo tree dispatches, byte-for-byte
        standby: bool = False,  # start as a WARM STANDBY for this span:
        # announce JOINING (holds weights + accepts kv_put replication but
        # takes no routed traffic), watch the span's serving replicas, and
        # self-promote to ONLINE on sustained overload or server loss —
        # then drain back to standby when the span cools (the elastic
        # self-healing control loop)
        promote_high_ms: float | None = None,  # promotion high watermark
        # in ms of the span's best serving server's predicted queue delay
        # (None -> BBTPU_PROMOTE_HIGH_MS env)
        promote_low_ms: float | None = None,  # demotion low watermark
        # (None -> BBTPU_PROMOTE_LOW_MS env)
        promote_sustain_s: float | None = None,  # hot/cool dwell before
        # acting (None -> BBTPU_PROMOTE_SUSTAIN_S env)
        promote_jitter_s: float | None = None,  # storm-guard jitter bound
        # (None -> BBTPU_PROMOTE_JITTER_S env)
        integrity: bool | None = None,  # stamp an out_digest (blake2b over
        # the exact serialized span-output bytes) into every step reply and
        # advertise it, so integrity-enabled clients get a deterministic
        # in-flight-corruption check (None -> BBTPU_INTEGRITY env)
        liar_p: float | None = None,  # TEST HOOK: per-step probability of
        # perturbing span outputs before serialization — the Byzantine
        # "liar" the client audits exist to convict (None -> BBTPU_LIAR_P
        # env; never enable in real serving)
        liar_seed: int | None = None,  # RNG seed for the liar hook
        # (None -> BBTPU_LIAR_SEED env)
        artifact_dir: str | None = None,  # swarm-shared compile-artifact
        # store (doubles as this process's JAX persistent compilation
        # cache dir): serve artifact_get, push artifacts to replication
        # standbys via artifact_put, and pre-install fetched artifacts
        # before warmup so a standby/JOINed server loads executables
        # instead of compiling them (None -> BBTPU_ARTIFACT_DIR env;
        # empty = artifact path off)
    ):
        self.model_dir = model_dir
        self.experts = experts
        if weight_quant is None:
            weight_quant = env.get("BBTPU_WEIGHT_QUANT")
        host_layers: list = []
        if params is None and offload_layers > 0:
            from bloombee_tpu.models.checkpoint import load_span_params_split

            resident = max(0, (end - start) - offload_layers)
            params, host_layers, spec = load_span_params_split(
                model_dir, start, end, resident, dtype=compute_dtype,
                adapter_dirs=adapter_dirs, weight_quant=(
                    None if not weight_quant or weight_quant == "none"
                    else weight_quant
                ),
            )
            weight_quant = "none"  # already applied per layer
        elif params is None:
            from bloombee_tpu.models.checkpoint import load_span_params

            params, spec = load_span_params(
                model_dir, start, end, dtype=compute_dtype,
                adapter_dirs=adapter_dirs, experts=experts,
            )
        elif offload_layers > 0:
            # pre-built params + offload: split the stacked span, move the
            # tail layers to host numpy (the executor streams them back per
            # step with one-ahead prefetch) and free their device copies
            import jax as _jax

            assert spec is not None, "pre-built params need a spec"
            n_span = end - start
            if not 0 < offload_layers <= n_span:
                raise ValueError(
                    f"offload_layers={offload_layers} outside span of "
                    f"{n_span} layers"
                )
            resident = n_span - offload_layers
            host_layers = [
                _jax.tree.map(lambda x, i=i: np.asarray(x[i]), params)
                for i in range(resident, n_span)
            ]
            params = (
                _jax.tree.map(lambda x: x[:resident], params)
                if resident else None
            )
            if weight_quant and weight_quant != "none":
                # quantize BOTH halves here (the later quant block only
                # sees the resident stack — dense host layers would
                # silently keep the full streamed bytes, defeating the
                # point of combining offload with --weight-quant)
                from bloombee_tpu.models import wquant

                bits = {"int8": 8, "int4": 4}[weight_quant]
                if params is not None:
                    params = wquant.quantize_span_params(params, bits)
                host_layers = [
                    _jax.device_get(wquant.quantize_layer_params(h, bits))
                    for h in host_layers
                ]
                weight_quant = "none"  # already applied
        assert spec is not None
        if weight_quant and weight_quant != "none":
            # weight-only quantization (reference compression.py's weight
            # half): decode reads every projection once per token, so int8
            # (int4) storage halves (quarters) HBM bytes per step. Composes
            # with TP (parallel/serving.py place_span_params shards the
            # quantized leaves) and with heterogeneous spans (per-layer
            # dicts quantize via a 1-stack each — attention geometry may
            # vary per layer but each layer quantizes independently anyway)
            from bloombee_tpu.models import wquant

            bits = {"int8": 8, "int4": 4}[weight_quant]
            before = wquant.params_nbytes(params)
            if spec.heterogeneous:
                params = tuple(
                    wquant.quantize_layer_params(p, bits) for p in params
                )
            else:
                params = wquant.quantize_span_params(params, bits)
            logger.info(
                "quantized span weights to %s: %.1f -> %.1f MiB",
                weight_quant, before / 2**20,
                wquant.params_nbytes(params) / 2**20,
            )
        # per-request switchable adapters (reference utils/peft.py
        # `using_adapter` + server --adapters): factors stay UNMERGED so the
        # same base weights serve base and every adapter; a session picks one
        # via open metadata
        self.adapter_factors: dict[str, dict] = {}
        if adapters:
            from bloombee_tpu.models.checkpoint import load_adapter_factors

            for name, adir in adapters.items():
                self.adapter_factors[name] = load_adapter_factors(
                    adir, start, end, dtype=compute_dtype
                )
        self.model_uid = model_uid
        self.start_block = start
        self.end_block = end
        self.spec = spec
        self.server_id = f"srv-{uuid.uuid4().hex[:8]}"
        self.registry = registry
        self.announce_period = announce_period
        self.alloc_timeout = alloc_timeout
        self.public_host = public_host or host
        self.throughput = throughput
        self.inference_rps: float | None = None
        self.compute_dtype = compute_dtype

        self.manager = CacheManager(
            num_layers=end - start,
            num_pages=num_pages,
            page_size=page_size,
            n_kv_heads=spec.num_key_value_heads,
            head_dim=spec.head_dim,
            dtype=compute_dtype,
            quant=kv_quant,
            hetero_spec=spec if spec.heterogeneous else None,
            start_block=start,
            oversubscribe=oversubscribe,
            prefix_cache=prefix_cache,
            ssm=spec.recurrent,
            state_slots=state_slots_for(
                spec, num_pages, page_size, max_batch
            ),
            payload=spec.mla.page_payload if spec.mla is not None else None,
            arena_layers=spec.arena_layers(start, end),
            sharded=tp > 1,
        )
        self.idle_park_s = idle_park_s
        if oversubscribe > 1.0:
            # serve more sessions than HBM fits: page pressure evicts idle
            # sessions' KV to host (the FlexGen offload story at the
            # session granularity); their next step unparks on demand
            self.manager.reclaimer = self._reclaim_idle
        mesh = None
        if tp > 1:
            # intra-server tensor parallelism over the local chips (ICI):
            # GSPMD-partitioned span step, KV heads + weight shards per chip
            # (reference flexgen_tensor_parallel.py:540-828 role)
            from bloombee_tpu.parallel.serving import make_serving_mesh

            mesh = make_serving_mesh(tp)
        self.tp = tp
        sp_mesh = None
        if sp > 1:
            from bloombee_tpu.parallel.sp_serving import make_sp_mesh

            sp_mesh = make_sp_mesh(sp)
        self.sp = sp
        self.executor = SpanExecutor(
            params, spec, self.manager,
            max_chunk_tokens=max_chunk_tokens,
            compute_dtype=compute_dtype,
            start_block=start,
            mesh=mesh,
            adapters=self.adapter_factors,
            host_layers=host_layers,
            attn_sparsity=attn_sparsity,
            sp_mesh=sp_mesh,
        )
        self.wire_dtype = name_for_dtype(self.executor.transfer_dtype)
        if (spec.heterogeneous or host_layers or spec.recurrent is not None
                or spec.mla is not None or spec.moe_router == "sigmoid"):
            # hetero / weight-offloaded spans: no dense training stack; a
            # state-space mixer, latent attention and the sigmoid router
            # (afmoe: positions in some layers only, a dense layer before
            # sparse ones) have no training-mode forward here either
            self.training = None
        else:
            from bloombee_tpu.runtime.training import TrainingExecutor

            # the executor's params, not the loader's: under --tp those are
            # the mesh-placed shards, and a second reference to the
            # unsharded stack would pin a full copy on the first device
            self.training = TrainingExecutor(
                self.executor.params, spec, windows=self.executor.windows,
                compute_dtype=compute_dtype, adapters=self.adapter_factors,
            )
        self.decode_n_max = int(decode_n_max)
        # per-token budget for a chained decode_n round trip through the
        # downstream spans (generous: the first chain step may hit a cold
        # XLA compile on a middle/tail span)
        self.chain_step_timeout = 120.0
        self.max_batch = max(1, int(max_batch))
        # ragged-path declines, per reason (BB006: rpc_info + health
        # --probe): every requested-but-unsupported fallback to monolithic
        # dispatch is operator-visible instead of a silent logger.info
        self.ragged_declines: dict[str, int] = {}
        if mixed_batch is None:
            mixed_batch = bool(env.get("BBTPU_MIXED_BATCH"))
        if mixed_batch:
            reason = self.executor.ragged_unsupported(has_tree=False)
            if reason is not None:
                logger.info(
                    "mixed-batch dispatch disabled: %s", reason
                )
                self.ragged_declines[reason] = (
                    self.ragged_declines.get(reason, 0) + 1
                )
                mixed_batch = False
        self.mixed_batch = bool(mixed_batch)
        if spec_batch is None:
            spec_batch = bool(env.get("BBTPU_SPEC_BATCH"))
        if spec_batch:
            reason = self.executor.ragged_unsupported(has_tree=True)
            if reason is not None:
                logger.info(
                    "batched tree verification disabled: %s", reason
                )
                self.ragged_declines[reason] = (
                    self.ragged_declines.get(reason, 0) + 1
                )
                spec_batch = False
        self.spec_batch = bool(spec_batch)
        if self.mixed_batch or self.spec_batch:
            # ONE kind-aware gather predicate covers every batchable row
            # kind (decode rows, the prefill chunk, tree-verify rows);
            # with --mixed-batch the chunk rides one extra group slot so
            # fusing never costs the batcher any of its max_batch seats
            self.compute = ComputeQueue(
                max_group=self.max_batch + (1 if self.mixed_batch else 0),
                compat=self._ragged_compat,
                group_hint=self._batch_group_hint,
            )
        else:
            self.compute = ComputeQueue(
                max_group=self.max_batch, group_hint=self._batch_group_hint
            )
        # prefills answered to a client with a tensor (committed steps of
        # more than one position: a prompt, a history replayed after a
        # failover; not tree, speculative or ragged-replay steps): the bytes
        # sent against the bytes of every row of their outputs (`reply_tail`)
        self.prefill_reply = {"n": 0, "reply_bytes": 0, "full_bytes": 0}
        # prefill steps that came in more than one part (`_StepRows`), their
        # parts, and how long their chunk loops stood waiting for a part
        # that had not arrived: near 0 says the device sets the pace, large
        # says the upload still does. rpc_info["prefill_parts"]
        self.prefill_parts = {"steps": 0, "parts": 0, "wait_us": 0}
        # every session's turns, leg by leg (wire/turn.py): rpc_info["turn"]
        self.turn_account = turn.TurnAccount()
        self.peers = _PeerPool()
        # server-side multi-step decode (decode_n): needs the checkpoint's
        # embed/norm/lm_head trio; lazy-loaded from model_dir on first use
        self._client_params = client_params
        self._client_params_unavailable = False
        self._client_params_lock: asyncio.Lock | None = None
        # mid-chain draft-tree pruning (reference speculative_pruner/): the
        # MidLMHead weight lazy-loads from the checkpoint's lm_head
        self._pruner_manager = None
        self._pruner_unavailable = False
        self._pruner_lock: asyncio.Lock | None = None
        # measured RTTs to servers of the block after this span, announced
        # in ServerInfo.next_pings for routing (reference server.py:1000-1007
        # ModuleAnnouncerThread next-block pings)
        from bloombee_tpu.swarm.ping import PingAggregator

        self.next_pings = PingAggregator()
        self._sessions: dict[str, _Session] = {}
        self._pending_pushes: dict[str, list] = {}
        self.pending_push_ttl = 30.0
        self._announce_task: asyncio.Task | None = None
        self._supervisor_task: asyncio.Task | None = None
        self._warmup_task: asyncio.Task | None = None
        self._throughput_task: asyncio.Task | None = None
        self.rebalance_period = float(rebalance_period)
        self.drain_timeout = float(drain_timeout)
        self._rebalancing = False
        # graceful shutdown: announces DRAINING (routing stops sending NEW
        # sessions), keeps serving in-flight sessions up to drain_timeout
        self._draining = False
        # chaos harness: crash() flips this; post-crash nothing may take a
        # graceful path (no park, no announce, no revoke)
        self._crashed = False
        # elastic self-healing: standby/promotion control-loop state. A
        # standby announces JOINING (invisible to routing, visible to
        # kv_put replication) and refuses session opens; _promotion_loop
        # flips _standby/_promoted on sustained span overload or loss.
        self._standby = bool(standby)
        self._promoted = False
        self.promote_high_ms = (
            float(env.get("BBTPU_PROMOTE_HIGH_MS"))
            if promote_high_ms is None else float(promote_high_ms)
        )
        self.promote_low_ms = (
            float(env.get("BBTPU_PROMOTE_LOW_MS"))
            if promote_low_ms is None else float(promote_low_ms)
        )
        self.promote_sustain_s = (
            float(env.get("BBTPU_PROMOTE_SUSTAIN_S"))
            if promote_sustain_s is None else float(promote_sustain_s)
        )
        self.promote_jitter_s = (
            float(env.get("BBTPU_PROMOTE_JITTER_S"))
            if promote_jitter_s is None else float(promote_jitter_s)
        )
        self._promotion_task: asyncio.Task | None = None
        # seeded per server: the storm-guard jitter must differ across
        # standbys even when they start in the same millisecond
        self._promote_rng = random.Random(self.server_id)
        # control-loop decision counters (rpc_info + health --probe):
        # every promote/demote/rebalance outcome is operator-visible
        self.promotions = 0
        self.demotions = 0
        self.promotions_yielded = 0
        self.demotions_aborted = 0
        self.rebalances_moved = 0
        self.rebalances_failed = 0
        self.rebalance_skipped_hysteresis = 0
        # work dropped because the client's deadline budget (meta
        # "deadline_s") expired before/while we would compute it; surfaced
        # via rpc_info for operators and the chaos tests
        self.deadlines_expired = 0
        # continuous-batching counters (rpc_info): member steps that shared
        # a merged dispatch, merged dispatches issued, and batcher-routed
        # steps that ran alone (width-1 pops, parked/stale-epoch members,
        # row-by-row replays after a failed merged dispatch)
        self.batched_steps = 0
        self.batch_dispatches = 0
        self.batch_solo_steps = 0
        # stall-free scheduling (chunked prefill): the per-server chunk
        # token budget (None -> BBTPU_PREFILL_CHUNK env, 0 = monolithic),
        # chunk/token counters, decode steps that dispatched while some
        # session's prefill was mid-stream (the interleaving this feature
        # exists for), and the live count of mid-stream chunked prefills
        self.prefill_chunk = prefill_chunk
        self.prefill_chunks = 0
        self.prefill_chunk_tokens = 0
        self.decode_steps_interleaved = 0
        self._chunking_sessions = 0
        # mixed-batch observability: fused ragged dispatches issued, the
        # tokens they carried, and the all-paths dispatch/token totals
        # behind dispatches_per_token (every inference dispatch counts —
        # solo steps, merged decodes, prefill chunks, mixed groups — so
        # the ratio falls exactly when fusing removes dispatches)
        self.mixed_dispatches = 0
        self.mixed_tokens = 0
        self.step_dispatches = 0
        self.step_tokens = 0
        # universal ragged dispatch observability: fused groups run
        # through the unified runner, and how many of them mixed row
        # KINDS (decode/chunk/tree) in one device step — the capability
        # the three legacy paths could never express
        self.ragged_group_dispatches = 0
        self.ragged_cross_kind_dispatches = 0
        # once the warm-up's fence is down a chunk is fused with decode
        # rows only into a program that exists (`_compute_ragged_group`);
        # the packs that went as their parts instead
        self._warm_fenced = False
        self.ragged_cold_splits = 0
        # speculative-decode observability (previously client-side only):
        # tree-verify steps served (solo or grouped), the session rows
        # they carried, drafted vs accepted speculative tokens (from the
        # accept metas riding each next step), and the batched-verification
        # group counters behind mean_tree_batch_width
        self.tree_steps = 0
        self.tree_rows = 0
        self.spec_tokens_drafted = 0
        self.spec_tokens_accepted = 0
        self.tree_group_dispatches = 0
        self.tree_group_members = 0
        # per-session acceptance outlives the session: closed sessions'
        # drafted/accepted tallies stay probeable (bounded ring) so an
        # operator can still see which finished streams speculated well
        self._closed_session_spec: "OrderedDict[str, dict]" = OrderedDict()
        # overload protection: the admission controller sheds NEW work
        # past the high watermark (established streams are never routed
        # through it); the load advert republishes live queue gauges
        from bloombee_tpu.server.admission import AdmissionController

        if admit is None:
            admit = bool(env.get("BBTPU_ADMIT"))
        self.admission = (
            AdmissionController(high_ms=admit_high_ms) if admit else None
        )
        self.load_advert_s = (
            float(env.get("BBTPU_LOAD_ADVERT_S"))
            if load_advert_s is None else float(load_advert_s)
        )
        # session-KV replication (fast failover): sealed pages this primary
        # shipped to standbys, and tokens recovering clients replayed into
        # us; the semaphore bounds concurrent replication sweeps so standby
        # traffic can never crowd out live inference
        self.repl_pages_sent = 0
        self.failover_replayed_tokens = 0
        self._repl_sem = asyncio.Semaphore(
            max(1, env.get("BBTPU_REPL_INFLIGHT"))
        )
        # session lifecycle hardening (leases + reconnect-resume): parked
        # sessions reclaimed by the lease reaper, parked sessions
        # re-attached by a reconnecting client, retried steps answered
        # from the recorded reply instead of re-applied, and push items
        # that teardown would otherwise silently discard
        self.session_lease_s = (
            float(env.get("BBTPU_SESSION_LEASE_S"))
            if session_lease_s is None else float(session_lease_s)
        )
        self.sessions_reaped = 0
        self.sessions_resumed = 0
        self.steps_deduped = 0
        self.pushes_dropped = 0
        self._reaper_task: asyncio.Task | None = None
        # integrity layer (server half): digest stamping + the liar test
        # hook. seq_hash_extend_failures surfaces the previously
        # debug-swallowed prefix-hash-chain extension errors (each one
        # silently degrades shared-prefix reuse for later sessions)
        self.integrity = (
            bool(env.get("BBTPU_INTEGRITY"))
            if integrity is None else bool(integrity)
        )
        self.liar_p = (
            float(env.get("BBTPU_LIAR_P")) if liar_p is None
            else float(liar_p)
        )
        self._liar_rng = random.Random(
            env.get("BBTPU_LIAR_SEED") if liar_seed is None else liar_seed
        )
        if self.liar_p > 0:
            logger.warning(
                "BYZANTINE LIAR TEST HOOK ENABLED (liar_p=%.3g): this "
                "server will return corrupted span outputs", self.liar_p,
            )
        self.out_digests_sent = 0
        self.audit_forwards = 0
        self.liar_steps = 0
        self.seq_hash_extend_failures = 0
        # zero-cold-start recovery: the swarm-shared compile-artifact
        # store (server/artifacts.py). Enabling it points JAX's
        # persistent compilation cache at the store dir, so this server's
        # own warmup compiles become servable artifacts with no extra
        # step. warmup_failures counts the per-bucket warmup errors the
        # warmup loop swallows (each one is a bucket that will compile on
        # its first real request — previously invisible behind a bare
        # logger.warning); the artifact_* counters make every install/
        # decline/fallback on the artifact path operator-visible
        if artifact_dir is None:
            artifact_dir = env.get("BBTPU_ARTIFACT_DIR")
        self.artifact_store: artifacts.ArtifactStore | None = None
        if artifact_dir:
            # the store serves whatever directory the cache really uses
            # (JAX_COMPILATION_CACHE_DIR wins over artifact_dir)
            cache_dir = artifacts.enable_persistent_cache(artifact_dir)
            if cache_dir:
                self.artifact_store = artifacts.ArtifactStore(cache_dir)
        self._artifacts_preinstalled = False
        self._artifact_pushed_standbys: set[tuple[str, int]] = set()
        self.warmup_failures = 0
        self.artifact_fallback_compiles = 0
        self.artifact_gets_served = 0
        self.artifact_puts_installed = 0
        self.artifact_puts_declined = 0
        self.artifact_blobs_fetched = 0
        self.artifact_fetch_retries = 0
        self._kv_quant = kv_quant
        self._num_pages = num_pages
        self._adapter_dirs = adapter_dirs
        self._weight_quant = weight_quant
        self.rpc = RpcServer(
            unary_handlers={
                "rpc_info": self._rpc_info,
                "rpc_forward": self._rpc_forward,
                "rpc_backward": self._rpc_backward,
                "kv_put": self._kv_put,
                "artifact_get": self._artifact_get,
                "artifact_put": self._artifact_put,
            },
            stream_handlers={"rpc_inference": self._rpc_inference},
            push_handlers={"rpc_push": self._rpc_push},
            host=host,
            port=port,
            keepalive_s=keepalive_s,
        )

    # ---------------------------------------------------------------- lifecycle
    @property
    def port(self) -> int:
        return self.rpc.port

    async def start(self) -> None:
        jitwatch.install()  # no-op unless BBTPU_JITWATCH=1
        await self.rpc.start()
        self.compute.start()
        if self.session_lease_s > 0:
            self._reaper_task = asyncio.create_task(self._lease_reaper_loop())
        if self.registry is not None:
            await self._announce(self._advert_state())
            self._announce_task = asyncio.create_task(self._announce_loop())
            if self._standby:
                self._promotion_task = asyncio.create_task(
                    self._promotion_loop()
                )
            # the announce loop IS the liveness signal: if it dies, the
            # registry record expires and the swarm silently loses this
            # server — supervise and restart it (reference restarts whole
            # unhealthy containers, server.py:524-541); the supervisor
            # also drives periodic rebalancing when enabled
            self._supervisor_task = asyncio.create_task(
                self._supervisor_loop()
            )
        if self.rebalance_period > 0 and self.rebalance_unsupported():
            # fail-loud: the operator asked for auto-balancing but this
            # configuration can never move — silence would hide the loss
            # of the whole feature
            logger.warning(
                "rebalance_period=%.0fs requested but rebalancing is "
                "disabled for this server: %s",
                self.rebalance_period, self.rebalance_unsupported(),
            )
        logger.info(
            "server %s serving %s[%d:%d] on port %d; kv arena %s",
            self.server_id, self.model_uid, self.start_block, self.end_block,
            self.port, "folded" if self.manager.folded else "unfolded",
        )
        from bloombee_tpu import native
        from bloombee_tpu.utils.memory import device_report

        # said once by the process that holds the device: what it runs on
        # (off the loop: the first look at a native component may build it)
        logger.info(
            "device %s; native components %s",
            device_report(), await asyncio.to_thread(native.loaded),
        )

    async def drain(self, timeout: float | None = None) -> None:
        """Graceful shutdown: announce DRAINING so routing stops starting
        NEW sessions here, keep serving the in-flight ones until they
        close (bounded by `timeout`, default drain_timeout), then stop.
        Sessions that outlive the drain replay elsewhere via the client's
        ordinary dead-server recovery path."""

        if self._draining:
            return
        self._draining = True
        deadline = clock.monotonic() + (
            self.drain_timeout if timeout is None else float(timeout)
        )
        logger.info(
            "draining %s: %d in-flight session(s), up to %.0fs",
            self.server_id, len(self._sessions),
            deadline - clock.monotonic(),
        )
        if self.registry is not None:
            try:
                # immediate announce — the periodic loop may be most of an
                # announce_period away, and every new session routed here
                # in that window dies with the server
                await self._announce(ServerState.DRAINING)
            except Exception as e:
                logger.warning("DRAINING announce failed: %s", e)
        # flush pending standby replication FIRST so a standby holds every
        # sealed page a recovering client will probe for — a drained
        # server's sessions fail over with at most the unsealed tail to
        # replay instead of their whole history
        flush = [
            asyncio.create_task(self._replicate_session(s))
            for s in list(self._sessions.values())
            if s.repl_standby is not None
        ]
        if flush:
            try:
                await asyncio.wait_for(
                    asyncio.gather(*flush, return_exceptions=True),
                    timeout=max(1.0, deadline - clock.monotonic()),
                )
            except asyncio.TimeoutError:
                logger.warning(
                    "replication flush outlived the drain window; standbys "
                    "hold a partial backlog"
                )
        # parked sessions have no live client to finish: force-expire their
        # leases NOW so the drain waits only on streams that can still make
        # progress (a wedged session must never eat the whole drain window)
        reaped = 0
        for s in list(self._sessions.values()):
            if s.parked and not s.reaped:
                s.reaped = True
                if s.resume_waiter is not None:
                    s.resume_waiter.set()
                reaped += 1
        if reaped:
            logger.info(
                "drain force-expired %d parked session lease(s)", reaped
            )
        while self._sessions and clock.monotonic() < deadline:
            # sessions parking DURING the drain are refused (the park path
            # checks _draining), so only live streams remain to wait on
            await clock.async_sleep(0.1)
        if self._sessions:
            logger.warning(
                "%d session(s) outlived the drain; they will replay "
                "elsewhere", len(self._sessions),
            )
        await self.stop()

    async def stop(self) -> None:
        for task in (self._supervisor_task, self._warmup_task,
                     self._throughput_task, self._reaper_task,
                     self._promotion_task):
            if task is not None:
                task.cancel()
        if self._announce_task is not None:
            self._announce_task.cancel()
        if self.registry is not None:
            try:
                await self.registry.revoke_blocks(
                    self.model_uid, self.server_id,
                    range(self.start_block, self.end_block),
                    # the tombstone must outlive any replica's stale copy of
                    # our announce (expiration = announce_period * 2.5)
                    expiration=max(60.0, self.announce_period * 2.5 + 10.0),
                )
            except Exception:
                # best-effort: a dead registry at shutdown must not block
                # drain; the announce record expires on its own anyway
                pass
        await self.compute.stop()
        await self.peers.close()
        await self.rpc.stop()

    def crash(self) -> None:
        """Process-crash emulation for the chaos harness: the server dies
        NOW, mid-whatever-it-was-doing. Unlike every graceful path above
        there is no DRAINING announce, no replication flush, no session
        park, no registry revoke (the announce record must expire on its
        own — that silence is what standby promotion watches for), and no
        orderly stream close: every connection's transport is aborted so
        peers see exactly what a kill -9 produces. Sessions and their KV
        are simply lost; recovery happens entirely elsewhere (standby
        promotion, client reroute-replay)."""
        if self._crashed:
            return
        self._crashed = True
        self._draining = True  # refuse any racing open/park/announce
        ledger.fault("server.crash")
        logger.warning("CRASH injected: server %s dying hard", self.server_id)
        for task in (self._supervisor_task, self._warmup_task,
                     self._throughput_task, self._reaper_task,
                     self._promotion_task, self._announce_task):
            if task is not None:
                task.cancel()
        # sessions die unresolved: wake parked resume-waiters so their
        # handler tasks unwind (they observe _crashed and abort), then
        # forget everything — no parking, no lease bookkeeping
        for s in list(self._sessions.values()):
            s.reaped = True
            if s.resume_waiter is not None:
                s.resume_waiter.set()
        self._sessions.clear()
        self.compute.kill()
        self.rpc.abort()

    async def warmup(
        self, batch_sizes=(1,), prefill_tokens: int = 128
    ) -> None:
        """Pre-compile the hot (batch, tokens, pages) buckets so the first
        real request skips multi-second XLA compiles (the role of the
        reference's CUDA-graph warmup + startup throughput measurement,
        throughput.py:244-345). Runs at training priority so any real
        inference outranks it.

        jitwatch phase contract: everything compiled in here is warmup;
        the fence drops when the LAST bucket is in, and any dispatch-
        attributed compile after that is a steady-state recompile the
        --require gate fails on. Re-entrant warmups (elastic rebalance,
        span moves) re-open the warmup phase the same way.

        With an artifact store configured, warmup first pre-installs the
        span's compile artifacts from covering peers (JOIN-time fetch);
        when that succeeds, the bucket loop below LOADS executables from
        the persistent cache instead of compiling them — the
        zero-cold-start path ``jitwatch --require --preinstalled``
        gates. Any fetch failure falls back to plain local compile."""
        jitwatch.install()
        jitwatch.set_phase("warmup")
        self._warm_fenced = False
        if (
            self.artifact_store is not None
            and self.registry is not None
            and not self._artifacts_preinstalled
        ):
            await self.prefetch_artifacts()
        try:
            await self._warmup_buckets(batch_sizes, prefill_tokens)
        finally:
            jitwatch.fence()
            self._warm_fenced = True

    async def _warmup_buckets(
        self, batch_sizes, prefill_tokens: int
    ) -> None:
        for b in batch_sizes:
            try:
                async with self.manager.allocate(
                    b, prefill_tokens + 1, timeout=5.0
                ) as handle:
                    hidden = np.zeros(
                        (b, prefill_tokens, self.spec.hidden_size), np.float32
                    )
                    out = await self.compute.submit(
                        PRIORITY_TRAINING, self.executor.prefill,
                        handle, hidden, True, None, False,
                    )
                    await asyncio.to_thread(self.executor.fetch, out)
                    step = np.zeros((b, 1, self.spec.hidden_size), np.float32)
                    out = await self.compute.submit(
                        PRIORITY_TRAINING, self.executor.decode,
                        handle, step,
                    )
                logger.info("warmed buckets for batch %d", b)
            except Exception as e:
                self._note_warmup_failure()
                logger.warning("warmup(batch=%d) failed: %s", b, e)
        budget = self._chunk_budget()
        if budget > 0 and self.executor.sp_mesh is None:
            # chunked prefill hits buckets the whole-prompt warmup above
            # misses: the chunk-sized token bucket, and (for continuation
            # chunks) the next page bucket up — run a two-chunk prefill so
            # the first real chunked prompt doesn't eat the compile stall
            # this scheduler exists to remove
            try:
                spans = plan_prefill_chunks(
                    2 * budget, budget, cap=self.executor.max_chunk_tokens
                )
                tokens = spans[-1][1]
                async with self.manager.allocate(
                    1, tokens + 1, timeout=5.0
                ) as handle:
                    hidden = np.zeros(
                        (1, tokens, self.spec.hidden_size), np.float32
                    )
                    out = await self.compute.submit(
                        PRIORITY_TRAINING, self.executor.prefill_chunked,
                        handle, hidden, budget, True, None, False,
                    )
                    await asyncio.to_thread(self.executor.fetch, out)
                logger.info(
                    "warmed chunked-prefill buckets (%d chunks of <= %d "
                    "tokens)", len(spans), spans[0][1] - spans[0][0],
                )
            except Exception as e:
                self._note_warmup_failure()
                logger.warning("chunk warmup failed: %s", e)
        if self.executor.sp_mesh is not None:
            # pre-compile the sp-prefill program at its smallest bucket:
            # the whole-span shard_map compile is exactly what would
            # otherwise land on the first long prompt's latency path
            try:
                sp_tokens = int(env.get("BBTPU_SP_MIN_TOKENS"))
                async with self.manager.allocate(
                    1, sp_tokens + 1, timeout=5.0
                ) as handle:
                    hidden = np.zeros(
                        (1, sp_tokens, self.spec.hidden_size), np.float32
                    )
                    await self.compute.submit(
                        PRIORITY_TRAINING, self.executor.prefill,
                        handle, hidden, True, None, False,
                    )
                logger.info("warmed sp prefill (%d tokens)", sp_tokens)
            except Exception as e:
                self._note_warmup_failure()
                logger.warning("sp warmup failed: %s", e)
        await self._warmup_ragged(prefill_tokens)

    async def _warmup_ragged(self, prefill_tokens: int) -> None:
        """Pre-compile the UNIFIED ragged-row buckets the fused group
        paths hit: the grouped-decode packed pair, the decode+chunk
        causal ragged bucket, the default-drafter tree-verify pair, and
        (with BOTH flags on) the cross-kind decode+tree[+chunk] fusions.
        Without this the first fused step after warmup eats the compile
        stall — exactly the steady-state recompile the jitwatch gate
        forbids."""
        mixed_on = self.mixed_batch
        spec_on = self.spec_batch
        if not (mixed_on or spec_on):
            return
        if self.executor.ragged_unsupported(has_tree=spec_on) is not None:
            return
        d = self.spec.hidden_size
        budget = self._chunk_budget() if self.executor.sp_mesh is None else 0
        # default GreedyTreeDrafter branching (2, 2, 1): 11 linearized
        # nodes per tree — the t_max/rb bucket real spec-decode rounds
        # dispatch
        t_i = 11
        cap = prefill_tokens + max(budget, 0) + 24
        try:
            async with self.manager.allocate(
                1, cap, timeout=5.0
            ) as h_a, self.manager.allocate(
                1, cap, timeout=5.0
            ) as h_b, self.manager.allocate(
                1, cap, timeout=5.0
            ) as h_c:
                handles = [h_a, h_b, h_c]
                hidden = np.zeros((1, prefill_tokens, d), np.float32)
                for h in handles:
                    # buckets already warm from the solo pass; this seeds
                    # realistic context depths so pb matches steady state
                    await self.compute.submit(
                        PRIORITY_TRAINING, self.executor.prefill,
                        h, hidden, True, None, False,
                    )

                def tree_rows():
                    return (
                        np.zeros((1, t_i, d), np.float32),
                        np.tril(np.ones((1, t_i, t_i), dtype=bool)),
                        np.arange(t_i, dtype=np.int32)[None, :],
                    )

                async def warm(pairs, label):
                    # pairs: list of (handle, hidden, mask, depths); every
                    # warm dispatch writes KV speculatively, so truncate
                    # each member back afterwards
                    snaps = [
                        [int(x) for x in self.manager.context_lens(h)]
                        for h, _, _, _ in pairs
                    ]
                    await self.compute.submit(
                        PRIORITY_TRAINING, self.executor.ragged_group,
                        [h for h, _, _, _ in pairs],
                        [x for _, x, _, _ in pairs],
                        [m for _, _, m, _ in pairs],
                        [q for _, _, _, q in pairs],
                    )
                    for (h, _, _, _), snap in zip(pairs, snaps):
                        self.manager.truncate_speculative(h, snap)
                    logger.info("warmed ragged buckets: %s", label)

                step = np.zeros((1, 1, d), np.float32)
                chunk = (
                    np.zeros((1, budget, d), np.float32)
                    if budget > 0 else None
                )
                if mixed_on:
                    # pure-decode pair: the packed fast path (grouped
                    # decode), same program _dispatch_batched runs
                    await warm(
                        [(h_a, step, None, None), (h_b, step, None, None)],
                        "decode pair (packed)",
                    )
                    if chunk is not None:
                        await warm(
                            [(h_a, step, None, None),
                             (h_b, chunk, None, None)],
                            "decode + chunk",
                        )
                if spec_on:
                    ta, tb = tree_rows(), tree_rows()
                    await warm(
                        [(h_a,) + ta, (h_b,) + tb],
                        "tree pair",
                    )
                if mixed_on and spec_on:
                    # cross-kind fusions only the universal path runs
                    tb = tree_rows()
                    await warm(
                        [(h_a, step, None, None), (h_b,) + tb],
                        "decode + tree",
                    )
                    if chunk is not None:
                        tc = tree_rows()
                        await warm(
                            [(h_a, step, None, None), (h_b,) + tc,
                             (h_c, chunk, None, None)],
                            "decode + tree + chunk",
                        )
        except Exception as e:
            self._note_warmup_failure()
            logger.warning("ragged warmup failed: %s", e)

    def _note_warmup_failure(self) -> None:
        """Audit a swallowed per-bucket warmup failure: the fence still
        drops (partial warmth beats none), but the bucket that failed
        will compile on its first real request. Counted in rpc_info /
        health --probe and flagged in the jitwatch report as
        warmup_degraded so a zero-recompile green can't mask it."""
        self.warmup_failures += 1
        jitwatch.note_warmup_failure()

    async def _supervisor_loop(self) -> None:
        """Keep the server's background tasks alive and the span balanced.

        - restarts a dead announce loop (its death would silently expire
          this server from the swarm — reference server.py:524-541 restarts
          unhealthy containers; here only the loop needs restarting)
        - surfaces warmup/throughput task failures (one-shots: logged loud,
          not restarted)
        - every rebalance_period seconds, checks whether moving the span
          to the least-served window beats the hysteresis and moves
          (reference server.py:479-542)."""

        last_rebalance = clock.monotonic()
        tick = max(1.0, min(self.announce_period, 15.0))
        while True:
            await clock.async_sleep(tick)
            try:
                self._supervisor_tick()
                if (
                    self.rebalance_period > 0
                    and not self._rebalancing
                    and not self._standby
                    and self.rebalance_unsupported() is None
                    and clock.monotonic() - last_rebalance
                    >= self.rebalance_period
                ):
                    last_rebalance = clock.monotonic()
                    from bloombee_tpu.server.block_selection import (
                        rebalance_if_needed,
                    )

                    moved = await rebalance_if_needed(self)
                    if moved:
                        logger.info(
                            "rebalanced to [%d:%d)",
                            self.start_block, self.end_block,
                        )
            except asyncio.CancelledError:
                raise
            except Exception as e:
                # a transient registry flap (fetch/announce/declare error)
                # must never kill the supervisor — it is the task that
                # restarts everything else. Log and retry next tick.
                logger.warning("supervisor tick failed: %s", e)

    def _supervisor_tick(self) -> None:
        """One supervision pass: restart dead background loops, surface
        one-shot task failures."""
        if self._announce_task is not None and self._announce_task.done():
            exc = (
                None if self._announce_task.cancelled()
                else self._announce_task.exception()
            )
            logger.error(
                "announce loop died (%s); restarting — without it this "
                "server would silently expire from the registry", exc,
            )
            self._announce_task = asyncio.create_task(
                self._announce_loop()
            )
        if (
            self._promotion_task is not None
            and self._promotion_task.done()
            and (self._standby or self._promoted)
        ):
            exc = (
                None if self._promotion_task.cancelled()
                else self._promotion_task.exception()
            )
            logger.error(
                "promotion loop died (%s); restarting — without it a "
                "standby never promotes and a promoted replica never "
                "drains back", exc,
            )
            self._promotion_task = asyncio.create_task(
                self._promotion_loop()
            )
        self._report_startup_tasks()

    def _report_startup_tasks(self) -> bool:
        """Surface the one-shot start-up tasks (bucket warm-up, throughput
        measurement) once each as they finish; True when none is still
        running. One that RAISED (instead of swallowing per bucket) counts
        as a warm-up failure too, so rpc_info / health --probe show it."""
        for name in ("_warmup_task", "_throughput_task"):
            task = getattr(self, name)
            if task is not None and task.done():
                setattr(self, name, None)  # report once
                if not task.cancelled() and task.exception() is not None:
                    self._note_warmup_failure()
                    logger.error(
                        "%s failed: %s", name.strip("_"),
                        task.exception(),
                    )
        return self._warmup_task is None and self._throughput_task is None

    def rebalance_unsupported(self) -> str | None:
        """Why this server cannot move its span at runtime; None if it can."""
        if self.model_dir is None:
            return "no model_dir to load a new span from"
        if self.executor.host_layers:
            return "weight-offloaded span"
        if self.executor.mesh is not None:
            return "TP-sharded span"
        if self.spec.heterogeneous:
            return "heterogeneous span"
        if self.adapter_factors:
            return "per-request adapters are span-sliced"
        if self._weight_quant and self._weight_quant != "none":
            return "weight-quantized span"
        return None

    async def rebalance_to(self, start: int, end: int) -> None:
        """Move this server to blocks [start, end): tombstone the old span,
        drain sessions (bounded), load the new span's params, swap the
        manager/executor/training stack, and re-announce. Sessions that
        outlive the drain get the typed session_lost on their next step
        (their seq ids are unknown to the fresh manager) and replay onto
        other servers — the same client path that handles a dead server."""
        reason = self.rebalance_unsupported()
        if reason is not None:
            raise RuntimeError(f"rebalance unsupported: {reason}")
        self._rebalancing = True
        try:
            logger.info(
                "rebalancing %s [%d:%d) -> [%d:%d)",
                self.server_id, self.start_block, self.end_block, start, end,
            )
            old_range = range(self.start_block, self.end_block)
            if self.registry is not None:
                try:
                    await self.registry.revoke_blocks(
                        self.model_uid, self.server_id, old_range,
                        expiration=max(
                            60.0, self.announce_period * 2.5 + 10.0
                        ),
                    )
                except Exception as e:
                    logger.warning("revoke of old span failed: %s", e)

            deadline = clock.monotonic() + self.drain_timeout
            while self._sessions and clock.monotonic() < deadline:
                await clock.async_sleep(0.25)
            if self._sessions:
                logger.warning(
                    "%d session(s) outlived the %.0fs drain; they will "
                    "replay elsewhere", len(self._sessions),
                    self.drain_timeout,
                )
            from bloombee_tpu.models.checkpoint import load_span_params

            params, spec = await asyncio.to_thread(
                load_span_params, self.model_dir, start, end,
                self.compute_dtype, self._adapter_dirs, self.experts,
            )
            manager = CacheManager(
                num_layers=end - start,
                num_pages=self._num_pages,
                page_size=self.manager.page_size,
                n_kv_heads=spec.num_key_value_heads,
                head_dim=spec.head_dim,
                dtype=self.compute_dtype,
                quant=self._kv_quant,
                start_block=start,
                oversubscribe=self.manager.oversubscribe,
                prefix_cache=self.manager.prefix_cache,
                ssm=spec.recurrent,
                state_slots=state_slots_for(
                    spec, self._num_pages, self.manager.page_size,
                    self.max_batch,
                ),
                payload=(
                    spec.mla.page_payload if spec.mla is not None else None
                ),
                arena_layers=spec.arena_layers(start, end),
                sharded=self.tp > 1,
            )
            if self.manager.reclaimer is not None:
                manager.reclaimer = self._reclaim_idle
            executor = SpanExecutor(
                params, spec, manager,
                max_chunk_tokens=self.executor.max_chunk_tokens,
                compute_dtype=self.compute_dtype,
                start_block=start,
                attn_sparsity=self.executor.attn_sparsity,
            )
            from bloombee_tpu.runtime.training import TrainingExecutor

            training = None if (
                spec.recurrent is not None or spec.mla is not None
                or spec.moe_router == "sigmoid"
            ) else TrainingExecutor(
                executor.params, spec, windows=executor.windows,
                compute_dtype=self.compute_dtype,
            )
            # swap atomically from the event loop's view; any step already
            # queued against the old stack fails its epoch check (the new
            # manager knows none of the old seq ids) and replies
            # session_lost
            self.manager = manager
            self.executor = executor
            self.training = training
            self.start_block = start
            self.end_block = end
            self.spec = spec
            if self.registry is not None:
                await self._announce(ServerState.ONLINE)
                ledger.recovery("server.rebalance_reannounce")
        except Exception:
            # mid-move crash: whatever span is actually loaded right now
            # (the OLD one unless the swap already landed — the swap is
            # atomic from the event loop's view) must get back into the
            # registry IMMEDIATELY, not an announce period from now: the
            # revoke above tombstoned it, so until a re-announce the swarm
            # believes this server serves nothing
            if self.registry is not None:
                try:
                    await self._announce(self._advert_state())
                except Exception as e:
                    logger.warning(
                        "re-announce after failed rebalance ALSO failed "
                        "(%s); the periodic announce loop will retry", e,
                    )
            raise
        finally:
            self._rebalancing = False

    def load_snapshot(self) -> dict:
        """Live load gauges republished in every advert (ServerInfo.load)
        and consumed by the client router's predicted-queue-delay term.
        Wall-clock `ts` lets readers staleness-discount the whole dict."""

        waits = self.compute.wait_stats_ms()
        window_s = (
            self.admission.window_s if self.admission is not None else 5.0
        )
        delay_ms = self.compute.current_delay_ms(window_s)
        table = getattr(self.manager, "table", None)
        pages_free = getattr(table, "free_pages", None)
        return {
            "ts": clock.now(),
            "delay_ms": round(delay_ms, 3),
            "queue_depth": self.compute.depth(),
            "wait_ms": {"p50": waits["p50"], "p95": waits["p95"]},
            "prefill_wait_ms": waits["prefill"],
            "decode_wait_ms": waits["decode"],
            "mean_batch_width": round(
                self.batched_steps / self.batch_dispatches
                if self.batch_dispatches else 0.0, 3,
            ),
            "chunk_streams": self._chunking_sessions,
            "pages_free": int(pages_free) if pages_free is not None else None,
            "active_sessions": len(self._sessions),
            # parked sessions hold no pinned pages (their KV sits in the
            # pool as evictable cached entries) — routers can discount them
            "parked_sessions": sum(
                1 for s in self._sessions.values() if s.parked
            ),
            "shedding": bool(
                self.admission is not None
                and delay_ms >= self.admission.high_ms
            ),
        }

    def _advert_state(self) -> ServerState:
        """The state this server should announce right now. JOINING is the
        standby advert: below ONLINE, so routing/spans filters keep the
        server invisible to traffic, while clients scanning for
        replication targets (pick_standby) still see it — no new enum
        value, so old peers parse standby adverts fine."""
        if self._draining:
            return ServerState.DRAINING
        if self._standby:
            return ServerState.JOINING
        return ServerState.ONLINE

    def server_info(self) -> ServerInfo:
        return ServerInfo(
            load=self.load_snapshot(),
            state=self._advert_state(),
            # promoted replicas yield in storm resolution and drain back
            # first when the span cools; the primary never demotes
            promoted_standby=self._promoted,
            host=self.public_host,
            port=self.port,
            throughput=self.throughput,
            inference_rps=self.inference_rps,
            cache_tokens_left=self.manager.tokens_left,
            start_block=self.start_block,
            end_block=self.end_block,
            wire_dtype=self.wire_dtype,
            next_pings=self.next_pings.to_wire() or None,
            adapters=sorted(self.adapter_factors) or None,
            decode_n_max=self.decode_n_max,
            # clients need the page geometry to build prefix hash chains
            # (0 advertises "no prefix cache here")
            page_size=(
                self.manager.page_size if self.manager.prefix_cache else 0
            ),
            # clients only pick standbys that can actually install kv_put
            # pages; a draining server is about to leave the swarm and
            # must not attract fresh replication traffic
            kv_repl=self.manager.repl_supported and not self._draining,
            # integrity-enabled clients verify our replies' out_digest
            # stamps; old clients drop the field (from_wire filtering)
            out_digest=self.integrity,
            # JOINing servers/standbys fetch compile artifacts from peers
            # advertising a store; a draining server is about to leave
            # and must not attract artifact fetch traffic
            artifacts=self.artifact_store is not None and not self._draining,
            # a client may cut a long prompt into parts at multiples of the
            # chunk length `_chunk_spans` plans with (0: it never chunks)
            prefill_chunk=(
                0 if self.executor.sp_mesh is not None
                else prefill_chunk_len(
                    self._chunk_budget(), self.executor.max_chunk_tokens
                )
            ),
        )

    async def _announce(self, state: ServerState) -> None:
        info = self.server_info()
        info.state = state
        await self.registry.declare_blocks(
            self.model_uid,
            self.server_id,
            range(self.start_block, self.end_block),
            info,
            expiration=self.announce_period * 2.5,
        )

    async def _announce_loop(self) -> None:
        while True:
            period = self.announce_period
            if self.load_advert_s > 0:
                # faster advert cadence so routing reacts to hot servers
                # within the load window, not a liveness period later; the
                # registry expiration stays announce_period * 2.5, so extra
                # announces only ever REFRESH liveness, never shorten it
                period = min(period, self.load_advert_s)
            await clock.async_sleep(period)
            if self._rebalancing:
                # mid-move: announcing the OLD span would overwrite the
                # tombstone (registry merge is latest-write-wins) and keep
                # routing new sessions onto blocks we are abandoning —
                # exactly defeating the drain. rebalance_to re-announces
                # the new span itself when the swap lands.
                continue
            try:
                # announce FIRST (liveness must not wait on pings — a slow
                # successor would expire our registry record); the pings
                # measured after ride the NEXT announce
                await self._announce(self._advert_state())
                if env.log_channel_enabled("transport"):
                    from bloombee_tpu.wire.tensor_codec import transport_stats

                    logger.info("[transport] %s", transport_stats())
                if env.log_channel_enabled("memory"):
                    from bloombee_tpu.utils.memory import (
                        format_report,
                        server_memory_report,
                    )

                    logger.info(
                        "[memory] %s",
                        format_report(server_memory_report(self)),
                    )
                await asyncio.wait_for(
                    self._measure_next_pings(), self.announce_period
                )
            except asyncio.TimeoutError:
                pass
            except Exception as e:
                logger.warning("announce failed: %s", e)

    async def _measure_next_pings(self) -> None:
        """Ping servers holding the block right after this span so routing
        can cost our push hop with real RTTs."""
        try:
            infos = await self.registry.get_module_infos(
                self.model_uid, [self.end_block]
            )
        except Exception:
            return
        if not infos or not infos[0].servers:
            return
        peers = [
            (sid, info.host, info.port)
            for sid, info in infos[0].servers.items()
            if sid != self.server_id and self.next_pings.needs_measure(sid)
        ][:8]
        if peers:
            await self.next_pings.measure_many(peers)

    # ------------------------------------------------------------------- RPCs
    async def _rpc_info(self, meta: dict, tensors):

        from bloombee_tpu.wire.tensor_codec import transport_stats

        fused_decline = self._decode_n_ineligible()
        params_ok = not self._client_params_unavailable and (
            self._client_params is not None or self.model_dir is not None
        )
        whole = (
            self.start_block == 0
            and self.end_block == self.spec.num_hidden_layers
        )
        warmup_done = self._report_startup_tasks()
        info = {
            "server_id": self.server_id,
            "server_time": clock.now(),  # NTP-style clock sync anchor
            # `prefill_reply`: prefill steps answered with a tensor, the
            # bytes sent against the bytes of all their rows (`reply_tail`)
            "transport": {
                **transport_stats(), "prefill_reply": dict(self.prefill_reply),
            },
            # off-loop codec pipeline counters (wire/pipeline.py): job
            # counts, max observed decode-queue depth, backpressure waits,
            # and the adaptive send-concurrency ceiling across accepted
            # connections
            "wire_pipeline": self.rpc.pipeline_stats(),
            # chaos/ops observability: expired-deadline work drops and the
            # drain flag (also visible as state=DRAINING in server_info)
            "deadlines_expired": self.deadlines_expired,
            "draining": self._draining,
            # elastic self-healing observability: standby/promoted role
            # flags plus the control-loop decision counters (promotion
            # storms resolve as promotions_yielded; drain-backs blocked by
            # live sessions as demotions_aborted)
            "standby": self._standby,
            "promotions": self.promotions,
            "demotions": self.demotions,
            "promotions_yielded": self.promotions_yielded,
            "demotions_aborted": self.demotions_aborted,
            "rebalances_moved": self.rebalances_moved,
            "rebalances_failed": self.rebalances_failed,
            "rebalance_skipped_hysteresis": self.rebalance_skipped_hysteresis,
            # session lifecycle observability (leases/keepalives/resume):
            # leases reaped, parked sessions re-attached, retried steps
            # answered from the recorded reply, keepalive pings sent on
            # accepted conns, pushed items rescued at loop teardown, and
            # the live session age/idle/parked gauges
            "sessions_reaped": self.sessions_reaped,
            "sessions_resumed": self.sessions_resumed,
            "steps_deduped": self.steps_deduped,
            "keepalives_sent": self.rpc.keepalives_sent,
            "pushes_dropped": self.pushes_dropped,
            "session_lease_s": self.session_lease_s,
            **self._session_ages(),
            # continuous-batching observability: how often concurrent
            # sessions' decode steps shared one span dispatch, and how long
            # steps sat in the compute queue (ms percentiles)
            "batched_steps": self.batched_steps,
            "batch_dispatches": self.batch_dispatches,
            "batch_solo_steps": self.batch_solo_steps,
            "mean_batch_width": (
                self.batched_steps / self.batch_dispatches
                if self.batch_dispatches else 0.0
            ),
            # includes per-class sub-dicts ("prefill"/"decode"): bounded
            # decode wait DURING a long prefill is the stall-free signal
            "queue_wait_ms": self.compute.wait_stats_ms(),
            # stall-free scheduling observability (chunked prefill):
            # chunk tasks run, prompt tokens prefilled through the chunked
            # path, and decode steps that dispatched while a prefill was
            # mid-stream (> 0 means prefills no longer head-of-line-block)
            "prefill_chunks": self.prefill_chunks,
            "prefill_chunk_tokens": self.prefill_chunk_tokens,
            "decode_steps_interleaved": self.decode_steps_interleaved,
            # mixed-batch observability: fused decode+prefill dispatches,
            # the tokens they carried, and dispatches_per_token over ALL
            # inference dispatches (1.0 from pure single-token decodes;
            # drops as chunking and fusing pack more tokens per dispatch)
            "mixed_batch": self.mixed_batch,
            "mixed_dispatches": self.mixed_dispatches,
            "mixed_tokens": self.mixed_tokens,
            "step_dispatches": self.step_dispatches,
            "step_tokens": self.step_tokens,
            "dispatches_per_token": (
                self.step_dispatches / max(self.step_tokens, 1)
            ),
            # universal ragged dispatch observability: every fused ragged
            # dispatch, the subset that actually crossed row kinds
            # (decode/tree/chunk in one device step), and every
            # requested-but-declined ragged path keyed by the executor's
            # unsupported reason (non-empty means an operator asked for
            # fusing on a span that can't run it)
            "ragged_group_dispatches": self.ragged_group_dispatches,
            "ragged_cross_kind_dispatches": self.ragged_cross_kind_dispatches,
            # packs of a chunk and decode rows that met no compiled program
            # after the warm-up and went as their parts
            "ragged_cold_splits": self.ragged_cold_splits,
            # with what the cache manager refused because a recurrent
            # state can be kept or zeroed, never cut or copied by pages
            "ragged_declines": {
                k: self.ragged_declines.get(k, 0)
                + self.manager.state_refusals.get(k, 0)
                for k in {*self.ragged_declines,
                          *self.manager.state_refusals}
            },
            # spec-decode observability (batched tree verification):
            # tree-verify steps served, the session rows they carried,
            # drafted vs accepted speculative tokens (from the accept
            # metas riding each next step — the server half of the
            # drafter's feedback loop), and the batched-group counters
            # (mean_tree_batch_width > 1 means sessions actually fused)
            "spec_batch": self.spec_batch,
            "tree_steps": self.tree_steps,
            "tree_rows": self.tree_rows,
            "spec_tokens_drafted": self.spec_tokens_drafted,
            "spec_tokens_accepted": self.spec_tokens_accepted,
            "spec_accept_rate": (
                self.spec_tokens_accepted / max(self.spec_tokens_drafted, 1)
            ),
            "tree_group_dispatches": self.tree_group_dispatches,
            "tree_group_members": self.tree_group_members,
            "mean_tree_batch_width": (
                self.tree_group_members / self.tree_group_dispatches
                if self.tree_group_dispatches else 0.0
            ),
            # per-session measured acceptance, keyed by session id: which
            # streams speculate(d) productively (a cold stream's low rate
            # is the signal the client's auto-tuner shrinks on); recently
            # closed sessions stay visible via the bounded teardown ring
            "session_spec": {
                **dict(self._closed_session_spec),
                **{
                    sid: {
                        "drafted": s.spec_drafted,
                        "accepted": s.spec_accepted,
                        "accept_rate": (
                            s.spec_accepted / max(s.spec_drafted, 1)
                        ),
                    }
                    for sid, s in self._sessions.items()
                    if s.spec_drafted
                },
            },
            # prefix-cache observability: sessions that adopted pooled
            # prompt pages, tokens they skipped prefilling, copy-on-write
            # page splits, and current cached-pool occupancy (plus
            # repl_pages_installed — kv_put pages accepted as a standby)
            **self.manager.prefix_stats(),
            # kv-replication observability (fast failover): sealed pages
            # shipped to standbys, the current sealed-but-unshipped
            # backlog, and tokens recovering clients replayed into us
            "repl_pages_sent": self.repl_pages_sent,
            "repl_lag_pages": self._repl_lag(),
            "failover_replayed_tokens": self.failover_replayed_tokens,
            # integrity observability: digest stamps emitted, audit
            # re-executions served to verifying clients, liar-hook
            # perturbations injected (test runs only), and prefix
            # hash-chain extensions that failed (silent shared-prefix
            # degradation until this surfaced it)
            "integrity": self.integrity,
            "out_digests_sent": self.out_digests_sent,
            "audit_forwards": self.audit_forwards,
            "liar_steps": self.liar_steps,
            "seq_hash_extend_failures": self.seq_hash_extend_failures,
            # warmup/artifact observability: swallowed per-bucket warmup
            # failures (each is a bucket that compiles on its first real
            # request), plus the compile-artifact path — blobs served/
            # installed/fetched, declines, per-peer fetch retries, the
            # ledgered local-compile fallbacks, and the bounded store's
            # occupancy/eviction gauges
            "warmup_failures": self.warmup_failures,
            # False while start-up warm-up / throughput measurement still
            # runs (a client that wants warm buckets waits for True)
            "warmup_done": warmup_done,
            # attention-path observability: device dispatches per path
            # (flash / paged / ragged Pallas kernels vs dense), and Pallas
            # kernel failures that gave way to the dense path — each a
            # kernel bug, never a normal condition
            "attn_dispatches": dict(self.executor.attn_dispatches),
            "kernel_fallbacks": self.executor.kernel_fallbacks,
            # a family with experts: dispatches by the form the experts
            # took: grouped (a list of the chosen experts), tiled (the
            # chosen pairs in row tiles) or dense over all (ops/moe.py)
            **(
                {"moe": {
                    f"{form}_dispatches": n
                    for form, n in self.executor.moe_dispatches.items()
                }}
                if self.spec.num_experts else {}
            ),
            # which of the router's experts this server holds (--experts),
            # and what a cached token costs in one layer, where the page is
            # a latent and not K and V head slabs
            **(
                {"experts_held": list(self.spec.experts_held)}
                if self.spec.num_experts else {}
            ),
            # a span whose layer kinds differ in their cache: how many of
            # each it holds (the K/V arena has a row a full layer, the state
            # arena a row a linear one: "memory" gives both arenas' layers)
            **(
                {"layer_kinds": dict(collections.Counter(
                    self.spec.layer_type(i)
                    for i in range(self.start_block, self.end_block)
                ))} if self.spec.kinds_interleave
                or self.spec.mamba is not None else {}
            ),
            # a SambaY span (runtime/sambay.py): rows through the
            # self-decoder and through the cross-decoder, steps that ended
            # after the shared layer (short) or went on (long), the cross
            # layers' reads of the ONE shared K/V row
            **(
                {"sambay": {
                    k: self.executor.sambay[k] for k in (
                        "self_rows", "cross_rows", "short_steps",
                        "long_steps", "shared_kv_reads")}}
                if self.spec.mamba is not None else {}
            ),
            # which layout the arena's slabs have (kv/arena.py `folds`);
            # and, for a span with window layers, summed over the steps'
            # sequences, the K/V tokens its arena held for them and those
            # of them in window layers' pages that no later query can see
            # (what a page table a layer kind would free)
            "kv": {
                "folded": self.manager.folded,
                # dispatches that held a sequence of more than one row, by
                # how its rows went into the arena (one index a page where
                # they come as page groups: kv/arena.py `rows_fill_pages`)
                **self.executor.kv_writes,
                **({
                    "held_tokens": self.executor.kv_held["kv_held_tokens"],
                    "window_dead_tokens":
                        self.executor.kv_held["window_dead_tokens"]}
                   if any(self.executor.windows) else {}),
            },
            # the tile the last chunk's flash kernel multiplied, by layer
            # kind, the kinds joined by "+" (runtime/executor.py `_flash_form`)
            **(
                {"flash": self.executor.flash_form}
                if self.executor.flash_form else {}
            ),
            # a share of the experts held: what the steps read so far
            # reached of it (sums over steps and sparse layers; per sparse
            # layer the distinct held experts the last step's rows chose)
            **(
                {"moe_reach": dict(self.executor.moe_reach)}
                if self.spec.moe_held is not None else {}
            ),
            **(
                {"latent_bytes_per_token": self.spec.mla.token_bytes}
                if self.spec.mla is not None else {}
            ),
            "artifact_preinstalled": self._artifacts_preinstalled,
            "artifact_fallback_compiles": self.artifact_fallback_compiles,
            "artifact_gets_served": self.artifact_gets_served,
            "artifact_puts_installed": self.artifact_puts_installed,
            "artifact_puts_declined": self.artifact_puts_declined,
            "artifact_blobs_fetched": self.artifact_blobs_fetched,
            "artifact_fetch_retries": self.artifact_fetch_retries,
            "artifact_store_bytes": (
                self.artifact_store.total_bytes()
                if self.artifact_store is not None else 0
            ),
            "artifact_evictions": (
                self.artifact_store.evictions
                if self.artifact_store is not None else 0
            ),
            "artifact_store_declined": (
                self.artifact_store.declined
                if self.artifact_store is not None else 0
            ),
            # lock-witness observability (BBTPU_LOCKWATCH=1): distinct
            # acquisition-order edges observed in this process and
            # hierarchy violations + cycles; both zero (and harmless)
            # when the witness is off, so probes need no conditionals
            **lockwatch.counters(),
            **jitwatch.counters(),
            # where the host's time went (BBTPU_JITWATCH=1; empty / zeros
            # with it off): per span name {"n", "total_ms"}, and the
            # compute worker's wall time by cause
            "host_spans": jitwatch.host_spans(),
            "worker": self.compute.worker_stats_ms(),
            # the worker's busy time by kind of dispatch (decode | chunk |
            # fused | other) and by leg, wall and thread CPU, and what each
            # launch found the device doing (compute_queue._WorkerAccount)
            "host_path": self.compute.host_path(),
            # a session's turn, reply to reply, leg by leg on this server's
            # clock (sums by the step's class; kept with the witness off)
            "turn": self.turn_account.stats_ms(),
            # prefills that came in parts along the sequence axis, and the
            # wait of their chunk loops for rows that had not arrived
            "prefill_parts": {
                "steps": self.prefill_parts["steps"],
                "parts": self.prefill_parts["parts"],
                "wait_ms": round(self.prefill_parts["wait_us"] / 1e3, 3),
            },
            # overload observability: shed/admit counters, retry_after
            # histogram, and per-client fair-share debt (None with the
            # admission controller off; the live load snapshot itself rides
            # in via server_info().to_wire()'s "load" key below)
            "admission": (
                self.admission.stats() if self.admission is not None else None
            ),
            # operator visibility into the decode_n fast paths: a client
            # falling back to per-step decoding is otherwise invisible.
            # decode_n: ANY single-span flavor (fused scan or host-driven
            # stepped loop); decode_n_first/last: the chained-decode roles
            # this span can play in a multi-server route
            "decode_n": whole and params_ok,
            "decode_n_fused": fused_decline is None,
            "decode_n_first": self.start_block == 0 and params_ok,
            "decode_n_last": (
                self.end_block == self.spec.num_hidden_layers and params_ok
            ),
            **self.server_info().to_wire(),
        }
        if fused_decline is not None:
            info["decode_n_decline"] = fused_decline
        from bloombee_tpu import native
        from bloombee_tpu.utils.memory import (
            device_report,
            server_memory_report,
        )

        # operator-pollable memory accounting (reference memory_usage.py's
        # logging surface, as a remote field instead of a local probe)
        info["memory"] = server_memory_report(self)
        # (once more where a reader that keeps `memory` alone finds it)
        info["memory"]["kv_writes"] = dict(self.executor.kv_writes)
        info["memory"]["kv_walk"] = dict(self.executor.kv_walk)
        info["memory"]["host_path"] = info["host_path"]
        if self.spec.mamba is not None or any(self.executor.windows):
            # the window-dead accounting and the SambaY counters once more,
            # beside the K/V arena they are about (a reader that keeps a
            # snapshot of `memory` and not of every key, as the benchmark's
            # does, finds them here; the key is the first family's that
            # had them)
            info["memory"]["sambay"] = {
                **(self.executor.sambay if self.spec.mamba is not None
                   else {}),
                **self.executor.kv_held,
            }
        info["device"] = device_report()
        info["native"] = native.loaded()
        if self._client_params is not None:
            info["head_dtype"] = str(self._client_params["lm_head"].dtype)
        return info, []

    # -------------------------------------------- session-KV replication
    async def _kv_put(self, meta: dict, tensors):
        """Standby side of session-KV replication: install hash-addressed
        sealed pages from a primary into the prefix pool as refcount-0
        cached entries. Cached pages are evictable, so replication can
        never OOM a healthy standby — a degraded pool just means a longer
        replay on failover. Declines (installed=0 + reason) instead of
        erroring so mixed swarms degrade to full replay."""
        decline = None
        if self._draining:
            decline = "draining"
        elif not self.manager.repl_supported:
            decline = (
                "kv replication unsupported (prefix cache off, quantized "
                "or heterogeneous arena, or recurrent state beside the "
                "pages)"
            )
        elif int(meta.get("page_size", 0)) != self.manager.page_size:
            decline = "page_size mismatch"
        elif (
            int(meta.get("start", -1)) != self.start_block
            or int(meta.get("end", -1)) != self.end_block
        ):
            decline = "span mismatch"
        if decline is not None:
            return {"installed": 0, "reason": decline}, []
        hashes = [str(h) for h in (meta.get("hashes") or [])]
        if not hashes or len(tensors) != 2:
            return {"installed": 0, "reason": "empty or malformed payload"}, []
        k = np.asarray(tensors[0])
        v = np.asarray(tensors[1])
        try:
            installed = await self.compute.submit(
                PRIORITY_TRAINING,
                self.manager.install_replicated, hashes, k, v,
            )
        except ValueError as e:
            return {"installed": 0, "reason": str(e)}, []
        return {"installed": int(installed)}, []

    def _note_kv_repl(self, session: _Session, repl: dict) -> None:
        """Primary side: a client's kv_repl stream item names the standby
        and carries each row's full-history page-hash chain. Publish our
        own freshly-sealed decode pages into the local pool under those
        hashes (so a future session can adopt them here too), then sweep
        the backlog to the standby in the background."""
        standby = repl.get("standby") or {}
        chains = [list(c) for c in (repl.get("chains") or [])]
        if not standby.get("host") or not chains:
            return
        if (
            session.repl_sent is None
            or len(session.repl_sent) != len(chains)
        ):
            session.repl_sent = [0] * len(chains)
        session.repl_standby = (str(standby["host"]), int(standby["port"]))
        session.repl_chains = chains
        try:
            self.manager.extend_seq_hashes(session.handle, chains)
        except Exception as e:
            # non-fatal (replication still runs on the client's chains) but
            # NOT silent: each failure quietly degrades shared-prefix reuse
            # for every later session, so surface it via rpc_info/--probe
            self.seq_hash_extend_failures += 1
            logger.warning(
                "extend_seq_hashes failed (%d so far): %s",
                self.seq_hash_extend_failures, e,
            )
        if (
            self.artifact_store is not None
            and session.repl_standby not in self._artifact_pushed_standbys
        ):
            # one-time per standby: ship the compile-artifact set
            # alongside the KV pages, so a later promotion warms by
            # loading executables instead of compiling them
            self._artifact_pushed_standbys.add(session.repl_standby)
            push = asyncio.create_task(
                self._push_artifacts(session.repl_standby)
            )
            session.step_tasks.add(push)
            push.add_done_callback(session.step_tasks.discard)
        task = asyncio.create_task(self._replicate_session(session))
        # step_tasks membership matters: the session loop gathers these
        # before the allocate context frees the pages a sweep is exporting
        session.step_tasks.add(task)
        task.add_done_callback(session.step_tasks.discard)

    async def _replicate_session(self, session: _Session) -> None:
        """Drain the session's replication backlog. Serialized per session
        (repl_sent is the only progress state); re-sweeps until no pages
        ship, since the chains may grow while a sweep is in flight."""
        if session.repl_lock.locked():
            return  # an earlier trigger is still draining the backlog
        async with session.repl_lock:
            # BB009 owner: block-server team. The chain reaches
            # Connection.call's wire serialization, but repl_lock is a
            # per-session drain latch (sole contender is a concurrent
            # trigger, which bails on locked() above) — nothing convoys
            # behind it, and payload size is bounded by _repl_sem plus
            # the per-pass page budget.
            while await self._replicate_pass(session):  # bbtpu: noqa[BB009]
                pass

    async def _replicate_pass(self, session: _Session) -> bool:
        """One sweep over the session's rows; True when any pages shipped
        (caller sweeps again). Failures leave repl_sent untouched so the
        next kv_repl trigger retries; a standby DECLINE stops replication
        for this session — the client re-picks a standby on recovery."""
        standby = session.repl_standby
        chains = session.repl_chains
        sent_by_row = session.repl_sent
        if standby is None or not chains or sent_by_row is None:
            return False
        ps = self.manager.page_size
        seq_ids = session.handle.seq_ids
        progress = False
        for row, chain in enumerate(chains):
            if row >= len(seq_ids) or row >= len(sent_by_row):
                break
            sent = sent_by_row[row]
            if sent >= len(chain):
                continue
            async with self._repl_sem:
                try:
                    res = await self.compute.submit(
                        PRIORITY_TRAINING, self.manager.export_pages,
                        seq_ids[row], sent, len(chain),
                    )
                except Exception as e:
                    logger.debug("kv replication export failed: %s", e)
                    return False
                if res is None:
                    continue  # row parked/adopted/unsupported — skip
                k_dev, v_dev, hi = res
                n = int(hi) - sent
                if n <= 0:
                    continue
                # device [L, n*ps, kv, hd] -> host [n, L, ps, kv, hd]
                # (one leading page axis so the standby scatters per hash)
                def _export(dev, n=n, ps=ps):
                    a = np.asarray(dev)
                    shape = (a.shape[0], n, ps) + a.shape[2:]
                    # the swapaxes copy is O(pages shipped) host work —
                    # keep it on the same worker thread as the d2h pull,
                    # not the event loop
                    return np.ascontiguousarray(
                        np.swapaxes(a.reshape(shape), 0, 1)
                    )

                k = await asyncio.to_thread(_export, k_dev)
                v = await asyncio.to_thread(_export, v_dev)
                try:
                    conn = await self.peers.get(*standby)
                    reply, _ = await conn.call(
                        "kv_put",
                        {
                            "page_size": ps,
                            "start": self.start_block,
                            "end": self.end_block,
                            "hashes": list(chain[sent:int(hi)]),
                        },
                        [k, v],
                        timeout=30.0,
                    )
                except Exception as e:
                    logger.debug("kv replication push failed: %s", e)
                    return False
                installed = (
                    int(reply.get("installed", 0))
                    if isinstance(reply, dict) else 0
                )
                if installed <= 0:
                    logger.info(
                        "standby %s:%d declined kv_put (%s); stopping "
                        "replication for session %s", standby[0], standby[1],
                        (reply or {}).get("reason", "?"), session.id,
                    )
                    session.repl_standby = None
                    return False
                sent_by_row[row] = int(hi)
                self.repl_pages_sent += n
                progress = True
        return progress

    def _repl_lag(self) -> int:
        """Gauge: sealed-but-unshipped pages across replicating sessions
        (bounds how much a failover would replay beyond the unsealed
        tail)."""
        lag = 0
        for s in self._sessions.values():
            if not s.repl_chains or s.repl_sent is None:
                continue
            for row, chain in enumerate(s.repl_chains):
                if row < len(s.repl_sent):
                    lag += max(0, len(chain) - s.repl_sent[row])
        return lag

    # ---------------------------------------- compile-artifact replication
    def _artifact_fp(self) -> dict:
        """This server's artifact-compatibility fingerprint (jax/jaxlib
        version, backend, topology, model spec hash, span, compute dtype,
        KV page geometry). Installing past a mismatch could at best be a
        silent cache miss and at worst a refused deserialize — so both
        ends check it and decline."""
        return artifacts.fingerprint(
            self.spec, self.start_block, self.end_block,
            name_for_dtype(self.compute_dtype), self.manager.page_size,
        )

    def _note_artifact_fallback(self, reason: str) -> None:
        """Every path that abandons pre-installed artifacts funnels here:
        counted, ledgered (the chaos gate requires the fallback path
        actually ran when faulted), and loud. The fallback itself is
        plain local compile — always correct, never a crash."""
        self.artifact_fallback_compiles += 1
        ledger.recovery("server.artifact_fallback_compile")
        logger.warning(
            "compile-artifact fallback: %s; warmup will compile locally",
            reason,
        )

    async def _artifact_get(self, meta: dict, tensors):
        """Serving side of the swarm-shared compile-artifact cache:
        {"manifest": True} returns the digest-stamped blob listing plus
        our fingerprint; {"name": ...} returns one blob (as a uint8
        tensor). Declines with a reason instead of erroring, mirroring
        kv_put; the "artifact" meta stamp marks these frames for the
        chaos harness's artifact-stream fault predicates."""
        store = self.artifact_store
        if store is None:
            return {"artifact": True, "reason": "no artifact store"}, []
        if self._draining or self._crashed:
            return {"artifact": True, "reason": "draining"}, []
        if meta.get("manifest"):
            self.artifact_gets_served += 1
            return {
                "artifact": True,
                "manifest": store.manifest(),
                "fp": self._artifact_fp(),
            }, []
        name = str(meta.get("name") or "")
        blob = store.read_blob(name)
        if blob is None:
            return {"artifact": True, "reason": f"unknown artifact {name!r}"}, []
        self.artifact_gets_served += 1
        return {
            "artifact": True,
            "name": name,
            "digest": artifacts.blob_digest(blob),
        }, [np.frombuffer(blob, dtype=np.uint8)]

    async def _artifact_put(self, meta: dict, tensors):
        """Standby side of artifact replication: install one pushed blob
        into the local store, digest- and fingerprint-checked. Declines
        (installed=0 + reason) instead of erroring so mixed swarms — and
        corrupt or incompatible pushes — degrade to local compile."""
        store = self.artifact_store
        if store is None:
            return {
                "artifact": True, "installed": 0,
                "reason": "no artifact store",
            }, []
        if self._draining:
            return {"artifact": True, "installed": 0,
                    "reason": "draining"}, []
        mismatch = artifacts.fingerprint_compatible(
            self._artifact_fp(), dict(meta.get("fp") or {})
        )
        if mismatch is not None:
            self.artifact_puts_declined += 1
            return {
                "artifact": True, "installed": 0,
                "reason": f"fingerprint mismatch: {mismatch}",
            }, []
        if len(tensors) != 1:
            return {"artifact": True, "installed": 0,
                    "reason": "malformed payload"}, []
        blob = np.asarray(tensors[0], dtype=np.uint8).tobytes()
        decline = store.install(
            str(meta.get("name") or ""), blob, str(meta.get("digest") or "")
        )
        if decline is not None:
            self.artifact_puts_declined += 1
            return {"artifact": True, "installed": 0, "reason": decline}, []
        self.artifact_puts_installed += 1
        return {"artifact": True, "installed": 1}, []

    async def prefetch_artifacts(self) -> bool:
        """JOIN/standby-side fetch: pull this span's compile artifacts
        from covering ONLINE peers before warmup, so warmup loads
        executables instead of compiling them. Fault-tolerant by
        construction: a dead/declining peer retries on the next covering
        peer with the remaining blob set; a corrupt blob (manifest-digest
        mismatch) is declined and dropped; ANY shortfall — no peers, no
        manifest, declined or unfetched blobs — falls back to local
        compile, ledgered. Only a complete install marks the run
        pre-installed (a partial install would turn the jitwatch
        pre-installed gate red on the missing buckets, and rightly so).
        Never raises."""
        store = self.artifact_store
        if store is None or self.registry is None:
            return False
        timeout = float(env.get("BBTPU_ARTIFACT_FETCH_TIMEOUT_S"))
        my_fp = self._artifact_fp()
        try:
            infos = await self.registry.get_module_infos(
                self.model_uid, range(self.start_block, self.end_block)
            )
        except Exception as e:
            self._note_artifact_fallback(
                f"registry fetch failed: {e.__class__.__name__}"
            )
            return False
        peers: dict[tuple[str, int], None] = {}
        for info in infos or []:
            for sid, s in (info.servers if info else {}).items():
                if (
                    sid != self.server_id
                    and getattr(s, "artifacts", False)
                    and s.state == ServerState.ONLINE
                    and s.start_block <= self.start_block
                    and s.end_block >= self.end_block
                ):
                    peers.setdefault((str(s.host), int(s.port)))
        if not peers:
            self._note_artifact_fallback("no covering peer with artifacts")
            return False
        pending: dict[str, str] | None = None  # name -> manifest digest
        declined = 0
        installed = 0
        for i, addr in enumerate(peers):
            if i:
                self.artifact_fetch_retries += 1
            try:
                conn = await self.peers.get(*addr)
                reply, _ = await conn.call(
                    "artifact_get", {"artifact": True, "manifest": True},
                    [], timeout=timeout,
                )
                if not isinstance(reply, dict) or reply.get("reason"):
                    continue
                mismatch = artifacts.fingerprint_compatible(
                    my_fp, dict(reply.get("fp") or {})
                )
                if mismatch is not None:
                    logger.info(
                        "peer %s:%d artifact fingerprint mismatch (%s); "
                        "trying next peer", addr[0], addr[1], mismatch,
                    )
                    continue
                if pending is None:
                    pending = {
                        str(e["name"]): str(e["digest"])
                        for e in (reply.get("manifest") or [])
                        if isinstance(e, dict) and e.get("name")
                    }
                for name in list(pending):
                    r2, blobs = await conn.call(
                        "artifact_get", {"artifact": True, "name": name},
                        [], timeout=timeout,
                    )
                    if (
                        not isinstance(r2, dict) or r2.get("reason")
                        or len(blobs) != 1
                    ):
                        declined += 1
                        pending.pop(name)
                        continue
                    blob = np.asarray(blobs[0], dtype=np.uint8).tobytes()
                    # verify against the MANIFEST digest, not the one
                    # riding the blob reply: the manifest fetch is the
                    # trust anchor, so a blob corrupted in flight can't
                    # vouch for itself
                    why = store.install(name, blob, pending[name])
                    if why is not None:
                        declined += 1
                        logger.warning(
                            "artifact %s declined: %s", name, why
                        )
                    else:
                        installed += 1
                        self.artifact_blobs_fetched += 1
                    pending.pop(name)
                if not pending:
                    break
            except Exception as e:
                # peer death mid-fetch: the remaining pending set retries
                # verbatim on the next covering peer
                logger.warning(
                    "artifact fetch from %s:%d failed mid-stream: %s",
                    addr[0], addr[1], e,
                )
                continue
        if pending is None:
            self._note_artifact_fallback("no usable manifest from any peer")
            return False
        if declined or pending:
            self._note_artifact_fallback(
                f"{declined} blob(s) declined, {len(pending)} unfetched"
            )
            return False
        if not installed:
            self._note_artifact_fallback("peer manifest was empty")
            return False
        self._artifacts_preinstalled = True
        jitwatch.mark_preinstalled()
        logger.info(
            "pre-installed %d compile artifact(s); warmup will load, "
            "not compile", installed,
        )
        return True

    async def _push_artifacts(self, standby: tuple[str, int]) -> None:
        """Primary side: best-effort ship of the artifact store to a
        replication standby (bounded by _repl_sem so artifact traffic
        never crowds out live inference, same as KV sweeps). A decline
        stops the push; any failure just leaves the standby to prefetch
        at its own next warmup."""
        store = self.artifact_store
        if store is None:
            return
        fp = self._artifact_fp()
        try:
            for entry in store.manifest():
                blob = store.read_blob(entry["name"])
                if blob is None:
                    continue  # evicted mid-push
                async with self._repl_sem:
                    conn = await self.peers.get(*standby)
                    reply, _ = await conn.call(
                        "artifact_put",
                        {
                            "artifact": True,
                            "name": entry["name"],
                            "digest": entry["digest"],
                            "fp": fp,
                        },
                        [np.frombuffer(blob, dtype=np.uint8)],
                        timeout=30.0,
                    )
                if not (isinstance(reply, dict) and reply.get("installed")):
                    logger.info(
                        "standby %s:%d declined artifact_put (%s); "
                        "stopping artifact push", standby[0], standby[1],
                        (reply or {}).get("reason", "?"),
                    )
                    return
        except Exception as e:
            logger.debug(
                "artifact push to %s:%d failed: %s", standby[0],
                standby[1], e,
            )

    async def _rpc_inference(self, stream: Stream) -> None:
        """One decode session. Open meta: {session_id, batch_size, max_length,
        start?, end?}; items: {step, commit, reply, route} + [hidden (B,T,D)]
        (+ tree mask u8 [B,T,T] when meta['tree'])."""
        meta = stream.open_meta
        if self._draining:
            # routing should already avoid us (DRAINING announce), but a
            # client racing a stale swarm view can still arrive — refuse
            # before allocating KV it could never finish using
            raise RuntimeError("server is draining; open a session elsewhere")
        if self._standby:
            # a standby (or a replica mid-drain-back) holds weights and
            # replicated KV but is NOT serving: it announces JOINING, so
            # only a client racing a stale swarm view lands here
            raise RuntimeError(
                "server is a standby for this span; open a session on a "
                "serving replica"
            )
        if meta.get("resume") is not None:
            # reconnect-resume: re-attach a parked session instead of
            # allocating anything — this handler only hands its fresh
            # stream to the surviving page-owning handler
            await self._rpc_resume(stream, str(meta["resume"]))
            return
        session_id = meta["session_id"]
        batch = int(meta["batch_size"])
        max_length = int(meta["max_length"])
        adapter = meta.get("adapter")
        client_id = str(meta.get("client_id") or session_id)
        if self.admission is not None:
            # admission check BEFORE allocating KV: a session open is new
            # work by definition. Shedding here (structured, retriable)
            # beats admitting a session whose steps would then rot in the
            # queue until the client's deadline aborts them.
            retry_ms = self.admission.admit_new(
                client_id, self.compute.current_delay_ms(
                    self.admission.window_s
                ),
            )
            if retry_ms is not None:
                self.admission.shed_sessions += 1
                raise OverloadedError(
                    "server overloaded: queue delay past admission high "
                    "watermark; retry elsewhere",
                    retry_after_ms=retry_ms,
                )
        from bloombee_tpu.models.checkpoint import resolve_adapter

        resolve_adapter(self.adapter_factors, adapter)  # loud on unknown
        layers = self._resolve_layers(meta)
        async with self.manager.allocate(
            batch, max_length, timeout=self.alloc_timeout
        ) as handle:

            session = _Session(session_id, handle, batch, layers, adapter,
                               client_id=client_id)
            session.has_state = self.spec.recurrent is not None
            session.opened_at = clock.monotonic()
            session.last_step_at = session.opened_at
            session.turns = turn.ServerTurns(
                self.turn_account, session_id, stream.open_read_ns
            )
            self._sessions[session_id] = session
            self._drain_pending_pushes(session)
            cur_stream = stream
            try:
                while True:
                    session.cur_stream = cur_stream
                    try:
                        await self._session_loop(session, cur_stream)
                        break  # client half-closed: done
                    except (ConnectionClosed, OSError, RpcError) as e:
                        # the stream died under the session. With leases on
                        # (and KV not run ahead of the client's history),
                        # park and wait for a reconnect instead of freeing
                        if (
                            self.session_lease_s <= 0
                            or self._draining
                            or session.kv_dirty
                        ):
                            raise
                        cur_stream = await self._park_until_resumed(
                            session, e
                        )
                        if cur_stream is None:
                            break  # lease expired; pages reclaimed below
            finally:
                self._sessions.pop(session_id, None)
                if session.spec_drafted:
                    self._closed_session_spec[session_id] = {
                        "drafted": session.spec_drafted,
                        "accepted": session.spec_accepted,
                        "accept_rate": (
                            session.spec_accepted
                            / max(session.spec_drafted, 1)
                        ),
                    }
                    while len(self._closed_session_spec) > 64:
                        self._closed_session_spec.popitem(last=False)
                session.parked = False
                # release the resume handler carrying the current stream
                # (it returns once we are done with its stream)
                if session.detach_event is not None:
                    session.detach_event.set()
                    session.detach_event = None
                if cur_stream is not stream:
                    # the session ended on a RESUMED stream: its client is
                    # live and reading — half-close so it sees end-of-
                    # stream instead of hanging (the original stream's
                    # teardown runs in our caller, against a dead conn)
                    try:
                        await cur_stream.close()
                    except Exception:
                        pass
                if session.n_steps:
                    wall = clock.monotonic() - session.opened_at
                    turns = session.turns
                    per_turn = 1e3 * max(turns.n, 1)
                    logger.info(
                        "[TIMING_TABLE] session=%s steps=%d tokens=%d "
                        "mean_dispatch_ms=%.2f mean_fetch_ms=%.2f "
                        "mean_away_ms=%.2f mean_ingest_ms=%.2f "
                        "mean_reply_ms=%.2f "
                        "wall_s=%.2f steps_per_s=%.2f",
                        session.id, session.n_steps, session.sum_tokens,
                        session.sum_dispatch_ms / session.n_steps,
                        session.sum_fetch_ms / session.n_steps,
                        turns.sum_away_us / per_turn,
                        turns.sum_ingest_us / per_turn,
                        turns.sum_reply_us / per_turn,
                        wall, session.n_steps / max(wall, 1e-9),
                    )

    def _resolve_layers(self, meta: dict) -> tuple[int, int] | None:
        """Honor a requested sub-span (the router may enter this server's span
        mid-way: suffix sub-spans, reference spans_containing_block)."""
        start = int(meta.get("start", self.start_block))
        end = int(meta.get("end", self.end_block))
        if not (self.start_block <= start < end <= self.end_block):
            raise ValueError(
                f"requested blocks [{start},{end}) outside served span "
                f"[{self.start_block},{self.end_block})"
            )
        if (start, end) == (self.start_block, self.end_block):
            return None
        return (start - self.start_block, end - self.start_block)

    # ------------------------------------------- session leases & resume
    async def _park_until_resumed(
        self, session: _Session, cause: Exception
    ) -> Stream | None:
        """The session's stream died but its lease keeps it alive: drain
        in-flight work, hand the KV pages to the prefix pool as evictable
        cached entries (a parked session can never pin memory — under
        pressure its pages are simply evicted and the resume degrades to
        full replay), then sleep until a resume handler delivers a fresh
        stream or the reaper expires the lease. Returns the new stream, or
        None once the session is reclaimed."""

        # fence the dead stream: nothing may still be writing KV when the
        # pages change owner (same ordering as _session_loop teardown)
        if session.step_tasks:
            await asyncio.gather(*session.step_tasks, return_exceptions=True)
        if session.detach_event is not None:
            # the stream that just died was itself a resumed one — let its
            # carrier handler go
            session.detach_event.set()
            session.detach_event = None
        session.cur_stream = None
        session.resume_stream = None
        session.resume_waiter = asyncio.Event()
        session.lease_deadline = clock.monotonic() + self.session_lease_s
        session.parked = True
        await self.manager.lease_park(session.handle)
        ledger.recovery("server.lease_park")
        logger.info(
            "session %s parked after stream death (%s: %s); resumable for "
            "%.1fs", session.id, type(cause).__name__, cause,
            self.session_lease_s,
        )
        await session.resume_waiter.wait()
        session.parked = False
        if session.reaped or session.resume_stream is None:
            self.manager.lease_reclaim(session.handle)
            self.sessions_reaped += 1
            ledger.recovery("server.lease_reap")
            logger.info(
                "session %s lease expired while parked; KV reclaimed",
                session.id,
            )
            return None
        stream = session.resume_stream
        session.resume_stream = None
        session.lease_deadline = 0.0
        return stream

    async def _rpc_resume(self, stream: Stream, session_id: str) -> None:
        """Resume half of reconnect-resume: re-attach a parked session to
        this fresh stream. On success the PARKED handler (which owns the
        pages) serves the stream; this handler just holds the stream's RPC
        frame open until the session lets go of it. Declines (resumed:
        False) instead of erroring so the client cleanly falls back to the
        standby/full-replay path."""

        session = self._sessions.get(session_id)
        reason = None
        if session is None:
            reason = "unknown session (lease expired or never parked here)"
        elif session.kv_dirty:
            reason = "session KV ran ahead of acked history; replay"
        elif not session.parked:
            # the old stream looks alive from here (half-open not yet
            # detected): the client knows better — fence it and wait
            # briefly for the owner to park
            old = session.cur_stream
            if old is not None and old.conn is not stream.conn:
                old.conn.abort("superseded by session resume")
            for _ in range(100):
                if session.parked or session_id not in self._sessions:
                    break
                await clock.async_sleep(0.05)
            if not session.parked:
                reason = "session is still attached to a live stream"
        if reason is None and (
            session.reaped or clock.monotonic() >= session.lease_deadline
        ):
            reason = "session lease expired"
        if reason is None and not await self.manager.lease_resume(
            session.handle
        ):
            # parked pages were evicted under pressure (or the arena was
            # rebuilt): the copy is gone — expire the lease so the parked
            # handler reclaims instead of waiting out the clock
            reason = "parked KV no longer intact; replay"
            session.reaped = True
            session.resume_waiter.set()
        if reason is not None:
            logger.info(
                "refusing resume of session %s: %s", session_id, reason
            )
            await stream.send({"resumed": False, "reason": reason})
            return
        session.stream_epoch += 1
        detach = asyncio.Event()
        session.detach_event = detach
        session.resume_stream = stream
        self.sessions_resumed += 1
        logger.info(
            "session %s resumed on a fresh stream (epoch %d, last applied "
            "step %d)", session_id, session.stream_epoch,
            session.last_step_id,
        )
        # the ack carries the last APPLIED step id so the client
        # retransmits exactly its unacked tail (any retransmit of an
        # applied step dedups server-side anyway — belt and braces)
        await stream.send(
            {
                "resumed": True,
                "last_step": session.last_step_id,
                "epoch": session.stream_epoch,
            }
        )
        session.resume_waiter.set()
        await detach.wait()

    async def _lease_reaper_loop(self) -> None:
        """Background sweeper: expire parked sessions whose lease ran out,
        and fence live sessions whose client has been silent past the
        lease (belt and braces under keepalives; the only detector when
        keepalives are off). A fenced stream fails into the ordinary park
        path, so even this late detection hands the pages to the pool
        rather than freeing them under a client that might still return."""

        interval = max(0.05, self.session_lease_s / 4)
        while True:
            await clock.async_sleep(interval)
            now = clock.monotonic()
            for session in list(self._sessions.values()):
                if session.parked:
                    if now >= session.lease_deadline and not session.reaped:
                        session.reaped = True
                        if session.resume_waiter is not None:
                            session.resume_waiter.set()
                    continue
                stream = session.cur_stream
                conn = stream.conn if stream is not None else None
                # the lease renews on any applied step AND on any inbound
                # frame (keepalive pongs included): only a truly silent
                # client expires
                renewed = max(
                    session.last_step_at,
                    conn.last_recv if conn is not None else 0.0,
                )
                if conn is not None and now - renewed >= self.session_lease_s:
                    logger.warning(
                        "session %s silent for %.1fs (lease %.1fs): "
                        "fencing its stream", session.id, now - renewed,
                        self.session_lease_s,
                    )
                    conn.abort("session lease expired (silent client)")

    def _session_ages(self) -> dict:
        """Operator gauges for rpc_info: how old and how idle the live
        sessions are, and how many sit parked awaiting a resume."""

        now = clock.monotonic()
        ages = [now - s.opened_at for s in self._sessions.values()]
        idles = [now - s.last_step_at for s in self._sessions.values()]
        return {
            "sessions_parked": sum(
                1 for s in self._sessions.values() if s.parked
            ),
            "session_oldest_s": round(max(ages), 3) if ages else 0.0,
            "session_oldest_idle_s": round(max(idles), 3) if idles else 0.0,
        }

    def _dedup_step(self, session: _Session, meta: dict):
        """At-most-once: a step already applied (recorded reply) must not
        re-apply KV when the client retries it after a lost ack. Returns
        the recorded (resp_meta, tensors) to resend, or None for fresh
        work. Only consulted with leases on — without resume there are no
        retransmits to dedup."""
        step = meta.get("step")
        if self.session_lease_s <= 0 or step is None:
            return None
        step = int(step)
        if step < session.last_step_id:
            # long-superseded retransmit; the recorded replies are gone but
            # the client has also long since acted on newer steps — ack it
            return {"step": step, "ack": True, "deduped": True}, []
        return session.applied_steps.get((step, int(meta.get("mb") or 0)))

    def _record_reply(
        self, session: _Session, meta: dict, resp: dict, tensors: list
    ) -> None:
        """Record a step's reply BEFORE first delivery (the KV mutation is
        already applied by now): if the ack is lost to a dying stream, the
        client's post-resume retransmit gets this exact reply back instead
        of a second application. Only the latest step's replies are kept —
        the client's window never retries older ones."""
        step = meta.get("step")
        if self.session_lease_s <= 0 or step is None:
            return
        step = int(step)
        if step > session.last_step_id:
            session.last_step_id = step
            session.applied_steps.clear()
        session.applied_steps[(step, int(meta.get("mb") or 0))] = (
            resp, tensors,
        )

    async def _session_loop(self, session: _Session, stream: Stream) -> None:
        """Race client-stream items against pushed items
        (reference handler.py:1677-1847). Micro-batch chunks (mb_of > 1) run
        as concurrent tasks so chunk k+1's compute dispatches while chunk k's
        output is still in flight downstream — the within-stage overlap of
        the reference's accumulate/immediate queues (handler.py:1850-2151);
        whole-batch steps keep strict sequential handling."""
        stream_next = asyncio.ensure_future(stream.recv())
        push_next = asyncio.ensure_future(session.push_inbox.get())
        try:
            while True:
                done, _ = await asyncio.wait(
                    {stream_next, push_next},
                    return_when=asyncio.FIRST_COMPLETED,
                )
                if stream_next in done:
                    item = stream_next.result()
                    if item is None:
                        break  # client closed the session
                    # the item's own note: no other recv() is armed yet
                    session.turns.noted(stream.read_ns)
                    await self._handle_item(session, stream, *item)
                    stream_next = asyncio.ensure_future(stream.recv())
                if push_next in done:
                    meta, tensors = push_next.result()
                    session.turns.noted(None)  # another server's push
                    await self._handle_item(session, stream, meta, tensors)
                    push_next = asyncio.ensure_future(session.push_inbox.get())
        finally:
            stream_next.cancel()
            if push_next.done() and not push_next.cancelled():
                # the race was lost at teardown: push_inbox.get() completed
                # with an item nobody consumed. Cancelling would silently
                # drop a pushed micro-batch chunk — requeue it instead so a
                # parked session's resume (or the pending-push buffer path)
                # still sees it, and count it for operators
                try:
                    session.push_inbox.put_nowait(push_next.result())
                    self.pushes_dropped += 1  # requeued, but the loop ended
                    logger.info(
                        "session %s teardown requeued an unconsumed pushed "
                        "item (%d total across sessions)", session.id,
                        self.pushes_dropped,
                    )
                except Exception:
                    # the push either failed in flight or the inbox is
                    # full — both moot at teardown; the client replays
                    pass
            else:
                push_next.cancel()
            # drain in-flight chunk tasks BEFORE the allocate context frees
            # the session's pages: a still-running dispatch must not write
            # KV into pages a new session may reuse
            if session.step_tasks:
                await asyncio.gather(
                    *session.step_tasks, return_exceptions=True
                )

    async def _handle_item(
        self, session: _Session, stream: Stream, meta: dict, tensors: list
    ) -> None:
        if int(meta.get("mb_of", 1)) <= 1:
            try:
                await self._run_step(session, stream, meta, tensors)
            finally:
                # a turn that got no reply (an expired deadline, a lost
                # session, a send that raised) is forgotten, and whatever
                # the step sent last (a typed error, a decline, a retry
                # answered from the record) is where the next `away` starts
                session.turns.dropped(meta.get("step"))
                session.turns.replied(meta.get("step"), stream.write_ns)
            return
        task = asyncio.create_task(
            self._run_step_logged(session, stream, meta, tensors)
        )
        session.step_tasks.add(task)
        task.add_done_callback(session.step_tasks.discard)

    async def _run_step_logged(
        self, session: _Session, stream: Stream, meta: dict, tensors: list
    ) -> None:
        try:
            await self._run_step(session, stream, meta, tensors)
        except asyncio.CancelledError:
            raise
        except Exception as e:
            # a failed chunk poisons the whole step: close the stream so the
            # client's retry path rebuilds the chain
            logger.warning("micro-batch step failed: %s", e)
            try:
                await stream.close()
            except Exception:
                pass

    async def _maybe_reply_session_lost(
        self, session: _Session, stream: Stream, meta: dict, e: Exception
    ) -> bool:
        """Classify a step failure: when this session's KV is gone (arena
        rebuilt, or a parked copy lost), reply the typed `session_lost` so
        the client replays WITHOUT banning the healthy server (advisor,
        round 4). Covers both the step that finds a stale epoch and the
        step whose own failure consumed the arena (the executor rebuilds
        before re-raising, so the epoch is stale by reply time)."""
        if isinstance(
            e, (SessionKVLost, ParkedKVLost)
        ) or not self.manager.epoch_valid(session.handle):
            await stream.send(
                {
                    "step": meta.get("step"),
                    "session_lost": True,
                    "reason": str(e),
                }
            )
            return True
        return False

    @staticmethod
    def _local_deadline(meta: dict) -> float | None:
        """meta['deadline_s'] (relative remaining seconds stamped by the
        client or shrunk by the previous hop) -> local monotonic cutoff,
        or None when the item carries no budget."""

        budget = meta.get("deadline_s")
        if budget is None:
            return None
        return clock.monotonic() + float(budget)

    @staticmethod
    def _deadline_passed(deadline: float | None) -> bool:

        return deadline is not None and clock.monotonic() > deadline

    def _liar_perturb(self, out: np.ndarray) -> np.ndarray:
        """TEST HOOK (liar_p): return a perturbed copy of a span output —
        the Byzantine lie the client integrity layer exists to convict.
        Deliberately LOUD (NaN poison / x64 scale / exponent bit-flip):
        the point is exercising detection+quarantine end to end, not
        probing the envelope's sensitivity floor."""
        arr = np.array(out, copy=True)
        if arr.size == 0:
            return out
        mode = ("nan", "scale", "bitflip")[self._liar_rng.randrange(3)]
        flat = arr.reshape(-1)
        idx = self._liar_rng.randrange(flat.size)
        if mode == "nan":
            flat[idx] = float("nan")
        elif mode == "scale":
            np.multiply(arr, arr.dtype.type(64), out=arr)
        else:
            view = flat.view(np.uint8)
            byte = idx * arr.dtype.itemsize + (arr.dtype.itemsize - 1)
            view[byte] ^= 0x40
        return arr

    def _note_deadline_expired(self, meta: dict, where: str) -> None:
        self.deadlines_expired += 1
        logger.info(
            "dropping step %s: client deadline expired %s "
            "(%d drops total)", meta.get("step"), where,
            self.deadlines_expired,
        )

    async def _run_step(
        self, session: _Session, stream: Stream, meta: dict, tensors: list
    ) -> None:
        if meta.get("part"):
            # a later part of a prefill in parts that no chunk loop took
            # (`_StepRows`): its step was answered from the record, refused,
            # dropped or failed at its first part, and said so there
            return
        if meta.get("chain") is not None:
            # pushed hop of a chained decode_n (never from the client
            # stream): errors go back to the coordinator via chain_error,
            # not to our own client's stream
            await self._run_chain_step(session, meta, tensors)
            return
        repl = meta.get("kv_repl")
        if repl is not None:
            # async session-KV replication control: record the standby +
            # the client's full-history hash chains, publish our own
            # sealed decode pages locally under those hashes, and schedule
            # shipping the backlog. Fire-and-forget: NO reply (a reply
            # would desync the client's strictly-ordered step stream).
            self._note_kv_repl(session, repl)
            return
        cached = self._dedup_step(session, meta)
        if cached is not None:
            # at-most-once: this step was already applied and its reply
            # recorded before the stream died — resend the identical reply
            # instead of mutating KV a second time
            self.steps_deduped += 1
            ledger.recovery("server.resume_dedup")
            resp, out_t = cached
            # (the turn was counted when the step was served)
            await stream.send({**resp, "deduped": True}, out_t)
            return
        # client deadline budget: "deadline_s" is RELATIVE remaining time
        # (never an absolute timestamp — clocks differ across machines);
        # convert to a local monotonic cutoff at arrival
        deadline = self._local_deadline(meta)
        if self._deadline_passed(deadline):
            self._note_deadline_expired(meta, "on arrival")
            return
        if not self.manager.epoch_valid(session.handle):
            # cheap pre-check so a stale session's accept/decode never
            # touches zeroed table state (authoritative check re-runs on
            # the compute thread, racing rebuilds are classified below)
            await stream.send(
                {
                    "step": meta.get("step"),
                    "session_lost": True,
                    "reason": "server KV arena was rebuilt; session cache "
                    "lost — replay",
                }
            )
            return
        probe = meta.get("prefix_probe")
        if probe is not None:
            # prefix-cache probe: adopt each row's longest pooled prompt
            # prefix NOW (refcount-pinning the pages against eviction) and
            # report the per-row hit; the client follows up with the
            # chain-wide skip on its prefill. Pure host-side table work —
            # no reason to wait behind the compute queue.
            matched = self.manager.adopt_prefix(session.handle, probe)
            resp = {"step": meta.get("step"), "prefix_matched": matched}
            self._record_reply(session, meta, resp, [])
            await stream.send(resp)
            return
        # speculative accept from the previous round: compact surviving KV
        # rows onto the committed prefix before this step's compute
        accept = meta.get("accept")
        if accept is not None:
            if session.last_tree is not None:
                # background: training must never stall the event loop or
                # delay this accept's own step
                task = asyncio.create_task(
                    self._train_pruner_head(session, accept)
                )
                session.step_tasks.add(task)
                task.add_done_callback(session.step_tasks.discard)
            try:
                await self.compute.submit(
                    PRIORITY_INFERENCE,
                    self.manager.accept_speculative,
                    session.handle,
                    [np.asarray(a, dtype=np.int64) for a in accept],
                )
            except Exception as e:
                if await self._maybe_reply_session_lost(
                    session, stream, meta, e
                ):
                    return
                raise
            # measured speculation: each row's accept keeps its surviving
            # path beyond node 0 (node 0 is the previous round's bonus
            # token — certain, not drafted)
            kept = sum(max(0, len(a) - 1) for a in accept)
            self.spec_tokens_accepted += kept
            session.spec_accepted += kept
        if meta.get("accept_only"):
            # the accept above compacted KV: record before delivery so a
            # retried accept after a lost ack never compacts twice
            resp = {"step": meta.get("step"), "ack": True}
            self._record_reply(session, meta, resp, [])
            await stream.send(resp)
            return
        if meta.get("decode_n"):
            await self._run_decode_n(session, stream, meta, tensors)
            return

        if self.admission is not None and session.n_steps == 0:
            # in-stream shed for NEW work only: a session that has never
            # completed a step is about to run its prefill — if overload
            # began after its open was admitted, refuse it now with the
            # typed retriable reply (mirrors session_lost) instead of
            # queueing it. A session with n_steps > 0 is ESTABLISHED: its
            # next decode step is always admitted, so live streams degrade
            # gracefully rather than die.
            retry_ms = self.admission.admit_new(
                session.client_id, self.compute.current_delay_ms(
                    self.admission.window_s
                ),
            )
            if retry_ms is not None:
                await stream.send({
                    "step": meta.get("step"),
                    "overloaded": True,
                    "retry_after_ms": retry_ms,
                    "reason": "server overloaded: new-session prefill shed "
                    "past admission high watermark",
                })
                return

        # keep the sender's dtype (bf16 on the production wire); the executor
        # casts to compute dtype on device
        hidden = np.asarray(tensors[0])
        # the step's rows along the sequence: all in `hidden`, or `hidden`
        # the first part of them and the rest still on its way
        seq = _StepRows(hidden, meta, stream, deadline, session.id,
                        self.prefill_parts)
        tree_mask = None
        depths = None
        # kind-aware group_hint gauge: tree steps mark the session
        # speculating (spec-decode rounds are all tree steps, so the flag
        # is stable between rounds); a plain single-token decode step
        # reveals a NON-speculating session. Prefill / chunk steps are
        # kind-neutral — the session might start speculating right after
        # its prompt, so they leave the optimistic default alone.
        if meta.get("tree"):
            session.speculating = True
        elif seq.tokens == 1:
            session.speculating = False
        if meta.get("tree"):
            tree_mask = np.asarray(tensors[1], dtype=bool)
            if meta.get("depths") is not None:
                depths = np.asarray(meta["depths"], dtype=np.int32)
            # spec-decode observability: every tree-verify step counts
            # (solo or grouped); node 0 of each row is the previous bonus
            # token, so drafted = rows * (nodes - 1)
            drafted = int(hidden.shape[0]) * max(0, int(hidden.shape[1]) - 1)
            self.tree_steps += 1
            self.tree_rows += int(hidden.shape[0])
            self.spec_tokens_drafted += drafted
            session.spec_drafted += drafted
        commit = bool(meta.get("commit", True))
        # micro-batch chunk: operate on a row slice of the session's cache
        # handle (seq_ids are independent, so a sub-handle is just a slice)
        rows = meta.get("rows")
        handle = session.handle
        if rows is not None and tuple(rows) != (0, session.batch_size):
            import dataclasses as _dc

            handle = _dc.replace(
                session.handle, seq_ids=session.handle.seq_ids[rows[0]:rows[1]]
            )
        if hidden.shape[0] != handle.batch_size:
            raise ValueError(
                f"step rows {rows} carry batch {hidden.shape[0]} != "
                f"{handle.batch_size} cache rows"
            )

        # Two phases: dispatch runs on the serialized compute queue (device
        # work enqueues in order, ~1 ms), but the d2h fetch happens HERE, off
        # the queue, so concurrent sessions overlap their device round trips
        # (a step cannot hide its own dependent h2d -> compute -> d2h trip —
        # the reference overlaps the same way with per-handler processes and
        # CUDA streams, task_pool.py:127-192).
        # ragged replay: the step writes a padded rectangle speculatively
        # and each row commits to its true length (freeing the padding's
        # pages) INSIDE the same compute-thread slot as the dispatch, so an
        # over-subscribed reclaimer can never park the session in between.
        # `handle` may be a row slice — align lengths to its rows.
        commit_lens = meta.get("commit_lens")
        if commit_lens is not None:
            commit_lens = [int(x) for x in commit_lens]
            if rows is not None:
                commit_lens = commit_lens[rows[0]:rows[1]]
        # the rows of the answer the client will read (`reply_tail`: the
        # last n positions of each sequence): only the span that ANSWERS
        # the client cuts, a next hop gets every row
        tail = meta.get("reply_tail")
        if tail is not None and (
            isinstance(tail, bool) or not isinstance(tail, int) or tail < 1
        ):
            raise ValueError(
                f"reply_tail must be a positive integer, got {tail!r}"
            )
        if (
            meta.get("route") or meta.get("reply", "tensor") != "tensor"
            or tree_mask is not None
        ):
            tail = None
        turns, step = session.turns, meta.get("step")
        turns.arrive(
            step,
            "prefill" if seq.tokens > 1 and tree_mask is None
            else "decode",
            meta.get(turn.META_KEY), frames=int(meta.get("mb_of", 1)),
        )
        try:
            spans = self._chunk_spans(
                seq.tokens, commit, tree_mask, commit_lens
            )
            if spans is None:
                # one task: a whole frame's rows are there, parts that this
                # server plans no chunks for are waited for
                hidden = await seq.take(0, seq.tokens)
            if spans is not None:
                # stall-free scheduling: the prefill becomes a stream of
                # resumable chunk tasks re-entering the priority queue, so
                # other sessions' decode steps run between chunks instead
                # of stalling behind the whole prompt; a chunk runs as soon
                # as the part that holds its rows has arrived
                out_dev, t_dispatch_ms = await self._run_chunked_prefill(
                    session, handle, seq, spans, deadline,
                    meta.get("prefix_skip"), tail,
                )
            elif self._batchable(commit, hidden, tree_mask, depths,
                                 commit_lens, meta.get("prefix_skip")):
                # continuous batching: compatible single-token decode steps
                # of OTHER sessions that are queued right now (or arrive
                # within BBTPU_BATCH_WINDOW_MS) share one merged span
                # dispatch; this call still returns only our own rows
                out_dev, t_dispatch_ms = await self.compute.submit_group(
                    PRIORITY_INFERENCE,
                    ("decode1", session.layers, session.adapter,
                     str(hidden.dtype)),
                    _BatchMember(session, handle, hidden),
                    # with --mixed-batch / --spec-batch the group may also
                    # hold a prefill chunk or tree-verify rows; the ragged
                    # runner degrades to the classic decode-group path for
                    # chunk-free, tree-free groups
                    self._compute_ragged_group
                    if (self.mixed_batch or self.spec_batch)
                    else self._compute_step_group,
                    deadline=deadline,
                    task_class="decode",
                )
            elif self._tree_batchable(commit, tree_mask, depths,
                                      commit_lens, meta):
                # batched tree verification: compatible tree-verify steps
                # of OTHER speculating sessions that are queued right now
                # (or arrive within BBTPU_BATCH_WINDOW_MS) pad/stack into
                # one ragged span dispatch; trees of differing size share
                # the key (size is not part of it), and with --mixed-batch
                # also on, the compat predicate fuses tree rows with
                # decode rows and a prefill chunk in the SAME dispatch
                out_dev, t_dispatch_ms = await self.compute.submit_group(
                    PRIORITY_INFERENCE,
                    ("tree", session.layers, session.adapter,
                     str(hidden.dtype)),
                    _TreeMember(session, handle, hidden, tree_mask, depths),
                    self._compute_ragged_group,
                    deadline=deadline,
                    task_class="decode",
                )
            else:
                is_prefill = seq.tokens > 1 and tree_mask is None
                out_dev, t_dispatch_ms = await self.compute.submit(
                    PRIORITY_INFERENCE,
                    self._compute_step,
                    session,
                    handle,
                    hidden,
                    commit,
                    tree_mask,
                    depths,
                    commit_lens,
                    meta.get("prefix_skip"),
                    tail,
                    deadline=deadline,
                    task_class="prefill" if is_prefill else "decode",
                )
        except DeadlineExpired:
            self._note_deadline_expired(meta, "while queued")
            turns.dropped(step)
            return
        except Exception as e:
            turns.dropped(step)
            if await self._maybe_reply_session_lost(
                session, stream, meta, e
            ):
                return
            raise

        out, t_fetch_ms = await asyncio.to_thread(
            self._fetch_timed, out_dev, session, tail=tail
        )
        turns.fetched(step)
        if self.liar_p > 0 and self._liar_rng.random() < self.liar_p:
            # TEST HOOK: lie BEFORE the digest/serialization below, so the
            # reply is a well-formed frame whose digest matches the lie —
            # only the client's sanity gate / cross-replica audits can
            # catch it (exactly the threat model they exist for)
            out = self._liar_perturb(out)
            self.liar_steps += 1
        # the reply says what the client's timing row keeps: the step's
        # compute (dispatch + fetch)
        timing_meta = {"t_compute_ms": t_dispatch_ms + t_fetch_ms}
        session.n_steps += 1
        session.sum_tokens += seq.batch * seq.tokens
        session.sum_dispatch_ms += t_dispatch_ms
        session.sum_fetch_ms += t_fetch_ms
        if self.admission is not None:
            # fair-share accounting: charge processed tokens (batch x seq)
            # to the owning client so heavy clients accrue debt
            self.admission.note_tokens(
                session.client_id, seq.batch * seq.tokens
            )
        dump_dir = env.get("BBTPU_DUMP_ACTIVATIONS")
        if dump_dir:
            self._dump_activations(
                dump_dir, session, meta, await seq.take(0, seq.tokens), out
            )

        # mid-chain tree pruning: score this span's output with the MidLMHead
        # and return only surviving rows + their indices (reference
        # backend.py:395-410 last-block prune, :763-775 flatten kept rows)
        keep = None
        prune = meta.get("prune")
        if prune is not None and tree_mask is not None:
            # first use loads the checkpoint's lm_head OFF the event loop
            # (a synchronous multi-GB safetensors read would stall every
            # session and the liveness announce)
            await self._ensure_pruner_loaded()
            keep = self._prune_tree(out, prune)
            if env.get("BBTPU_PRUNER_TRAIN"):
                # retain this tree's mid hidden; the accept that names the
                # full model's true path arrives with the NEXT step and
                # becomes the head's training signal
                session.last_tree = (
                    np.asarray(out, dtype=np.float32),
                    np.asarray(prune["tokens"], dtype=np.int64),
                    np.asarray(prune["parents"], dtype=np.int32),
                )
            if keep is not None:
                gather = np.where(keep >= 0, keep, 0)
                out = np.stack(
                    [out[i][gather[i]] for i in range(out.shape[0])]
                )

        route = meta.get("route") or []
        reply = meta.get("reply", "tensor")
        if route:
            nxt = route[0]
            push_meta = {
                "session_id": nxt["session_id"],
                "step": meta.get("step"),
                "commit": commit,
                "tree": meta.get("tree", False),
                "reply": reply,
                "route": route[1:],
            }
            for key in ("mb", "mb_of", "rows", "commit_lens", "prefix_skip",
                        "reply_tail"):
                if meta.get(key) is not None:
                    push_meta[key] = meta[key]
            if meta.get("tree"):
                push_meta["depths"] = meta["depths"]
            if accept is not None:
                push_meta["accept"] = accept
            if deadline is not None:
                # each hop spends part of the budget; forward the REMAINDER
                # so a downstream span never computes for a client whose
                # overall step timeout already fired
                remaining = deadline - clock.monotonic()
                if remaining <= 0:
                    self._note_deadline_expired(meta, "before forwarding")
                    turns.dropped(step)
                    return
                push_meta["deadline_s"] = remaining
            push_tensors = [out]  # executor output is already wire dtype
            if tree_mask is not None:
                push_tensors.append(tree_mask.astype(np.uint8))
            conn = await self.peers.get(nxt["host"], nxt["port"])
            async with self.peers.limiter(nxt["host"], nxt["port"]).slot():
                await conn.push("rpc_push", push_meta, push_tensors)
            # ack our own client stream so it can detect this hop succeeded
            # (recorded AFTER the downstream push: a resume-retried step
            # must re-push only if the push itself never happened)
            resp = {"step": meta.get("step"), "ack": True, **timing_meta}
            self._record_reply(session, meta, resp, [])
            await stream.send(resp)
        elif reply == "ack":
            resp = {"step": meta.get("step"), "ack": True, **timing_meta}
            self._record_reply(session, meta, resp, [])
            await stream.send(resp)
        else:
            resp = {"step": meta.get("step"), **timing_meta}
            for key in ("mb", "rows"):
                if meta.get(key) is not None:
                    resp[key] = meta[key]
            if (
                seq.tokens > 1 and tree_mask is None and commit
                and commit_lens is None
            ):
                self.prefill_reply["n"] += 1
                self.prefill_reply["reply_bytes"] += int(out.nbytes)
                self.prefill_reply["full_bytes"] += (
                    int(out.nbytes) // int(out.shape[1]) * seq.tokens
                )
            if keep is not None:
                resp["keep"] = keep.tolist()
            if self.integrity:
                # digest over the exact array we serialize next: integrity
                # clients recompute it on the deserialized chunk, so ANY
                # in-flight byte corruption is caught deterministically
                from bloombee_tpu.kv.prefix import out_digest

                resp["out_digest"] = out_digest(out)
                self.out_digests_sent += 1
            # record-then-send: the KV commit already happened at dispatch,
            # so this reply is the step's only at-most-once fence
            self._record_reply(session, meta, resp, [out])
            await stream.send(resp, [out])
        turns.replied(step, stream.write_ns)

    async def _run_decode_n(
        self, session: _Session, stream: Stream, meta: dict, tensors: list
    ) -> None:
        """Server-side multi-step greedy decode: one RPC returns N token
        ids, amortizing the client<->server round trip that floors served
        throughput. Three flavors, picked per request:

        - FUSED (route empty, dense un-sharded whole-model span): one
          jitted lax.scan runs embed -> span -> head -> select N times
          entirely on device (runtime/decode_loop.py) — one host<->device
          round trip for N tokens.
        - LOCAL STEPPED (route empty, whole-model span that the scan can't
          fuse: TP-sharded / quantized KV / weight-offloaded / hetero /
          sparse): the same loop driven per-step from the host through the
          ordinary executor paths. Still ONE client RTT per N tokens;
          per-step device round trips are local and cheap.
        - CHAINED (route non-empty): this span embeds + computes block 0's
          prefix and pushes hidden downstream; the LAST span applies
          norm+head+select and pushes the next id back here; this
          coordinator replies [B, n] ids after n rounds. The client RTT —
          the expensive wide-area hop — is paid once per N tokens; the
          per-token hops ride server-to-server links. This beats the
          reference's per-token client loop for the multi-server topology
          (remote_generation.py:286-386).

        An ineligible server replies decode_n_unsupported so the client
        falls back to per-step decoding without banning the peer."""
        n = int(meta["decode_n"])
        route = meta.get("route") or []
        decline = None
        if not (1 <= n <= self.decode_n_max):
            # unvalidated n would let one RPC eagerly commit n write_slots
            # per row (trivial OutOfPages) — clamp before any allocation
            decline = (
                f"decode_n={n} outside the server's accepted range "
                f"[1, {self.decode_n_max}]"
            )
        if decline is None:
            # every flavor embeds ids at this span, so the session must
            # enter the model at block 0 and the embed table must exist
            rel = session.layers or (0, self.end_block - self.start_block)
            if self.start_block + rel[0] != 0:
                decline = "session does not enter the model at block 0"
            elif (
                not route
                and self.start_block + rel[1] != self.spec.num_hidden_layers
            ):
                decline = (
                    "single-span decode_n needs the whole model on this "
                    "server (send a route for chained decode)"
                )
        if decline is None:
            await self._ensure_client_params()
            if self._client_params is None:
                decline = "server has no embed/norm/lm_head params"
        if decline is None:
            want_dt = meta.get("head_dtype")
            have_dt = str(self._client_params["lm_head"].dtype)
            if want_dt is not None and want_dt != have_dt:
                # client loaded its head with a dtype override; different
                # weights would yield different logits than its per-step path
                decline = (
                    f"head dtype mismatch: client {want_dt} vs server "
                    f"{have_dt}"
                )
        if decline is not None:
            # the reason rides the reply so an operator can see WHY a
            # client fell back to per-step decoding (a silent decline loses
            # the whole feature invisibly — round-3 verdict)
            logger.warning("decode_n declined: %s", decline)
            await stream.send(
                {
                    "step": meta.get("step"),
                    "decode_n_unsupported": True,
                    "reason": decline,
                }
            )
            return
        session.turns.arrive(
            meta.get("step"), "decode", meta.get(turn.META_KEY)
        )
        if route or self._decode_n_ineligible(session) is not None:
            await self._run_decode_n_stepped(
                session, stream, meta, tensors, route
            )
            return
        await self._run_decode_n_fused(session, stream, meta, tensors)

    def _fetch_timed(self, out_dev, session: _Session, fetch=None,
                     tail: int | None = None):
        """Runs on a fetch thread: the d2h wait as the span `bbtpu.fetch`,
        whose duration is the fetch's part of the wire's t_compute_ms (and
        the [TIMING_TABLE]'s mean_fetch_ms). `step` is the session's
        count of steps served so far, the same on every span of one wire
        step. `tail`: only the last `tail` positions of each sequence are
        copied to the host: cut on the device, here and not on the compute
        thread, and the earlier chunks of a chunked prefill are never
        fetched at all."""
        with jitwatch.stopwatch(
            "bbtpu.fetch", session=session.id, step=session.n_steps
        ) as sw:
            if tail is not None:
                out_dev = _tail_rows(out_dev, tail)
            out = (fetch or self.executor.fetch)(out_dev)
        return out, sw.ms

    async def _run_decode_n_fused(
        self, session: _Session, stream: Stream, meta: dict, tensors: list
    ) -> None:
        n = int(meta["decode_n"])
        ids = np.asarray(tensors[0]).reshape(-1)
        if ids.shape[0] != session.handle.batch_size:
            raise ValueError(
                f"decode_n ids carry batch {ids.shape[0]} != "
                f"{session.handle.batch_size} cache rows"
            )
        eos = meta.get("eos_token_id")
        finished = (
            np.asarray(meta["finished"], dtype=bool)
            if meta.get("finished") is not None else None
        )

        def _dispatch():
            if not self.manager.epoch_valid(session.handle):
                raise SessionKVLost(
                    "server KV arena was rebuilt; session cache lost — "
                    "replay"
                )
            session.last_step_at = clock.monotonic()
            with jitwatch.stopwatch(
                "bbtpu.dispatch", session=session.id, step=session.n_steps
            ) as sw:
                out = self.executor.decode_n(
                    session.handle, ids, n, self._client_params,
                    eos_token_id=eos, finished=finished,
                    adapter=session.adapter,
                )
            return out, sw.ms

        try:
            out_dev, t_dispatch_ms = await self.compute.submit(
                PRIORITY_INFERENCE, _dispatch,
                deadline=self._local_deadline(meta),
            )
        except DeadlineExpired:
            self._note_deadline_expired(meta, "while queued")
            return
        except Exception as e:
            if await self._maybe_reply_session_lost(
                session, stream, meta, e
            ):
                return
            raise
        toks, t_fetch_ms = await asyncio.to_thread(
            self._fetch_timed, out_dev, session,
            lambda dev: np.asarray(dev, dtype=np.int32),
        )
        session.turns.fetched(meta.get("step"))
        session.n_steps += n
        session.sum_tokens += int(ids.shape[0]) * n
        session.sum_dispatch_ms += t_dispatch_ms
        session.sum_fetch_ms += t_fetch_ms
        if self.admission is not None:
            self.admission.note_tokens(
                session.client_id, int(ids.shape[0]) * n
            )
        resp = {
            "step": meta.get("step"),
            "t_compute_ms": t_dispatch_ms + t_fetch_ms,
        }
        # the fused loop committed n KV slots per row: record before
        # delivery so a post-resume retry resends these exact tokens
        # instead of decoding (and committing) n more
        self._record_reply(session, meta, resp, [toks])
        await stream.send(resp, [toks])
        session.turns.replied(meta.get("step"), stream.write_ns)

    async def _run_decode_n_stepped(
        self, session: _Session, stream: Stream, meta: dict, tensors: list,
        route: list,
    ) -> None:
        """Host-driven decode_n loop (the LOCAL STEPPED and CHAINED flavors
        of _run_decode_n). Each round: embed the current ids, run this
        span's ordinary per-step executor path, then either apply the head
        locally (empty route) or push hidden downstream and await the tail
        span's selected ids. EOS masking happens HERE, identically to the
        client's per-step loop (_greedy_next), so outputs are token-exact
        vs per-step decoding on the same backend.

        Failure contract: once any KV was committed this RPC, spans hold
        ragged extra tokens — the decline carries dirty=True so the client
        rebuilds-and-replays before falling back (clean by construction)."""

        n = int(meta["decode_n"])
        ids = np.asarray(tensors[0]).reshape(-1).astype(np.int64)
        if ids.shape[0] != session.handle.batch_size:
            raise ValueError(
                f"decode_n ids carry batch {ids.shape[0]} != "
                f"{session.handle.batch_size} cache rows"
            )
        b = int(ids.shape[0])
        eos = meta.get("eos_token_id")
        finished = (
            np.asarray(meta["finished"], dtype=bool)
            if meta.get("finished") is not None
            else np.zeros((b,), dtype=bool)
        )
        cid = uuid.uuid4().hex[:12]
        # drop stale control messages from an earlier timed-out chain
        while not session.chain_inbox.empty():
            session.chain_inbox.get_nowait()
        toks = np.zeros((b, n), dtype=np.int32)
        committed = 0
        t_start = clock.perf_counter()
        t_dispatch_sum = 0.0
        # total budget for the WHOLE chain RPC: one cold-compile allowance
        # plus 1s/token. Deliberately under the client's recv budget
        # (2*step_timeout + n): the server must always answer — a typed
        # transient decline beats the client timing out and BANNING a
        # coordinator that was making slow-but-legal progress. A retry
        # after replay hits warm compile caches and converges.
        t_deadline = clock.monotonic() + self.chain_step_timeout + float(n)
        budget = meta.get("deadline_s")
        if budget is not None:
            # never outlive the CLIENT's budget either: past it the reply
            # lands on a closed ear and every further token is waste
            t_deadline = min(t_deadline, clock.monotonic() + float(budget))
        try:
            for i in range(n):
                if clock.monotonic() > t_deadline:
                    raise _ChainError(
                        f"chain exceeded its {self.chain_step_timeout:.0f}s"
                        f"+{n}s budget after {i}/{n} tokens"
                    )
                def _dispatch(ids_now=ids):
                    if not self.manager.epoch_valid(session.handle):
                        raise SessionKVLost(
                            "server KV arena was rebuilt; session cache "
                            "lost — replay"
                        )
                    session.last_step_at = clock.monotonic()
                    with jitwatch.stopwatch(
                        "bbtpu.dispatch", session=session.id,
                        step=session.n_steps,
                    ) as sw:
                        h = self._embed_ids(ids_now)
                        out = self.executor.decode(
                            session.handle,
                            h.astype(self.executor.transfer_dtype),
                            commit=True, layers=session.layers,
                            fetch=False, adapter=session.adapter,
                        )
                    return out, sw.ms
                out_dev, dt_ms = await self.compute.submit(
                    PRIORITY_INFERENCE, _dispatch
                )
                committed += 1
                t_dispatch_sum += dt_ms
                if route:
                    out = await asyncio.to_thread(self.executor.fetch, out_dev)
                    chain = {
                        "origin": {
                            "host": self.public_host,
                            "port": self.port,
                            "session_id": session.id,
                        },
                        "cid": cid,
                        "i": i,
                    }
                    await self._push_hop(
                        route, chain, meta.get("step"),
                        meta.get("head_dtype"), out,
                        deadline_s=t_deadline - clock.monotonic(),
                    )
                    nxt = await self._await_chain_ids(
                        session, cid, i, t_deadline
                    )
                else:
                    nxt = await self.compute.submit(
                        PRIORITY_INFERENCE, self._select_head, out_dev
                    )
                # EOS masking: one definition with the client's per-step
                # loop semantics (client/model.py _mask_finished)
                if eos is not None:
                    nxt = np.where(finished, eos, nxt)
                    finished = finished | (nxt == eos)
                toks[:, i] = nxt
                ids = nxt.astype(np.int64)
        except Exception as e:
            # committed KV the client was never told about makes a parked
            # resume unsound (token histories would diverge): if the dirty
            # decline below cannot be delivered, the park path sees
            # kv_dirty and falls back to full replay. Delivering it
            # clears the flag — the client then rebuilds explicitly.
            session.kv_dirty = committed > 0
            if await self._maybe_reply_session_lost(
                session, stream, meta, e
            ):
                session.kv_dirty = False
                return
            logger.warning(
                "chained decode_n failed after %d/%d committed steps: %s",
                committed, n, e,
            )
            await stream.send(
                {
                    "step": meta.get("step"),
                    "decode_n_unsupported": True,
                    "reason": f"{type(e).__name__}: {e}",
                    # committed KV ran ahead of the client's history: the
                    # client must rebuild-and-replay before continuing
                    "dirty": committed > 0,
                    # transient route failures (a span died) are worth a
                    # rebuild-and-RETRY of chained decode; capability
                    # declines are not
                    "transient": not getattr(e, "permanent", False),
                }
            )
            # the decline reached the client: it rebuilds-and-replays, so
            # the ragged KV no longer blocks a later park
            session.kv_dirty = False
            return
        session.turns.fetched(meta.get("step"))
        total_ms = (clock.perf_counter() - t_start) * 1000.0
        session.n_steps += n
        session.sum_tokens += b * n
        session.sum_dispatch_ms += t_dispatch_sum
        session.sum_fetch_ms += max(total_ms - t_dispatch_sum, 0.0)
        if self.admission is not None:
            self.admission.note_tokens(session.client_id, b * n)
        resp = {
            "step": meta.get("step"),
            "t_compute_ms": total_ms,
        }
        self._record_reply(session, meta, resp, [toks])
        await stream.send(resp, [toks])
        session.turns.replied(meta.get("step"), stream.write_ns)

    async def _push_hop(
        self, route: list, chain: dict, step, head_dtype, out,
        deadline_s: float | None = None,
    ) -> None:
        """Push one chained-decode hidden state to the next hop (shared by
        the coordinator and middle spans — the hop wire format lives in
        exactly one place)."""
        nxt_hop = route[0]
        push_meta = {
            "session_id": nxt_hop["session_id"],
            "step": step,
            "commit": True,
            "chain": chain,
            "route": route[1:],
        }
        if head_dtype is not None:
            push_meta["head_dtype"] = head_dtype
        if deadline_s is not None:
            push_meta["deadline_s"] = deadline_s
        conn = await self.peers.get(nxt_hop["host"], nxt_hop["port"])
        async with self.peers.limiter(
            nxt_hop["host"], nxt_hop["port"]
        ).slot():
            await conn.push("rpc_push", push_meta, [out])

    async def _await_chain_ids(
        self, session: _Session, cid: str, i: int, t_deadline: float
    ) -> np.ndarray:
        """Wait for the tail span's selected ids for chain step (cid, i);
        stale messages from earlier chains are dropped, errors raise.
        Bounded by the chain's overall deadline so the RPC always answers
        inside the client's recv budget."""

        while True:
            remaining = t_deadline - clock.monotonic()
            if remaining <= 0:
                raise asyncio.TimeoutError("chain deadline exhausted")
            msg_meta, msg_tensors = await asyncio.wait_for(
                session.chain_inbox.get(), remaining
            )
            if msg_meta.get("cid") != cid:
                continue  # stale chain
            if msg_meta.get("chain_error"):
                raise _ChainError(
                    msg_meta["chain_error"],
                    permanent=bool(msg_meta.get("permanent")),
                )
            if int(msg_meta.get("i", -1)) != i:
                raise _ChainError(
                    f"chain step mismatch: got {msg_meta.get('i')}, "
                    f"expected {i}"
                )
            return np.asarray(msg_tensors[0]).reshape(-1)

    async def _run_chain_step(
        self, session: _Session, meta: dict, tensors: list
    ) -> None:
        """One pushed hop of a chained decode_n on a MIDDLE or TAIL span:
        run the span step; middles push hidden onward, the tail applies
        norm+head+select and pushes the ids back to the coordinator. All
        failures travel to the coordinator as chain_error pushes — never
        onto this span's own client stream (the client is not reading it
        mid-decode_n)."""

        chain = meta["chain"]
        origin = chain["origin"]
        deadline = self._local_deadline(meta)
        try:
            hidden = np.asarray(tensors[0])

            def _dispatch():
                if not self.manager.epoch_valid(session.handle):
                    raise SessionKVLost(
                        "server KV arena was rebuilt; session cache lost "
                        "— replay"
                    )
                session.last_step_at = clock.monotonic()
                return self.executor.decode(
                    session.handle, hidden, commit=True,
                    layers=session.layers, fetch=False,
                    adapter=session.adapter,
                )

            route = meta.get("route") or []
            if not route:
                # tail role: eligibility must be checked before committing
                # anything downstream of a doomed chain is pointless — but
                # the coordinator already committed this round regardless,
                # so dirty replay handles either ordering; check first to
                # fail the cheapest way
                err = await self._chain_tail_ineligible(meta)
                if err is not None:
                    raise _ChainError(err, permanent=True)
            try:
                out_dev = await self.compute.submit(
                    PRIORITY_INFERENCE, _dispatch, deadline=deadline
                )
            except DeadlineExpired:
                # the coordinator's chain deadline already fired; it has
                # answered its client, so a chain_error would land on a
                # stale cid anyway — count the drop and stop quietly
                self._note_deadline_expired(meta, "in chain hop queue")
                return
            session.n_steps += 1
            session.sum_tokens += int(hidden.shape[0])
            if route:
                out = await asyncio.to_thread(self.executor.fetch, out_dev)
                remaining = None
                if deadline is not None:
                    remaining = deadline - clock.monotonic()
                    if remaining <= 0:
                        self._note_deadline_expired(
                            meta, "before chain forward"
                        )
                        return
                await self._push_hop(
                    route, chain, meta.get("step"), meta.get("head_dtype"),
                    out, deadline_s=remaining,
                )
            else:
                nxt = await self.compute.submit(
                    PRIORITY_INFERENCE, self._select_head, out_dev
                )
                conn = await self.peers.get(origin["host"], origin["port"])
                async with self.peers.limiter(
                    origin["host"], origin["port"]
                ).slot():
                    await conn.push(
                        "rpc_push",
                        {
                            "session_id": origin["session_id"],
                            "chain_ids": True,
                            "cid": chain.get("cid"),
                            "i": chain.get("i"),
                        },
                        [nxt.astype(np.int32)],
                    )
        except Exception as e:
            logger.warning("chain step failed: %s", e)
            try:
                conn = await self.peers.get(origin["host"], origin["port"])
                await conn.push(
                    "rpc_push",
                    {
                        "session_id": origin["session_id"],
                        "chain_error": f"{type(e).__name__}: {e}",
                        "permanent": bool(getattr(e, "permanent", False)),
                        "cid": chain.get("cid"),
                    },
                    [],
                )
            except Exception:
                pass  # coordinator's timeout covers a dead push path

    async def _chain_tail_ineligible(self, meta: dict) -> str | None:
        """Why this span cannot play the TAIL role (apply norm+head) of a
        chained decode_n; None when it can."""
        if self.end_block != self.spec.num_hidden_layers:
            return (
                f"span ends at block {self.end_block}, not the model's "
                f"last block {self.spec.num_hidden_layers}"
            )
        await self._ensure_client_params()
        if self._client_params is None:
            return "tail has no norm/lm_head params"
        want_dt = meta.get("head_dtype")
        have_dt = str(self._client_params["lm_head"].dtype)
        if want_dt is not None and want_dt != have_dt:
            return (
                f"head dtype mismatch: client {want_dt} vs tail {have_dt}"
            )
        return None

    def _embed_ids(self, ids: np.ndarray) -> np.ndarray:
        """ids [B] -> hidden [B, 1, D] fp32, numerically identical to the
        client's embed (client/model.py embed: same impl, same params
        loaded the same way, fp32 host result)."""
        from bloombee_tpu.models.head import embed_impl

        if not hasattr(self, "_embed_jit"):
            import functools

            import jax

            self._embed_jit = functools.partial(
                jax.jit,
                static_argnames=(
                    "embedding_multiplier", "has_embed_norm", "eps"
                ),
            )(embed_impl)
        h = self._embed_jit(
            self._client_params,
            jnp.asarray(np.asarray(ids, np.int64)[:, None]),
            self.spec.embedding_multiplier,
            "embed_norm" in self._client_params,
            self.spec.rms_norm_eps,
        )
        return np.asarray(h, dtype=np.float32)

    def _select_head(self, out_dev) -> np.ndarray:
        """Span output [B, 1, D] -> greedy next ids [B], via the same
        norm+head math and the same wire-dtype->fp32 cast as the client's
        per-step path (fetch as transfer dtype, cast fp32, norm+head,
        first-index argmax) so chained decode stays token-exact."""
        from bloombee_tpu.models.head import norm_head_impl

        if not hasattr(self, "_head_jit"):
            import functools

            import jax

            self._head_jit = functools.partial(
                jax.jit,
                static_argnames=("eps", "soft_cap", "norm_type"),
            )(norm_head_impl)
        out = np.asarray(out_dev).astype(self.executor.transfer_dtype)
        logits = self._head_jit(
            self._client_params,
            jnp.asarray(out[:, -1].astype(np.float32)),
            self.spec.rms_norm_eps,
            self.spec.logits_soft_cap,
            self.spec.norm_type,
        )
        return np.argmax(np.asarray(logits), axis=-1).astype(np.int64)

    def _decode_n_ineligible(self, session: _Session | None = None):
        """The session-independent (and, given a session, session-specific)
        reasons this server cannot run the FUSED decode_n scan (the
        host-driven stepped loop has weaker requirements — see
        _run_decode_n). Returns None when eligible, else a human-readable
        reason (surfaced in rpc_info/health as decode_n_decline)."""
        if session is not None and session.layers is not None:
            return "session routes a sub-span, not the whole model"
        # the loop applies the LM head after THIS span, so the span must
        # be the whole model, not a prefix
        if not (
            self.start_block == 0
            and self.end_block == self.spec.num_hidden_layers
        ):
            return (
                f"span [{self.start_block},{self.end_block}) is not the "
                f"whole model"
            )
        if self.spec.heterogeneous:
            return "heterogeneous head_dim span"
        if self.spec.recurrent is not None:
            return "recurrent state beside the KV arena"
        if self.spec.mla is not None:
            return "latent attention (the decode loop attends K and V pages)"
        if self.spec.moe_held is not None:
            return ("a share of the experts held (the span step hands its "
                    "reach counters out beside the arena)")
        if self.executor.host_layers:
            return "span has weight-offloaded layers"
        if self.executor.mesh is not None:
            return "span is TP-sharded"
        if self.manager.quant is not None:
            return "quantized KV arena"
        # sparse decode recomputes k per step on the per-step path; a
        # frozen k inside the scan would break token-exactness
        if self.executor.attn_sparsity < 1.0:
            return "sparse decode attention"
        if self._client_params_unavailable or (
            self._client_params is None and self.model_dir is None
        ):
            return "server has no embed/norm/lm_head params"
        return None

    async def _ensure_client_params(self) -> None:
        if (
            self._client_params is not None
            or self._client_params_unavailable
        ):
            return
        if self.model_dir is None:
            self._client_params_unavailable = True
            return
        if self._client_params_lock is None:
            self._client_params_lock = lockwatch.async_lock(
                "server.client_params"
            )
        async with self._client_params_lock:
            if (
                self._client_params is None
                and not self._client_params_unavailable
            ):
                # multi-GB safetensors read: off the event loop
                await asyncio.to_thread(self._load_client_params)

    def _load_client_params(self) -> None:
        try:
            from bloombee_tpu.models.checkpoint import load_client_params

            # checkpoint-native dtype: the client loads the same tensors the
            # same way, keeping the server loop's logits identical to the
            # client's per-step head on the same backend
            self._client_params = load_client_params(self.model_dir)
        except Exception as e:
            logger.warning("decode_n unavailable (client params): %s", e)
            self._client_params_unavailable = True

    # ------------------------------------------- stall-free chunked prefill
    def _chunk_budget(self) -> int:
        """Per-prefill chunk token budget: the server ctor value wins,
        else BBTPU_PREFILL_CHUNK; 0 disables (monolithic prefill)."""
        if self.prefill_chunk is not None:
            return int(self.prefill_chunk)
        return int(env.get("BBTPU_PREFILL_CHUNK"))

    def _chunk_spans(
        self, tokens: int, commit, tree_mask, commit_lens
    ) -> list[tuple[int, int]] | None:
        """[start, end) chunk spans for this step, or None when the step
        must stay one monolithic compute task. Only plain committing
        prefills chunk: tree steps aren't prefills, speculative
        (commit=False) and ragged-replay steps own bespoke table side
        effects, and sp-mesh servers hand long prompts to ring attention
        (which needs the whole prompt in one call). A suffix prefill after
        a prefix-cache adoption chunks too — the adoption settles before
        the first chunk."""
        budget = self._chunk_budget()
        if (
            budget <= 0
            or tokens <= 1
            or tree_mask is not None
            or not commit
            or commit_lens is not None
            or self.executor.sp_mesh is not None
        ):
            return None
        spans = plan_prefill_chunks(
            tokens, budget, cap=self.executor.max_chunk_tokens
        )
        return spans if len(spans) > 1 else None

    async def _run_chunked_prefill(
        self, session: _Session, handle, seq: _StepRows, spans, deadline,
        prefix_skip=None, tail: int | None = None,
    ):
        """Drive one prefill as a stream of resumable chunk tasks. Each
        chunk is its own compute-queue submission at an AGING chunk
        priority (fresh streams yield to queued decode steps; an old
        stream reaches decode priority, so it cannot starve), with the
        client deadline re-checked both between chunks (here) and at each
        chunk's queue pop (the submit's deadline=). A chunk is submitted
        once `seq` holds its rows: a prompt sent in parts is planned on its
        whole length and computed as its parts arrive, and a stream that
        closes or a deadline that passes between parts aborts the step
        like a failed chunk.

        Chunks write their KV speculatively; the LAST chunk's compute-
        thread slot commits the whole prompt (same pattern as the batched
        decode path), so any abort — deadline expiry, a failed chunk, a
        lost arena — rolls back and frees every partial page. Returns
        (per-chunk lazy outputs, total dispatch ms); `executor.fetch`
        concatenates the chunk list off-queue. `tail`: the client reads
        only the step's last `tail` rows, so each chunk is told how many of
        ITS rows those are (a span whose last layers write no cache runs
        them on those rows alone: runtime/sambay.py)."""
        from bloombee_tpu.runtime.executor import chunk_reply_rows

        stream_t0 = clock.monotonic()
        outs: list = []
        total_ms = 0.0
        last = len(spans) - 1
        self._chunking_sessions += 1
        try:
            for idx, (s, e) in enumerate(spans):
                if self._deadline_passed(deadline):
                    raise DeadlineExpired(
                        "client deadline expired between prefill chunks"
                    )
                hidden = await seq.take(s, e)
                reply_rows = chunk_reply_rows(tail, s, e, seq.tokens)
                # a chunk of SEVERAL sequences with recurrent state (or a
                # latent cache) goes alone: a ragged pack runs the mixer's
                # (latent attention's) chunk form on one
                if self.mixed_batch and not (
                    self.executor.one_chunk_a_pack and seq.batch > 1
                ):
                    # batchable chunk: the worker may fuse this chunk with
                    # queued decode steps — and, with --spec-batch also
                    # on, tree-verify rows — into one ragged dispatch (and
                    # a popped decode may likewise absorb this chunk)
                    out, dt_ms = await self.compute.submit_group(
                        aged_chunk_priority(stream_t0),
                        ("chunkm", session.layers, session.adapter,
                         str(hidden.dtype), e - s),
                        _ChunkMember(
                            session, handle, hidden,
                            idx == 0, idx == last, prefix_skip, reply_rows,
                        ),
                        self._compute_ragged_group,
                        deadline=deadline,
                        task_class="prefill",
                    )
                else:
                    out, dt_ms = await self.compute.submit(
                        aged_chunk_priority(stream_t0),
                        self._compute_prefill_chunk,
                        session,
                        handle,
                        hidden,
                        idx == 0,
                        idx == last,
                        prefix_skip,
                        reply_rows,
                        deadline=deadline,
                        task_class="prefill",
                    )
                outs.append(out)
                total_ms += dt_ms
                self.prefill_chunks += 1
                self.prefill_chunk_tokens += seq.batch * (e - s)
        except BaseException:
            # free the partial prefill's speculative pages — a session
            # holding pages for a prompt nobody will finish is a leak
            # until close; deadline-driven aborts especially must release
            # capacity NOW (that is the point of aborting)
            await self._abort_chunked_prefill(handle)
            raise
        finally:
            self._chunking_sessions -= 1
        return outs, total_ms

    async def _abort_chunked_prefill(self, handle) -> None:
        """Roll the handle back to its committed state, freeing the
        aborted prefill's speculative pages. Runs on the compute thread —
        the only thread that mutates the paged table — and is epoch-
        guarded: an arena rebuild already invalidated (and freed) the
        session's table state."""
        try:
            await self.compute.submit(
                PRIORITY_INFERENCE, self._rollback_if_valid, handle
            )
        except Exception:
            logger.warning(
                "chunked-prefill rollback failed; pages free at session "
                "close instead", exc_info=True,
            )

    def _rollback_if_valid(self, handle) -> None:
        if self.manager.epoch_valid(handle):
            self.manager.rollback(handle)

    def _compute_prefill_chunk(
        self, session: _Session, handle, hidden, first, last,
        prefix_skip=None, reply_rows=None,
    ):
        """Runs on the compute thread: one chunk of a chunked prefill.
        Same contract as _compute_step (dispatch only; fetch happens
        off-queue) with the chunk-stream twists: the FIRST chunk settles
        a pending prefix-cache adoption, every chunk writes speculatively,
        and the LAST chunk commits the whole prompt."""

        if not self.manager.epoch_valid(handle):
            raise SessionKVLost(
                "server KV arena was rebuilt; session cache lost — replay"
            )
        session.last_step_at = clock.monotonic()
        with jitwatch.stopwatch(
            "bbtpu.dispatch", session=session.id, step=session.n_steps
        ) as sw:
            if first and self.manager.has_adopted(handle):
                # settle the probe adoption before the suffix's first chunk
                # (same semantics as _compute_step's settle)
                self.manager.ensure_resident(handle)
                self.manager.trim_adopted(handle, int(prefix_skip or 0))
            session.adoption_settled = True
            # recovery owner: _run_chunked_prefill's except BaseException
            # -> _abort_chunked_prefill (epoch-guarded rollback); this
            # helper runs only inside that stream driver
            out = self.executor.prefill_chunk(  # bbtpu: noqa[BB001]
                handle, hidden, commit=False, layers=session.layers,
                fetch=False, adapter=session.adapter, reply_rows=reply_rows,
            )
            if last:
                with jitwatch.span("bbtpu.commit"):
                    self.manager.commit(handle)
            self.step_dispatches += 1
            self.step_tokens += int(hidden.shape[0]) * int(hidden.shape[1])
        dt_ms = sw.ms
        if env.log_channel_enabled("timing"):
            logger.info(
                "[timing] session=%s prefill chunk tokens=%d%s "
                "dispatch_ms=%.2f",
                session.id, hidden.shape[1],
                " (final)" if last else "", dt_ms,
            )
        return out, dt_ms

    def _compute_step(
        self, session: _Session, handle, hidden, commit, tree_mask,
        depths=None, commit_lens=None, prefix_skip=None, reply_rows=None,
    ):
        """Runs on the compute thread: plan packing + async device dispatch
        only (the d2h fetch happens off-queue in _run_step). The dispatch
        time is the serialized cost per step — the unit that bounds server
        throughput (reference [TIMING_TABLE] decomposition,
        handler.py:1276-1605)."""

        if not self.manager.epoch_valid(handle):
            # the arena was rebuilt after a kernel failure and this
            # session's KV was device-resident (not parked): its table
            # state describes KV that no longer exists — fail loudly with
            # the typed error so the client replays without banning us
            # (a silent step would compute on a zeroed context)
            raise SessionKVLost(
                "server KV arena was rebuilt; session cache lost — replay"
            )
        session.last_step_at = clock.monotonic()
        with jitwatch.stopwatch(
            "bbtpu.dispatch", session=session.id, step=session.n_steps
        ) as sw:
            if self.manager.has_adopted(handle):
                # settle an outstanding probe adoption: unpark first so the
                # trim acts on live lengths, then shrink each row's adopted
                # prefix to the chain-wide skip the client actually uses. A
                # step that never declares prefix_skip drops the adoption
                # entirely (skip 0) — the safe interpretation of a client
                # that changed its mind (or a stale retry).
                self.manager.ensure_resident(handle)
                self.manager.trim_adopted(
                    handle, int(prefix_skip or 0)
                )
            session.adoption_settled = True
            if commit_lens is not None and self.spec.recurrent is not None:
                full = self.manager.context_lens(handle) + hidden.shape[1]
                if any(int(c) < int(f) for c, f in zip(commit_lens, full)):
                    # a ragged replay writes a padded rectangle and commits
                    # each row shorter: the padding would have fed the state
                    self.manager._refuse("ragged replay commit")
                    raise ValueError(
                        "ragged replay (rows of different lengths in one "
                        "step) unsupported: a recurrent state cannot be cut "
                        "back to a row's own length — replay row by row"
                    )
            if hidden.shape[1] > 1 and tree_mask is None:
                out = self.executor.prefill(
                    handle, hidden, commit=commit, layers=session.layers,
                    fetch=False, adapter=session.adapter,
                    reply_rows=reply_rows,
                )
            else:
                if hidden.shape[1] == 1 and self._chunking_sessions:
                    # a decode step ran while some session's chunked
                    # prefill was mid-stream: the stall this scheduler
                    # removes
                    self.decode_steps_interleaved += 1
                out = self.executor.decode(
                    handle, hidden, commit=commit, tree_mask=tree_mask,
                    layers=session.layers, depths=depths, fetch=False,
                    adapter=session.adapter,
                )
            if commit_lens is not None:
                # ragged explicit-length commit only happens on an
                # id-session failover replay: account the replayed tokens
                # so the chaos tests can assert the replication bound from
                # rpc_info
                with jitwatch.span("bbtpu.commit"):
                    self.manager.commit(handle, lengths=commit_lens)
                self.failover_replayed_tokens += int(
                    hidden.shape[0] * hidden.shape[1]
                )
            self.step_dispatches += 1
            self.step_tokens += int(hidden.shape[0]) * int(hidden.shape[1])
        dt_ms = sw.ms
        if env.log_channel_enabled("timing"):
            logger.info(
                "[timing] session=%s tokens=%d dispatch_ms=%.2f",
                session.id, hidden.shape[1], dt_ms,
            )
        return out, dt_ms

    def _batchable(
        self, commit, hidden, tree_mask, depths, commit_lens,
        prefix_skip=None,
    ) -> bool:
        """Whether this step may share a merged dispatch: plain committing
        single-token decode only. Tree-verify steps, prefills, ragged
        replays and speculative (commit=False) steps keep their own
        compute task — their table side effects are per-session. A step
        declaring prefix_skip is a suffix PREFILL even at one token (a
        warm prefix hit can shrink the uncached tail that far) and must
        settle its adoption on the solo path. A draining server also
        stops coalescing: its sessions are winding down and the simple
        per-step path keeps the drain predictable."""
        return (
            self.max_batch > 1
            and hidden.shape[1] == 1
            and tree_mask is None
            and depths is None
            and commit_lens is None
            and prefix_skip is None
            and commit
            and not self._draining
        )

    def _compute_step_group(self, members: list[_BatchMember]) -> list:
        """Runs on the compute thread: execute a group of compatible
        single-token decode steps as ONE merged span dispatch. Returns one
        outcome per member — (lazy out rows, dispatch_ms) or an Exception
        instance, which the queue raises only at that member's caller.

        Members whose KV can't safely join the merged dispatch (stale
        epoch, host-parked) fall out to the solo path so their failure
        modes stay their own; if the merged dispatch itself fails, its
        speculative writes roll back and the group replays row-by-row, so
        one member's fault never sinks its co-batched peers."""
        results: list = [None] * len(members)
        ready: list[int] = []
        # the members' hygiene (a solo step inside it is a `bbtpu.dispatch`
        # of its own: the span's self time is the checks alone)
        with jitwatch.span("bbtpu.group", members=len(members)):
            for i, m in enumerate(members):
                if not self.manager.epoch_valid(m.handle):
                    results[i] = SessionKVLost(
                        "server KV arena was rebuilt; session cache lost — "
                        "replay"
                    )
                elif (self.manager.has_parked(m.handle)
                      or (not m.session.adoption_settled
                          and self.manager.has_adopted(m.handle))):
                    # unparking inside a merged dispatch could OutOfPages
                    # the whole batch; alone, only this member wears the
                    # failure. An UNSETTLED prefix adoption likewise needs
                    # the solo path (_compute_step trims it to the declared
                    # skip before computing) — but only until its first
                    # step settles it: a settled adopted session batches
                    # like any other instead of soloing for the rest of its
                    # life
                    results[i] = self._solo_member_step(m)
                else:
                    ready.append(i)
        if len(ready) == 1:
            results[ready[0]] = self._solo_member_step(members[ready[0]])
        elif ready:
            group = [members[i] for i in ready]
            try:
                outs = self._dispatch_batched(group)
            except Exception as e:
                logger.warning(
                    "batched decode of %d sessions failed (%r); "
                    "replaying row-by-row", len(group), e,
                )
                outs = [self._solo_member_step(m) for m in group]
            for i, out in zip(ready, outs):
                results[i] = out
        return results

    def _solo_member_step(self, m: _BatchMember):
        self.batch_solo_steps += 1
        ledger.recovery("server.rollback_solo_replay")
        try:
            return self._compute_step(
                m.session, m.handle, m.hidden, True, None
            )
        except Exception as e:
            return e

    def _dispatch_batched(self, group: list[_BatchMember]) -> list:
        """One row-stacked span dispatch for >= 2 sessions' decode steps.
        KV writes go in speculatively and commit only after the dispatch
        succeeds, so a failure rolls the whole group's tables back to the
        pre-step state and the row-by-row replay appends no ghost tokens."""

        with jitwatch.stopwatch(
            "bbtpu.dispatch", members=len(group),
            sessions="+".join(m.session.id for m in group),
        ) as sw:
            now = clock.monotonic()
            for m in group:
                m.session.last_step_at = now
            handles = [m.handle for m in group]
            try:
                out, combined = self.executor.decode_group(
                    handles,
                    [m.hidden for m in group],
                    layers=group[0].session.layers,
                    adapter=group[0].session.adapter,
                )
            except Exception:
                with jitwatch.span("bbtpu.commit"):
                    self.manager.rollback(
                        self.manager.combine_handles(handles)
                    )
                raise
            with jitwatch.span("bbtpu.commit"):
                self.manager.commit(combined)
        dt_ms = sw.ms
        self.batch_dispatches += 1
        self.batched_steps += len(group)
        self.step_dispatches += 1
        self.step_tokens += sum(m.handle.batch_size for m in group)
        if self._chunking_sessions:
            self.decode_steps_interleaved += len(group)
        if env.log_channel_enabled("timing"):
            logger.info(
                "[timing] batched decode: %d sessions, %d rows, "
                "dispatch_ms=%.2f",
                len(group), sum(m.handle.batch_size for m in group), dt_ms,
            )
        outs = []
        row = 0
        with jitwatch.span("bbtpu.slice", members=len(group)):
            for m in group:
                b = m.handle.batch_size
                outs.append((out[row:row + b], dt_ms))
                row += b
        return outs

    # ----------------------------------------- batched tree verification
    def _tree_batchable(
        self, commit, tree_mask, depths, commit_lens, meta
    ) -> bool:
        """Whether this tree-verify step may share a batched ragged
        dispatch (--spec-batch): a plain speculative (commit=False) tree
        step with depth positions. Pruned relay steps keep the solo path
        (their keep-set reply is computed per session against the solo
        step's layout), as do failover replays (commit_lens) and
        prefix-skip settles; a draining server stops coalescing."""
        return (
            self.spec_batch
            and self.max_batch > 1
            and tree_mask is not None
            and depths is not None
            and not commit
            and commit_lens is None
            and meta.get("prune") is None
            and meta.get("prefix_skip") is None
            and not self._draining
        )

    def _solo_tree_step(self, m: _TreeMember):
        self.batch_solo_steps += 1
        try:
            return self._compute_step(
                m.session, m.handle, m.hidden, False, m.tree_mask,
                m.depths,
            )
        except Exception as e:
            return e

    # ----------------------------------------- universal ragged dispatch
    def _batch_group_hint(self, members: list | None = None) -> int:
        """Upper bound on how many members a ComputeQueue gather window
        could still collect: a session submits at most one step (or
        prefill chunk) at a time, so once every open session is in the
        group the window is pure dead time — a solo session never waits
        it out at all.

        KIND-AWARE when only one of the batching flags is on: a tree-only
        gather can admit nothing but tree rows, so it is bounded by the
        sessions currently speculating (without this, tree groups slept
        the full window whenever any non-speculating session was open —
        the phase-lock caveat PR 10 root-caused); symmetrically, a causal
        gather can't admit a speculating session's tree row. With BOTH
        flags on every kind fuses, so every open session counts."""
        total = len(self._sessions)
        if not members or (self.mixed_batch and self.spec_batch):
            return total
        speculating = sum(
            1 for s in self._sessions.values() if s.speculating
        )
        if all(m.key[0] == "tree" for m in members):
            return speculating
        if self.spec_batch:
            return total - speculating
        return total

    def _ragged_compat(self, members: list, cand) -> bool:
        """ONE kind-aware ComputeQueue group-membership predicate for the
        universal ragged dispatch. Mixable kinds follow the flags: decode
        steps ("decode1") and prefill chunks ("chunkm") with
        --mixed-batch (PR 8), tree-verify rows ("tree") with --spec-batch
        (PR 10), and all three fuse cross-kind when both are on. Members
        must agree on layers/adapter/dtype, a group holds at most ONE
        chunk (the ragged step models N row-groups + one chunk row-group)
        and at most max_batch non-chunk members (the chunk rides the +1
        group slot, never a batch seat). Any non-mixable kind falls back
        to exact-key coalescing."""
        mixable = set()
        if self.mixed_batch:
            mixable |= {"decode1", "chunkm"}
        if self.spec_batch:
            mixable.add("tree")
        keys = [m.key for m in members]
        if cand.key[0] not in mixable or keys[0][0] not in mixable:
            return cand.key == keys[0]
        if any(k[1:4] != cand.key[1:4] for k in keys):
            return False
        kinds = [k[0] for k in keys]
        if cand.key[0] == "chunkm":
            return "chunkm" not in kinds
        return sum(1 for k in kinds if k != "chunkm") < self.max_batch

    def _compute_ragged_group(self, members: list) -> list:
        """Runs on the compute thread: ONE group that may hold decode
        steps, tree-verify steps AND one prefill chunk, in any mix the
        compat predicate admitted. Returns one outcome per member — (lazy
        out, dispatch_ms) or an Exception instance, which the queue
        raises only at that member's caller.

        Same member hygiene as _compute_step_group: stale-epoch members
        fail typed; parked / adoption-unsettled members fall out to their
        kind's solo path (their table side effects stay their own).
        Chunk-free all-decode groups take the classic merged-decode path
        (identical outcomes to _compute_step_group); everything else runs
        as ONE ragged span dispatch via executor.ragged_group, with
        per-kind solo replay if the fused dispatch fails so one member's
        fault never sinks its peers. A warmed server fuses a chunk with
        decode rows only into a program its executor has run; a pack that
        would compile one goes as its parts (`ragged_cold_splits`)."""
        results: list = [None] * len(members)
        decode_idx: list[int] = []
        tree_idx: list[int] = []
        chunk_idx: list[int] = []
        # the members' hygiene, as _compute_step_group's
        with jitwatch.span("bbtpu.group", members=len(members)):
            for i, m in enumerate(members):
                if not self.manager.epoch_valid(m.handle):
                    results[i] = SessionKVLost(
                        "server KV arena was rebuilt; session cache lost — "
                        "replay"
                    )
                elif isinstance(m, _ChunkMember):
                    if (self.manager.has_parked(m.handle)
                            or (m.first
                                and self.manager.has_adopted(m.handle))):
                        # unpark / adoption settle mutate the table
                        # mid-group; the solo chunk path owns those side
                        # effects
                        results[i] = self._solo_chunk_step(m)
                    else:
                        chunk_idx.append(i)
                elif (self.manager.has_parked(m.handle)
                      or (not m.session.adoption_settled
                          and self.manager.has_adopted(m.handle))):
                    # same solo carve-outs as _compute_step_group
                    results[i] = (
                        self._solo_tree_step(m) if isinstance(m, _TreeMember)
                        else self._solo_member_step(m)
                    )
                elif isinstance(m, _TreeMember):
                    tree_idx.append(i)
                else:
                    decode_idx.append(i)

        def decode_members() -> None:
            # exact _compute_step_group semantics
            if len(decode_idx) == 1:
                results[decode_idx[0]] = self._solo_member_step(
                    members[decode_idx[0]]
                )
            elif decode_idx:
                group = [members[i] for i in decode_idx]
                try:
                    outs = self._dispatch_batched(group)
                except Exception as e:
                    logger.warning(
                        "batched decode of %d sessions failed (%r); "
                        "replaying row-by-row", len(group), e,
                    )
                    outs = [self._solo_member_step(m) for m in group]
                for i, out in zip(decode_idx, outs):
                    results[i] = out

        if not chunk_idx and not tree_idx:
            decode_members()
            return results
        if not decode_idx and not tree_idx:
            results[chunk_idx[0]] = self._solo_chunk_step(members[chunk_idx[0]])
            return results
        if len(tree_idx) == 1 and not decode_idx and not chunk_idx:
            results[tree_idx[0]] = self._solo_tree_step(members[tree_idx[0]])
            return results
        # member-major row order: decodes, then trees, then the chunk
        # LAST (its multi-token row-group caps the ragged packing)
        order = decode_idx + tree_idx + chunk_idx
        group = [members[i] for i in order]
        cold = False
        if chunk_idx and not tree_idx and self._warm_fenced:
            with jitwatch.span("bbtpu.group", members=len(group)):
                cold = self.executor.ragged_bucket(
                    [m.handle for m in group], [m.hidden for m in group]
                ) not in self.executor.ragged_buckets_run
        if cold:
            # FUSE ONLY INTO A PROGRAM THAT EXISTS: which packs form
            # depends on arrival times, so no warm-up list meets every
            # bucket, and a fused program compiled now holds the compute
            # thread, and with it every session, for seconds. The parts
            # are the decode group's and the chunk's own programs, which
            # every session runs anyway: decode rows first. (A pack with
            # tree rows keeps the fused path: its warm-up pairs are the
            # buckets a speculating round runs.)
            self.ragged_cold_splits += 1
            decode_members()
            results[chunk_idx[0]] = self._solo_chunk_step(
                members[chunk_idx[0]]
            )
            return results
        try:
            outs = self._dispatch_ragged(group)
        except Exception as e:
            logger.warning(
                "ragged dispatch of %d decodes + %d trees + %d chunks "
                "failed (%r); replaying solo",
                len(decode_idx), len(tree_idx), len(chunk_idx), e,
            )
            outs = []
            for m in group:
                if isinstance(m, _ChunkMember):
                    outs.append(self._solo_chunk_step(m))
                elif isinstance(m, _TreeMember):
                    outs.append(self._solo_tree_step(m))
                else:
                    outs.append(self._solo_member_step(m))
        for i, out in zip(order, outs):
            results[i] = out
        return results

    def _solo_chunk_step(self, m: _ChunkMember):
        try:
            return self._compute_prefill_chunk(
                m.session, m.handle, m.hidden, m.first, m.last,
                m.prefix_skip, m.reply_rows,
            )
        except Exception as e:
            return e

    def _dispatch_ragged(self, group: list) -> list:
        """ONE universal ragged span dispatch for any admitted mix of
        decode steps, tree-verify steps and at most one prefill chunk
        (the chunk, if present, is group[-1]). Every member's KV writes
        go in speculatively and commit/rollback stays PER KIND, exactly
        as the three dedicated stacks did:

        - decode members commit after the dispatch succeeds and roll
          back to their committed state on failure;
        - the chunk commits only on its stream's LAST chunk and is
          TRUNCATED to its pre-dispatch length on failure (a plain
          rollback would also discard the stream's earlier, still-wanted
          speculative chunks);
        - tree members never commit here — on failure each truncates
          back to its pre-dispatch committed length and replays solo; on
          success the surviving slots settle when the session's next
          accept rides in (accept_speculative, unchanged)."""

        with jitwatch.stopwatch(
            "bbtpu.dispatch", members=len(group),
            sessions="+".join(m.session.id for m in group),
        ) as sw:
            now = clock.monotonic()
            for m in group:
                m.session.last_step_at = now
            chunk = group[-1] if isinstance(group[-1], _ChunkMember) else None
            decodes = [m for m in group if isinstance(m, _BatchMember)]
            trees = [m for m in group if isinstance(m, _TreeMember)]
            # pre-dispatch speculative lengths: the truncate targets on
            # failure for the chunk and for every tree member
            chunk_snap = (
                [int(x) for x in self.manager.context_lens(chunk.handle)]
                if chunk is not None else None
            )
            tree_snaps = [
                [int(x) for x in self.manager.context_lens(m.handle)]
                for m in trees
            ]
            try:
                out, _combined = self.executor.ragged_group(
                    [m.handle for m in group],
                    [m.hidden for m in group],
                    tree_masks=[
                        m.tree_mask if isinstance(m, _TreeMember) else None
                        for m in group
                    ],
                    depths_list=[
                        m.depths if isinstance(m, _TreeMember) else None
                        for m in group
                    ],
                    layers=group[0].session.layers,
                    adapter=group[0].session.adapter,
                    reply_rows=[
                        m.reply_rows if isinstance(m, _ChunkMember) else None
                        for m in group
                    ],
                )
            except Exception:
                with jitwatch.span("bbtpu.commit"):
                    if (chunk is not None
                            and self.manager.epoch_valid(chunk.handle)):
                        self.manager.truncate_speculative(
                            chunk.handle, chunk_snap
                        )
                    for m, snap in zip(trees, tree_snaps):
                        if self.manager.epoch_valid(m.handle):
                            self.manager.truncate_speculative(
                                m.handle, snap
                            )
                    for m in decodes:
                        if self.manager.epoch_valid(m.handle):
                            self.manager.rollback(m.handle)
                raise
            with jitwatch.span("bbtpu.commit"):
                for m in decodes:
                    self.manager.commit(m.handle)
                if chunk is not None and chunk.last:
                    self.manager.commit(chunk.handle)
        dt_ms = sw.ms
        ntok = sum(
            m.handle.batch_size * int(m.hidden.shape[1]) for m in group
        )
        self.ragged_group_dispatches += 1
        kinds = (
            (1 if decodes else 0) + (1 if trees else 0)
            + (1 if chunk is not None else 0)
        )
        if kinds > 1:
            self.ragged_cross_kind_dispatches += 1
        if chunk is not None:
            self.mixed_dispatches += 1
            self.mixed_tokens += ntok
        if trees:
            self.tree_group_dispatches += 1
            self.tree_group_members += len(trees)
        self.step_dispatches += 1
        self.step_tokens += ntok
        if chunk is not None:
            # the decodes/trees literally ran inside a mid-stream
            # prefill's dispatch
            self.decode_steps_interleaved += len(group) - 1
        elif self._chunking_sessions:
            self.decode_steps_interleaved += len(group)
        if env.log_channel_enabled("timing"):
            logger.info(
                "[timing] ragged dispatch: %d decodes + %d trees + "
                "%d-token chunk, %d rows, dispatch_ms=%.2f",
                len(decodes), len(trees),
                int(chunk.hidden.shape[1]) if chunk is not None else 0,
                sum(
                    m.handle.batch_size * int(m.hidden.shape[1])
                    for m in group
                ), dt_ms,
            )
        # slice the member-major token-packed [R, D] result back out:
        # decode members get [b, 1, D], trees and the chunk [b, t, D]
        outs = []
        off = 0
        with jitwatch.span("bbtpu.slice", members=len(group)):
            for m in group:
                b = m.handle.batch_size
                t = int(m.hidden.shape[1])
                outs.append(
                    (out[off:off + b * t].reshape(b, t, -1), dt_ms)
                )
                off += b * t
        return outs

    def _reclaim_idle(self, need_pages: int, exclude_seq_ids: set) -> int:
        """Park idle sessions' KV (LRU by last step) until `need_pages` are
        freed. Runs on the compute thread — the only thread that mutates
        the paged table — so no step can race the eviction."""

        now = clock.monotonic()
        victims = sorted(
            (
                s for s in list(self._sessions.values())
                if now - s.last_step_at >= self.idle_park_s
                and not (set(s.handle.seq_ids) & exclude_seq_ids)
            ),
            key=lambda s: s.last_step_at,
        )
        freed = 0
        for sess in victims:
            if freed >= need_pages:
                break
            for sid in sess.handle.seq_ids:
                try:
                    if (
                        self.manager.table.has_seq(sid)
                        and sid not in self.manager._parked
                        and self.manager.table.seq(sid).l_seq > 0
                    ):
                        before = self.manager.table.free_pages
                        self.manager.park_sequence(sid)
                        freed += self.manager.table.free_pages - before
                except KeyError:
                    continue  # session tore down between snapshot and park
            logger.info(
                "parked idle session %s (freed %d pages so far)",
                sess.id, freed,
            )
        return freed

    def _dump_activations(
        self, dump_dir: str, session: _Session, meta: dict,
        hidden: np.ndarray, out: np.ndarray
    ) -> None:
        """Capture real per-step hidden states for compression research
        (reference utils/real_activation_dumper.py, hooked at
        backend.inference_step:500)."""
        import os

        n = getattr(self, "_dump_count", 0)
        if n >= env.get("BBTPU_DUMP_LIMIT"):
            return
        self._dump_count = n + 1
        os.makedirs(dump_dir, exist_ok=True)
        rows = meta.get("rows")
        suffix = f"_rows{rows[0]}-{rows[1]}" if rows else ""
        path = os.path.join(
            dump_dir,
            f"{self.server_id}_{session.id}_step{meta.get('step')}"
            f"{suffix}.npz",
        )
        np.savez(
            path,
            hidden_in=np.asarray(hidden, dtype=np.float32),
            hidden_out=np.asarray(out, dtype=np.float32),
            start_block=self.start_block,
            end_block=self.end_block,
        )

    async def _train_pruner_head(self, session: _Session, accept: list):
        """Online MidLMHead training (reference lm_head_trainer): each
        accepted (parent -> child) edge supplies (mid_hidden[parent],
        token[child]) — the full model chose token[child] there, so the
        head learns to predict it from mid-network state. Device work runs
        on the compute queue at training priority; file I/O off-loop."""
        mgr = self._pruner_manager
        if mgr is None or getattr(mgr, "trainer", None) is None:
            session.last_tree = None
            return
        hidden, tokens, parents = session.last_tree
        session.last_tree = None
        feats, targets = [], []
        for i, acc in enumerate(accept):
            path = [int(a) for a in np.asarray(acc).ravel()]
            for parent, child in zip(path, path[1:]):
                feats.append(hidden[i, parent])
                targets.append(int(tokens[i, child]))
        if not feats:
            return
        try:
            loss = await self.compute.submit(
                PRIORITY_TRAINING, mgr.trainer.train_step,
                np.stack(feats), np.asarray(targets, dtype=np.int64),
            )
        except Exception as e:
            logger.warning("pruner-head train step failed: %s", e)
            return
        if getattr(mgr, "neural_trainer", None) is not None:
            await self._train_neural_pruner(
                mgr, hidden, tokens, parents, accept
            )
        if env.log_channel_enabled("spec"):
            logger.info(
                "[pruner-train] step=%d pairs=%d loss=%.3f",
                mgr.trainer.steps, len(targets), loss,
            )
        ckpt = env.get("BBTPU_PRUNER_CKPT")
        if ckpt and mgr.trainer.steps % 50 == 0:
            try:
                await asyncio.to_thread(mgr.trainer.save, ckpt)
            except Exception as e:
                logger.warning("pruner checkpoint save failed: %s", e)

    async def _train_neural_pruner(self, mgr, hidden, tokens, parents,
                                   accept):
        """Online BCE training of the learned keep/prune scorer (reference
        adaptive_neural_pruner collect_training_data): recompute each
        row's probability features under the CURRENT head, label
        accepted-path nodes 1 and drafted-but-rejected nodes 0."""
        from bloombee_tpu.spec.pruner import node_features
        from bloombee_tpu.spec.tree import DraftTree

        bsz, t = tokens.shape

        def _head_probs():
            # ONE small matmul: rides the compute queue at training
            # priority like every other device forward (the queue's
            # documented contract is that all device work funnels through
            # its single thread — advisor, round 4), while the O(B*T)
            # numpy feature loop below stays on a plain worker thread.
            return mgr._head.probs(
                hidden.reshape(bsz * t, -1).astype(np.float32)
            ).reshape(bsz, t, -1)

        def _build_features(all_probs):
            feat_rows, label_rows = [], []
            for i, acc in enumerate(accept):
                tree = DraftTree(tokens=tokens[i], parents=parents)
                root = np.zeros(all_probs.shape[2], dtype=np.float64)
                root[int(tokens[i, 0])] = 1.0
                feat_rows.append(node_features(tree, all_probs[i], root))
                lbl = np.zeros((t,), dtype=np.float32)
                for node in np.asarray(acc).ravel():
                    if 0 <= int(node) < t:
                        lbl[int(node)] = 1.0
                label_rows.append(lbl)
            return np.concatenate(feat_rows), np.concatenate(label_rows)

        try:
            all_probs = await self.compute.submit(
                PRIORITY_TRAINING, _head_probs
            )
            feats, labels = await asyncio.to_thread(
                _build_features, all_probs
            )
            loss = await self.compute.submit(
                PRIORITY_TRAINING, mgr.neural_trainer.train_step,
                feats, labels,
            )
        except Exception as e:
            logger.warning("neural pruner train step failed: %s", e)
            return
        if env.log_channel_enabled("spec"):
            logger.info(
                "[pruner-net-train] step=%d loss=%.3f",
                mgr.neural_trainer.steps, loss,
            )
        ckpt = env.get("BBTPU_PRUNER_CKPT")
        if ckpt and mgr.neural_trainer.steps % 50 == 0:
            try:
                await asyncio.to_thread(
                    mgr.neural_trainer.save, f"{ckpt}.net"
                )
            except Exception as e:
                logger.warning("neural pruner checkpoint save failed: %s", e)

    def _prune_tree(self, out: np.ndarray, prune: dict):
        """Per-row keep indices from the MidLMHead over this span's output
        hidden; None if no pruner weight is available (degrade to full)."""
        mgr = self._ensure_pruner(float(prune.get("threshold", 0.05)))
        if mgr is None:
            return None
        from bloombee_tpu.spec.tree import DraftTree

        tokens = np.asarray(prune["tokens"], dtype=np.int64)  # [B, T]
        parents = np.asarray(prune["parents"], dtype=np.int32)
        max_keep = int(prune.get("max_keep", tokens.shape[1]))
        mgr._pruner.max_keep = max_keep
        bsz, t = tokens.shape
        # one batched head call for every row's nodes (per-step hot path)
        all_probs = mgr._head.probs(
            np.asarray(out, dtype=np.float32).reshape(bsz * t, -1)
        ).reshape(bsz, t, -1)
        rows = []
        for i in range(bsz):
            tree = DraftTree(tokens=tokens[i], parents=parents)
            # node 0 is the certain token: its "root" distribution is a
            # one-hot so it always survives the threshold
            root = np.zeros(all_probs.shape[2], dtype=np.float64)
            root[int(tokens[i, 0])] = 1.0
            rows.append(mgr._pruner.keep_indices(tree, all_probs[i], root))
        return np.stack(rows)

    async def _ensure_pruner_loaded(self) -> None:
        if self._pruner_manager is not None or self._pruner_unavailable:
            return
        if self._pruner_lock is None:
            self._pruner_lock = lockwatch.async_lock("server.pruner")
        async with self._pruner_lock:
            if self._pruner_manager is None and not self._pruner_unavailable:
                await asyncio.to_thread(self._load_pruner)

    def _load_pruner(self) -> None:
        if self.model_dir is None:
            self._pruner_unavailable = True
            return
        try:
            import os

            from bloombee_tpu.spec.pruner import (
                MidHeadTrainer,
                NeuralPrunerTrainer,
                PrunerManager,
            )

            method = env.get("BBTPU_PRUNER_METHOD")
            mgr = PrunerManager(method=method)
            ckpt = env.get("BBTPU_PRUNER_CKPT")
            if method == "neural":
                # the learned scorer has its own sidecar checkpoint
                net_ckpt = f"{ckpt}.net" if ckpt else ""
                import os as _os

                if net_ckpt and _os.path.exists(
                    MidHeadTrainer.ckpt_path(net_ckpt)
                ):
                    try:
                        mgr.neural_trainer = NeuralPrunerTrainer.load(
                            net_ckpt
                        )
                        mgr._pruner = mgr.neural_trainer.pruner
                    except Exception as e:
                        logger.warning(
                            "neural pruner checkpoint unreadable (%s); "
                            "fresh init", e,
                        )
                        mgr.neural_trainer = NeuralPrunerTrainer(mgr._pruner)
                else:
                    mgr.neural_trainer = NeuralPrunerTrainer(mgr._pruner)
            else:
                mgr.neural_trainer = None
            trainer = None
            if ckpt and os.path.exists(MidHeadTrainer.ckpt_path(ckpt)):
                try:
                    # resume a previously trained head (reference
                    # adaptive_neural_pruner.load_model)
                    trainer = MidHeadTrainer.load(
                        ckpt, dtype=self.compute_dtype
                    )
                    mgr._head = trainer.head
                except Exception as e:
                    # a torn checkpoint must degrade to fresh init, never
                    # disable pruning outright
                    logger.warning(
                        "pruner checkpoint unreadable (%s); fresh init", e
                    )
                    trainer = None
            if trainer is None:
                from bloombee_tpu.models.checkpoint import load_client_params

                client = load_client_params(
                    self.model_dir, dtype=self.compute_dtype
                )
                mgr.ensure_head(
                    client["lm_head"], client.get("norm"),
                    self.spec.rms_norm_eps,
                )
                trainer = MidHeadTrainer(mgr._head)
            mgr.trainer = trainer
            self._pruner_manager = mgr
        except Exception as e:
            logger.warning("pruner unavailable: %s", e)
            self._pruner_unavailable = True

    def _ensure_pruner(self, threshold: float):
        if self._pruner_manager is None:
            return None
        self._pruner_manager.set_request_threshold(threshold)
        return self._pruner_manager

    async def _rpc_push(self, meta: dict, tensors) -> None:
        session = self._sessions.get(meta["session_id"])
        if meta.get("chain_ids") or meta.get("chain_error"):
            # chained-decode control message for a waiting coordinator:
            # bypass push_inbox (its consumer — the session loop — is
            # blocked inside the coordinator awaiting exactly this)
            if session is None:
                logger.warning(
                    "chain message for unknown session %s dropped",
                    meta["session_id"],
                )
                return
            session.chain_inbox.put_nowait((meta, tensors))
            return
        if session is None:
            # A push can race ahead of the session's stream-open (allocation
            # may be waiting on cache budget); buffer it briefly — the
            # reference accumulates early micro-batch pushes the same way
            # (handler.py:1850-2151 accumulate/immediate queues).
            self._buffer_pending_push(meta, tensors)
            return
        session.push_inbox.put_nowait((meta, tensors))

    def _buffer_pending_push(self, meta: dict, tensors) -> None:

        now = clock.monotonic()
        sid = meta["session_id"]
        self._pending_pushes.setdefault(sid, []).append((now, meta, tensors))
        # drop stale buffers
        for key in list(self._pending_pushes):
            self._pending_pushes[key] = [
                e
                for e in self._pending_pushes[key]
                if now - e[0] < self.pending_push_ttl
            ]
            if not self._pending_pushes[key]:
                del self._pending_pushes[key]

    def _drain_pending_pushes(self, session: _Session) -> None:
        for _, meta, tensors in self._pending_pushes.pop(session.id, []):
            session.push_inbox.put_nowait((meta, tensors))

    async def _rpc_forward(self, meta: dict, tensors):
        """Span forward without a session (training / one-shot),
        reference block_functions.py:247 run_rpc_forward."""
        if meta.get("audit"):
            # an integrity client re-executing another replica's recorded
            # step through us; count it so operators can see audit load
            self.audit_forwards += 1
        if self.training is None:
            raise RuntimeError("training path unavailable for this family")
        hidden = np.asarray(tensors[0], dtype=np.float32)
        prompts = (
            np.asarray(tensors[1], dtype=np.float32)
            if meta.get("deep_prompts") and len(tensors) > 1
            else None
        )
        layers = self._resolve_layers(meta)
        out = await self.compute.submit(
            PRIORITY_TRAINING, self.training.forward, hidden, layers, prompts,
            meta.get("adapter"),
        )
        if self.liar_p > 0 and self._liar_rng.random() < self.liar_p:
            # TEST HOOK: a Byzantine server lies on every plane — including
            # when another client drafts it as an audit replica (a lying
            # auditor must get outvoted by the tiebreak, not trusted)
            out = self._liar_perturb(out)
            self.liar_steps += 1
        return {"ok": True}, [out]

    async def _rpc_backward(self, meta: dict, tensors):
        """Gradient w.r.t. span inputs (blocks frozen; backward recomputes
        the forward — reference block_functions.py:357 run_rpc_backward)."""
        if self.training is None:
            raise RuntimeError("training path unavailable for this family")
        hidden_in = np.asarray(tensors[0], dtype=np.float32)
        grad_out = np.asarray(tensors[1], dtype=np.float32)
        prompts = (
            np.asarray(tensors[2], dtype=np.float32)
            if meta.get("deep_prompts") and len(tensors) > 2
            else None
        )
        layers = self._resolve_layers(meta)
        result = await self.compute.submit(
            PRIORITY_TRAINING, self.training.backward, hidden_in, grad_out,
            layers, prompts, meta.get("adapter"),
        )
        if prompts is not None:
            g_in, g_prompts = result
            return {"ok": True}, [g_in, g_prompts]
        return {"ok": True}, [result]
