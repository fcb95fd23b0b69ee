"""InferenceSession: client-side stateful decode across the span chain.

Port of /root/reference/src/bloombee/client/inference_session.py:438-855:
owns one rpc_inference stream per span, steps hidden states through the chain,
and on a span failure re-routes that suffix of the chain and replays the input
history into the replacement servers to rebuild their KV caches
(`_update_sequence`, :802-831). Supports server-to-server push-only decode:
the client sends only to span 0 and each hop forwards activations directly
(reference ClientConfig.push_only_downstream_decode, config.py:19-42).
"""

from __future__ import annotations

import asyncio
import logging
import random
import time
import uuid

import numpy as np

from bloombee_tpu.client.integrity import (
    IntegrityError,
    SanityGate,
    tensors_close,
)
from bloombee_tpu.client.sequence_manager import (
    MissingBlocksError,
    RemoteSequenceManager,
)
from bloombee_tpu.swarm.data import RemoteSpanInfo
from bloombee_tpu.utils import env, ledger
from bloombee_tpu.wire import turn
from bloombee_tpu.wire.rpc import (
    Connection,
    OverloadedError,
    RpcError,
    Stream,
    connect,
)
from bloombee_tpu.wire.tensor_codec import dtype_for_name

logger = logging.getLogger(__name__)

env.declare(
    "BBTPU_MICROBATCH", int, 1,
    "default within-stage micro-batch count for client sessions (>1 splits "
    "each step's batch so stage N+1 computes chunk k while stage N computes "
    "k+1 — the reference's BLOOMBEE_MICRO_BATCH_SIZE overlap)",
)
env.declare(
    "BBTPU_REPL_EVERY", int, 0,
    "session-KV replication interval: every N newly-sealed pages the "
    "client asks each span's server to ship them (kv_put) to a standby "
    "covering the same span, so failover replays at most one interval "
    "plus the unsealed tail (0 = replication off)",
)
env.declare(
    "BBTPU_RESUME", bool, True,
    "reconnect-resume: after a stream failure, try to re-attach each "
    "span's lease-parked session (resume: session_id) and retransmit the "
    "failed step under its original id — at-most-once server-side, zero "
    "prompt replay — before falling back to full-replay recovery. Safe "
    "against servers with leases off: they decline and recovery proceeds "
    "as before",
)

# A part of a prompt on the client -> first span hop: the rows that make about
# this many bytes in the span's wire dtype, in whole chunks of the span's
# advertised chunk length and at least one (`_part_rows`). The span starts on
# a part while the client casts and encodes the next, so a part is what a
# prompt still uploads with nothing under it, and a frame's fixed cost is paid
# once a part: PERF.md section 6 (PR 43) has the readings it came from.
_PART_BYTES = 8 << 20


def _part_rows(chunk: int, row_bytes: int) -> int:
    """Rows of one part of a step whose position (every sequence of the
    batch) takes `row_bytes` on the wire, for a span that plans its
    prefills in chunks of `chunk` rows."""
    return max(1, _PART_BYTES // (chunk * row_bytes)) * chunk


# the first no-embed_fn decode_n session in the process warns loudly; later
# sessions demote to DEBUG (a client spawning many raw sessions would
# otherwise repeat the identical warning once per session)
_warned_no_embed_process = False

# default admission-control identity: one id per client process, so all of
# a process's sessions share one fair-share bucket server-side (a client
# can't dodge fairness accounting by opening more sessions)
_PROCESS_CLIENT_ID = f"cli-{uuid.uuid4().hex[:8]}"


class DecodeNUnsupported(RuntimeError):
    """The server cannot run server-side multi-step decode for this session
    (no client params / sub-span route / sharded span). Not a failure — the
    caller falls back to per-step decoding without banning the peer."""


def _raise_if_session_lost(resp_meta: dict) -> None:
    """Typed `session_lost` reply: the server is healthy but this session's
    KV is gone (arena rebuilt after a kernel failure). Raise a plain wire
    error so the caller's retry loop recovers and replays WITHOUT banning
    the peer (the ban paths only trigger on transport failures)."""
    if resp_meta.get("session_lost"):
        raise RpcError(resp_meta.get("reason", "session KV lost"))


def _sanitize_retry_ms(retry_ms) -> int | None:
    try:
        v = int(retry_ms)
    except (TypeError, ValueError):
        return None
    return v if v > 0 else None


class _SpanSession:
    """One open rpc_inference stream to one server
    (reference _ServerInferenceSession)."""

    def __init__(self, span: RemoteSpanInfo, conn: Connection, stream: Stream,
                 session_id: str):
        self.span = span
        self.conn = conn
        self.stream = stream
        self.session_id = session_id

    async def close(self):
        try:
            await self.stream.close()
        except Exception:
            pass
        try:
            await self.conn.close()
        except Exception:
            pass


class InferenceSession:
    def __init__(
        self,
        manager: RemoteSequenceManager,
        max_length: int,
        batch_size: int = 1,
        use_push: bool = True,
        max_retries: int = 3,
        step_timeout: float = 120.0,
        microbatch: int | str | None = None,  # count or "auto"
        embed_fn=None,  # ids [B, T] -> hidden; enables token-id replay
        adapter: str | None = None,  # per-request LoRA adapter name
        prefix_cache: bool | None = None,  # probe servers' shared-prefix
        # pools before the first prefill and send only the uncached suffix
        # (None -> BBTPU_PREFIX_CACHE env)
        repl_every: int | None = None,  # standby-KV replication interval
        # in sealed pages (None -> BBTPU_REPL_EVERY env; 0 disables)
        client_id: str | None = None,  # admission-control identity sent in
        # every session open (None -> one shared id per client process)
        overload_retries: int = 10,  # how many `overloaded` sheds a step
        # rides out (backoff + reroute) before failing hard — a separate,
        # more generous budget than max_retries because a shed is the
        # server WORKING AS DESIGNED under load, not a fault
        resume: bool | None = None,  # reconnect-resume after a stream
        # failure: re-attach each span's lease-parked session and
        # retransmit the failed step under its original id (at-most-once
        # server-side, zero prompt replay); None -> BBTPU_RESUME env
        resume_timeout: float = 10.0,  # per-span resume handshake budget
        # before giving up on the cheap path (the lease clock is running)
        keepalive_s: float | None = None,  # client-side wire keepalive for
        # span connections (None -> BBTPU_KEEPALIVE_S env; 0 disables)
        integrity: bool | None = None,  # Byzantine-robust mode: inline
        # sanity gate + out_digest verification on every received span
        # output; rejects strike the peer and heal via the existing
        # reroute+replay recovery (None -> BBTPU_INTEGRITY env)
        audit_p: float | None = None,  # per-step probability of
        # re-executing a recorded span step on a different replica and
        # tolerance-comparing the outputs (None -> BBTPU_AUDIT_P env;
        # > 0 implies integrity for this session)
    ):
        self.manager = manager
        self.adapter = adapter
        self.max_length = max_length
        self.batch_size = batch_size
        self.use_push = use_push
        self.max_retries = max_retries
        self.step_timeout = step_timeout
        self.client_id = client_id or _PROCESS_CLIENT_ID
        self.overload_retries = max(0, int(overload_retries))
        self.embed_fn = embed_fn
        self.resume = (
            bool(env.get("BBTPU_RESUME")) if resume is None else bool(resume)
        )
        self.resume_timeout = float(resume_timeout)
        self.keepalive_s = keepalive_s
        # integrity layer (opt-in; off = byte-for-byte legacy behavior)
        self.audit_p = (
            float(env.get("BBTPU_AUDIT_P")) if audit_p is None
            else float(audit_p)
        )
        self.integrity = (
            bool(env.get("BBTPU_INTEGRITY")) if integrity is None
            else bool(integrity)
        ) or self.audit_p > 0
        self._gate = SanityGate() if self.integrity else None
        # integrity observability
        self.sanity_rejects = 0
        self.audits_run = 0
        self.audit_mismatches = 0
        self.integrity_reroutes = 0
        self._audit_rng = random.Random()
        # audit input records: span 0 re-embeds its full input from the id
        # history, spans > 0 accumulate their relay-mode input chunks here;
        # None = invalidated (push-mode multi-span, prefix skip, reroute,
        # decode_n/spec commits) — audits then cover span 0 only
        self._span_in: list[list[np.ndarray]] | None = None
        self._last_span_outs: list = []
        # reconnect-resume observability: streams re-attached without
        # replay, resumes the servers declined (fell back to recovery),
        # and the (step_id, prefix_skip) of the last transmitted step so a
        # post-resume retry retransmits it bit-identical under the SAME id
        self.resumed_streams = 0
        self.resume_declines = 0
        self._last_sent: tuple[int, int | None] | None = None
        self.prefix_cache = (
            env.get("BBTPU_PREFIX_CACHE") if prefix_cache is None
            else bool(prefix_cache)
        )
        # standby replication: every `repl_every` sealed pages the client
        # tells each span's server (kv_repl stream item) to export the new
        # pages and kv_put them into a same-span standby's prefix pool, so
        # `_recover`'s probe adopts them and replays only the unsealed tail
        self.repl_every = (
            env.get("BBTPU_REPL_EVERY") if repl_every is None
            else int(repl_every)
        )
        self._repl: list[dict | None] = []  # per-span replication state
        # incremental full-history hash chains, keyed by page size
        self._chains_by_ps: dict[int, list[list[str]]] = {}
        # client-side failover observability: pages sealed but not yet
        # announced to a standby, and tokens re-prefilled by recoveries
        self.repl_lag_pages = 0
        self.failover_replayed_tokens = 0
        # within-stage micro-batch pipelining (reference
        # microbatch_config.py:84-130 overlap-only mode): split each step's
        # batch into chunks so downstream spans start on chunk k while
        # upstream computes k+1
        self.microbatch = (
            microbatch if microbatch is not None
            else env.get("BBTPU_MICROBATCH")
        )
        if not (
            self.microbatch == "auto"
            or (isinstance(self.microbatch, int) and self.microbatch >= 1)
        ):
            raise ValueError(
                f"microbatch must be >= 1 or 'auto', got {self.microbatch!r}"
            )
        self._spans: list[_SpanSession] = []
        # failure-replay history. Preferred: per-row committed token ids
        # (ragged; replayed by re-embedding — the reference replays ids, not
        # hidden states, inference_session.py:802-831). Fallback when no
        # embed_fn / raw-hidden steps: stored hidden arrays (memory-heavy).
        self._id_rows: list[list[int]] = [[] for _ in range(batch_size)]
        self._history: list[np.ndarray] = []  # legacy hidden replay
        self._step_counter = 0
        self.position = 0
        # set when the server-side KV ran past the committed history (e.g.
        # a decode_n chunk truncated at EOS); the next step rebuilds the
        # chain and replays the true history before proceeding
        self._needs_rebuild = False
        self._warned_no_embed = False
        # per-step timing rows (the client half of the reference's
        # [TIMING_TABLE], handler.py:1276-1605): one entry per step with
        # per-span compute ms and `total_ms`, which ends when the last reply
        # is in `out` and counts the server's ingest, queue wait and reply
        # beside the wire. In `step` it starts AFTER the request was cast,
        # encoded and written (the turn's `c_send`, wire/turn.py, is in no
        # `total_ms`); in the tree step and in `decode_n` it starts BEFORE
        # the send
        self.timings: list[dict] = []
        # this client's parts of a turn, told to span 0 in every request
        self._legs = turn.ClientLegs()

    # ------------------------------------------------------------- lifecycle
    def note_head_ms(self, ms: float) -> None:
        """The caller's final norm + LM head since the last reply (the turn's
        `c_head`); unreported time is `c_other`."""
        self._legs.note_head_ms(ms)

    def note_embed_ms(self, ms: float) -> None:
        """The caller's embedding of the next step's ids (`c_embed`)."""
        self._legs.note_embed_ms(ms)

    async def __aenter__(self) -> "InferenceSession":
        self._legs.entering()
        await self.manager.update(force=True)
        route = self.manager.make_sequence(
            cache_tokens_needed=self.batch_size * self.max_length,
            relay=not self.use_push,
        )
        self._spans = [await self._open_span(s) for s in route]
        self._legs.opened()
        self._init_repl()
        return self

    async def __aexit__(self, *exc) -> None:
        await self.close()

    async def close(self) -> None:
        for s in self._spans:
            await s.close()
        self._spans = []

    async def _open_span(self, span: RemoteSpanInfo) -> _SpanSession:
        session_id = f"sess-{uuid.uuid4().hex[:12]}"
        conn = await connect(
            span.server_info.host, span.server_info.port,
            keepalive_s=self.keepalive_s,
        )
        stream = await conn.open_stream(
            "rpc_inference",
            {
                "session_id": session_id,
                "batch_size": self.batch_size,
                "max_length": self.max_length,
                "start": span.start,
                "end": span.end,
                # fair-share identity for admission control (old servers
                # ignore unknown meta keys)
                "client_id": self.client_id,
                **({"adapter": self.adapter} if self.adapter else {}),
            },
        )
        return _SpanSession(span, conn, stream, session_id)

    def _raise_if_shed(self, resp_meta: dict, peer_id: str) -> None:
        """Typed `overloaded` reply (in-stream shed of this session's new
        work): penalize the peer with the SHORT overload class — never a
        fault ban, the server is healthy — and raise the retriable error so
        step()'s overload handler backs off and reroutes."""
        if not resp_meta.get("overloaded"):
            return
        retry_ms = _sanitize_retry_ms(resp_meta.get("retry_after_ms"))
        self.manager.note_peer_overloaded(
            peer_id,
            retry_after_s=retry_ms / 1000.0 if retry_ms else None,
        )
        raise OverloadedError(
            resp_meta.get("reason", "server overloaded"),
            retry_after_ms=retry_ms,
        )

    def _note_shed_exc(self, e: OverloadedError, peer_id: str) -> None:
        """Wire-level overloaded err frame (session-open shed) seen on a
        span's stream: short overload penalty instead of a fault ban."""
        self.manager.note_peer_overloaded(
            peer_id,
            retry_after_s=(
                e.retry_after_ms / 1000.0 if e.retry_after_ms else None
            ),
        )

    # ----------------------------------------------------------- prefix cache
    async def _probe_prefix(
        self,
        id_rows: list[list[int]] | None = None,
        hidden_rows: list[np.ndarray] | None = None,
    ) -> int:
        """Ask every span how much of each row's history its shared-prefix
        pool already holds; returns the chain-wide skippable token count
        (min over spans AND rows — every span receives the same suffix
        hidden, so the chain can only skip what ALL of them have).

        Probes hash whichever history the caller passes: token-id rows
        (the normal prompt / replay path) or raw [T, D] hidden rows
        (embed-less sessions — their chains use a distinct hash root so
        they can never alias an id chain). Spans that don't advertise a
        page size (cache off / old server) force 0. Wire failures
        propagate as step errors so the caller's retry loop rebuilds the
        chain — a timed-out probe must never leave a stale reply queued
        on a reused stream."""
        from bloombee_tpu.kv.prefix import hidden_hash_chain, page_hash_chain

        rows = id_rows if id_rows is not None else hidden_rows
        builder = page_hash_chain if id_rows is not None else hidden_hash_chain
        lens = [len(r) for r in rows] if rows else []
        ps_list = [s.span.server_info.page_size for s in self._spans]
        if not ps_list or any(ps <= 0 for ps in ps_list) or not any(lens):
            # some span can't share (or nothing to hash): whole-chain miss
            return 0
        sizes = set(ps_list)
        chains_by_ps = {
            ps: [builder(row, ps) for row in rows] for ps in sizes
        }
        step_id = self._step_counter
        self._step_counter += 1
        for s in self._spans:
            chains = chains_by_ps[s.span.server_info.page_size]
            await s.stream.send(
                {"step": step_id, "prefix_probe": chains}, []
            )
        matched = None
        for i, s in enumerate(self._spans):
            try:
                item = await asyncio.wait_for(
                    s.stream.recv(), self.step_timeout
                )
            except OverloadedError as e:
                self._note_shed_exc(e, s.span.peer_id)
                raise
            except (RpcError, OSError, asyncio.TimeoutError):
                self.manager.ban_peer(s.span.peer_id)
                raise
            if item is None:
                self.manager.ban_peer(s.span.peer_id)
                raise RpcError(f"span {i} closed during prefix probe")
            resp_meta, _ = item
            _raise_if_session_lost(resp_meta)
            self._raise_if_shed(resp_meta, s.span.peer_id)
            span_min = min(
                int(x) for x in resp_meta.get("prefix_matched") or [0]
            )
            matched = span_min if matched is None else min(matched, span_min)
        # cap below the shortest row so the final prompt position always
        # computes (the caller consumes its output) — ALSO the genuine
        # divergence point: the uncached tail writes into the last shared
        # page and copy-on-write splits it server-side
        shortest = min(lens)
        return max(0, min(matched or 0, shortest - 1))

    # ------------------------------------------------------- kv replication
    def _history_rows(self):
        """(kind, per-row history) for hashing: ("ids", ragged id lists),
        ("hidden", [T, D] arrays), or (None, None) when nothing committed
        yet (or the history kinds are mixed — recovery refuses those)."""
        if any(self._id_rows):
            if self._history:
                return None, None
            return "ids", self._id_rows
        if self._history:
            full = np.concatenate(self._history, axis=1)
            return "hidden", [full[i] for i in range(full.shape[0])]
        return None, None

    def _full_chains(self, ps: int) -> list[list[str]] | None:
        """Per-row hash chains over the session's FULL committed history
        (prompt + generated) at page size `ps`, extended incrementally —
        sealed pages already hashed are never rehashed."""
        kind, rows = self._history_rows()
        if kind is None:
            return None
        from bloombee_tpu.kv.prefix import hidden_hash_chain, page_hash_chain

        fn = page_hash_chain if kind == "ids" else hidden_hash_chain
        cached = self._chains_by_ps.get(ps)
        chains = [
            fn(row, ps, chain=cached[i] if cached else None)
            for i, row in enumerate(rows)
        ]
        self._chains_by_ps[ps] = chains
        return chains

    def _init_repl(self) -> None:
        """(Re)select one standby per span for KV replication. A None slot
        means that span can't replicate: knob off, no page size advertised,
        the session uses a sub-span of the server (its pages would carry
        layers the session doesn't own), or no capable same-span
        alternative exists — all of which degrade to plain full-replay
        recovery, byte-for-byte today's behavior."""
        self._repl = [None] * len(self._spans)
        if self.repl_every <= 0 or not self.prefix_cache:
            return
        exclude = {s.span.peer_id for s in self._spans}
        for i, s in enumerate(self._spans):
            info = s.span.server_info
            if (
                info.page_size <= 0
                or s.span.start != info.start_block
                or s.span.end != info.end_block
            ):
                continue
            standby = self.manager.pick_standby(s.span, exclude=exclude)
            if standby is None:
                continue
            self._repl[i] = {
                "standby": {
                    "host": standby.server_info.host,
                    "port": standby.server_info.port,
                },
                "peer": standby.peer_id,
                "announced": [0] * self.batch_size,
            }

    def _standby_peers(self) -> set[str]:
        """Peers holding (some of) this session's replicated pages — the
        recovery route hint."""
        return {st["peer"] for st in self._repl or [] if st is not None}

    async def _maybe_replicate(self) -> None:
        """Announce newly-sealed pages to each span's server, which exports
        them off the critical path and kv_puts them into the standby's
        prefix pool. Fire-and-forget: no reply rides the stream (so the
        step recv loop never desyncs) and a failed send just leaves the
        pages for the next interval."""
        live = [st for st in self._repl if st is not None]
        if not live:
            self.repl_lag_pages = 0
            return
        kind, rows = self._history_rows()
        if kind is None:
            return
        lag = 0
        for s, st in zip(self._spans, self._repl):
            if st is None:
                continue
            ps = s.span.server_info.page_size
            sealed = [len(r) // ps for r in rows]
            behind = max(
                sl - a for sl, a in zip(sealed, st["announced"])
            )
            if behind < self.repl_every:
                lag = max(lag, behind)
                continue
            chains = self._full_chains(ps)
            if chains is None:
                return
            try:
                await s.stream.send(
                    {"kv_repl": {"standby": st["standby"], "chains": chains}},
                    [],
                )
            except (RpcError, OSError, asyncio.TimeoutError) as e:
                logger.debug("kv_repl announce failed: %s", e)
                lag = max(lag, behind)
                continue
            st["announced"] = sealed
        self.repl_lag_pages = lag

    # ------------------------------------------------------------------ steps
    async def step(
        self,
        hidden: np.ndarray,  # [B, T, D]
        commit: bool = True,
        tree_mask: np.ndarray | None = None,
        depths: np.ndarray | None = None,
        accept: list | None = None,
        ids: np.ndarray | None = None,  # [B, T]: enables token-id replay
        commit_lens: list | None = None,
        prune: dict | None = None,  # mid-chain tree pruning (tree steps)
        accept_per_span: list | None = None,  # pruned chains: accept per span
        rows: tuple | None = None,  # (lo, hi): hidden covers only this
        # contiguous row window of the session's cache; accept stays
        # full-width (servers apply it before slicing the handle)
        reply_tail: int | None = None,  # the caller reads only the last n
        # positions of each sequence (a prefill's caller: the last one):
        # the span that answers sends only those and the result is
        # [B, n, D]; a server that does not know the field sends every row
        # and the cut is made here. None: every row, as ever
    ) -> np.ndarray:
        """Push hidden through the whole chain; returns last span's output
        (or (output, keep) for pruned tree steps)."""
        attempt = 0
        overload_waits = 0
        resume_step = None  # (step_id, skip): retransmit after a resume
        while True:
            try:
                if self._needs_rebuild:
                    await self._recover()
                    self._needs_rebuild = False
                if prune is not None or accept_per_span is not None:
                    return await self._step_pruned(
                        hidden, tree_mask, depths, prune, accept_per_span
                    )
                send_hidden, skip, step_id = hidden, None, None
                if resume_step is not None:
                    # retransmit the exact failed step: same id (servers
                    # that applied it dedup instead of re-applying), same
                    # prefix skip (identical suffix bytes) — and no fresh
                    # probe, which would both waste a round trip and bump
                    # the server's last-applied step past the retransmit
                    step_id, skip = resume_step
                    resume_step = None
                    if skip:
                        send_hidden = hidden[:, skip:]
                elif (
                    # shared-prefix fast path: on the session's FIRST
                    # committed prefill, probe the chain's prefix pools and
                    # ship only the uncached suffix (the servers' KV for the
                    # skipped positions is adopted from pooled pages). The
                    # returned output covers only the suffix — callers
                    # consume the last position, which is always kept (the
                    # probe caps the skip below the prompt).
                    self.prefix_cache
                    and commit
                    and tree_mask is None
                    and ids is not None
                    and self.position == 0
                    and hidden.shape[1] > 1
                ):
                    skip = await self._probe_prefix(
                        [list(map(int, row)) for row in np.asarray(ids)]
                    )
                    if skip:
                        send_hidden = hidden[:, skip:]
                out = await self._step_once(
                    send_hidden, commit, tree_mask, depths, accept,
                    commit_lens, prefix_skip=skip, step_id=step_id,
                    rows=rows,
                    # a cross-replica audit compares whole outputs
                    reply_tail=None if (
                        self._gate is not None and self.audit_p > 0
                    ) else reply_tail,
                )
                if (
                    self._gate is not None
                    and self.audit_p > 0
                    and commit
                    and tree_mask is None
                    and rows is None
                    and self._audit_rng.random() < self.audit_p
                ):
                    # BEFORE the commit: a convicted primary raises here
                    # and the retry loop re-executes the step on an honest
                    # chain, so the lying output never reaches the caller
                    # and the committed history stays clean
                    await self._audit_step(out, ids, skip)
                if commit and tree_mask is None:
                    self._record_span_inputs(skip)
                    if ids is not None and self.embed_fn is not None:
                        for i, row in enumerate(np.asarray(ids)):
                            self._id_rows[i].extend(int(t) for t in row)
                    else:
                        self._history.append(hidden)
                    self.position += hidden.shape[1]
                    await self._maybe_replicate()
                return out if reply_tail is None else out[:, -reply_tail:]
            except OverloadedError as e:
                # retriable shed: the peer told us to go elsewhere, not that
                # it is broken. Separate (more generous) budget than fault
                # retries, honor the server's retry_after hint, then reroute
                # — the overload penalty in the manager steers the rebuilt
                # chain away from the hot peer.
                overload_waits += 1
                if overload_waits > self.overload_retries:
                    raise
                wait_s = min((e.retry_after_ms or 500) / 1000.0, 5.0)
                wait_s *= random.uniform(0.75, 1.25)
                logger.info(
                    "step shed by overloaded server (%s); rerouting in "
                    "%.2fs (shed %d/%d)",
                    e, wait_s, overload_waits, self.overload_retries,
                )
                await asyncio.sleep(wait_s)
                try:
                    await self._recover()
                    accept = None
                    accept_per_span = None
                except (
                    RpcError, OSError, asyncio.TimeoutError,
                    MissingBlocksError,
                ) as e2:
                    logger.warning("recovery after shed failed: %s", e2)
            except (RpcError, OSError, asyncio.TimeoutError) as e:
                attempt += 1
                if attempt > self.max_retries:
                    raise
                if (
                    self.resume
                    and self._last_sent is not None
                    and prune is None
                    and accept_per_span is None
                    # resume would retransmit to the SAME peer whose output
                    # an integrity check just rejected — and a lying
                    # server's at-most-once dedup would replay the recorded
                    # lie verbatim. Integrity rejects always take the full
                    # reroute+replay path.
                    and not isinstance(e, IntegrityError)
                ):
                    # cheap path first: re-attach the lease-parked sessions
                    # on fresh streams and retransmit the failed step under
                    # its original id — spans that already applied it answer
                    # from the recorded reply, so no KV is rebuilt and no
                    # prompt token is replayed
                    last = self._last_sent
                    if await self._try_resume():
                        resume_step = last
                        logger.info(
                            "step failed (%s); resumed session, "
                            "retransmitting step %d", e, last[0],
                        )
                        continue
                logger.warning(
                    "step failed (%s); re-routing (attempt %d)", e, attempt
                )
                try:
                    await self._recover()
                    # history replay already committed every accepted token
                    # on the fresh chain; the rebuilt servers have an empty
                    # speculative window, so a carried accept is stale
                    accept = None
                    accept_per_span = None
                except (
                    RpcError, OSError, asyncio.TimeoutError,
                    MissingBlocksError,
                ) as e2:
                    logger.warning("recovery attempt failed: %s", e2)
                    await asyncio.sleep(min(0.2 * attempt, 2.0))

    def _note_spans_ok(self) -> None:
        """A full step succeeded through every span: clear any ban history
        (half-open probes resolve to healthy; backoff resets to base)."""
        for s in self._spans:
            self.manager.note_peer_ok(s.span.peer_id)

    # ------------------------------------------------------------- integrity
    def _check_span_output(self, span_sess, resp_meta, chunk) -> None:
        """Inline checks on one received span-output chunk, run BEFORE the
        chunk enters the output buffer or gets relayed to the next span.
        Digest first (exact: the server hashed the exact bytes it
        serialized, so any in-flight corruption mismatches — this is a
        same-bytes check, never a cross-replica float compare), then the
        O(B*D) sanity gate (all-finite + activation-RMS envelope)."""
        span = span_sess.span
        digest = resp_meta.get("out_digest")
        verified = False
        if digest is not None:
            from bloombee_tpu.kv.prefix import out_digest

            if out_digest(chunk) != digest:
                # bytes changed BETWEEN serialization and us: that is
                # evidence against the wire, not the peer (a liar's digest
                # matches its lie) — ordinary short ban, no quarantine
                # strike, so ambient chaos corruption never convicts an
                # honest server of lying
                self._integrity_reject(
                    span.peer_id,
                    "out_digest mismatch (in-flight corruption)",
                    strike=False, ban=True,
                )
            verified = True
        reason = self._gate.check((span.start, span.end), chunk)
        if reason is not None:
            # a digest-VERIFIED gate reject is the peer's own computation
            # (the wire is ruled out): count a strike but do NOT ban, so
            # routing re-picks the peer and its next lie convicts it at
            # the strike limit — conviction needs repeat evidence, never
            # a single sample. Without a digest the wire could be at
            # fault, so the reroute also takes the safe short ban.
            self._integrity_reject(
                span.peer_id, reason, strike=True, ban=not verified
            )

    def _integrity_reject(
        self, peer_id: str, reason: str, strike: bool, ban: bool
    ) -> None:
        """An integrity check failed: raise into the session retry loop —
        integrity rejects heal exactly like crash faults (reroute +
        replay), they just never silently propagate a poisoned activation
        downstream. `strike=True` (the digest passed or was absent, yet
        the numbers are wrong: the peer COMPUTED garbage) counts a
        quarantine strike, tipping a repeat offender into quarantine;
        `ban` additionally takes the ordinary short fault ban so the
        rebuilt route avoids the peer right now."""
        self.sanity_rejects += 1
        self.integrity_reroutes += 1
        if strike:
            self.manager.note_integrity_strike(peer_id)
        if ban:
            self.manager.ban_peer(peer_id)
        logger.warning(
            "integrity reject: %s from peer %s; rerouting", reason, peer_id
        )
        raise IntegrityError(f"span output rejected ({reason})")

    def _record_span_inputs(self, skip) -> None:
        """Accumulate per-span input history for cross-replica audits.
        Relay-mode chunks are exactly span i+1's inputs, so recording them
        costs nothing extra; span 0 never records (its input re-embeds
        from the id history on demand). Anything that breaks completeness
        — prefix skip, push-mode multi-span hops the client never sees, a
        rerouted chain — invalidates the record and audits fall back to
        span 0 only."""
        if self._gate is None or self.audit_p <= 0 or len(self._spans) <= 1:
            return
        outs = self._last_span_outs
        if (
            skip
            or self.use_push
            or len(outs) != len(self._spans)
            or any(o is None for o in outs[:-1])
            or (
                self._span_in is not None
                and len(self._span_in) != len(self._spans)
            )
        ):
            self._span_in = None
            return
        if self._span_in is None:
            if self.position != 0:
                return  # history started before recording did: incomplete
            self._span_in = [[] for _ in self._spans]
        for i in range(1, len(self._spans)):
            self._span_in[i].append(outs[i - 1])

    def _find_covering(self, start: int, end: int, exclude: set):
        """Active (non-banned, non-quarantined) spans whose server covers
        [start, end), deterministically ordered."""
        spans = [
            s for s in self.manager._active_spans()
            if s.peer_id not in exclude and s.start <= start and s.end >= end
        ]
        spans.sort(key=lambda s: s.peer_id)
        return spans

    async def _remote_forward(self, span, start, end, hidden):
        """Re-execute blocks [start, end) over the full recorded input on
        `span`'s server via the sessionless rpc_forward plane. Returns the
        f32 output, or None when the server is unreachable or declines
        (hetero/host-offload spans have no training path) — an absent
        auditor is never evidence against anyone."""
        try:
            conn = await connect(
                span.server_info.host, span.server_info.port,
                keepalive_s=self.keepalive_s,
            )
        except (OSError, RpcError, asyncio.TimeoutError):
            return None
        try:
            meta = {"start": int(start), "end": int(end), "audit": True}
            if self.adapter:
                meta["adapter"] = self.adapter
            resp, tensors = await conn.call(
                "rpc_forward", meta,
                [np.ascontiguousarray(hidden, dtype=np.float32)],
                timeout=self.step_timeout,
            )
            if not resp.get("ok") or not tensors:
                return None
            return np.asarray(tensors[0], dtype=np.float32)
        except (OSError, RpcError, asyncio.TimeoutError):
            return None
        finally:
            try:
                await conn.close()
            except Exception:
                pass

    async def _audit_step(self, out, ids, skip) -> None:
        """Probabilistic activation audit: re-execute the step just
        received for one span S on a DIFFERENT server covering S, over
        S's full recorded input history (attention needs every previous
        position — a single-step re-execution would compare garbage), and
        tolerance-compare the last step's positions.

        NEVER exact equality: honest replicas differ in ulps because
        float reductions are batch-width dependent (the primary may have
        batched our rows with another session's). A digest fast-path
        short-circuits the compare when the replicas happen to agree
        bitwise; a mismatch escalates to the dtype-aware tolerance
        compare, never straight to a verdict. Disagreement within
        tolerance triggers a third-replica tiebreak when one exists; the
        outvoted peer is quarantined. No quorum -> suspicion strikes for
        both, conviction for neither."""
        from bloombee_tpu.kv.prefix import out_digest

        # choose an auditable span: 0 when the id history re-embeds
        # cleanly, plus any span with a complete relay input record
        candidates: list[int] = []
        if (
            self.embed_fn is not None
            and not self._history
            and ids is not None
            and len({len(r) for r in self._id_rows}) == 1
        ):
            candidates.append(0)
        if self._span_in is not None and len(self._span_in) == len(self._spans):
            candidates.extend(range(1, len(self._spans)))
        if not candidates:
            return
        i = candidates[self._audit_rng.randrange(len(candidates))]
        span_sess = self._spans[i]
        span = span_sess.span
        outs = self._last_span_outs
        primary_out = outs[i] if i < len(outs) else None
        if primary_out is None:
            return
        peers = self._find_covering(span.start, span.end, {span.peer_id})
        if not peers:
            return  # no alternative replica covers S on this topology
        # reconstruct span S's full input history (this step included —
        # the audit runs before the commit, so the id rows don't hold this
        # step's ids yet)
        if i == 0:
            rows = [
                list(r) + [int(t) for t in step_row]
                for r, step_row in zip(self._id_rows, np.asarray(ids))
            ]
            if len({len(r) for r in rows}) != 1:
                return
            full_in = np.asarray(
                self.embed_fn(np.asarray(rows, dtype=np.int64)),
                dtype=np.float32,
            )
        else:
            prev = outs[i - 1]
            if prev is None:
                return
            full_in = np.concatenate(self._span_in[i] + [prev], axis=1)
        self.audits_run += 1
        aud_out = await self._remote_forward(
            peers[0], span.start, span.end, full_in
        )
        if aud_out is None or aud_out.shape[1] < primary_out.shape[1]:
            return  # auditor unavailable: not evidence against the primary
        t_step = primary_out.shape[1]
        aud_tail = np.ascontiguousarray(aud_out[:, -t_step:])
        wire_dt = span.server_info.wire_dtype
        if out_digest(aud_tail) == out_digest(
            np.ascontiguousarray(primary_out)
        ):
            return  # bitwise agreement: cheap fast-path, nothing to judge
        if tensors_close(aud_tail, primary_out, dtype=wire_dt):
            return  # within tolerance: ulp drift, both honest
        self.audit_mismatches += 1
        third = self._find_covering(
            span.start, span.end, {span.peer_id, peers[0].peer_id}
        )
        third_out = (
            await self._remote_forward(
                third[0], span.start, span.end, full_in
            ) if third else None
        )
        if third_out is None or third_out.shape[1] < t_step:
            # no quorum: suspicion (not conviction) strikes both sides
            logger.warning(
                "audit mismatch on span [%d,%d) with no tiebreak replica: "
                "striking %s and %s", span.start, span.end, span.peer_id,
                peers[0].peer_id,
            )
            self.manager.note_integrity_strike(span.peer_id)
            self.manager.note_integrity_strike(peers[0].peer_id)
            return
        third_tail = np.ascontiguousarray(third_out[:, -t_step:])
        agrees_primary = tensors_close(third_tail, primary_out, dtype=wire_dt)
        agrees_auditor = tensors_close(third_tail, aud_tail, dtype=wire_dt)
        if agrees_primary and not agrees_auditor:
            logger.warning(
                "audit tiebreak: auditor %s outvoted; quarantining it",
                peers[0].peer_id,
            )
            self.manager.quarantine_peer(peers[0].peer_id)
            return
        if agrees_auditor and not agrees_primary:
            # primary convicted: quarantine and re-execute the step on an
            # honest chain (we ran before the commit, so history is clean)
            self.manager.quarantine_peer(span.peer_id)
            self.integrity_reroutes += 1
            raise IntegrityError(
                f"audit convicted span peer {span.peer_id} "
                f"(outvoted 2-to-1 on blocks [{span.start},{span.end}))"
            )
        # three-way disagreement: something is deeply wrong, but there is
        # no majority — strike everyone, convict no one
        for pid in (span.peer_id, peers[0].peer_id, third[0].peer_id):
            self.manager.note_integrity_strike(pid)

    async def _step_pruned(
        self, hidden, tree_mask, depths, prune, accept_per_span
    ):
        """Tree step through the chain with mid-chain pruning: span 0 runs
        the full tree and returns only surviving rows + keep indices; the
        client forwards the pruned tree (restricted mask/depths) downstream
        (relay mode only). Accepts may differ per span — downstream spans
        hold KV in kept-row order (reference backend.py:763-775 +
        block_functions.py restore_hidden_states, inverted client-side).

        Returns (out [B, K, D] fp32, keep [B, K] or None if the pruning
        span has no pruner weight)."""
        if not self._spans:
            raise RpcError("session chain is closed (recovery pending)")
        if self.use_push and len(self._spans) > 1:
            raise ValueError("pruned tree steps need relay mode (use_push=False)")
        assert tree_mask is not None and depths is not None
        step_id = self._step_counter
        self._step_counter += 1
        wire_dt = dtype_for_name(self._spans[0].span.server_info.wire_dtype)
        chunk = hidden.astype(wire_dt)
        mask_u8 = np.asarray(tree_mask).astype(np.uint8)
        depths_list = np.asarray(depths).tolist()
        keep = None

        send_ns = turn.now_ns()
        t_start = time.perf_counter()
        compute_ms = []
        for i, span_sess in enumerate(self._spans):
            meta = {
                "step": step_id,
                "commit": False,
                "tree": True,
                "depths": depths_list,
                "reply": "tensor",
                "deadline_s": self.step_timeout,
            }
            if accept_per_span is not None and accept_per_span[i] is not None:
                meta["accept"] = [
                    np.asarray(a).tolist() for a in accept_per_span[i]
                ]
            if i == 0 and prune is not None:
                meta["prune"] = prune
            try:
                if i == 0:
                    self._legs.ride(meta, span_sess.stream, send_ns)
                await span_sess.stream.send(meta, [chunk, mask_u8])
                item = await asyncio.wait_for(
                    span_sess.stream.recv(), self.step_timeout
                )
                self._legs.replied(span_sess.stream.read_ns)
            except OverloadedError as e:
                self._note_shed_exc(e, span_sess.span.peer_id)
                raise
            except (RpcError, OSError, asyncio.TimeoutError):
                self.manager.ban_peer(span_sess.span.peer_id)
                raise
            if item is None:
                self.manager.ban_peer(span_sess.span.peer_id)
                raise RpcError(f"span {i} closed mid-session")
            resp_meta, resp_tensors = item
            _raise_if_session_lost(resp_meta)
            self._raise_if_shed(resp_meta, span_sess.span.peer_id)
            compute_ms.append(resp_meta.get("t_compute_ms"))
            chunk = resp_tensors[0]
            if self._gate is not None:
                self._check_span_output(span_sess, resp_meta, chunk)
            if i == 0 and resp_meta.get("keep") is not None:
                from bloombee_tpu.spec.tree import pruned_step_arrays

                keep = np.asarray(resp_meta["keep"], dtype=np.int32)
                mask_k, depths_k = pruned_step_arrays(
                    np.asarray(tree_mask, dtype=bool),
                    np.asarray(depths),
                    keep,
                )
                mask_u8 = mask_k.astype(np.uint8)
                depths_list = depths_k.tolist()
        self._note_spans_ok()
        self.timings.append(
            {
                "step": step_id,
                "tokens": hidden.shape[1],
                "span_compute_ms": compute_ms,
                "total_ms": (time.perf_counter() - t_start) * 1000.0,
            }
        )
        return np.asarray(chunk, dtype=np.float32), keep

    async def _step_once(
        self, hidden, commit, tree_mask, depths=None, accept=None,
        commit_lens=None, prefix_skip=None, step_id=None, rows=None,
        reply_tail=None,
    ):
        if not self._spans:
            # a failed recovery left no open chain; surface as a retryable
            # wire error so the caller's retry loop attempts recovery again
            raise RpcError("session chain is closed (recovery pending)")
        if step_id is None:
            step_id = self._step_counter
            self._step_counter += 1
        # remembered for reconnect-resume: a retransmit after a resumed
        # stream must reuse this exact id (the server's at-most-once dedup
        # keys on it) and the same prefix_skip (same suffix bytes)
        self._last_sent = (step_id, prefix_skip)
        send_ns = turn.now_ns()
        meta_base = {
            "step": step_id,
            "commit": commit,
            "tree": tree_mask is not None,
            # remaining-time budget: the server aborts work this client
            # has already given up on (it shrinks the budget by its own
            # elapsed time before forwarding down a push route)
            "deadline_s": self.step_timeout,
        }
        if depths is not None:
            meta_base["depths"] = np.asarray(depths).tolist()
        if accept is not None:
            meta_base["accept"] = [np.asarray(a).tolist() for a in accept]
        if commit_lens is not None:
            meta_base["commit_lens"] = [int(x) for x in commit_lens]
        if prefix_skip is not None:
            # settle the preceding probe: servers keep exactly this many
            # adopted tokens per row (0 drops the adoption). Present on
            # every mb chunk and relay forward via **meta_base.
            meta_base["prefix_skip"] = int(prefix_skip)
        # ship hidden in the first span's advertised wire dtype (bf16 for
        # bf16-compute servers: half the bytes on the latency-critical hop)
        first_info = self._spans[0].span.server_info
        wire_dt = np.dtype(dtype_for_name(first_info.wire_dtype))
        extra = [tree_mask.astype(np.uint8)] if tree_mask is not None else []
        # only the span that answers the caller cuts its reply: in push mode
        # the field rides the route to it, in relay mode the client tells it
        tail = None
        if reply_tail is not None and tree_mask is None:
            tail = min(int(reply_tail), hidden.shape[1])

        # within-stage micro-batching: plain committed steps only (tree/
        # accept steps keep whole-batch semantics)
        b = hidden.shape[0]
        mb = self.microbatch
        if mb == "auto":
            # size chunks to the pipeline depth (reference
            # microbatch_config.py:84-130 derives the count from the
            # deployment, not a constant): overlap pays when there is more
            # than one stage, and more chunks than stages adds per-chunk
            # overhead without more overlap
            mb = (
                min(b, max(2, len(self._spans)))
                if len(self._spans) > 1 and b > 1
                else 1
            )
        if (
            tree_mask is not None
            or accept is not None
            or commit_lens is not None
            or mb > b
        ):
            mb = 1
        # live-row window (tree steps): hidden carries only rows
        # [rows[0], rows[1]) of the cache — the servers slice their handle
        # to that window, so finished rows stop burning tree slots. All
        # row labels on the wire stay ABSOLUTE; row_base maps them back
        # onto this window-sized hidden/out.
        row_base = 0
        if rows is not None:
            lo_r, hi_r = int(rows[0]), int(rows[1])
            if hi_r - lo_r != b:
                raise ValueError(
                    f"rows window {rows} does not match hidden batch {b}"
                )
            mb = 1
            row_base = lo_r
            bounds = [(lo_r, hi_r)]
        else:
            bounds = [
                (round(k * b / mb), round((k + 1) * b / mb))
                for k in range(mb)
            ]

        # a plain committing prefill to a first span that says which chunk
        # length it plans with goes as ONE step in PARTS along the sequence
        # (one id, one deadline, one reply): each part is cast, encoded and
        # written in turn, and the span computes the chunks of one while the
        # next is made here. Anything else, and a prompt of one part's
        # size, is one part: the frame it always was
        t = hidden.shape[1]
        per = max(1, t)
        chunk = first_info.prefill_chunk
        if (
            isinstance(chunk, int) and chunk > 0 and mb == 1
            and tree_mask is None and commit and commit_lens is None
            and rows is None
        ):
            per = _part_rows(chunk, b * hidden.shape[2] * wire_dt.itemsize)
        cuts = [(r, min(r + per, t)) for r in range(0, max(1, t), per)]

        route = []
        if self.use_push and len(self._spans) > 1:
            route = [
                {
                    "host": s.span.server_info.host,
                    "port": s.span.server_info.port,
                    "session_id": s.session_id,
                }
                for s in self._spans[1:]
            ]
        for k, (lo, hi) in enumerate(bounds):
            meta = {
                **meta_base,
                "reply": "tensor",
                "mb": k,
                "mb_of": mb,
                "rows": [lo, hi],
            }
            if route:
                meta["route"] = route
            if tail and (route or len(self._spans) == 1):
                meta["reply_tail"] = tail
            if len(cuts) > 1:
                meta["parts"] = [len(cuts), t]
            for part, (r0, r1) in enumerate(cuts):
                if part:
                    meta = {"step": step_id, "part": part}
                piece = hidden[lo - row_base:hi - row_base, r0:r1].astype(
                    wire_dt
                )
                if k == 0 and part == 0:
                    # the turn's entry rides the step's first frame: its
                    # `c_send` is the first part's, the rest lie under
                    # the span's `served`
                    self._legs.ride(meta, self._spans[0].stream, send_ns)
                await self._spans[0].stream.send(meta, [piece] + extra)

        t_start = time.perf_counter()
        out = np.zeros(hidden.shape, dtype=np.float32)
        if tail:
            last_out = np.zeros(
                (hidden.shape[0], tail, hidden.shape[2]),
                dtype=np.float32,
            )
        got_tensor = False
        compute_ms = []
        # per-span outputs this step (audit records): span i's tensor
        # chunks land on span i's stream in both relay and push mode
        span_outs: list = [None] * len(self._spans)
        for i, span_sess in enumerate(self._spans):
            span_ms = 0.0
            for _ in range(mb):
                try:
                    item = await asyncio.wait_for(
                        span_sess.stream.recv(), self.step_timeout
                    )
                except OverloadedError as e:
                    self._note_shed_exc(e, span_sess.span.peer_id)
                    raise
                except (RpcError, OSError, asyncio.TimeoutError):
                    self.manager.ban_peer(span_sess.span.peer_id)
                    raise
                if item is None:
                    self.manager.ban_peer(span_sess.span.peer_id)
                    raise RpcError(f"span {i} closed mid-session")
                self._legs.replied(span_sess.stream.read_ns)
                resp_meta, resp_tensors = item
                _raise_if_session_lost(resp_meta)
                self._raise_if_shed(resp_meta, span_sess.span.peer_id)
                if resp_meta.get("t_compute_ms") is not None:
                    span_ms += resp_meta["t_compute_ms"]
                if resp_meta.get("ack"):
                    continue
                lo, hi = resp_meta.get("rows") or (row_base, row_base + b)
                chunk = resp_tensors[0]
                if self._gate is not None:
                    # inline integrity: digest + sanity gate BEFORE this
                    # chunk enters `out` or gets forwarded to the next span
                    self._check_span_output(span_sess, resp_meta, chunk)
                if tail and i + 1 == len(self._spans):
                    # the answer: cut by the server, or by an older one not
                    last_out[lo - row_base:hi - row_base] = np.asarray(
                        chunk[:, -tail:], dtype=np.float32
                    )
                else:
                    out[lo - row_base:hi - row_base] = np.asarray(
                        chunk, dtype=np.float32
                    )
                got_tensor = True
                if self._gate is not None and self.audit_p > 0:
                    buf = span_outs[i]
                    if buf is None:
                        buf = span_outs[i] = np.zeros(
                            hidden.shape, dtype=np.float32
                        )
                    buf[lo - row_base:hi - row_base] = np.asarray(
                        chunk, dtype=np.float32
                    )
                if not self.use_push and i + 1 < len(self._spans):
                    # relay mode: forward each chunk as it lands so the next
                    # span starts while this span computes the next chunk
                    fwd_meta = {
                        **meta_base,
                        "reply": "tensor",
                        "mb": resp_meta.get("mb", 0),
                        "mb_of": mb,
                        "rows": [lo, hi],
                    }
                    if tail and i + 2 == len(self._spans):
                        fwd_meta["reply_tail"] = tail
                    await self._spans[i + 1].stream.send(
                        fwd_meta, [chunk] + extra
                    )
            compute_ms.append(span_ms)
        assert got_tensor, "no span returned a tensor"
        self._last_span_outs = span_outs
        self._note_spans_ok()
        total_ms = (time.perf_counter() - t_start) * 1000.0
        self.timings.append(
            {
                "step": step_id,
                "tokens": hidden.shape[1],
                "span_compute_ms": compute_ms,
                "total_ms": total_ms,
            }
        )
        return last_out if tail else out

    async def decode_n(
        self,
        ids: np.ndarray,  # [B] int: input token of the first step
        n: int,
        eos_token_id: int | None = None,
        finished: np.ndarray | None = None,  # [B] bool rows already at EOS
        head_dtype: str | None = None,  # client's lm_head dtype; servers
        # decline on mismatch so logits stay identical across both paths
    ) -> np.ndarray:
        """Server-side multi-step greedy decode: one RPC returns [B, n] token
        ids — the round-trip-amortizing fast path. Single-span routes run
        the fused on-device scan (runtime/decode_loop.py) or the server's
        host-driven loop; multi-span routes run CHAINED decode: span 0
        embeds and coordinates, hidden states hop server-to-server via
        rpc_push, the tail span applies norm+head+select and pushes each
        next id back to span 0, which replies all n ids at once. Either
        way the client pays ONE round trip per n tokens. Raises
        DecodeNUnsupported when the server declines, so the caller can
        fall back to per-step decoding.

        The servers write n tokens of KV (the input token plus the first
        n-1 selected tokens), so position advances by n and those ids enter
        the replay history.

        Exactness caveat: a chunk whose context CROSSES the paged-attention
        crossover (BBTPU_PAGED_MIN_CONTEXT) runs one kernel for the whole
        chunk while the per-step path would switch mid-way; the kernels
        agree to ~1e-5, so only an exact argmax tie at the boundary could
        differ (runtime/executor.py decode_n gating)."""
        if self.embed_fn is None and not self._warned_no_embed:
            # ids recorded without an embed_fn cannot be replayed: a later
            # transient transport failure becomes a hard RuntimeError in
            # _recover instead of a transparent re-route (fail-loud is
            # intentional; the warning makes the trade visible up front).
            # WARNING once per process, DEBUG for later sessions — a client
            # spawning many raw sessions repeats the identical line
            global _warned_no_embed_process
            self._warned_no_embed = True
            log = (
                logger.debug if _warned_no_embed_process else logger.warning
            )
            _warned_no_embed_process = True
            log(
                "decode_n on a session without embed_fn: the session loses "
                "failure recovery (id history cannot be re-embedded); use "
                "model.inference_session() for recoverable decode"
            )
        self._check_decode_n_route()
        ids = np.asarray(ids).reshape(-1).astype(np.int32)
        attempt = 0
        overload_waits = 0
        resume_step = None  # step_id to retransmit after a resume
        while True:
            try:
                if self._needs_rebuild:
                    await self._recover()
                    self._needs_rebuild = False
                    self._check_decode_n_route()
                step_id, resume_step = resume_step, None
                toks = await self._decode_n_once(
                    ids, n, eos_token_id, finished, head_dtype,
                    step_id=step_id,
                )
            except OverloadedError as e:
                # retriable shed (see step()): separate budget, honor the
                # retry hint, reroute via the overload-penalized manager
                overload_waits += 1
                if overload_waits > self.overload_retries:
                    raise
                wait_s = min((e.retry_after_ms or 500) / 1000.0, 5.0)
                wait_s *= random.uniform(0.75, 1.25)
                logger.info(
                    "decode_n shed by overloaded server (%s); rerouting in "
                    "%.2fs (shed %d/%d)",
                    e, wait_s, overload_waits, self.overload_retries,
                )
                await asyncio.sleep(wait_s)
                try:
                    await self._recover()
                    self._needs_rebuild = False
                    self._check_decode_n_route()
                except (
                    RpcError, OSError, asyncio.TimeoutError,
                    MissingBlocksError,
                ) as e2:
                    logger.warning("recovery after shed failed: %s", e2)
                continue
            except (RpcError, OSError, asyncio.TimeoutError) as e:
                attempt += 1
                if attempt > self.max_retries:
                    raise
                if self.resume and self._last_sent is not None:
                    # cheap path first (see step()): re-attach the parked
                    # sessions and retransmit the chunk under its original
                    # id; a coordinator that already finished it replies
                    # the recorded [B, n] tokens (at-most-once). A chunk
                    # that died mid-commit leaves the server kv_dirty, so
                    # its park is refused and this decline is immediate.
                    last = self._last_sent
                    if await self._try_resume():
                        resume_step = last[0]
                        logger.info(
                            "decode_n failed (%s); resumed session, "
                            "retransmitting step %d", e, last[0],
                        )
                        continue
                logger.warning(
                    "decode_n failed (%s); re-routing (attempt %d)",
                    e, attempt,
                )
                try:
                    await self._recover()
                    # recovery replayed the full history; a dirty-decline's
                    # pending rebuild is satisfied
                    self._needs_rebuild = False
                    self._check_decode_n_route()
                except (
                    RpcError, OSError, asyncio.TimeoutError,
                    MissingBlocksError,
                ) as e2:
                    logger.warning("recovery attempt failed: %s", e2)
                    await asyncio.sleep(min(0.2 * attempt, 2.0))
                continue
            # KV now holds [input, toks[:, :-1]] per row: record for replay
            written = np.concatenate([ids[:, None], toks[:, :-1]], axis=1)
            for i, row in enumerate(written):
                self._id_rows[i].extend(int(t) for t in row)
            self.position += n
            self._span_in = None  # server-side hops: no relay record
            await self._maybe_replicate()
            return toks

    def _check_decode_n_route(self) -> None:
        """decode_n needs a route whose spans cover the whole model: span 0
        embeds (must enter at block 0) and the tail applies the head (must
        end at the last block). Multi-span routes additionally chain via
        server-to-server push."""
        if not self._spans:
            return  # closed chain surfaces as RpcError in _decode_n_once
        if (
            self._spans[0].span.start != 0
            or self._spans[-1].span.end != self.manager.num_blocks
        ):
            raise DecodeNUnsupported(
                "route does not cover the whole model"
            )
        if len(self._spans) > 1 and not self.use_push:
            raise DecodeNUnsupported(
                "chained decode_n needs push transport (use_push=True)"
            )

    async def _decode_n_once(
        self, ids, n, eos_token_id, finished, head_dtype=None, step_id=None
    ) -> np.ndarray:
        if not self._spans:
            raise RpcError("session chain is closed (recovery pending)")
        if step_id is None:
            step_id = self._step_counter
            self._step_counter += 1
        # remembered for reconnect-resume: a retransmit after a resumed
        # stream must reuse this exact id so a coordinator that already
        # finished the chunk answers from its recorded reply
        self._last_sent = (step_id, None)
        meta = {
            "step": step_id,
            "decode_n": int(n),
            "reply": "tensor",
            # matches the client's own recv budget below: once that expires
            # the client re-routes, so any remaining server work is wasted
            "deadline_s": 2 * self.step_timeout + float(n),
        }
        if eos_token_id is not None:
            meta["eos_token_id"] = int(eos_token_id)
        if finished is not None:
            meta["finished"] = np.asarray(finished, dtype=bool).tolist()
        if head_dtype is not None:
            meta["head_dtype"] = head_dtype
        if len(self._spans) > 1:
            # chained decode: span 0 coordinates; give it the downstream
            # hops (same wire shape as the per-step push route)
            meta["route"] = [
                {
                    "host": s.span.server_info.host,
                    "port": s.span.server_info.port,
                    "session_id": s.session_id,
                }
                for s in self._spans[1:]
            ]
        span_sess = self._spans[0]
        t_start = time.perf_counter()
        try:
            self._legs.ride(meta, span_sess.stream, turn.now_ns())
            await span_sess.stream.send(meta, [ids])
            # one RPC covers n whole-model steps; chained routes also pay
            # per-token server-to-server hops and may hit cold XLA
            # compiles on MIDDLE/TAIL spans (the coordinator itself allows
            # chain_step_timeout=120s per hop for that) — budget at least
            # two cold compiles so a healthy coordinator is never banned
            # for its downstream spans' first-step compile time
            item = await asyncio.wait_for(
                span_sess.stream.recv(), 2 * self.step_timeout + float(n)
            )
        except OverloadedError as e:
            self._note_shed_exc(e, span_sess.span.peer_id)
            raise
        except (RpcError, OSError, asyncio.TimeoutError):
            self.manager.ban_peer(span_sess.span.peer_id)
            raise
        if item is None:
            self.manager.ban_peer(span_sess.span.peer_id)
            raise RpcError("span closed mid-session")
        self._legs.replied(span_sess.stream.read_ns)
        resp_meta, resp_tensors = item
        _raise_if_session_lost(resp_meta)
        self._raise_if_shed(resp_meta, span_sess.span.peer_id)
        if resp_meta.get("decode_n_unsupported"):
            if resp_meta.get("dirty"):
                # a chained decode failed mid-way: spans hold ragged extra
                # KV beyond the committed history — rebuild-and-replay on
                # the session's next use restores exact state
                self._needs_rebuild = True
            if resp_meta.get("transient"):
                # a span died mid-chain (not a capability decline): surface
                # as a wire error so the retry loop rebuilds the route,
                # replays, and RETRIES chained decode instead of dropping
                # the fast path for the rest of the generation
                raise RpcError(
                    resp_meta.get("reason") or "chained decode_n failed"
                )
            raise DecodeNUnsupported(
                resp_meta.get("reason")
                or "server declined decode_n for this session"
            )
        self._note_spans_ok()
        self.timings.append(
            {
                "step": step_id,
                "tokens": n,
                "decode_n": True,
                "span_compute_ms": [resp_meta.get("t_compute_ms")],
                "total_ms": (time.perf_counter() - t_start) * 1000.0,
            }
        )
        return np.asarray(resp_tensors[0], dtype=np.int64)

    async def send_accept(
        self, accept: list, per_span: list | None = None
    ) -> None:
        """Apply a speculative accept on every span without running compute
        (the final accept of a generation, or an accept with no next tree).
        `per_span` overrides the accept for each span (pruned chains hold KV
        in kept-row order downstream)."""
        step_id = self._step_counter
        self._step_counter += 1
        for i, span_sess in enumerate(self._spans):
            acc = accept if per_span is None else per_span[i]
            meta = {
                "step": step_id,
                "accept": [np.asarray(a).tolist() for a in acc],
                "accept_only": True,
                "reply": "ack",
            }
            await span_sess.stream.send(meta, [])
        for i, span_sess in enumerate(self._spans):
            item = await asyncio.wait_for(
                span_sess.stream.recv(), self.step_timeout
            )
            if item is None:
                raise RpcError(f"span {i} closed during accept")

    def rewind_decoded_tail(self, n_drop: int) -> None:
        """Drop the last `n_drop` tokens from the committed history (every
        row) after a decode_n chunk over-ran an EOS stop. The server-side KV
        still holds them, so the chain is marked for a rebuild-and-replay on
        the session's next use — which restores exactly the rewound context.
        Requires embed_fn (the replay re-embeds ids)."""
        if self.embed_fn is None:
            raise ValueError(
                "rewind_decoded_tail needs a session with embed_fn to "
                "replay the rewound history"
            )
        if n_drop <= 0:
            return
        for row in self._id_rows:
            del row[len(row) - n_drop:]
        self.position -= n_drop
        # incremental chains cover tokens that no longer exist: rehash
        self._chains_by_ps.clear()
        self._span_in = None  # relay records cover dropped tokens too
        self._needs_rebuild = True

    def record_history_ids(self, rows: list[list[int]]) -> None:
        """Ragged per-row committed token ids (batched speculative rounds:
        each row accepts a different count). Requires embed_fn — id history
        can only be replayed by re-embedding."""
        if self.embed_fn is None:
            raise ValueError(
                "record_history_ids needs a session with embed_fn "
                "(model.inference_session provides it)"
            )
        for i, row in enumerate(rows):
            self._id_rows[i].extend(int(t) for t in row)
        # committed via the speculative window: no relay input record
        self._span_in = None

    # -------------------------------------------------------------- recovery
    async def _try_resume(self) -> bool:
        """Cheap half of recovery: reopen each span with `resume:
        session_id` so the server re-attaches our lease-parked session to
        the fresh stream — KV intact, nothing to replay. All-or-nothing
        across spans: any decline (lease expired, leases off, parked pages
        evicted, old server) abandons the whole attempt and the caller
        falls back to the ordinary standby/full-replay path. On success
        the caller retransmits the failed step under its ORIGINAL id;
        spans that already applied it answer from their recorded reply
        (at-most-once), the rest compute it fresh."""
        if not self.resume or not self._spans:
            return False
        old = self._spans
        fresh: list[_SpanSession] = []
        ok = True
        reason = None
        for s in old:
            try:
                conn = await connect(
                    s.span.server_info.host, s.span.server_info.port,
                    keepalive_s=self.keepalive_s,
                )
                stream = await conn.open_stream(
                    "rpc_inference",
                    {
                        "resume": s.session_id,
                        # session_id rides along so the wire trace stays
                        # self-describing; resume-aware servers key off
                        # "resume" alone
                        "session_id": s.session_id,
                        "client_id": self.client_id,
                    },
                )
                fresh.append(_SpanSession(s.span, conn, stream, s.session_id))
                item = await asyncio.wait_for(
                    stream.recv(), self.resume_timeout
                )
                resp_meta = item[0] if item is not None else {}
                if not resp_meta.get("resumed"):
                    ok = False
                    reason = resp_meta.get("reason", "stream closed")
                    break
            except (RpcError, OSError, asyncio.TimeoutError) as e:
                ok = False
                reason = str(e) or type(e).__name__
                break
        if not ok:
            self.resume_declines += 1
            logger.info("session resume declined (%s); falling back to "
                        "full recovery", reason)
            for sp in fresh:
                await sp.close()
            return False
        # the dead streams' conns linger half-open on our side too: abort
        # them so nothing keeps pinging a connection we just superseded
        for sp in old:
            try:
                sp.conn.abort("superseded by resume")
            except Exception:
                pass
        self._spans = fresh
        self.resumed_streams += len(fresh)
        return True

    async def _recover(self) -> None:
        """Rebuild the entire chain and replay history
        (v1 of reference `_update_sequence`: suffix-only rebuild is an
        optimization; full rebuild is correct because servers key KV caches by
        session, and new sessions start empty).

        Route selection prefers peers holding this session's replicated
        pages (the standby hint), so the probe below usually adopts them
        and the replay shrinks to the unsealed tail. A bounded retry loop
        wraps rebuild + replay: each failed attempt bans the offending
        peer (existing backoff machinery), so the next attempt routes
        around it instead of one flaky standby killing the session."""
        if any(self._id_rows) and self.embed_fn is None:
            # id history can only be replayed by re-embedding; a session
            # that recorded ids without an embed_fn (e.g. decode_n from a
            # raw-hidden harness) must fail loudly, not resume with an
            # empty-KV chain
            await self.close()
            raise RuntimeError(
                "session recorded token-id history but has no embed_fn to "
                "replay it"
            )
        if any(self._id_rows) and self._history:
            # both histories populated -> replay interleaving is unknowable;
            # refuse before touching the chain (sessions must record ids
            # consistently: pass ids= to step / record_history_ids)
            await self.close()
            raise RuntimeError(
                "session mixed token-id and hidden-state history; replay "
                "order is ambiguous"
            )
        await self.close()
        attempts = max(1, int(self.max_retries))
        last_exc: Exception | None = None
        for attempt in range(attempts):
            if attempt:
                await asyncio.sleep(min(0.2 * attempt, 1.0))
            try:
                await self._recover_once()
                ledger.recovery("client.reroute_replay")
                return
            except (
                RpcError, OSError, asyncio.TimeoutError, MissingBlocksError,
            ) as e:
                # MissingBlocksError is retriable here: a span can go dark
                # for a beat while the swarm self-heals (standby promoting
                # after the primary died) — give the heal the same bounded
                # retry budget a flaky peer gets
                last_exc = e
                await self.close()
                logger.warning(
                    "recovery attempt %d/%d failed: %s",
                    attempt + 1, attempts, e,
                )
                if isinstance(e, OverloadedError):
                    # replay prefill shed by the rebuilt chain: honor the
                    # retry hint so back-to-back rebuilds don't hammer a
                    # swarm that is uniformly hot
                    await asyncio.sleep(
                        min((e.retry_after_ms or 500) / 1000.0, 2.0)
                    )
        raise last_exc

    async def _recover_once(self) -> None:
        """One rebuild + replay attempt (see _recover)."""
        self._legs.entering()
        await self.manager.update(force=True)
        route = self.manager.make_sequence(
            cache_tokens_needed=self.batch_size * self.max_length,
            relay=not self.use_push,
            prefer=self._standby_peers() or None,
        )
        spans: list[_SpanSession] = []
        try:
            for s in route:
                try:
                    spans.append(await self._open_span(s))
                except OverloadedError as e:
                    # session-open shed: short overload penalty, not a
                    # fault ban — the peer is healthy, just hot
                    self._note_shed_exc(e, s.peer_id)
                    raise
                except (OSError, RpcError, asyncio.TimeoutError):
                    self.manager.ban_peer(s.peer_id)
                    raise
        except Exception:
            for sp in spans:
                await sp.close()
            raise
        self._spans = spans
        self._legs.opened()
        # the rebuilt chain may have different span boundaries and replays
        # skip relay recording: spans > 0 lose auditability (span 0 keeps
        # it — its input always re-embeds from the id history)
        self._span_in = None
        self._last_span_outs = []
        try:
            if self.embed_fn is not None and any(self._id_rows):
                # token-id replay (ragged rows): right-pad to a rectangle,
                # write speculatively, then commit each row to its true
                # length — padded garbage lands after a row's real tokens so
                # the causal mask hides it, and commit_lens frees its pages
                lens = [len(r) for r in self._id_rows]
                width = max(lens)
                padded = np.zeros((self.batch_size, width), np.int64)
                for i, r in enumerate(self._id_rows):
                    padded[i, : len(r)] = r
                # a prior session (this one, before it failed) likely left
                # its prompt pages in the servers' prefix pools — and a
                # standby holds whatever was replicated — probe so the
                # replay re-embeds and re-ships only the uncached suffix.
                # Chains come from the RAGGED rows, never the padded
                # rectangle: pad garbage must not hash-alias a pooled page
                # of real zeros. commit_lens are absolute, so they need no
                # adjustment for the adopted offset.
                skip = 0
                if self.prefix_cache:
                    skip = await self._probe_prefix(
                        [list(r) for r in self._id_rows]
                    )
                replay = self.embed_fn(padded)
                # recovery owner: commit_lens commits server-side within
                # this same step; a failed replay just re-runs failover
                await self._step_once(  # bbtpu: noqa[BB001]
                    replay[:, skip:], commit=False, tree_mask=None,
                    commit_lens=lens, prefix_skip=skip,
                )
                self.failover_replayed_tokens += sum(
                    max(0, ln - skip) for ln in lens
                )
            elif self._history:
                # hidden-state history probes too: replicated/pooled pages
                # are keyed by hidden-byte chains for these sessions, so a
                # standby hit trims the replay exactly like the id path
                replay = np.concatenate(self._history, axis=1)
                skip = 0
                if self.prefix_cache:
                    skip = await self._probe_prefix(
                        hidden_rows=[
                            replay[i] for i in range(replay.shape[0])
                        ]
                    )
                await self._step_once(
                    replay[:, skip:], commit=True, tree_mask=None,
                    prefix_skip=skip if skip else None,
                )
                self.failover_replayed_tokens += replay.shape[0] * (
                    replay.shape[1] - skip
                )
        except Exception:
            # a half-replayed chain must not be reused: its KV caches are
            # incomplete and a later "successful" step would be garbage
            await self.close()
            raise
        # replicate to a fresh standby from now on (the old one is likely
        # on the new route — often it IS the new primary)
        self._init_repl()
