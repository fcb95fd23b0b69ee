"""CacheManager: token-budget admission + session cache lifecycle + host tiering.

TPU-native replacement for the reference's MemoryCache + KVCacheManager pair
(/root/reference/src/bloombee/server/memory_cache.py:83-460,
memory_cache_manager.py:28-2160). The reference splits allocation across
handler processes and a runtime process via pipes and shared mp.Values; the
JAX runtime is process-hostile, so here everything is one asyncio process and
the cross-process machinery collapses into an asyncio.Condition.

Capabilities kept:
- token-budget admission with timeout (memory_cache.py `_schedule_alloc`)
- handle -> per-sequence cache state, freed on context exit
- speculative write / commit / rollback via the PagedKVTable
- HBM <-> host-DRAM tiering at page granularity (the FlexGen offload
  capability, flexgen_utils/pytorch_backend.py TorchMixedDevice) via
  `park_sequence` / `unpark_sequence`: a parked sequence's KV moves to host
  numpy and its device pages are freed for other sessions.
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import functools
import itertools
import threading
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from bloombee_tpu.kv import arena as arena_ops
from bloombee_tpu.utils import clock, env, lockwatch

env.declare(
    "BBTPU_PARK_QUANT", bool, False,
    "quantize host-parked KV of dense arenas to int4 (4x less host DRAM)",
)
env.declare(
    "BBTPU_DISK_DIR", str, "",
    "directory for disk-parked KV memmaps (empty = system temp dir); the "
    "reference's TorchDisk tier",
)
env.declare(
    "BBTPU_KV_QUANT", str, "none",
    "KV cache quantization: none | int4 (group-wise 4-bit device arena + "
    "quantized host parking, ~3.2x token capacity; reference "
    "compression.py TorchCompressedDevice)",
)
env.declare(
    "BBTPU_PREFIX_CACHE", bool, False,
    "cross-session shared-prefix KV cache: finished sequences' committed "
    "prompt pages stay pooled under content hashes; new sessions whose "
    "prompt chain matches adopt them and prefill only the suffix "
    "(forces the pure-Python paged table)",
)
env.declare(
    "BBTPU_PREFIX_MAX_PAGES", int, 0,
    "cap on refcount-0 pages retained in the prefix pool "
    "(0 = bounded only by allocation pressure / LRU eviction)",
)


class AllocationTimeout(RuntimeError):
    pass


class ParkedKVLost(RuntimeError):
    """The background d2h copy of parked KV failed (e.g. disk full) after
    the device pages were already reused. The sequence's server-side KV is
    gone; the client recovers by replaying its token history onto a fresh
    allocation (the same path that handles a dead server)."""


class SessionKVLost(RuntimeError):
    """A session's KV no longer exists (the arena was rebuilt after a
    kernel failure consumed the donated buffers). Not a server fault: the
    server replies a typed `session_lost` so the client replays its token
    history onto a fresh chain WITHOUT banning the (healthy) peer
    (advisor, round 4)."""


@dataclasses.dataclass
class _Parked:
    """One parked sequence's KV: either still in flight to host (`future`
    resolves to the (k_host, v_host) tuple) or already resolved (`host`)."""

    l_acc: int
    l_seq: int
    host: tuple | None = None
    future: object | None = None  # concurrent.futures.Future

    def resolve(self) -> tuple:
        if self.host is None:
            try:
                self.host = self.future.result()
            except Exception as e:
                raise ParkedKVLost(
                    f"background park copy failed ({e!r}); KV for this "
                    "sequence is unrecoverable — replay the session"
                ) from e
            self.future = None
        return self.host


class _DaemonPool:
    """Two-worker submit() pool built on daemon threads.

    concurrent.futures.ThreadPoolExecutor joins its (non-daemon) workers at
    interpreter exit, so a d2h copy that never returns would keep the
    process from exiting. Daemon threads let the interpreter die with the
    copy still pending."""

    def __init__(self, max_workers: int = 2, name: str = "kv-park"):
        import concurrent.futures
        import queue

        self._futures = concurrent.futures
        self._q: queue.Queue = queue.Queue()
        for i in range(max_workers):
            threading.Thread(
                target=self._worker, name=f"{name}-{i}", daemon=True
            ).start()

    def _worker(self):
        while True:
            fut, fn = self._q.get()
            if fut.set_running_or_notify_cancel():
                try:
                    fut.set_result(fn())
                except BaseException as e:  # noqa: BLE001 — relay to waiter
                    fut.set_exception(e)

    def submit(self, fn):
        fut = self._futures.Future()
        self._q.put((fut, fn))
        return fut


def _locked(fn):
    """Serialize table/arena mutations across the compute thread and the
    event loop (see CacheManager._lock)."""

    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self._lock:
            return fn(self, *args, **kwargs)

    return wrapper


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _reorder_all_layers(ak, av, src, dst):
    """Compact surviving speculative rows across all layers in one fused
    gather+scatter (module-level jit: compiles once per slot-count bucket).
    Slabs are pytrees (dense array or int4 QuantSlab) — every leaf shares
    the [L, S, ...] slot layout, so the move maps over leaves."""

    def move(a):
        return a.at[:, dst].set(a[:, src], mode="drop")

    return jax.tree.map(move, ak), jax.tree.map(move, av)


def state_slots_for(spec, num_pages: int, page_size: int, max_batch: int) -> int:
    """How many sequences may hold a recurrent-state slot at once, derived
    from what the server is already told: as many as make the state arena
    the size of the K/V arena (a sequence's state costs what
    `ssm state bytes / kv bytes a token` tokens of context cost: 2048 at
    falcon-h1-34b's widths), and at least twice the batcher's width, so a
    full decode group and the sessions opening behind it all fit. 0 for a
    family without recurrent state.

    The rule was written for a family with both caches in every layer. A
    family whose layers keep ONE of them each (`spec.kinds_interleave`) gets twice the
    batcher's width and no more: its K/V arena is sized for contexts
    hundreds of times a state's worth of tokens in a quarter of the layers,
    and a state arena as large (82 slots, 1 GB at qwen3-next's widths under
    5376 pages) would hold states no session could own, since the K/V
    pages admit a few long sessions at a time."""
    ssm = spec.recurrent
    if ssm is None:
        return 0
    if spec.kinds_interleave or spec.mamba is not None:
        return 2 * int(max_batch)
    kv_token = 2 * spec.num_key_value_heads * spec.head_dim * 2  # bf16 K+V
    slot = arena_ops.state_slot_bytes(ssm)
    return max(2 * int(max_batch), (num_pages * page_size * kv_token) // slot)


@dataclasses.dataclass
class CacheHandle:
    handle_id: int
    seq_ids: list[int]
    max_length: int

    @property
    def batch_size(self) -> int:
        return len(self.seq_ids)


class CacheManager:
    _global_seq_counter = itertools.count()
    _global_handle_counter = itertools.count()

    def __init__(
        self,
        num_layers: int,
        num_pages: int,
        page_size: int,
        n_kv_heads: int,
        head_dim: int,
        dtype=None,
        quant: str | None = None,  # None -> BBTPU_KV_QUANT env default
        hetero_spec=None,  # ModelSpec with per-layer geometry (gemma-4)
        start_block: int = 0,
        oversubscribe: float = 1.0,  # admit up to this x capacity (parking)
        prefix_cache: bool | None = None,  # None -> BBTPU_PREFIX_CACHE env
        ssm=None,  # models.spec.SsmSpec: the family keeps recurrent state
        state_slots: int = 0,  # sequences that may hold a state slot at once
        payload=None,  # a latent-attention family's page payload
        # (models/spec.py MlaSpec.page_payload); None = K and V head slabs
        arena_layers: tuple[int, int] | None = None,  # (rows of the K/V
        # arena, rows of the state arena) where the span's layers keep ONE
        # cache kind each (models/spec.py `ModelSpec.arena_layers`); None =
        # every layer has a row in each arena the family has
        sharded: bool = False,  # the arena will be committed to a mesh on
        # its head axis (--tp): never stored folded (kv/arena.py)
    ):
        dtype = dtype or jnp.bfloat16
        if quant is None:
            quant = env.get("BBTPU_KV_QUANT")
        self.quant = None if quant in (None, "none") else quant
        if prefix_cache is None:
            prefix_cache = env.get("BBTPU_PREFIX_CACHE")
        # what this manager refused because a recurrent state can be kept
        # or zeroed but never cut, copied by pages or parked: by reason
        # (rpc_info `ragged_declines` carries them to health --probe)
        self.state_refusals: dict[str, int] = {}
        self.ssm = ssm
        kv_layers, state_layers = arena_layers or (num_layers, num_layers)
        self.kv_layers, self.state_layers = kv_layers, state_layers
        if ssm is not None and prefix_cache:
            # a pooled page holds K/V of a prefix; the state after that
            # prefix went with the session that wrote it
            self._refuse("prefix cache")
            prefix_cache = False
        self.prefix_cache = bool(prefix_cache)
        from bloombee_tpu.kv.paged_native import make_table

        self.table = make_table(
            num_pages, page_size, prefix_cache=self.prefix_cache
        )
        if self.prefix_cache:
            self.table.max_cached_pages = env.get("BBTPU_PREFIX_MAX_PAGES")
        # prefix-cache serving counters (rpc_info observability)
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        # KV replication receive counter (pages installed via kv_put)
        self.repl_pages_installed = 0
        # probe-adopted token counts per seq, consumed by trim_adopted once
        # the prefill's final skip arrives (also the idempotency guard: a
        # retried prefill must not trim real committed tokens)
        self._adopted: dict[int, int] = {}
        if hetero_spec is not None and hetero_spec.heterogeneous:
            from bloombee_tpu.runtime.hetero import make_hetero_arena

            self._make_arena = lambda: make_hetero_arena(
                hetero_spec, num_layers, start_block, num_pages, page_size,
                dtype, quant=self.quant,
            )
        else:
            self._make_arena = lambda: arena_ops.make_arena(
                kv_layers, num_pages, page_size, n_kv_heads, head_dim,
                dtype, quant=self.quant, payload=payload, sharded=sharded,
            )
        self.arena = self._make_arena()
        # whether the slabs are stored folded, [L, tokens * heads, head_dim]
        # (kv/arena.py `folds`: a rule of the shape), and the rows a token
        # takes along a slab's token axis: what `_rows` scales a slot by
        self.folded = (
            payload is None and getattr(self.arena["k"], "ndim", 0) == 3
        )
        self._fold = n_kv_heads if self.folded else 1
        # recurrent state beside the pages (kv/arena.py): one slot per
        # sequence and layer, taken at allocate() and given back at its
        # exit. A slot is never zeroed by a write of its own: a sequence at
        # position 0 starts from zeros inside the step (runtime/layer_body.py
        # reads `start == 0`), so a fresh slot, a rollback to 0 and a replay
        # from 0 are one case.
        self.state = None
        self._make_state = None
        self._state_slot: dict[int, int] = {}
        self._free_state_slots: list[int] = []
        # sequences whose state ran ahead of their pages (a rollback or a
        # cut to a position > 0): not servable until their length is 0
        self._state_lost: set[int] = set()
        self.num_state_slots = 0
        if ssm is not None:
            if state_slots < 1:
                raise ValueError("a family with recurrent state needs state_slots")
            if self.quant is not None or hetero_spec is not None:
                raise ValueError(
                    "recurrent state + quantized or heterogeneous KV arena "
                    "not supported together"
                )
            self.num_state_slots = int(state_slots)
            self._make_state = lambda: arena_ops.make_state_arena(
                state_layers, self.num_state_slots, ssm, dtype
            )
            self.state = self._make_state()
            self._free_state_slots = list(range(self.num_state_slots))[::-1]
        # bumped by rebuild_arena(); sessions opened under an older epoch
        # hold table state describing KV that no longer exists
        self.arena_epoch = 0
        # per-seq validity epoch: stamped at allocation, RE-stamped on
        # rebuild for sequences whose KV was host-parked at that moment
        # (their copies survive the rebuild, so they stay servable)
        self._seq_epoch: dict[int, int] = {}
        self._live_seqs: set[int] = set()
        self.num_layers = num_layers
        self.page_size = page_size
        self.capacity_tokens = num_pages * page_size
        self._reserved_tokens = 0
        self._cond: asyncio.Condition | None = None
        # PROCESS-wide counters (class attributes set below), not
        # per-manager: a server that rebalances swaps in a fresh manager
        # while old sessions' handles are still live — per-manager counters
        # restarting at 0 would alias an old handle's seq ids onto a new
        # session's KV (epoch_valid would then wrongly pass)
        self._seq_counter = CacheManager._global_seq_counter
        self._handle_counter = CacheManager._global_handle_counter
        self._parked: dict[int, _Parked] = {}
        # session-lease parking (wire half-open / client-death domain):
        # seq_id -> (per-page pool keys, committed length, arena epoch at
        # park time). Distinct from _parked (host d2h tiering) — the pages
        # stay device-resident as refcount-0 cached pool entries
        self._lease_parked: dict[int, tuple[list[str], int, int]] = {}
        # handles whose token reservation was returned at lease-park time
        # (allocate()'s exit must not subtract it a second time)
        self._lease_released: set[int] = set()
        # d2h copies of parked KV run here so parking never stalls the
        # compute thread (the copy engine half of the reference's async
        # offload, mcm.py:972-1335); 2 workers keep host-link order sane
        self._park_pool = None  # created lazily on first park
        # over-subscription (the FlexGen serve-more-than-HBM-fits story):
        # admission may reserve up to oversubscribe x capacity; physical
        # page pressure is relieved by the reclaimer callback (the server
        # parks idle sessions' KV to host) invoked from write/unpark paths
        self.oversubscribe = max(float(oversubscribe), 1.0)
        self.reclaimer = None  # callable(need_pages, exclude_seq_ids) -> int
        # table mutations happen on BOTH the compute thread (steps,
        # reclaim-parking) and the event loop (session teardown): a
        # reentrant lock keeps them atomic (reentrant because the reclaimer
        # runs inside write_slots/ensure_resident which already hold it)
        self._lock = lockwatch.thread_lock("kv.cache_manager", reentrant=True)

    @property
    def admit_limit(self) -> int:
        """Max reservable tokens (the load-bearing over-subscription
        invariant, derived in exactly one place)."""
        return int(self.capacity_tokens * self.oversubscribe)

    # reference: ServerInfo.cache_tokens_left (handler.py:3256-3273 rpc_info)
    @property
    def tokens_left(self) -> int:
        """Admittable tokens (scaled by oversubscribe — that IS the
        admission limit, so routing must see it, not raw capacity)."""
        return self.admit_limit - self._reserved_tokens

    def _condition(self) -> asyncio.Condition:
        if self._cond is None:
            self._cond = asyncio.Condition()
        return self._cond

    # ------------------------------------------------------------- admission
    @contextlib.asynccontextmanager
    async def allocate(
        self, batch_size: int, max_length: int, timeout: float | None = None
    ):
        """Async context manager reserving `batch_size * max_length` tokens.

        Mirrors KVCacheManager.allocate_cache (memory_cache_manager.py:391-420):
        blocks until the budget fits or the timeout elapses; frees everything
        on exit.
        """
        # charge page-granular budget: a sequence of max_length tokens pins
        # ceil(max_length / page_size) whole pages
        per_seq = -(-max_length // self.page_size) * self.page_size
        need = batch_size * per_seq
        admit_limit = self.admit_limit
        if need > admit_limit:
            raise AllocationTimeout(
                f"request for {need} tokens exceeds capacity "
                f"{admit_limit}"
            )
        if self.ssm is not None and batch_size > self.num_state_slots:
            raise AllocationTimeout(
                f"request for {batch_size} state slots exceeds the pool of "
                f"{self.num_state_slots}"
            )
        cond = self._condition()
        deadline = clock.deadline(timeout)
        async with cond:
            while (
                self._reserved_tokens + need > admit_limit
                or (self.ssm is not None
                    and len(self._free_state_slots) < batch_size)
            ):
                remaining = None
                if deadline is not None:
                    remaining = deadline - clock.monotonic()
                    if remaining <= 0:
                        raise AllocationTimeout(
                            f"timed out waiting for {need} cache tokens"
                        )
                try:
                    await clock.cond_wait(cond, remaining)
                except asyncio.TimeoutError:
                    raise AllocationTimeout(
                        f"timed out waiting for {need} cache tokens"
                    ) from None
            self._reserved_tokens += need
        handle = CacheHandle(
            handle_id=next(self._handle_counter),
            seq_ids=[next(self._seq_counter) for _ in range(batch_size)],
            max_length=max_length,
        )
        with self._lock:
            for sid in handle.seq_ids:
                self.table.add_seq(sid)
                self._seq_epoch[sid] = self.arena_epoch
                if self.ssm is not None:
                    self._state_slot[sid] = self._free_state_slots.pop()
            self._live_seqs.update(handle.seq_ids)
        try:
            yield handle
        finally:
            with self._lock:
                for sid in handle.seq_ids:
                    if self.table.has_seq(sid):
                        self.table.drop_seq(sid)
                    self._parked.pop(sid, None)
                    self._seq_epoch.pop(sid, None)
                    self._adopted.pop(sid, None)
                    self._live_seqs.discard(sid)
                    self._state_lost.discard(sid)
                    slot = self._state_slot.pop(sid, None)
                    if slot is not None:
                        self._free_state_slots.append(slot)
                    entry = self._lease_parked.pop(sid, None)
                    if entry is not None and hasattr(
                        self.table, "purge_parked"
                    ):
                        self.table.purge_parked(entry[0])
            async with cond:
                if handle.handle_id in self._lease_released:
                    # the reservation already went back at lease-park time
                    self._lease_released.discard(handle.handle_id)
                else:
                    self._reserved_tokens -= need
                cond.notify_all()

    # ----------------------------------------------------------- device plans
    @_locked
    def write_slots(
        self, handle: CacheHandle, num_tokens: int, commit: bool = True
    ) -> np.ndarray:
        """[B * num_tokens] flat slots for this step's new tokens (row-major
        batch-then-token order, matching hidden.reshape(B*T, ...)).

        Atomic across the batch: page availability is pre-checked so a
        mid-batch OutOfPages cannot leave earlier sequences claiming tokens
        that were never written.
        """
        table = self.table
        need = 0
        for sid in handle.seq_ids:
            st = table.seq(sid)
            need += max(
                0,
                -(-(st.l_seq + num_tokens) // self.page_size)
                - st.num_pages,
            )
        if need > table.free_pages and self.reclaimer is not None:
            # over-subscribed: evict idle sessions' KV to host and retry
            self.reclaimer(need - table.free_pages, set(handle.seq_ids))
        if need > table.free_pages:
            from bloombee_tpu.kv.paged import OutOfPages

            raise OutOfPages(
                f"batch write needs {need} pages, only "
                f"{table.free_pages} free"
            )
        slots = np.concatenate(
            [
                table.assign_write_slots(sid, num_tokens, commit=commit)
                for sid in handle.seq_ids
            ]
        )
        # copy-on-write pairs queued by the assigns must land on device
        # BEFORE the step scatters into `slots` (dispatch order == device
        # order, same guarantee parking relies on)
        self._apply_pending_copies()
        return slots

    @_locked
    def write_slots_ragged(
        self, handle: CacheHandle, counts: list[int], commit: bool = False
    ) -> np.ndarray:
        """write_slots with a PER-SEQUENCE token count: [sum(counts)] flat
        slots, sequence-major in handle.seq_ids order (matching the ragged
        mixed-batch packing, where decode members contribute 1 token and
        the prefill-chunk member contributes its whole chunk).

        Same atomicity contract as write_slots: availability is pre-checked
        across all members so a mid-group OutOfPages cannot leave earlier
        members claiming tokens that were never written.
        """
        if len(counts) != len(handle.seq_ids):
            raise ValueError(
                f"{len(counts)} counts for {len(handle.seq_ids)} sequences"
            )
        table = self.table
        need = 0
        for sid, n in zip(handle.seq_ids, counts):
            st = table.seq(sid)
            need += max(
                0,
                -(-(st.l_seq + int(n)) // self.page_size) - st.num_pages,
            )
        if need > table.free_pages and self.reclaimer is not None:
            self.reclaimer(need - table.free_pages, set(handle.seq_ids))
        if need > table.free_pages:
            from bloombee_tpu.kv.paged import OutOfPages

            raise OutOfPages(
                f"ragged write needs {need} pages, only "
                f"{table.free_pages} free"
            )
        slots = np.concatenate(
            [
                table.assign_write_slots(sid, int(n), commit=commit)
                for sid, n in zip(handle.seq_ids, counts)
            ]
        )
        self._apply_pending_copies()
        return slots

    @_locked
    def truncate_speculative(
        self, handle: CacheHandle, lengths: list[int]
    ) -> None:
        """Partial rollback to a pre-dispatch l_seq snapshot: undoes one
        failed dispatch's speculative writes without discarding earlier
        still-speculative tokens (mid-stream prefill chunks)."""
        for sid, length in zip(handle.seq_ids, lengths):
            self._state_cut(sid, int(length))
            self.table.truncate_speculative(sid, int(length))

    def page_table(self, handle: CacheHandle, max_pages: int) -> np.ndarray:
        return self.table.page_table(handle.seq_ids, max_pages)

    def context_lens(
        self, handle: CacheHandle, committed_only: bool = False
    ) -> np.ndarray:
        return self.table.context_lens(handle.seq_ids, committed_only)

    @_locked
    def commit(self, handle: CacheHandle, lengths: list[int] | None = None):
        for i, sid in enumerate(handle.seq_ids):
            if lengths is not None:
                self._state_cut(sid, int(lengths[i]))
            self.table.commit(sid, None if lengths is None else lengths[i])

    @_locked
    def rollback(self, handle: CacheHandle):
        for sid in handle.seq_ids:
            self._state_cut(sid, self.table.seq(sid).l_acc)
            self.table.rollback(sid)

    # ------------------------------------------------------- recurrent state
    def _refuse(self, reason: str) -> None:
        self.state_refusals[reason] = self.state_refusals.get(reason, 0) + 1

    def _state_cut(self, seq_id: int, length: int) -> None:
        """A sequence's pages are about to be cut to `length`. Its recurrent
        state (if the family has one) stands after every row it was fed and
        cannot follow: cut to 0 it starts from zeros again, cut to anything
        else it is lost, and `epoch_valid` says so until the session replays
        from 0 (the server answers `session_lost`, as after an arena
        rebuild). Caller holds the lock."""
        if self.ssm is None:
            return
        if length <= 0:
            self._state_lost.discard(seq_id)
        elif length < self.table.seq(seq_id).l_seq:
            self._state_lost.add(seq_id)

    def state_slots(self, handle: CacheHandle) -> np.ndarray:
        """[B] state slot of each sequence of `handle`."""
        return np.asarray(
            [self._state_slot[sid] for sid in handle.seq_ids], np.int32
        )

    def state_stats(self) -> dict:
        """rpc_info["memory"]["state"]: the pool's size, slots held, bytes."""
        from bloombee_tpu.utils.memory import tree_nbytes

        return {
            "slots": int(self.num_state_slots),
            "live": int(self.num_state_slots - len(self._free_state_slots)),
            "bytes": tree_nbytes(self.state) if self.state is not None else 0,
        }

    def accept_speculative(
        self, handle: CacheHandle, accepted_indices: list
    ) -> None:
        """Compact surviving speculative KV rows onto the committed prefix
        and commit them (the on-device replacement for the reference's async
        reorder thread, memory_cache_manager.py:2011-2160).

        `accepted_indices[i]` lists row i's surviving tree-relative indices
        in path order (depth 0, 1, ...).
        """
        if self.ssm is not None:
            self._refuse("speculative accept")
            raise ValueError(
                "speculative accept unsupported: recurrent state cannot be "
                "compacted onto the accepted rows"
            )
        # an over-subscribed server may have parked this session between
        # rounds. Unpark OUTSIDE the lock — ensure_resident's d2h resolve
        # must not run with the manager lock held — then re-check under
        # it: the reclaimer (serving another session) may park us again
        # in the gap.
        while True:
            self.ensure_resident(handle)
            with self._lock:
                if any(sid in self._parked for sid in handle.seq_ids):
                    continue
                return self._accept_speculative(handle, accepted_indices)

    def _token_shape(self, leaf) -> tuple[int, ...]:
        """What one token holds in an arena leaf, as the wire and the host
        see it: [heads, head_dim] also where the arena is stored folded."""
        if self.folded:
            return (self._fold, leaf.shape[-1])
        return tuple(leaf.shape[2:])

    def _rows(self, slots):
        """Token slots -> indices along the stored slabs' token axis (axis
        1): themselves, or a folded arena's `n_kv_heads` rows a token
        (kv/arena.py `slot_rows`). Every path that addresses the arena by
        slot outside a span step goes through here: the speculative
        compaction, copy-on-write, park / unpark, the replication export and
        install. An out-of-range slot stays out of range (dropped)."""
        return arena_ops.slot_rows(slots, self._fold)

    @_locked
    def _accept_speculative(
        self, handle: CacheHandle, accepted_indices: list
    ) -> None:
        src_all, dst_all = [], []
        for sid, idx in zip(handle.seq_ids, accepted_indices):
            st = self.table.seq(sid)
            idx = np.asarray(idx, dtype=np.int64)
            spec_slots = self.table.range_slots(sid, st.l_acc, st.l_seq)
            src_all.append(spec_slots[idx])
            dst_all.append(spec_slots[: len(idx)])
            self.table.accept(sid, len(idx))
        src = np.concatenate(src_all) if src_all else np.zeros(0, np.int32)
        dst = np.concatenate(dst_all) if dst_all else np.zeros(0, np.int32)
        keep = src != dst  # in-place rows need no move
        src, dst = src[keep], dst[keep]
        if len(src) == 0:
            return
        # pad to a small bucket so reorder compiles once per bucket
        from bloombee_tpu.runtime.executor import next_pow2

        n = next_pow2(len(src), floor=4)
        oob = self.capacity_tokens  # out-of-bounds slot => dropped scatter
        src_p = np.zeros((n,), np.int32)  # padded gathers read slot 0
        dst_p = np.full((n,), oob, np.int32)  # padded scatters are dropped
        src_p[: len(src)] = src
        dst_p[: len(dst)] = dst
        self.arena["k"], self.arena["v"] = _reorder_all_layers(
            self.arena["k"], self.arena["v"],
            jnp.asarray(self._rows(src_p)), jnp.asarray(self._rows(dst_p)),
        )

    def ensure_resident(self, handle: CacheHandle) -> None:
        """Unpark any parked sequences of this handle before a step (the
        demand-paging half of over-subscription), reclaiming pages from
        idle sessions when tight. Raises OutOfPages when nothing can be
        evicted — the client's retry path handles it.

        Deliberately NOT @_locked: unpark_sequence resolves the parked
        d2h future, and that resolve must run with the manager lock
        RELEASED (its whole point — see unpark_sequence). An @_locked
        wrapper here reentrantly defeats that and stalls every cache op
        on the server behind one session's host copy. Page accounting
        and the reclaimer still run under a short lock hold per
        sequence."""
        while True:
            with self._lock:
                parked = [
                    sid for sid in handle.seq_ids if sid in self._parked
                ]
                if not parked:
                    return
                sid = parked[0]
                l_seq = self._parked[sid].l_seq
                need = -(-l_seq // self.page_size)
                if (
                    need > self.table.free_pages
                    and self.reclaimer is not None
                ):
                    self.reclaimer(
                        need - self.table.free_pages, set(handle.seq_ids)
                    )
            try:
                self.unpark_sequence(sid)
            except KeyError:
                # raced with a lease-teardown purge between the scan and
                # the unpark; the entry is gone, re-scan what's left
                continue

    # ------------------------------------------------------- prefix cache
    def _apply_pending_copies(self) -> None:
        """Drain the table's queued copy-on-write page pairs into one fused
        device copy (the same gather+scatter jit the speculative accept
        uses). Caller holds the lock (write_slots / write paths)."""
        take = getattr(self.table, "take_pending_copies", None)
        if take is None:
            return
        pairs = take()
        if not pairs:
            return
        ps = self.page_size
        offs = np.arange(ps, dtype=np.int64)
        src = np.concatenate([s * ps + offs for s, _ in pairs])
        dst = np.concatenate([d * ps + offs for _, d in pairs])
        from bloombee_tpu.runtime.executor import next_pow2

        n = next_pow2(len(src), floor=4)
        oob = self.capacity_tokens  # out-of-bounds slot => dropped scatter
        src_p = np.zeros((n,), np.int32)  # padded gathers read slot 0
        dst_p = np.full((n,), oob, np.int32)  # padded scatters are dropped
        src_p[: len(src)] = src
        dst_p[: len(dst)] = dst
        self.arena["k"], self.arena["v"] = _reorder_all_layers(
            self.arena["k"], self.arena["v"],
            jnp.asarray(self._rows(src_p)), jnp.asarray(self._rows(dst_p)),
        )

    @_locked
    def adopt_prefix(self, handle: "CacheHandle", chains) -> list[int]:
        """Map each row's longest pooled prompt prefix into its (empty)
        sequence; returns per-row adopted token counts. Rows with no chain,
        non-empty state, or a parked copy adopt nothing. Adopted pages are
        refcount-pinned until the prefill's trim_adopted settles the final
        skip — or session teardown drops them."""
        out: list[int] = []
        for sid, chain in zip(handle.seq_ids, chains):
            matched = 0
            if (
                self.prefix_cache
                and chain
                and sid not in self._parked
                and hasattr(self.table, "adopt_prefix")
            ):
                st = self.table.seq(sid)
                if not (st.l_seq or st.l_acc or st.pages):
                    matched = self.table.adopt_prefix(
                        sid, chain, max_tokens=handle.max_length
                    )
                    if matched:
                        self._adopted[sid] = matched
                elif st.hashes is None:
                    # active seq (e.g. a retried probe): just attach the
                    # chain so its committed pages publish
                    self.table.set_seq_hashes(sid, chain)
            out.append(matched)
        return out

    @_locked
    def trim_adopted(self, handle: "CacheHandle", keep_tokens: int) -> None:
        """Settle a probe: shrink each adopted prefix to the chain-wide
        skip the client actually uses (min across spans, capped below the
        prompt length so the last position still computes) and record the
        hit. Idempotent — only sequences with an outstanding adoption are
        touched, so a retried prefill can't trim real tokens."""
        for sid in handle.seq_ids:
            adopted = self._adopted.pop(sid, None)
            if adopted is None:
                continue
            kept = min(keep_tokens, adopted)
            if kept < adopted:
                self.table.trim_adopted(sid, kept)
            if kept > 0:
                self.prefix_hits += 1
                self.prefix_hit_tokens += kept

    def has_adopted(self, handle: "CacheHandle") -> bool:
        """True while a probe's adoption awaits its prefill's settle."""
        return any(sid in self._adopted for sid in handle.seq_ids)

    @_locked
    def prefix_stats(self) -> dict:
        """Prefix-cache observability for rpc_info."""
        return {
            "prefix_hits": int(self.prefix_hits),
            "prefix_hit_tokens": int(self.prefix_hit_tokens),
            "cow_copies": int(getattr(self.table, "cow_count", 0)),
            "prefix_cached_pages": int(
                getattr(self.table, "cached_pages", 0)
            ),
            "repl_pages_installed": int(self.repl_pages_installed),
            # device-arena rebuilds after a failed donated dispatch: a
            # nonzero value means sessions lost KV to self-heal events,
            # which an operator should correlate with failover replays
            "arena_epoch": int(self.arena_epoch),
        }

    # ------------------------------------------------------- kv replication
    @property
    def repl_supported(self) -> bool:
        """Page payloads can be exported/installed byte-exact only on a
        dense unquantized arena (int4 slabs and hetero tuples have no
        single canonical page layout on the wire) with the prefix pool
        available to hold them."""
        return (
            self.prefix_cache
            and self.quant is None
            and not isinstance(self.arena["k"], tuple)
            and hasattr(self.table, "install_cached")
        )

    @_locked
    def export_pages(self, seq_id: int, lo_page: int, hi_page: int):
        """Gather sealed pages [lo_page, hi_page) of one sequence for
        replication. Returns (k_dev, v_dev, hi) — device arrays of shape
        [L, n * page_size, kv, hd] (the caller moves them to host off the
        compute thread) and the page bound actually exported, clamped to
        the fully-committed (sealed) prefix. None when the sequence has
        nothing exportable (parked, reset, or replication unsupported)."""
        if not self.repl_supported or not self.table.has_seq(seq_id):
            return None
        if seq_id in self._parked or seq_id in self._adopted:
            return None
        state = self.table.seq(seq_id)
        sealed = state.l_acc // self.page_size
        hi = min(hi_page, sealed, state.num_pages)
        if hi <= max(lo_page, 0):
            return None
        slots = self.table.range_slots(
            seq_id, lo_page * self.page_size, hi * self.page_size
        )
        idx = jnp.asarray(self._rows(slots))

        def take(a):  # the wire's page layout is the unfolded one
            return a[:, idx].reshape(
                a.shape[0], len(slots), *self._token_shape(a))

        return take(self.arena["k"]), take(self.arena["v"]), hi

    @_locked
    def install_replicated(self, hashes, k_pages, v_pages) -> int:
        """kv_put receive path: install replicated pages into the prefix
        pool as refcount-0 cached entries and scatter their bytes into the
        arena. `k_pages`/`v_pages` are host arrays [n, L, page_size, kv,
        hd] aligned with `hashes` (chain order — parents first). Pages the
        pool already holds, or that no free/cached page can back, are
        skipped; returns the number actually installed."""
        if not self.repl_supported:
            return 0
        lead = (len(hashes), self.num_layers, self.page_size)
        want = lead + self._token_shape(self.arena["k"])
        k_pages = np.asarray(k_pages)
        v_pages = np.asarray(v_pages)
        # the two slabs' rows differ for a latent page (latent | rotary key)
        if (
            k_pages.shape != want
            or v_pages.shape != lead + self._token_shape(self.arena["v"])
        ):
            raise ValueError(
                f"replicated page payload {k_pages.shape} does not match "
                f"arena geometry {want}"
            )
        pages, rows = [], []
        for i, h in enumerate(hashes):
            page = self.table.install_cached(h)
            if page is not None:
                pages.append(page)
                rows.append(i)
        if not pages:
            return 0
        ps = self.page_size
        offs = np.arange(ps, dtype=np.int64)
        slots = jnp.asarray(self._rows(
            np.concatenate([p * ps + offs for p in pages]).astype(np.int32)
        ))

        def put(stored, a):  # [m, L, ps, kv, hd] -> [L, m*ps, kv, hd], or
            # as a folded arena stores them, [L, m*ps*kv, hd]
            sel = np.swapaxes(a[np.asarray(rows)], 0, 1)
            return stored.at[:, slots].set(jnp.asarray(
                sel.reshape(a.shape[1], -1, *stored.shape[2:])
            ).astype(stored.dtype))

        self.arena["k"] = put(self.arena["k"], k_pages)
        self.arena["v"] = put(self.arena["v"], v_pages)
        self.repl_pages_installed += len(pages)
        return len(pages)

    @_locked
    def extend_seq_hashes(self, handle: "CacheHandle", chains) -> None:
        """Attach each row's full-history hash chain (replication keeps
        them growing past the prompt) so the primary's own sealed decode
        pages publish locally too. Extend-only: a shorter chain than the
        one on record is ignored (a stale replication message)."""
        if not self.prefix_cache or not hasattr(
            self.table, "set_seq_hashes"
        ):
            return
        for sid, chain in zip(handle.seq_ids, chains):
            if not chain or not self.table.has_seq(sid):
                continue
            if sid in self._parked or sid in self._adopted:
                continue
            st = self.table.seq(sid)
            if st.hashes is not None and len(chain) < len(st.hashes):
                continue
            self.table.set_seq_hashes(sid, chain)

    # ------------------------------------------------------ session leases
    def _handle_need(self, handle: "CacheHandle") -> int:
        """The token reservation allocate() charged for this handle
        (page-granular, same formula)."""
        per_seq = -(-handle.max_length // self.page_size) * self.page_size
        return handle.batch_size * per_seq

    async def lease_park(self, handle: "CacheHandle") -> None:
        """Park a stream-dead session for the lease window.

        Speculative tokens roll back, then every sequence's pages are
        handed to the prefix pool as refcount-0 *cached* entries (the
        install_cached trick the replication standbys use): immediately
        evictable under allocation pressure — a parked session can never
        OOM the server — yet device-resident for an exact zero-recompute
        resume while memory lasts. Tables without a prefix pool fall back
        to host-tier parking (same resume contract, a d2h/h2d copy more).
        The session's token reservation is returned to the admission
        budget for the duration of the park."""
        if self.ssm is not None:
            # pages could become evictable pool entries, the state slot
            # could not follow them: the session stays resident, pages,
            # slot and reservation, until it resumes or its lease runs out
            with self._lock:
                for sid in handle.seq_ids:
                    if self.table.has_seq(sid):
                        self._state_cut(sid, self.table.seq(sid).l_acc)
                        self.table.rollback(sid)
            return
        with self._lock:
            for sid in handle.seq_ids:
                if sid in self._parked or not self.table.has_seq(sid):
                    continue  # already host-parked: the copy survives as-is
                if sid in self._lease_parked:
                    continue
                self.table.rollback(sid)
                # an unsettled probe adoption parks as plain committed
                # pages (their hashes are real — resume re-pins them)
                self._adopted.pop(sid, None)
                if hasattr(self.table, "park_seq_cached"):
                    keys, l_acc = self.table.park_seq_cached(sid)
                    self._lease_parked[sid] = (keys, l_acc, self.arena_epoch)
                elif self.table.seq(sid).l_seq > 0:
                    self.park_sequence(sid)
            self._lease_released.add(handle.handle_id)
        cond = self._condition()
        async with cond:
            self._reserved_tokens -= self._handle_need(handle)
            cond.notify_all()

    async def lease_resume(self, handle: "CacheHandle") -> bool:
        """Re-pin a lease-parked session on reconnect. All-or-nothing:
        True means every sequence is back exactly as parked (same pages,
        same committed lengths — zero recompute); False means at least one
        page was evicted (or the arena rebuilt) and the caller must treat
        the session as lost (full-replay fallback, then reclaim)."""
        cond = self._condition()
        async with cond:
            if handle.handle_id in self._lease_released:
                # re-acquire the reservation. This may transiently push
                # reserved past admit_limit — acceptable: the pages backing
                # the resume were evictable all along, so this cannot OOM,
                # and admission pressure re-equalizes as sessions close
                self._reserved_tokens += self._handle_need(handle)
                self._lease_released.discard(handle.handle_id)
        with self._lock:
            if not self.epoch_valid(handle):
                return False
            for sid in handle.seq_ids:
                entry = self._lease_parked.get(sid)
                if entry is None:
                    continue  # host-parked fallback: next step unparks it
                keys, l_acc, epoch = entry
                if epoch != self.arena_epoch:
                    return False
                if not self.table.unpark_seq_cached(sid, keys, l_acc):
                    return False
                del self._lease_parked[sid]
            return True

    @_locked
    def lease_reclaim(self, handle: "CacheHandle") -> None:
        """Final reclaim of a reaped (or unresumable) session: purge its
        synthetic park entries so those pages return to the free list now
        instead of lingering as unreachable cached entries. Real-hash
        pages stay pooled — they still serve the prefix cache. The rest of
        the teardown (drop_seq, reservation) happens at allocate() exit."""
        for sid in handle.seq_ids:
            entry = self._lease_parked.pop(sid, None)
            if entry is not None and hasattr(self.table, "purge_parked"):
                self.table.purge_parked(entry[0])

    def has_lease_parked(self, handle: "CacheHandle") -> bool:
        return any(sid in self._lease_parked for sid in handle.seq_ids)

    # ------------------------------------------------------- host tiering
    @_locked
    def park_sequence(self, seq_id: int, tier: str = "host") -> None:
        """Move one sequence's KV off the device and free its pages.

        tier="host": KV lands in host DRAM numpy (optionally int4 via
        BBTPU_PARK_QUANT). tier="disk": KV lands in a memmapped file under
        BBTPU_DISK_DIR — the third tier of the reference's FlexGen substrate
        (pytorch_backend.py TorchDisk, np.memmap-backed tensors).
        Lengths are preserved; `unpark_sequence` restores (possibly to
        different pages).

        ASYNC: only the device-side gather (and optional int4 quantize) runs
        here; pages are freed immediately and the d2h copy overlaps ongoing
        serving on a background thread (the copy-engine overlap of the
        reference's async offload, memory_cache_manager.py:972-1335).
        Freeing before the copy lands is safe: the gather is dispatched
        before any later step that could write the freed slots, and the
        device executes dispatches in order. Until the copy drains, the
        gathered slice transiently holds its bytes in HBM (int4 planes when
        quantized parking is on).
        """
        if tier not in ("host", "disk"):
            # before the expensive d2h copy, not after
            raise ValueError(f"unknown park tier {tier!r}")
        if self.ssm is not None:
            # pages could go to host, the state slot is not carried: the
            # sequence stays resident and the reclaimer finds other victims
            self._refuse("host park")
            return
        if seq_id in self._adopted:
            # probe-adopted, prefill imminent: parking now would record the
            # un-trimmed adopted length and desync the client's suffix
            # offset on unpark — skip; the reclaimer finds other victims
            return
        slots = self._rows(
            self.table.prefix_slots(seq_id, committed_only=False)
        )
        state = self.table.seq(seq_id)

        hetero = isinstance(self.arena["k"], tuple)
        if self.quant is None and not hetero and env.get("BBTPU_PARK_QUANT"):
            # dense arena, quantized parking: quantize the still-device-
            # resident slice FIRST so only the int4 planes cross the link —
            # 4x less host DRAM and d2h transfer (the host-side half of the
            # reference's compressed offload)
            from bloombee_tpu.kv import quant as q

            k_dev = q.quantize(self.arena["k"][:, slots])
            v_dev = q.quantize(self.arena["v"][:, slots])
        else:

            def take(a):
                return a[:, slots]

            k_dev = jax.tree.map(take, self.arena["k"])  # [L, n, kv, hd]
            v_dev = jax.tree.map(take, self.arena["v"])

        def fetch(k_dev=k_dev, v_dev=v_dev, tier=tier, seq_id=seq_id):
            k_host = jax.tree.map(np.asarray, k_dev)
            v_host = jax.tree.map(np.asarray, v_dev)
            if tier == "disk":
                k_host = jax.tree.map(
                    lambda a, tag=("k", seq_id): self._to_disk(a, *tag),
                    k_host,
                )
                v_host = jax.tree.map(
                    lambda a, tag=("v", seq_id): self._to_disk(a, *tag),
                    v_host,
                )
            return k_host, v_host

        if self._park_pool is None:
            self._park_pool = _DaemonPool()
        self._parked[seq_id] = _Parked(
            l_acc=state.l_acc,
            l_seq=state.l_seq,
            future=self._park_pool.submit(fetch),
        )
        # free device pages but keep the seq registered with zero length
        self.table.reset_seq(seq_id)

    _disk_counter = itertools.count()

    def _to_disk(self, arr: np.ndarray, kind: str, seq_id: int) -> np.ndarray:
        """Spill one parked leaf to a memmapped file (deleted when the
        memmap is garbage-collected via the unlink-after-open trick on
        POSIX: the file keeps living until the mapping drops)."""
        import os
        import tempfile

        if arr.size == 0:
            return arr  # np.memmap cannot map an empty file
        disk_dir = env.get("BBTPU_DISK_DIR") or tempfile.gettempdir()
        os.makedirs(disk_dir, exist_ok=True)
        path = os.path.join(
            disk_dir,
            f"bbtpu_kv_{os.getpid()}_{kind}{seq_id}_"
            f"{next(self._disk_counter)}.bin",
        )
        mm = np.memmap(path, dtype=arr.dtype, mode="w+", shape=arr.shape)
        mm[:] = arr
        mm.flush()
        os.unlink(path)  # POSIX: mapping keeps the data until released
        return mm

    def unpark_sequence(self, seq_id: int) -> None:
        with self._lock:
            entry = self._parked[seq_id]
        # resolve OUTSIDE the manager lock: the d2h copy is usually long
        # done (the sequence sat parked precisely because it was idle),
        # but when it isn't, blocking here must not stall every other
        # cache operation behind this one future
        try:
            k_host, v_host = entry.resolve()
        except ParkedKVLost:
            # the copy is gone for good: drop the entry so the client's
            # replay lands on a clean zero-length sequence, not a wedge
            with self._lock:
                if self._parked.get(seq_id) is entry:
                    del self._parked[seq_id]
            raise
        self._unpark_restore(seq_id, entry, k_host, v_host)

    @_locked
    def _unpark_restore(self, seq_id, entry, k_host, v_host) -> None:
        """Second half of unpark: re-check ownership under the lock (a
        concurrent lease teardown may have purged the entry while the
        future resolved), then scatter the host copy back into the arena."""
        if self._parked.get(seq_id) is not entry:
            raise KeyError(seq_id)
        l_acc, l_seq = entry.l_acc, entry.l_seq
        state = self.table.seq(seq_id)
        assert state.l_seq == 0, "unpark target must be empty"
        # may raise OutOfPages: the parked host copy must survive a failed
        # attempt, so only drop it once slots are secured; recovery owner:
        # on failure the seq simply stays empty+parked (nothing committed
        # yet), so there is nothing to roll back
        slots_np = self.table.assign_write_slots(
            seq_id, l_seq, commit=False)  # bbtpu: noqa[BB001]
        del self._parked[seq_id]
        self.table.restore_committed(seq_id, l_acc)
        slots = jnp.asarray(self._rows(slots_np))
        from bloombee_tpu.kv.quant import QuantSlab, dequantize

        if self.quant is None and isinstance(k_host, QuantSlab):
            k_host = dequantize(
                QuantSlab(*(jnp.asarray(x) for x in k_host)),
                self.arena["k"].dtype,
            )
            v_host = dequantize(
                QuantSlab(*(jnp.asarray(x) for x in v_host)),
                self.arena["v"].dtype,
            )

        def put(a, h):
            return a.at[:, slots].set(jnp.asarray(h))

        self.arena["k"] = jax.tree.map(put, self.arena["k"], k_host)
        self.arena["v"] = jax.tree.map(put, self.arena["v"], v_host)

    def parked_seqs(self) -> Iterator[int]:
        return iter(self._parked)

    # ------------------------------------------------------------- recovery
    @_locked
    def rebuild_arena(self) -> None:
        """Replace a consumed arena with a fresh zeroed one after a kernel
        failure destroyed the donated buffers mid-chain (e.g. a paged
        failure between layer_step calls on the offload path). Every live
        device-RESIDENT sequence's KV is gone: their table state resets to
        zero length and their validity epoch goes stale, so the server
        fails their next step with a typed `session_lost` and the client
        replays history onto a fresh chain (the same path that handles a
        dead server). Host-parked sequences keep their copies AND get
        re-stamped to the new epoch: their next step unparks into the
        fresh arena intact, no replay needed (advisor, round 4)."""
        for sid in list(self._live_seqs):
            if self.table.has_seq(sid) and sid not in self._parked:
                self.table.reset_seq(sid)
        # pooled pages describe the OLD arena's bytes — a hit against them
        # would serve garbage KV
        if hasattr(self.table, "invalidate_pool"):
            self.table.invalidate_pool()
        self._adopted.clear()
        self.arena = self._make_arena()
        if self._make_state is not None:
            self.state = self._make_state()
            self._state_lost.clear()  # every resident sequence is stale now
        self.arena_epoch += 1
        for sid in self._parked:
            if sid in self._seq_epoch:
                self._seq_epoch[sid] = self.arena_epoch

    @_locked
    def is_fresh(self, handle: "CacheHandle") -> bool:
        """True iff every sequence in `handle` has NO server-side state at
        all: zero committed/speculative length AND nothing parked to host.
        (A parked sequence's table length reads 0 — its KV lives in
        `_parked` — so a bare length check would misclassify it as fresh;
        the sp-prefill eligibility gate needs the distinction.)"""
        for sid in handle.seq_ids:
            if sid in self._parked:
                return False
            state = self.table.seq(sid)
            if state.l_seq or state.l_acc:
                return False
        return True

    @_locked
    def memory_stats(self) -> dict:
        """KV-side byte/token accounting for the memory-observability
        surface (utils/memory.py) — kept here so it reads this manager's
        state through one accessor instead of private attributes."""
        from bloombee_tpu.utils.memory import (
            tree_nbytes,
            tree_nbytes_by_device,
        )

        parked_resolved = 0
        parked_total = 0
        for entry in self._parked.values():
            parked_total += 1
            if entry.host is not None:
                parked_resolved += tree_nbytes(entry.host)
        return {
            "kv_arena_bytes": tree_nbytes(self.arena),
            "kv_arena_layers": int(self.kv_layers),
            "kv_arena_bytes_by_device": tree_nbytes_by_device(self.arena),
            "parked_kv_host_bytes": parked_resolved,
            "parked_seqs": parked_total,
            "kv_tokens_reserved": int(self._reserved_tokens),
            "kv_tokens_capacity": int(self.capacity_tokens),
            **(
                {"state": self.state_stats(),
                 "state_arena_layers": int(self.state_layers)}
                if self.ssm is not None else {}
            ),
        }

    @_locked
    def combine_handles(self, handles: list["CacheHandle"]) -> "CacheHandle":
        """Merged view over several live handles for ONE batched decode
        step (continuous batching). The combined seq_id list is what drives
        cross-session page-table row gathering: `page_table` /
        `write_slots` / `context_lens` already operate per-sequence over
        `handle.seq_ids`, so rows from different sessions compose into one
        kernel launch with no new table machinery.

        The combined handle is EPHEMERAL — it borrows the member sessions'
        sequences for the duration of one dispatch and is never registered
        (handle_id=-1), so dropping it frees nothing and it must not
        outlive the member allocations."""
        return CacheHandle(
            handle_id=-1,
            seq_ids=[sid for h in handles for sid in h.seq_ids],
            max_length=max(h.max_length for h in handles),
        )

    @_locked
    def has_parked(self, handle: "CacheHandle") -> bool:
        """True when any sequence of `handle` is host-parked, i.e. its next
        step must unpark first. The decode batcher runs such members solo:
        an unpark inside a merged dispatch could raise OutOfPages for the
        whole group, failing sessions whose KV was resident all along."""
        return any(sid in self._parked for sid in handle.seq_ids)

    @_locked
    def epoch_valid(self, handle: "CacheHandle") -> bool:
        """True iff every sequence in `handle` still has servable KV: its
        validity epoch matches the current arena epoch (either no rebuild
        happened since allocation, or the seq was host-parked through every
        rebuild)."""
        for sid in handle.seq_ids:
            if sid in self._state_lost:
                # counted once, when a step first finds it (a warm-up's own
                # truncations never get here)
                self._state_lost.discard(sid)
                self._seq_epoch[sid] = -1
                self._refuse("rollback to a position > 0")
        return all(
            self._seq_epoch.get(sid) == self.arena_epoch
            for sid in handle.seq_ids
        )
