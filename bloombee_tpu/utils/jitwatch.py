"""Runtime compile/transfer witness — the dynamic half of bbtpu-lint's
JIT-boundary story (the static half is BB011/BB012/BB013 in
analysis/rules.py).

Static analysis proves which call sites CAN recompile or sync; this
module records what a run ACTUALLY compiled and transferred. Opt-in via
``BBTPU_JITWATCH=1``: :func:`install` registers one
``jax.monitoring`` event-duration listener that ledgers every XLA
backend compile as ``(function, shape_signature, compile_ms, phase)``.
Attribution rides a thread-local region stack: the executor wraps each
dispatch in :func:`region` naming the jit entry and its bucket
signature, so a compile that fires inside the dispatch is pinned to the
exact (function, bucket) that caused it. Compiles outside any region
(model load, client-side jnp work sharing the process) are ledgered as
``(unattributed)`` — counted, visible, but not gated, because only
region-attributed compiles are provably the serving path's fault.

Phases split the compile budget: every process starts in ``warmup``;
``BlockServer.warmup`` drops the fence (:func:`fence`) when its bucket
pre-compilation finishes, and every region-attributed compile after the
fence is a **steady-state recompile** — the recompile-storm signal this
witness exists to catch. Host syncs are recorded by the explicit d2h
sites (``executor.fetch``) via :func:`host_sync`; ones that fire while
the compute-queue worker is mid-task (:func:`hot_wrap`) count as
``host_syncs_hot_path`` — a device stall inside the serialized step
pipeline, the convoy BB011 flags statically.

The same thread-local stack carries **host spans** (:func:`span`): every
boundary of the served step (queue task, payload pack, h2d, the jit
dispatches above, commit, output slicing, the off-queue fetch, the wire
codec) is a named interval that (a) enters a
``jax.profiler.TraceAnnotation`` with its ids, so a profiler session —
and only a profiler session — puts it on the device trace's own clock,
and (b) adds ``(n, total_ns)`` per name on ``time.perf_counter_ns``,
read back through :func:`host_spans` / ``rpc_info["host_spans"]``. A
:func:`region` IS a span, named ``bbtpu.jit.<function>`` with the bucket
as an id. Spans are synchronous: never hold one across an ``await`` (an
event-loop thread interleaves coroutines and would break the nesting).
:func:`stopwatch` is the span whose duration its caller reads (the two
parts of the wire's ``t_compute_ms``, the client's ``c_head`` /
``c_embed``): it reads the clock with the witness off too, and is named
and counted only with it on.

Spans that close while a ``bbtpu.task`` is open on their thread (the
compute thread: :func:`hot_wrap`) also feed that task's **ledger**: each
adds its SELF time (its duration less what its direct children cover) under
its name (read by leg: every ``bbtpu.jit.*`` is ``jit_call``, the self
time of ``bbtpu.task`` and ``bbtpu.dispatch`` is ``unnamed``), in wall
and in thread-CPU nanoseconds (``time.thread_time_ns`` is read beside
``perf_counter_ns`` on such a thread only, and there in the one task of 32
that is read in full, :func:`read_in_full`: ``wall - cpu`` is the time the
thread did not run). A leg's numbers come from the ONE clock pair
its span reads, so a task's legs sum to its wall to the nanosecond.
:func:`launch` says, just before a dispatch of such a task, whether the
device had finished what it was given: the next region carries it (``device`` =
``idle`` | ``busy``) and the task counts it. When the task closes its
ledger goes to the `sink` its wrapper gave (server/compute_queue.py
``_WorkerAccount``: ``rpc_info["host_path"]``).

At interpreter exit the witness appends one JSON line to
``BBTPU_JITWATCH_REPORT`` (append mode, multi-process merge — same
contract as lockwatch/ledger). ``python -m bloombee_tpu.utils.jitwatch
PATH --require`` merges the lines and FAILS on: zero observed compiles
(vacuous green — a witness that saw no XLA activity validated nothing),
no warmup fence in any line (the steady window never opened, so "zero
steady recompiles" is also vacuous), zero warmup compiles (same), or
ANY steady-state recompile. clock is deliberately NOT imported here
(the ledger/clock/*watch utility layer stays import-cycle-free).

The witness also understands the persistent compile-artifact cache
(server/artifacts.py): a cache hit still fires backend_compile_duration,
but ``/jax/compilation_cache/cache_retrieval_time_sec`` fires first on
the same thread, so hits are ledgered as ``cached`` loads — they spend
no warmup budget and never count as steady recompiles. A server that
pre-installed fetched artifacts calls :func:`mark_preinstalled`; any
non-cached region-attributed warmup compile after that is a
``preinstalled_warmup_miss``, and ``--require --preinstalled`` fails on
any miss (or on zero cache hits — a vacuous pre-install). Swallowed
per-bucket warmup failures are recorded via :func:`note_warmup_failure`
and fail plain ``--require`` (``warmup_degraded``), so a zero-recompile
green can't mask buckets that never warmed.
"""

from __future__ import annotations

import atexit
import json
import threading
import time

from bloombee_tpu.utils import env

env.declare(
    "BBTPU_JITWATCH", bool, False,
    "install the runtime compile/transfer witness: ledgers every XLA "
    "backend compile with (function, shape bucket, ms, phase) via the "
    "jax.monitoring hook, counts host syncs on the compute hot path, "
    "names every boundary of the served step as a host span (bbtpu.*: "
    "a jax.profiler.TraceAnnotation plus n/total_ms in rpc_info's "
    "host_spans and worker groups), and reports at exit. Off = listener "
    "never registered, spans are the shared no-op, zero overhead",
)
env.declare(
    "BBTPU_JITWATCH_REPORT", str, "",
    "path to append this process's compile-witness report to at exit "
    "(one JSON line: compile ledger, warmup/steady split, hot-path host "
    "syncs); empty = in-memory only. Set by scripts/chaos.sh so the "
    "gate can require zero steady-state recompiles",
)

_MAX_COMPILES = 200  # keep each report line bounded under a compile storm
_UNATTRIBUTED = "(unattributed)"


class _Witness:
    """Process-wide compile/transfer ledger. Internal mutex is a PLAIN
    threading.Lock — the witness must never watch itself. Phase is
    process-wide (one warmup fence per server process); the attribution
    region and hot-path marks are thread-local because dispatches run
    synchronously on the compute worker thread."""

    def __init__(self):
        self._mu = threading.Lock()
        self._tls = threading.local()
        self.compiles: list[dict] = []
        self.xla_compiles = 0
        self.compile_ms_total = 0.0
        self.warmup_compiles = 0
        self.steady_state_recompiles = 0
        self.compile_cache_hits = 0
        self.preinstalled = False
        self.preinstalled_warmup_misses = 0
        self.warmup_failures = 0
        self.host_syncs: dict[str, int] = {}
        self.host_syncs_hot_path = 0
        # span name -> [n, total_ns] on time.perf_counter_ns
        self.spans: dict[str, list[int]] = {}
        self.phase = "warmup"
        self.fenced = False

    # ---------------------------------------------------- thread context
    def _regions(self) -> list["_Span"]:
        st = getattr(self._tls, "regions", None)
        if st is None:
            st = self._tls.regions = []
        return st

    def _hot_depth(self) -> int:
        return getattr(self._tls, "hot", 0)

    # ------------------------------------------------------------ record
    def note_cache_retrieval(self) -> None:
        # a persistent-cache hit still fires backend_compile_duration
        # immediately after cache_retrieval_time_sec on the same thread;
        # flag the thread so the next record_compile knows the executable
        # was LOADED, not compiled
        self._tls.cache_hit = True

    def record_compile(self, duration_s: float) -> None:
        cached = getattr(self._tls, "cache_hit", False)
        self._tls.cache_hit = False
        # the innermost REGION on this thread's stack owns the compile; a
        # plain span (pack, slice, ...) owns none, so a compile under it
        # stays unattributed exactly as before spans existed
        function, shape = next(
            ((f.function, f.shape) for f in reversed(self._regions())
             if f.function is not None),
            (_UNATTRIBUTED, ""),
        )
        ms = float(duration_s) * 1000.0
        with self._mu:
            phase = self.phase
            self.xla_compiles += 1
            self.compile_ms_total += ms
            if cached:
                # loaded from the persistent compile-artifact cache: not a
                # real XLA compile, so it never counts as a steady-state
                # recompile — but a warmup-phase load still populates its
                # dispatch bucket, so it satisfies the warmup fence (the
                # shared chaos-matrix cache can legitimately serve EVERY
                # warmup bucket; only --preinstalled mode cares whether the
                # load was a hit, via preinstalled_warmup_misses)
                self.compile_cache_hits += 1
                if phase == "warmup":
                    self.warmup_compiles += 1
            elif phase == "warmup":
                self.warmup_compiles += 1
                if self.preinstalled and function != _UNATTRIBUTED:
                    # pre-installed artifacts promised this bucket would
                    # load, not compile — a miss is the cold start the
                    # artifact path exists to eliminate
                    self.preinstalled_warmup_misses += 1
            elif function != _UNATTRIBUTED:
                # only region-attributed compiles gate: the serving path
                # owns its dispatch buckets, not the client-side jnp work
                # that may share a test process
                self.steady_state_recompiles += 1
            if len(self.compiles) < _MAX_COMPILES:
                self.compiles.append({
                    "function": function,
                    "shape": shape,
                    "compile_ms": round(ms, 3),
                    "phase": phase,
                    "cached": cached,
                })

    def record_host_sync(self, tag: str) -> None:
        hot = self._hot_depth() > 0
        with self._mu:
            self.host_syncs[tag] = self.host_syncs.get(tag, 0) + 1
            if hot:
                self.host_syncs_hot_path += 1

    def record_span(self, name: str, ns: int) -> None:
        with self._mu:
            rec = self.spans.get(name)
            if rec is None:
                self.spans[name] = [1, ns]
            else:
                rec[0] += 1
                rec[1] += ns

    def record_spans(self, spans: dict) -> None:
        """A closed task's {name: [n, total_ns, ...]}, under one lock."""
        with self._mu:
            for name, rec in spans.items():
                into = self.spans.get(name)
                if into is None:
                    self.spans[name] = [rec[0], rec[1]]
                else:
                    into[0] += rec[0]
                    into[1] += rec[1]

    def _spans_ms(self) -> dict:  # caller holds self._mu
        return {
            name: {"n": n, "total_ms": round(ns / 1e6, 3)}
            for name, (n, ns) in sorted(self.spans.items())
        }

    def host_spans(self) -> dict:
        with self._mu:
            return self._spans_ms()

    def note_warmup_failure(self) -> None:
        with self._mu:
            self.warmup_failures += 1

    def mark_preinstalled(self) -> None:
        with self._mu:
            self.preinstalled = True

    # ------------------------------------------------------------- phase
    def set_phase(self, phase: str) -> None:
        with self._mu:
            self.phase = phase

    def fence(self) -> None:
        with self._mu:
            self.phase = "steady"
            self.fenced = True

    # ------------------------------------------------------------ reading
    def snapshot(self) -> dict:
        with self._mu:
            return {
                "compiles": [dict(c) for c in self.compiles],
                "xla_compiles": self.xla_compiles,
                "compile_ms_total": round(self.compile_ms_total, 3),
                "warmup_compiles": self.warmup_compiles,
                "steady_state_recompiles": self.steady_state_recompiles,
                "compile_cache_hits": self.compile_cache_hits,
                "preinstalled": self.preinstalled,
                "preinstalled_warmup_misses": self.preinstalled_warmup_misses,
                "warmup_failures": self.warmup_failures,
                "warmup_degraded": bool(self.warmup_failures),
                "host_syncs": dict(self.host_syncs),
                "host_syncs_hot_path": self.host_syncs_hot_path,
                "host_spans": self._spans_ms(),
                "fenced": self.fenced,
            }

    def reset(self) -> None:
        with self._mu:
            self.compiles.clear()
            self.xla_compiles = 0
            self.compile_ms_total = 0.0
            self.warmup_compiles = 0
            self.steady_state_recompiles = 0
            self.compile_cache_hits = 0
            self.preinstalled = False
            self.preinstalled_warmup_misses = 0
            self.warmup_failures = 0
            self.host_syncs.clear()
            self.host_syncs_hot_path = 0
            self.spans.clear()
            self.phase = "warmup"
            self.fenced = False
        # the CALLING thread's context only (other threads' region stacks
        # are theirs to unwind) — a harness that leaked a region would
        # otherwise misattribute every later compile
        self._regions().clear()
        self._tls.hot = 0
        self._tls.cache_hit = False
        self._tls.task = None
        self._tls.launch = None


_witness = _Witness()
_installed = False
_atexit_registered = False


def enabled() -> bool:
    return bool(env.get("BBTPU_JITWATCH"))


def install() -> bool:
    """Register the XLA compile listener (idempotent; no-op when the
    switch is off). Called by BlockServer startup — the listener
    is process-global and permanent, so the callback re-checks
    :func:`enabled` per event to honor env flips in tests."""
    global _installed, _atexit_registered
    if not enabled():
        return False
    if not _atexit_registered:
        _atexit_registered = True
        if env.get("BBTPU_JITWATCH_REPORT"):
            atexit.register(flush)
    if _installed:
        return True
    try:
        from jax import monitoring
    except Exception:  # jax-free analysis/CLI contexts: witness stays off
        return False

    def _on_event(event: str, duration_s: float, **kwargs) -> None:
        if not enabled():
            return
        # a persistent-cache hit emits cache_retrieval_time_sec and THEN
        # backend_compile_duration for the same executable on the same
        # thread — note the retrieval first so the compile record can
        # tell a cache load from a true XLA compile
        if "cache_retrieval" in event:
            _witness.note_cache_retrieval()
        # one jit call can emit several backend_compile events (aux
        # computations); each is a real XLA compile, ledger them all
        elif "backend_compile" in event:
            _witness.record_compile(duration_s)

    monitoring.register_event_duration_secs_listener(_on_event)
    _installed = True
    return True


# ------------------------------------------------------ spans + attribution
_trace_annotation = None  # jax.profiler.TraceAnnotation, imported lazily


def _annotation_class():
    """``jax.profiler.TraceAnnotation``, imported on first use; False where
    JAX is absent (analysis / CLI contexts)."""
    global _trace_annotation
    if _trace_annotation is None:
        try:
            from jax.profiler import TraceAnnotation
        except Exception:  # jax-free analysis/CLI contexts
            TraceAnnotation = False
        _trace_annotation = TraceAnnotation
    return _trace_annotation


def _annotation(name: str, ids: dict):
    """A ``jax.profiler.TraceAnnotation`` for one span, or None where JAX
    is absent. Inside a profiler session the span lands in the profiler's
    own trace. The profiler encodes ids as ``name#k=v,k=v#``, so a comma
    inside a value (a bucket tag ``b2,t1,p64``) would cut it short: commas
    travel as ``;``."""
    cls = _annotation_class()
    if not cls:
        return None
    return cls(name, **{
        k: v.replace(",", ";") if isinstance(v, str) else v
        for k, v in ids.items()
    })


def _recording() -> bool:
    """Whether a profiler session is recording on this host right now: an
    annotation entered outside one is dropped by the profiler, so a span
    makes none (most of a span's cost in a server nobody is tracing)."""
    cls = _annotation_class()
    return bool(cls) and cls.is_enabled()


class _NoSpan:
    """The shared frame of a span with the witness off: no clock, no
    stack, no annotation."""

    __slots__ = ()
    ns = 0
    ms = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        return None


_NOOP = _NoSpan()


class _Span:
    """One named host interval on this thread's stack. `function` is set
    for a region only: compiles fired while it is the innermost region
    are pinned to (function, shape). `_task` is the `bbtpu.task` open on
    the thread when the span opened (None on every other thread): only
    then is the thread's CPU clock read, and the span's self time added to
    the task's ledger when it closes."""

    __slots__ = ("name", "ids", "function", "shape", "ns", "cpu_ns", "_on",
                 "_t0", "_c0", "_ann", "_task", "_kids_ns", "_kids_cpu")

    def __init__(self, name: str, ids: dict, on: bool,
                 function: str | None = None, shape: str = ""):
        self.name = name
        self.ids = ids
        self.function = function
        self.shape = shape
        self.ns = 0
        self.cpu_ns = 0
        self._on = on
        self._ann = None
        self._task = None
        self._kids_ns = 0  # what the direct children cover: wall, CPU
        self._kids_cpu = 0

    def __enter__(self):
        if not self._on:
            self._t0 = time.perf_counter_ns()
            return self
        # the clocks first and (in __exit__) last: the span's own
        # bookkeeping is inside its pair, not in its parent's self time
        task = self._task = getattr(_witness._tls, "task", None)
        self._t0 = time.perf_counter_ns()
        if task is not None and task.full:
            # inside the wall pair, so a span's CPU never passes its wall
            self._c0 = time.thread_time_ns()
        st = _witness._regions()
        if st and "task" in st[-1].ids and "task" not in self.ids:
            # everything a queue task runs repeats its number
            self.ids = dict(self.ids, task=st[-1].ids["task"])
        st.append(self)
        if _recording():
            self._ann = _annotation(self.name, self.ids)
            if self._ann is not None:
                self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        if not self._on:
            self.ns = time.perf_counter_ns() - self._t0
            return
        if self._ann is not None:
            self._ann.__exit__(*exc)
        st = _witness._regions()
        if st:
            st.pop()
        task = self._task
        if task is None:
            ns = self.ns = time.perf_counter_ns() - self._t0
            _witness.record_span(self.name, ns)
            return
        if task.full:
            cpu = self.cpu_ns = time.thread_time_ns() - self._c0
        else:
            cpu = 0
        ns = self.ns = time.perf_counter_ns() - self._t0
        # under a task: [n, wall, self wall, self CPU] by name in its
        # ledger, which reaches the witness's sums when the task closes
        if st and self is not task:
            st[-1]._kids_ns += ns
            st[-1]._kids_cpu += cpu
        own = ns - self._kids_ns
        rec = task.spans.get(self.name)
        if rec is None:
            task.spans[self.name] = [1, ns, own, cpu - self._kids_cpu]
        else:
            rec[0] += 1
            rec[1] += ns
            rec[2] += own
            rec[3] += cpu - self._kids_cpu
        device = self.function and self.ids.get("device")
        if device:  # a region whose launch said what the device was doing
            idle = device == "idle"
            task.launches[0] += 1
            task.launches[1] += idle
            task.launches[2 if idle else 3] += own

    @property
    def ms(self) -> float:
        return self.ns / 1e6


def read_in_full(number: int) -> bool:
    """Whether the task of this number is one of the (one in 32) tasks read
    in FULL: its spans read the thread's CPU clock and its launches ask the
    device. Both cost a system call's time on the machine with the chip
    (`time.thread_time_ns` 5 us alone and 12 in a task, against 0.09 for the
    wall clock; `is_ready()` of a TPU array 70), a dozen spans a task would
    pay the first twice each, and the compute thread is what the account is
    there to watch. A multiplicative hash of the number, so that no period
    of the traffic (every fourth task a chunk) meets the same tasks."""
    return (number * 0x9E3779B1 >> 15) & 31 == 0


class _TaskSpan(_Span):
    """The `bbtpu.task` span of one compute-queue task, and the task's
    ledger: `spans` {name: [n, wall ns, self wall ns, self CPU ns]} of every
    span that closed under it (itself included), `launches` [dispatches
    that said what the device was doing, those that found it idle, the
    self wall ns of the idle ones' regions, of the busy ones']. `t0` is a
    start its caller read already (the worker's account: ONE clock pair a
    task). `full`: this task is read in full (:func:`read_in_full`, by its
    number): CPU beside wall in every span, the device asked at every
    launch; every other task keeps wall times alone. As the task closes, on
    its thread, the spans' (n, wall) join the witness's sums and
    `sink(task)` is called."""

    __slots__ = ("spans", "launches", "full", "_sink", "_outer", "_start")

    def __init__(self, ids: dict, sink=None, t0: int | None = None):
        super().__init__("bbtpu.task", ids, True)
        self.spans: dict[str, list[int]] = {}
        self.launches = [0, 0, 0, 0]
        self.full = read_in_full(ids.get("task", 0))
        self._sink = sink
        self._start = t0

    def __enter__(self):
        tls = _witness._tls
        # a task inside a task is a plain span of the outer one's ledger
        self._outer = getattr(tls, "task", None)
        if self._outer is None:
            tls.task = self
        super().__enter__()
        if self._start is not None:
            self._t0 = self._start
        return self

    def __exit__(self, *exc) -> None:
        super().__exit__(*exc)
        if self._outer is None:
            _witness._tls.task = None
            _witness.record_spans(self.spans)
            if self._sink is not None:
                self._sink(self)


def legs_of(spans: dict) -> dict[str, list[int]]:
    """A ledger's {name: [n, wall, self wall, self CPU, ...]} by LEG, {leg:
    [self wall ns, self CPU ns, ...]} (the fields from the third on, summed):
    every `bbtpu.jit.*` under `jit_call`, the self time of `bbtpu.task` and
    `bbtpu.dispatch` under `unnamed`, every other span under its name. A
    task's legs sum to its `ns` (and, where its spans read the CPU clock, to
    its `cpu_ns`)."""
    legs: dict[str, list[int]] = {}
    for name, rec in spans.items():
        leg = ("jit_call" if name.startswith("bbtpu.jit.")
               else "unnamed" if name in _UNNAMED else name)
        into = legs.get(leg)
        if into is None:
            legs[leg] = list(rec[2:])
        else:
            for i, v in enumerate(rec[2:]):
                into[i] += v
    return legs


_UNNAMED = ("bbtpu.task", "bbtpu.dispatch")


def _on() -> bool:
    """:func:`enabled`, without the read of the environment where a task is
    open on this thread: it opened with the witness on, and what runs under
    it is on to its end (a dozen spans a task pay no switch)."""
    return getattr(_witness._tls, "task", None) is not None or enabled()


def span(name: str, **ids):
    """Name one synchronous host interval: ``with jitwatch.span(
    "bbtpu.pack", session=sid, step=n): ...``. Witness off: the shared
    no-op frame."""
    if not _on():
        return _NOOP
    return _Span(name, ids, True)


def stopwatch(name: str, **ids) -> _Span:
    """A span whose duration the caller reads (``.ms`` / ``.ns`` after the
    block): ONE clock pair feeds both the caller's number and, with the
    witness on, the span's. Witness off: the clock pair alone."""
    return _Span(name, ids, _on())


def region(function: str, shape: str):
    """Wrap one jit dispatch: ``with jitwatch.region("span_step",
    "b2,t1,p64"): ...``. A span named ``bbtpu.jit.<function>`` that also
    owns the compiles fired inside it, and carries what :func:`launch` said
    of the device just before it (``device`` = ``idle`` | ``busy``). Cheap
    no-op frame when the witness is off."""
    if not _on():
        return _NOOP
    ids = {"bucket": shape}
    device = getattr(_witness._tls, "launch", None)
    if device is not None:
        _witness._tls.launch = None
        ids["device"] = device
    return _Span("bbtpu.jit." + function, ids, True, function, shape)


def launch(device_idle, *args) -> None:
    """Say, just before a dispatch, whether the device had finished
    everything it was given: `device_idle(*args)` is asked in a task read
    in full only (True | False | None for "cannot say"; never with the
    witness off), the next :func:`region` on this thread carries the bit,
    and the task counts the launch and the call's wall under it. A launch
    on an idle device is a bubble the host made; the call's wall on a busy
    one holds the device queue's back-pressure besides its own cost."""
    task = getattr(_witness._tls, "task", None)
    if task is not None and task.full:
        idle = device_idle(*args)
        if idle is not None:
            _witness._tls.launch = "idle" if idle else "busy"


def hot_wrap(fn, sink=None, t0: int | None = None, **ids):
    """Mark `fn` as compute-queue hot-path work: host syncs recorded
    while it runs count as ``host_syncs_hot_path``, and the call is the
    span ``bbtpu.task`` carrying `ids`, whose ledger (the self time of
    every span under it, by leg: :class:`_TaskSpan`) goes to `sink` as it
    closes. Returns `fn` unchanged when the witness is off (zero-overhead
    contract)."""
    if not enabled():
        return fn

    def _hot(*args, **kwargs):
        _witness._tls.hot = _witness._hot_depth() + 1
        try:
            with _TaskSpan(ids, sink, t0):
                return fn(*args, **kwargs)
        finally:
            _witness._tls.hot = _witness._hot_depth() - 1

    return _hot


def host_sync(tag: str) -> None:
    """Record one device→host sync at an instrumented site (the BB011
    sites that survive triage call this next to the transfer)."""
    if enabled():
        _witness.record_host_sync(tag)


# ------------------------------------------------------------------ phase
def set_phase(phase: str) -> None:
    """Re-open a phase (BlockServer.warmup sets "warmup" so re-entrant
    warmups — e.g. after elastic rebalance — ledger under warmup)."""
    if enabled():
        _witness.set_phase(phase)


def fence() -> None:
    """Drop the warmup fence: every region-attributed compile after this
    is a steady-state recompile and fails the --require gate."""
    if enabled():
        _witness.fence()


def note_warmup_failure() -> None:
    """Record one swallowed per-bucket warmup failure: the fence still
    drops, but the report carries ``warmup_degraded`` so a zero-recompile
    green can't mask buckets that never warmed."""
    if enabled():
        _witness.note_warmup_failure()


def mark_preinstalled() -> None:
    """Declare that compile artifacts were pre-installed before warmup:
    from here on, any non-cached region-attributed warmup compile is a
    ``preinstalled_warmup_miss`` and fails ``--require --preinstalled``."""
    if enabled():
        _witness.mark_preinstalled()


# -------------------------------------------------------------- reporting
def counters() -> dict:
    """Live counter group for rpc_info / health --probe."""
    snap = _witness.snapshot()
    return {
        "xla_compiles": snap["xla_compiles"],
        "compile_ms_total": snap["compile_ms_total"],
        "warmup_compiles": snap["warmup_compiles"],
        "steady_state_recompiles": snap["steady_state_recompiles"],
        "compile_cache_hits": snap["compile_cache_hits"],
        "preinstalled_warmup_misses": snap["preinstalled_warmup_misses"],
        "host_syncs_hot_path": snap["host_syncs_hot_path"],
    }


def host_spans() -> dict:
    """``{span name: {"n", "total_ms"}}`` since start (or reset): the
    ``rpc_info["host_spans"]`` group. Empty with the witness off."""
    return _witness.host_spans()


def snapshot() -> dict:
    return _witness.snapshot()


def reset() -> None:
    _witness.reset()


def flush(path: str | None = None) -> None:
    """Append this process's witness report as one JSON line (atexit
    hook; callable directly by harnesses)."""
    path = path or env.get("BBTPU_JITWATCH_REPORT")
    if not path:
        return
    snap = _witness.snapshot()
    if not snap["xla_compiles"] and not snap["host_syncs"]:
        return
    try:
        with open(path, "a") as f:
            f.write(json.dumps(snap, sort_keys=True) + "\n")
    except OSError:  # the witness must never take down the run it audits
        pass


def merge_lines(text: str) -> dict:
    """Merge a multi-process report file into one compile/sync ledger."""
    merged = {
        "compiles": [],
        "xla_compiles": 0,
        "compile_ms_total": 0.0,
        "warmup_compiles": 0,
        "steady_state_recompiles": 0,
        "compile_cache_hits": 0,
        "preinstalled": False,
        "preinstalled_warmup_misses": 0,
        "warmup_failures": 0,
        "warmup_degraded": False,
        "host_syncs": {},
        "host_syncs_hot_path": 0,
        "host_spans": {},
        "fenced": False,
    }
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            snap = json.loads(line)
        except ValueError:
            continue
        merged["compiles"].extend(snap.get("compiles") or [])
        for key in ("xla_compiles", "warmup_compiles",
                    "steady_state_recompiles", "compile_cache_hits",
                    "preinstalled_warmup_misses", "warmup_failures",
                    "host_syncs_hot_path"):
            merged[key] += int(snap.get(key) or 0)
        merged["compile_ms_total"] += float(snap.get("compile_ms_total") or 0)
        for tag, n in (snap.get("host_syncs") or {}).items():
            merged["host_syncs"][tag] = (
                merged["host_syncs"].get(tag, 0) + int(n)
            )
        for name, rec in (snap.get("host_spans") or {}).items():
            into = merged["host_spans"].setdefault(
                name, {"n": 0, "total_ms": 0.0}
            )
            into["n"] += int(rec.get("n") or 0)
            into["total_ms"] = round(
                into["total_ms"] + float(rec.get("total_ms") or 0), 3
            )
        merged["fenced"] = merged["fenced"] or bool(snap.get("fenced"))
        merged["preinstalled"] = (
            merged["preinstalled"] or bool(snap.get("preinstalled"))
        )
    merged["compile_ms_total"] = round(merged["compile_ms_total"], 3)
    merged["warmup_degraded"] = bool(merged["warmup_failures"])
    return merged


def _main(argv=None) -> int:
    """``python -m bloombee_tpu.utils.jitwatch PATH [--require]``: merge
    and print a witness report; with --require, exit 1 unless the run
    observed >=1 warmup compile behind a dropped fence (proof the
    witness and the warmup both ran) with ZERO steady-state recompiles."""
    import argparse
    import sys

    ap = argparse.ArgumentParser(description=_main.__doc__)
    ap.add_argument("path")
    ap.add_argument("--require", action="store_true",
                    help="fail (exit 1) on zero compiles, a missing "
                         "warmup fence, any steady-state recompile, or a "
                         "degraded warmup (swallowed per-bucket failures)")
    ap.add_argument("--preinstalled", action="store_true",
                    help="with --require: expect a pre-installed "
                         "compile-artifact run — fail unless some process "
                         "marked itself preinstalled AND loaded >=1 "
                         "executable from the artifact cache AND showed "
                         "zero non-cached warmup compiles for its buckets")
    args = ap.parse_args(argv)
    try:
        with open(args.path) as f:
            text = f.read()
    except OSError:
        text = ""
    merged = merge_lines(text)
    steady = [c for c in merged["compiles"]
              if c.get("phase") == "steady"
              and c.get("function") != _UNATTRIBUTED]
    print(
        f"jitwatch: {merged['xla_compiles']} compile(s) "
        f"({merged['warmup_compiles']} warmup, "
        f"{merged['steady_state_recompiles']} steady-state, "
        f"{merged['compile_cache_hits']} cache hit(s)), "
        f"{merged['compile_ms_total']:.0f}ms total, "
        f"{merged['host_syncs_hot_path']} hot-path host sync(s), "
        f"fenced={merged['fenced']}, "
        f"preinstalled={merged['preinstalled']} "
        f"(misses={merged['preinstalled_warmup_misses']}), "
        f"warmup_failures={merged['warmup_failures']}"
    )
    for tag, n in sorted(merged["host_syncs"].items()):
        print(f"  sync {tag} x{n}")
    for name, rec in sorted(merged["host_spans"].items()):
        if rec["n"]:
            print(
                f"  span {name} x{rec['n']} "
                f"mean {rec['total_ms'] / rec['n']:.3f}ms"
            )
    for c in steady:
        print(
            f"  STEADY RECOMPILE {c['function']}[{c['shape']}] "
            f"{c['compile_ms']}ms"
        )
    if args.require:
        if not merged["xla_compiles"]:
            print(
                "jitwatch: EMPTY — a witness-enabled run must observe "
                ">=1 XLA compile; a run that compiled nothing validated "
                "nothing", file=sys.stderr,
            )
            return 1
        if args.preinstalled:
            # pre-installed mode: warmup may legitimately compile NOTHING
            # (everything loads from the artifact cache), so the vacuity
            # proof shifts from warmup compiles to cache hits
            if not merged["preinstalled"]:
                print(
                    "jitwatch: NOT PREINSTALLED — no process marked "
                    "compile artifacts as pre-installed, so the "
                    "zero-cold-start claim was never put to the test",
                    file=sys.stderr,
                )
                return 1
            if not merged["compile_cache_hits"]:
                print(
                    "jitwatch: NO CACHE HITS — a pre-installed run loaded "
                    "zero executables from the artifact cache; the "
                    "artifacts installed were never exercised",
                    file=sys.stderr,
                )
                return 1
            if not merged["fenced"]:
                print(
                    "jitwatch: NO WARMUP FENCE — the pre-installed run "
                    "never fenced, so its steady window never opened",
                    file=sys.stderr,
                )
                return 1
            if merged["preinstalled_warmup_misses"]:
                print(
                    "jitwatch: preinstalled warmup miss(es) — a promoted "
                    "replica with pre-installed artifacts still compiled "
                    "during warmup; the artifact for that (function, "
                    "bucket) was missing, stale, or declined",
                    file=sys.stderr,
                )
                return 1
        elif not merged["fenced"] or not merged["warmup_compiles"]:
            print(
                "jitwatch: NO WARMUP FENCE — no process dropped the "
                "warmup fence after >=1 warmup compile, so the "
                "steady-state window never opened and 'zero recompiles' "
                "is vacuous", file=sys.stderr,
            )
            return 1
        if merged["steady_state_recompiles"]:
            print(
                "jitwatch: steady-state recompile(s) observed — a decode "
                "bucket escaped BlockServer.warmup or a shape escaped its "
                "pow2 bucketer (BB012); the ledger above names the "
                "(function, shape) to pre-compile", file=sys.stderr,
            )
            return 1
        if merged["warmup_degraded"]:
            print(
                "jitwatch: DEGRADED WARMUP — per-bucket warmup failures "
                "were swallowed (warmup_failures="
                f"{merged['warmup_failures']}); the fence dropped over "
                "buckets that never warmed, so this green is hollow",
                file=sys.stderr,
            )
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
