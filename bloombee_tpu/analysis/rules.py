"""bbtpu-lint rules BB001–BB013.

Each rule encodes one invariant this codebase has already been burned by
(see ARCHITECTURE.md "Invariants"). Rules are plugin classes over the
shared SourceFile from core.py: per-file `visit_file` plus a cross-file
`finalize` for rules that correlate a declaration in one file with its
surfacing in another (BB006) or need nothing global (most). Rules that
define `prepare(files, graph)` additionally get the module-level call
graph (analysis/callgraph.py) before the per-file pass — BB002/BB003/
BB009 use it to follow lock effects across call edges and print the
full call chain in the finding.

Rule-authoring contract: a rule must be cheap (pure ast walk), must
build findings via ``sf.finding(...)`` so `# bbtpu: noqa[...]` works,
and must prefer missing a contorted true positive over spamming false
positives — the gate is only useful while `scripts/analyze.sh` exits 0
on a healthy tree.
"""

from __future__ import annotations

import ast
import dataclasses
import re

from bloombee_tpu.analysis import lock_hierarchy
from bloombee_tpu.analysis.callgraph import body_walk
from bloombee_tpu.analysis.core import Finding, SourceFile

_STRINGS_RE = re.compile(r"'[^']*'|\"[^\"]*\"")


def _call_name(node: ast.Call) -> str:
    """Trailing name of the called thing: `a.b.write_slots(...)` ->
    'write_slots', `rollback(...)` -> 'rollback'."""
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def _expr_text(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:
        return ""


def _mentions_lock(expr: ast.AST) -> bool:
    """'lock' appears in the expression's code, not inside a string
    literal (`open(".evict.lock")` is a file, not a mutex)."""
    text = _STRINGS_RE.sub("", _expr_text(expr))
    return "lock" in text.lower()


def _is_locked_decorated(fn: ast.AST) -> bool:
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return False
    return any("_locked" in _expr_text(d) for d in fn.decorator_list)


class Rule:
    code = "BB000"
    name = "base"
    summary = ""

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        return []

    def finalize(self) -> list[Finding]:
        return []


class SpeculativeWriteRule(Rule):
    """BB001: a speculative KV mutation must be dominated by a try whose
    handlers/finally reach rollback/truncate_speculative.

    Motivated by PR 8: a failed mixed dispatch that plain-rollback'd the
    fused handle destroyed prefill chunks committed by EARLIER chunks —
    the fix (truncate_speculative) only exists because someone noticed.
    Sites that deliberately delegate recovery to their caller (the
    stream driver owns the handle's lifecycle) carry
    `# bbtpu: noqa[BB001]` with a comment naming the owner.
    """

    code = "BB001"
    name = "speculative-write-unprotected"
    summary = (
        "speculative KV mutation not dominated by a try reaching "
        "rollback/truncate_speculative"
    )

    # These mutate KV speculatively no matter how they're called.
    ALWAYS = {"append_speculative", "decode_group"}
    # These are speculative only when explicitly called commit=False
    # (a literal False keyword; `commit=commit` pass-through is the
    # callee's own contract and stays quiet).
    WHEN_COMMIT_FALSE = {
        "write_slots",
        "write_slots_ragged",
        "assign_write_slots",
        "prefill",
        "prefill_chunk",
        "prefill_chunked",
        "decode",
        "decode_n",
        "step",
        "_step",
        "_step_once",
    }
    RECOVERY = {
        "commit",
        "rollback",
        "truncate_speculative",
        "rollback_if_valid",
        "_rollback_if_valid",
        "abort_chunked_prefill",
        "_abort_chunked_prefill",
    }

    def _is_speculative(self, node: ast.Call) -> bool:
        name = _call_name(node)
        if name in self.ALWAYS:
            return True
        if name not in self.WHEN_COMMIT_FALSE:
            return False
        return any(
            kw.arg == "commit"
            and isinstance(kw.value, ast.Constant)
            and kw.value.value is False
            for kw in node.keywords
        )

    def _has_recovery(self, stmts: list[ast.stmt]) -> bool:
        for stmt in stmts:
            for n in ast.walk(stmt):
                if isinstance(n, ast.Call) and (
                    _call_name(n) in self.RECOVERY
                ):
                    return True
        return False

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        # id()-sets of every node inside a try body whose failure path
        # (handlers or finally) reaches a recovery call
        guarded: list[set[int]] = []
        for t in ast.walk(sf.tree):
            if not isinstance(t, ast.Try):
                continue
            recovery_stmts: list[ast.stmt] = list(t.finalbody)
            for h in t.handlers:
                recovery_stmts.extend(h.body)
            if not self._has_recovery(recovery_stmts):
                continue
            guarded.append(
                {
                    id(x)
                    for stmt in t.body
                    for x in ast.walk(stmt)
                }
            )
        out = []
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            if not self._is_speculative(node):
                continue
            if any(id(node) in g for g in guarded):
                continue
            f = sf.finding(
                self.code,
                node,
                f"speculative KV write `{_call_name(node)}(...)` is not "
                "dominated by a try whose handlers reach "
                "rollback/truncate_speculative; wrap it, or mark the "
                "recovery owner with `# bbtpu: noqa[BB001]`",
            )
            if f:
                out.append(f)
        return out


class BlockingUnderLockRule(Rule):
    """BB002: no blocking call while a threading lock is held — now
    TRANSITIVE across call edges.

    CacheManager serializes on one RLock (`@_locked`); a recv/sleep/
    future-result/device-sync inside it stalls every session on the
    server, which is exactly the head-of-line blocking PR 5/8 spent two
    PRs removing from the dispatch path. v2: `with lock: flush()` where
    flush() sleeps three helpers down is the same bug, so any resolved
    call under the lock whose callee transitively reaches a blocking
    site is flagged with the full call chain. asyncio locks are out of
    scope here (they don't pin a thread) — BB009 owns the event loop.
    """

    code = "BB002"
    name = "blocking-call-under-lock"
    summary = "blocking call while a threading lock is held"

    BLOCKING_ATTRS = {
        "sleep",
        "recv",
        "result",
        "block_until_ready",
        "resolve",
    }

    def __init__(self):
        self._graph = None
        self._chains: dict[str, tuple[str, ...]] = {}
        self._site: dict[str, str] = {}  # qname -> its blocking callee

    def _is_blocking(self, node: ast.Call) -> bool:
        f = node.func
        if isinstance(f, ast.Attribute):
            if f.attr in self.BLOCKING_ATTRS:
                return True
            # device dispatch through the executor is a synchronous
            # multi-ms device round-trip
            if "executor" in _STRINGS_RE.sub("", _expr_text(f.value)):
                return True
        return False

    def prepare(self, files: list[SourceFile], graph) -> None:
        self._graph = graph
        for q, fi in graph.functions.items():
            for n in body_walk(fi.node):
                if isinstance(n, ast.Call) and self._is_blocking(n):
                    self._site[q] = _expr_text(n.func)
                    break
        self._chains = graph.reach(set(self._site))

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        out: list[Finding] = []
        graph = self._graph

        def walk(node: ast.AST, depth: int, cls, fname: str) -> None:
            if isinstance(node, ast.ClassDef):
                for child in ast.iter_child_nodes(node):
                    walk(child, depth, node.name, fname)
                return
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # a nested def's body doesn't run under the outer lock
                inner = 1 if _is_locked_decorated(node) else 0
                label = f"{cls}.{node.name}" if cls else node.name
                for child in ast.iter_child_nodes(node):
                    walk(child, inner, cls, label)
                return
            d = depth
            if isinstance(node, ast.With):  # sync only, not AsyncWith
                if any(
                    _mentions_lock(item.context_expr)
                    for item in node.items
                ):
                    d = depth + 1
            if depth > 0 and isinstance(node, ast.Call):
                if self._is_blocking(node):
                    f = sf.finding(
                        self.code,
                        node,
                        f"blocking call `{_expr_text(node.func)}(...)` "
                        "while a threading lock is held stalls every "
                        "thread contending for it; move it outside the "
                        "lock",
                    )
                    if f:
                        out.append(f)
                elif graph is not None:
                    q = graph.resolve(sf.path, cls, node)
                    chain = self._chains.get(q) if q else None
                    if chain:
                        names = tuple(graph.display(x) for x in chain)
                        if fname:
                            names = (fname,) + names
                        f = sf.finding(
                            self.code,
                            node,
                            f"call `{_expr_text(node.func)}(...)` while "
                            "a threading lock is held reaches blocking "
                            f"`{self._site[chain[-1]]}(...)` via "
                            f"{' -> '.join(names)}; move the blocking "
                            "work outside the lock",
                            chain=names,
                        )
                        if f:
                            out.append(f)
            for child in ast.iter_child_nodes(node):
                walk(child, d, cls, fname)

        walk(sf.tree, 0, None, "")
        return out


class LockOrderRule(Rule):
    """BB003: locks must be acquired in the declared hierarchy
    (analysis/lock_hierarchy.py) — now covering every package lock
    (thread AND asyncio) and TRANSITIVE across call edges.

    Acquiring a lower-level lock while holding a higher-level one is the
    classic ABBA deadlock setup; the levels in lock_hierarchy.HIERARCHY
    match the call direction the code actually uses (replication sweep
    reaches into the peer pool and the wire, manager methods reach into
    the table — never the reverse). v2 also flags a call site under a
    held lock whose callee transitively acquires an out-of-order lock,
    with the full call chain, and resolves simple local aliases
    (`lock = self._locks.setdefault(...)` then `async with lock:`).
    """

    code = "BB003"
    name = "lock-order-violation"
    summary = "lock acquired against the declared hierarchy"

    def __init__(self):
        self._graph = None
        # lock key -> {qname: shortest chain to a direct acquirer}
        self._chains: dict[str, dict[str, tuple[str, ...]]] = {}

    @staticmethod
    def _classify(sf: SourceFile, expr: ast.AST, aliases: dict) -> str | None:
        if isinstance(expr, ast.Name) and expr.id in aliases:
            return aliases[expr.id]
        text = _STRINGS_RE.sub("", _expr_text(expr)).lower()
        return lock_hierarchy.classify(text, sf.path)

    @classmethod
    def _aliases(cls, sf: SourceFile, fn: ast.AST) -> dict[str, str]:
        """name -> lock key for simple local lock aliases inside fn."""
        out: dict[str, str] = {}
        for n in body_walk(fn):
            if (
                isinstance(n, ast.Assign)
                and len(n.targets) == 1
                and isinstance(n.targets[0], ast.Name)
            ):
                text = _STRINGS_RE.sub("", _expr_text(n.value)).lower()
                key = lock_hierarchy.classify(text, sf.path)
                if key:
                    out[n.targets[0].id] = key
        return out

    @classmethod
    def _direct_keys(cls, sf: SourceFile, fn: ast.AST) -> set[str]:
        keys: set[str] = set()
        if sf.path.endswith("kv/cache_manager.py") and _is_locked_decorated(
            fn
        ):
            keys.add("kv.cache_manager")
        aliases = cls._aliases(sf, fn)
        for n in body_walk(fn):
            if isinstance(n, (ast.With, ast.AsyncWith)):
                for item in n.items:
                    k = cls._classify(sf, item.context_expr, aliases)
                    if k:
                        keys.add(k)
        return keys

    def prepare(self, files: list[SourceFile], graph) -> None:
        self._graph = graph
        direct = {
            q: self._direct_keys(fi.sf, fi.node)
            for q, fi in graph.functions.items()
        }
        all_keys = set().union(*direct.values()) if direct else set()
        self._chains = {
            k: graph.reach({q for q, ks in direct.items() if k in ks})
            for k in sorted(all_keys)
        }

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        out: list[Finding] = []
        graph = self._graph
        in_cm = sf.path.endswith("kv/cache_manager.py")

        def walk(node, held: list[str], cls, fname: str, aliases) -> None:
            if isinstance(node, ast.ClassDef):
                for child in ast.iter_child_nodes(node):
                    walk(child, held, node.name, fname, aliases)
                return
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # @_locked methods run with the cache_manager lock held
                inner = (
                    ["kv.cache_manager"]
                    if (in_cm and _is_locked_decorated(node))
                    else []
                )
                label = f"{cls}.{node.name}" if cls else node.name
                fa = self._aliases(sf, node)
                for child in ast.iter_child_nodes(node):
                    walk(child, inner, cls, label, fa)
                return
            h = held
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    k = self._classify(sf, item.context_expr, aliases)
                    if k is None:
                        continue
                    for prev in h:
                        ok, why = lock_hierarchy.edge_allowed(prev, k)
                        if not ok:
                            f = sf.finding(
                                self.code,
                                node,
                                f"acquires `{k}` while holding `{prev}`: "
                                f"{why} (see analysis/lock_hierarchy.py)",
                            )
                            if f:
                                out.append(f)
                            break
                    h = h + [k]
            elif h and isinstance(node, ast.Call) and graph is not None:
                q = graph.resolve(sf.path, cls, node)
                if q:
                    done = False
                    for k, chains in self._chains.items():
                        if done:
                            break
                        chain = chains.get(q)
                        if not chain:
                            continue
                        for prev in h:
                            ok, why = lock_hierarchy.edge_allowed(prev, k)
                            if ok:
                                continue
                            names = tuple(
                                graph.display(x) for x in chain
                            )
                            if fname:
                                names = (fname,) + names
                            f = sf.finding(
                                self.code,
                                node,
                                f"call `{_expr_text(node.func)}(...)` "
                                f"transitively acquires `{k}` via "
                                f"{' -> '.join(names)} while holding "
                                f"`{prev}`: {why} (see "
                                "analysis/lock_hierarchy.py)",
                                chain=names,
                            )
                            if f:
                                out.append(f)
                            done = True
                            break
            for child in ast.iter_child_nodes(node):
                walk(child, h, cls, fname, aliases)

        walk(sf.tree, [], None, "", {})
        return out


class WireCompatRule(Rule):
    """BB004: a wire dataclass whose `from_wire` splats the wire dict
    into the constructor must (a) filter unknown keys through
    dataclasses.fields and (b) default every field.

    PR 6's compat story in one rule: (a) lets an OLD server accept a
    NEW peer's dict (unknown fields dropped), (b) lets a NEW server
    accept an OLD peer's dict (missing fields defaulted). from_wire
    bodies that construct field-by-field (TensorMeta) opt out of the
    splat pattern and are trusted to handle versioning manually.
    """

    code = "BB004"
    name = "wire-field-compat"
    summary = "wire dataclass field without from_wire filter or default"

    def _is_dataclass(self, cls: ast.ClassDef) -> bool:
        for d in cls.decorator_list:
            if "dataclass" in _expr_text(d):
                return True
        return False

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        out: list[Finding] = []
        for cls in ast.walk(sf.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            if not self._is_dataclass(cls):
                continue
            fw = next(
                (
                    n
                    for n in cls.body
                    if isinstance(
                        n, (ast.FunctionDef, ast.AsyncFunctionDef)
                    )
                    and n.name == "from_wire"
                ),
                None,
            )
            if fw is None:
                continue
            splat = any(
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Name)
                and n.func.id == "cls"
                and any(kw.arg is None for kw in n.keywords)
                for n in ast.walk(fw)
            )
            if not splat:
                continue
            filtered = any(
                isinstance(n, ast.Call) and _call_name(n) == "fields"
                for n in ast.walk(fw)
            )
            if not filtered:
                f = sf.finding(
                    self.code,
                    fw,
                    f"{cls.name}.from_wire splats the wire dict into "
                    "cls(**...) without a dataclasses.fields filter; "
                    "a newer peer's unknown field will crash this "
                    "version",
                )
                if f:
                    out.append(f)
            for stmt in cls.body:
                if (
                    isinstance(stmt, ast.AnnAssign)
                    and isinstance(stmt.target, ast.Name)
                    and stmt.value is None
                    and not stmt.target.id.startswith("_")
                ):
                    f = sf.finding(
                        self.code,
                        stmt,
                        f"wire field {cls.name}.{stmt.target.id} has no "
                        "default; an older peer's dict that lacks it "
                        "will crash from_wire",
                    )
                    if f:
                        out.append(f)
        return out


class EnvRegistryRule(Rule):
    """BB005: every BBTPU_* switch is read through utils/env.get, never
    raw os.environ/getenv.

    The registry is what makes `cli/health --switches` and the README
    table authoritative; a raw read is an undocumented switch with no
    type coercion and no default in one place. Raw WRITES (tests'
    save/set/restore) are out of scope.
    """

    code = "BB005"
    name = "env-read-bypasses-registry"
    summary = "raw os.environ/getenv read of a BBTPU_* switch"

    def _bbtpu_key(self, node: ast.AST) -> str | None:
        if (
            isinstance(node, ast.Constant)
            and isinstance(node.value, str)
            and node.value.startswith("BBTPU_")
        ):
            return node.value
        return None

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        if sf.path.endswith("utils/env.py"):
            return []
        out: list[Finding] = []
        for node in ast.walk(sf.tree):
            key = None
            if isinstance(node, ast.Call) and node.args:
                f = node.func
                if (
                    isinstance(f, ast.Attribute)
                    and f.attr == "get"
                    and _expr_text(f.value).endswith("environ")
                ):
                    key = self._bbtpu_key(node.args[0])
                elif _call_name(node) == "getenv":
                    key = self._bbtpu_key(node.args[0])
            elif isinstance(node, ast.Subscript) and isinstance(
                node.ctx, ast.Load
            ):
                if _expr_text(node.value).endswith("environ"):
                    key = self._bbtpu_key(node.slice)
            if key is None:
                continue
            f = sf.finding(
                self.code,
                node,
                f"raw environment read of {key} bypasses "
                "utils/env.declare; declare the switch and read it "
                "via env.get",
            )
            if f:
                out.append(f)
        return out


class CounterSurfacingRule(Rule):
    """BB006: a counter incremented in server/kv code must be surfaced —
    its name must appear as a string literal somewhere in the scanned
    tree (rpc_info dict key, health --probe key, stats() dict).

    A counter nobody can read is debugging theater: PR 4/5/8 each
    shipped counters precisely so operators can see replication lag /
    chunking / fusing without log access. Private bookkeeping escapes
    with a leading underscore.
    """

    code = "BB006"
    name = "counter-not-surfaced"
    summary = "server counter never surfaced via rpc_info/health"

    def __init__(self):
        # name -> (SourceFile, node) of the first increment site
        self.counters: dict[str, tuple[SourceFile, ast.AST]] = {}
        self.surfaced: set[str] = set()

    SCOPES = ("/server/", "/kv/", "server/", "kv/")

    def _in_scope(self, path: str) -> bool:
        return "/server/" in path or "/kv/" in path or path.startswith(
            ("server/", "kv/")
        )

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Constant) and isinstance(
                node.value, str
            ):
                self.surfaced.add(node.value)
        if self._in_scope(sf.path):
            for node in ast.walk(sf.tree):
                if (
                    isinstance(node, ast.AugAssign)
                    and isinstance(node.target, ast.Attribute)
                    and isinstance(node.target.value, ast.Name)
                    and node.target.value.id == "self"
                    and not node.target.attr.startswith("_")
                ):
                    self.counters.setdefault(
                        node.target.attr, (sf, node)
                    )
        return []

    def finalize(self) -> list[Finding]:
        out = []
        for name, (sf, node) in sorted(self.counters.items()):
            if name in self.surfaced:
                continue
            f = sf.finding(
                self.code,
                node,
                f"counter `self.{name}` is incremented in server code "
                "but never surfaced (no string literal names it in "
                "rpc_info / health --probe / stats()); surface it or "
                "prefix it with `_`",
            )
            if f:
                out.append(f)
        return out


class ExactTensorCompareRule(Rule):
    """BB007: no exact equality on hidden-state tensors in client/server
    verification paths.

    Honest replicas differ in ulps: float reductions are batch-width
    dependent (a server batching our rows with a stranger's sums in a
    different order), so `lie == truth`-style checks convict honest
    peers — the exact trap the integrity layer's `tensors_close`
    (client/integrity.py) exists to avoid. Byte-exact digests over the
    SAME serialized array (kv/prefix.out_digest) are a different thing
    and stay quiet: the rule only fires on float-compare calls
    (np.array_equal & co.) and on `==`/`!=` where BOTH sides are
    hidden-state expressions. Shape/dtype/index comparisons are excluded
    by token.
    """

    code = "BB007"
    name = "exact-float-tensor-compare"
    summary = "exact equality compare on hidden-state tensors"

    EQ_CALLS = {"array_equal", "array_equiv", "assert_array_equal"}
    HIDDENISH = ("hidden", "activation", "logits")
    # any of these underscore-separated name parts anywhere in the
    # expression means it is NOT a float-tensor payload (geometry,
    # bookkeeping, identifiers). Matched per-part, not per-substring:
    # "hidden" must not be excluded just because it contains "id"
    EXCLUDE = {
        "shape", "dtype", "size", "dim", "dims", "len", "count", "num",
        "idx", "index", "id", "ids", "step", "pos", "digest", "token",
        "tokens",
    }

    def _in_scope(self, path: str) -> bool:
        return (
            "/client/" in path
            or "/server/" in path
            or path.startswith(("client/", "server/"))
        )

    @staticmethod
    def _tokens(node: ast.AST) -> list[str]:
        toks = []
        for n in ast.walk(node):
            if isinstance(n, ast.Name):
                toks.append(n.id.lower())
            elif isinstance(n, ast.Attribute):
                toks.append(n.attr.lower())
        return toks

    def _hiddenish(self, node: ast.AST) -> bool:
        toks = self._tokens(node)
        if any(p in self.EXCLUDE for t in toks for p in t.split("_")):
            return False
        for t in toks:
            if any(h in t for h in self.HIDDENISH):
                return True
            # span outputs are conventionally named out / outs / *_out
            if any(p in ("out", "outs", "outputs") for p in t.split("_")):
                return True
        return False

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        if not self._in_scope(sf.path):
            return []
        out: list[Finding] = []
        for node in ast.walk(sf.tree):
            bad = None
            if isinstance(node, ast.Call):
                if _call_name(node) in self.EQ_CALLS and any(
                    self._hiddenish(a) for a in node.args
                ):
                    bad = f"`{_call_name(node)}(...)`"
            elif isinstance(node, ast.Compare):
                if (
                    all(
                        isinstance(op, (ast.Eq, ast.NotEq))
                        for op in node.ops
                    )
                    and self._hiddenish(node.left)
                    and all(
                        self._hiddenish(c) for c in node.comparators
                    )
                ):
                    bad = f"`{_expr_text(node)}`"
            if bad is None:
                continue
            f = sf.finding(
                self.code,
                node,
                f"exact equality {bad} on hidden-state tensors convicts "
                "honest replicas over ulp drift (float reductions are "
                "batch-width dependent); use the dtype-aware "
                "tensors_close (client/integrity.py) instead",
            )
            if f:
                out.append(f)
        return out


class RawClockRule(Rule):
    """BB008: package code must tell time through utils/clock.py, never
    the stdlib directly.

    The deterministic chaos substrate works by swapping the process
    clock (scaled for soak runs, steppable for timing tests): every
    lease expiry, ban probe, quarantine window, keepalive and announce
    period advances on `clock.*`. One raw `time.monotonic()` in a
    timing decision silently splits the codebase into two clock domains
    and the steppable tests hang (virtual time advances, the raw site
    doesn't). Flags calls to ``time()``/``monotonic()``/``sleep()`` on
    any imported alias of the ``time`` module, and ``from time import``
    of those names (they escape as callbacks). ``time.perf_counter()``
    stays legal: duration *measurement* (throughput, codec timing) must
    read real hardware time even under a virtual clock — but it must
    never feed a deadline. Harnesses outside the package (chip_smoke.py,
    scripts/) keep real time and are out of scope.
    """

    code = "BB008"
    name = "raw-clock"
    summary = "raw time.time/monotonic/sleep bypasses the virtual clock"

    BANNED = {"time", "monotonic", "sleep"}

    def _in_scope(self, path: str) -> bool:
        p = path.replace("\\", "/")
        if "bloombee_tpu/" not in p and not p.startswith(
            ("client/", "server/", "kv/", "swarm/", "wire/", "utils/",
             "models/", "runtime/", "cli/", "analysis/")
        ):
            return False
        return not p.endswith("utils/clock.py")

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        if not self._in_scope(sf.path):
            return []
        out: list[Finding] = []
        aliases: set[str] = set()
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "time":
                        aliases.add(a.asname or "time")
            elif isinstance(node, ast.ImportFrom):
                if node.module == "time" and node.level == 0:
                    for a in node.names:
                        if a.name in self.BANNED:
                            f = sf.finding(
                                self.code, node,
                                f"`from time import {a.name}` escapes the "
                                "virtual clock as a bare callable; import "
                                "bloombee_tpu.utils.clock and call "
                                f"clock.{'now' if a.name == 'time' else a.name}"
                                "() instead",
                            )
                            if f:
                                out.append(f)
        if not aliases:
            return out
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if (
                isinstance(fn, ast.Attribute)
                and isinstance(fn.value, ast.Name)
                and fn.value.id in aliases
                and fn.attr in self.BANNED
            ):
                repl = "now" if fn.attr == "time" else fn.attr
                f = sf.finding(
                    self.code, node,
                    f"raw `{fn.value.id}.{fn.attr}()` bypasses the virtual "
                    "clock (utils/clock.py): steppable/scaled test clocks "
                    "cannot reach it, so chaos timing tests hang or race; "
                    f"use clock.{repl}() (clock.async_sleep() in "
                    "coroutines; clock.perf_counter() is allowed for pure "
                    "duration measurement)",
                )
                if f:
                    out.append(f)
        return out


class AsyncBlockingRule(Rule):
    """BB009: blocking sync work on the event loop.

    One stalled loop tick delays EVERY session on the server — an
    event-loop stall is a time-between-tokens regression for the whole
    swarm, the exact Orca-metric the batcher exists to protect. Two
    modes on the shared call graph:

    - direct: a blocking sync call (`clock.sleep`, d2h `.resolve()` /
      `block_until_ready`, `open` file I/O, tensor (de)serialization)
      written directly in a coroutine body. Awaited calls are exempt
      (`await clock.async_sleep()` suspends, it doesn't block), and
      callables passed to `to_thread`/`run_in_executor` never look like
      call sites, so thread offload stays quiet by construction.
    - transitive, inside an `async with <lock>` critical section: a
      resolved call whose callee reaches a blocking site through the
      call graph. Under an asyncio lock a stall is a convoy — every
      task queued on the lock serializes behind the blocked tick — so
      the deeper search is worth its false-positive risk there, and
      only there.

    Harnesses outside the package (chip_smoke.py, scripts/) keep their
    blocking I/O and are out of scope, like BB008.
    """

    code = "BB009"
    name = "event-loop-blocking-call"
    summary = "blocking sync call on the event loop / under an asyncio lock"

    BLOCKING_ATTRS = {"sleep", "resolve", "block_until_ready"}
    BLOCKING_NAMES = {"open", "serialize_tensors", "deserialize_tensors"}

    def __init__(self):
        self._graph = None
        self._chains: dict[str, tuple[str, ...]] = {}
        self._site: dict[str, str] = {}

    def _in_scope(self, path: str) -> bool:
        p = path.replace("\\", "/")
        return "bloombee_tpu/" in p or p.startswith(
            ("client/", "server/", "kv/", "swarm/", "wire/", "utils/",
             "models/", "runtime/", "cli/", "analysis/")
        )

    def _is_blocking(self, node: ast.Call) -> bool:
        f = node.func
        if isinstance(f, ast.Attribute):
            return (
                f.attr in self.BLOCKING_ATTRS
                or f.attr in self.BLOCKING_NAMES
            )
        if isinstance(f, ast.Name):
            return f.id in self.BLOCKING_NAMES
        return False

    def prepare(self, files: list[SourceFile], graph) -> None:
        self._graph = graph
        for q, fi in graph.functions.items():
            if not self._in_scope(fi.path):
                continue
            nodes = list(body_walk(fi.node))
            awaited = {
                id(n.value)
                for n in nodes
                if isinstance(n, ast.Await)
                and isinstance(n.value, ast.Call)
            }
            for n in nodes:
                if (
                    isinstance(n, ast.Call)
                    and id(n) not in awaited
                    and self._is_blocking(n)
                ):
                    self._site[q] = _expr_text(n.func)
                    break
        self._chains = graph.reach(set(self._site))

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        if not self._in_scope(sf.path):
            return []
        out: list[Finding] = []
        graph = self._graph
        awaited = {
            id(n.value)
            for n in ast.walk(sf.tree)
            if isinstance(n, ast.Await) and isinstance(n.value, ast.Call)
        }

        def walk(node, cls, fname: str, in_async: bool, alock: int):
            if isinstance(node, ast.ClassDef):
                for child in ast.iter_child_nodes(node):
                    walk(child, node.name, fname, False, 0)
                return
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # a nested def runs when called, not here; its body gets
                # its own loop/lock context
                label = f"{cls}.{node.name}" if cls else node.name
                is_async = isinstance(node, ast.AsyncFunctionDef)
                for child in ast.iter_child_nodes(node):
                    walk(child, cls, label, is_async, 0)
                return
            a = alock
            if isinstance(node, ast.AsyncWith):
                if any(
                    _mentions_lock(item.context_expr)
                    for item in node.items
                ):
                    a = alock + 1
            if isinstance(node, ast.Call):
                if (
                    in_async
                    and id(node) not in awaited
                    and self._is_blocking(node)
                ):
                    where = (
                        "inside an `async with` lock critical section"
                        if alock
                        else "in a coroutine on the event loop"
                    )
                    f = sf.finding(
                        self.code,
                        node,
                        "blocking sync call "
                        f"`{_expr_text(node.func)}(...)` {where} stalls "
                        "every task on the loop (a TBT regression for "
                        "every session); await an async variant or move "
                        "it to asyncio.to_thread/run_in_executor",
                    )
                    if f:
                        out.append(f)
                elif alock and in_async and graph is not None:
                    q = graph.resolve(sf.path, cls, node)
                    chain = self._chains.get(q) if q else None
                    if chain:
                        names = tuple(graph.display(x) for x in chain)
                        if fname:
                            names = (fname,) + names
                        f = sf.finding(
                            self.code,
                            node,
                            f"call `{_expr_text(node.func)}(...)` inside "
                            "an `async with` lock critical section "
                            "reaches blocking "
                            f"`{self._site[chain[-1]]}(...)` via "
                            f"{' -> '.join(names)}; the loop stalls with "
                            "the lock held, convoying every task queued "
                            "on it — move the blocking work to a thread "
                            "or out of the critical section",
                            chain=names,
                        )
                        if f:
                            out.append(f)
            for child in ast.iter_child_nodes(node):
                walk(child, cls, fname, in_async, a)

        walk(sf.tree, None, "", False, 0)
        return out


class FireAndForgetTaskRule(Rule):
    """BB010: no fire-and-forget `create_task`/`ensure_future`.

    A task whose handle is discarded loses its exception to the GC's
    "Task exception was never retrieved" black hole — and the task
    itself can be collected mid-flight (asyncio only holds a weak
    reference). The promotion/announce loops died exactly this way
    before the supervisor existed. Only a bare expression statement
    counts: assigning the handle, returning it, passing it to a
    gather/list, or chaining `.add_done_callback(...)` (the rpc._spawn
    pattern) all keep an owner and stay quiet.
    """

    code = "BB010"
    name = "fire-and-forget-task"
    summary = "create_task/ensure_future handle discarded"

    SPAWNERS = {"create_task", "ensure_future"}

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(sf.tree):
            if (
                isinstance(node, ast.Expr)
                and isinstance(node.value, ast.Call)
                and _call_name(node.value) in self.SPAWNERS
            ):
                f = sf.finding(
                    self.code,
                    node,
                    "task handle discarded: fire-and-forget "
                    f"`{_expr_text(node.value.func)}(...)` loses the "
                    "task's exception and the task itself can be GC'd "
                    "mid-flight; keep the handle and attach "
                    "add_done_callback (see wire/rpc.py _spawn) or "
                    "register it with the supervisor",
                )
                if f:
                    out.append(f)
        return out


# --------------------------------------------------------------------------
# JIT-boundary rules (BB011–BB013). Shared scanner: every jax.jit entry
# point in the tree, with its static (shape-bearing) and donated argument
# names. Two defining idioms are recognized:
#
#   span_step = functools.partial(jax.jit, static_argnames=(...),
#                                 donate_argnames=(...))(span_step_impl)
#   @functools.partial(jax.jit, donate_argnames=(...))
#   def _arena_write_all(arena_k, arena_v, ...): ...
#
# plus plain @jax.jit / name = jax.jit(impl). argnums variants map to
# names through the impl's positional parameter order.


@dataclasses.dataclass
class _JitEntry:
    name: str
    path: str
    params: list[str]  # positional parameter order of the impl
    statics: set[str]
    donated: set[str]


def _str_tuple(node: ast.AST) -> list[str]:
    vals = []
    elts = node.elts if isinstance(node, (ast.Tuple, ast.List)) else [node]
    for e in elts:
        if isinstance(e, ast.Constant) and isinstance(e.value, str):
            vals.append(e.value)
    return vals


def _int_tuple(node: ast.AST) -> list[int]:
    vals = []
    elts = node.elts if isinstance(node, (ast.Tuple, ast.List)) else [node]
    for e in elts:
        if isinstance(e, ast.Constant) and isinstance(e.value, int):
            vals.append(e.value)
    return vals


def _jit_keywords(call: ast.Call) -> dict[str, ast.AST] | None:
    """If `call` is a jax.jit(...) / functools.partial(jax.jit, ...)
    configuration call, its keyword nodes; else None."""
    text = _expr_text(call.func)
    if text.endswith("jit"):
        return {kw.arg: kw.value for kw in call.keywords if kw.arg}
    if _call_name(call) == "partial" and call.args:
        if _expr_text(call.args[0]).endswith("jit"):
            return {kw.arg: kw.value for kw in call.keywords if kw.arg}
    return None


def _param_names(fn: ast.AST) -> list[str]:
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return []
    a = fn.args
    return [p.arg for p in a.posonlyargs + a.args]


def _outermost_functions(tree: ast.AST):
    """Function defs not nested inside another function def: closures
    are analyzed via their enclosing function's walk (they share its
    frame), and walking them twice would duplicate findings."""
    nested: set[int] = set()
    for fn in ast.walk(tree):
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for sub in ast.walk(fn):
                if sub is not fn and isinstance(
                    sub, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    nested.add(id(sub))
    for fn in ast.walk(tree):
        if (
            isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
            and id(fn) not in nested
        ):
            yield fn


def scan_jit_entries(files: list[SourceFile]) -> dict[str, _JitEntry]:
    """Name -> entry for every recognized jit entry point. First
    definition wins on a (pathological) name collision."""
    out: dict[str, _JitEntry] = {}

    def add(name, path, params, kws):
        statics = set(_str_tuple(kws.get("static_argnames", ast.Tuple([], None))))
        donated = set(_str_tuple(kws.get("donate_argnames", ast.Tuple([], None))))
        for i in _int_tuple(kws.get("static_argnums", ast.Tuple([], None))):
            if 0 <= i < len(params):
                statics.add(params[i])
        for i in _int_tuple(kws.get("donate_argnums", ast.Tuple([], None))):
            if 0 <= i < len(params):
                donated.add(params[i])
        out.setdefault(
            name, _JitEntry(name, path, params, statics, donated)
        )

    for sf in files:
        defs = {
            n.name: n
            for n in ast.walk(sf.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if isinstance(dec, ast.Call):
                        kws = _jit_keywords(dec)
                        if kws is not None:
                            add(node.name, sf.path, _param_names(node), kws)
                    elif _expr_text(dec).endswith("jit"):
                        add(node.name, sf.path, _param_names(node), {})
            elif (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Call)
            ):
                # name = functools.partial(jax.jit, ...)(impl)  or
                # name = jax.jit(impl, static_argnames=...)
                inner = node.value
                kws = None
                impl = None
                if isinstance(inner.func, ast.Call):
                    kws = _jit_keywords(inner.func)
                    impl = inner.args[0] if inner.args else None
                else:
                    text = _expr_text(inner.func)
                    if text.endswith("jit"):
                        kws = {
                            kw.arg: kw.value
                            for kw in inner.keywords
                            if kw.arg
                        }
                        impl = inner.args[0] if inner.args else None
                if kws is None:
                    continue
                params: list[str] = []
                if isinstance(impl, ast.Name) and impl.id in defs:
                    params = _param_names(defs[impl.id])
                add(node.targets[0].id, sf.path, params, kws)
    return out


class HotPathHostSyncRule(Rule):
    """BB011: no implicit device→host sync reachable from a decode hot
    path.

    The compute queue serializes every session's device work; one
    `.item()` / `float(out)` / `np.asarray(out)` / `block_until_ready`
    inside the dispatch subtree stalls the whole pipeline for a device
    round trip per step — the convoy PR 5/8 removed by making fetch an
    off-queue operation. Hot roots are the group dispatchers and the
    step driver; reachability rides the PR-14 call graph, and each
    finding prints the chain from its root. `float()`/`int()`/`bool()`/
    `np.asarray` only fire on device-ish value names (out/logits/
    dev/...) — host-side numpy bookkeeping (`int(lens.max())`) is not a
    sync. The one deliberate sync (executor.fetch, wire-bound by
    contract) carries an owner noqa.
    """

    code = "BB011"
    name = "hot-path-host-sync"
    summary = "implicit device->host sync reachable from a decode hot path"

    HOT_ROOTS = {"decode_group", "prefill_chunk", "_run_step"}
    ALWAYS_SYNC_ATTRS = {"item", "block_until_ready", "device_get"}
    CAST_NAMES = {"float", "int", "bool"}
    NP_ALIASES = {"np", "numpy", "onp"}
    DEVICEISH = {"out", "dev", "device", "logits", "toks"}
    # code shipped to another thread is off the compute queue / event
    # loop by construction — the entire point of these wrappers
    OFFLOAD_CALLS = {"to_thread", "run_in_executor"}
    # a name bound from one of these is a HOST value: the d2h round
    # trip already happened, deliberately, at the one chokepoint
    HOST_PRODUCERS = {"to_thread", "run_in_executor", "fetch"}

    def __init__(self):
        self._graph = None
        self._hot: dict[str, tuple[str, ...]] = {}  # qname -> chain

    def prepare(self, files: list[SourceFile], graph) -> None:
        self._graph = graph
        roots = [
            q for q, fi in graph.functions.items()
            if fi.name in self.HOT_ROOTS
        ]
        parent: dict[str, str] = {}
        seen = set(roots)
        queue = list(roots)
        while queue:
            q = queue.pop(0)
            for callee, _ in graph.edges.get(q, ()):
                if callee not in seen:
                    seen.add(callee)
                    parent[callee] = q
                    queue.append(callee)
        for q in seen:
            chain = [q]
            while chain[-1] in parent:
                chain.append(parent[chain[-1]])
            self._hot[q] = tuple(reversed(chain))

    def _deviceish(self, node: ast.AST, host_names: set[str]) -> bool:
        for n in ast.walk(node):
            name = None
            if isinstance(n, ast.Name):
                name = n.id
            elif isinstance(n, ast.Attribute):
                name = n.attr
            if name is None or name in host_names:
                continue
            if any(p in self.DEVICEISH for p in name.lower().split("_")):
                return True
        return False

    @classmethod
    def _host_names(cls, fn: ast.AST) -> set[str]:
        """Names this function declares host-side: parameters annotated
        np.ndarray, and names bound from an offload wrapper or a
        fetch() — the sync already happened where it belongs."""
        out: set[str] = set()
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for p in fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs:
                ann = _expr_text(p.annotation) if p.annotation else ""
                if "ndarray" in ann:
                    out.add(p.arg)
        for n in ast.walk(fn):
            if not isinstance(n, ast.Assign):
                continue
            v = n.value
            if isinstance(v, ast.Await):
                v = v.value
            if (
                isinstance(v, ast.Call)
                and _call_name(v) in cls.HOST_PRODUCERS
            ):
                for t in n.targets:
                    elts = (
                        t.elts if isinstance(t, (ast.Tuple, ast.List))
                        else [t]
                    )
                    out.update(
                        e.id for e in elts if isinstance(e, ast.Name)
                    )
        return out

    @classmethod
    def _offloaded_ids(cls, fn: ast.AST) -> set[int]:
        """Ids of nodes inside the argument subtrees of
        asyncio.to_thread / loop.run_in_executor calls: that code runs
        on another thread, off the compute queue."""
        out: set[int] = set()
        for n in ast.walk(fn):
            if (
                isinstance(n, ast.Call)
                and _call_name(n) in cls.OFFLOAD_CALLS
            ):
                for a in list(n.args) + [kw.value for kw in n.keywords]:
                    out.update(id(x) for x in ast.walk(a))
        return out

    def _sync_site(
        self, node: ast.Call, host_names: set[str]
    ) -> str | None:
        f = node.func
        if isinstance(f, ast.Attribute):
            if f.attr in self.ALWAYS_SYNC_ATTRS:
                return f.attr
            if (
                f.attr in ("asarray", "array")
                and isinstance(f.value, ast.Name)
                and f.value.id in self.NP_ALIASES
                and node.args
                and self._deviceish(node.args[0], host_names)
            ):
                return f"np.{f.attr}"
        elif isinstance(f, ast.Name) and f.id in self.CAST_NAMES:
            if len(node.args) == 1 and self._deviceish(
                node.args[0], host_names
            ):
                return f.id
        return None

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        out: list[Finding] = []
        graph = self._graph
        if graph is None:
            return out
        seen_sites: set[int] = set()  # closures appear under their
        # enclosing function's qname too; flag each site once
        for q, chain in self._hot.items():
            fi = graph.functions[q]
            if fi.sf is not sf:
                continue
            names = " -> ".join(graph.display(x) for x in chain)
            host_names = self._host_names(fi.node)
            offloaded = self._offloaded_ids(fi.node)
            # full walk, nested closures included: the dispatchers run
            # their `_run` closures inline on the compute thread
            for n in ast.walk(fi.node):
                if not isinstance(n, ast.Call) or id(n) in seen_sites:
                    continue
                if id(n) in offloaded:
                    continue  # runs on another thread, off-queue
                site = self._sync_site(n, host_names)
                if site is None:
                    continue
                seen_sites.add(id(n))
                f = sf.finding(
                    self.code,
                    n,
                    f"implicit device->host sync `{site}` on the decode "
                    f"hot path (reachable via {names}): it blocks the "
                    "serialized compute queue for a device round trip — "
                    "return the lazy array and fetch off-queue "
                    "(executor.fetch), or mark the deliberate sync with "
                    "`# bbtpu: noqa[BB011]` naming the owner",
                    chain=tuple(graph.display(x) for x in chain),
                )
                if f:
                    out.append(f)
        return out


class UnbucketedJitShapeRule(Rule):
    """BB012: a static (shape-bearing) argument of a jit entry call must
    not derive from a data-dependent Python value without a bucketer on
    the path.

    Every distinct static-arg tuple is a full XLA retrace+recompile;
    feeding a request-dependent raw size (`t = hidden.shape[1]`,
    `r = sum(counts)`) straight into `t=`/`r=`/`max_pages=` compiles
    once PER REQUEST SHAPE — the recompile storm the pow2 bucketing
    discipline (next_pow2 / plan_prefill_chunks) exists to cap at
    O(log T). The rule follows simple local assignments (closures read
    their enclosing frame): a bucketer call anywhere on the derivation
    path clears the value; a derivation showing data sources (.shape,
    len()/int()/sum()/max()/min()) with no bucketer is flagged; anything
    else (attributes, constants, config) stays quiet. Scope: entries
    defined in runtime/ and ops/.
    """

    code = "BB012"
    name = "unbucketed-jit-shape-arg"
    summary = "data-dependent static jit arg with no pow2 bucketing"

    BUCKETERS = ("next_pow2", "plan_prefill_chunks")
    _DATA_RE = re.compile(
        r"\bint\(|\blen\(|\bsum\(|\bmax\(|\bmin\(|\.shape\b"
    )
    _BUCKET_RE = re.compile(r"\bnext_pow2\(|\bplan_prefill_chunks\(")

    def __init__(self):
        self._entries: dict[str, _JitEntry] = {}

    def prepare(self, files: list[SourceFile], graph) -> None:
        self._entries = {
            name: e
            for name, e in scan_jit_entries(files).items()
            if "runtime/" in e.path or "ops/" in e.path
        }

    @staticmethod
    def _assign_map(fn: ast.AST) -> dict[str, list[str]]:
        """name -> [assigned expr text, ...] over the whole function,
        nested closures included (they read the enclosing frame)."""
        out: dict[str, list[str]] = {}
        for n in ast.walk(fn):
            if isinstance(n, ast.Assign):
                targets = []
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        targets.append(t.id)
                    elif isinstance(t, ast.Tuple):
                        targets.extend(
                            e.id for e in t.elts if isinstance(e, ast.Name)
                        )
                text = _expr_text(n.value)
                for t in targets:
                    out.setdefault(t, []).append(text)
            elif isinstance(n, ast.AugAssign) and isinstance(
                n.target, ast.Name
            ):
                out.setdefault(n.target.id, []).append(_expr_text(n.value))
        return out

    def _classify(
        self, expr: ast.AST, assigns: dict[str, list[str]]
    ) -> str | None:
        """'bucketed' | 'raw' | None (unknown/benign). Bucketer wins."""
        texts = [_expr_text(expr)]
        names = [
            n.id for n in ast.walk(expr) if isinstance(n, ast.Name)
        ]
        seen = set()
        for _ in range(5):  # bounded transitive expansion
            nxt: list[str] = []
            for name in names:
                if name in seen:
                    continue
                seen.add(name)
                for text in assigns.get(name, ()):
                    texts.append(text)
                    try:
                        nxt.extend(
                            n.id
                            for n in ast.walk(ast.parse(text, mode="eval"))
                            if isinstance(n, ast.Name)
                        )
                    except SyntaxError:
                        pass
            if not nxt:
                break
            names = nxt
        blob = " ".join(texts)
        if self._BUCKET_RE.search(blob):
            return "bucketed"
        if self._DATA_RE.search(blob):
            return "raw"
        return None

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        out: list[Finding] = []
        if not self._entries:
            return out
        for fn in _outermost_functions(sf.tree):
            assigns = self._assign_map(fn)
            for n in ast.walk(fn):
                if not isinstance(n, ast.Call):
                    continue
                entry = self._entries.get(_call_name(n))
                if entry is None:
                    continue
                checks: list[tuple[str, ast.AST]] = []
                for kw in n.keywords:
                    if kw.arg and kw.arg in entry.statics:
                        checks.append((kw.arg, kw.value))
                for i, a in enumerate(n.args):
                    if i < len(entry.params) and (
                        entry.params[i] in entry.statics
                    ):
                        checks.append((entry.params[i], a))
                for arg_name, val in checks:
                    if self._classify(val, assigns) != "raw":
                        continue
                    f = sf.finding(
                        self.code,
                        n,
                        f"jit entry `{entry.name}(...)`: static shape "
                        f"arg `{arg_name}={_expr_text(val)}` derives "
                        "from a data-dependent value with no bucketer "
                        "(next_pow2/plan_prefill_chunks) on the path — "
                        "every distinct value is a full XLA recompile; "
                        "bucket it like executor._step's bb/tb/pb",
                    )
                    if f:
                        out.append(f)
        return out


class UseAfterDonationRule(Rule):
    """BB013: no read of a donated argument after the jitted call
    returns.

    `donate_argnames` hands the argument's buffer to XLA — after the
    call it is DELETED; any later read raises (or worse, on some
    backends, reads garbage). The `arena_k`/`arena_v` slabs are exactly
    this class: every step donates the KV arena and must thread the
    RETURNED arena forward. The rule tracks the donated argument
    expressions (and the manager-attribute they alias) per function,
    lineno-ordered; a Load of the same expression after the donating
    call is flagged. Reads inside except handlers stay quiet — the
    `_arena_consumed` self-heal contract probes donated buffers
    deliberately — and a reassignment of the root name kills tracking
    (rebinding to the returned buffers is the correct pattern).
    """

    code = "BB013"
    name = "use-after-donation"
    summary = "donated jit argument read after the call"

    def __init__(self):
        self._donating: dict[str, _JitEntry] = {}

    def prepare(self, files: list[SourceFile], graph) -> None:
        self._donating = {
            name: e
            for name, e in scan_jit_entries(files).items()
            if e.donated
        }

    @staticmethod
    def _in_handler(node: ast.AST, handlers: list[set[int]]) -> bool:
        return any(id(node) in h for h in handlers)

    def visit_file(self, sf: SourceFile) -> list[Finding]:
        out: list[Finding] = []
        if not self._donating:
            return out
        handler_sets = [
            {id(x) for stmt in h.body for x in ast.walk(stmt)}
            for h in ast.walk(sf.tree)
            if isinstance(h, ast.ExceptHandler)
        ]
        for fn in _outermost_functions(sf.tree):
            # donating calls in source order, with their donated exprs
            donations: list[tuple[int, ast.Call, list[str]]] = []
            for n in ast.walk(fn):
                if not isinstance(n, ast.Call):
                    continue
                entry = self._donating.get(_call_name(n))
                if entry is None:
                    continue
                exprs: list[str] = []
                for kw in n.keywords:
                    if kw.arg and kw.arg in entry.donated:
                        exprs.append(_expr_text(kw.value))
                for i, a in enumerate(n.args):
                    if i < len(entry.params) and (
                        entry.params[i] in entry.donated
                    ):
                        exprs.append(_expr_text(a))
                if exprs:
                    donations.append((n.lineno, n, exprs))
            if not donations:
                continue
            # a Store of the donated expression (or its root name) after
            # the call rebinds it to the RETURNED buffers — the correct
            # pattern (`ak, av = span_step(ak, av, ...)`) — and kills
            # tracking from that line on. Same-line counts: the rebind
            # statement IS the donating call.
            kills: dict[str, list[int]] = {}
            for n in ast.walk(fn):
                targets: list[ast.AST] = []
                if isinstance(n, ast.Assign):
                    targets = list(n.targets)
                elif isinstance(n, (ast.AugAssign, ast.For)):
                    targets = [n.target]
                for t in targets:
                    elts = (
                        t.elts if isinstance(t, (ast.Tuple, ast.List))
                        else [t]
                    )
                    for e in elts:
                        text = _expr_text(e)
                        if text:
                            kills.setdefault(text, []).append(n.lineno)
            # mutually exclusive if/else arms: a read in the sibling arm
            # of the donating call never executes after it
            branch_pairs: list[tuple[set[int], set[int]]] = []
            for n in ast.walk(fn):
                if isinstance(n, ast.If) and n.orelse:
                    body_ids = {
                        id(x) for s in n.body for x in ast.walk(s)
                    }
                    else_ids = {
                        id(x) for s in n.orelse for x in ast.walk(s)
                    }
                    branch_pairs.append((body_ids, else_ids))
            for call_line, call, exprs in donations:
                call_ids = {id(x) for x in ast.walk(call)}
                flagged: set[str] = set()
                for n in ast.walk(fn):
                    if id(n) in call_ids:
                        continue  # the donating call's own arguments
                    if not isinstance(
                        n, (ast.Subscript, ast.Attribute, ast.Name)
                    ):
                        continue
                    if not isinstance(
                        getattr(n, "ctx", None), ast.Load
                    ):
                        continue
                    line = getattr(n, "lineno", 0)
                    if line <= call_line:
                        continue
                    text = _expr_text(n)
                    if text not in exprs or text in flagged:
                        continue
                    root = text.split("[")[0].split(".")[0]
                    if any(
                        call_line <= k <= line
                        for k in kills.get(text, [])
                        + kills.get(root, [])
                    ):
                        continue  # rebound to the returned buffers
                    if self._in_handler(n, handler_sets):
                        continue  # _arena_consumed recovery contract
                    if any(
                        (id(call) in b and id(n) in e)
                        or (id(call) in e and id(n) in b)
                        for b, e in branch_pairs
                    ):
                        continue  # mutually exclusive branches
                    f = sf.finding(
                        self.code,
                        n,
                        f"`{text}` was DONATED to "
                        f"`{_call_name(call)}(...)` on line {call_line} "
                        "(donate_argnames) — its buffer is deleted when "
                        "the call returns; thread the returned arrays "
                        "forward instead of re-reading the donated ones",
                    )
                    if f:
                        out.append(f)
                    # at most one finding per donated expr per call:
                    # every later read is the same defect
                    flagged.add(text)
        return out


def make_rules() -> list[Rule]:
    """Fresh rule instances (BB006 keeps cross-file state)."""
    return [
        SpeculativeWriteRule(),
        BlockingUnderLockRule(),
        LockOrderRule(),
        WireCompatRule(),
        EnvRegistryRule(),
        CounterSurfacingRule(),
        ExactTensorCompareRule(),
        RawClockRule(),
        AsyncBlockingRule(),
        FireAndForgetTaskRule(),
        HotPathHostSyncRule(),
        UnbucketedJitShapeRule(),
        UseAfterDonationRule(),
    ]


ALL_CODES = tuple(r.code for r in make_rules())
