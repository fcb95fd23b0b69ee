"""bbtpu-lint (bloombee_tpu/analysis): one true-positive and one
true-negative fixture per rule BB001-BB008, plus suppression and
baseline mechanics. Fixtures run through `analyze_source` on in-memory
sources, so these tests never depend on the live tree's findings."""

import textwrap

from bloombee_tpu.analysis import analyze_source
from bloombee_tpu.analysis.cli import main as cli_main
from bloombee_tpu.analysis.core import Finding, SourceFile

CLIENT = "bloombee_tpu/client/mod.py"
SERVER = "bloombee_tpu/server/mod.py"


def codes(src: str, path: str = CLIENT) -> list[str]:
    return [
        f.code
        for f in analyze_source({path: textwrap.dedent(src)})
    ]


# ------------------------------------------------------------------ BB001
BB001_TP = """
    def step(mgr, handle, h):
        mgr.write_slots_ragged(handle, [1], commit=False)
        return h
"""

BB001_TN = """
    def step(mgr, handle, h):
        try:
            mgr.write_slots_ragged(handle, [1], commit=False)
            mgr.commit(handle)
        except Exception:
            mgr.rollback(handle)
            raise
        return h
"""


def test_bb001_true_positive():
    assert codes(BB001_TP) == ["BB001"]


def test_bb001_true_negative():
    assert codes(BB001_TN) == []


def test_bb001_committed_write_is_quiet():
    # commit=True (and commit passed through a variable) is the
    # callee's contract, not a speculative site
    assert codes(
        """
        def f(mgr, handle):
            mgr.write_slots(handle, 2, commit=True)
            mgr.prefill(handle, commit=commit_flag)
        """
    ) == []


def test_bb001_finally_counts_as_recovery():
    assert codes(
        """
        def f(mgr, handle):
            try:
                mgr.assign_write_slots(handle, 4, commit=False)
            finally:
                mgr.truncate_speculative(handle, snaps)
        """
    ) == []


# ------------------------------------------------------------------ BB002
BB002_TP = """
    class C:
        def f(self, conn):
            with self._lock:
                return conn.recv()
"""

BB002_TN = """
    class C:
        def f(self, conn):
            with self._lock:
                self.n = 1
            return conn.recv()

        async def g(self, conn):
            async with self._alock:
                return await conn.recv()
"""


def test_bb002_true_positive():
    assert codes(BB002_TP) == ["BB002"]


def test_bb002_true_negative():
    # blocking outside the lock, or under an asyncio lock (which does
    # not pin a thread), is fine
    assert codes(BB002_TN) == []


def test_bb002_locked_decorator_and_nested_def():
    src = """
        class C:
            @_locked
            def f(self):
                return self.future.result()

            @_locked
            def g(self):
                def later():
                    return self.future.result()
                return later
    """
    # f() blocks under the decorator's lock; g() only DEFINES a
    # function, which does not run under the lock
    assert codes(src) == ["BB002"]


# ------------------------------------------------------------------ BB003
BB003_TP = """
    def f(self):
        with self.table._lock:
            with self.manager._lock:
                pass
"""

BB003_TN = """
    def f(self):
        with self.manager._lock:
            with self.table._lock:
                with self.compute.queue_lock:
                    pass
"""


def test_bb003_true_positive():
    assert codes(BB003_TP) == ["BB003"]


def test_bb003_true_negative():
    assert codes(BB003_TN) == []


# ----------------------------------------- transitive BB002/BB003 (v2)
def findings(src: str, path: str = CLIENT):
    return analyze_source({path: textwrap.dedent(src)})


BB002_TRANSITIVE_TP = """
    class C:
        def hot(self, conn):
            with self._lock:
                self.helper(conn)

        def helper(self, conn):
            return conn.recv()
"""


def test_bb002_transitive_chain_is_flagged_with_trace():
    """The lock holder is flagged even though the blocking call lives
    in a lock-free callee — with the full call chain in the finding."""
    fs = findings(BB002_TRANSITIVE_TP)
    assert [f.code for f in fs] == ["BB002"]
    assert fs[0].chain, "transitive finding carries no call chain"
    assert "helper" in " -> ".join(fs[0].chain)
    assert "recv" in fs[0].message


def test_bb002_transitive_quiet_when_chain_broken():
    # same shape, but the callee no longer blocks: no finding
    assert codes(
        """
        class C:
            def hot(self, conn):
                with self._lock:
                    self.helper(conn)

            def helper(self, conn):
                return conn.poll_nowait()
        """
    ) == []


def test_bb002_transitive_two_deep():
    fs = findings(
        """
        class C:
            def hot(self, conn):
                with self._lock:
                    self.mid(conn)

            def mid(self, conn):
                return self.leaf(conn)

            def leaf(self, conn):
                return conn.recv()
        """
    )
    assert [f.code for f in fs] == ["BB002"]
    chain = " -> ".join(fs[0].chain)
    assert "mid" in chain and "leaf" in chain


def test_bb002_transitive_survives_recursion_and_cycles():
    # recursion (f -> f) and a call cycle (a -> b -> a) must neither
    # hang the reachability pass nor suppress the real finding
    fs = findings(
        """
        class C:
            def hot(self, conn):
                with self._lock:
                    self.a(conn)

            def a(self, conn, n=0):
                if n:
                    return self.a(conn, n - 1)
                return self.b(conn)

            def b(self, conn):
                self.a(conn)
                return conn.recv()
        """
    )
    assert [f.code for f in fs] == ["BB002"]


def test_bb003_transitive_descending_through_call():
    """Holding the paged-table lock (70) while CALLING a helper that
    takes the cache-manager lock (60) is the same ABBA setup as nesting
    the `with` blocks directly."""
    fs = findings(
        """
        class C:
            def f(self):
                with self.table._lock:
                    self.grab_manager()

            def grab_manager(self):
                with self.manager._lock:
                    pass
        """
    )
    assert [f.code for f in fs] == ["BB003"]
    assert fs[0].chain


def test_bb003_transitive_ascending_is_quiet():
    assert codes(
        """
        class C:
            def f(self):
                with self.manager._lock:
                    self.grab_table()

            def grab_table(self):
                with self.table._lock:
                    pass
        """
    ) == []


# ------------------------------------------------------------------ BB009
BB009_TP = """
    import clock

    async def tick(self):
        clock.sleep(0.1)
        return 1
"""

BB009_TN = """
    import clock

    async def tick(self, entry):
        await clock.async_sleep(0.1)
        return await entry.resolve()

    def sync_path(self):
        clock.sleep(0.1)
"""


def test_bb009_true_positive():
    assert codes(BB009_TP) == ["BB009"]


def test_bb009_true_negative():
    # awaited calls suspend instead of blocking, and sync defs are
    # BB002's territory (they don't run on the loop by construction)
    assert codes(BB009_TN) == []


def test_bb009_serialization_under_async_lock():
    fs = findings(
        """
        class C:
            async def send(self, tensors):
                async with self._send_lock:
                    tm, blobs = serialize_tensors(tensors, "none")
                    return tm
        """
    )
    assert [f.code for f in fs] == ["BB009"]
    assert "critical section" in fs[0].message


def test_bb009_transitive_under_async_lock():
    """Under an asyncio lock the search goes through the call graph:
    the helper's sync blocking site convoys every task queued on the
    lock, even though the hot function never blocks directly."""
    fs = findings(
        """
        class C:
            async def send(self, tensors):
                async with self._send_lock:
                    return self.encode(tensors)

            def encode(self, tensors):
                return serialize_tensors(tensors, "none")
        """
    )
    assert [f.code for f in fs] == ["BB009"]
    assert fs[0].chain


def test_bb009_transitive_quiet_without_lock():
    # the transitive mode is deliberately lock-scoped: helper indirection
    # on the plain hot path would be too false-positive-prone
    assert codes(
        """
        class C:
            async def send(self, tensors):
                return self.encode(tensors)

            def encode(self, tensors):
                return serialize_tensors(tensors, "none")
        """
    ) == []


def test_bb009_noqa_suppresses():
    assert codes(
        """
        async def tick(self):
            clock.sleep(0.1)  # bbtpu: noqa[BB009]
        """
    ) == []


# ------------------------------------------------------------------ BB010
BB010_TP = """
    def kick(self, coro):
        asyncio.create_task(coro)
"""

BB010_TN = """
    def kick(self, coro, loop):
        t = asyncio.create_task(coro)
        self._tasks.add(t)
        asyncio.create_task(coro).add_done_callback(self._tasks.discard)
        return asyncio.ensure_future(coro, loop=loop)
"""


def test_bb010_true_positive():
    fs = findings(BB010_TP)
    assert [f.code for f in fs] == ["BB010"]
    assert "_spawn" in fs[0].message


def test_bb010_true_negative():
    assert codes(BB010_TN) == []


# ------------------------------------------------------------------ BB011
BB011_TP = """
    class BlockServer:
        def decode_group(self, out_dev):
            return self._finish(out_dev)

        def _finish(self, out_dev):
            return float(out_dev.sum())
"""

BB011_TN = """
    class BlockServer:
        def cold_path(self, out_dev):
            return out_dev.item()

        def decode_group(self, lens):
            return int(lens.max())
"""


def test_bb011_true_positive_transitive_chain():
    fs = findings(BB011_TP)
    assert [f.code for f in fs] == ["BB011"]
    assert "decode_group" in " -> ".join(fs[0].chain)
    assert "_finish" in " -> ".join(fs[0].chain)


def test_bb011_true_negative():
    # .item() off the hot path, and int() of a host-side length, are
    # both quiet
    assert codes(BB011_TN) == []


def test_bb011_direct_sync_in_hot_root():
    assert codes(
        """
        class BlockServer:
            def prefill_chunk(self, members):
                out = self.executor.prefill_chunk(members)
                out.block_until_ready()
                return out
        """
    ) == ["BB011"]


def test_bb011_offloaded_and_host_bound_are_quiet():
    # the one deliberate d2h runs via asyncio.to_thread (off the
    # compute queue), and names bound from to_thread/fetch are host
    # values — converting them again is not a sync
    assert codes(
        """
        class BlockServer:
            async def decode_group(self, out_dev):
                out = await asyncio.to_thread(self.executor.fetch, out_dev)
                arr = np.asarray(out, dtype=np.int32)
                toks = await asyncio.to_thread(
                    lambda: np.asarray(out_dev, dtype=np.int32)
                )
                return arr, toks
        """
    ) == []


def test_bb011_ndarray_annotated_param_is_quiet():
    # an np.ndarray-annotated parameter declares the value host-side:
    # the fetch already happened at the caller's chokepoint
    assert codes(
        """
        class BlockServer:
            def decode_group(self, out: np.ndarray):
                return np.asarray(out, dtype=np.float32)
        """
    ) == []


def test_bb011_noqa_suppresses():
    assert codes(
        """
        class BlockServer:
            def decode_group(self, out_dev):
                return np.asarray(out_dev)  # bbtpu: noqa[BB011] wire-bound
        """
    ) == []


# ------------------------------------------------------------------ BB012
RUNTIME = "bloombee_tpu/runtime/mod.py"


def jit_src(body: str) -> str:
    """Prelude (a runtime-style jit entry) + a test body, each dedented
    on its own so their indent levels need not match."""
    return textwrap.dedent(BB012_PRELUDE) + textwrap.dedent(body)


BB012_PRELUDE = """
    import functools
    import jax

    def span_step_impl(params, ak, av, h, *, b, t):
        return h, ak, av

    span_step = functools.partial(
        jax.jit, static_argnames=("b", "t"),
        donate_argnames=("ak", "av"),
    )(span_step_impl)
"""

BB012_TP = BB012_PRELUDE + """
    class Exec:
        def step(self, params, arena, hidden):
            t = hidden.shape[1]
            h, ak, av = span_step(
                params, arena["k"], arena["v"], hidden, b=2, t=t
            )
            return h, ak, av
"""

BB012_TN = BB012_PRELUDE + """
    class Exec:
        def step(self, params, arena, hidden):
            t = next_pow2(hidden.shape[1])
            h, ak, av = span_step(
                params, arena["k"], arena["v"], hidden, b=2, t=t
            )
            return h, ak, av
"""


def test_bb012_true_positive_raw_shape():
    fs = findings(BB012_TP, path=RUNTIME)
    assert [f.code for f in fs] == ["BB012"]
    assert "t=t" in fs[0].message


def test_bb012_true_negative_bucketed():
    # the bucketer anywhere on the derivation path clears the value
    assert codes(BB012_TN, path=RUNTIME) == []


def test_bb012_constant_static_is_quiet():
    assert codes(
        jit_src("""
        class Exec:
            def step(self, params, arena, hidden):
                h, ak, av = span_step(
                    params, arena["k"], arena["v"], hidden, b=2, t=8
                )
                return h, ak, av
        """),
        path=RUNTIME,
    ) == []


def test_bb012_transitive_derivation_is_flagged():
    # t -> t_raw -> len(rows): two assignment hops, still raw
    assert codes(
        jit_src("""
        class Exec:
            def step(self, params, arena, hidden, rows):
                t_raw = len(rows)
                t = t_raw + 1
                h, ak, av = span_step(
                    params, arena["k"], arena["v"], hidden, b=2, t=t
                )
                return h, ak, av
        """),
        path=RUNTIME,
    ) == ["BB012"]


def test_bb012_entries_outside_runtime_are_out_of_scope():
    # client-side jit helpers are not serving hot paths
    assert codes(BB012_TP, path=CLIENT) == []


# ------------------------------------------------------------------ BB013
BB013_TP = BB012_PRELUDE + """
    class Exec:
        def step(self, params, arena, hidden):
            h, ak, av = span_step(
                params, arena["k"], arena["v"], hidden, b=2, t=8
            )
            leak = arena["k"].sum()
            return h, leak
"""

BB013_TN = BB012_PRELUDE + """
    class Exec:
        def step(self, params, arena, hidden):
            ak, av = arena["k"], arena["v"]
            h, ak, av = span_step(params, ak, av, hidden, b=2, t=8)
            return h, ak, av
"""


def test_bb013_true_positive():
    fs = findings(BB013_TP, path=RUNTIME)
    assert [f.code for f in fs] == ["BB013"]
    assert "DONATED" in fs[0].message
    assert "arena['k']" in fs[0].message


def test_bb013_true_negative_rebound():
    # rebinding to the returned arrays (same statement) is THE correct
    # donation pattern
    assert codes(BB013_TN, path=RUNTIME) == []


def test_bb013_later_rebind_kills_tracking():
    assert codes(
        jit_src("""
        class Exec:
            def step(self, params, arena, hidden):
                h, ak, av = span_step(
                    params, arena["k"], arena["v"], hidden, b=2, t=8
                )
                arena["k"], arena["v"] = ak, av
                return h, arena["k"].sum()
        """),
        path=RUNTIME,
    ) == []


def test_bb013_except_handler_read_is_quiet():
    # the donated-arena self-heal contract probes consumed buffers in
    # the except handler on purpose (_arena_consumed)
    assert codes(
        jit_src("""
        class Exec:
            def step(self, params, arena, hidden):
                try:
                    h, ak, av = span_step(
                        params, arena["k"], arena["v"], hidden, b=2, t=8
                    )
                except Exception:
                    if self._arena_consumed(arena["k"]):
                        self._rebuild_after_failure("step")
                    raise
                return h, ak, av
        """),
        path=RUNTIME,
    ) == []


def test_bb013_sibling_branch_read_is_quiet():
    # mutually exclusive if/else arms never execute in sequence
    assert codes(
        jit_src("""
        class Exec:
            def step(self, params, arena, hidden, fancy):
                if fancy:
                    h, ak, av = span_step(
                        params, arena["k"], arena["v"], hidden, b=2, t=8
                    )
                else:
                    h = hidden
                    ak, av = arena["k"], arena["v"]
                return h, ak, av
        """),
        path=RUNTIME,
    ) == []


def test_bb013_decorated_jit_form_and_noqa():
    src = jit_src("""
    @functools.partial(jax.jit, donate_argnames=("ak",))
    def write_all(ak, xs):
        return ak

    class Exec:
        def flush(self, arena, xs):
            ak = write_all(arena["k"], xs)
            return arena["k"].shape{noqa}
    """)
    assert codes(src.format(noqa=""), path=RUNTIME) == ["BB013"]
    assert codes(
        src.format(noqa="  # bbtpu: noqa[BB013] probe only"),
        path=RUNTIME,
    ) == []


# ------------------------------------------------------------------ BB004
BB004_TP = """
    import dataclasses

    @dataclasses.dataclass
    class Info:
        version: str

        @classmethod
        def from_wire(cls, d):
            return cls(**d)
"""

BB004_TN = """
    import dataclasses

    @dataclasses.dataclass
    class Info:
        version: str = "v0"

        @classmethod
        def from_wire(cls, d):
            known = {f.name for f in dataclasses.fields(cls)}
            return cls(**{k: v for k, v in d.items() if k in known})
"""


def test_bb004_true_positive():
    # both defects fire: the unfiltered splat (newer peer's unknown
    # field) and the undefaulted field (older peer's missing field)
    found = codes(BB004_TP, path="bloombee_tpu/swarm/mod.py")
    assert found == ["BB004", "BB004"]


def test_bb004_true_negative():
    assert codes(BB004_TN, path="bloombee_tpu/swarm/mod.py") == []


def test_bb004_explicit_construction_opts_out():
    # field-by-field from_wire (TensorMeta-style) handles versioning
    # manually; the splat rules don't apply
    assert codes(
        """
        import dataclasses

        @dataclasses.dataclass
        class Meta:
            dtype: str

            @classmethod
            def from_wire(cls, d):
                return cls(d["dtype"])
        """,
        path="bloombee_tpu/wire/mod.py",
    ) == []


# ------------------------------------------------------------------ BB005
BB005_TP = """
    import os
    TIMEOUT = float(os.environ.get("BBTPU_TIMEOUT_S", "1"))
"""

BB005_TN = """
    import os
    from bloombee_tpu.utils import env
    TIMEOUT = env.get("BBTPU_TIMEOUT_S")
    HOME = os.environ.get("HOME")
    os.environ["BBTPU_TIMEOUT_S"] = "2"
"""


def test_bb005_true_positive():
    assert codes(BB005_TP) == ["BB005"]
    assert codes("import os\nX = os.getenv('BBTPU_X')\n") == ["BB005"]
    assert codes("import os\nX = os.environ['BBTPU_X']\n") == ["BB005"]


def test_bb005_true_negative():
    # registry reads, non-BBTPU keys, and writes (save/set/restore) are
    # all out of scope
    assert codes(BB005_TN) == []


# ------------------------------------------------------------------ BB006
BB006_TP = """
    class S:
        def step(self):
            self.widgets_made += 1
"""

BB006_TN = """
    class S:
        def step(self):
            self.widgets_made += 1
            self._scratch += 1

        def stats(self):
            return {"widgets_made": self.widgets_made}
"""


def test_bb006_true_positive():
    assert codes(BB006_TP, path=SERVER) == ["BB006"]


def test_bb006_true_negative():
    # surfaced via a stats() string key; underscore-prefixed private
    # bookkeeping never needs surfacing
    assert codes(BB006_TN, path=SERVER) == []


def test_bb006_surfacing_may_live_in_another_file():
    findings = analyze_source(
        {
            SERVER: textwrap.dedent(BB006_TP),
            "bloombee_tpu/cli/health.py": "KEYS = ('widgets_made',)\n",
        }
    )
    assert findings == []


def test_bb006_ignores_non_server_code():
    assert codes(BB006_TP, path=CLIENT) == []


# ------------------------------------------------------------------ BB007
BB007_TP = """
    import numpy as np

    def audit(primary_out, audited_hidden):
        if np.array_equal(primary_out, audited_hidden):
            return True
        return audited_hidden == primary_out
"""

BB007_TN = """
    import numpy as np

    def audit(primary_out, audited_hidden, expected_digest, tokens):
        ok = tensors_close(primary_out, audited_hidden)
        same_geom = primary_out.shape == audited_hidden.shape
        byte_check = out_digest(primary_out) == expected_digest
        toks = tokens == [1, 2, 3]
        return ok and same_geom and byte_check and toks
"""


def test_bb007_true_positive():
    # both the helper-call form and the bare `==` on two hidden-state
    # expressions are exact compares that convict honest ulp drift
    assert codes(BB007_TP, path=CLIENT) == ["BB007", "BB007"]


def test_bb007_true_negative():
    # tolerance compare, shape compare, byte-digest compare over the SAME
    # serialized array, and token-id compare are all legitimate
    assert codes(BB007_TN, path=CLIENT) == []


def test_bb007_scoped_to_client_server_paths():
    # test helpers asserting exactness on purpose live outside the
    # verification paths and stay quiet
    assert codes(BB007_TP, path="bloombee_tpu/kv/mod.py") == []


# ------------------------------------------------------------------ BB008
BB008_TP = """
    import time
    import time as _time

    def reap(sessions, lease_s):
        cutoff = time.monotonic() - lease_s
        time.sleep(0.1)
        return [s for s in sessions if s.t < cutoff], _time.time()
"""

BB008_TN = """
    import time
    from bloombee_tpu.utils import clock

    def measure(sessions, lease_s):
        t0 = time.perf_counter()
        cutoff = clock.monotonic() - lease_s
        clock.sleep(0.1)
        live = [s for s in sessions if s.t >= cutoff]
        return live, time.perf_counter() - t0
"""

BB008_FROM_IMPORT = """
    from time import monotonic

    def stamp():
        return monotonic()
"""


def test_bb008_true_positive():
    # every banned call fires, through the bare alias AND the `as _time`
    # alias — the rename idiom must not dodge the rule
    assert codes(BB008_TP, path=SERVER) == ["BB008", "BB008", "BB008"]


def test_bb008_true_negative():
    # clock.* calls and perf_counter duration measurement are the
    # sanctioned idioms; neither fires
    assert codes(BB008_TN, path=SERVER) == []


def test_bb008_flags_from_import():
    # `from time import monotonic` escapes the virtual clock as a bare
    # callable; the import itself is the finding (the call site no longer
    # mentions `time` at all)
    assert codes(BB008_FROM_IMPORT, path=SERVER) == ["BB008"]


def test_bb008_exempts_clock_module_and_harness_code():
    # utils/clock.py IS the real-time boundary; chip_smoke.py is a
    # harness outside the package that reports wall time on purpose
    assert codes(BB008_TP, path="bloombee_tpu/utils/clock.py") == []
    assert codes(BB008_TP, path="chip_smoke.py") == []


# ------------------------------------------------- suppressions & baseline
def test_noqa_suppresses_named_code():
    src = 'import os\nX = os.getenv("BBTPU_X")  # bbtpu: noqa[BB005]\n'
    assert codes(src) == []


def test_noqa_bare_suppresses_everything():
    src = 'import os\nX = os.getenv("BBTPU_X")  # bbtpu: noqa\n'
    assert codes(src) == []


def test_noqa_wrong_code_does_not_suppress():
    src = 'import os\nX = os.getenv("BBTPU_X")  # bbtpu: noqa[BB001]\n'
    assert codes(src) == ["BB005"]


def test_noqa_applies_to_multiline_statement():
    src = (
        "def f(mgr, handle):\n"
        "    mgr.write_slots_ragged(  # bbtpu: noqa[BB001]\n"
        "        handle, [1], commit=False\n"
        "    )\n"
    )
    assert codes(src) == []


def test_fingerprint_survives_line_drift():
    src = 'import os\nX = os.getenv("BBTPU_X")\n'
    (f1,) = analyze_source({CLIENT: src})
    (f2,) = analyze_source({CLIENT: "# a new leading comment\n" + src})
    assert f1.line != f2.line
    assert f1.fingerprint() == f2.fingerprint()


def test_fingerprint_changes_when_line_changes():
    a = Finding("BB005", CLIENT, 2, "m", snippet="X = 1")
    b = Finding("BB005", CLIENT, 2, "m", snippet="X = 2")
    assert a.fingerprint() != b.fingerprint()


def test_source_file_rejects_unparsable_noqa_scan():
    sf = SourceFile(CLIENT, "x = 1  # bbtpu: noqa[BB001, BB005]\n")
    assert sf.noqa[1] == {"BB001", "BB005"}


def test_cli_baseline_workflow(tmp_path, monkeypatch, capsys):
    """new finding fails -> --update-baseline accepts it -> gate green
    -> the NEXT new finding fails again; --no-baseline sees through."""
    monkeypatch.chdir(tmp_path)
    mod = tmp_path / "mod.py"
    mod.write_text('import os\nX = os.getenv("BBTPU_X")\n')
    argv = ["mod.py", "--baseline", "bl.txt"]

    assert cli_main(argv) == 1
    assert cli_main(argv + ["--update-baseline"]) == 0
    assert (tmp_path / "bl.txt").exists()
    assert cli_main(argv) == 0  # baselined finding no longer fails

    mod.write_text(
        'import os\nX = os.getenv("BBTPU_X")\n'
        'Y = os.getenv("BBTPU_Y")\n'
    )
    assert cli_main(argv) == 1  # only the NEW finding trips the gate
    out = capsys.readouterr()
    assert "BBTPU_Y" in out.out
    assert cli_main(argv + ["--no-baseline"]) == 1


def test_cli_json_output(tmp_path, monkeypatch, capsys):
    """--json emits the findings machine-readably on stdout (rule id,
    fingerprint, path:line, call chain) with the summary on stderr; the
    human text format is a separate code path and stays byte-stable."""
    import json

    monkeypatch.chdir(tmp_path)
    mod = tmp_path / "mod.py"
    mod.write_text(
        "class C:\n"
        "    def hot(self, conn):\n"
        "        with self._lock:\n"
        "            self.helper(conn)\n"
        "    def helper(self, conn):\n"
        "        return conn.recv()\n"
    )
    argv = ["mod.py", "--baseline", "bl.txt"]

    assert cli_main(argv + ["--json"]) == 1
    out = capsys.readouterr()
    doc = json.loads(out.out)  # stdout is pure JSON
    assert "bbtpu-lint" in out.err
    (f,) = doc["findings"]
    assert f["rule"] == "BB002"
    assert f["location"] == f"{f['path']}:{f['line']}"
    assert len(f["fingerprint"]) == 12
    assert any("helper" in hop for hop in f["chain"])

    # clean tree: stdout still pure JSON, empty findings, exit 0
    mod.write_text("x = 1\n")
    assert cli_main(argv + ["--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["findings"] == []


def test_cli_fingerprints_are_cwd_independent(tmp_path, monkeypatch,
                                              capsys):
    """A baseline written from the checkout root must still match when
    the CLI runs from an unrelated cwd with absolute path arguments
    (findings relativize against the detected checkout, not cwd)."""
    proj = tmp_path / "proj"
    (proj / "bloombee_tpu").mkdir(parents=True)
    (proj / "bloombee_tpu" / "__init__.py").write_text("")
    mod = proj / "bloombee_tpu" / "mod.py"
    mod.write_text('import os\nX = os.getenv("BBTPU_X")\n')
    bl = proj / "bl.txt"

    monkeypatch.chdir(proj)
    argv = ["bloombee_tpu", "--baseline", str(bl)]
    assert cli_main(argv + ["--update-baseline"]) == 0
    assert cli_main(argv) == 0

    elsewhere = tmp_path / "elsewhere"
    elsewhere.mkdir()
    monkeypatch.chdir(elsewhere)
    assert cli_main(
        [str(proj / "bloombee_tpu"), "--baseline", str(bl)]
    ) == 0
    capsys.readouterr()


def test_cli_select(tmp_path, monkeypatch, capsys):
    monkeypatch.chdir(tmp_path)
    (tmp_path / "mod.py").write_text(
        'import os\nX = os.getenv("BBTPU_X")\n'
    )
    base = ["mod.py", "--baseline", "bl.txt", "--no-baseline"]
    assert cli_main(base + ["--select", "BB001"]) == 0
    assert cli_main(base + ["--select", "BB005"]) == 1
    capsys.readouterr()
