"""Swarm-shared compile-artifact cache (server/artifacts.py + the
BlockServer artifact_get/artifact_put surface): zero-cold-start recovery.

Unit half: the bounded on-disk store (digest declines, path-escape
declines, LRU eviction under the cap), the compatibility fingerprint
(covering spans pass, anything else names the mismatching key), and the
strengthened CLI gates (ledger --require-recovery, jitwatch --require
--preinstalled).

Live half (chaos-marked, replayed by the scripts/chaos.sh ARTIFACT
entry): a standby that pre-installs the primary's artifacts over the
wire must warm up from persistent-cache LOADS alone — zero true warmup
compiles — then promote on primary death and serve tokens identical to
HF greedy; a corrupted artifact stream must decline every blob, fall
back to local compile (ledgered as server.artifact_fallback_compile),
and STILL serve token-identically; a dead covering peer must be retried
on the next peer, and exhausting every peer must degrade to local
compile — never a crash.
"""

import asyncio
import json
import os

import numpy as np
import pytest
import torch

import jax
import jax.numpy as jnp

from bloombee_tpu.server import artifacts
from bloombee_tpu.utils import clock, jitwatch, ledger
from bloombee_tpu.utils.clock import ScaledClock
from bloombee_tpu.wire import faults
from bloombee_tpu.wire.faults import FaultPlan, FaultRule

# jax's persistent-cache config is process-global; every test restores it
# so later suites (test_jitwatch.py's e2e in particular) never find the
# cache dir still pointing at this module's artifact stores
_CFG_KEYS = (
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
    "jax_persistent_cache_enable_xla_caches",
)


@pytest.fixture(autouse=True)
def clean_slate(monkeypatch):
    # one process stands for several machines here, each with its own
    # store; a cache placed from outside (scripts/chaos.sh shares one
    # across its matrix) would merge them into one directory
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    saved = {k: getattr(jax.config, k) for k in _CFG_KEYS}
    faults.set_plan(None)
    jitwatch.reset()
    yield
    faults.set_plan(None)
    jitwatch.reset()
    for k, v in saved.items():
        jax.config.update(k, v)
    # the persistent-cache OBJECT latches the dir it initialized with;
    # re-latch against the restored config so later suites don't keep
    # writing into this module's (temporary) artifact stores
    from jax.experimental.compilation_cache import compilation_cache as cc

    cc.reset_cache()


# ------------------------------------------------------------- store unit
def test_compile_cache_is_placed_from_outside_or_fixed(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR places the cache and nothing in the code
    sets another directory (a store then serves that one); unset, the
    caller's directory or the checkout's fixed git-ignored one is used."""
    before = jax.config.jax_compilation_cache_dir
    placed = tmp_path / "placed"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
    assert artifacts.enable_persistent_cache(str(tmp_path / "store")) == str(
        placed
    )
    assert placed.is_dir() and not (tmp_path / "store").exists()
    assert jax.config.jax_compilation_cache_dir == before  # untouched
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert artifacts.enable_persistent_cache(str(tmp_path / "store")) == str(
        tmp_path / "store"
    )
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "store")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert artifacts.DEFAULT_COMPILE_CACHE_DIR == os.path.join(
        repo, ".cache", "xla"
    )
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".cache/" in f.read().split()


def test_install_and_manifest_roundtrip(tmp_path):
    store = artifacts.ArtifactStore(str(tmp_path))
    blob = b"executable bytes" * 8
    assert store.install(
        "jit_f-0a-cache", blob, artifacts.blob_digest(blob)
    ) is None
    man = store.manifest()
    assert [e["name"] for e in man] == ["jit_f-0a-cache"]
    assert man[0]["digest"] == artifacts.blob_digest(blob)
    assert man[0]["size"] == len(blob)
    assert store.read_blob("jit_f-0a-cache") == blob


def test_corrupt_or_truncated_blob_declines(tmp_path):
    """A blob whose content does not match its manifest digest —
    truncated OR bit-flipped in flight — must never reach the store."""
    store = artifacts.ArtifactStore(str(tmp_path))
    blob = b"y" * 100
    digest = artifacts.blob_digest(blob)
    assert store.install("a-cache", blob[:-1], digest) == "digest_mismatch"
    flipped = bytes([blob[0] ^ 0x40]) + blob[1:]
    assert store.install("a-cache", flipped, digest) == "digest_mismatch"
    assert store.read_blob("a-cache") is None
    assert store.declined == 2
    assert store.manifest() == []


def test_path_escaping_names_decline(tmp_path):
    store = artifacts.ArtifactStore(str(tmp_path))
    blob = b"z"
    digest = artifacts.blob_digest(blob)
    for name in (
        "../evape-cache", "a/b-cache", "c\\d-cache", "e:f-cache",
        ".hidden-cache", "", "x" * 600 + "-cache",
    ):
        assert store.install(name, blob, digest) == "bad_name", name
        assert store.read_blob(name) is None
    # non-suffixed droppings in the directory are invisible, not errors
    (tmp_path / "notes.txt").write_bytes(b"hi")
    assert store.manifest() == []


def test_lru_eviction_under_cap(tmp_path):
    store = artifacts.ArtifactStore(str(tmp_path), max_mb=1)
    blob = bytes(300 * 1024)
    digest = artifacts.blob_digest(blob)
    for i, name in enumerate(("a-cache", "b-cache", "c-cache")):
        assert store.install(name, blob, digest) is None
        # pin strictly increasing mtimes so LRU order is deterministic
        os.utime(tmp_path / name, (i + 1.0, i + 1.0))
    assert store.evictions == 0  # 3 x 300KiB fits the 1MiB cap
    assert store.install("d-cache", blob, digest) is None  # 4th overflows
    assert store.total_bytes() <= store.max_bytes
    names = {e["name"] for e in store.manifest()}
    assert "a-cache" not in names, "oldest entry must be the one evicted"
    assert {"c-cache", "d-cache"} <= names
    assert store.evictions >= 1


# ------------------------------------------------------- fingerprint unit
def _spec():
    from bloombee_tpu.models.spec import ModelSpec

    return ModelSpec(
        family="llama", hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_hidden_layers=3, vocab_size=128,
    )


def test_fingerprint_compatibility():
    fp = artifacts.fingerprint(_spec(), 0, 3, "f32", 4)
    assert artifacts.fingerprint_compatible(fp, dict(fp)) is None
    other = dict(fp, spec_hash="0" * 32)
    assert artifacts.fingerprint_compatible(fp, other) == "spec_hash"
    assert artifacts.fingerprint_compatible(
        fp, dict(fp, dtype="bf16")
    ) == "dtype"
    assert artifacts.fingerprint_compatible(
        fp, dict(fp, jaxlib="0.0.0")
    ) == "jaxlib"
    # a covering peer's wider span is compatible; a narrower one is not
    mine = dict(fp, span=[1, 2])
    assert artifacts.fingerprint_compatible(mine, dict(fp, span=[0, 3])) \
        is None
    assert artifacts.fingerprint_compatible(fp, dict(fp, span=[1, 2])) \
        == "span"


def test_server_info_artifact_advert_wire_compat():
    from bloombee_tpu.swarm.data import ServerInfo

    si = ServerInfo(artifacts=True)
    assert ServerInfo.from_wire(si.to_wire()).artifacts is True
    # old peers omit the field entirely -> defaults False (the BB004
    # from_wire splat-filter contract for mixed swarms)
    d = si.to_wire()
    d.pop("artifacts")
    assert ServerInfo.from_wire(d).artifacts is False
    d["artifact_v2"] = {"future": 1}  # unknown fields drop, never raise
    assert ServerInfo.from_wire(d).artifacts is False


# ----------------------------------------------------------- gate CLI unit
def test_ledger_require_recovery_cli(tmp_path, capsys):
    path = tmp_path / "ledger.jsonl"
    line = {
        "faults": {"wire.corrupt": 2},
        "recoveries": {"server.promotion": 1},
    }
    req = ["--require", "--require-recovery",
           "server.artifact_fallback_compile"]
    path.write_text(json.dumps(line) + "\n")
    assert ledger._main([str(path)] + req) == 1
    assert "server.artifact_fallback_compile" in capsys.readouterr().err
    line["recoveries"]["server.artifact_fallback_compile"] = 3
    path.write_text(json.dumps(line) + "\n")
    assert ledger._main([str(path)] + req) == 0


def test_jitwatch_preinstalled_gate_cli(tmp_path, capsys):
    path = tmp_path / "w.jsonl"
    good = {"xla_compiles": 5, "compile_cache_hits": 5,
            "preinstalled": True, "fenced": True}
    path.write_text(json.dumps(good) + "\n")
    assert jitwatch._main([str(path), "--require", "--preinstalled"]) == 0
    # no process ever marked itself pre-installed: vacuous claim
    path.write_text(json.dumps(dict(good, preinstalled=False)) + "\n")
    assert jitwatch._main([str(path), "--require", "--preinstalled"]) == 1
    assert "NOT PREINSTALLED" in capsys.readouterr().err
    # zero cache hits: the installed artifacts were never exercised
    path.write_text(json.dumps(dict(good, compile_cache_hits=0)) + "\n")
    assert jitwatch._main([str(path), "--require", "--preinstalled"]) == 1
    assert "NO CACHE HITS" in capsys.readouterr().err
    # any true warmup compile for a pre-installed bucket is exactly the
    # cold start the artifact path exists to eliminate
    path.write_text(
        json.dumps(dict(good, preinstalled_warmup_misses=1)) + "\n"
    )
    assert jitwatch._main([str(path), "--require", "--preinstalled"]) == 1
    assert "miss" in capsys.readouterr().err
    # swallowed per-bucket warmup failures fail plain --require too
    path.write_text(json.dumps({
        "xla_compiles": 2, "warmup_compiles": 2, "fenced": True,
        "warmup_failures": 1,
    }) + "\n")
    assert jitwatch._main([str(path), "--require"]) == 1
    assert "DEGRADED WARMUP" in capsys.readouterr().err


# ----------------------------------------------------------------- live e2e
@pytest.fixture(scope="module")
def tiny_model_dir(tmp_path_factory):
    from transformers import LlamaConfig, LlamaForCausalLM

    config = LlamaConfig(
        hidden_size=64,
        intermediate_size=128,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_hidden_layers=3,
        vocab_size=128,
        max_position_embeddings=256,
        tie_word_embeddings=False,
    )
    torch.manual_seed(5)
    model = LlamaForCausalLM(config).eval().to(torch.float32)
    d = tmp_path_factory.mktemp("tiny_llama_artifacts")
    model.save_pretrained(d, safe_serialization=True)
    return str(d), model, config


def _primary(model_dir, rc, art_dir, **kw):
    from bloombee_tpu.server.block_server import BlockServer

    return BlockServer(
        model_uid="tinyart", start=0, end=3, model_dir=model_dir,
        registry=rc, compute_dtype=jnp.float32, num_pages=64,
        page_size=4, announce_period=0.3, artifact_dir=art_dir, **kw,
    )


def _standby(model_dir, rc, art_dir, **kw):
    kw.setdefault("promote_high_ms", 500.0)
    kw.setdefault("promote_low_ms", 100.0)
    kw.setdefault("promote_sustain_s", 0.3)
    kw.setdefault("promote_jitter_s", 0.4)
    return _primary(
        model_dir, rc, art_dir, standby=True, drain_timeout=2.0, **kw
    )


async def _wait_for(cond, timeout, what):
    deadline = asyncio.get_event_loop().time() + timeout
    while not cond():
        if asyncio.get_event_loop().time() > deadline:
            raise AssertionError(f"timed out waiting for {what}")
        await asyncio.sleep(0.1)


async def _hf_identical(model_dir, rc, hf_model, config, seed):
    """Greedy-generate through the swarm and require exact HF parity."""
    from bloombee_tpu.client.model import DistributedModelForCausalLM

    model = DistributedModelForCausalLM.from_pretrained(
        model_dir, rc, model_uid="tinyart"
    )
    rng = np.random.default_rng(seed)
    input_ids = rng.integers(0, config.vocab_size, size=(1, 8))
    ids = await model.generate(
        input_ids, max_new_tokens=4, server_decode=False
    )
    with torch.no_grad():
        ref = hf_model.generate(
            torch.tensor(input_ids), max_new_tokens=4, do_sample=False,
            use_cache=True,
        ).numpy()
    np.testing.assert_array_equal(ids, ref)


@pytest.mark.chaos
def test_preinstalled_standby_zero_warmup_compiles(
    tiny_model_dir, monkeypatch, tmp_path
):
    """The acceptance run: the primary's warmup populates its artifact
    store; a standby pre-installs those artifacts over artifact_get, and
    — with the in-memory jit cache cleared to simulate a fresh process —
    warms up entirely from persistent-cache LOADS (>=1 cache hit, zero
    preinstalled warmup misses). The primary then dies, the standby
    promotes, and its tokens match HF greedy exactly. The flushed witness
    line must pass ``--require --preinstalled``."""
    monkeypatch.setenv("BBTPU_JITWATCH", "1")
    model_dir, hf_model, config = tiny_model_dir
    report = tmp_path / "jitwatch.jsonl"
    dir_a, dir_b = str(tmp_path / "store_a"), str(tmp_path / "store_b")

    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()

        def rc():
            return RegistryClient("127.0.0.1", reg.port)

        # control-plane deadlines (announce lease, watcher, sustain,
        # jitter) run 4x compressed; restored to real before the compute-
        # heavy generate (test_promotion.py's clock discipline)
        prev = clock.install(ScaledClock(scale=4.0))
        try:
            primary = _primary(model_dir, rc(), dir_a)
            # the ctor just pointed jax's persistent cache at store A;
            # drop the in-memory executable cache so warmup actually
            # compiles — and therefore actually WRITES artifacts — even
            # when earlier tests already compiled these shapes
            jax.clear_caches()
            await primary.start()
            await primary.warmup(batch_sizes=(1,), prefill_tokens=8)
            assert primary.artifact_store is not None
            assert primary.artifact_store.manifest(), \
                "warmup persisted no artifacts"
            assert primary.server_info().artifacts is True

            standby = _standby(model_dir, rc(), dir_b)
            await standby.start()
            # a fresh process's worth of amnesia at the JOIN boundary:
            # nothing in memory, everything must ride fetched artifacts
            jax.clear_caches()
            jitwatch.reset()
            await standby.warmup(batch_sizes=(1,), prefill_tokens=8)
            assert standby._artifacts_preinstalled is True
            assert standby.artifact_blobs_fetched >= 1
            assert primary.artifact_gets_served >= 1
            snap = jitwatch.snapshot()
            assert snap["preinstalled"] is True
            assert snap["compile_cache_hits"] >= 1, snap
            assert snap["preinstalled_warmup_misses"] == 0, snap["compiles"]
            assert snap["fenced"] is True

            await primary.stop()  # tombstones the span: advert silence
            await _wait_for(
                lambda: standby._promoted, 20.0, "promotion after span loss"
            )
        finally:
            clock.install(prev)

        await _hf_identical(model_dir, rc(), hf_model, config, seed=3)

        # the artifact counters ride rpc_info (BB006 surfacing)
        from bloombee_tpu.wire.rpc import connect

        conn = await connect("127.0.0.1", standby.port)
        info, _ = await conn.call("rpc_info", {})
        assert info["artifact_preinstalled"] is True
        assert info["artifact_blobs_fetched"] >= 1
        assert info["artifact_store_bytes"] > 0
        await conn.close()

        await standby.stop()
        await reg.stop()

    asyncio.run(run())

    snap = jitwatch.snapshot()
    assert snap["steady_state_recompiles"] == 0, [
        c for c in snap["compiles"] if c["phase"] == "steady"
    ]
    jitwatch.flush(str(report))
    assert jitwatch._main(
        [str(report), "--require", "--preinstalled"]
    ) == 0
    # under scripts/chaos.sh the same line feeds the ARTIFACT entry's
    # strengthened gate (the autouse reset leaves nothing for the atexit
    # flush to double-write)
    jitwatch.flush()


@pytest.mark.chaos
def test_corrupt_artifact_stream_falls_back_token_identical(
    tiny_model_dir, tmp_path
):
    """Byzantine artifact transfer: every blob reply is bit-flipped in
    flight (well-formed frame, lying payload). The standby must decline
    every blob on the manifest-digest check, install NOTHING, fall back
    to local compile (ledgered as server.artifact_fallback_compile), and
    still promote + serve token-identically when the primary dies. Zero
    hard failures, zero crashes."""
    model_dir, hf_model, config = tiny_model_dir
    dir_a, dir_b = str(tmp_path / "store_a"), str(tmp_path / "store_b")
    base = ledger.snapshot()["recoveries"].get(
        "server.artifact_fallback_compile", 0
    )

    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()

        def rc():
            return RegistryClient("127.0.0.1", reg.port)

        prev = clock.install(ScaledClock(scale=4.0))
        try:
            primary = _primary(model_dir, rc(), dir_a)
            jax.clear_caches()
            await primary.start()
            await primary.warmup(batch_sizes=(1,), prefill_tokens=8)
            assert primary.artifact_store.manifest()

            standby = _standby(model_dir, rc(), dir_b)
            await standby.start()
            # corrupt every artifact frame on the wire from here on; the
            # manifest reply carries no tensor (unaffected), each blob
            # reply gets one byte flipped
            plan = FaultPlan(seed=11)
            plan.add(FaultRule(
                site="send", action="corrupt", method="res",
                predicate=faults._is_artifact_transfer, nth=1, count=0,
            ))
            faults.set_plan(plan)
            await standby.warmup(batch_sizes=(1,), prefill_tokens=8)
            faults.set_plan(None)
            assert standby._artifacts_preinstalled is False
            assert standby.artifact_fallback_compiles >= 1
            assert standby.artifact_store.declined >= 1
            assert standby.artifact_blobs_fetched == 0, \
                "a corrupt blob survived the digest check"

            await primary.stop()
            await _wait_for(
                lambda: standby._promoted, 20.0, "promotion after span loss"
            )
        finally:
            clock.install(prev)
            faults.set_plan(None)

        await _hf_identical(model_dir, rc(), hf_model, config, seed=7)

        from bloombee_tpu.wire.rpc import connect

        conn = await connect("127.0.0.1", standby.port)
        info, _ = await conn.call("rpc_info", {})
        assert info["artifact_fallback_compiles"] >= 1
        assert info["artifact_store_declined"] >= 1
        await conn.close()

        await standby.stop()
        await reg.stop()

    asyncio.run(run())

    snap = ledger.snapshot()
    assert snap["recoveries"].get(
        "server.artifact_fallback_compile", 0
    ) > base, "the fallback path never ledgered"
    assert snap["faults"].get("wire.corrupt", 0) >= 1


class _DeadPeerFirst:
    """Registry wrapper pinning a known-dead peer to the front of every
    server listing, so the retry-on-next-peer path runs deterministically
    (live-registry dict order depends on declare order)."""

    def __init__(self, inner, dead_port: int):
        self._inner = inner
        self._dead_port = dead_port

    def __getattr__(self, name):
        return getattr(self._inner, name)

    async def get_module_infos(self, uid, blocks):
        infos = await self._inner.get_module_infos(uid, blocks)
        for info in infos or []:
            if info:
                info.servers = dict(sorted(
                    info.servers.items(),
                    key=lambda kv: kv[1].port != self._dead_port,
                ))
        return infos


@pytest.mark.chaos
def test_peer_death_mid_fetch_retries_then_falls_back(
    tiny_model_dir, tmp_path
):
    """Fetch fault tolerance, three acts: (1) the first covering peer is
    dead on the wire — the fetch retries the full blob set on the next
    peer and still pre-installs; (2) a stale fingerprint declines the
    whole peer and falls back; (3) with every peer dead or declined the
    fetch degrades to local compile — it never raises."""
    model_dir, _, _ = tiny_model_dir
    dir_a, dir_b = str(tmp_path / "store_a"), str(tmp_path / "store_b")

    from bloombee_tpu.swarm.data import ServerInfo, ServerState
    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()

        def rc():
            return RegistryClient("127.0.0.1", reg.port)

        primary = _primary(model_dir, rc(), dir_a)
        jax.clear_caches()
        await primary.start()
        await primary.warmup(batch_sizes=(1,), prefill_tokens=8)
        assert primary.artifact_store.manifest()

        # a covering "peer" that is ONLINE in the registry but already
        # dead on the wire (port 1 never listens)
        dead = ServerInfo(
            state=ServerState.ONLINE, host="127.0.0.1", port=1,
            throughput=1.0, start_block=0, end_block=3, artifacts=True,
        )
        await rc().declare_blocks(
            "tinyart", "srv-00dead", range(3), dead, expiration=60.0
        )

        standby = _standby(
            model_dir, _DeadPeerFirst(rc(), dead_port=1), dir_b
        )
        await standby.start()

        # act 1: dead peer first -> retried on the live primary
        assert await standby.prefetch_artifacts() is True
        assert standby._artifacts_preinstalled is True
        assert standby.artifact_fetch_retries >= 1
        assert standby.artifact_blobs_fetched >= 1

        # act 2: stale fingerprint -> the peer's whole artifact set is
        # for a different world; decline it all and fall back
        standby._artifacts_preinstalled = False
        real_fp = standby._artifact_fp
        standby._artifact_fp = lambda: dict(
            real_fp(), spec_hash="0" * 32
        )
        before = standby.artifact_fallback_compiles
        assert await standby.prefetch_artifacts() is False
        assert standby.artifact_fallback_compiles > before
        assert standby._artifacts_preinstalled is False
        standby._artifact_fp = real_fp

        # act 3: every peer dead -> graceful local-compile fallback
        await primary.stop()
        before = standby.artifact_fallback_compiles
        assert await standby.prefetch_artifacts() is False
        assert standby.artifact_fallback_compiles > before

        await standby.stop()
        await reg.stop()

    asyncio.run(run())


def test_warmup_failures_surface_in_rpc_info(
    tiny_model_dir, monkeypatch, tmp_path
):
    """Satellite of the same robustness story: per-bucket warmup failures
    were silently swallowed (logged, nothing else) — now they count into
    warmup_failures (rpc_info / health --probe) and flag the jitwatch
    report as warmup_degraded, so a zero-recompile green can't mask
    buckets that never warmed."""
    monkeypatch.setenv("BBTPU_JITWATCH", "1")
    model_dir, _, _ = tiny_model_dir

    from bloombee_tpu.swarm.registry import RegistryClient, RegistryServer
    from bloombee_tpu.wire.rpc import connect

    async def run():
        reg = RegistryServer(host="127.0.0.1")
        await reg.start()
        server = _primary(
            model_dir, RegistryClient("127.0.0.1", reg.port), None
        )
        await server.start()
        jitwatch.reset()

        def boom(*a, **k):
            raise RuntimeError("no pages for warmup")

        monkeypatch.setattr(server.manager, "allocate", boom)
        await server.warmup(batch_sizes=(1, 2), prefill_tokens=8)
        assert server.warmup_failures >= 2
        snap = jitwatch.snapshot()
        assert snap["warmup_failures"] >= 2
        assert snap["warmup_degraded"] is True
        assert snap["fenced"] is True  # the fence still drops — degraded,
        # not deadlocked

        conn = await connect("127.0.0.1", server.port)
        info, _ = await conn.call("rpc_info", {})
        assert info["warmup_failures"] >= 2
        # no artifact store configured: the counters still surface, zeroed
        assert info["artifact_preinstalled"] is False
        assert info["artifact_store_bytes"] == 0
        await conn.close()

        await server.stop()
        await reg.stop()

    asyncio.run(run())

    # the degraded report fails plain --require (hollow-green protection)
    report = tmp_path / "degraded.jsonl"
    jitwatch.flush(str(report))
    assert jitwatch._main([str(report), "--require"]) == 1
