"""Swarm-shared compile-artifact cache: zero-cold-start recovery.

Elastic self-healing promotes a standby in milliseconds, but the standby
then pays the full warmup-compile bill before it serves at speed — on
real models that bill is minutes, and recovery speed IS availability in
a churning swarm. This module makes compiled executables travel the
swarm the same way KV pages already do: a server's warmed bucket set is
serialized through JAX's persistent compilation cache into a bounded
on-disk **artifact store**, every blob is content-addressed with a
blake2b digest, and a compatibility **fingerprint** (jax/jaxlib version,
backend, device topology, model spec hash, span, dtype, KV page
geometry) guards against installing executables compiled for a different
world. BlockServer exposes the store over ``artifact_get`` (manifest +
named-blob fetch) and pushes it to standbys alongside KV replication via
``artifact_put``; a standby or JOINing server pre-installs the blobs
before warmup, so warmup LOADS executables instead of compiling them
(jitwatch discriminates the two via the cache-retrieval monitoring
event and ``--require --preinstalled`` proves zero true warmup
compiles).

Robustness is the point, not a bolt-on: digest mismatches, fingerprint
mismatches, truncated blobs, and path-escaping names all DECLINE the
install and fall back to local compile (JAX itself treats a corrupt
cache entry as a miss — ``raise_persistent_cache_errors`` stays False —
so a bad blob can never crash the server or serve a wrong executable;
the cache key covers the HLO and compile options). Every fallback is
ledgered as ``server.artifact_fallback_compile`` so the chaos gate can
require the degraded path actually ran. The store is LRU-bounded by
``BBTPU_ARTIFACT_MAX_MB`` so standbys never fill the disk.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
import pathlib

from bloombee_tpu.utils import env

logger = logging.getLogger(__name__)

env.declare(
    "BBTPU_ARTIFACT_DIR", str, "",
    "directory for the swarm-shared compile-artifact store (doubles as "
    "this process's JAX persistent compilation cache dir; where "
    "JAX_COMPILATION_CACHE_DIR is set, that directory is used instead and "
    "this switch only turns the store on). Servers with a store serve "
    "artifact_get, push artifacts to standbys alongside KV replication, "
    "and pre-install fetched artifacts before warmup. Empty = artifact "
    "path off (compile locally, serve/fetch nothing)",
)
env.declare(
    "BBTPU_ARTIFACT_MAX_MB", int, 256,
    "on-disk cap for the artifact store in MiB; least-recently-used "
    "entries are evicted past it so standbys never fill the disk",
)
env.declare(
    "BBTPU_ARTIFACT_FETCH_TIMEOUT_S", float, 10.0,
    "per-peer timeout for one artifact_get call during pre-install; on "
    "timeout/death the fetch retries on the next covering peer, then "
    "falls back to local compile (ledgered)",
)

# only jax persistent-cache files are servable artifacts; anything else
# in the directory (tmp files, stray droppings) is invisible to the store
_SUFFIXES = ("-cache", "-atime")


def blob_digest(blob: bytes) -> str:
    """Content address for one artifact blob (also the wire integrity
    check: recomputed on every install)."""
    return hashlib.blake2b(bytes(blob), digest_size=16).hexdigest()


def fingerprint(spec, start: int, end: int, dtype: str,
                page_size: int) -> dict:
    """Compatibility fingerprint for a span's artifact set.

    Executables are only portable between processes that agree on all of
    this; anything less and a pre-installed blob could silently be a
    miss (harmless but pointless) or — across jaxlib versions — refuse
    to deserialize. The model spec rides as a blake2b hash of its full
    primitive field set, so two servers of different models never trade
    artifacts even over the same span indices.
    """
    import jax
    import jaxlib

    spec_src = json.dumps(
        dataclasses.asdict(spec), sort_keys=True, default=str
    )
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "spec_hash": hashlib.blake2b(
            spec_src.encode(), digest_size=16
        ).hexdigest(),
        "span": [int(start), int(end)],
        "dtype": str(dtype),
        "page_size": int(page_size),
    }


def fingerprint_compatible(mine: dict, theirs: dict) -> str | None:
    """None when compatible, else the first mismatching key (the decline
    reason surfaced in counters/logs)."""
    for key in ("jax", "jaxlib", "backend", "device_count", "spec_hash",
                "dtype", "page_size"):
        if mine.get(key) != theirs.get(key):
            return key
    # spans need not be identical — a covering peer's span is a superset
    # of the fetcher's — but they must overlap the fetcher's span, else
    # the artifacts are for someone else's layers entirely
    ms, me = (mine.get("span") or [0, 0])[:2]
    ts, te = (theirs.get("span") or [0, 0])[:2]
    if not (int(ts) <= int(ms) and int(me) <= int(te)):
        return "span"
    return None


# where the compile cache lives when nothing outside places it: one fixed,
# git-ignored directory in the checkout, so every run of run_server /
# chip_smoke.py from that checkout finds the last run's entries
DEFAULT_COMPILE_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[2] / ".cache" / "xla"
)


def enable_persistent_cache(path: str | None = None) -> str | None:
    """Turn on JAX's persistent compilation cache and return the directory
    it uses (None when that directory cannot be created: the cache is an
    optimization, never a crash).

    JAX_COMPILATION_CACHE_DIR, when set, places the cache from outside:
    JAX reads it itself and nothing here sets another directory, so the
    artifact store serves from that same place. Otherwise `path` (an
    artifact store's directory) or DEFAULT_COMPILE_CACHE_DIR is
    configured. Thresholds drop to zero so every executable lands in the
    cache, not just the slow ones. Safe to call again with a new dir
    mid-process."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as _cc

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = placed or path or DEFAULT_COMPILE_CACHE_DIR
    try:
        os.makedirs(path, exist_ok=True)
    except OSError as e:
        logger.warning("persistent compile cache unavailable: %s", e)
        return None
    if not placed:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # the default XLA-caches integration bakes an autotune-cache PATH
    # (derived from the cache dir) into every compile's options, and the
    # options are hashed into the cache key, so an entry written under one
    # directory could never hit from another (a peer's store, a moved
    # checkout). Re-checked under jax 0.9.0: still so; "none" makes the
    # keys directory-independent.
    jax.config.update("jax_persistent_cache_enable_xla_caches", "none")
    # jax 0.9.0 honours a directory configured after earlier compiles, but
    # still ignores a CHANGE of directory once the cache object exists
    # (several stores in one process): drop it so the next compile opens
    # the directory just configured
    _cc.reset_cache()
    return path


def _safe_name(name: str) -> bool:
    """Artifact names are flat jax cache-file names; anything that could
    escape the store directory (separators, drive letters, dot-dirs) is
    rejected before it reaches the filesystem."""
    if not name or len(name) > 512:
        return False
    if name.startswith("."):
        return False
    if "/" in name or "\\" in name or ".." in name or ":" in name:
        return False
    return True


class ArtifactStore:
    """Bounded on-disk artifact store over one directory (the same dir
    the process's JAX persistent cache writes to, so locally-compiled
    executables become servable artifacts with no extra step).

    Not thread-safe by design: all callers run on the server's asyncio
    loop. Crash-safe installs (tmp + rename) mean a concurrent reader
    in another process never sees a torn blob.
    """

    def __init__(self, root: str, max_mb: int | None = None):
        self.root = root
        if max_mb is None:
            max_mb = env.get("BBTPU_ARTIFACT_MAX_MB")
        self.max_bytes = max(1, int(max_mb)) * 2**20
        self.evictions = 0
        self.declined = 0
        os.makedirs(root, exist_ok=True)

    # ------------------------------------------------------------- reads
    def _entries(self) -> list[tuple[str, int, float]]:
        out = []
        try:
            names = os.listdir(self.root)
        except OSError:
            return out
        for name in names:
            if not _safe_name(name) or not name.endswith(_SUFFIXES):
                continue
            path = os.path.join(self.root, name)
            try:
                st = os.stat(path)
            except OSError:
                continue
            out.append((name, st.st_size, st.st_mtime))
        return out

    def total_bytes(self) -> int:
        return sum(size for _, size, _ in self._entries())

    def stats(self) -> dict:
        """Operator-visible store gauges (surfaced through rpc_info as
        artifact_store_bytes / artifact_evictions /
        artifact_store_declined)."""
        return {
            "bytes": self.total_bytes(),
            "max_bytes": self.max_bytes,
            "entries": len(self._entries()),
            "evictions": self.evictions,
            "declined": self.declined,
        }

    def manifest(self) -> list[dict]:
        """Digest-stamped listing of every servable blob. Unreadable
        entries are skipped (a concurrent eviction is not an error)."""
        out = []
        for name, size, _ in sorted(self._entries()):
            blob = self.read_blob(name)
            if blob is None:
                continue
            out.append({
                "name": name,
                "size": len(blob),
                "digest": blob_digest(blob),
            })
        return out

    def read_blob(self, name: str) -> bytes | None:
        if not _safe_name(name):
            return None
        try:
            with open(os.path.join(self.root, name), "rb") as f:
                return f.read()
        except OSError:
            return None

    # ------------------------------------------------------------ writes
    def install(self, name: str, blob: bytes, digest: str) -> str | None:
        """Install one fetched blob. Returns None on success or a decline
        reason; declines never raise — the caller's fallback is local
        compile, which is always safe."""
        if not _safe_name(name):
            self.declined += 1
            return "bad_name"
        if blob_digest(blob) != digest:
            # truncated or corrupted in flight; installing it would at
            # best be a cache miss and at worst poison the store
            self.declined += 1
            return "digest_mismatch"
        path = os.path.join(self.root, name)
        tmp = path + ".tmp"
        try:
            with open(tmp, "wb") as f:
                f.write(bytes(blob))
            os.replace(tmp, path)
        except OSError as e:
            self.declined += 1
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return f"io_error:{e.__class__.__name__}"
        self.evict()
        return None

    def evict(self) -> int:
        """LRU-evict (by mtime — jax touches -atime files on hits) until
        the store fits the cap. Returns entries removed."""
        entries = self._entries()
        total = sum(size for _, size, _ in entries)
        removed = 0
        for name, size, _ in sorted(entries, key=lambda e: e[2]):
            if total <= self.max_bytes:
                break
            try:
                os.unlink(os.path.join(self.root, name))
            except OSError:
                continue
            total -= size
            removed += 1
            self.evictions += 1
        return removed
