"""Page-aligned content hash chains for cross-session prefix sharing.

The chain is the identity of a cached KV page: page i's hash covers its own
token ids AND the parent page's hash, so equal hashes imply equal *full
prefixes*, not just equal page contents (SGLang's RadixAttention collapses
the same property into a trie; a chained flat list is equivalent for the
page-granular pool in kv/paged.py and is trivially wire-serializable).

Shared by the client (hash computation over the prompt), the server
(pool lookup + adoption) and the tests — one definition so a
version skew shows up as a clean cache miss, never a wrong hit.
"""

from __future__ import annotations

import hashlib

import numpy as np

# bumped whenever the hash layout changes: a stale client's chains must
# miss, not alias, a newer server's pool
_CHAIN_VERSION = b"bbtpu-prefix-v1"
# hidden-state sessions (no token ids) hash raw activations instead; a
# distinct root guarantees a hidden chain can never alias an id chain
_HIDDEN_VERSION = b"bbtpu-hidden-v1"
# span-output digests (integrity layer): one-shot, not chained — each step's
# output stands alone so a single corrupted reply can't invalidate the rest
_DIGEST_VERSION = b"bbtpu-outdigest-v1"


def out_digest(arr) -> str:
    """blake2b hex digest over a span output's exact bytes.

    Canonicalizes only layout (C-contiguous), never dtype: the digest
    covers the bytes the server actually serialized, so the client can
    recompute it over the received array and detect *in-flight* corruption
    exactly. It is NOT a cross-replica equality check — honest replicas
    differ in ulps (batch-width-dependent float reductions), so two
    replicas' digests matching is a fast-path only; a mismatch must
    escalate to a tolerance compare, never straight to a verdict."""
    a = np.ascontiguousarray(arr)
    h = hashlib.blake2b(_DIGEST_VERSION, digest_size=16)
    h.update(str(a.dtype).encode("ascii"))
    h.update(str(a.shape).encode("ascii"))
    h.update(a.tobytes())
    return h.hexdigest()


def _extend_chain(
    pages_bytes, total_pages: int, chain: list[str] | None, root: bytes
) -> list[str]:
    """Shared chaining core: extend `chain` (treated as already covering
    its own length in pages) out to `total_pages` using `pages_bytes(p)`
    for page p's canonical byte content."""
    out = list(chain or [])
    if len(out) >= total_pages:
        return out[:total_pages]
    parent = out[-1].encode("ascii") if out else root
    for p in range(len(out), total_pages):
        digest = hashlib.blake2b(
            parent + pages_bytes(p), digest_size=16
        ).hexdigest()
        out.append(digest)
        parent = digest.encode("ascii")
    return out


def page_hash_chain(
    ids, page_size: int, chain: list[str] | None = None
) -> list[str]:
    """Chained hashes of the *full* pages of one row of token ids.

    Returns one hex digest per complete page (a trailing partial page gets
    no hash — it cannot be shared, its content is still growing). Token ids
    are canonicalized to int64 so the same prompt hashes identically
    whatever integer dtype the caller tokenized into. `chain` (an earlier
    result over a prefix of the same row) lets long-running sessions extend
    incrementally instead of rehashing from the root.
    """
    if page_size <= 0:
        raise ValueError(f"page_size must be positive, got {page_size}")
    row = np.asarray(ids).reshape(-1).astype(np.int64)
    return _extend_chain(
        lambda p: row[p * page_size : (p + 1) * page_size].tobytes(),
        len(row) // page_size, chain, _CHAIN_VERSION,
    )


def hidden_hash_chain(
    hidden, page_size: int, chain: list[str] | None = None
) -> list[str]:
    """Chained hashes of the full pages of one row of hidden states.

    `hidden` is [T, D] activations; bytes are canonicalized to contiguous
    float32 so the chain is stable across the dtypes a client may hold its
    history in. Used by hidden-state sessions (no token-id history) for
    recovery probes and replication — same pool, different hash root."""
    if page_size <= 0:
        raise ValueError(f"page_size must be positive, got {page_size}")
    row = np.ascontiguousarray(np.asarray(hidden), dtype=np.float32)
    if row.ndim != 2:
        raise ValueError(f"hidden row must be [T, D], got {row.shape}")
    return _extend_chain(
        lambda p: row[p * page_size : (p + 1) * page_size].tobytes(),
        row.shape[0] // page_size, chain, _HIDDEN_VERSION,
    )
