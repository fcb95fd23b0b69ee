"""Tensor (de)serialization with optional lossless compression.

Capability port of the reference's lossless transport wrapper
(/root/reference/src/bloombee/utils/lossless_transport.py): every tensor on
the wire may be wrapped in a losslessly-compressed envelope with
- codec choice (zstd default, zlib fallback),
- a byte-split layout for 2-byte dtypes (bf16/fp16): the two byte planes of
  the little-endian pairs are separated before compression, which compresses
  far better because the exponent-byte plane is highly redundant (reference
  `byte_split` layout),
- min-size and min-gain gates so tiny or incompressible payloads ship raw
  (reference: 48 KiB min size, 2 KiB min gain).

bfloat16 is handled via ml_dtypes so client/server never need torch.
"""

from __future__ import annotations

import dataclasses
import threading
import zlib

import ml_dtypes
import numpy as np

try:
    import zstandard as _zstd
except Exception:  # pragma: no cover - zstandard is in the base image
    _zstd = None

# a zstandard (de)compressor object must not be used by two threads at
# once, and the off-loop codec pool's workers do compress concurrently
# (one shared object segfaulted concurrent sessions): one per thread
_ZSTD_TLS = threading.local()


def _zstd_compress(buf) -> bytes:
    ctx = getattr(_ZSTD_TLS, "compressor", None)
    if ctx is None:
        ctx = _ZSTD_TLS.compressor = _zstd.ZstdCompressor(level=3)
    return ctx.compress(buf)


def _zstd_decompress(buf) -> bytes:
    ctx = getattr(_ZSTD_TLS, "decompressor", None)
    if ctx is None:
        ctx = _ZSTD_TLS.decompressor = _zstd.ZstdDecompressor()
    return ctx.decompress(buf)


from bloombee_tpu.utils import env as _env
from bloombee_tpu.utils import lockwatch as _lockwatch

import time as _time


class _TransportStats:
    """Per-process transport profiling (the role of the reference
    lossless_transport profiling channels): per direction, tensor count,
    raw vs wire bytes, codec time. Snapshot via transport_stats(); the
    `transport` log channel (BBTPU_LOG_CHANNELS=transport) logs one line
    per call site."""

    def __init__(self):
        self._lock = _lockwatch.thread_lock("wire.codec_stats")
        self.reset()

    def reset(self):
        with self._lock:
            self._d = {
                "tx": {"n": 0, "raw_bytes": 0, "wire_bytes": 0, "s": 0.0,
                       "compressed": 0},
                "rx": {"n": 0, "raw_bytes": 0, "wire_bytes": 0, "s": 0.0,
                       "compressed": 0},
            }

    def record(self, direction, raw_len, wire_len, seconds, compressed):
        with self._lock:
            d = self._d[direction]
            d["n"] += 1
            d["raw_bytes"] += raw_len
            d["wire_bytes"] += wire_len
            d["s"] += seconds
            d["compressed"] += bool(compressed)

    def snapshot(self) -> dict:
        with self._lock:
            out = {}
            for k, d in self._d.items():
                out[k] = dict(d)
                out[k]["ratio"] = (
                    d["wire_bytes"] / d["raw_bytes"] if d["raw_bytes"] else 1.0
                )
            return out


_STATS = _TransportStats()


def transport_stats() -> dict:
    """Snapshot of this process's wire-codec counters (tx/rx tensors, raw vs
    wire bytes, compression ratio, codec seconds)."""
    return _STATS.snapshot()


def reset_transport_stats() -> None:
    _STATS.reset()


# defaults; overridable per process via the env switches declared below
MIN_COMPRESS_BYTES = 48 * 1024
MIN_GAIN_BYTES = 2 * 1024

_env.declare(
    "BBTPU_MIN_COMPRESS_BYTES", int, MIN_COMPRESS_BYTES,
    "payloads below this ship raw (reference lossless_transport 48 KiB gate)",
)
_env.declare(
    "BBTPU_MIN_COMPRESS_GAIN", int, MIN_GAIN_BYTES,
    "compression kept only if it saves at least this many bytes",
)
_env.declare(
    "BBTPU_WIRE_COMPRESSION", bool, True,
    "losslessly compress large wire tensors (zstd byte-split)",
)
_env.declare(
    "BBTPU_WIRE_CODECS", str, "",
    "comma-separated allowlist restricting which codecs this process "
    "advertises and uses on the wire (negotiation, wire/rpc.py); empty "
    "means every built-in codec, 'raw' disables compression entirely",
)


# --- codec registry + negotiation support -----------------------------------
# name -> (compress, decompress). "raw" is implicit and always supported.
_CODECS: dict[str, tuple] = {"zlib": (lambda b: zlib.compress(b, 6),
                                      zlib.decompress)}
if _zstd is not None:
    _CODECS["zstd"] = (_zstd_compress, _zstd_decompress)

# preference order when several codecs are permitted for a payload
_PREFERENCE: list[str] = ["zstd", "zlib"]

# The pre-negotiation wire contract: every historical peer decodes exactly
# these. A peer that never advertises (older build) is assumed to speak
# them and nothing more, so mixed swarms degrade byte-for-byte to the
# legacy codec choice instead of flag-daying.
LEGACY_WIRE_CODECS = frozenset({"raw", "zstd", "zlib"})


def register_codec(name: str, compress, decompress, *,
                   prefer: bool = False) -> None:
    """Plug in a codec (e.g. a dict-trained zstd for activation planes).
    Registered codecs are only chosen toward peers that advertise them in
    the connection handshake (wire/rpc.py negotiation) — an un-upgraded
    swarm never sees the new name on the wire."""
    _CODECS[name] = (compress, decompress)
    if name not in _PREFERENCE:
        if prefer:
            _PREFERENCE.insert(0, name)
        else:
            _PREFERENCE.append(name)


def unregister_codec(name: str) -> None:
    """Test hook: remove a codec registered by register_codec."""
    _CODECS.pop(name, None)
    if name in _PREFERENCE:
        _PREFERENCE.remove(name)


def supported_codecs() -> frozenset:
    """Codecs this process can encode/decode right now — what a connection
    advertises to its peer. BBTPU_WIRE_CODECS restricts the set ("raw" is
    always kept: it is the identity codec, not an option)."""
    names = {"raw", *_CODECS}
    allow = str(_env.get("BBTPU_WIRE_CODECS")).strip()
    if allow:
        keep = {c.strip() for c in allow.split(",") if c.strip()}
        names &= keep | {"raw"}
    return frozenset(names)

_DTYPES = {
    "f32": np.float32,
    "f16": np.float16,
    "bf16": ml_dtypes.bfloat16,
    "i32": np.int32,
    "i64": np.int64,
    "u8": np.uint8,
    "bool": np.bool_,
    "f64": np.float64,
}
_DTYPE_NAMES = {np.dtype(v): k for k, v in _DTYPES.items()}


def dtype_for_name(name: str, default=np.float32):
    """Resolve a wire dtype name ("bf16", "f32", ...) to a numpy dtype."""
    dt = _DTYPES.get(name)
    return np.dtype(dt) if dt is not None else np.dtype(default)


def name_for_dtype(dtype) -> str:
    """Wire name of a numpy dtype (the inverse of dtype_for_name)."""
    return _DTYPE_NAMES[np.dtype(dtype)]


@dataclasses.dataclass
class TensorMeta:
    dtype: str
    shape: tuple[int, ...]
    codec: str  # "raw" | "zstd" | "zlib"
    byte_split: bool

    def to_wire(self) -> dict:
        return {
            "d": self.dtype,
            "s": list(self.shape),
            "c": self.codec,
            "b": self.byte_split,
        }

    @classmethod
    def from_wire(cls, d: dict) -> "TensorMeta":
        # .get defaults so an older peer's lean meta (dtype+shape only)
        # never KeyErrors a newer server: absent codec means raw bytes
        return cls(d["d"], tuple(d["s"]), d.get("c", "raw"),
                   d.get("b", False))


def _compress(buf, codec: str) -> bytes:
    try:
        return _CODECS[codec][0](buf)
    except KeyError:
        raise ValueError(f"unknown codec {codec}") from None


def _decompress(buf, codec: str) -> bytes:
    try:
        return _CODECS[codec][1](buf)
    except KeyError:
        raise ValueError(f"unknown codec {codec}") from None


def serialize_tensor(
    arr: np.ndarray, compression: bool = True,
    allowed: frozenset | None = None,
) -> tuple[TensorMeta, bytes]:
    """Serialize one array; returns (meta, payload bytes).

    `allowed` is the negotiated codec set for the destination peer (see
    wire/rpc.py). None means the pre-negotiation contract
    (LEGACY_WIRE_CODECS), so un-negotiated callers keep the seed's exact
    codec choice byte-for-byte."""
    t0 = _time.perf_counter()
    arr = np.ascontiguousarray(arr)
    dtype = np.dtype(arr.dtype)
    if dtype not in _DTYPE_NAMES:
        raise TypeError(f"unsupported wire dtype {dtype}")
    raw = arr.tobytes()
    codec = "raw"
    byte_split = False
    payload = raw
    min_bytes = _env.get("BBTPU_MIN_COMPRESS_BYTES")
    min_gain = _env.get("BBTPU_MIN_COMPRESS_GAIN")
    if not _env.get("BBTPU_WIRE_COMPRESSION"):
        compression = False
    if allowed is None:
        allowed = LEGACY_WIRE_CODECS
    usable = [c for c in _PREFERENCE if c in _CODECS and c in allowed]
    if compression and usable and len(raw) >= min_bytes:
        candidate = raw
        if dtype.itemsize == 2:
            # byte-plane split: [b0 b1 b0 b1 ...] -> [b0 b0 ...][b1 b1 ...]
            candidate = _split_planes(raw)
            byte_split = True
        chosen = usable[0]
        compressed = _compress(candidate, chosen)
        if len(compressed) + min_gain <= len(raw):
            payload = compressed
            codec = chosen
        else:
            byte_split = False
    _STATS.record(
        "tx", len(raw), len(payload), _time.perf_counter() - t0,
        codec != "raw",
    )
    return TensorMeta(_DTYPE_NAMES[dtype], arr.shape, codec, byte_split), payload


def deserialize_tensor(meta: TensorMeta, payload, *,
                       writable: bool = False) -> np.ndarray:
    """Decode one payload (bytes or memoryview) into an ndarray.

    Raw-codec payloads come back as a READ-ONLY view over the receive
    buffer — no copy on the wire hot path. Pass writable=True only when
    the caller mutates the array in place; that is the one path that
    still pays the copy."""
    t0 = _time.perf_counter()
    dtype = np.dtype(_DTYPES[meta.dtype])
    if meta.codec == "raw":
        raw = payload
    else:
        raw = _decompress(payload, meta.codec)
        if meta.byte_split:
            raw = _merge_planes(raw)
    out = np.frombuffer(raw, dtype=dtype).reshape(meta.shape)
    if writable and not out.flags.writeable:
        out = out.copy()
    _STATS.record(
        "rx", len(raw), len(payload), _time.perf_counter() - t0,
        meta.codec != "raw",
    )
    return out


def _split_planes(raw: bytes) -> bytes:
    lib = _native_lib()
    n = len(raw) // 2
    if lib is not None:
        src = np.frombuffer(raw, dtype=np.uint8)
        dst = np.empty(2 * n, dtype=np.uint8)
        lib.byte_split_2(
            src.ctypes.data, dst.ctypes.data, n
        )
        return dst.tobytes()
    return np.frombuffer(raw, dtype=np.uint8).reshape(-1, 2).T.tobytes()


def _merge_planes(raw: bytes) -> bytes:
    lib = _native_lib()
    n = len(raw) // 2
    if lib is not None:
        src = np.frombuffer(raw, dtype=np.uint8)
        dst = np.empty(2 * n, dtype=np.uint8)
        lib.byte_merge_2(src.ctypes.data, dst.ctypes.data, n)
        return dst.tobytes()
    return np.frombuffer(raw, dtype=np.uint8).reshape(2, -1).T.tobytes()


def _native_lib():
    from bloombee_tpu.native import byte_split_lib

    return byte_split_lib()


def serialize_tensors(
    arrays: list[np.ndarray], compression: bool = True,
    allowed: frozenset | None = None,
) -> tuple[list[dict], list[bytes]]:
    metas, blobs = [], []
    for a in arrays:
        m, b = serialize_tensor(a, compression, allowed=allowed)
        metas.append(m.to_wire())
        blobs.append(b)
    return metas, blobs


def deserialize_tensors(metas: list[dict], blobs: list,
                        writable: bool = False) -> list[np.ndarray]:
    return [
        deserialize_tensor(TensorMeta.from_wire(m), b, writable=writable)
        for m, b in zip(metas, blobs)
    ]
