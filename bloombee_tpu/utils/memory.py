"""Memory observability: device HBM stats + server-side accounting.

TPU-native role of the reference's utils/memory_usage.py (nvidia-smi /
torch.cuda.memory_allocated probes + the [MBPIPE_MEM] logging surface):
here the device side comes from PJRT's `memory_stats()` and the
framework-side accounting is exact — the server knows precisely which
arrays it holds (span params, KV arena, host-offloaded layers, parked KV).

Surfaces:
- `[memory]` log channel (BBTPU_LOG_CHANNELS=memory): one line per
  announce period from each server
- `rpc_info`/health: a `memory` dict the operator can poll remotely
"""

from __future__ import annotations

from typing import Any


def device_report() -> dict:
    """The accelerator as the process that holds it sees it (JAX's own
    words): platform, device kind and how many devices."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def device_memory_stats() -> dict:
    """PJRT memory counters of the first device (bytes_in_use / peak /
    limit) plus bytes_in_use of every device in jax.devices() order;
    empty on backends that expose none (CPU)."""
    import jax

    try:
        per_device = [d.memory_stats() or {} for d in jax.devices()]
    except Exception:
        return {}
    stats = per_device[0]
    out = {
        k: int(stats[k])
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
        if k in stats
    }
    if out:
        out["bytes_in_use_by_device"] = [
            int(s.get("bytes_in_use", 0)) for s in per_device
        ]
    return out


def tree_nbytes(tree: Any) -> int:
    """Total bytes of every array leaf in a pytree (QuantWeight/QuantSlab
    NamedTuples flatten to their codes/scale leaves, so quantized storage
    is counted at its real size)."""
    import jax

    total = 0
    for leaf in jax.tree.leaves(tree):
        size = getattr(leaf, "size", None)
        itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", None)
        if size is not None and itemsize is not None:
            total += int(size) * int(itemsize)
    return total


def tree_nbytes_by_device(tree: Any) -> list[int]:
    """Bytes of a pytree's array leaves by the device that holds them, in
    jax.devices() order: a mesh-sharded leaf counts each shard where it
    lives, a replicated leaf once per device. Under --tp this is what
    shows the span and the arena really spread over the chips."""
    import jax

    index = {d.id: i for i, d in enumerate(jax.devices())}
    totals = [0] * len(index)
    for leaf in jax.tree.leaves(tree):
        for shard in getattr(leaf, "addressable_shards", ()):
            totals[index[shard.device.id]] += int(shard.data.nbytes)
    return totals


def server_memory_report(server) -> dict:
    """Exact framework-side accounting for one BlockServer + the device
    counters. All values in bytes (MiB is a presentation concern)."""
    report = {
        "span_params_bytes": tree_nbytes(server.executor.params),
        "span_params_bytes_by_device": tree_nbytes_by_device(
            server.executor.params
        ),
        "host_layer_bytes": tree_nbytes(server.executor.host_layers),
        **server.manager.memory_stats(),
        "device": device_memory_stats(),
    }
    sp_params = getattr(server.executor, "_sp_params", None)
    if sp_params is not None:
        # the sp-prefill mesh holds a REPLICATED second copy of the span
        # params (one buffer per sp chip) — capacity planning must see it
        report["sp_params_bytes"] = tree_nbytes(sp_params) * int(
            server.executor.sp_mesh.devices.size
        )
    if server.adapter_factors:
        report["adapter_bytes"] = tree_nbytes(server.adapter_factors)
    return report


def format_report(report: dict) -> str:
    """One-line human rendering for the [memory] log channel."""
    mib = 1024 * 1024

    def m(key):
        return f"{report.get(key, 0) / mib:.1f}MiB"

    parts = [
        f"params={m('span_params_bytes')}",
        f"arena={m('kv_arena_bytes')}",
        f"host_layers={m('host_layer_bytes')}",
        f"parked={m('parked_kv_host_bytes')}({report.get('parked_seqs', 0)})",
        f"kv_tokens={report.get('kv_tokens_reserved', 0)}"
        f"/{report.get('kv_tokens_capacity', 0)}",
    ]
    dev = report.get("device") or {}
    if dev:
        used = dev.get("bytes_in_use", 0) / mib
        peak = dev.get("peak_bytes_in_use", 0) / mib
        limit = dev.get("bytes_limit", 0) / mib
        parts.append(f"hbm={used:.0f}/{limit:.0f}MiB(peak {peak:.0f})")
    return " ".join(parts)


__all__ = [
    "device_report",
    "device_memory_stats",
    "tree_nbytes",
    "tree_nbytes_by_device",
    "server_memory_report",
    "format_report",
]
