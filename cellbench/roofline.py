"""Peaks of the chips the benchmark knows, and the least time a stated amount
of work takes at them: the yardstick's side of `step_roofline` and
`chunk_roofline`.

A roofline share is least_time / measured device time. least_time counts only
what the algorithm NEEDS for one decode step or one prefill chunk: the bytes
and FLOPs that the configuration's family states
(cellbench/families/<model_type>.py: `decode_step_needs`, `chunk_needs`; each
layer's weights once, for sparse experts only the DISTINCT ones the rows are
routed to, every row's live keys, values or state once, the rows' activations
in and out). Work the program does beyond that (computing every expert,
copying the arena) lowers the share; it can never push it past 100%.
"""

from __future__ import annotations

# Published peaks, keyed by jax's device_kind. Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}
BF16 = 2


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}: add it to "
            f"cellbench/roofline.py with its source, do not guess")
    return PEAKS[device_kind]


def expected_distinct_experts(experts: int, top_k: int, rows: float) -> float:
    """Distinct experts hit by `rows` tokens that each pick top_k of
    `experts` uniformly: E * (1 - (1 - k/E)**rows)."""
    return experts * (1.0 - (1.0 - top_k / experts) ** rows)


def least_seconds(needs: dict, device_kind: str) -> tuple[float, str]:
    """The larger of bytes over peak bandwidth and FLOPs over peak rate, and
    which of the two it is."""
    p = peaks(device_kind)
    by_bytes = needs["bytes"] / p["bytes_per_s"]
    by_flops = needs["flops"] / p["flops_per_s"]
    return (by_bytes, "memory") if by_bytes >= by_flops else (by_flops, "compute")
