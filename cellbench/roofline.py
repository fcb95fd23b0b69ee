"""Peaks of the chips the benchmark knows, and the least work a decode step
or a prefill chunk needs: the yardstick's side of `step_roofline` and
`chunk_roofline`.

A roofline share is least_time / measured device time. least_time counts only
what the algorithm NEEDS for one step of `rows` single-token decodes at a mean
live context: each layer's attention weights once, the MLP weights once (for
a sparse-expert layer: the router and only the DISTINCT experts the rows are
routed to, in expectation under uniform routing), every row's live keys and
values once (inside the sliding window), and the rows' activations in and
out. Work the program does beyond that (computing every expert, copying the
arena) lowers the share; it can never push it past 100%.
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


def layer_weights(config: dict) -> dict:
    """Parameter counts of one layer: attention, and the MLP as either one
    dense block or (router, one expert, number of experts, experts/token)."""
    d = config["hidden_size"]
    hd = config.get("head_dim") or d // config["num_attention_heads"]
    q = config["num_attention_heads"] * hd
    kv = config["num_key_value_heads"] * hd
    out = {"attn": d * q + 2 * d * kv + q * d}
    if config.get("num_experts"):
        out["router"] = d * config["num_experts"]
        out["expert"] = 3 * d * config["moe_intermediate_size"]
        out["experts"] = config["num_experts"]
        out["top_k"] = config["num_experts_per_tok"]
    else:
        out["mlp"] = 3 * d * config["intermediate_size"]
    return out


def expected_distinct_experts(experts: int, top_k: int, rows: float) -> float:
    """Distinct experts hit by `rows` tokens that each pick top_k of
    `experts` uniformly: E * (1 - (1 - k/E)**rows)."""
    return experts * (1.0 - (1.0 - top_k / experts) ** rows)


def decode_step_needs(config: dict, rows: float, context: float) -> dict:
    """Bytes and FLOPs one decode step of `rows` rows at mean live context
    `context` needs, over the configuration's layers."""
    w = layer_weights(config)
    layers = config["num_hidden_layers"]
    d = config["hidden_size"]
    hd = config.get("head_dim") or d // config["num_attention_heads"]
    if config.get("sliding_window"):
        context = min(context, config["sliding_window"])
    if "expert" in w:
        distinct = expected_distinct_experts(w["experts"], w["top_k"], rows)
        mlp_read = w["router"] + distinct * w["expert"]
        mlp_active = w["router"] + w["top_k"] * w["expert"]
    else:
        mlp_read = mlp_active = w["mlp"]
    kv_row = 2 * config["num_key_value_heads"] * hd  # K and V of one token
    weight_bytes = layers * (w["attn"] + mlp_read) * BF16
    kv_bytes = layers * rows * (context + 1) * kv_row * BF16
    act_bytes = 2 * rows * d * BF16
    flops = layers * rows * (
        2 * (w["attn"] + mlp_active)
        + 4 * context * config["num_attention_heads"] * hd
    )
    return {"bytes": weight_bytes + kv_bytes + act_bytes, "flops": flops,
            "weight_bytes": weight_bytes, "kv_bytes": kv_bytes}


def chunk_needs(config: dict, rows: float, context: float) -> dict:
    """Bytes and FLOPs one prefill chunk of `rows` tokens of ONE sequence
    needs when `context` tokens of it are already cached: every layer's
    weights once (sparse experts: the distinct ones `rows` tokens pick), the
    cached keys and values once (inside the sliding window), the chunk's own
    keys and values written once, activations in and out; causal attention
    over the cache and over the chunk's own lower triangle."""
    w = layer_weights(config)
    layers = config["num_hidden_layers"]
    d = config["hidden_size"]
    hd = config.get("head_dim") or d // config["num_attention_heads"]
    if config.get("sliding_window"):
        context = min(context, config["sliding_window"])
    if "expert" in w:
        distinct = expected_distinct_experts(w["experts"], w["top_k"], rows)
        mlp_read = w["router"] + distinct * w["expert"]
        mlp_active = w["router"] + w["top_k"] * w["expert"]
    else:
        mlp_read = mlp_active = w["mlp"]
    kv_row = 2 * config["num_key_value_heads"] * hd
    weight_bytes = layers * (w["attn"] + mlp_read) * BF16
    kv_bytes = layers * (context + rows) * kv_row * BF16
    act_bytes = 2 * rows * d * BF16
    flops = layers * rows * (
        2 * (w["attn"] + mlp_active)
        + 4 * (context + rows / 2) * config["num_attention_heads"] * hd
    )
    return {"bytes": weight_bytes + kv_bytes + act_bytes, "flops": flops,
            "weight_bytes": weight_bytes, "kv_bytes": kv_bytes}


def least_seconds(needs: dict, device_kind: str) -> tuple[float, str]:
    """The larger of bytes over peak bandwidth and FLOPs over peak rate, and
    which of the two it is."""
    p = peaks(device_kind)
    by_bytes = needs["bytes"] / p["bytes_per_s"]
    by_flops = needs["flops"] / p["flops_per_s"]
    return (by_bytes, "memory") if by_bytes >= by_flops else (by_flops, "compute")
