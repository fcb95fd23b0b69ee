"""Normalization ops.

Matches HF Llama semantics bit-for-bit in fp32 (reference kernel:
/root/reference/src/bloombee/flexgen_utils/pytorch_backend.py:111 `rms_norm`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def layer_norm(
    x: jax.Array,
    weight: jax.Array,
    bias: jax.Array | None = None,
    eps: float = 1e-5,
) -> jax.Array:
    """Standard LayerNorm (Bloom/Falcon families), fp32 accumulation."""
    in_dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mean) ** 2, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(in_dtype)


def rms_norm(
    x: jax.Array, weight: jax.Array, eps: float = 1e-6,
    one_plus: bool = False,
) -> jax.Array:
    """RMSNorm with fp32 accumulation, output cast back to input dtype.

    Order of operations matches HF LlamaRMSNorm: normalize in fp32, cast back to
    the input dtype, then multiply by the (un-cast) weight. `one_plus`: the
    stored weight is zero-centred and the scale is `1 + weight`, taken in
    fp32 before the cast (HF Qwen3NextRMSNorm: in bfloat16 `1 + w` would
    round the weight to 2**-8 of its scale).
    """
    in_dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    if one_plus:
        return (y * (1.0 + weight.astype(jnp.float32))).astype(in_dtype)
    return weight * y.astype(in_dtype)
