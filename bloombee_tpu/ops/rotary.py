"""Rotary position embeddings (RoPE).

Replaces the reference's rotary helpers + CUDA-graphed rotary
(/root/reference/src/bloombee/flexgen_utils/pytorch_backend.py:59-110,
/root/reference/src/bloombee/models/llama/block.py:76-81). The CUDA-graph capture
role is played by `jax.jit`: the whole step is traced once and compiled.

Position ids are explicit everywhere (no module state) because the paged KV design
and tree speculative decoding both need arbitrary per-token positions
(reference: backend.py:944-1047 tree rotary position ids).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention-magnitude factor: 0.1 * mscale * ln(factor) + 1."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(
    dim: int, theta: float, factor: float, original_max: int,
    beta_fast: float, beta_slow: float,
) -> np.ndarray:
    """YaRN's inverse frequencies [dim / 2] (float32, a trace-time
    constant): dimensions that turn more than `beta_fast` times over the
    original context keep their frequency, those that turn fewer than
    `beta_slow` times are interpolated by `factor`, a linear ramp between
    the two correction indices."""
    pos = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = 1.0 / theta**pos
    inter = 1.0 / (factor * theta**pos)

    def correction(turns):
        return (
            dim * math.log(original_max / (turns * 2 * math.pi))
        ) / (2 * math.log(theta))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    ramp = (np.arange(dim // 2, dtype=np.float32) - low) / max(
        high - low, 0.001
    )
    keep = 1.0 - np.clip(ramp, 0.0, 1.0)  # 1: extrapolated as trained
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def mla_cos_sin(positions: jax.Array, mla, theta: float):
    """cos/sin [..., T, rope_dim] for latent attention's rotary part, from
    its descriptor (models/spec.py MlaSpec): YaRN frequencies when
    `rope_factor` is not 1, scaled by mscale / mscale_all_dim's mscale."""
    if mla.rope_factor == 1.0:
        return rotary_cos_sin(positions, mla.rope_dim, theta)
    inv_freq = yarn_inv_freq(
        mla.rope_dim, theta, mla.rope_factor, mla.rope_original_max,
        mla.rope_beta_fast, mla.rope_beta_slow,
    )
    scale = yarn_mscale(mla.rope_factor, mla.rope_mscale) / yarn_mscale(
        mla.rope_factor, mla.rope_mscale_all_dim
    )
    cos, sin = rotary_cos_sin(positions, mla.rope_dim, inv_freq=inv_freq)
    return (cos, sin) if scale == 1.0 else (cos * scale, sin * scale)


def deinterleave(n: int) -> np.ndarray:
    """The permutation [0, 2, 4, ..., 1, 3, 5, ...] of `n` interleaved
    rotary dims (pairs (x0, x1), (x2, x3), ...): the published DeepSeek-V2
    code applies it to q_pe and k_pe before the half-rotation. Queries and
    keys both go through it, so their products are those of the interleaved
    rotation; a loader applies it ONCE to the rows of the projections that
    make them (models/deepseek_v2.py) and the step rotates halves."""
    return np.concatenate([np.arange(0, n, 2), np.arange(1, n, 2)])


def rotary_cos_sin(
    positions: jax.Array,  # [..., T] int32 absolute positions
    head_dim: int,
    theta: float = 10000.0,
    dtype: jnp.dtype = jnp.float32,
    inv_freq=None,  # [head_dim / 2] frequencies other than theta's
) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for the given absolute positions; fp32 math like HF."""
    if inv_freq is None:
        inv_freq = 1.0 / (
            theta
            ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
        )
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., T, hd/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [..., T, hd]
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def _rotate_half(x: jax.Array) -> jax.Array:
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rotary(
    q: jax.Array,  # [B, T, H, hd]
    k: jax.Array,  # [B, T, Hkv, hd]
    cos: jax.Array,  # [B, T, hd]
    sin: jax.Array,  # [B, T, hd]
) -> tuple[jax.Array, jax.Array]:
    """Apply RoPE to q and k (head axis broadcast). Tables narrower than
    the heads (`ModelSpec.rotary_dim`) turn the first dims only: the rest
    of a head carries no position."""
    rd = cos.shape[-1]
    if rd < q.shape[-1]:
        q_rot, k_rot = apply_rotary(q[..., :rd], k[..., :rd], cos, sin)
        return (
            jnp.concatenate([q_rot, q[..., rd:]], axis=-1),
            jnp.concatenate([k_rot, k[..., rd:]], axis=-1),
        )
    cos = cos[:, :, None, :].astype(q.dtype)
    sin = sin[:, :, None, :].astype(q.dtype)
    q_out = q * cos + _rotate_half(q) * sin
    k_out = k * cos.astype(k.dtype) + _rotate_half(k) * sin.astype(k.dtype)
    return q_out, k_out
