"""Group-wise 4-bit KV quantization (the reference's KV-capacity lever).

Capability port of /root/reference/src/bloombee/flexgen_utils/compression.py
:22-210 (`TorchCompressedDevice`: group-wise asymmetric 4-bit quant of
weights/KV with `general_copy_compressed`), redesigned for the jitted paged
arena: the quantized slab is a pytree (`QuantSlab`) whose leaves ride the
span step's `lax.scan` carry and donation exactly like the dense slab, writes
quantize on-device as part of the step, and page gathers dequantize into the
attention dtype — so int4 KV needs no separate copy path at all.

Layout per slab: codes pack two 4-bit values per uint8 along head_dim;
scale/zero are per-(slot, head, group) float16. At head_dim 128 and
group_size 32 a token costs 64 B codes + 16 B scale/zero = 80 B vs 256 B
bf16 -> 3.2x more tokens per HBM byte.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

GROUP_SIZE = 32


class QuantSlab(NamedTuple):
    """int4-quantized KV slab; a jax pytree (leaves scan/donate like arrays).

    Leading dims mirror the dense slab ([L, S, H, ...] or [S, H, ...]).
    """

    codes: jax.Array  # [..., hd // 2] uint8, two nibbles per byte
    scale: jax.Array  # [..., hd // GROUP_SIZE] f16, (max - min) / 15
    zero: jax.Array  # [..., hd // GROUP_SIZE] f16, group min

    @property
    def shape(self) -> tuple[int, ...]:
        """Logical (unpacked) leading shape; the slot dim matches dense."""
        return self.codes.shape

    @property
    def head_dim(self) -> int:
        return self.codes.shape[-1] * 2


def make_quant_slab(shape: tuple[int, ...], _dtype=None) -> QuantSlab:
    """Empty quantized slab for a dense-equivalent shape [..., hd]."""
    *lead, hd = shape
    gs = min(GROUP_SIZE, hd)
    assert hd % 2 == 0 and hd % gs == 0, f"head_dim {hd} not int4-packable"
    groups = hd // gs
    return QuantSlab(
        codes=jnp.zeros((*lead, hd // 2), jnp.uint8),
        scale=jnp.zeros((*lead, groups), jnp.float16),
        zero=jnp.zeros((*lead, groups), jnp.float16),
    )


def quantize(x: jax.Array) -> QuantSlab:
    """Group-wise asymmetric int4 quantization along the last dim."""
    *lead, hd = x.shape
    gs = min(GROUP_SIZE, hd)
    g = hd // gs
    xg = x.astype(jnp.float32).reshape(*lead, g, gs)
    mn = xg.min(axis=-1)
    mx = xg.max(axis=-1)
    scale = (mx - mn) / 15.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(
        jnp.round((xg - mn[..., None]) / safe[..., None]), 0, 15
    ).astype(jnp.uint8)
    q = q.reshape(*lead, hd)
    codes = q[..., 0::2] | (q[..., 1::2] << 4)
    return QuantSlab(
        codes=codes,
        scale=scale.astype(jnp.float16),
        zero=mn.astype(jnp.float16),
    )


def dequantize(slab: QuantSlab, dtype=jnp.bfloat16) -> jax.Array:
    """Inverse of `quantize`: [..., hd] in the requested dtype."""
    codes, scale, zero = slab.codes, slab.scale, slab.zero
    lo = codes & 0xF
    hi = codes >> 4
    q = jnp.stack([lo, hi], axis=-1).reshape(
        *codes.shape[:-1], codes.shape[-1] * 2
    )
    hd = q.shape[-1]
    gs = min(GROUP_SIZE, hd)
    g = hd // gs
    qg = q.reshape(*q.shape[:-1], g, gs).astype(jnp.float32)
    out = qg * scale[..., None].astype(jnp.float32) + zero[..., None].astype(
        jnp.float32
    )
    return out.reshape(*q.shape[:-1], hd).astype(dtype)


def slab_nbytes(slab) -> int:
    """Total bytes of a slab (dense array or QuantSlab)."""
    from bloombee_tpu.utils.memory import tree_nbytes

    return tree_nbytes(slab)
