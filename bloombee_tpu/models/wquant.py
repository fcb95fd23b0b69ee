"""Weight-only quantization for serving spans (int8 / int4).

The weight half of the reference's compression lever
(/root/reference/src/bloombee/flexgen_utils/compression.py:22-210 compresses
weights as well as KV). Decode is weight-bandwidth-bound — the span step
reads every projection matrix once per token — so storing projections as
int8 (or group-wise int4) halves (quarters) the HBM bytes per step and
raises the decode-throughput roofline accordingly. Compute stays bf16: the
dequantize (convert + scale multiply) is an elementwise producer that XLA
fuses into the matmul's operand read on TPU, so the dequantized matrix is
never materialized in HBM.

Layouts (for a weight stored [..., in, out]; an output-major key,
models/layout.py, has the last two axes of every leaf swapped):
- int8: per-output-channel symmetric scale. codes [..., in, out] int8,
  scale [..., 1, out].
- int4: group-wise (GROUP=32 x out) asymmetric — same group size as the
  int4 KV slab; round-to-nearest at larger groups is too noisy — two
  values packed per byte along the input dim. codes [..., in/2, out]
  uint8, scale/zero [..., in/GROUP, out] f16 (0.625 B/weight vs 2 B bf16,
  3.2x).

`QuantWeight` is a pytree: quantized leaves stack, scan, and donate through
the span step exactly like dense arrays.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from bloombee_tpu.models.layout import in_axis_of, plain_key

GROUP = 32

# 2D projection keys eligible for quantization (per-layer params dict);
# norms/biases/router stay dense — tiny, and precision-critical
QUANT_KEYS = (
    "q_proj", "k_proj", "v_proj", "o_proj",
    "gate_proj", "up_proj", "down_proj",
    "experts_gate", "experts_up", "experts_down",
    # falcon_h1's mixer: its two projections only (the convolution's taps,
    # A_log, D, dt_bias and the norms stay as the checkpoint has them)
    "ssm_in_proj", "ssm_out_proj",
    # deepseek_v2: latent attention's projections and the shared experts
    # (the router and the norms stay as the checkpoint has them)
    "q_a_proj", "q_b_nope", "q_b_rope", "kv_a_proj", "kv_b_k", "kv_b_v",
    "shared_gate", "shared_up", "shared_down",
    # qwen3_next: the gate rows of a gated attention, and the gated-DeltaNet
    # mixer's two wide projections (b | a, the taps, A_log, dt_bias and the
    # norms stay as the checkpoint has them)
    "q_gate_proj", "gdn_in_proj", "gdn_out_proj",
    # kimi_linear: KDA's q | k | v and o are those two keys, its full-rank
    # latent queries `q_b_nope` / `q_b_rope` above; the low-rank gates
    # (`gdn_low_proj`, `gdn_f_b_proj`, `gdn_g_b_proj`) stay as the checkpoint
    # has them, like qwen3_next's b | a
    # phi4flash: the Mamba-1 mixer's and the gated memory unit's two wide
    # projections each (x_proj, dt_proj, the taps, A, D and the norms stay as
    # the checkpoint has them)
    "mamba_in_proj", "mamba_out_proj", "gmu_in_proj", "gmu_out_proj",
)


class QuantWeight(NamedTuple):
    codes: jax.Array
    scale: jax.Array
    zero: jax.Array | None = None  # int4 only

    @property
    def bits(self) -> int:
        return 8 if self.codes.dtype == jnp.int8 else 4


def _swap(x):
    return None if x is None else jnp.swapaxes(x, -1, -2)


@functools.partial(jax.jit, static_argnames=("bits", "in_axis"))
def quantize_weight(
    w: jax.Array, bits: int = 8, in_axis: int = -2
) -> QuantWeight:
    """Quantize along the input (contraction) dim, `in_axis` of the last
    two: -2 for [..., in, out], -1 for an output-major [..., out, in].
    Jitted, so a stacked span's leaf is never held in float32 beside itself
    (eagerly, an 8-layer 5120 x 21504 stack asked for two 3.5 GB
    temporaries and did not load on a 16 GB chip)."""
    w = w.astype(jnp.float32)
    if bits == 8:
        # one scale per output channel: [..., 1, out] or [..., out, 1]
        amax = jnp.max(jnp.abs(w), axis=in_axis, keepdims=True)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        codes = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
        return QuantWeight(codes=codes, scale=scale.astype(jnp.float32))
    if bits == 4 and in_axis == -1:
        return QuantWeight(*map(_swap, quantize_weight(_swap(w), 4)))
    if bits == 4:
        *lead, din, dout = w.shape
        gs = min(GROUP, din)
        if din % gs or din % 2:
            raise ValueError(f"in dim {din} not int4-groupable")
        g = din // gs
        wg = w.reshape(*lead, g, gs, dout)
        mn = wg.min(axis=-2, keepdims=True)  # [..., g, 1, out]
        mx = wg.max(axis=-2, keepdims=True)
        scale = (mx - mn) / 15.0
        safe = jnp.where(scale > 0, scale, 1.0)
        q = jnp.clip(jnp.round((wg - mn) / safe), 0, 15).astype(jnp.uint8)
        q = q.reshape(*lead, din, dout)
        codes = q[..., 0::2, :] | (q[..., 1::2, :] << 4)
        return QuantWeight(
            codes=codes,
            scale=scale.squeeze(-2).astype(jnp.float16),
            zero=mn.squeeze(-2).astype(jnp.float16),
        )
    raise ValueError(f"unsupported weight bits {bits}")


def dequantize_weight(
    qw: QuantWeight, dtype=jnp.bfloat16, in_axis: int = -2
) -> jax.Array:
    if qw.bits == 8:
        # the scale's shape says which axis is the channels'
        return (qw.codes.astype(jnp.float32) * qw.scale).astype(dtype)
    if in_axis == -1:
        return _swap(dequantize_weight(QuantWeight(*map(_swap, qw)), dtype))
    codes = qw.codes
    lo = (codes & 0xF).astype(jnp.float32)
    hi = (codes >> 4).astype(jnp.float32)
    *lead, half, dout = codes.shape
    q = jnp.stack([lo, hi], axis=-2).reshape(*lead, half * 2, dout)
    din = half * 2
    gs = min(GROUP, din)
    g = din // gs
    qg = q.reshape(*lead, g, gs, dout)
    out = (
        qg * qw.scale[..., :, None, :].astype(jnp.float32)
        + qw.zero[..., :, None, :].astype(jnp.float32)
    )
    return out.reshape(*lead, din, dout).astype(dtype)


def maybe_dequantize(w, dtype=jnp.bfloat16, in_axis: int = -2):
    """Dense passthrough or fused-dequant entry used by the layer body."""
    if isinstance(w, QuantWeight):
        return dequantize_weight(w, dtype, in_axis)
    return w


def quantize_span_params(stacked: dict, bits: int) -> dict:
    """Quantize the eligible 2D projections of a stacked span params dict
    (leaves carry a leading L dim). Returns a new dict; ineligible leaves
    (norms, biases, router) pass through dense."""
    out = {}
    for key, leaf in stacked.items():
        name = plain_key(key)  # a span's leading run, or its linear kind
        if name in QUANT_KEYS and getattr(leaf, "ndim", 0) >= 3:
            out[key] = quantize_weight(leaf, bits, in_axis_of(name))
        else:
            out[key] = leaf
    return out


def quantize_layer_params(params: dict, bits: int) -> dict:
    """Per-layer (unstacked) variant of quantize_span_params: quantize via
    a transient 1-stack so the stacked-ndim eligibility gate applies
    unchanged — the shared idiom for hetero spans, offloaded host tails,
    and per-layer checkpoint loading."""
    import jax

    from bloombee_tpu.utils.tree import stack_params

    one = quantize_span_params(stack_params([params]), bits)
    return jax.tree.map(lambda x: x[0], one)


def params_nbytes(stacked: dict) -> int:
    from bloombee_tpu.utils.memory import tree_nbytes

    return tree_nbytes(stacked)
