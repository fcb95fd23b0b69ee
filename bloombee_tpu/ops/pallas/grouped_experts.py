"""Grouped expert MLP (Pallas TPU kernel): a step reads an expert's weights
only if one of its rows chose that expert.

The dense-over-experts einsum (ops/moe.py) streams every expert's three
matrices through the MXU for every step. A decode step of 1-8 rows picks at
most rows * top_k of them, so this kernel walks a LIST of chosen experts
instead: the list rides in as a scalar-prefetch operand and steers each grid
step's weight BlockSpec index map to that expert's block of the stack where
it lies (the paged attention kernels steer K/V pages the same way,
ops/pallas/paged_attention.py). No expert is gathered into a buffer first and
none is read twice: a slot past the list's end re-names the last live block,
so Pallas elides its DMA and `pl.when` skips its compute.

Every grid step multiplies ALL of the step's rows with one expert and weights
the result per row (zero for a row that did not choose it): on the MXU one
row costs what sixteen do, and the per-row weights are the whole routing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# what the three double-buffered weight blocks of one grid step may take of
# VMEM (a v5e core has 128 MiB; the default scoped limit is 16 MiB, raised
# below to what the blocks need)
_WEIGHT_BLOCKS_BYTES = 24 * 2**20


def _kernel(
    e_ref,  # [P] i32 scalar prefetch: slot s's expert (row of the stack)
    n_ref,  # [1] i32 scalar prefetch: live slots
    x_ref,  # [R, D]
    w_ref,  # [R, 1] f32: each row's router weight for slot s's expert
    g_ref,  # [D, tI]
    u_ref,  # [D, tI]
    d_ref,  # [tI, D]
    o_ref,  # [R, D] f32, resident over the whole grid
):
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((s == 0) & (j == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(s < n_ref[0])
    def _expert():
        x = x_ref[...]
        g = jnp.dot(x, g_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, u_ref[...], preferred_element_type=jnp.float32)
        h = jax.nn.silu(g) * u * w_ref[...]
        o_ref[...] += jnp.dot(
            h.astype(x.dtype), d_ref[...], preferred_element_type=jnp.float32
        )


def _i_tile(d: int, i: int, itemsize: int) -> int:
    """Largest tile of the intermediate dim whose three double-buffered
    weight blocks fit the budget: all of it, or a multiple of 128 that
    divides it (Mosaic's lane tiling)."""
    fits = _WEIGHT_BLOCKS_BYTES // (3 * 2 * d * itemsize)
    if i <= fits or i % 128:
        return i
    tile = max(128, fits // 128 * 128)
    while i % tile:
        tile -= 128
    return tile


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_experts(
    x: jax.Array,  # [R, D]
    slot_expert: jax.Array,  # [P] i32: the experts some row chose, padded
    # past `live` with the last live one
    live: jax.Array,  # i32 scalar: how many slots count
    slot_weights: jax.Array,  # [P, R] f32: row r's weight for slot s
    gate_w: jax.Array,  # [E, D, I]
    up_w: jax.Array,  # [E, D, I]
    down_w: jax.Array,  # [E, I, D]
    interpret: bool = False,
) -> jax.Array:
    """sum over live slots s of w[s, r] * mlp_{slot_expert[s]}(x[r]): [R, D]
    float32."""
    r, d = x.shape
    p = slot_expert.shape[0]
    i = gate_w.shape[-1]
    # whole sublane tiles for the MXU's left operand; the added rows are
    # zeros with zero weights
    r_pad = -r % (16 if x.dtype.itemsize == 2 else 8)
    x = jnp.pad(x, ((0, r_pad), (0, 0)))
    w = jnp.pad(slot_weights.astype(jnp.float32), ((0, 0), (0, r_pad)))
    rows = r + r_pad
    ti = _i_tile(d, i, gate_w.dtype.itemsize)
    n_j = i // ti

    def tile(s, j, n):
        # a dead slot keeps the last block fetched: no DMA
        return jnp.where(s < n[0], j, n_j - 1)

    in_block = pl.BlockSpec(  # gate and up: [D, tI] of slot s's expert
        (None, d, ti), lambda s, j, e, n: (e[s], 0, tile(s, j, n))
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(p, n_j),
        in_specs=[
            pl.BlockSpec((rows, d), lambda s, j, e, n: (0, 0)),
            pl.BlockSpec((None, rows, 1), lambda s, j, e, n: (s, 0, 0)),
            in_block, in_block,
            pl.BlockSpec(
                (None, ti, d), lambda s, j, e, n: (e[s], tile(s, j, n), 0)
            ),
        ],
        out_specs=pl.BlockSpec((rows, d), lambda s, j, e, n: (0, 0)),
    )
    block_bytes = 3 * 2 * d * ti * gate_w.dtype.itemsize
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=block_bytes + 8 * 2**20,
        ),
        interpret=interpret,
        name="grouped_experts",
    )(
        slot_expert.astype(jnp.int32),
        jnp.asarray(live, jnp.int32).reshape(1),
        x, w[:, :, None], gate_w, up_w, down_w,
    )
    return out[:r]
