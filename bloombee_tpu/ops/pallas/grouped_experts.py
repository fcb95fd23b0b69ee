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

An expert is three matrices, down(silu(gate(x)) * up(x)), or TWO where the
family's experts have no gate (`gate_w` None, `activation` "relu2":
down(relu(up(x)) ** 2), nemotron_h): the kernels then take two weight blocks
a grid step and the same budget buys a wider tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from bloombee_tpu.ops.moe import expert_hidden

# what the double-buffered weight blocks (three, or an ungated expert's two)
# of one grid step may take of VMEM (a v5e core has 128 MiB; the default scoped limit is 16 MiB, raised
# below to what the blocks need)
_WEIGHT_BLOCKS_BYTES = 24 * 2**20


def _hidden(x, g_ref, u_ref, activation: str):
    """One expert's hidden activations for rows `x` from its weight blocks
    (`g_ref` None: an ungated expert), float32 [rows, tI]."""
    g = None if g_ref is None else jnp.dot(
        x, g_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, u_ref[...], preferred_element_type=jnp.float32)
    return expert_hidden(g, u, activation)


def _kernel(
    e_ref,  # [P] i32 scalar prefetch: slot s's expert (row of the stack)
    n_ref,  # [1] i32 scalar prefetch: live slots
    x_ref,  # [R, D]
    w_ref,  # [R, 1] f32: each row's router weight for slot s's expert
    *refs,  # g_ref [D, tI] (a gated expert only), u_ref [D, tI],
    # d_ref [tI, D], o_ref [R, D] f32, resident over the whole grid
    activation: str,
):
    *g_ref, u_ref, d_ref, o_ref = refs
    g_ref = g_ref[0] if g_ref else None
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((s == 0) & (j == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(s < n_ref[0])
    def _expert():
        x = x_ref[...]
        h = _hidden(x, g_ref, u_ref, activation) * w_ref[...]
        o_ref[...] += jnp.dot(
            h.astype(x.dtype), d_ref[...], preferred_element_type=jnp.float32
        )


def _i_tile(d: int, i: int, itemsize: int, matrices: int = 3) -> int:
    """Largest tile of the intermediate dim whose `matrices` double-buffered
    weight blocks fit the budget: all of it, or a multiple of 128 that
    divides it (Mosaic's lane tiling). A width of no whole lanes cannot be
    tiled and goes in whole: a loader pads such a stack (models/
    nemotron_h.py: 1856 -> 1920)."""
    fits = _WEIGHT_BLOCKS_BYTES // (matrices * 2 * d * itemsize)
    if i <= fits or i % 128:
        return i
    tile = max(128, fits // 128 * 128)
    while i % tile:
        tile -= 128
    return tile


def _weight_blocks(up_w: jax.Array, gated: bool):
    """(tiles of the intermediate dim, the [gate /] up / down BlockSpecs of
    a grid (slot, tile) steered by the first two scalar-prefetch operands,
    the slots' experts and how many slots count, and the bytes of the
    double-buffered blocks)."""
    _, d, i = up_w.shape
    matrices = 3 if gated else 2
    itemsize = up_w.dtype.itemsize
    ti = _i_tile(d, i, itemsize) if gated else _i_tile(
        d, i, itemsize, matrices=2)
    n_j = i // ti

    def tile(s, j, n):
        # a dead slot keeps the last block fetched: no DMA
        return jnp.where(s < n[0], j, n_j - 1)

    in_block = pl.BlockSpec(  # gate and up: [D, tI] of slot s's expert
        (None, d, ti), lambda s, j, e, n, *_: (e[s], 0, tile(s, j, n))
    )
    down_block = pl.BlockSpec(
        (None, ti, d), lambda s, j, e, n, *_: (e[s], tile(s, j, n), 0)
    )
    return (
        n_j, [in_block] * (matrices - 1) + [down_block],
        matrices * 2 * d * ti * up_w.dtype.itemsize,
    )


@functools.partial(jax.jit, static_argnames=("interpret", "activation"))
def grouped_experts(
    x: jax.Array,  # [R, D]
    slot_expert: jax.Array,  # [P] i32: the experts some row chose, padded
    # past `live` with the last live one
    live: jax.Array,  # i32 scalar: how many slots count
    slot_weights: jax.Array,  # [P, R] f32: row r's weight for slot s
    gate_w: jax.Array | None,  # [E, D, I]; None: an ungated expert
    up_w: jax.Array,  # [E, D, I]
    down_w: jax.Array,  # [E, I, D]
    interpret: bool = False,
    activation: str = "silu",
) -> jax.Array:
    """sum over live slots s of w[s, r] * mlp_{slot_expert[s]}(x[r]): [R, D]
    float32."""
    r, d = x.shape
    p = slot_expert.shape[0]
    # whole sublane tiles for the MXU's left operand; the added rows are
    # zeros with zero weights
    r_pad = -r % (16 if x.dtype.itemsize == 2 else 8)
    x = jnp.pad(x, ((0, r_pad), (0, 0)))
    w = jnp.pad(slot_weights.astype(jnp.float32), ((0, 0), (0, r_pad)))
    rows = r + r_pad
    n_j, weight_blocks, block_bytes = _weight_blocks(
        up_w, gate_w is not None)
    weights = [w for w in (gate_w, up_w, down_w) if w is not None]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(p, n_j),
        in_specs=[
            pl.BlockSpec((rows, d), lambda s, j, e, n: (0, 0)),
            pl.BlockSpec((None, rows, 1), lambda s, j, e, n: (s, 0, 0)),
            *weight_blocks,
        ],
        out_specs=pl.BlockSpec((rows, d), lambda s, j, e, n: (0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, activation=activation),
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
        x, w[:, :, None], *weights,
    )
    return out[:r]


# rows a grid step of the tiled form gathers, multiplies and scatters at a
# time: an expert's run of (row, expert) pairs is walked in tiles of this
# many INSIDE its grid step, so its weight blocks are fetched once however
# long the run is. 32, 64 and 128 read the same time on the chip to 0.3%
# (PERF.md section 6, PR 40): the step waits for the next expert's blocks
ROW_TILE = 64


def _tiled_kernel(
    e_ref,  # [P] i32 scalar prefetch: slot s's expert (row of the stack)
    n_ref,  # [1] i32: live slots
    start_ref,  # [P] i32: where slot s's run of pairs starts in src / w
    count_ref,  # [P] i32: how many pairs it has
    src_ref,  # [N] i32: the row each sorted pair came from
    w_ref,  # [N] f32: its router weight
    x_hbm,  # [R, D] f32, where it lies
    *refs,  # g_ref [D, tI] (a gated expert only), u_ref [D, tI],
    # d_ref [tI, D], o_hbm [R, D] f32, where it lies, then the scratch:
    # x_ref [R, D] f32, the rows, fetched once; o_ref [R, D] f32, the sum,
    # written back once; xt_ref [tile, D] f32, a tile's gathered rows;
    # yt_ref [tile, D] f32, their outputs for this expert; sem
    activation: str,
):
    *g_ref, u_ref, d_ref, o_hbm, x_ref, o_ref, xt_ref, yt_ref, sem = refs
    g_ref = g_ref[0] if g_ref else None
    s = pl.program_id(0)
    j = pl.program_id(1)
    tile = xt_ref.shape[0]

    @pl.when((s == 0) & (j == 0))
    def _init():
        rows_in = pltpu.make_async_copy(x_hbm, x_ref, sem)
        rows_in.start()
        o_ref[...] = jnp.zeros_like(o_ref)
        # rows of a tile past its pairs are multiplied too and never read:
        # they must hold numbers
        xt_ref[...] = jnp.zeros_like(xt_ref)
        rows_in.wait()

    @pl.when(s < n_ref[0])
    def _expert():
        start = start_ref[s]
        count = count_ref[s]

        def row_tile(t, carry):
            base = start + t * tile
            live = jnp.minimum(count - t * tile, tile)

            def gather(i, c):
                xt_ref[pl.ds(i, 1), :] = x_ref[pl.ds(src_ref[base + i], 1), :]
                return c

            jax.lax.fori_loop(0, live, gather, 0)
            x = xt_ref[...].astype(u_ref.dtype)
            h = _hidden(x, g_ref, u_ref, activation)
            yt_ref[...] = jnp.dot(
                h.astype(x.dtype), d_ref[...],
                preferred_element_type=jnp.float32,
            )

            def scatter(i, c):
                row = pl.ds(src_ref[base + i], 1)
                o_ref[row, :] += w_ref[base + i] * yt_ref[pl.ds(i, 1), :]
                return c

            jax.lax.fori_loop(0, live, scatter, 0)
            return carry

        jax.lax.fori_loop(0, pl.cdiv(count, tile), row_tile, 0)

    @pl.when(
        (s == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1)
    )
    def _done():
        rows_out = pltpu.make_async_copy(o_ref, o_hbm, sem)
        rows_out.start()
        rows_out.wait()


@functools.partial(jax.jit, static_argnames=("interpret", "activation"))
def tiled_experts(
    x: jax.Array,  # [R, D]
    slot_expert: jax.Array,  # [P] i32: the experts some pair chose, padded
    # past `live` with the last live one
    live: jax.Array,  # i32 scalar: how many slots count
    slot_start: jax.Array,  # [P] i32: slot s's pairs are
    # src[start : start + count]
    slot_count: jax.Array,  # [P] i32
    src: jax.Array,  # [N] i32: the pairs sorted by expert, each one's row
    weights: jax.Array,  # [N] f32: each one's router weight
    gate_w: jax.Array | None,  # [E, D, I]; None: an ungated expert
    up_w: jax.Array,  # [E, D, I]
    down_w: jax.Array,  # [E, I, D]
    interpret: bool = False,
    activation: str = "silu",
) -> jax.Array:
    """sum over the listed pairs p of weights[p] * mlp_{expert(p)}(x[src[p]])
    into row src[p]: [R, D] float32. Only the pairs' rows are multiplied
    with an expert, `ROW_TILE` at a time; a row no pair names stays zero."""
    r, d = x.shape
    p = slot_expert.shape[0]
    n_j, weight_blocks, block_bytes = _weight_blocks(
        up_w, gate_w is not None)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(p, n_j),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY), *weight_blocks],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((r, d), jnp.float32),
            pltpu.VMEM((r, d), jnp.float32),
            pltpu.VMEM((ROW_TILE, d), jnp.float32),
            pltpu.VMEM((ROW_TILE, d), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    scratch_bytes = 2 * (r + ROW_TILE) * d * 4
    return pl.pallas_call(
        functools.partial(_tiled_kernel, activation=activation),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=block_bytes + scratch_bytes + 8 * 2**20,
        ),
        interpret=interpret,
        name="tiled_experts",
    )(
        slot_expert.astype(jnp.int32),
        jnp.asarray(live, jnp.int32).reshape(1),
        slot_start.astype(jnp.int32),
        slot_count.astype(jnp.int32),
        src.astype(jnp.int32),
        weights.astype(jnp.float32),
        x.astype(jnp.float32),
        *(w for w in (gate_w, up_w, down_w) if w is not None),
    )
