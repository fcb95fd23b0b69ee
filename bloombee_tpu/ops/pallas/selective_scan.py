"""Mamba-1 selective scan over one sequence's chunk (Pallas TPU kernel).

`ops/ssm.py` `mamba1_chunk` as a `lax.scan` reads and writes the whole
[N, C] state (328 KB at d_inner 5120, state 16) once a TOKEN. Here the
channels are tiled over the grid (each tile is independent: the recurrence
couples nothing across channels), the tokens are walked inside the kernel,
and a tile's state stays on the core from the chunk's first token to its
last: read from HBM once, written once.

Layout (since PR 59): a channel tile is `block_c` = G x 128 channels and
everything a token owns per channel is ONE [G, 128] array, the channels over
the sublanes AND the lanes (a whole vector register at G = 8), so the state
is [N, G, 128], a register a state column, carried in registers along the
walk. B_t[n] and C_t[n] (per token, shared by all channels) are SCALARS, read
from SMEM as flat [T * N] arrays of the numbers' own size: nothing is
broadcast along the sublanes or the lanes, and the sum over the state columns
that gives y_t is N - 1 additions of whole registers, no reduction across
one. x, dt and y stay [T, C] in HBM; a turn of the walk takes a
sublane tile of 8 tokens as whole aligned [8, 128] loads, re-files them in a
VMEM scratch so that token r of the tile is rows r, r + 8, ... of it (one
sublane-strided load a token), walks the 8 tokens with static indices (the
loop over them is unrolled where the kernel is lowered), and stores y back as
whole unmasked tiles the same way. What does not read the
carried state (the decays exp(dt_t * A), the feeds (dt_t x_t) B_t, the
scalars' broadcasts) is straight-line code beside the chain S = dA_t S +
dBx_t, which the scheduler overlaps with it: the chain itself is one
multiply and one add a register a token. The arithmetic is `mamba1_step`'s,
float32, the same exp of the same argument in the same order along the
tokens. A row with dt == 0 leaves S as it was, wherever it lies in a tile.

Readings (one v5e, PR 59; T 512, C 5120, N 16): 92 us a call in the cell's
traced chunks (0.083 s over 909 calls; the walk of PR 45, a token a turn over
state-major [N, 512] tiles with [T, N, 1] columns, 525 us), 39.7 us of it the
bytes' time at 819 GB/s; a turn of 8 tokens is 375 instruction bundles with
the four vector slots about 80% full, the scalars' broadcasts two of a (token,
column)'s nine vector operations. scripts/selective_scan_readings.py, the
kernel alone: `block_c` 512 takes 1.8 times the rule's 1024, 256 3.2 times
(emptier registers, the same count of instructions); `block_t` 64 / 128 / 256
lie within 2%.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES, TILE = 128, 8  # a float32 register: 8 sublanes of 128 lanes


def scan_block_c(c: int) -> int:
    """Channels a grid tile holds: the most whole lanes, up to one full
    register (8 x 128) a state column, that divide `c`. One register a
    column keeps the N carried columns and their decays inside the core's
    64 registers at N = 16; two a column (2048 channels) spill, and less
    than a full one leaves sublanes empty at the same count of instructions
    (block_c 512: 1.8 times the call, the file's readings)."""
    groups = c // LANES
    return LANES * max(g for g in range(1, TILE + 1) if groups % g == 0)


def _kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref, s0_ref, y_ref, s_ref,
            s_scr, a_scr, d_scr, x_scr, dt_scr, y_scr, *, block_t: int,
            n_t: int):
    ti = pl.program_id(1)
    n, g = s_scr.shape[:2]
    rows = TILE * g  # scratch rows one 8-row tile of [8, block_c] takes

    def refile(src, row0, dst, tiles=1):
        """src[row0 + 8 k + r, 128 j + l] -> dst[k rows + 8 j + r, l]."""
        for k in range(tiles):
            for j in range(g):
                dst[pl.ds(k * rows + j * TILE, TILE), :] = src[
                    pl.ds(row0 + k * TILE, TILE), pl.ds(j * LANES, LANES)]

    def unfile(src, dst, row0, tiles=1):
        """`refile`'s inverse: whole tiles of src back to dst's rows."""
        for k in range(tiles):
            for j in range(g):
                dst[pl.ds(row0 + k * TILE, TILE), pl.ds(j * LANES, LANES)] = (
                    src[pl.ds(k * rows + j * TILE, TILE), :])

    def row(r, k=0):
        """Row r of the k-th re-filed 8-row tile as one [G, 128]."""
        return pl.ds(k * rows + r, g, stride=TILE)

    @pl.when(ti == 0)
    def _init():
        for src, dst in ((s0_ref, s_scr), (a_ref, a_scr)):
            refile(src, 0, y_scr, n // TILE)
            for i in range(n):
                dst[i] = y_scr[row(i % TILE, i // TILE), :]
        for j in range(g):
            d_scr[pl.ds(j, 1), :] = d_ref[:, pl.ds(j * LANES, LANES)]

    d = d_scr[...]

    def turn(i, s):
        r0 = pl.multiple_of(i * TILE, TILE)
        refile(x_ref, r0, x_scr)
        refile(dt_ref, r0, dt_scr)
        a = a_scr[...]

        def column(ref, at):
            """Token `at`'s N scalars, each over a whole [G, 128]."""
            base = at * n
            return jnp.stack([
                jnp.full((g, LANES), ref[base + k]) for k in range(n)])

        def token(r, s):
            dt_t, x_t = dt_scr[row(r), :], x_scr[row(r), :]
            s = jnp.exp(dt_t[None] * a) * s + (dt_t * x_t)[None] * column(
                b_ref, r0 + r)
            y_scr[row(r), :] = jnp.sum(
                s * column(c_ref, r0 + r), axis=0) + d * x_t
            return s

        # (unrolled where the kernel is lowered, traced once: a turn traced
        # token by token took 0.5 s of Python a program that holds the kernel)
        s = lax.fori_loop(0, TILE, token, s, unroll=True)
        unfile(y_scr, y_ref, r0)
        return s

    s = lax.fori_loop(0, block_t // TILE, turn, s_scr[...])
    s_scr[...] = s

    @pl.when(ti == n_t - 1)
    def _done():
        for i in range(n):
            y_scr[row(i % TILE, i // TILE), :] = s[i]
        unfile(y_scr, s_ref, 0, n // TILE)


def selective_scan_ok(c: int, n: int = 16) -> bool:
    """Whether `selective_scan` takes `c` channels and `n` state columns:
    whole lanes, whole sublane tiles of columns (any number of rows: they
    are padded with dt = 0 rows)."""
    return c % LANES == 0 and n % TILE == 0


@functools.partial(
    jax.jit, static_argnames=("block_c", "block_t", "interpret")
)
def selective_scan(
    x: jax.Array,  # [T, C] float32
    dt: jax.Array,  # [T, C] float32, after softplus; 0 = the row is skipped
    a_t: jax.Array,  # [N, C] float32, negative
    b: jax.Array,  # [T, N] float32
    c: jax.Array,  # [T, N]
    d: jax.Array,  # [C]
    s0: jax.Array,  # [N, C] float32
    block_c: int | None = None,
    block_t: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(y [T, C], the state after the last row [N, C]) as
    `ops/ssm.py` `mamba1_chunk` gives them (y to the order of the sum over
    the state columns). `block_c` defaults to `scan_block_c`'s tile."""
    t_real, ch = x.shape
    n = a_t.shape[0]
    block_c = min(block_c or scan_block_c(ch), ch)
    if not selective_scan_ok(ch, n) or ch % block_c or block_c % LANES:
        raise ValueError(
            f"selective_scan: C={ch}, N={n} are not whole tiles of {block_c}"
        )
    # whole token blocks (or one block of whole sublanes): rows of dt = 0
    # after the last leave S as it was
    unit = TILE if t_real < block_t else block_t
    t = -(-t_real // unit) * unit
    block_t = min(block_t, t)
    if t != t_real:
        x, dt, b, c = (
            jnp.pad(z, ((0, t - t_real), (0, 0))) for z in (x, dt, b, c)
        )
    n_t, g = t // block_t, block_c // LANES
    rows = pl.BlockSpec((block_t, block_c), lambda ci, ti: (ti, ci))
    tile = pl.BlockSpec((n, block_c), lambda ci, ti: (0, ci))
    scalars = pl.BlockSpec(
        (block_t * n,), lambda ci, ti: (ti,), memory_space=pltpu.SMEM
    )
    y, s = pl.pallas_call(
        functools.partial(_kernel, block_t=block_t, n_t=n_t),
        grid=(ch // block_c, n_t),
        in_specs=[
            scalars, scalars, rows, rows, tile,
            pl.BlockSpec((1, block_c), lambda ci, ti: (0, ci)), tile,
        ],
        out_specs=[rows, tile],
        out_shape=[
            jax.ShapeDtypeStruct((t, ch), jnp.float32),
            jax.ShapeDtypeStruct((n, ch), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, g, LANES), jnp.float32),  # S, a column an array
            pltpu.VMEM((n, g, LANES), jnp.float32),  # A likewise
            pltpu.VMEM((g, LANES), jnp.float32),  # D
            pltpu.VMEM((TILE * g, LANES), jnp.float32),  # a turn's x
            pltpu.VMEM((TILE * g, LANES), jnp.float32),  # a turn's dt
            pltpu.VMEM((max(n, TILE) * g, LANES), jnp.float32),  # y; S, A
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(b.reshape(-1), c.reshape(-1), x, dt, a_t, d[None, :], s0)
    return y[:t_real], s
