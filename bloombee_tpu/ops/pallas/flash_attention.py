"""Flash attention (Pallas TPU kernel): causal/full, GQA, fp32 accumulation.

Replaces the reference's prefill attention kernel
(/root/reference/src/bloombee/flexgen_utils/pytorch_backend.py:665
`mha_llama`) for long sequences: attention logits never hit HBM, and K/V
stream through VMEM one [block_k, hd] tile at a time (last grid dimension)
with online-softmax stats (m, l, acc) carried in VMEM scratch across the
K-tile steps — so VMEM residency is O(tile) regardless of sequence length.

WHAT A TILE IS. A grid row is one K/V head, not one query head: the query
tile stacks `heads` of the K/V head's `n_rep` query heads, `block_q` rows
each, into `[heads * block_q, hd]` (row r sits at position r % block_q of
its block), so a K/V tile is fetched once and multiplied once for the whole
group, and a 128-row chunk still feeds the MXU several hundred rows. The
operands go to the MXU in the type they ARRIVE in (bfloat16 on the serving
path: one pass), both products accumulate in float32, and the scale is
applied to the float32 logits. The kernel never narrows what it is given:
float32 operands, or operands of mixed types, take the float32 path. On
16-bit operands the probabilities go to `p @ v` as TWO terms of v's type
(`hi = p.astype`, `lo = (p - hi).astype`: 16 mantissa bits, far under the
rounding of the output).

HOW BIG. `flash_tiles` is the one rule: (block_q, block_k, heads) from the
shapes the kernel sees (rows, keys, n_rep, head_dim, operand bytes), the
fattest tile whose operands, float32 logits and scratch fit the kernel's
VMEM budget, with `block_k` a divisor of the gathered length and the
group's heads split across grid rows where they do not fit together. It
has no knob: no environment switch, no argument of a caller;
`block_q` / `block_k` are overrides for tests.

Row r's query i sits at absolute position `starts[r] + i`; keys occupy
absolute positions 0..S-1 and row r sees keys below `lens[r]`. starts/lens
are *traced* per-row vectors (scalar-prefetch inputs), so chunked prefill
at varying — and MIXED — start positions reuses one compiled kernel: a
batch whose rows carry different committed context lengths (multi-turn
session prefill) runs flash instead of falling back to the dense gather
(round-4 verdict #10). The per-row lens mask also hides the garbage tail
of a gathered page run (the serving path gathers whole pages, so S is the
page-aligned bucket, not the exact context length), and K blocks wholly
past a row's lens are skipped outright. The uniform-offset API remains as
`offset=` sugar.

Callers that need tree masks / ALiBi / soft-capping use
`ops.attention.masked_attention`; the serving executor picks per step
(CPU tests run this kernel in interpreter mode). A STATIC `window` masks a
query to its last `window` keys and skips the K blocks below the window of
a tile's FIRST query, as the causal skip takes the tile's LAST (phi4flash's
and afmoe's window layers, whose window is one per run of layers:
runtime/layer_body.py gathers only the pages a chunk's windows span).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30

# the widest K block the rule takes: past 512 keys a step the two-term
# `p @ v` gains nothing (scripts/flash_tile_readings.py), under it every
# halving costs a third. A caller that CHOOSES the length it gathers (a
# window layer's run of pages) rounds it to this, so that the widest block
# divides it (4,736 keys are 37 x 128: nothing but 128 would)
BLOCK_K = 512
# what a tile is sized for, and what the compiler may take: the operands
# twice (the pipeline's two buffers), the scratch, and the float32 logits
# with what the softmax makes of them (the compiler's own count came to 7
# bytes a logit where it was read, 3,072 and 2,048 rows x 512 keys)
_VMEM_BUDGET = 14 * 2**20
_VMEM_LIMIT = 32 * 2**20
_LOGIT_BYTES = 7
_LANES = 128


def flash_takes(t: int, s: int) -> bool:
    """Whether the kernel takes a call of t query rows over s keys a
    sequence: whole 128-blocks of both, the queries among the keys. The ONE
    test its callers make before they call it (a shorter bucket goes their
    dense way) and the executor makes before it says a step's tile."""
    return t > 0 and t % _LANES == 0 and s % _LANES == 0 and s >= t


def _blocks(n: int, pinned: int | None) -> list[int]:
    """Block lengths for an axis of n: every divisor that is a whole number
    of lanes (a short axis: itself), or `pinned` (a test's override, clipped
    to the axis) alone."""
    if pinned is not None:
        return [min(pinned, n)]
    if n < _LANES:
        return [n]
    return [d for d in range(_LANES, n + 1, _LANES) if n % d == 0]


def _tile_bytes(block_q: int, block_k: int, heads: int, hd: int,
                itemsize: int) -> int:
    rows = heads * block_q
    lanes = -(-hd // _LANES) * _LANES
    return (
        rows * lanes * (2 * itemsize + 2 * 4 + 4)  # q, out (<= f32), acc
        + 2 * rows * _LANES * 4  # m, l: a lane-padded column each
        + 4 * block_k * lanes * itemsize  # k, v
        + rows * block_k * _LOGIT_BYTES
    )


@functools.lru_cache(maxsize=None)  # the executor asks at every chunk step
def flash_tiles(t: int, s: int, n_rep: int, hd: int, itemsize: int,
                block_q: int | None = None,
                block_k: int | None = None) -> tuple[int, int, int]:
    """(block_q, block_k, heads): the tile `flash_attention` multiplies, a
    pure function of the shapes it sees. t query rows and s keys a sequence,
    n_rep query heads a K/V head, head_dim hd, operands of `itemsize` bytes
    (a window does not enter: the tile that is best without one was best
    under Trinity's 4096 and within a tenth under phi4flash's 512).
    `block_q` divides t, `block_k` divides s (whole lanes, or a short axis
    itself), `heads` divides n_rep.

    The widest K block up to `BLOCK_K` first (the softmax's row reductions
    and the accumulator's rescale are paid once a K block: at 128 keys the
    kernel reads a third of what it reads at 512), then the most rows the
    VMEM budget holds (fewer, fatter grid steps), whole heads before longer
    query blocks (the group's heads share the K/V tile, and shorter blocks
    skip more of the causal and the window's edge)."""
    qs, ks = _blocks(t, block_q), _blocks(s, block_k)
    if not qs or not ks:
        raise ValueError(f"seq lens must be whole 128-blocks: T={t}, S={s}")
    if block_k is None:
        ks = [d for d in ks if d <= BLOCK_K] or ks[:1]
    best = None
    for bk in ks:
        for g in (g for g in range(1, n_rep + 1) if n_rep % g == 0):
            for bq in qs:
                fits = _tile_bytes(bq, bk, g, hd, itemsize) <= _VMEM_BUDGET
                # a tile over the budget only where nothing fits: the least
                key = (fits, bk, g * bq, g) if fits else (fits, -bk, -g * bq)
                if best is None or key > best[0]:
                    best = (key, (bq, bk, g))
    return best[1]


def _kernel(
    starts_ref,  # [B] i32 scalar prefetch: absolute position of each
    # row's query 0 (rows may differ — mixed-length batches)
    lens_ref,  # [B] i32 scalar prefetch: per-row visible key count
    q_ref,  # [heads, block_q, hd]: `heads` query heads of ONE K/V head
    k_ref,  # [block_k, hd] (current K tile)
    v_ref,  # [block_k, hd]
    o_ref,  # [heads, block_q, hd]
    m_scr,  # [heads * block_q, 1] f32 scratch
    l_scr,  # [heads * block_q, 1] f32 scratch
    acc_scr,  # [heads * block_q, hd] f32 scratch
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    heads: int,
    n_k: int,
    hkv: int,  # K/V heads (grid dim 0 is b*hkv; b_idx = bh // hkv)
    window: int = 0,  # static: > 0, a query sees its last `window` keys only
):
    b_idx = pl.program_id(0) // hkv
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    rows, hd = heads * block_q, q_ref.shape[-1]

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lens_ref[b_idx]
    # the tile's first and last absolute query position, the block's keys
    q_first = starts_ref[b_idx] + qi * block_q
    q_last = q_first + block_q - 1
    k_first = kj * block_k
    k_last = k_first + block_k - 1
    # K blocks wholly past this row's length, past the tile's LAST query or
    # below the window of its FIRST cost neither compute nor (via the
    # index-map clamp) HBM bandwidth
    visible = k_first < length
    if causal:
        visible &= k_first <= q_last
    if window:
        visible &= k_last > q_first - window

    @pl.when(visible)
    def _update():
        q = q_ref[...].reshape(rows, hd)
        k, v = k_ref[...], v_ref[...]
        # the MXU takes the operands in the type they arrive in; mixed
        # types meet in the wider one (nothing is narrowed)
        qk_t = jnp.promote_types(q.dtype, k.dtype)
        logits = jax.lax.dot_general(
            q.astype(qk_t), k.astype(qk_t), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [rows, bk]
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        q_pos = q_first + (row % block_q if heads > 1 else row)
        k_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        mask = jnp.broadcast_to(k_pos < length, (rows, block_k))
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window:
            mask = mask & (k_pos > q_pos - window)
        logits = jnp.where(mask, logits, NEG)
        m = m_scr[...]
        m_new = jnp.maximum(m, logits.max(axis=1, keepdims=True))
        # a row with no visible key so far keeps m at NEG and sums ones;
        # its first visible key's `corr` is exp(NEG - m) = 0 and wipes
        # them, and a row that never sees one is zeroed at the end
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        if q.dtype == v.dtype and v.dtype.itemsize == 2:
            pv = _probs_times_v(p, v)
        else:
            pv = _pv(p, v.astype(jnp.float32))
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new

    @pl.when(kj == n_k - 1)
    def _finalize():
        out = jnp.where(
            m_scr[...] > NEG / 2,
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30), 0.0,
        )
        o_ref[...] = out.reshape(heads, block_q, hd).astype(o_ref.dtype)


def _pv(p, v):
    return jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _probs_times_v(p, v):
    """float32 probabilities times 16-bit values as two single-pass
    products: `hi` is p rounded to v's type and `lo` what the rounding
    dropped, 16 mantissa bits together. (One cast of p reads 500 times
    farther from float64 where the output is float32 and is no faster at
    512 keys a block: scripts/flash_tile_readings.py --forms.)"""
    hi = p.astype(v.dtype)
    lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
    return _pv(hi, v) + _pv(lo, v)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "block_q", "block_k", "interpret", "window",
        "out_dtype",
    ),
)
def flash_attention(
    q: jax.Array,  # [B, T, H, hd]
    k: jax.Array,  # [B, S, Hkv, hd], S >= T (extra = committed prefix)
    v: jax.Array,  # [B, S, Hkv, hd]
    causal: bool = True,
    scale: float | None = None,
    block_q: int | None = None,  # tests only: `flash_tiles` sizes the tile
    block_k: int | None = None,
    interpret: bool = False,
    offset=None,  # traced i32 scalar, uniform-start sugar; None and no
    # starts => S - T (queries at the end)
    starts=None,  # [B] traced i32: per-row absolute position of query 0
    # (mixed-length batches); overrides offset
    lens=None,  # [B] traced i32: per-row visible key count; None =>
    # starts + T when causal (exactly the keys the causal mask would
    # allow), else S (non-causal attends everything, as before)
    window: int = 0,  # static: > 0, query i sees keys (pos_i - window, pos_i]
    # only (a sliding window; the caller gathers just the pages it spans)
    out_dtype=None,  # the output's type; None => q's
) -> jax.Array:
    b, t, h, hd = q.shape
    s, hkv = k.shape[1], k.shape[2]
    if h % hkv:
        raise ValueError(f"H={h} must be a multiple of Hkv={hkv}")
    if s < t:
        raise ValueError(f"S={s} must be >= T={t}")
    n_rep = h // hkv
    if scale is None:
        scale = hd**-0.5
    block_q, block_k, heads = flash_tiles(
        t, s, n_rep, hd, max(q.dtype.itemsize, k.dtype.itemsize),
        block_q, block_k,
    )
    if t % block_q or s % block_k:
        raise ValueError(
            f"seq lens must divide blocks: T={t}%{block_q}, S={s}%{block_k}"
        )
    n_k = s // block_k
    if starts is None:
        starts = jnp.full((b,), s - t if offset is None else offset)
    starts = jnp.asarray(starts, jnp.int32).reshape(b)
    if lens is None:
        lens = starts + t if causal else jnp.full((b,), s)
    lens = jnp.asarray(lens, jnp.int32).reshape(b)

    qf = q.transpose(0, 2, 1, 3).reshape(b * hkv, n_rep, t, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, hd)

    def q_index(bh, hg, qi, kj, st, ln):
        return (bh, hg, qi, 0)

    def kv_index(bh, hg, qi, kj, st, ln):
        # K blocks outside this tile's visible range must not cost HBM
        # bandwidth: clamp dead steps onto the nearest visible block so
        # Pallas elides the duplicate DMA (their compute is skipped by
        # pl.when(visible) in the kernel)
        q_first = st[bh // hkv] + qi * block_q
        last = ln[bh // hkv] - 1
        if causal:
            last = jnp.minimum(last, q_first + block_q - 1)
        last_blk = jnp.maximum(last, 0) // block_k
        blk = jnp.minimum(kj, last_blk)
        if window:
            first_blk = jnp.maximum(q_first - window + 1, 0) // block_k
            blk = jnp.maximum(blk, jnp.minimum(first_blk, last_blk))
        return (bh, blk, 0)

    rows = heads * block_q
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * hkv, n_rep // heads, t // block_q, n_k),
        in_specs=[
            pl.BlockSpec((None, heads, block_q, hd), q_index),
            pl.BlockSpec((None, block_k, hd), kv_index),
            pl.BlockSpec((None, block_k, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((None, heads, block_q, hd), q_index),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel,
            scale=scale,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
            heads=heads,
            n_k=n_k,
            hkv=hkv,
            window=window,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (b * hkv, n_rep, t, hd), out_dtype or q.dtype
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary",
            ),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(starts, lens, qf, kf, vf)
    return out.reshape(b, h, t, hd).transpose(0, 2, 1, 3)
