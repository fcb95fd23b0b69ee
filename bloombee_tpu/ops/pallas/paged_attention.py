"""Paged decode attention (Pallas TPU kernel).

The dense decode path gathers every page of context into a contiguous
[B, S, Hkv, hd] buffer each step (kv/arena.py gather_pages) and then runs
masked attention over it — two full passes over the context's HBM bytes per
step. This kernel instead streams K/V pages straight out of the paged arena
(one pass): the page table rides in as a scalar-prefetch operand and steers
each grid step's K/V BlockSpec index map to the right physical page, with
online-softmax stats carried in VMEM scratch across the page dimension.
Covers the decode-attention role of the reference's fused kernels
(/root/reference/src/bloombee/flexgen_utils/pytorch_backend.py:733
`mha_gen_llama`), built vLLM-paged-attention-style for the TPU memory
hierarchy.

Kernel layout note (Mosaic constraint): a block may not squeeze the
second-to-last array dimension, so blocking one KV head at a time out of the
[tokens, Hkv, hd] arena is not lowerable. Instead each grid step loads one
whole page ACROSS heads as a [page_size*Hkv, hd] block (a free reshape of
the arena) and computes every query head against every row in ONE MXU
matmul; rows belonging to a different KV-head group are masked off in the
logits. Decode attention is HBM-bandwidth-bound — the x Hkv extra FLOPs are
noise, and the bytes read are exactly one pass over the context.

Scope: four kernels share the online-softmax page-streaming machinery.
`paged_decode_attention` covers single-token decode (T=1; per-sequence
lengths masked per page, sliding windows in-kernel with whole-page skips);
`paged_decode_attention_int4` is its in-VMEM-dequant variant for
int4-quantized arenas; `paged_chunk_attention` covers T>1 steps —
tree-verify steps (the [T, T] tree mask applied in-kernel) and short
multi-token chunks below flash's T>=128 domain; `paged_ragged_attention`
covers mixed-batch steps (N decode rows plus one prefill-chunk row-group
packed raggedly, per-row owning sequence and position) in one grid launch
over the cross-session page-table view. ALiBi, logit soft-caps, and
tree+window combinations take the dense path (the executor checks
eligibility host-side, like the flash prefill kernel).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _online_softmax_body(
    load_kv,  # () -> (k [rows, hd], v [rows, hd]) f32 for the current page
    lens_ref, win_ref, q_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale, page_size, n_pages, hkv, g,
):
    """The page-streaming online-softmax state machine shared by the dense
    and int4 kernels (they differ ONLY in how a K/V page is materialized).

    - block row r holds token (r // hkv) of the page for kv head (r % hkv)
      (row-major flatten of [page_size, Hkv]); query head i belongs to kv
      head i // g. Positions past `length` (page-table padding included)
      and off-group rows mask to NEG before the online-softmax max.
    - sliding window: the decode query sits at position length-1 and sees
      keys in [length - win, length) (matching attend_paged's
      `key_pos > q_pos - window`); win == 0 means full attention. Pages
      wholly below the window are skipped outright — for long contexts
      that is most of them, which is the point of a sliding window.
    """
    b = pl.program_id(0)
    j = pl.program_id(1)
    h = hkv * g
    rows = page_size * hkv

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lens_ref[b]
    win = win_ref[0]
    low = jnp.where(win > 0, jnp.maximum(length - win, 0), 0)
    r = jax.lax.broadcasted_iota(jnp.int32, (h, rows), 1)
    qh = jax.lax.broadcasted_iota(jnp.int32, (h, rows), 0)
    pos = j * page_size + r // hkv
    own = (r % hkv) == (qh // g)
    page_live = (j * page_size < length) & ((j + 1) * page_size > low)

    @pl.when(page_live)
    def _update():
        q = q_ref[...].astype(jnp.float32) * scale
        k, v = load_kv()
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [H, page_size * Hkv]
        mask = own & (pos < length) & (pos >= low)
        logits = jnp.where(mask, logits, NEG)
        m = m_scr[...]
        m_new = jnp.maximum(m, logits.max(axis=1, keepdims=True))
        p = jnp.exp(logits - m_new) * mask.astype(jnp.float32)
        corr = jnp.exp(m - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        # off-group p entries are exactly zero, so contracting against ALL
        # rows picks out each head's own V rows
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(j == n_pages - 1)
    def _finalize():
        # fully-masked rows (zero-length padding sequences) divide by eps
        # and emit zeros, which the executor drops with the pad rows
        o_ref[...] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


def _make_kv_index(page_size: int):
    """Index map steering each grid step's K/V block to the right physical
    page. Out-of-window grid steps must not cost HBM bandwidth: clamp the
    logical page to the first in-window page, so dead steps re-name the
    same block and Pallas elides the duplicate DMA entirely (their compute
    is skipped by pl.when(page_live) in the kernel)."""

    def kv_index(bi, j, pt, ln, wn):
        first = jnp.where(
            wn[0] > 0,
            jnp.maximum(ln[bi] - wn[0], 0) // page_size,
            0,
        )
        return (pt[bi, jnp.maximum(j, first)], 0, 0)

    return kv_index


def _kernel(
    pt_ref,  # [B, NP] i32 scalar prefetch: logical page j of seq b
    lens_ref,  # [B] i32 scalar prefetch: context length per sequence
    win_ref,  # [1] i32 scalar prefetch: sliding window (0 = full attention)
    q_ref,  # [H, hd] — every query head of this sequence
    k_ref,  # [page_size * Hkv, hd] — current physical page, ALL kv heads
    v_ref,  # [page_size * Hkv, hd]
    o_ref,  # [H, hd]
    m_scr,  # [H, 1] f32
    l_scr,  # [H, 1] f32
    acc_scr,  # [H, hd] f32
    *,
    scale: float,
    page_size: int,
    n_pages: int,
    hkv: int,
    g: int,  # query heads per kv head (H = hkv * g)
):
    def load_kv():
        return k_ref[...].astype(jnp.float32), v_ref[...].astype(jnp.float32)

    _online_softmax_body(
        load_kv, lens_ref, win_ref, q_ref, o_ref, m_scr, l_scr, acc_scr,
        scale=scale, page_size=page_size, n_pages=n_pages, hkv=hkv, g=g,
    )


def _f16_bits_to_f32(bits: jax.Array) -> jax.Array:
    """Decode float16 bit patterns (uint16) to f32 with integer ops. Mosaic
    cannot load f16 vectors, so the int4 kernel takes the slab's f16
    scale/zero leaves bitcast to uint16 (free in XLA) and rebuilds the
    values here; exact for every finite f16, subnormals and -0 included."""
    bits = bits.astype(jnp.int32)
    exp = (bits >> 10) & 0x1F
    mant = bits & 0x3FF
    normal = jax.lax.bitcast_convert_type(
        ((exp + 112) << 23) | (mant << 13), jnp.float32
    )
    mag = jnp.where(exp == 0, mant.astype(jnp.float32) * 2.0**-24, normal)
    return jnp.where(bits >> 15 == 1, -mag, mag)


def _int4_kernel(
    pt_ref,  # [B, NP] i32 scalar prefetch
    lens_ref,  # [B] i32
    win_ref,  # [1] i32
    q_ref,  # [H, hd] — PERMUTED head dim (evens then odds)
    kc_ref,  # [page_size * Hkv, hd // 2] u8 int4 codes, current page
    ks_ref,  # [page_size * Hkv, groups] u16: the f16 scales' BITS
    kz_ref,  # [page_size * Hkv, groups] u16: the f16 zeros' BITS
    vc_ref,
    vs_ref,
    vz_ref,
    o_ref,  # [H, hd] — PERMUTED
    m_scr,
    l_scr,
    acc_scr,
    *,
    scale: float,
    page_size: int,
    n_pages: int,
    hkv: int,
    g: int,
    groups: int,
):
    """int4 variant of _kernel: the shared online-softmax body runs over
    pages dequantized in VMEM (reference TorchCompressedDevice decompress,
    compression.py:163-210). Nibble unpack avoids lane interleaving: low
    nibbles are the EVEN original head positions and high nibbles the ODD
    ones, so concat(lo, hi) is the dequantized row in a permuted head
    order — the caller permutes q and un-permutes the output instead.
    Group-wise scales stay compact: original group i covers permuted lanes
    [i*gs/2, (i+1)*gs/2) in each half (evens of a contiguous group are
    contiguous), so dequant is an unrolled per-group slice-scale-concat."""
    half = kc_ref.shape[-1]
    per = half // groups  # permuted lanes per original group, per half

    def deq(codes_ref, s_ref, z_ref):
        # widen before the nibble math: Mosaic has no uint8 -> f32 cast
        codes = codes_ref[...].astype(jnp.int32)
        s = _f16_bits_to_f32(s_ref[...])
        z = _f16_bits_to_f32(z_ref[...])
        lo = (codes & 0xF).astype(jnp.float32)
        hi = (codes >> 4).astype(jnp.float32)
        halves = []
        for nib in (lo, hi):
            parts = [
                nib[:, i * per : (i + 1) * per] * s[:, i : i + 1]
                + z[:, i : i + 1]
                for i in range(groups)
            ]
            halves.append(
                parts[0] if len(parts) == 1
                else jnp.concatenate(parts, axis=-1)
            )
        return jnp.concatenate(halves, axis=-1)  # [rows, hd] permuted

    def load_kv():
        return deq(kc_ref, ks_ref, kz_ref), deq(vc_ref, vs_ref, vz_ref)

    _online_softmax_body(
        load_kv, lens_ref, win_ref, q_ref, o_ref, m_scr, l_scr, acc_scr,
        scale=scale, page_size=page_size, n_pages=n_pages, hkv=hkv, g=g,
    )


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "scale", "interpret"),
)
def paged_decode_attention_int4(
    q: jax.Array,  # [B, H, hd]
    k_slab,  # QuantSlab (codes [S_tot, Hkv, hd/2] u8, scale/zero f16)
    v_slab,
    page_table: jax.Array,
    lens: jax.Array,
    page_size: int,
    scale: float | None = None,
    interpret: bool = False,
    window=0,
) -> jax.Array:
    """Paged decode attention straight off an int4-quantized arena: one HBM
    pass over ~1/3 the bytes of the bf16 slab (codes + group scales), with
    dequantization in VMEM."""
    b, h, hd = q.shape
    s_tot, hkv = k_slab.codes.shape[0], k_slab.codes.shape[1]
    if h % hkv:
        raise ValueError(f"H={h} must be a multiple of Hkv={hkv}")
    if s_tot % page_size:
        raise ValueError(f"arena slots {s_tot} % page_size {page_size}")
    g = h // hkv
    groups = k_slab.scale.shape[-1]
    n_pages = page_table.shape[1]
    if scale is None:
        scale = hd**-0.5
    rows = page_size * hkv

    # permuted head order: evens then odds (see kernel docstring)
    q_perm = jnp.concatenate([q[..., 0::2], q[..., 1::2]], axis=-1)

    def pages(x, last):
        return x.reshape(-1, rows, last)

    def f16_bits(x):
        return jax.lax.bitcast_convert_type(x, jnp.uint16)

    kc, ks, kz = (
        pages(k_slab.codes, hd // 2),
        pages(f16_bits(k_slab.scale), groups),
        pages(f16_bits(k_slab.zero), groups),
    )
    vc, vs, vz = (
        pages(v_slab.codes, hd // 2),
        pages(f16_bits(v_slab.scale), groups),
        pages(f16_bits(v_slab.zero), groups),
    )

    kv_index = _make_kv_index(page_size)

    def q_index(bi, j, pt, ln, wn):
        return (bi, 0, 0)

    kv_spec = lambda last: pl.BlockSpec((None, rows, last), kv_index)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_pages),
        in_specs=[
            pl.BlockSpec((None, h, hd), q_index),
            kv_spec(hd // 2), kv_spec(groups), kv_spec(groups),
            kv_spec(hd // 2), kv_spec(groups), kv_spec(groups),
        ],
        out_specs=pl.BlockSpec((None, h, hd), q_index),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, hd), jnp.float32),
        ],
    )
    win_arr = jnp.asarray(window, jnp.int32).reshape(1)
    out = pl.pallas_call(
        functools.partial(
            _int4_kernel, scale=scale, page_size=page_size, n_pages=n_pages,
            hkv=hkv, g=g, groups=groups,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        interpret=interpret,
    )(
        page_table.astype(jnp.int32), lens.astype(jnp.int32), win_arr,
        q_perm, kc, ks, kz, vc, vs, vz,
    )
    # un-permute: permuted lane i < hd/2 holds original 2i; i >= hd/2 holds
    # original 2(i - hd/2) + 1
    inv = np.empty((hd,), np.int32)
    inv[0::2] = np.arange(hd // 2)
    inv[1::2] = np.arange(hd // 2) + hd // 2
    return out[..., jnp.asarray(inv)]


def _chunk_kernel(
    pt_ref,  # [B, NP] i32 scalar prefetch
    lens_ref,  # [B] i32 scalar prefetch (lens INCLUDE the T new tokens)
    meta_ref,  # [2] i32 scalar prefetch: [window (0 = full), t_real].
    # t_real = real query tokens: the step's tokens occupy positions
    # [length - t_real, length); bucket-padding rows (qt >= t_real) wrote
    # to dropped slots and their outputs are sliced away by the caller.
    # TRACED (not static) so varying real token counts inside one pow2
    # bucket share a compile.
    *refs,
    scale: float,
    page_size: int,
    n_pages: int,
    hkv: int,
    g: int,
    t_q: int,  # query-token BUCKET (may be padded past the real count)
    has_tree: bool,
):
    """T>1 variant of _kernel: each grid step attends ALL T query tokens'
    heads (a [T*H, hd] block) against one K/V page. Covers the two T>1 hot
    paths the dense gather served before (round-4 verdict #5):

    - plain causal chunks: query token t sits at position start+t
      (start = length - T); key visible iff pos <= start+t (and inside the
      per-query sliding window when one is set)
    - tree-verify steps (has_tree): the T new tokens' mutual visibility
      comes from the [T, T] tree mask; the committed prefix (pos < start)
      is fully visible to every tree token (reference backend.py:596-652
      tree masks — here streamed per page instead of materializing
      [B, H, T, S] logits over a gathered context)

    The tree lookup tm[t, pos-start] is expressed as two small one-hot
    matmuls (tm @ sel, then query-row expansion) because Mosaic has no
    arbitrary 2D gather; both contract tiny [T, .] operands on the MXU.
    """
    if has_tree:
        tm_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
        tm_ref = None
    b = pl.program_id(0)
    j = pl.program_id(1)
    h = hkv * g
    rows = page_size * hkv  # key rows per page
    rq = t_q * h  # query rows

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lens_ref[b]
    win = meta_ref[0]
    t_real = meta_ref[1]
    start = length - t_real
    rk = jax.lax.broadcasted_iota(jnp.int32, (rq, rows), 1)
    rqi = jax.lax.broadcasted_iota(jnp.int32, (rq, rows), 0)
    pos = j * page_size + rk // hkv  # key position
    qh = rqi % h
    qt = rqi // h  # query token index
    own = (rk % hkv) == (qh // g)
    # earliest position ANY query can see (window applies per query; the
    # page-skip bound uses the earliest query t=0)
    low0 = jnp.where(win > 0, jnp.maximum(start + 1 - win, 0), 0)
    page_live = (j * page_size < length) & ((j + 1) * page_size > low0)

    @pl.when(page_live)
    def _update():
        q = q_ref[...].astype(jnp.float32) * scale
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [rq, rows]
        valid = pos < length
        if tm_ref is None:
            mask = own & valid & (pos <= start + qt) & (qt < t_real)
            mask &= (win <= 0) | (pos > start + qt - win)
        else:
            tm = tm_ref[...].astype(jnp.float32)  # [t_q, t_q]
            ti = jax.lax.broadcasted_iota(jnp.int32, (t_q, rows), 0)
            posk = (
                j * page_size
                + jax.lax.broadcasted_iota(jnp.int32, (t_q, rows), 1) // hkv
            )
            sel = (posk == start + ti).astype(jnp.float32)  # [t_q, rows]
            tree_vis = jax.lax.dot_general(
                tm, sel, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [t_q, rows]
            oh = (
                jax.lax.broadcasted_iota(jnp.int32, (rq, t_q), 0) // h
                == jax.lax.broadcasted_iota(jnp.int32, (rq, t_q), 1)
            ).astype(jnp.float32)
            tree_rows = jax.lax.dot_general(
                oh, tree_vis, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [rq, rows]
            mask = own & valid & ((pos < start) | (tree_rows > 0.5))
        logits = jnp.where(mask, logits, NEG)
        m = m_scr[...]
        m_new = jnp.maximum(m, logits.max(axis=1, keepdims=True))
        p = jnp.exp(logits - m_new) * mask.astype(jnp.float32)
        corr = jnp.exp(m - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(j == n_pages - 1)
    def _finalize():
        o_ref[...] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "scale", "interpret", "has_tree"),
)
def paged_chunk_attention(
    q: jax.Array,  # [B, T, H, hd] — T new tokens per sequence (T may be a
    # padded bucket; t_real marks the real count)
    k_slab: jax.Array,  # [S_tot, Hkv, hd] — the paged arena, one layer
    v_slab: jax.Array,
    page_table: jax.Array,  # [B, NP] i32
    lens: jax.Array,  # [B] i32 (INCLUDING the t_real new tokens)
    page_size: int,
    tree_mask: jax.Array | None = None,  # [B, T, T] bool (has_tree)
    scale: float | None = None,
    interpret: bool = False,
    window=0,  # traced i32 scalar; 0 = full (tree steps gate window off
    # host-side: depth-positioned tree tokens + window stay on the dense
    # path)
    has_tree: bool = False,
    t_real=None,  # real (unpadded) query tokens; None = T. TRACED so real
    # counts inside one pow2 bucket share a compile.
) -> jax.Array:  # [B, T, H, hd]
    """Paged attention for T>1 steps (tree verify, short multi-token
    chunks): one HBM pass over the context pages instead of the dense
    path's gather-then-attend two passes. VMEM budget: caller gates on
    T*H rows (executor allows <= 2048)."""
    b, t_q, h, hd = q.shape
    s_tot, hkv = k_slab.shape[0], k_slab.shape[1]
    if h % hkv:
        raise ValueError(f"H={h} must be a multiple of Hkv={hkv}")
    if s_tot % page_size:
        raise ValueError(f"arena slots {s_tot} % page_size {page_size}")
    g = h // hkv
    n_pages = page_table.shape[1]
    if scale is None:
        scale = hd**-0.5
    if t_real is None:
        t_real = t_q
    rows = page_size * hkv
    rq = t_q * h

    kp = k_slab.reshape(-1, rows, hd)
    vp = v_slab.reshape(-1, rows, hd)
    q2 = q.reshape(b, rq, hd)

    def kv_index(bi, j, pt, ln, mt):
        # page-skip clamp for the windowed-chunk case: the earliest page
        # any query needs starts at max(start + 1 - win, 0)
        first = jnp.where(
            mt[0] > 0,
            jnp.maximum(ln[bi] - mt[1] + 1 - mt[0], 0) // page_size,
            0,
        )
        return (pt[bi, jnp.maximum(j, first)], 0, 0)

    def q_index(bi, j, pt, ln, mt):
        return (bi, 0, 0)

    in_specs = [
        pl.BlockSpec((None, rq, hd), q_index),
        pl.BlockSpec((None, rows, hd), kv_index),
        pl.BlockSpec((None, rows, hd), kv_index),
    ]
    args = [q2, kp, vp]
    if has_tree:
        assert tree_mask is not None
        in_specs.insert(0, pl.BlockSpec((None, t_q, t_q), q_index))
        args.insert(0, tree_mask.astype(jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, rq, hd), q_index),
        scratch_shapes=[
            pltpu.VMEM((rq, 1), jnp.float32),
            pltpu.VMEM((rq, 1), jnp.float32),
            pltpu.VMEM((rq, hd), jnp.float32),
        ],
    )
    meta_arr = jnp.stack(
        [
            jnp.asarray(window, jnp.int32).reshape(()),
            jnp.asarray(t_real, jnp.int32).reshape(()),
        ]
    )
    out = pl.pallas_call(
        functools.partial(
            _chunk_kernel, scale=scale, page_size=page_size,
            n_pages=n_pages, hkv=hkv, g=g, t_q=t_q, has_tree=has_tree,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rq, hd), q.dtype),
        interpret=interpret,
    )(
        page_table.astype(jnp.int32), lens.astype(jnp.int32), meta_arr,
        *args,
    )
    return out.reshape(b, t_q, h, hd)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "scale", "interpret"),
)
def paged_decode_attention(
    q: jax.Array,  # [B, H, hd] — one decode token per sequence
    k_slab: jax.Array,  # [S_tot, Hkv, hd] — the paged arena, one layer
    v_slab: jax.Array,
    page_table: jax.Array,  # [B, NP] i32 physical page ids (padding = 0)
    lens: jax.Array,  # [B] i32 context lengths (incl. this token)
    page_size: int,
    scale: float | None = None,
    interpret: bool = False,
    window=0,  # traced i32 scalar; 0 = full attention (per-layer in scan)
) -> jax.Array:  # [B, H, hd]
    b, h, hd = q.shape
    s_tot, hkv = k_slab.shape[0], k_slab.shape[1]
    if h % hkv:
        raise ValueError(f"H={h} must be a multiple of Hkv={hkv}")
    if s_tot % page_size:
        raise ValueError(f"arena slots {s_tot} % page_size {page_size}")
    g = h // hkv
    n_pages = page_table.shape[1]
    if scale is None:
        scale = hd**-0.5

    # arena as pages with heads folded into rows:
    # [n_phys, page_size * Hkv, hd] (free reshape of the contiguous slab)
    kp = k_slab.reshape(-1, page_size * hkv, hd)
    vp = v_slab.reshape(-1, page_size * hkv, hd)

    kv_index = _make_kv_index(page_size)

    grid = (b, n_pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, h, hd), lambda bi, j, pt, ln, wn: (bi, 0, 0)),
            pl.BlockSpec((None, page_size * hkv, hd), kv_index),
            pl.BlockSpec((None, page_size * hkv, hd), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (None, h, hd), lambda bi, j, pt, ln, wn: (bi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, hd), jnp.float32),
        ],
    )
    win_arr = jnp.asarray(window, jnp.int32).reshape(1)
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, page_size=page_size, n_pages=n_pages,
            hkv=hkv, g=g,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        interpret=interpret,
    )(
        page_table.astype(jnp.int32), lens.astype(jnp.int32), win_arr,
        q, kp, vp,
    )
    return out


def _ragged_kernel(
    pt_ref,  # [B, NP] i32 scalar prefetch: logical page j of seq b
    lens_ref,  # [B] i32 scalar prefetch (lens INCLUDE each seq's new tokens)
    win_ref,  # [1] i32 scalar prefetch: sliding window (0 = full attention)
    *refs,  # [nt_ref (has_tree)], [tree_ref (has_tree)], seq_ref, pos_ref,
    # q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr — see below
    scale: float,
    page_size: int,
    n_pages: int,
    n_seqs: int,
    hkv: int,
    g: int,
    has_tree: bool = False,
    t_max: int = 0,
):
    """Ragged mixed-batch variant of _chunk_kernel: ONE launch covers every
    member of a mixed group (N single-token decode rows + one multi-token
    prefill-chunk row-group). The grid walks (sequence, page); every grid
    step attends ALL rq query rows against sequence b's page j and masks
    rows owned by a different sequence (their online-softmax state passes
    through untouched: p = 0, corr = 1, exactly the masked-page contract of
    _online_softmax_body). Ownership and causality are per ROW — seq_ref /
    pos_ref replace _chunk_kernel's block-uniform (length, t_real) — so
    T=1 and T=chunk members coexist in one [rq, hd] block.

    Scratch persists across the WHOLE grid (init at the first step,
    finalize at the last), not per sequence: that is what lets one q block
    serve B sequences. The x B masked FLOPs are the price of fusing the
    dispatches; the HBM bytes stay one pass over every member's pages —
    the same bytes B separate kernel calls would read. No windowed
    page-skip here (the skip bound is per row, not per block); dead pages
    still predicate off their compute via page_live.

    has_tree switches the causal term into ragged TREE-verify semantics:
    nt_ref[b] is sequence b's in-step (speculative) token count — its last
    nt storage slots hold this step's linearized tree — committed keys
    (pos < length - nt) stay fully visible, and tree_ref[i, m] says whether
    query row i may attend the m-th in-step slot of its own sequence.
    Mosaic has no arbitrary 2D gather, so the per-key lookup rides the
    one-hot matmul trick from _chunk_kernel: sel one-hots each key column
    to its in-step index, tree_vis = tree_ref @ sel."""
    if has_tree:
        nt_ref, tree_ref = refs[0], refs[1]
        refs = refs[2:]
    else:
        nt_ref = tree_ref = None
    (
        seq_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    ) = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    h = hkv * g
    rows = page_size * hkv
    rq = q_ref.shape[0]

    @pl.when((b == 0) & (j == 0))
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lens_ref[b]
    win = win_ref[0]
    rk = jax.lax.broadcasted_iota(jnp.int32, (rq, rows), 1)
    rqi = jax.lax.broadcasted_iota(jnp.int32, (rq, rows), 0)
    pos = j * page_size + rk // hkv  # key position
    own = (rk % hkv) == ((rqi % h) // g)
    seq = seq_ref[...]  # [rq, 1] — broadcasts over key rows
    qpos = pos_ref[...]
    page_live = j * page_size < length

    @pl.when(page_live)
    def _update():
        q = q_ref[...].astype(jnp.float32) * scale
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [rq, rows]
        if has_tree:
            ss = length - nt_ref[b]  # first in-step storage slot of seq b
            tm = tree_ref[...].astype(jnp.float32)  # [rq, t_max]
            ti = jax.lax.broadcasted_iota(jnp.int32, (t_max, rows), 0)
            posk = (
                j * page_size
                + jax.lax.broadcasted_iota(jnp.int32, (t_max, rows), 1)
                // hkv
            )
            sel = (posk == ss + ti).astype(jnp.float32)  # [t_max, rows]
            tree_vis = jax.lax.dot_general(
                tm, sel, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [rq, rows]
            mask = own & (pos < length) & (seq == b) & (
                (pos < ss) | (tree_vis > 0.5)
            )
        else:
            mask = own & (pos < length) & (seq == b) & (pos <= qpos)
            mask &= (win <= 0) | (pos > qpos - win)
        logits = jnp.where(mask, logits, NEG)
        m = m_scr[...]
        m_new = jnp.maximum(m, logits.max(axis=1, keepdims=True))
        p = jnp.exp(logits - m_new) * mask.astype(jnp.float32)
        corr = jnp.exp(m - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when((b == n_seqs - 1) & (j == n_pages - 1))
    def _finalize():
        # rows owned by no live sequence (bucket padding: seq >= B) never
        # accumulate and divide by eps into zeros, dropped by the caller
        o_ref[...] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "scale", "interpret", "has_tree"),
)
def paged_ragged_attention(
    q: jax.Array,  # [R, H, hd] — ragged token rows across ALL members
    k_slab: jax.Array,  # [S_tot, Hkv, hd] — the paged arena, one layer
    v_slab: jax.Array,
    page_table: jax.Array,  # [B, NP] i32 physical page ids (padding = 0)
    lens: jax.Array,  # [B] i32 context lengths (incl. each seq's new tokens)
    q_seq: jax.Array,  # [R] i32 owning sequence per token (>= B = padding)
    q_pos: jax.Array,  # [R] i32 context position per token
    page_size: int,
    scale: float | None = None,
    interpret: bool = False,
    window=0,  # traced i32 scalar; 0 = full attention (per-layer in scan)
    nt: jax.Array | None = None,  # [B] i32 in-step token count (has_tree)
    tree_rows: jax.Array | None = None,  # [R, t_max] in-step visibility
    has_tree: bool = False,
) -> jax.Array:  # [R, H, hd]
    """Paged attention over a ragged mixed batch: R tokens spread unevenly
    across B sequences (decode members contribute 1 row, the prefill-chunk
    member contributes its chunk), all in ONE grid launch. Token row i
    belongs to sequence q_seq[i] at context position q_pos[i]; padding rows
    (q_seq >= B) emit zeros. has_tree switches into the ragged TREE-verify
    variant: nt rides as a fourth scalar prefetch and tree_rows (row-major
    in-step visibility, head-expanded here) as an extra VMEM input; the
    window must be 0 (tree groups gate windowed models off host-side).
    VMEM budget: caller gates on R*H rows (the executor allows <= 2048,
    mirroring paged_chunk_attention)."""
    r, h, hd = q.shape
    s_tot, hkv = k_slab.shape[0], k_slab.shape[1]
    if h % hkv:
        raise ValueError(f"H={h} must be a multiple of Hkv={hkv}")
    if s_tot % page_size:
        raise ValueError(f"arena slots {s_tot} % page_size {page_size}")
    g = h // hkv
    b = page_table.shape[0]
    n_pages = page_table.shape[1]
    if scale is None:
        scale = hd**-0.5
    rows = page_size * hkv
    rq = r * h

    kp = k_slab.reshape(-1, rows, hd)
    vp = v_slab.reshape(-1, rows, hd)
    q2 = q.reshape(rq, hd)
    # per-ROW ownership/position: each token's values repeated per head
    seq_rows = jnp.repeat(q_seq.astype(jnp.int32), h).reshape(rq, 1)
    pos_rows = jnp.repeat(q_pos.astype(jnp.int32), h).reshape(rq, 1)

    # index-map arity follows num_scalar_prefetch (3, +1 for the tree
    # variant's nt), so take the prefetch refs variadically
    def kv_index(bi, j, pt, ln, wn, *rest):
        return (pt[bi, j], 0, 0)

    def const_index(bi, j, pt, ln, wn, *rest):
        return (0, 0)

    t_max = tree_rows.shape[1] if has_tree else 0
    in_specs = [
        pl.BlockSpec((rq, 1), const_index),
        pl.BlockSpec((rq, 1), const_index),
        pl.BlockSpec((rq, hd), const_index),
        pl.BlockSpec((None, rows, hd), kv_index),
        pl.BlockSpec((None, rows, hd), kv_index),
    ]
    prefetch = [
        page_table.astype(jnp.int32), lens.astype(jnp.int32),
        jnp.asarray(window, jnp.int32).reshape(1),
    ]
    args = [seq_rows, pos_rows, q2, kp, vp]
    if has_tree:
        assert nt is not None and tree_rows is not None
        prefetch.append(nt.astype(jnp.int32))
        # per-ROW visibility: each token's tree row repeated per head,
        # mirroring seq_rows/pos_rows
        tree_rq = jnp.repeat(
            tree_rows.astype(jnp.float32), h, axis=0
        ).reshape(rq, t_max)
        in_specs.insert(0, pl.BlockSpec((rq, t_max), const_index))
        args.insert(0, tree_rq)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + int(has_tree),
        grid=(b, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rq, hd), const_index),
        scratch_shapes=[
            pltpu.VMEM((rq, 1), jnp.float32),
            pltpu.VMEM((rq, 1), jnp.float32),
            pltpu.VMEM((rq, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _ragged_kernel, scale=scale, page_size=page_size,
            n_pages=n_pages, n_seqs=b, hkv=hkv, g=g,
            has_tree=has_tree, t_max=t_max,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rq, hd), q.dtype),
        interpret=interpret,
    )(*prefetch, *args)
    return out.reshape(r, h, hd)
