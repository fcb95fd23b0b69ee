"""Attention through the latent cache (MLA, absorbed form): Pallas TPU kernels.

With the key/value up-projection absorbed into the query and the output
(runtime/layer_body.py `mla_absorb`), latent attention is multi-QUERY
attention over one shared "head": every query head of every row scores the
same cached rows, a latent `c` [kv_rank] and a rotary key `pe` [rope_dim] a
token, and its values are the latents themselves:

    score = q_lat . c + q_pe . pe ;  o_lat = softmax(score * scale) @ c

So rows and heads flatten into ONE matrix of queries and both kernels are a
plain flash loop of two MXU products a block, never holding scores for a
whole context nor per-head keys and values.

`paged_decode_attention_latent`: one token a sequence. Streams latent pages
straight out of the paged arena, `pages_per_step` pages a grid step (a
16-token latent page is 18 KB: the slab is handed to the call that many
times, each operand steered to its own page by the page table), pages past
a sequence's length costing neither DMA nor compute.

`latent_flash_attention`: a chunk of ONE sequence over its gathered latent
context (the gather is 1,152 B a token: 14 MB at 12k tokens, against the
chunk's gigaflops). Queries come HEAD-major [H, T, C], as the absorb
product leaves them, blocked a few heads x up to 512 tokens; causal by
position, key blocks above a query block's last position skipped.

`latent_attend_dense` is the same mathematics in plain XLA for programs in
which no kernel may run (off the TPU, under a mesh, after a kernel
fallback); it holds [rows, heads, context] scores, so it serves tests and
short contexts, not a 12k-token chunk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
_VMEM_LIMIT = 64 * 2**20


def _flash_update(ql, qp, c, pe, mask, scale, m_scr, l_scr, acc_scr):
    """One block of keys into the online-softmax state: ql [Q, C], qp
    [Q, R], c [K, C], pe [K, R], mask [Q, K]."""
    contract_last = (((1,), (1,)), ((), ()))
    logits = (
        jax.lax.dot_general(
            ql, c, contract_last, preferred_element_type=jnp.float32
        )
        + jax.lax.dot_general(
            qp, pe, contract_last, preferred_element_type=jnp.float32
        )
    ) * scale
    logits = jnp.where(mask, logits, NEG)
    m = m_scr[...]
    m_new = jnp.maximum(m, logits.max(axis=1, keepdims=True))
    p = jnp.exp(logits - m_new) * mask.astype(jnp.float32)
    corr = jnp.exp(m - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new


def _init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _finish(o_ref, l_scr, acc_scr):
    # a row that saw no key (a padding row) divides by eps and emits zeros
    o_ref[...] = (
        acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
    ).astype(o_ref.dtype).reshape(o_ref.shape)


def _decode_kernel(
    pt_ref, lens_ref,  # scalar prefetch: [B, NP] page table, [B] lengths
    ql_ref, qp_ref,  # [H, C], [H, R]: every head of this sequence's token
    *rest,  # pps latent pages [ps, C], pps rotary pages [ps, R], then the
    # output [H, C] and the scratch m, l [H, 1], acc [H, C]
    scale: float, page_size: int, n_steps: int, pps: int,
):
    c_refs, pe_refs = rest[:pps], rest[pps : 2 * pps]
    o_ref, m_scr, l_scr, acc_scr = rest[2 * pps :]
    b, j = pl.program_id(0), pl.program_id(1)
    keys = pps * page_size

    @pl.when(j == 0)
    def _():
        _init(m_scr, l_scr, acc_scr)

    length = lens_ref[b]

    @pl.when(j * keys < length)
    def _():
        c = jnp.concatenate([r[...] for r in c_refs], axis=0)
        pe = jnp.concatenate([r[...] for r in pe_refs], axis=0)
        pos = j * keys + jax.lax.broadcasted_iota(
            jnp.int32, (ql_ref.shape[0], keys), 1
        )
        _flash_update(
            ql_ref[...], qp_ref[...], c, pe, pos < length, scale,
            m_scr, l_scr, acc_scr,
        )

    @pl.when(j == n_steps - 1)
    def _():
        _finish(o_ref, l_scr, acc_scr)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "scale", "pages_per_step", "interpret"),
)
def paged_decode_attention_latent(
    q_lat: jax.Array,  # [B, H, C]: queries through W_kvb's key half
    q_pe: jax.Array,  # [B, H, R]: their rotary part
    c_slab: jax.Array,  # [S_tot, C]: the latent arena (flat over layers)
    pe_slab: jax.Array,  # [S_tot, R]: the rotary-key arena
    page_table: jax.Array,  # [B, NP] i32 page ids into the slabs
    lens: jax.Array,  # [B] i32 context lengths, this token included
    page_size: int,
    scale: float,
    pages_per_step: int = 8,
    interpret: bool = False,
) -> jax.Array:  # [B, H, C] in the latent's space
    b, h, c = q_lat.shape
    r = q_pe.shape[-1]
    n_pages = page_table.shape[1]
    pps = min(pages_per_step, n_pages)
    while n_pages % pps:
        pps -= 1
    n_steps = n_pages // pps
    cp = c_slab.reshape(-1, page_size, c)
    pp = pe_slab.reshape(-1, page_size, r)

    def page_index(n):
        # a step past the sequence's last live page re-names that page, so
        # its DMA is elided (and its compute skipped in the kernel)
        def index(bi, j, pt, ln):
            last = jnp.maximum(ln[bi] - 1, 0) // page_size
            return (pt[bi, jnp.minimum(j * pps + n, last)], 0, 0)

        return index

    q_index = lambda bi, j, pt, ln: (bi, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_steps),
        in_specs=[
            pl.BlockSpec((None, h, c), q_index),
            pl.BlockSpec((None, h, r), q_index),
            *(pl.BlockSpec((None, page_size, c), page_index(n))
              for n in range(pps)),
            *(pl.BlockSpec((None, page_size, r), page_index(n))
              for n in range(pps)),
        ],
        out_specs=pl.BlockSpec((None, h, c), q_index),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, c), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _decode_kernel, scale=scale, page_size=page_size,
            n_steps=n_steps, pps=pps,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, c), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_decode_attention_latent",
    )(
        page_table.astype(jnp.int32), lens.astype(jnp.int32),
        q_lat, q_pe, *([cp] * pps), *([pp] * pps),
    )


def _flash_kernel(
    info_ref,  # scalar prefetch [3]: position of row 0, visible keys, real rows
    ql_ref, qp_ref,  # [bh, bt, C], [bh, bt, R]: bh heads x bt tokens
    c_ref, pe_ref,  # [block_k, C], [block_k, R]
    o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, block_k: int, n_k: int,
):
    tj, kj = pl.program_id(1), pl.program_id(2)
    start, length, n_real = info_ref[0], info_ref[1], info_ref[2]
    bh, bt, c = ql_ref.shape
    rows = bh * bt

    @pl.when(kj == 0)
    def _():
        _init(m_scr, l_scr, acc_scr)

    tok0 = tj * bt
    q_max = start + tok0 + bt - 1
    visible = (kj * block_k < length) & (kj * block_k <= q_max) & (tok0 < n_real)

    @pl.when(visible)
    def _():
        q_pos = start + tok0 + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0
        ) % bt
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1
        )
        mask = (k_pos < length) & (k_pos <= q_pos)
        _flash_update(
            ql_ref[...].reshape(rows, c),
            qp_ref[...].reshape(rows, qp_ref.shape[-1]),
            c_ref[...], pe_ref[...], mask, scale, m_scr, l_scr, acc_scr,
        )

    @pl.when(kj == n_k - 1)
    def _():
        _finish(o_ref, l_scr, acc_scr)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_rows", "block_k", "interpret"),
)
def latent_flash_attention(
    q_lat: jax.Array,  # [H, T, C] one sequence's chunk, HEAD-major (as the
    # absorb product leaves it: the head is its batch dimension)
    q_pe: jax.Array,  # [H, T, R]
    c_ctx: jax.Array,  # [S, C] the sequence's latent rows, by position
    pe_ctx: jax.Array,  # [S, R]
    start,  # i32: position of row 0
    length,  # i32: visible keys (the chunk's own included)
    n_real,  # i32: rows that are real (a bucket's tail is skipped)
    scale: float,
    block_rows: int = 2048,  # queries a block: heads x tokens
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:  # [H, T, C]
    h, t, c = q_lat.shape
    r = q_pe.shape[-1]
    s = c_ctx.shape[0]
    t_pad = -t % 16  # whole sublane tiles of a 16-bit row block
    if t_pad:
        q_lat = jnp.pad(q_lat, ((0, 0), (0, t_pad), (0, 0)))
        q_pe = jnp.pad(q_pe, ((0, 0), (0, t_pad), (0, 0)))
    tt = t + t_pad
    bt = min(tt, 512)
    while tt % bt:
        bt //= 2
    bh = max(1, min(h, block_rows // bt))
    while h % bh:
        bh -= 1
    block_k = min(block_k, s)
    if s % block_k:
        raise ValueError(f"context {s} % block_k {block_k}")
    n_k = s // block_k

    def k_index(hj, tj, kj, info):
        # blocks past what this query block may see re-name the last one
        # that it does: no DMA for them
        q_max = info[0] + tj * bt + bt - 1
        last = jnp.maximum(jnp.minimum(info[1] - 1, q_max), 0) // block_k
        return (jnp.minimum(kj, last), 0)

    q_index = lambda hj, tj, kj, info: (hj, tj, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h // bh, tt // bt, n_k),
        in_specs=[
            pl.BlockSpec((bh, bt, c), q_index),
            pl.BlockSpec((bh, bt, r), q_index),
            pl.BlockSpec((block_k, c), k_index),
            pl.BlockSpec((block_k, r), k_index),
        ],
        out_specs=pl.BlockSpec((bh, bt, c), q_index),
        scratch_shapes=[
            pltpu.VMEM((bh * bt, 1), jnp.float32),
            pltpu.VMEM((bh * bt, 1), jnp.float32),
            pltpu.VMEM((bh * bt, c), jnp.float32),
        ],
    )
    info = jnp.stack([
        jnp.asarray(x, jnp.int32).reshape(()) for x in (start, length, n_real)
    ])
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, block_k=block_k, n_k=n_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((h, tt, c), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="latent_flash_attention",
    )(info, q_lat, q_pe, c_ctx, pe_ctx)
    return out[:, :t] if t_pad else out


def latent_attend_dense(
    q_lat: jax.Array,  # [N, H, T, C]: N sequences, T rows each, head-major
    q_pe: jax.Array,  # [N, H, T, R]
    c_ctx: jax.Array,  # [N, S, C]
    pe_ctx: jax.Array,  # [N, S, R]
    q_pos: jax.Array,  # [N, T] positions of the rows
    lens: jax.Array,  # [N] visible keys
    scale: float,
) -> jax.Array:  # [N, H, T, C]
    """The kernels' mathematics in plain XLA (module docstring)."""
    logits = (
        jnp.einsum("nhtc,nsc->nhts", q_lat, c_ctx,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("nhtr,nsr->nhts", q_pe, pe_ctx,
                     preferred_element_type=jnp.float32)
    ) * scale
    key_pos = jnp.arange(c_ctx.shape[1], dtype=jnp.int32)
    mask = (key_pos[None, None, :] <= q_pos[:, :, None]) & (
        key_pos[None, None, :] < lens[:, None, None]
    )
    logits = jnp.where(mask[:, None, :, :], logits, NEG)
    probs = jax.nn.softmax(logits, axis=-1).astype(c_ctx.dtype)
    return jnp.einsum("nhts,nsc->nhtc", probs, c_ctx)
