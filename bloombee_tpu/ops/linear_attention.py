"""Gated delta rule (Gated DeltaNet linear attention): one step, and the
chunk form.

Per value head (keys of d_k, values of d_v; q and k L2-normalised, q scaled
by d_k ** -0.5 by the caller), the state S [d_k, d_v] goes token by token:

    S   = exp(g_t) * S                        g_t <= 0: the head's log decay
    u_t = beta_t * (v_t - S^T k_t)            the delta: what k_t does not
    S   = S + outer(k_t, u_t)                 already read out of S
    o_t = S^T q_t

`gdn_step` is those lines for rows that each own a state (decode rows: the
state read and written once a row). `gdn_chunk` is the same recurrence over
C rows of ONE sequence with the state touched once each way. With G the
inclusive running sum of g inside the block, unrolling the lines gives a
unit-lower-triangular system for the deltas,

    (I + tril(diag(beta) (K K^T) * exp(G_i - G_j), -1)) U
        = diag(beta) (V - diag(exp(G)) K S_0)

then  O = tril(Q K^T * exp(G_i - G_j)) U + diag(exp(G)) Q S_0
and   S_C = exp(G_C) S_0 + (diag(exp(G_C - G)) K)^T U.

The system is solved by forward substitution (`solve_triangular`), not by a
series in the strictly-lower part: keys of one direction make that series
cancel catastrophically, and substitution does not care. `gdn_sequence`
walks a longer run block by block. All in float32, matmuls at `highest`
precision (a few percent of a layer's projections, and S is a sum over
thousands of positions).

A row with beta == 0 and g == 0 neither decays nor feeds the state: that is
how padding rows and bucket tails are kept out of it (the caller zeroes
both). The convolution before the rule is `ops/ssm.py conv_taps`.

Kimi delta attention (KDA, `kda_*` below) is the same four lines with the
decay a VECTOR over the key channels, `S = diag(exp(g_t)) S`, g_t [d_k]: it
scales S's ROWS. In the chunk form the decay then sits INSIDE the
contraction, `A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)`, and the
factored product `(k_i * exp(G_i)) . (k_j * exp(-G_j))` overflows (|g| of 16
a token a channel is `exp(+1024)` over a 64-row block). `kda_chunk` never
takes the exponential of a positive number: the block is cut into
sub-blocks of `KDA_SUB` rows; a pair of sub-blocks (later I, earlier J) is
taken relative to G at I's FIRST row n, `(k_i * exp(G_i - G_n)) . (k_j *
exp(G_n - G_j))`, both exponents <= 0 (a factor that underflows is one whose
true product is smaller still); a diagonal sub-block is taken exactly, its
`exp(G_i - G_j)` over [rows, rows, d_k] masked to j <= i BEFORE the
exponential (the published kernel's form).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST


def l2_normalize(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """x / sqrt(sum(x * x) + eps) over the last axis, as the published
    `l2norm`."""
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gdn_step(
    q: jax.Array,  # [R, H, K] float32, normalised and scaled
    k: jax.Array,  # [R, H, K] float32, normalised
    v: jax.Array,  # [R, H, V] float32
    g: jax.Array,  # [R, H] float32 log decay (<= 0); 0 = the row is skipped
    beta: jax.Array,  # [R, H] float32; 0 = the row is skipped
    s0: jax.Array,  # [R, H, K, V] float32: each row's own state
) -> tuple[jax.Array, jax.Array]:
    """One rule step a row: (o [R, H, V], S [R, H, K, V])."""
    s = s0 * jnp.exp(g)[..., None, None]
    read = jnp.einsum("rhkv,rhk->rhv", s, k, precision=_HI)
    u = (v - read) * beta[..., None]
    s = s + k[..., :, None] * u[..., None, :]
    return jnp.einsum("rhkv,rhk->rhv", s, q, precision=_HI), s


def gdn_chunk(
    q: jax.Array,  # [C, H, K] float32, one sequence's rows in order
    k: jax.Array,  # [C, H, K]
    v: jax.Array,  # [C, H, V]
    g: jax.Array,  # [C, H]; 0 on rows that must not advance the state
    beta: jax.Array,  # [C, H]; 0 on those rows
    s0: jax.Array,  # [H, K, V] the state before the first row
) -> tuple[jax.Array, jax.Array]:
    """(o [C, H, V], the state after the last row [H, K, V])."""
    c = q.shape[0]
    cum = jnp.cumsum(g, axis=0)  # [C, H] inclusive
    diff = cum[:, None, :] - cum[None, :, :]  # [C(i), C(j), H], <= 0 on j <= i
    causal = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(causal[:, :, None], diff, -jnp.inf))
    decay = decay.transpose(2, 0, 1)  # [H, C, C]
    kk = jnp.einsum("ihk,jhk->hij", k, k, precision=_HI)
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    bt = beta.T  # [H, C]
    a = jnp.where(strict, kk * decay * bt[:, :, None], 0.0)
    into = jnp.exp(cum)  # [C, H] decay from before the block to row i
    k_in = k * into[..., None]
    rhs = (
        v - jnp.einsum("ihk,hkv->ihv", k_in, s0, precision=_HI)
    ) * beta[..., None]
    u = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(c, dtype=a.dtype), rhs.transpose(1, 0, 2),
        lower=True, unit_diagonal=True,
    )  # [H, C, V]
    qk = jnp.einsum("ihk,jhk->hij", q, k, precision=_HI) * decay
    o = jnp.einsum("hij,hjv->ihv", qk, u, precision=_HI) + jnp.einsum(
        "ihk,hkv->ihv", q * into[..., None], s0, precision=_HI
    )
    to_end = jnp.exp(cum[-1][None, :] - cum)  # [C, H]
    s = s0 * jnp.exp(cum[-1])[:, None, None] + jnp.einsum(
        "jhk,hjv->hkv", k * to_end[..., None], u, precision=_HI
    )
    return o, s


def gdn_sequence(q, k, v, g, beta, s0, chunk: int):
    """`gdn_chunk` over T rows, `chunk` rows at a time (T a multiple of
    `chunk`, or at most one block): the triangular system stays
    [chunk, chunk]."""
    t = q.shape[0]
    if t <= chunk or t % chunk:
        return gdn_chunk(q, k, v, g, beta, s0)

    def body(s, xs):
        o, s = gdn_chunk(*xs, s)
        return s, o

    split = lambda z: z.reshape(t // chunk, chunk, *z.shape[1:])  # noqa: E731
    s, o = lax.scan(body, s0, tuple(map(split, (q, k, v, g, beta))))
    return o.reshape(t, *o.shape[2:]), s


# ------------------------------------------------- a decay a key channel
KDA_SUB = 16  # rows of a sub-block of `kda_chunk` (exact inside, a
# reference row between)


def kda_step(
    q: jax.Array,  # [R, H, K] float32, normalised and scaled
    k: jax.Array,  # [R, H, K] float32, normalised
    v: jax.Array,  # [R, H, V] float32
    g: jax.Array,  # [R, H, K] float32 log decay a key channel (<= 0)
    beta: jax.Array,  # [R, H] float32; 0 = the row is skipped
    s0: jax.Array,  # [R, H, K, V] float32: each row's own state
) -> tuple[jax.Array, jax.Array]:
    """`gdn_step` with the decay on S's rows: (o [R, H, V], S)."""
    s = s0 * jnp.exp(g)[..., None]
    read = jnp.einsum("rhkv,rhk->rhv", s, k, precision=_HI)
    u = (v - read) * beta[..., None]
    s = s + k[..., :, None] * u[..., None, :]
    return jnp.einsum("rhkv,rhk->rhv", s, q, precision=_HI), s


def _kda_pairs(a, k, cum, sub: int):
    """sum_c a_ic k_jc exp(G_ic - G_jc) for j <= i, 0 above the diagonal:
    a, k, cum [C, H, K] (cum the inclusive running sum of g, C a multiple
    of `sub`) -> [H, C, C]. No exponent is ever positive."""
    c, h, kd = k.shape
    n = c // sub
    blocks = lambda z: z.reshape(n, sub, h, kd)  # noqa: E731
    cum_b = blocks(cum)
    ref = cum_b[:, 0]  # [n, H, K]: G at each sub-block's first row
    # the later rows, down from their sub-block's reference: i >= n_I
    a_down = blocks(a) * jnp.exp(cum_b - ref[:, None])
    # every EARLIER row, up to a sub-block's reference: j < n_I
    early = (
        jnp.arange(c)[None, :] < (jnp.arange(n) * sub)[:, None]
    )[:, :, None, None]  # [n, C, 1, 1]
    k_up = k[None] * jnp.exp(
        jnp.where(early, ref[:, None] - cum[None], -jnp.inf)
    )  # [n, C, H, K]
    off = jnp.einsum("nihc,njhc->hnij", a_down, k_up, precision=_HI)
    # the diagonal sub-blocks, exactly
    tri = jnp.tril(jnp.ones((sub, sub), bool))[None, :, :, None, None]
    w = jnp.exp(jnp.where(tri, cum_b[:, :, None] - cum_b[:, None], -jnp.inf))
    diag = jnp.sum(
        blocks(a)[:, :, None] * blocks(k)[:, None] * w, axis=-1
    )  # [n, sub(i), sub(j), H]
    eye = jnp.eye(n, dtype=diag.dtype)
    diag = jnp.einsum("nijh,nm->hnimj", diag, eye).reshape(h, c, c)
    return off.reshape(h, c, c) + diag


def kda_chunk(
    q: jax.Array,  # [C, H, K] float32, one sequence's rows in order
    k: jax.Array,  # [C, H, K]
    v: jax.Array,  # [C, H, V]
    g: jax.Array,  # [C, H, K]; 0 on rows that must not advance the state
    beta: jax.Array,  # [C, H]; 0 on those rows
    s0: jax.Array,  # [H, K, V] the state before the first row
    sub: int = KDA_SUB,
) -> tuple[jax.Array, jax.Array]:
    """`gdn_chunk` with the decay a key channel: (o [C, H, V], the state
    after the last row [H, K, V]). C is a multiple of `sub`, or one
    sub-block (`kda_sequence` pads)."""
    c = q.shape[0]
    if c % sub:
        sub = c
    cum = jnp.cumsum(g, axis=0)  # [C, H, K] inclusive, <= 0
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    a = jnp.where(strict, _kda_pairs(k, k, cum, sub), 0.0) * beta.T[:, :, None]
    into = jnp.exp(cum)  # decay from before the block to row i
    rhs = (
        v - jnp.einsum("ihk,hkv->ihv", k * into, s0, precision=_HI)
    ) * beta[..., None]
    u = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(c, dtype=a.dtype), rhs.transpose(1, 0, 2),
        lower=True, unit_diagonal=True,
    )  # [H, C, V]
    o = jnp.einsum(
        "hij,hjv->ihv", _kda_pairs(q, k, cum, sub), u, precision=_HI
    ) + jnp.einsum("ihk,hkv->ihv", q * into, s0, precision=_HI)
    to_end = jnp.exp(cum[-1][None] - cum)  # [C, H, K]
    s = s0 * jnp.exp(cum[-1])[..., None] + jnp.einsum(
        "jhk,hjv->hkv", k * to_end, u, precision=_HI
    )
    return o, s


def kda_sequence(q, k, v, g, beta, s0, chunk: int, sub: int = KDA_SUB):
    """`kda_chunk` over T rows, `chunk` rows at a time. T is padded to whole
    blocks (whole sub-blocks under one block) with rows of beta = 0, g = 0,
    which leave the state alone; their outputs are cut off."""
    t = q.shape[0]
    unit = chunk if t > chunk else min(sub, t)
    pad = -t % unit
    if pad:
        q, k, v, g, beta = (
            jnp.pad(z, ((0, pad),) + ((0, 0),) * (z.ndim - 1))
            for z in (q, k, v, g, beta)
        )
    n = t + pad
    if n <= chunk:
        o, s = kda_chunk(q, k, v, g, beta, s0, sub)
        return o[:t], s

    def body(s, xs):
        o, s = kda_chunk(*xs, s, sub)
        return s, o

    split = lambda z: z.reshape(n // chunk, chunk, *z.shape[1:])  # noqa: E731
    s, o = lax.scan(body, s0, tuple(map(split, (q, k, v, g, beta))))
    return o.reshape(n, *o.shape[2:])[:t], s
