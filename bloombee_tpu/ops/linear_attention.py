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
cancel catastrophically, and substitution does not care. All in float32,
matmuls at `highest` precision (a few percent of a layer's projections, and
S is a sum over thousands of positions).

`gdn_sequence` takes a longer run (a 512-row chunk is eight blocks of 64).
Only ONE term of a block reads the state it meets, and the system is linear
in its right-hand side: with T = (I + A)^-1,

    U = T beta V - (T beta diag(exp(G)) K) S_0 = U_v - W S_0.

So everything that does not read the carried state is computed ONCE for all
of the run's blocks, batched over [blocks, heads] in front of the walk
(`_gdn_block` under `jax.vmap`): the running sums and decays, A, the
decay-weighted Q K^T, the decayed K and Q, and one forward substitution
against [beta V | beta K_in] side by side, which gives U_v and W
(`_walk_blocks`; blocks and heads as ONE batch axis, which the chip's solve
lays on its lanes where it walks a leading one). The walk (`lax.scan`)
keeps what reads or writes S: U = U_v - W S, O = P U + Q_in S,
S = exp(G_C) S + K_end^T U. It is the same arithmetic with one subtraction
reassociated and the solve d_k columns wider, at an eighth of the dependent
depth: a block's products are tiny (64 x 64 x 128 a head) and eight turns of
ten small ops and a solve each, one after the other, were what a layer paid
(`gdn_rule` 6.2 ms of a Qwen3-Next chunk for needs of 0.16: PERF.md
section 6, PR 52). Substitution stays for the reason above. A run of one
block (a tail, a short chunk) is `gdn_chunk` as it stands.

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
exponential (the published kernel's form). `kda_sequence` batches and
walks as `gdn_sequence` does (`_kda_block`: both pair matrices with their
sub-block references and exact diagonals as they are; the block's decay
scales S's rows).
"""

from __future__ import annotations

import functools

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


def sequence_blocks(t: int, chunk: int, channel_decay: bool) -> int:
    """How many `chunk`-row blocks the chunk form of T rows takes in its one
    batched pass: 1 where it is a single block (`gdn_chunk` / `kda_chunk`).
    The vector-decay rule pads T to whole blocks; the scalar one takes a T
    that is no multiple of `chunk` as one block."""
    if t <= chunk or (t % chunk and not channel_decay):
        return 1
    return -(-t // chunk)


def _gdn_block(q, k, v, g, beta):
    """What ONE block of `gdn_chunk` holds that does not read the carried
    state (`jax.vmap`ped over a chunk's blocks): (A [H, C, C] strictly
    lower, beta [V | K_in] [H, C, V + K], P [H, C, C], Q_in [C, H, K],
    K_end [C, H, K], the block's whole decay [H, 1, 1])."""
    c = q.shape[0]
    cum = jnp.cumsum(g, axis=0)  # [C, H] inclusive
    diff = cum[:, None, :] - cum[None, :, :]  # [C(i), C(j), H], <= 0 on j <= i
    causal = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.exp(jnp.where(causal[:, :, None], diff, -jnp.inf))
    decay = decay.transpose(2, 0, 1)  # [H, C, C]
    kk = jnp.einsum("ihk,jhk->hij", k, k, precision=_HI)
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    a = jnp.where(strict, kk * decay * beta.T[:, :, None], 0.0)
    into = jnp.exp(cum)[..., None]  # decay from before the block to row i
    p = jnp.einsum("ihk,jhk->hij", q, k, precision=_HI) * decay
    to_end = jnp.exp(cum[-1][None, :] - cum)[..., None]
    return (
        a, _state_free_rhs(k * into, v, beta), p, q * into, k * to_end,
        jnp.exp(cum[-1])[:, None, None],
    )


def _state_free_rhs(k_in, v, beta):
    """beta [V | K_in], heads first: the two right-hand sides of a block's
    system that do not read the state, side by side."""
    rhs = jnp.concatenate([v, k_in], axis=-1) * beta[..., None]
    return rhs.transpose(1, 0, 2)


def _walk_blocks(s0, a, rhs, p, q_in, k_end, decay_end):
    """A chunk's blocks (`_gdn_block`'s or `_kda_block`'s values, each with
    a leading [blocks] axis) from the state s0 on: ONE batched forward
    substitution, (I + A) [U_v | W] = beta [V | K_in], so that a block's
    deltas are U = U_v - W S for whatever state S it meets; then the walk,
    which holds only what reads or writes S. -> (o [blocks, C, H, V], the
    state after the last block)."""
    n, h, c, _ = a.shape
    # (blocks and heads as ONE batch axis: the chip's solve walks the
    # batch's leading axes and lays only the last on its lanes)
    sol = jax.scipy.linalg.solve_triangular(
        (a + jnp.eye(c, dtype=a.dtype)).reshape(n * h, c, c),
        rhs.reshape(n * h, c, -1), lower=True, unit_diagonal=True,
    ).reshape(n, h, c, -1)  # [blocks, H, C, V + K]
    dv = s0.shape[-1]

    def body(s, block):
        u_v, w, p, q_in, k_end, decay_end = block
        u = u_v - jnp.einsum("hik,hkv->hiv", w, s, precision=_HI)
        o = jnp.einsum("hij,hjv->ihv", p, u, precision=_HI) + jnp.einsum(
            "ihk,hkv->ihv", q_in, s, precision=_HI
        )
        s = s * decay_end + jnp.einsum("jhk,hjv->hkv", k_end, u, precision=_HI)
        return s, o

    s, o = lax.scan(
        body, s0, (sol[..., :dv], sol[..., dv:], p, q_in, k_end, decay_end)
    )
    return o, s


def gdn_sequence(q, k, v, g, beta, s0, chunk: int):
    """`gdn_chunk` over T rows, `chunk` rows at a time (T a multiple of
    `chunk`, or at most one block): the triangular system stays
    [chunk, chunk], all of the blocks' systems solved in one batched pass
    (`_gdn_block`) and the state walked through them after it."""
    t = q.shape[0]
    n = sequence_blocks(t, chunk, False)
    if n == 1:
        return gdn_chunk(q, k, v, g, beta, s0)
    split = lambda z: z.reshape(n, chunk, *z.shape[1:])  # noqa: E731
    o, s = _walk_blocks(
        s0, *jax.vmap(_gdn_block)(*map(split, (q, k, v, g, beta)))
    )
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


def _kda_block(q, k, v, g, beta, sub: int):
    """`_gdn_block` with the decay a key channel: the block's decay comes
    back [H, K, 1], on the state's rows."""
    c = q.shape[0]
    cum = jnp.cumsum(g, axis=0)  # [C, H, K] inclusive, <= 0
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    a = jnp.where(strict, _kda_pairs(k, k, cum, sub), 0.0) * beta.T[:, :, None]
    into = jnp.exp(cum)  # decay from before the block to row i
    to_end = jnp.exp(cum[-1][None] - cum)  # [C, H, K]
    return (
        a, _state_free_rhs(k * into, v, beta), _kda_pairs(q, k, cum, sub),
        q * into, k * to_end, jnp.exp(cum[-1])[..., None],
    )


def kda_sequence(q, k, v, g, beta, s0, chunk: int, sub: int = KDA_SUB):
    """`kda_chunk` over T rows, `chunk` rows at a time, batched and walked
    as `gdn_sequence`. T is padded to whole blocks (whole sub-blocks under
    one block) with rows of beta = 0, g = 0, which leave the state alone;
    their outputs are cut off."""
    t = q.shape[0]
    unit = chunk if t > chunk else min(sub, t)
    pad = -t % unit
    if pad:
        q, k, v, g, beta = (
            jnp.pad(z, ((0, pad),) + ((0, 0),) * (z.ndim - 1))
            for z in (q, k, v, g, beta)
        )
    n = sequence_blocks(t, chunk, True)
    if n == 1:
        o, s = kda_chunk(q, k, v, g, beta, s0, sub)
        return o[:t], s
    split = lambda z: z.reshape(n, chunk, *z.shape[1:])  # noqa: E731
    block = functools.partial(_kda_block, sub=sub)
    o, s = _walk_blocks(s0, *jax.vmap(block)(*map(split, (q, k, v, g, beta))))
    return o.reshape(n * chunk, *o.shape[2:])[:t], s
