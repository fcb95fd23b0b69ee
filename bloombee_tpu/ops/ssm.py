"""State-space (Mamba-2 SSD) recurrence: one step, and the chunk form.

Per head h (head_dim P, state N; heads of one group share B and C):

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * outer(x_t, B_t)     S: [P, N]
    y_t = S_t @ C_t + D * x_t

`ssm_step` is that line for rows that each own a state (decode rows: the
state is read and written once a row). `ssd_chunk` is the same recurrence
over T rows of ONE sequence without a per-token loop (the SSD chunk form of
Mamba-2, arXiv:2405.21060 section 6: quadratic inside the chunk, the state
touched once each way): a per-token scan over a chunk would read and write
the [H, P, N] state T times. `ssd_sequence` walks a longer run chunk by
chunk. All in float32; the matmuls at `highest` precision (their FLOPs are
a few percent of a layer's projections, and the state is a sum over
thousands of positions).

A row with dt == 0 neither decays nor feeds the state: that is how padding
rows and bucket tails are kept out of it (the caller zeroes their dt).

`conv_taps` is the depthwise causal convolution's input for ragged rows:
each row's last K inputs, reaching back into the sequence's carried tail.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST


def ssm_step(
    x: jax.Array,  # [R, H, P] float32
    dt: jax.Array,  # [R, H] float32, after softplus; 0 = the row is skipped
    a: jax.Array,  # [H] float32, negative (-exp(A_log))
    b: jax.Array,  # [R, G, N] float32
    c: jax.Array,  # [R, G, N]
    d: jax.Array,  # [H]
    s0: jax.Array,  # [R, H, P, N] float32: each row's own state
) -> tuple[jax.Array, jax.Array]:
    """One recurrence step a row: (y [R, H, P], S [R, H, P, N])."""
    r, h, p = x.shape
    g, n = b.shape[1:]
    k = h // g
    s0 = s0.reshape(r, g, k, p, n)
    decay = jnp.exp(dt * a).reshape(r, g, k, 1, 1)
    dx = (dt[..., None] * x).reshape(r, g, k, p, 1)
    s = s0 * decay + dx * b[:, :, None, None, :]
    y = jnp.sum(s * c[:, :, None, None, :], axis=-1).reshape(r, h, p)
    return y + d[None, :, None] * x, s.reshape(r, h, p, n)


def ssd_chunk(
    x: jax.Array,  # [T, H, P] float32, one sequence's rows in order
    dt: jax.Array,  # [T, H]; 0 on rows that must not advance the state
    a: jax.Array,  # [H]
    b: jax.Array,  # [T, G, N]
    c: jax.Array,  # [T, G, N]
    d: jax.Array,  # [H]
    s0: jax.Array,  # [H, P, N] the state before the first row
) -> tuple[jax.Array, jax.Array]:
    """(y [T, H, P], the state after the last row [H, P, N])."""
    t, h, p = x.shape
    g, n = b.shape[1:]
    k = h // g
    la = dt * a  # [T, H] log decay per row, <= 0
    cum = jnp.cumsum(la, axis=0)  # inclusive
    # decay from row j (exclusive) to row i (inclusive), j <= i
    diff = cum[:, None, :] - cum[None, :, :]  # [T(i), T(j), H]
    causal = jnp.tril(jnp.ones((t, t), bool))
    decay = jnp.exp(jnp.where(causal[:, :, None], diff, -jnp.inf))
    cb = jnp.einsum("ign,jgn->ijg", c, b, precision=_HI)  # [T, T, G]
    w = (
        cb[..., None]
        * decay.reshape(t, t, g, k)
        * dt.reshape(1, t, g, k)
    )
    xg = x.reshape(t, g, k, p)
    y = jnp.einsum("ijgk,jgkp->igkp", w, xg, precision=_HI)
    # what the state before the chunk still contributes to each row
    s0g = s0.reshape(g, k, p, n)
    y = y + (
        jnp.einsum("ign,gkpn->igkp", c, s0g, precision=_HI)
        * jnp.exp(cum).reshape(t, g, k, 1)
    )
    # the state after the chunk: s0 decayed over all of it, plus every row's
    # contribution decayed from that row to the end
    to_end = jnp.exp(cum[-1][None, :] - cum)  # [T, H]
    xw = xg * (dt * to_end).reshape(t, g, k, 1)
    s = s0g * jnp.exp(cum[-1]).reshape(g, k, 1, 1) + jnp.einsum(
        "jgkp,jgn->gkpn", xw, b, precision=_HI
    )
    return (
        y.reshape(t, h, p) + d[None, :, None] * x,
        s.reshape(h, p, n),
    )


def ssd_sequence(x, dt, a, b, c, d, s0, chunk: int):
    """`ssd_chunk` over T rows, `chunk` rows at a time (T a multiple of
    `chunk`, or at most one chunk): the quadratic term stays [chunk, chunk]."""
    t = x.shape[0]
    if t <= chunk or t % chunk:
        return ssd_chunk(x, dt, a, b, c, d, s0)

    def body(s, xs):
        y, s = ssd_chunk(*xs[:2], a, *xs[2:], d, s)
        return s, y

    split = lambda z: z.reshape(t // chunk, chunk, *z.shape[1:])  # noqa: E731
    s, y = lax.scan(body, s0, (split(x), split(dt), split(b), split(c)))
    return y.reshape(t, *y.shape[2:]), s


def conv_taps(
    xbc: jax.Array,  # [R, C] the rows' convolution inputs
    tails: jax.Array,  # [S, K-1, C] each sequence's last K-1 inputs
    q_seq: jax.Array,  # [R] owning sequence of a row (>= S: padding)
    row0: jax.Array,  # [S] a sequence's first row
    nt: jax.Array,  # [S] a sequence's REAL rows in this step (0: none)
) -> tuple[jax.Array, jax.Array]:
    """(taps [R, K, C], new tails [S, K-1, C]). taps[i, k] is the input
    K-1-k positions before row i in its own sequence, read from the tail
    where that lies before this step's rows. The new tail of a sequence is
    its last K-1 inputs after `nt` real rows: a sequence with nt == 0 (a
    padding row, a member with nothing in this step) keeps its tail."""
    r = xbc.shape[0]
    s, km1 = tails.shape[:2]
    ext = jnp.concatenate([tails.reshape(s * km1, -1), xbc], axis=0)
    seq = jnp.clip(q_seq, 0, s - 1)
    pos = jnp.arange(r, dtype=jnp.int32) - row0[seq]  # position in the step
    back = jnp.arange(km1, -1, -1, dtype=jnp.int32)  # K-1 .. 0
    j = pos[:, None] - back[None, :]  # [R, K] position of tap k, < 0: tail
    rows = jnp.arange(r, dtype=jnp.int32)[:, None] - back[None, :]
    idx = jnp.where(j >= 0, s * km1 + rows, seq[:, None] * km1 + km1 + j)
    taps = ext[jnp.clip(idx, 0, ext.shape[0] - 1)]
    m = jnp.arange(km1, dtype=jnp.int32)
    jt = nt[:, None] + m[None, :] - km1  # [S, K-1]
    idx_t = jnp.where(
        jt >= 0,
        s * km1 + row0[:, None] + jt,
        jnp.arange(s, dtype=jnp.int32)[:, None] * km1 + km1 + jt,
    )
    return taps, ext[jnp.clip(idx_t, 0, ext.shape[0] - 1)]


# ------------------------------------------------------------------ Mamba-1
# The selective scan of Mamba-1 (arXiv:2312.00752), per channel c (of d_inner)
# and state column n:
#
#     S_t[n, c] = exp(dt_t[c] * A[n, c]) * S_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
#     y_t[c]    = sum_n S_t[n, c] C_t[n] + D[c] x_t[c]
#
# The decay differs by channel AND state column, so the SSD chunk form above
# (a scalar decay a head) does not apply: `mamba1_chunk` walks the tokens, the
# state read and written once (ops/pallas/selective_scan.py is the same
# recurrence with S carried in registers: a tile of 8 tokens a turn, a
# token's channels over sublanes and lanes, B_t and C_t as scalars). S and A
# are kept STATE-major [N, C] here: the channels lie along the lanes, B_t and
# C_t broadcast along them. A row with dt == 0 neither decays nor feeds S,
# exactly as `ssm_step`'s.


def mamba1_step(
    x: jax.Array,  # [R, C] float32
    dt: jax.Array,  # [R, C] float32, after softplus; 0 = the row is skipped
    a_t: jax.Array,  # [N, C] float32, negative (-exp(A_log), transposed)
    b: jax.Array,  # [R, N] float32
    c: jax.Array,  # [R, N]
    d: jax.Array,  # [C]
    s0: jax.Array,  # [R, N, C] float32: each row's own state
) -> tuple[jax.Array, jax.Array]:
    """One recurrence step a row: (y [R, C], S [R, N, C])."""
    s = jnp.exp(dt[:, None, :] * a_t[None]) * s0 + (
        (dt * x)[:, None, :] * b[:, :, None]
    )
    return jnp.sum(s * c[:, :, None], axis=1) + d[None, :] * x, s


def mamba1_chunk(x, dt, a_t, b, c, d, s0):
    """`mamba1_step` over T rows of ONE sequence in order, from the state
    s0 [N, C]: (y [T, C], the state after the last row). The plain form: the
    kernel's fallback and its test oracle."""

    def body(s, row):
        x_t, dt_t, b_t, c_t = row
        y, s = mamba1_step(
            x_t[None], dt_t[None], a_t, b_t[None], c_t[None], d, s[None]
        )
        return s[0], y[0]

    s, y = lax.scan(body, s0, (x, dt, b, c))
    return y, s
