"""The span step of a SambaY stack (phi4flash, arXiv:2507.06607): Mamba-1
layers among window layers, ONE full layer whose K/V pages seven
cross-attention layers read, gated memory units that read the last Mamba
layer's scan, differential attention, no positional encoding.

A span is up to three RUNS of (mixer, attention) pairs (models/layout.py
`split_sambay`), each its own `lax.scan` over its pairs' stacks, the hidden
rows, both flat arenas and the recurrent state handed from run to run:

  a   (mamba, window attention) pairs: a state row and a K/V row a pair
  b   the (mamba, full attention) pair: also hands on `m`, the scan's output
      before its gate [rows, d_inner], and its K/V row to run c
  c   (gated memory unit, cross attention) pairs: NO cache of their own. A
      GMU reads `m` for the same rows; a cross layer has a query and an
      output projection only and attends run b's K/V row, causally.

Run c writes nothing, so its output matters only at the rows the caller
reads. The step takes those rows' indices in its plan (`cross_idx`,
`n_cross`) and has THREE exits it picks between AT RUN TIME (one program a
shape: a warm-up that reads every row and a `generate` that reads the last
compile the same program): no reply row, it ends after run b (the
architecture's published prefill: YOCO's early exit, exact); a few, run c
runs on those rows gathered (each one decode-like query against the shared
pages); more than the block holds, on every row (the chunk form). Rows that
are not reply rows come back as zeros. A decode step (one row a sequence)
runs run c on all of its rows and has no switch.

Everything works on FLAT rows plus `SsmRows` (runtime/layer_body.py): the
packed decode step, the solo chunk and the fused ragged pack are one path.

Differential attention through the kernels that exist, in ONE call at
head_dim 128: K heads pair up (k1, k2) and V is taken as heads of 2 x 64, so
the arena's row [kv pairs, 128] is the natural reshape of the published
[2 * pairs, 64] for K and V alike. A query half q_j goes in as a 128-wide
head with zeros in the other half ([q1 | 0], [0 | q2]): its scores against
[k1 | k2] are q_j . k_j, and its output softmax(.) [v1 | v2] is the a_j the
published form concatenates from two calls. Under plain GQA query half
2p + j reads K/V pair p // 2, as published. The queries go in as float32 so
the two softmaxes come back unrounded before they are subtracted.

At the published widths the K/V arena is stored FOLDED, a layer's slab
[tokens * pairs, 128] (kv/arena.py `folds`): 10 pairs are no whole number of
sublane tiles, so [tokens, 10, 128] is laid out with the 10 padded to 16 and
the kernels' view of a page as [16 * 10, 128] rows would be a copy of the
whole arena. The step addresses either layout through the arena's own
helpers (`arena_write`, `gather_pages`, `heads_view`), as every family's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bloombee_tpu.kv.arena import (
    arena_tokens,
    arena_write,
    flat_arena,
    gather_pages,
    heads_view,
    layer_pages,
    layer_slots,
    layer_state_slots,
    stacked_arena,
)
from bloombee_tpu.models.layout import split_sambay
from bloombee_tpu.models.spec import ModelSpec
from bloombee_tpu.ops.ssm import conv_taps, mamba1_chunk, mamba1_step
from bloombee_tpu.runtime.layer_body import (
    SsmRows,
    _chunk_pages,
    _finish_layer,
    _norm,
    _place_rows,
    _proj,
    _window_rows,
    attend_ragged,
    attn_scale,
)
from bloombee_tpu.utils import env

F32 = jnp.float32
CROSS_BLOCK = 8  # reply rows the gathered cross-decoder holds, at least


def cross_block(rows: int, n_seqs: int) -> int:
    """Rows of the gathered cross-decoder's block for a step of `rows` row
    buckets and `n_seqs` sequence buckets: a reply row a sequence fits."""
    return min(rows, max(CROSS_BLOCK, n_seqs))


# --------------------------------------------------------------- the mixers
def _mamba_mixer(spec: ModelSpec, params: dict, x, state: dict, slots,
                 rows: SsmRows, kernels: bool):
    """The Mamba-1 mixer on flat rows: x [R, D] (the layer's normed input)
    -> (the mixer's output [R, D], the scan's output before its gate
    [R, d_inner] float32, the state arena). `state` is the flat state arena,
    `slots` [S] each sequence's row of it for THIS layer (out of range: read
    clamped, write dropped). A sequence with one row takes one recurrence
    step, one with more the chunk form; rows past a sequence's real count
    get dt = 0, which neither decays nor feeds S, and are left out of the
    convolution's new tail."""
    mb = spec.mamba
    r = x.shape[0]
    s = rows.row0.shape[0]
    c, n = mb.d_inner, mb.state
    with jax.named_scope("mamba_proj"):
        xz = _proj(x, params, "mamba_in_proj")
        xs_in, z = xz[:, :c], xz[:, c:]
    with jax.named_scope("state_io"):
        tails = state["conv"].at[slots].get(mode="clip")
        tails = jnp.where(rows.fresh[:, None, None], 0, tails)
    with jax.named_scope("mamba_conv"):
        taps, new_tails = conv_taps(
            xs_in, tails, rows.q_seq, rows.row0, rows.nt
        )
        conv = jax.nn.silu(jnp.einsum(
            "rkc,kc->rc", taps.astype(F32), params["mamba_conv_w"].astype(F32)
        ) + params["mamba_conv_b"].astype(F32))
    with jax.named_scope("mamba_proj"):
        # x_proj is stored with zero columns up to whole lanes; the product
        # is cut after the barrier (models/layout.py, as Falcon-H1's in_proj)
        dbc = lax.optimization_barrier(
            _proj(conv.astype(x.dtype), params, "mamba_x_proj")
        )
        # (`_proj` adds `mamba_dt_bias`, float32, as it adds every bias)
        dt = jax.nn.softplus(
            _proj(dbc[:, : mb.dt_rank], params, "mamba_dt_proj").astype(F32)
        )
        bm = dbc[:, mb.dt_rank : mb.dt_rank + n].astype(F32)
        cm = dbc[:, mb.dt_rank + n : mb.dt_rank + 2 * n].astype(F32)
    a_t, d_skip = params["mamba_a_t"], params["mamba_d"]
    y = jnp.zeros((r, c), F32)
    oob = state["ssm"].shape[0]
    writes = []  # (slots [k], states [k, N, C])
    if rows.step_form:
        one = rows.nt == 1
        at = jnp.clip(rows.row0, 0, r - 1)
        with jax.named_scope("state_io"):
            s0 = state["ssm"].at[slots].get(mode="clip")
            s0 = jnp.where(rows.fresh[:, None, None], 0.0, s0)
        with jax.named_scope("mamba_scan"):
            y_s, s_new = mamba1_step(
                conv[at], jnp.where(one[:, None], dt[at], 0.0), a_t, bm[at],
                cm[at], d_skip, s0,
            )
            y = y.at[jnp.where(one, rows.row0, r)].set(y_s, mode="drop")
        writes.append((jnp.where(one, slots, oob), s_new))
    w = rows.window
    whole = s == 1 and w == r  # static: one sequence owns every row
    for i in range(rows.chunk_seqs.shape[0]):
        q = rows.chunk_seqs[i]
        r0, n_q, slot_q = rows.row0[q], rows.nt[q], slots[q]
        with jax.named_scope("state_io"):
            s0 = state["ssm"].at[slot_q].get(mode="clip")
            s0 = jnp.where(rows.fresh[q], 0.0, s0)
        with jax.named_scope("mamba_scan"):
            def take(z_rows):
                return z_rows if whole else _window_rows(z_rows, r0, w)

            valid = (jnp.arange(w, dtype=jnp.int32) < n_q)[:, None]
            args = (take(conv), jnp.where(valid, take(dt), 0.0), a_t,
                    take(bm), take(cm), d_skip, s0)
            if kernels:
                from bloombee_tpu.ops.pallas.selective_scan import (
                    selective_scan,
                )

                y_q, s_q = selective_scan(
                    *args, interpret=env.get("BBTPU_PAGED_INTERPRET")
                )
            else:
                y_q, s_q = mamba1_chunk(*args)
            y_q = jnp.where(valid, y_q, 0.0)
            y = y + (y_q if whole else _place_rows(y_q, r0, r))
        writes.append((slot_q[None], s_q[None]))
    with jax.named_scope("state_io"):
        ssm_arena = state["ssm"]
        for slots_w, s_w in writes:
            ssm_arena = ssm_arena.at[slots_w].set(s_w, mode="drop")
        state = {
            "ssm": ssm_arena,
            "conv": state["conv"].at[slots].set(
                new_tails.astype(state["conv"].dtype), mode="drop"
            ),
        }
    with jax.named_scope("mamba_proj"):
        out = _proj(
            (y * jax.nn.silu(z.astype(F32))).astype(x.dtype), params,
            "mamba_out_proj",
        )
    return out, y, state


def _gmu(params: dict, x, m):
    """A gated memory unit: x [C, D] (the layer's normed input), m
    [C, d_inner] float32 (the shared scan's output for the same rows)."""
    with jax.named_scope("gmu"):
        gate = jax.nn.silu(_proj(x, params, "gmu_in_proj").astype(F32))
        return _proj((m * gate).astype(x.dtype), params, "gmu_out_proj")


# ------------------------------------------------- differential attention
def _query_halves(spec: ModelSpec, params: dict, x):
    """x [R, D] -> the query halves as 128-wide heads [R, H, 128] in the
    projection's type: half j of a pair in lanes j * 64 .., zeros in the
    other half. Who attends with them widens them where its output takes
    the queries' type (the paged decode kernel, the dense path); a chunk's
    flash call takes them as they are."""
    r = x.shape[0]
    h, hd = spec.num_attention_heads, spec.head_dim
    q = _proj(x, params, "q_proj").reshape(r, h // 2, 2, hd // 2)
    zeros = jnp.zeros_like(q[:, :, 0])
    return jnp.stack([
        jnp.concatenate([q[:, :, 0], zeros], -1),
        jnp.concatenate([zeros, q[:, :, 1]], -1),
    ], axis=2).reshape(r, h, hd)


def _diff_out(spec: ModelSpec, params: dict, a, dtype):
    """a [R, H, 128] float32 (a_1, a_2 of every pair) -> [R, D] through the
    subtraction, the sub-norm and o_proj."""
    with jax.named_scope("attn_proj"):
        r, h, hd = a.shape
        a = a.reshape(r, h // 2, 2, hd)
        lam, lam_init = params["attn_lambda"][0], params["attn_lambda"][1]
        o = a[:, :, 0] - lam * a[:, :, 1]
        o = o * lax.rsqrt(
            jnp.mean(o * o, axis=-1, keepdims=True) + spec.rms_norm_eps
        )
        o = o * params["attn_subln"].astype(F32) * (1.0 - lam_init)
        return _proj(o.reshape(r, -1).astype(dtype), params, "o_proj")


def _diff_attend(spec: ModelSpec, page_size: int, q, k_slab, v_slab,
                 page_table, q_pos, total_lens, rows: SsmRows, window: int,
                 kernels: bool):
    """q [R, H, 128] in the projection's type against the slabs' pages,
    sequence by sequence as `rows` tells them apart: -> [R, H, 128] float32,
    for the differential subtraction. A sequence with one row streams its
    pages through the paged decode kernel on float32 queries (pages below
    its window skipped whole); one with more runs the flash kernel over its
    gathered pages with the queries as they are and a float32 output, under
    a window only the pages its rows' windows span.
    Without kernels: dense scores over every sequence's pages."""
    r, h, hd = q.shape
    kvh = spec.num_key_value_heads
    scale = attn_scale(spec)
    if not kernels:
        with jax.named_scope("arena_gather"):
            k_ctx = gather_pages(
                k_slab, page_table, page_size, kvh).astype(F32)
            v_ctx = gather_pages(
                v_slab, page_table, page_size, kvh).astype(F32)
        with jax.named_scope("attention"):
            return attend_ragged(
                spec, q.astype(F32), k_ctx, v_ctx, q_pos, rows.q_seq,
                total_lens, jnp.int32(window),
            )
    from bloombee_tpu.ops.pallas.flash_attention import (
        flash_attention,
        flash_takes,
    )
    from bloombee_tpu.ops.pallas.paged_attention import paged_decode_attention

    out = jnp.zeros((r, h, hd), F32)
    if rows.step_form:
        one = rows.nt == 1
        at = jnp.clip(rows.row0, 0, r - 1)
        with jax.named_scope("attention"):
            o = paged_decode_attention(
                q[at].astype(F32), heads_view(k_slab, kvh),
                heads_view(v_slab, kvh),
                page_table, jnp.where(one, total_lens, 0),
                page_size=page_size,
                scale=scale, interpret=env.get("BBTPU_PAGED_INTERPRET"),
                window=jnp.int32(window),
            )
        out = out.at[jnp.where(one, rows.row0, r)].set(o, mode="drop")
    w = rows.window
    whole = rows.row0.shape[0] == 1 and w == r
    for i in range(rows.chunk_seqs.shape[0]):
        c = rows.chunk_seqs[i]
        r0, n_c = rows.row0[c], rows.nt[c]
        q_c = q if whole else _window_rows(q, r0, w)
        start = q_pos[jnp.clip(r0, 0, r - 1)]
        pages, p0 = _chunk_pages(page_table[c], start, w, window, page_size)
        with jax.named_scope("arena_gather"):
            k_ctx = gather_pages(k_slab, pages[None], page_size, kvh)
            v_ctx = gather_pages(v_slab, pages[None], page_size, kvh)
        shift = p0 * page_size
        with jax.named_scope("attention"):
            if flash_takes(w, k_ctx.shape[1]):
                o_c = flash_attention(
                    q_c[None], k_ctx.astype(q.dtype), v_ctx.astype(q.dtype),
                    causal=True, scale=scale, starts=(start - shift)[None],
                    lens=(total_lens[c] - shift)[None], window=window,
                    out_dtype=F32,
                    interpret=env.get("BBTPU_FLASH_INTERPRET"),
                )[0]
            else:  # a bucket under the flash kernel's tile (a short tail)
                o_c = attend_ragged(
                    spec, q_c.astype(F32), k_ctx.astype(F32),
                    v_ctx.astype(F32),
                    start - shift + jnp.arange(w, dtype=jnp.int32),
                    jnp.zeros((w,), jnp.int32),
                    (total_lens[c] - shift)[None], jnp.int32(window),
                )
        real = (jnp.arange(w, dtype=jnp.int32) < n_c)[:, None, None]
        o_c = jnp.where(real, o_c, 0.0)
        out = out + (o_c if whole else _place_rows(o_c, r0, r))
    return out


def _self_attention(spec, page_size, params, x, k_slab, v_slab, slots,
                    page_table, q_pos, total_lens, rows, window, kernels):
    """A window or full layer's attention on flat rows: x [R, D] ->
    ([R, D] after o_proj, the two slabs with the rows' K and V written)."""
    r = x.shape[0]
    kvh, hd = spec.num_key_value_heads, spec.head_dim
    with jax.named_scope("attn_proj"):
        q = _query_halves(spec, params, x)
        k = _proj(x, params, "k_proj").reshape(r, kvh, hd)
        v = _proj(x, params, "v_proj").reshape(r, kvh, hd)
    with jax.named_scope("arena_write"):
        k_slab, v_slab = arena_write(k_slab, v_slab, slots, k, v)
    a = _diff_attend(
        spec, page_size, q, k_slab, v_slab, page_table, q_pos, total_lens,
        rows, window, kernels,
    )
    return _diff_out(spec, params, a, x.dtype), k_slab, v_slab


def _cross_attention(spec, page_size, params, x, k_slab, v_slab, page_table,
                     q_pos, q_seq, total_lens, kernels):
    """A cross layer's attention on GATHERED rows: x [C, D], each row one
    query at position q_pos [C] of sequence q_seq [C] (>= S: a padding row)
    against the shared layer's pages, causally: -> [C, D] after o_proj."""
    with jax.named_scope("cross_attention"):
        with jax.named_scope("attn_proj"):
            q = _query_halves(spec, params, x).astype(F32)
        n_seqs = page_table.shape[0]
        kvh = spec.num_key_value_heads
        real = (q_seq >= 0) & (q_seq < n_seqs)
        if kernels:
            from bloombee_tpu.ops.pallas.paged_attention import (
                paged_decode_attention,
            )

            with jax.named_scope("attention"):
                a = paged_decode_attention(
                    q, heads_view(k_slab, kvh), heads_view(v_slab, kvh),
                    page_table[jnp.clip(q_seq, 0, n_seqs - 1)],
                    jnp.where(real, q_pos + 1, 0), page_size=page_size,
                    scale=attn_scale(spec),
                    interpret=env.get("BBTPU_PAGED_INTERPRET"),
                )
        else:
            with jax.named_scope("arena_gather"):
                k_ctx = gather_pages(
                    k_slab, page_table, page_size, kvh).astype(F32)
                v_ctx = gather_pages(
                    v_slab, page_table, page_size, kvh).astype(F32)
            with jax.named_scope("attention"):
                a = attend_ragged(
                    spec, q, k_ctx, v_ctx, q_pos, q_seq, total_lens,
                    jnp.int32(0),
                )
        return _diff_out(spec, params, a, x.dtype)


# --------------------------------------------------------------- the layers
def _layer(spec, params, hidden, x, mix):
    """Residual, second norm and MLP around a layer's mixer output."""
    return _finish_layer(spec, params, hidden, x, mix, None, None)[0]


def _mamba_layer(spec, params, hidden, state, slots, rows, kernels):
    x = _norm(hidden, params, "input_layernorm", spec)
    mix, y, state = _mamba_mixer(spec, params, x, state, slots, rows, kernels)
    return _layer(spec, params, hidden, x, mix), y, state


def _attention_layer(spec, page_size, params, hidden, k_slab, v_slab, slots,
                     page_table, q_pos, total_lens, rows, window, kernels):
    x = _norm(hidden, params, "input_layernorm", spec)
    mix, k_slab, v_slab = _self_attention(
        spec, page_size, params, x, k_slab, v_slab, slots, page_table, q_pos,
        total_lens, rows, window, kernels,
    )
    return _layer(spec, params, hidden, x, mix), k_slab, v_slab


# ------------------------------------------------------------------ the span
def sambay_span(
    spec: ModelSpec,
    page_size: int,
    stacked_params: dict,
    hidden: jax.Array,  # [R, D] flat rows
    arena_k: jax.Array,  # [kv rows, S_tot * pairs, 128] folded, or
    # [kv rows, S_tot, pairs, head_dim] (kv/arena.py `folds`)
    arena_v: jax.Array,
    state: dict,  # {"ssm": [state rows, slots, N, C], "conv": ...}
    slots: jax.Array,  # [R] (out of range: a padding row)
    page_table: jax.Array,  # [S, NP]
    q_pos: jax.Array,  # [R]
    total_lens: jax.Array,  # [S]
    rows: SsmRows,
    state_slots: jax.Array,  # [S]
    cross: tuple | None,  # (cross_idx [R], n_cross scalar): the rows the
    # caller reads, first n_cross entries real; None: every row (a decode
    # step, whose rows are one a sequence)
    kernels: bool,
):
    """All of a span's layers over one step's flat rows: (out [R, D] (zeros
    at rows that are no reply rows, where the span ends with the
    cross-decoder), arena_k, arena_v, state)."""
    runs = split_sambay(stacked_params)
    r = hidden.shape[0]
    kv_layers = arena_k.shape[0]
    s_tot = arena_tokens(arena_k, spec.num_key_value_heads)
    num_pages = s_tot // page_size
    state_layers, num_state_slots = state["ssm"].shape[:2]
    k_flat, v_flat = flat_arena(arena_k), flat_arena(arena_v)
    state_flat = flat_arena(state)

    def pair(carry, row, mixers_l, attn_l, window):
        h, k_flat, v_flat, state_flat = carry
        h, y, state_flat = _mamba_layer(
            spec, mixers_l, h, state_flat,
            layer_state_slots(state_slots, row, num_state_slots, state_layers),
            rows, kernels,
        )
        h, k_flat, v_flat = _attention_layer(
            spec, page_size, attn_l, h, k_flat, v_flat,
            layer_slots(slots, row, s_tot, kv_layers),
            layer_pages(page_table, row, num_pages), q_pos, total_lens, rows,
            window, kernels,
        )
        return (h, k_flat, v_flat, state_flat), y

    carry = (hidden, k_flat, v_flat, state_flat)
    row = 0
    if "a" in runs:
        mixers, attn = runs["a"]
        n_a = jax.tree.leaves(mixers)[0].shape[0]

        def body_a(carry, xs):
            p, mixers_l, attn_l = xs
            return pair(
                carry, p, mixers_l, attn_l, spec.sliding_window
            )[0], None

        carry, _ = lax.scan(
            body_a, carry, (jnp.arange(n_a, dtype=jnp.int32), mixers, attn)
        )
        row = n_a
    out = carry[0]
    if "b" in runs:
        mixers, attn = (jax.tree.map(lambda a: a[0], t) for t in runs["b"])
        carry, m = pair(carry, row, mixers, attn, 0)
        h = carry[0]
        gmus, crosses = runs["c"]
        k_flat, v_flat = carry[1], carry[2]
        shared_pages = layer_pages(page_table, row, num_pages)

        def cross_decoder(h_c, m_c, attend):
            """Run c over some rows: `attend(params_l, x)` is the cross
            layer's attention for them."""

            def body_c(h_c, xs):
                gmu_l, cross_l = xs
                x = _norm(h_c, gmu_l, "input_layernorm", spec)
                h_c = _layer(spec, gmu_l, h_c, x, _gmu(gmu_l, x, m_c))
                x = _norm(h_c, cross_l, "input_layernorm", spec)
                return _layer(spec, cross_l, h_c, x, attend(cross_l, x)), None

            return lax.scan(body_c, h_c, (gmus, crosses))[0]

        def gathered(idx):
            """Run c on the rows `idx` [C] (out of range: padding)."""
            at = jnp.clip(idx, 0, r - 1)
            real = (idx >= 0) & (idx < r)
            seq_c = jnp.where(real, rows.q_seq[at], page_table.shape[0])
            pos_c = q_pos[at]
            h_c = cross_decoder(
                h[at], m[at],
                lambda p, x: _cross_attention(
                    spec, page_size, p, x, k_flat, v_flat, shared_pages,
                    pos_c, seq_c, total_lens, kernels,
                ),
            )
            return h_c, jnp.where(real, idx, r)

        def every_row():
            def attend(p, x):
                with jax.named_scope("cross_attention"):
                    with jax.named_scope("attn_proj"):
                        q = _query_halves(spec, p, x)
                    a = _diff_attend(
                        spec, page_size, q, k_flat, v_flat, shared_pages,
                        q_pos, total_lens, rows, 0, kernels,
                    )
                    return _diff_out(spec, p, a, x.dtype)

            return cross_decoder(h, m, attend)

        if cross is None:
            out = gathered(jnp.arange(r, dtype=jnp.int32))[0]
        else:
            cross_idx, n_cross = cross
            block = cross_block(r, rows.row0.shape[0])

            def few():
                h_c, idx = gathered(cross_idx[:block])
                return jnp.zeros_like(h).at[idx].set(h_c, mode="drop")

            def all_rows():
                keep = jnp.zeros((r + 1,), bool).at[
                    jnp.where(
                        jnp.arange(r) < n_cross, jnp.clip(cross_idx, 0, r), r
                    )
                ].set(True)[:r]
                return jnp.where(keep[:, None], every_row(), 0)

            out = lax.switch(
                jnp.where(n_cross == 0, 0, jnp.where(n_cross <= block, 1, 2)),
                (lambda: jnp.zeros_like(h), few, all_rows),
            )
    return (
        out,
        stacked_arena(carry[1], kv_layers),
        stacked_arena(carry[2], kv_layers),
        stacked_arena(carry[3], state_layers),
    )
