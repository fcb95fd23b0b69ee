"""The jitted span step: all local blocks, one compiled function.

Equivalent of the reference's merged-pool inference step
(/root/reference/src/bloombee/server/backend.py:1368-1399
`_MergedInferenceStep` runs every local block in one pool call, and
backend.py:487-789 `inference_step` does select-cache -> mask -> forward ->
finalize per block). Here the whole span is a single `lax.scan` over stacked
block params; the paged KV arena rides the scan as per-layer xs/ys so XLA can
alias the donated buffers, and the attention mask is computed once from
positions + context lengths.

Shape discipline (SURVEY.md section 7 hard part #1): everything is padded to
static buckets — batch, step tokens T, and cache pages — and validity is
carried by `ctx_lens` / position masks. Out-of-bucket padding rows scatter to
out-of-bounds slots, which jax drops (`mode="drop"`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bloombee_tpu.models.spec import ModelSpec
from bloombee_tpu.ops.rotary import rotary_cos_sin
from bloombee_tpu.runtime.layer_body import layer_body, layer_body_ragged


def unpack_plan(plan: jax.Array, b: int, t: int, max_pages: int, num_layers: int):
    """Split the packed int32 plan array back into its parts.

    The plan packs [slots(B*T) | page_table(B*max_pages) | positions(B*T) |
    total_lens(B) | layer_active(L)] into one int32 vector so a step costs ONE
    host->device transfer for all control data (transfer latency dominates on
    DCN-attached hosts; cf. the reference's single metadata sidecar per
    request, handler.py rpc metadata). `layer_active` gates which of the
    server's layers run — a session entering mid-span (suffix sub-span
    routing, reference `spans_containing_block`) skips the leading layers.
    """
    o1 = b * t
    o2 = o1 + b * max_pages
    o3 = o2 + b * t
    o4 = o3 + b
    slots = plan[:o1]
    page_table = plan[o1:o2].reshape(b, max_pages)
    q_positions = plan[o2:o3].reshape(b, t)
    total_lens = plan[o3:o4]
    layer_active = plan[o4 : o4 + num_layers]
    return slots, page_table, q_positions, total_lens, layer_active


def pack_plan(slots, page_table, q_positions, total_lens, layer_active):
    import numpy as np

    return np.concatenate(
        [
            np.ravel(slots).astype(np.int32),
            np.ravel(page_table).astype(np.int32),
            np.ravel(q_positions).astype(np.int32),
            np.ravel(total_lens).astype(np.int32),
            np.ravel(layer_active).astype(np.int32),
        ]
    )


def pack_step_payload(h_pad, plan):
    """Host side: plan + hidden bitcast into ONE vector, so a serving step
    costs a single h2d transfer (the count of dependent transfers, not
    their size, is what a step pays for). The device side splits and
    bitcasts back (see unpack_step_payload); verified
    little-endian-consistent between numpy views and XLA
    bitcast_convert_type on both CPU and TPU.

    The PLAN goes first: the TPU compiler's time for a slice that starts
    deep inside a 1-D 16-bit vector grows with the offset (hidden-first
    cost ~25 s of compile per 128-token bucket and ~130 s per 512-token
    bucket at hidden size 4096; plan-first compiles in ~1 s)."""
    import numpy as np

    lane = np.uint16 if h_pad.dtype.itemsize == 2 else np.uint32
    return np.concatenate([plan.view(lane).ravel(), h_pad.view(lane).ravel()])


def unpack_step_payload(payload: jax.Array, b: int, t: int, d: int):
    """Device side of pack_step_payload: split one uint16/uint32 buffer back
    into (hidden [b, t, d], plan int32). uint16 lanes are bf16 hidden +
    int32 plan as low/high half pairs (little-endian, matching numpy views
    on both CPU and TPU)."""
    n_plan = payload.shape[0] - b * t * d
    if payload.dtype == jnp.uint16:
        hidden = lax.bitcast_convert_type(payload[n_plan:], jnp.bfloat16)
        plan = lax.bitcast_convert_type(
            payload[:n_plan].reshape(-1, 2), jnp.int32
        )
    else:
        hidden = lax.bitcast_convert_type(payload[n_plan:], jnp.float32)
        plan = lax.bitcast_convert_type(payload[:n_plan], jnp.int32)
    return hidden.reshape(b, t, d), plan


def span_step_packed_impl(
    stacked_params: dict,
    arena_k: jax.Array,
    arena_v: jax.Array,
    payload: jax.Array,  # uint16 (bf16 compute) or uint32 (f32 compute)
    tree_mask: jax.Array | None = None,
    lora: dict | None = None,  # per-request LoRA factors, leading dim L
    *,
    spec: ModelSpec,
    b: int,
    t: int,
    page_size: int,
    max_pages: int,
    use_tree_mask: bool = False,
    windows: tuple | None = None,
    use_flash: bool = False,
    use_paged: bool = False,
    resident: int | None = None,
    attn_topk: int = 0,
    t_real: int | None = None,
):
    """span_step over a pack_step_payload buffer (one h2d per step).

    `resident` (weight-offload mode): the params stack covers only the
    first `resident` of the arena's layers — scan over that prefix, write
    the updated slabs back into the full donated arena, and leave the
    offloaded layers' slabs untouched (they get their own layer_step calls
    with host-streamed weights)."""
    hidden, plan = unpack_step_payload(payload, b, t, spec.hidden_size)
    if resident is None:
        return span_step_impl(
            stacked_params, arena_k, arena_v, hidden, plan, tree_mask,
            lora=lora,
            spec=spec, page_size=page_size, max_pages=max_pages,
            use_tree_mask=use_tree_mask, windows=windows, use_flash=use_flash,
            use_paged=use_paged, attn_topk=attn_topk, t_real=t_real,
        )
    hidden, ak, av = span_step_impl(
        stacked_params, arena_k[:resident], arena_v[:resident], hidden, plan,
        tree_mask, lora=lora,
        spec=spec, page_size=page_size, max_pages=max_pages,
        use_tree_mask=use_tree_mask, windows=windows, use_flash=use_flash,
        use_paged=use_paged, attn_topk=attn_topk, t_real=t_real,
    )
    arena_k = jax.lax.dynamic_update_slice_in_dim(arena_k, ak, 0, 0)
    arena_v = jax.lax.dynamic_update_slice_in_dim(arena_v, av, 0, 0)
    return hidden, arena_k, arena_v


span_step_packed = functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "b", "t", "page_size", "max_pages", "use_tree_mask",
        "windows", "use_flash", "use_paged", "resident", "attn_topk",
    ),
    donate_argnames=("arena_k", "arena_v"),
)(span_step_packed_impl)


def span_step_impl(
    stacked_params: dict,  # pytree, leading dim L on every leaf
    arena_k: jax.Array,  # [L, S_tot, Hkv, hd] (donated)
    arena_v: jax.Array,  # [L, S_tot, Hkv, hd] (donated)
    hidden: jax.Array,  # [B, T, D]
    plan: jax.Array,  # packed int32 (see unpack_plan)
    tree_mask: jax.Array | None = None,  # [B, T, T] bool
    prompts: jax.Array | None = None,  # [L, P, D] deep p-tuning prompts
    lora: dict | None = None,  # {proj: {a: [L,in,r], b: [L,r,out]}}
    *,
    spec: ModelSpec,
    page_size: int,
    max_pages: int,
    use_tree_mask: bool = False,
    windows: tuple | None = None,
    use_flash: bool = False,
    use_paged: bool = False,
    attn_topk: int = 0,
    t_real: int | None = None,
):
    """Run all local blocks over one step; returns (hidden, arena_k, arena_v).

    Rotary cos/sin are computed on-device from the plan's positions (no
    per-step host tables), in fp32 like HF. `prompts` adds a trainable
    per-layer vector to the first P positions of each ACTIVE layer's input
    (deep p-tuning — reference ptune.py:21-80 deep mode); inactive layers'
    rows are ignored.
    """
    b, t, _ = hidden.shape
    num_layers = arena_k.shape[0]
    slots, page_table, q_positions, total_lens, layer_active = unpack_plan(
        plan, b, t, max_pages, num_layers
    )
    cos, sin = rotary_cos_sin(q_positions, spec.head_dim, spec.rope_theta)
    cos = cos.astype(hidden.dtype)
    sin = sin.astype(hidden.dtype)
    if spec.rope_local_theta and spec.rope_local_theta != spec.rope_theta:
        # gemma3-style: sliding layers rope with the local base frequency;
        # the per-layer window (already riding the scan) selects the pair
        cos_loc, sin_loc = rotary_cos_sin(
            q_positions, spec.head_dim, spec.rope_local_theta
        )
        cos_loc = cos_loc.astype(hidden.dtype)
        sin_loc = sin_loc.astype(hidden.dtype)
    else:
        cos_loc, sin_loc = cos, sin

    tm = tree_mask if use_tree_mask else None
    windows_arr = jnp.asarray(
        windows if windows is not None else (0,) * num_layers, jnp.int32
    )

    xs = (stacked_params, arena_k, arena_v, layer_active, windows_arr)
    if prompts is not None:
        xs = xs + (prompts,)
    if lora is not None:
        xs = xs + (lora,)

    def body(h, xs):
        params_l, k_l, v_l, active, window_l = xs[:5]
        rest = list(xs[5:])
        prompt_l = rest.pop(0) if prompts is not None else None
        lora_l = rest.pop(0) if lora is not None else None
        use_local = window_l > 0
        cos_l = jnp.where(use_local, cos_loc, cos)
        sin_l = jnp.where(use_local, sin_loc, sin)

        def run(h, k_l, v_l):
            if prompt_l is not None:
                p = prompt_l.shape[0]
                h = h.at[:, :p].add(prompt_l[None].astype(h.dtype))
            return layer_body(
                spec, page_size, h, params_l, k_l, v_l, cos_l, sin_l, slots,
                page_table, q_positions, total_lens, tm, window_l,
                use_flash=use_flash, use_paged=use_paged, lora=lora_l,
                attn_topk=attn_topk, t_real=t_real,
            )

        def skip(h, k_l, v_l):
            return h, k_l, v_l

        h, k_l, v_l = lax.cond(active > 0, run, skip, h, k_l, v_l)
        return h, (k_l, v_l)

    hidden, (arena_k, arena_v) = lax.scan(body, hidden, xs)
    return hidden, arena_k, arena_v


span_step = functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "page_size", "max_pages", "use_tree_mask", "windows",
        "use_flash", "use_paged", "attn_topk",
    ),
    donate_argnames=("arena_k", "arena_v"),
)(span_step_impl)


def unpack_ragged_plan(
    plan: jax.Array, r: int, n_seqs: int, max_pages: int, num_layers: int,
    t_max: int = 0,
):
    """unpack_plan for the ragged mixed-batch step: token-axis vectors are
    [R] (one entry per ragged token row) and sequence-axis vectors are
    [n_seqs], tied together by q_seq — [slots(R) | page_table(B*max_pages)
    | positions(R) | total_lens(B) | q_seq(R) | layer_active(L)]. A ragged
    TREE-verify group (t_max > 0) appends two more segments:
    [... | nt(B) | tree_rows(R*t_max)] — nt[b] is sequence b's in-step
    (speculative) token count and tree_rows[i, m] says whether token row i
    may attend the m-th in-step token of its own sequence."""
    o1 = r
    o2 = o1 + n_seqs * max_pages
    o3 = o2 + r
    o4 = o3 + n_seqs
    o5 = o4 + r
    slots = plan[:o1]
    page_table = plan[o1:o2].reshape(n_seqs, max_pages)
    q_positions = plan[o2:o3].reshape(1, r)
    total_lens = plan[o3:o4]
    q_seq = plan[o4:o5]
    o6 = o5 + num_layers
    layer_active = plan[o5:o6]
    if not t_max:
        return (
            slots, page_table, q_positions, total_lens, q_seq, layer_active,
            None, None,
        )
    o7 = o6 + n_seqs
    nt = plan[o6:o7]
    tree_rows = plan[o7 : o7 + r * t_max].reshape(r, t_max)
    return (
        slots, page_table, q_positions, total_lens, q_seq, layer_active,
        nt, tree_rows,
    )


def pack_ragged_plan(
    slots, page_table, q_positions, total_lens, q_seq, layer_active,
    nt=None, tree_rows=None,
):
    import numpy as np

    parts = [
        np.ravel(slots).astype(np.int32),
        np.ravel(page_table).astype(np.int32),
        np.ravel(q_positions).astype(np.int32),
        np.ravel(total_lens).astype(np.int32),
        np.ravel(q_seq).astype(np.int32),
        np.ravel(layer_active).astype(np.int32),
    ]
    if nt is not None:
        parts.append(np.ravel(nt).astype(np.int32))
        parts.append(np.ravel(tree_rows).astype(np.int32))
    return np.concatenate(parts)


def span_step_ragged_impl(
    stacked_params: dict,
    arena_k: jax.Array,  # [L, S_tot, Hkv, hd] (donated)
    arena_v: jax.Array,
    payload: jax.Array,  # uint16 (bf16 compute) or uint32 (f32 compute)
    lora: dict | None = None,
    *,
    spec: ModelSpec,
    r: int,  # ragged token bucket (pow2-padded sum of member tokens)
    n_seqs: int,  # sequence bucket (pow2-padded member sequence count)
    page_size: int,
    max_pages: int,
    windows: tuple | None = None,
    use_kernel: bool = False,
    t_max: int = 0,
):
    """The ragged mixed-batch span step: N single-token decode members plus
    one prefill-chunk member packed into ONE [1, R, D] dispatch (the
    Sarathi-Serve fused iteration). Rides pack_step_payload as a b=1, t=R
    hidden; per-row (q_seq, q_positions) carry the member structure the
    block shapes no longer do. t_max > 0 switches the step into the ragged
    TREE-verify variant: the plan carries per-sequence in-step counts and
    per-row tree visibility, so N sessions' speculative trees verify in one
    dispatch. No prompts or offload-resident splits here — those step types
    stay on their dedicated paths (the executor gates eligibility
    host-side)."""
    hidden, plan = unpack_step_payload(payload, 1, r, spec.hidden_size)
    num_layers = arena_k.shape[0]
    (
        slots, page_table, q_positions, total_lens, q_seq, layer_active,
        nt, tree_rows,
    ) = unpack_ragged_plan(plan, r, n_seqs, max_pages, num_layers, t_max)
    cos, sin = rotary_cos_sin(q_positions, spec.head_dim, spec.rope_theta)
    cos = cos.astype(hidden.dtype)
    sin = sin.astype(hidden.dtype)
    if spec.rope_local_theta and spec.rope_local_theta != spec.rope_theta:
        cos_loc, sin_loc = rotary_cos_sin(
            q_positions, spec.head_dim, spec.rope_local_theta
        )
        cos_loc = cos_loc.astype(hidden.dtype)
        sin_loc = sin_loc.astype(hidden.dtype)
    else:
        cos_loc, sin_loc = cos, sin

    windows_arr = jnp.asarray(
        windows if windows is not None else (0,) * num_layers, jnp.int32
    )
    xs = (stacked_params, arena_k, arena_v, layer_active, windows_arr)
    if lora is not None:
        xs = xs + (lora,)

    def body(h, xs):
        params_l, k_l, v_l, active, window_l = xs[:5]
        lora_l = xs[5] if lora is not None else None
        use_local = window_l > 0
        cos_l = jnp.where(use_local, cos_loc, cos)
        sin_l = jnp.where(use_local, sin_loc, sin)

        def run(h, k_l, v_l):
            return layer_body_ragged(
                spec, page_size, h, params_l, k_l, v_l, cos_l, sin_l,
                slots, page_table, q_positions, total_lens, q_seq,
                window_l, use_kernel=use_kernel, lora=lora_l,
                nt=nt, tree_rows=tree_rows,
            )

        def skip(h, k_l, v_l):
            return h, k_l, v_l

        h, k_l, v_l = lax.cond(active > 0, run, skip, h, k_l, v_l)
        return h, (k_l, v_l)

    hidden, (arena_k, arena_v) = lax.scan(body, hidden, xs)
    return hidden, arena_k, arena_v


span_step_ragged = functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "r", "n_seqs", "page_size", "max_pages", "windows",
        "use_kernel", "t_max",
    ),
    donate_argnames=("arena_k", "arena_v"),
)(span_step_ragged_impl)


def layer_step_impl(
    params_l: dict,  # ONE layer's params (no leading L dim)
    arena_k: jax.Array,  # [L, S_tot, Hkv, hd] (donated; updated at layer_idx)
    arena_v: jax.Array,
    hidden: jax.Array,  # [B, T, D]
    plan: jax.Array,  # packed with ONE layer_active entry
    layer_idx: jax.Array,  # traced i32 scalar: which arena slab to touch
    tree_mask: jax.Array | None = None,
    lora_l: dict | None = None,
    *,
    spec: ModelSpec,
    page_size: int,
    max_pages: int,
    use_tree_mask: bool = False,
    window: int = 0,  # static per-layer window (<= 2 distinct compiles)
    use_flash: bool = False,
    use_paged: bool = False,
    attn_topk: int = 0,
    t_real: int | None = None,
):
    """One layer of the span as its own compiled step — the unit of the
    weight-offload path (reference FlexGen Policy weight percentages /
    convert_block.py PipelineParallelWrapper pre-forward H2D): offloaded
    layers' params arrive from host per step, so they can't ride the
    resident stack's scan. The layer's K/V slab is read out of and written
    back into the DONATED arena in place (dynamic_update_index aliases the
    buffer), so the persistent KV state never leaves the device."""
    b, t, _ = hidden.shape
    slots, page_table, q_positions, total_lens, _ = unpack_plan(
        plan, b, t, max_pages, 1
    )
    local = bool(
        window > 0
        and spec.rope_local_theta
        and spec.rope_local_theta != spec.rope_theta
    )
    theta = spec.rope_local_theta if local else spec.rope_theta
    cos, sin = rotary_cos_sin(q_positions, spec.head_dim, theta)
    cos = cos.astype(hidden.dtype)
    sin = sin.astype(hidden.dtype)
    k_l = jax.lax.dynamic_index_in_dim(arena_k, layer_idx, 0, keepdims=False)
    v_l = jax.lax.dynamic_index_in_dim(arena_v, layer_idx, 0, keepdims=False)
    hidden, k_l, v_l = layer_body(
        spec, page_size, hidden, params_l, k_l, v_l, cos, sin, slots,
        page_table, q_positions, total_lens,
        tree_mask if use_tree_mask else None,
        jnp.int32(window),
        use_flash=use_flash, use_paged=use_paged, lora=lora_l,
        attn_topk=attn_topk, t_real=t_real,
    )
    arena_k = jax.lax.dynamic_update_index_in_dim(arena_k, k_l, layer_idx, 0)
    arena_v = jax.lax.dynamic_update_index_in_dim(arena_v, v_l, layer_idx, 0)
    return hidden, arena_k, arena_v


layer_step = functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "page_size", "max_pages", "use_tree_mask", "window",
        "use_flash", "use_paged", "attn_topk",
    ),
    donate_argnames=("arena_k", "arena_v"),
)(layer_step_impl)
