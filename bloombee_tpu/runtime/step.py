"""The jitted span step: all local blocks, one compiled function.

Equivalent of the reference's merged-pool inference step
(/root/reference/src/bloombee/server/backend.py:1368-1399
`_MergedInferenceStep` runs every local block in one pool call, and
backend.py:487-789 `inference_step` does select-cache -> mask -> forward ->
finalize per block). Here the whole span is a single `lax.scan` over stacked
block params, and the attention mask is computed once from positions +
context lengths.

The paged KV arena rides the scan's CARRY, whole, as one flat slab
[L * S_tot, Hkv, hd] (a bitcast of the stored [L, S_tot, Hkv, hd]); the
layer index rides as xs, and layer `l` writes at `slots + l * S_tot` and
reads through `page_table + l * num_pages` (kv/arena.py `layer_slots` /
`layer_pages`). So a layer scatters its rows into the donated buffer in
place (a chunk whose rows come as page groups, `page_groups`, one index a
PAGE: kv/arena.py `PageSlots`) and streams only its context's pages out of it. No step slices a
layer's slab out of the arena or stacks one back: as xs/ys of the scan every
layer would copy a whole slab out and in, and the donated buffer could not
be shared between xs and ys, so every run would copy the whole arena too
(measured: half of the device's busy time in the densest cell, PERF.md
section 6, PR 28). The benchmark's `scan_slab_move_share` reads such copies.

A family with a state-space mixer (`spec.ssm`) has a second arena, the
recurrent state (kv/arena.py `make_state_arena`): it rides the same carry,
whole and flat over (layer, slot), and layer `l` reads and writes the rows
`layer_state_slots(state_slots, l)`. The steps then take `state=` (donated)
and return it as a fourth value; the plan carries each sequence's slot at its
end. A family without one passes `state=None` and compiles the programs it
compiled before.

A family whose layer KINDS interleave and keep different caches
(`spec.gdn`: three linear layers, then a full one; `spec.one_sublayer`:
layers that are a mixer, an expert layer or attention ALONE, in a pattern
whose periods differ in length) is scanned as a list of runs of a repeated
unit (`_scan_periods`): the K/V arena has a row a layer that attends, the
state arena a row a layer with recurrent state, an expert layer neither,
and both arenas ride every run's carry.

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

from bloombee_tpu.kv.arena import (
    PageSlots,
    arena_tokens,
    flat_arena,
    layer_pages,
    layer_slots,
    layer_state_slots,
    stacked_arena,
)
from bloombee_tpu.models.layout import (
    period_stacks,
    plain_key,
    split_runs,
    stacked_layers,
)
from bloombee_tpu.models.spec import ModelSpec
from bloombee_tpu.ops.moe import expert_form, reach_fields
from bloombee_tpu.ops.rotary import mla_cos_sin, rotary_cos_sin
from bloombee_tpu.runtime.layer_body import (
    SsmRows,
    collecting_reach,
    layer_body,
    layer_body_ragged,
    packed_rows,
    packed_ssm_rows,
)


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
    state: dict | None = None,  # the recurrent-state arena (donated)
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
    attn_topk: int = 0,
    t_real: int | None = None,
    expert_kernels: bool = False,
    page_groups: bool = False,
):
    """span_step over a pack_step_payload buffer (one h2d per step)."""
    hidden, plan = unpack_step_payload(payload, b, t, spec.hidden_size)
    return span_step_impl(
        stacked_params, arena_k, arena_v, hidden, plan, tree_mask,
        lora=lora, state=state,
        spec=spec, page_size=page_size, max_pages=max_pages,
        use_tree_mask=use_tree_mask, windows=windows, use_flash=use_flash,
        use_paged=use_paged, attn_topk=attn_topk, t_real=t_real,
        expert_kernels=expert_kernels, page_groups=page_groups,
    )


span_step_packed = functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "b", "t", "page_size", "max_pages", "use_tree_mask",
        "windows", "use_flash", "use_paged", "attn_topk", "expert_kernels",
        "page_groups",
    ),
    donate_argnames=("arena_k", "arena_v", "state"),
)(span_step_packed_impl)


def _rope_by_window(spec: ModelSpec, q_positions: jax.Array, dtype):
    """Rotary tables from the plan's positions (fp32 like HF, cast to the
    compute dtype), as `pick(window_l) -> (cos, sin)` for one layer.
    gemma3-style models rope their sliding layers with the local base
    frequency; the per-layer window (riding the scan) selects the pair.
    Where only the window layers have positions (`spec.rope_window_only`:
    afmoe) a full layer gets the identity's tables, cos 1 and sin 0, and
    its rotary turns nothing."""

    def tables(theta):
        if spec.mla is not None:
            # the rotary part only, YaRN frequencies from the descriptor
            return tuple(
                x.astype(dtype)
                for x in mla_cos_sin(q_positions, spec.mla, theta)
            )
        # `rotary_dim`: tables of the dims that turn (ops/rotary.py
        # `apply_rotary` leaves the rest of a head alone)
        return tuple(
            x.astype(dtype)
            for x in rotary_cos_sin(
                q_positions, spec.rotary_dim or spec.head_dim, theta
            )
        )

    cos, sin = tables(spec.rope_theta)
    if spec.rope_window_only:
        cos_loc, sin_loc = cos, sin
        cos, sin = jnp.ones_like(cos), jnp.zeros_like(sin)
    elif spec.rope_local_theta and spec.rope_local_theta != spec.rope_theta:
        cos_loc, sin_loc = tables(spec.rope_local_theta)
    else:
        cos_loc, sin_loc = cos, sin

    def pick(window_l):
        use_local = window_l > 0
        return (
            jnp.where(use_local, cos_loc, cos),
            jnp.where(use_local, sin_loc, sin),
        )

    return pick


EXPERT_STACKS = ("experts_gate", "experts_up", "experts_down")


def _expert_stacks(stacked_params: dict) -> tuple[str, ...]:
    """The expert stacks these layers hold: all three, or an ungated
    expert's two (no `experts_gate`: nemotron_h)."""
    return tuple(k for k in EXPERT_STACKS if k in stacked_params)


def experts_form(
    spec: ModelSpec, stacked_params: dict, rows: int, kernels: bool
) -> str:
    """The form a step of `rows` rows takes for its experts, "list", "tiled"
    or "dense" (ops/moe.py `expert_form`): a kernel form needs a program in
    which Pallas kernels may run and stacks a kernel can read as they lie (a
    quantised stack is dequantised a layer at a time and stays dense)."""
    # (a span stored by run and position holds them under prefixed keys)
    stacks = [
        w for k, w in stacked_params.items() if plain_key(k) in EXPERT_STACKS
    ]
    if not (
        spec.num_experts
        and kernels
        and stacks
        and all(isinstance(w, jax.Array) for w in stacks)
    ):
        return "dense"
    return expert_form(
        rows, spec.num_experts_per_tok, spec.num_experts, True
    )


def lift_expert_stacks(
    spec: ModelSpec, stacked_params: dict, rows: int, kernels: bool
):
    """(the params that ride the scan as xs, the expert stacks held WHOLE or
    None).

    The list and the tiled form walk the chosen experts over the stacks where
    they lie, so the stacks must not ride the scan: a layer's [E, D, I] slice
    of xs handed to a kernel is a copy of it (1.2 GB a layer at 128 experts).
    Like the arena they are viewed flat over (layer, expert), closed over by
    the layer, and the scan carries only `expert_base`, the row where layer
    l's experts start. Every other step (the dense form: rows under the
    ridge that hit all experts anyway, no kernel may run, quantised stacks;
    a family without experts) gets its params back as they came and traces
    what it traced before."""
    if experts_form(spec, stacked_params, rows, kernels) == "dense":
        return stacked_params, None
    stacks = _expert_stacks(stacked_params)
    xs = {k: w for k, w in stacked_params.items() if k not in stacks}
    n = stacked_params[stacks[0]].shape[0]
    # a layer's experts in the stack: all the router scores, or the share
    # this server holds
    xs["expert_base"] = (
        jnp.arange(n, dtype=jnp.int32) * stacked_params[stacks[0]].shape[1]
    )
    whole = {
        k: stacked_params[k].reshape(-1, *stacked_params[k].shape[2:])
        for k in stacks
    }
    return xs, whole


def _scan_layers(
    run_layer,  # (h, k_flat, v_flat, slots_l, pages_l, xs_l, ssm_l) ->
    # (h, k, v) or, with a state arena, (h, k, v, state)
    hidden: jax.Array,
    arena_k,  # [L, S_tot, Hkv, hd] or its int4 QuantSlab
    arena_v,
    slots: jax.Array,
    page_table: jax.Array,
    layer_active: jax.Array,  # [n]: the first n of the arena's L layers run
    xs,  # per-layer inputs, leading dim n on every leaf
    page_size: int,
    state: dict | None = None,  # {"ssm", "conv"}: [L, slots, ...] each
    state_slots: jax.Array | None = None,  # [S] a sequence's state slot
    ssm_rows: SsmRows | None = None,
    first_layer: int = 0,  # static: the arena's layer the scan begins at (a
    # span of two runs of layers scans each on its own: `_scan_runs`)
    reach: int = 0,  # static, > 0: these layers route over experts of which
    # the server holds a share; the result then ends with what each layer's
    # rows reached of them, i32 [n, reach] (ops/moe.py `held_reach`: 3
    # counters, 5 where a bias corrects the router's choice)
    *,
    s_tot: int,  # the arena's tokens a layer (`_arena_dims`): not its
    # second dimension where it is stored folded
):
    """The span's layer scan, the arena WHOLE in the carry.

    The arena is viewed flat and never sliced: layer l (its index is the
    scan's xs) gets the flat arena plus its own offset slot and page ids.
    An inactive layer (`layer_active[l] == 0`: a session entering mid-span)
    takes the `skip` branch, which hands the carry through untouched — no
    compute, the arena bit-identical. `n < L` is the weight-offload prefix:
    the scan covers the resident layers and cannot reach the others' rows.
    The recurrent-state arena, where the family has one, is carried and
    addressed the same way; with it the result has a fourth value.
    """
    num_layers = arena_k.shape[0]
    num_pages = s_tot // page_size
    n = layer_active.shape[0]
    num_state_slots = 0 if state is None else state["ssm"].shape[1]

    def body(carry, xs_l):
        layer, active, rest = xs_l
        slots_l = layer_slots(slots, layer, s_tot, num_layers)
        pages_l = layer_pages(page_table, layer, num_pages)

        def run(h, k_flat, v_flat, state_flat):
            ssm_l = None
            if state_flat is not None:
                ssm_l = (
                    state_flat,
                    layer_state_slots(
                        state_slots, layer, num_state_slots, num_layers
                    ),
                    ssm_rows,
                )
            if not reach:
                out = run_layer(
                    h, k_flat, v_flat, slots_l, pages_l, rest, ssm_l
                )
                return out if state_flat is not None else (*out, None)
            with collecting_reach() as sown:
                out = run_layer(
                    h, k_flat, v_flat, slots_l, pages_l, rest, ssm_l
                )
            (reach_l,) = sown  # one sparse MLP a layer
            return (*out[:3], out[3] if len(out) == 4 else None, reach_l)

        def skip(h, k_flat, v_flat, state_flat):
            out = (h, k_flat, v_flat, state_flat)
            return (*out, jnp.zeros((reach,), jnp.int32)) if reach else out

        out = lax.cond(active > 0, run, skip, *carry)
        return out[:4], (out[4] if reach else None)

    (hidden, k_flat, v_flat, state_flat), reached = lax.scan(
        body,
        (
            hidden, flat_arena(arena_k), flat_arena(arena_v),
            None if state is None else flat_arena(state),
        ),
        (
            jnp.arange(first_layer, first_layer + n, dtype=jnp.int32),
            layer_active, xs,
        ),
    )
    out = (
        hidden,
        stacked_arena(k_flat, num_layers),
        stacked_arena(v_flat, num_layers),
    )
    if state is not None:
        out = (*out, stacked_arena(state_flat, num_layers))
    return (*out, reached) if reach else out


def _arena_dims(spec: ModelSpec, arena_k) -> tuple[int, int]:
    """(rows, S_tot) of a stored arena [rows, S_tot, ...]; a folded one
    (kv/arena.py `folds`) holds S_tot * kv_heads rows of head_dim a layer."""
    kv_heads = None if spec.mla is not None else spec.num_key_value_heads
    return arena_k.shape[0], arena_tokens(arena_k, kv_heads)


def _position_arena(params: dict) -> str | None:
    """The arena a period's position has a row in, read from the keys its
    stack holds: "state" (a delta-rule or state-space mixer in attention's
    place), "kv" (a layer that attends: every attention form ends in
    `o_proj`), None (an expert layer that is a layer of its own)."""
    if "gdn_in_proj" in params or "ssm_in_proj" in params:
        return "state"
    return "kv" if "o_proj" in params else None


def _scan_periods(run_layer, spec, stacked_params, rows, kernels, hidden,
                  arena_k, arena_v, slots, page_table, layer_active,
                  per_layer, page_size, state, state_slots, ssm_rows):
    """The layer scan of a span whose layer KINDS interleave and keep
    different caches (`spec.kinds_interleave`): ONE scan over a LIST of
    runs, each run a unit of layers repeated (`ModelSpec.period_runs`,
    models/layout.py `period_stacks`). Delta-rule layers among full ones
    (`spec.gdn`) are one run of like periods (linear, linear, linear, full),
    or two where a period differs from the rest (the model's leading dense
    layer stands in the first, or its last is short: the first run's stacks
    under `LEAD`); layers that are one sublayer each (`spec.one_sublayer`)
    are as many runs as the pattern has, a unit a period that comes again
    at once, else a pair of kinds that does, or a single layer ((moe, mamba,
    moe, mamba, moe, mamba, full) x 2; (moe, mamba) x 4, (full,) x 1). A
    run's scan body runs the
    unit's layers in order; its xs are one stack a position in the unit,
    each [repeats, ...]; both flat arenas ride the carry whole, from one
    run's scan into the next's. A layer's row in ITS arena is its index
    among its kind in the span (`ModelSpec.cache_rows`): in a run whose
    unit has m layers with recurrent state and f that attend, the j-th
    stateful layer of repeat p has row `state_row0 + p * m + j` of the state
    arena and none in the K/V arena, the i-th attending one row `kv_row0 +
    p * f + i` there (K and V, or a latent page) and none in the state
    arena; an expert layer that is a layer of its own has neither.
    `layer_active` still gates layer by layer (a session entering
    mid-span). Returns what `_scan_runs` returns with a state arena: on a
    server that holds a share of the experts the result ends with the
    SPARSE layers' reach, in layer order. A span with no attending layer
    (the tail of a `one_sublayer` model) has a K/V arena of no rows, which
    comes back as it came."""
    held = spec.moe_held is not None
    kv_layers, s_tot = _arena_dims(spec, arena_k)
    num_pages = s_tot // page_size
    state_layers, num_state_slots = state["ssm"].shape[:2]
    carry = (
        hidden, flat_arena(arena_k), flat_arena(arena_v), flat_arena(state)
    )
    layer0 = kv_row0 = state_row0 = 0
    reached_runs = []
    for positions in period_stacks(stacked_params):
        per = len(positions)
        arenas = [_position_arena(params) for params in positions]
        # a position's index among its kind in the unit
        among = [arenas[:j].count(arenas[j]) for j in range(per)]
        m, f = arenas.count("state"), arenas.count("kv")
        periods = jax.tree.leaves(positions[-1])[0].shape[0]
        # a position's reach vector: none where its MLP is dense
        fields = [
            len(reach_fields("expert_bias" in params)) if held and (
                "router" in params or "router_t" in params) else 0
            for params in positions
        ]
        stacks = [
            lift_expert_stacks(spec, params, rows, kernels)
            for params in positions
        ]

        # (everything below is traced inside this iteration's `lax.scan`:
        # the closures read the run's own values)
        def at(j):
            # [span's layers, ...] xs beside the params -> the run's
            # position j's [periods, ...]
            return lambda x: None if x is None else jax.tree.map(
                lambda a: a[layer0 : layer0 + periods * per].reshape(
                    periods, per, *a.shape[1:])[:, j], x
            )

        def kv_row(p, j):
            # kv_row0 + p * f + among[j], a period's ONE full layer traced
            # as the `kv_row0 + p` it always was
            row = kv_row0 + (p if f == 1 else p * f)
            return row + among[j] if among[j] else row

        def body(carry, xs_p):
            p, *by_position = xs_p
            # the attending layers' rows of the K/V arena; a layer without
            # one is handed the plan's own ids and never reads them
            addressed = {
                j: (
                    layer_slots(slots, kv_row(p, j), s_tot, kv_layers),
                    layer_pages(page_table, kv_row(p, j), num_pages),
                )
                for j in range(per) if arenas[j] == "kv"
            }
            fallback = next(iter(addressed.values()), (slots, page_table))
            reached = []
            for j, (active, params_l, *extras_l) in enumerate(by_position):
                stateful = arenas[j] == "state"
                slots_l, pages_l = addressed.get(j, fallback)
                experts = stacks[j][1]
                if experts is not None:
                    params_l = {**params_l, **experts}
                xs_l = (params_l, *extras_l)

                def run(h, k_flat, v_flat, state_flat, xs_l=xs_l,
                        stateful=stateful, slots_l=slots_l, pages_l=pages_l,
                        row=state_row0 + p * m + among[j], reach=fields[j]):
                    ssm_l = None
                    if stateful:
                        ssm_l = (
                            state_flat,
                            layer_state_slots(
                                state_slots, row, num_state_slots,
                                state_layers,
                            ),
                            ssm_rows,
                        )
                    with collecting_reach() as sown:
                        out = run_layer(
                            h, k_flat, v_flat, slots_l, pages_l, xs_l, ssm_l
                        )
                    out = out if stateful else (*out, state_flat)
                    return (*out, sown[0]) if reach else out

                def skip(h, k_flat, v_flat, state_flat, reach=fields[j]):
                    out = (h, k_flat, v_flat, state_flat)
                    return (
                        (*out, jnp.zeros((reach,), jnp.int32)) if reach
                        else out
                    )

                out = lax.cond(active > 0, run, skip, *carry)
                carry = out[:4]
                if fields[j]:
                    reached.append(out[4])
            return carry, (jnp.stack(reached) if reached else None)

        carry, reached = lax.scan(
            body, carry,
            (
                jnp.arange(periods, dtype=jnp.int32),
                *(
                    (at(j)(layer_active), stacks[j][0],
                     *(at(j)(x) for x in per_layer))
                    for j in range(per)
                ),
            ),
        )
        if reached is not None:
            reached_runs.append(reached.reshape(-1, reached.shape[-1]))
        layer0 += periods * per
        kv_row0 += periods * f
        state_row0 += periods * m
    hidden, k_flat, v_flat, state_flat = carry
    out = (
        hidden,
        stacked_arena(k_flat, kv_layers) if kv_layers else arena_k,
        stacked_arena(v_flat, kv_layers) if kv_layers else arena_v,
        stacked_arena(state_flat, state_layers),
    )
    return (*out, jnp.concatenate(reached_runs)) if reached_runs else out


def _scan_runs(run_layer, spec, stacked_params, rows, kernels, hidden,
               arena_k, arena_v, slots, page_table, layer_active, per_layer,
               page_size, **state):
    """`_scan_layers` over a span's runs of same-kind layers: ONE run for
    every span whose layers are of one kind (the scan it always was), two
    where the first layers' MLP is of another kind than the rest's
    (models/layout.py `LEAD`): each run its own scan over its own stack,
    the hidden rows and the one flat arena handed from the first to the
    second. `per_layer` are the xs beside the params (leading dim = all the
    span's layers, or None); `run_layer` takes (…, xs_l, ssm_l) with
    xs_l = (params_l, *per_layer_l). On a server that holds a share of the
    experts (`spec.moe_held`) the result ends with what the sparse layers'
    rows reached of them, i32 [sparse layers, 3 or 5] (`_scan_layers`). A span
    whose kinds interleave goes to `_scan_periods`."""
    if spec.kinds_interleave:
        return _scan_periods(
            run_layer, spec, stacked_params, rows, kernels, hidden, arena_k,
            arena_v, slots, page_table, layer_active, per_layer, page_size,
            **state,
        )
    lead, main = split_runs(stacked_params)
    runs = [main] if lead is None else [lead, main]
    first = 0
    out = (hidden, arena_k, arena_v)
    reached = []
    for params in runs:
        reach = len(reach_fields("expert_bias" in params)) if (
            spec.moe_held is not None
            and ("router" in params or "router_t" in params)
        ) else 0
        params, experts = lift_expert_stacks(spec, params, rows, kernels)
        n = jax.tree.leaves(params)[0].shape[0]
        cut = (lambda a: a) if lead is None else (
            lambda a, lo=first, hi=first + n: a[lo:hi]
        )
        xs = (params, *(
            None if x is None else jax.tree.map(cut, x) for x in per_layer
        ))

        def run(h, k, v, slots_l, pages_l, xs_l, ssm_l, experts=experts):
            if experts is not None:
                xs_l = ({**xs_l[0], **experts}, *xs_l[1:])
            return run_layer(h, k, v, slots_l, pages_l, xs_l, ssm_l)

        out = _scan_layers(
            run, out[0], out[1], out[2], slots, page_table,
            cut(layer_active), xs, page_size, first_layer=first, reach=reach,
            s_tot=_arena_dims(spec, out[1])[1], **state,
        )
        if reach:
            *out, reached_run = out
            reached.append(reached_run)
        if len(out) == 4:
            state = {**state, "state": out[3]}
        first += n
    return (*out, jnp.concatenate(reached)) if reached else tuple(out)


def span_step_impl(
    stacked_params: dict,  # pytree, leading dim L on every leaf
    arena_k: jax.Array,  # [L, S_tot, Hkv, hd] (donated)
    arena_v: jax.Array,  # [L, S_tot, Hkv, hd] (donated)
    hidden: jax.Array,  # [B, T, D]
    plan: jax.Array,  # packed int32 (see unpack_plan)
    tree_mask: jax.Array | None = None,  # [B, T, T] bool
    prompts: jax.Array | None = None,  # [L, P, D] deep p-tuning prompts
    lora: dict | None = None,  # {proj: {a: [L,in,r], b: [L,r,out]}}
    state: dict | None = None,  # the recurrent-state arena (donated); the
    # plan then ends with each row's state slot [B]
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
    expert_kernels: bool = False,  # the experts' kernels may run though the
    # paged attention kernels do not (a chunk that attends through flash)
    page_groups: bool = False,  # the host read in the slots that the rows
    # come as page groups (kv/arena.py `rows_fill_pages`): they are handed
    # down as `PageSlots`, which `arena_write` writes one index a page
):
    """Run all local blocks over one step; returns (hidden, arena_k, arena_v)
    and, given a state arena, that as a fourth value.

    Rotary cos/sin are computed on-device from the plan's positions (no
    per-step host tables), in fp32 like HF. `prompts` adds a trainable
    per-layer vector to the first P positions of each ACTIVE layer's input
    (deep p-tuning — reference ptune.py:21-80 deep mode); inactive layers'
    rows are ignored.
    """
    b, t, _ = hidden.shape
    # the params stack says how many layers run: all of the arena's, or the
    # resident prefix in weight-offload mode (the offloaded layers get their
    # own layer_step calls with host-streamed weights)
    n = stacked_layers(stacked_params)
    if spec.mamba is not None:
        return _sambay_packed(
            stacked_params, arena_k, arena_v, hidden, plan, state, spec, n,
            page_size, max_pages, use_paged, t_real, page_groups,
        )
    slots, page_table, q_positions, total_lens, layer_active = unpack_plan(
        plan, b, t, max_pages, n
    )
    if page_groups:
        slots = PageSlots(slots, page_size)
    rope = _rope_by_window(spec, q_positions, hidden.dtype)
    tm = tree_mask if use_tree_mask else None
    windows_arr = jnp.asarray(
        windows if windows is not None else (0,) * n, jnp.int32
    )
    state_slots = ssm_rows = None
    if state is not None:
        state_slots = plan[-b:]
        ssm_rows = packed_ssm_rows(
            b, t, q_positions, state_slots, state["ssm"].shape[1], t_real
        )
    rows = None
    if spec.mla is not None:
        # latent attention reads whose the rows are as the mixer does; a
        # padding row of the batch bucket has length 0
        rows = packed_rows(b, t, q_positions, total_lens > 0, t_real)

    def run_layer(h, k_flat, v_flat, slots_l, pages_l, xs_l, ssm_l):
        params_l, window_l, prompt_l, lora_l = xs_l
        if prompt_l is not None:
            p = prompt_l.shape[0]
            h = h.at[:, :p].add(prompt_l[None].astype(h.dtype))
        return layer_body(
            spec, page_size, h, params_l, k_flat, v_flat, *rope(window_l),
            slots_l, pages_l, q_positions, total_lens, tm, window_l,
            use_flash=use_flash, use_paged=use_paged, lora=lora_l,
            attn_topk=attn_topk, t_real=t_real, ssm=ssm_l, rows=rows,
        )

    return _scan_runs(
        run_layer, spec, stacked_params, b * t, use_paged or expert_kernels,
        hidden, arena_k, arena_v, slots, page_table, layer_active,
        (windows_arr, prompts, lora), page_size,
        state=state, state_slots=state_slots, ssm_rows=ssm_rows,
    )


def _sambay_packed(stacked_params, arena_k, arena_v, hidden, plan, state,
                   spec, n, page_size, max_pages, kernels, t_real,
                   page_groups):
    """A SambaY span's packed [B, T] step (runtime/sambay.py): the plan ends
    with each row's state slot [B] and, for T > 1, the flat rows the caller
    reads and their count (`pack_cross_tail`); a decode step's rows are all
    reply rows."""
    from bloombee_tpu.runtime.sambay import sambay_span

    b, t, d = hidden.shape
    cross = None
    if t > 1:
        cross = (plan[-(b * t + 1):-1], plan[-1])
        plan = plan[: -(b * t + 1)]
    slots, page_table, q_positions, total_lens, _ = unpack_plan(
        plan, b, t, max_pages, n
    )
    state_slots = plan[-b:]
    if page_groups:
        slots = PageSlots(slots, page_size)
    rows = packed_ssm_rows(
        b, t, q_positions, state_slots, state["ssm"].shape[1], t_real
    )
    out, arena_k, arena_v, state = sambay_span(
        spec, page_size, stacked_params, hidden.reshape(b * t, d), arena_k,
        arena_v, state, slots, page_table, q_positions.reshape(-1),
        total_lens, rows, state_slots, cross, kernels,
    )
    return out.reshape(b, t, d), arena_k, arena_v, state


def pack_cross_tail(cross_idx, rows: int):
    """What a SambaY step's plan ends with where its rows are more than one
    a sequence: the flat rows the caller reads (out of range past the
    last: `rows`), then their count."""
    import numpy as np

    idx = np.full((rows + 1,), rows, np.int32)
    idx[: len(cross_idx)] = cross_idx
    idx[rows] = len(cross_idx)
    return idx


span_step = functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "page_size", "max_pages", "use_tree_mask", "windows",
        "use_flash", "use_paged", "attn_topk", "expert_kernels",
        "page_groups",
    ),
    donate_argnames=("arena_k", "arena_v", "state"),
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


def pack_chunk_on_flash(spec: ModelSpec) -> bool:
    """Whether a fused pack's ONE chunk attends through the flash kernel,
    sequence by sequence, where kernels run: the full layers among linear
    ones (layer_body.py `_attend_by_rows`) and a SambaY span
    (runtime/sambay.py `_diff_attend`). Every other family's pack takes the
    ragged paged kernel, latent attention its own flash form (also where
    its layers stand among linear ones: kimi_linear)."""
    return (
        spec.kinds_interleave and spec.mla is None
    ) or spec.mamba is not None


def pack_ragged_ssm_tail(state_slots, row0, nt, chunk_seq: int):
    """What a ragged plan of a family with recurrent state ends with: per
    sequence its state slot, its first row and its real row count, then THE
    sequence that has more than one row and takes the chunk form (a pack
    holds exactly one: a prefill chunk beside decode rows)."""
    import numpy as np

    return np.concatenate([
        np.ravel(x).astype(np.int32)
        for x in (state_slots, row0, nt, [chunk_seq])
    ])


def span_step_ragged_impl(
    stacked_params: dict,
    arena_k: jax.Array,  # [L, S_tot, Hkv, hd] (donated)
    arena_v: jax.Array,
    payload: jax.Array,  # uint16 (bf16 compute) or uint32 (f32 compute)
    lora: dict | None = None,
    state: dict | None = None,  # the recurrent-state arena (donated); the
    # plan then ends with [state_slots(S) | row0(S) | nt(S) | chunk_seq(1)]
    # (pack_ragged_ssm_tail)
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
    # the span's layers: the K/V arena's rows, except where the layer kinds
    # differ in their cache and the arena has a row a FULL layer
    num_layers = (
        arena_k.shape[0]
        if not spec.kinds_interleave and spec.mamba is None
        else stacked_layers(stacked_params)
    )
    cross = None
    if spec.mamba is not None:
        # the rows the caller reads and their count (`pack_cross_tail`)
        cross = (plan[-(r + 1):-1], plan[-1])
        plan = plan[: -(r + 1)]
    (
        slots, page_table, q_positions, total_lens, q_seq, layer_active,
        nt, tree_rows,
    ) = unpack_ragged_plan(plan, r, n_seqs, max_pages, num_layers, t_max)
    rope = _rope_by_window(spec, q_positions, hidden.dtype)
    windows_arr = jnp.asarray(
        windows if windows is not None else (0,) * num_layers, jnp.int32
    )
    state_slots = ssm_rows = None
    if state is not None or spec.mla is not None:
        # whose the rows are (pack_ragged_ssm_tail): the mixer and latent
        # attention both take a sequence's rows by (row0, nt)
        tail = plan[plan.shape[0] - (3 * n_seqs + 1):]
        state_slots, row0, rows_nt = (
            tail[i * n_seqs : (i + 1) * n_seqs] for i in range(3)
        )
        at = jnp.clip(row0, 0, r - 1)
        ssm_rows = SsmRows(
            q_seq=q_seq, row0=row0, nt=rows_nt,
            fresh=q_positions[0, at] == 0,
            chunk_seqs=tail[3 * n_seqs :], window=r, step_form=True,
        )
    # latent attention, and the full layers among linear ones (their packs'
    # contexts are long: layer_body.py `_attend_by_rows`), attend by rows
    rows = (
        ssm_rows if spec.mla is not None or pack_chunk_on_flash(spec)
        else None
    )
    if state is None:
        state_slots = ssm_rows = None
    if spec.mamba is not None:
        from bloombee_tpu.runtime.sambay import sambay_span

        out, arena_k, arena_v, state = sambay_span(
            spec, page_size, stacked_params, hidden[0], arena_k, arena_v,
            state, slots, page_table, q_positions[0], total_lens, ssm_rows,
            state_slots, cross, use_kernel,
        )
        return out[None], arena_k, arena_v, state

    def run_layer(h, k_flat, v_flat, slots_l, pages_l, xs_l, ssm_l):
        params_l, window_l, lora_l = xs_l
        return layer_body_ragged(
            spec, page_size, h, params_l, k_flat, v_flat, *rope(window_l),
            slots_l, pages_l, q_positions, total_lens, q_seq,
            window_l, use_kernel=use_kernel, lora=lora_l,
            nt=nt, tree_rows=tree_rows, ssm=ssm_l, rows=rows,
        )

    return _scan_runs(
        run_layer, spec, stacked_params, r, use_kernel, hidden, arena_k,
        arena_v, slots, page_table, layer_active, (windows_arr, lora),
        page_size, state=state, state_slots=state_slots, ssm_rows=ssm_rows,
    )


span_step_ragged = functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "r", "n_seqs", "page_size", "max_pages", "windows",
        "use_kernel", "t_max",
    ),
    donate_argnames=("arena_k", "arena_v", "state"),
)(span_step_ragged_impl)


def layer_step_impl(
    params_l: dict,  # ONE layer's params (no leading L dim)
    arena_k: jax.Array,  # [L, S_tot, Hkv, hd] (donated; updated at layer_idx)
    arena_v: jax.Array,
    hidden: jax.Array,  # [B, T, D]
    plan: jax.Array,  # packed with ONE layer_active entry
    layer_idx: jax.Array,  # traced i32 scalar: whose rows of the arena
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
    resident stack's scan. The layer addresses the DONATED arena exactly as
    a scanned layer does — the flat view plus `layer_idx` as the offset of
    its slot and page ids — so its rows are scattered in place and the
    persistent KV state never leaves the device or moves on it."""
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
    num_layers, s_tot = _arena_dims(spec, arena_k)
    hidden, k_flat, v_flat = layer_body(
        spec, page_size, hidden, params_l,
        flat_arena(arena_k), flat_arena(arena_v), cos, sin,
        layer_slots(slots, layer_idx, s_tot, num_layers),
        layer_pages(page_table, layer_idx, s_tot // page_size),
        q_positions, total_lens,
        tree_mask if use_tree_mask else None,
        jnp.int32(window),
        use_flash=use_flash, use_paged=use_paged, lora=lora_l,
        attn_topk=attn_topk, t_real=t_real,
    )
    return (
        hidden,
        stacked_arena(k_flat, num_layers),
        stacked_arena(v_flat, num_layers),
    )


layer_step = functools.partial(
    jax.jit,
    static_argnames=(
        "spec", "page_size", "max_pages", "use_tree_mask", "window",
        "use_flash", "use_paged", "attn_topk",
    ),
    donate_argnames=("arena_k", "arena_v"),
)(layer_step_impl)
