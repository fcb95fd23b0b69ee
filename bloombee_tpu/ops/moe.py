"""Mixture-of-experts MLP: rows go to their top-k experts by index.

The router gives each row exactly `top_k` expert indices and their weights
(`route_topk`: Mixtral's form masks to the top-k logits and takes the softmax
over them; Qwen3-MoE's takes the softmax over all experts, picks the top-k
and renormalises; DeepSeek-V2's limits the choice to the best groups; afmoe's
takes the sigmoid of every logit, CHOOSES by score plus a per-expert bias and
WEIGHS by the unbiased scores). The experts are stacked weight tensors `[E, D, I]` /
`[E, I, D]`, the loader's layout, and a step computes them in one of three
forms. The rule (`expert_form`) reads the shape the program is compiled for
and whether Pallas kernels may run in it, and nothing else: no family, no
flag, and not how many of the router's experts the server holds.

- LIST, `rows * top_k < E` (a decode group, a short tail): the experts some
  row chose are listed in index order and ops/pallas/grouped_experts.py
  `grouped_experts` walks the list over the stacks where they lie, so an
  expert nobody chose is never read. Every listed expert multiplies ALL the
  step's rows (one row costs the MXU what sixteen do) and the per-row router
  weights, zero for a row that did not choose it, are the whole routing. A
  row whose input is exactly zero (a bucket's padding row) has a zero output
  whichever experts it goes to, so it adds none to the list.
- DENSE, three einsums over all experts: every program in which no kernel
  may run (a mesh, off the TPU, after a kernel fallback, quantised stacks),
  and the rows between the two other forms. The router's `[rows, E]`
  weights, zero off the top-k, scale the gated activations, so the down
  projection is ONE contraction over (expert, intermediate) that reads
  `[E, I, D]` as it lies; per-expert outputs `[rows, E, D]` weighted
  afterwards made the compiler re-lay-out the whole stack every run. It
  tiles onto the MXU, and the expert dimension shards over a mesh for real
  expert parallelism (bloombee_tpu/parallel/spmd.py psums the partial
  outputs). It does `rows` multiply-adds a weight it reads, 2 * rows FLOPs
  for 2 bytes: `rows` FLOPs a byte. The chip's ridge is its peak FLOPs over
  its peak bytes, 197e12 / 819e9 = 240 on a v5e: under about 240 rows the
  dense form waits for the weights it reads, which a chunk whose rows reach
  every expert has to read anyway, and is at its floor; above it the form is
  compute-bound by `rows / 240` on (row, expert) products whose router
  weight is exactly zero.
- TILED, `rows >= TILED_MIN_ROWS` (the 512-row prefill chunk, the fused
  pack): the (row, expert) pairs the router chose are sorted by expert,
  `tiled_experts` walks the experts that have a pair over the stacks where
  they lie, each read ONCE a layer, gathers only that expert's rows, a row
  tile at a time inside the grid step, multiplies them with the expert's
  blocks and adds each weighted output into its row: rows * top_k products
  in place of rows * E. Padding rows and pairs whose expert another chip
  holds list nothing.

All three compute every chosen (row, expert) pair, drop none (no capacity),
take bfloat16 operands, accumulate in float32 on the MXU and differ only in
the order of a row's top_k-term sum.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def route_topk(
    logits: jax.Array,  # [..., E]
    top_k: int,
    pre_softmax: bool = False,
    norm_topk: bool = False,
    groups: int = 0,
    topk_groups: int = 0,
    scale: float = 1.0,
    sigmoid: bool = False,
    bias: jax.Array | None = None,  # [E], with `sigmoid`: on the CHOICE only
) -> tuple[jax.Array, jax.Array]:
    """Per row exactly `top_k` expert indices [..., k] and their float32
    weights [..., k]; a tie goes to the lower index (`lax.top_k`), as in the
    published implementations.

    pre_softmax=False: HF Mixtral semantics — the top-k logits, then softmax
    over them. pre_softmax=True: HF Qwen3-MoE semantics — softmax over ALL
    experts, select top-k, renormalize iff norm_topk. groups > 0: DeepSeek-V2
    group-limited greedy — softmax over all experts; the experts are
    `groups` equal runs, a group scores as its best expert, the
    `topk_groups` best groups keep their scores and every other score is 0;
    the top-k of what is left, weights times `scale`, not renormalised.
    sigmoid=True: afmoe (the published `AfmoeTokenChoiceRouter`) — scores =
    sigmoid of ALL logits in float32; the top-k of scores + `bias` (a buffer
    the training's load balancing moves: it changes WHICH experts a row gets
    and never what they weigh); the weights are the chosen experts' UNBIASED
    scores, over their sum iff norm_topk, times `scale`."""
    if sigmoid:
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        _, idx = jax.lax.top_k(
            scores if bias is None else scores + bias.astype(jnp.float32),
            top_k,
        )
        weights = jnp.take_along_axis(scores, idx, axis=-1)
        if norm_topk:
            weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
        return idx, weights * scale
    if groups:
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        e = probs.shape[-1]
        best = probs.reshape(*probs.shape[:-1], groups, e // groups).max(-1)
        _, kept = jax.lax.top_k(best, topk_groups)
        keep = (
            kept[..., None] == jnp.arange(groups, dtype=kept.dtype)
        ).any(axis=-2)  # [..., groups]
        probs = jnp.where(jnp.repeat(keep, e // groups, axis=-1), probs, 0.0)
        weights, idx = jax.lax.top_k(probs, top_k)
        return idx, weights * scale
    if pre_softmax:
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        weights, idx = jax.lax.top_k(probs, top_k)
        if norm_topk:
            weights = weights / jnp.maximum(
                weights.sum(axis=-1, keepdims=True), 1e-20
            )
        return idx, weights
    top_vals, idx = jax.lax.top_k(logits, top_k)
    return idx, jax.nn.softmax(top_vals.astype(jnp.float32), axis=-1)


def _spread(idx: jax.Array, weights: jax.Array, num_experts: int) -> jax.Array:
    """[..., k] indices and weights -> [..., E] weights, zero off the k."""
    chose = idx[..., None] == jnp.arange(num_experts, dtype=idx.dtype)
    return jnp.where(chose, weights[..., None], 0.0).sum(axis=-2)


def router_topk_weights(
    logits: jax.Array,  # [B, T, E]
    top_k: int,
    pre_softmax: bool = False,
    norm_topk: bool = False,
) -> jax.Array:
    """`route_topk` spread over the expert axis: [B, T, E], zero off the
    `top_k` selected experts."""
    idx, weights = route_topk(logits, top_k, pre_softmax, norm_topk)
    return _spread(idx, weights, logits.shape[-1]).astype(logits.dtype)


# rows from which a program takes the TILED form where it would otherwise be
# dense: the first row bucket above the v5e's ridge of 240 FLOPs a byte
# (module docstring), where the dense form's time is the MXU's and no longer
# the weights'. Measured on the chip at Qwen3-Next's and DeepSeek-V2's held
# stacks, a layer alone (PERF.md section 6, PR 40): tiled ahead by 4-7% at
# 128 rows before its plan's sort, 7-10% at 256, 1.9 times at 512.
TILED_MIN_ROWS = 256


def expert_form(
    rows: int, top_k: int, num_experts: int, kernels: bool
) -> str:
    """The form a program of `rows` rows takes for experts of which the
    router scores `num_experts` and a row chooses `top_k`: "list", "tiled"
    or "dense" (module docstring). `kernels`: may Pallas kernels run in it."""
    if not kernels:
        return "dense"
    if rows * top_k < num_experts:
        return "list"  # the rows cannot hit every expert
    return "tiled" if rows >= TILED_MIN_ROWS else "dense"


def _held_local(idx, weights, held):
    """The router's choices over ALL experts cut to the ones held here
    (`held` = (first, count)): local indices into the held stack, the
    weights of pairs whose expert lies elsewhere zeroed, and which pairs
    are here [..., k]. A row with no held expert gets nothing from the
    routed experts on this chip."""
    first, count = held
    local = idx - first
    here = (local >= 0) & (local < count)
    return (
        jnp.clip(local, 0, count - 1), jnp.where(here, weights, 0.0), here
    )


REACH_FIELDS = ("held_hit", "routed_pairs_here", "rows_with_held_expert")
# a router whose choice a bias corrects counts two more (`bias_moved`)
BIAS_FIELDS = ("bias_moved_pairs", "routed_pairs")


def reach_fields(biased: bool) -> tuple[str, ...]:
    """The counters a sparse layer's reach vector holds, in order."""
    return REACH_FIELDS + (BIAS_FIELDS if biased else ())


def bias_moved(logits, idx) -> jax.Array:
    """Which of a sigmoid router's chosen pairs `idx` [..., k] are among the
    top-k ONLY because of the bias, bool [..., k]: not among the top-k of
    the unbiased scores (the sigmoid is monotone: of the logits)."""
    _, plain = jax.lax.top_k(logits.astype(jnp.float32), idx.shape[-1])
    return ~(idx[..., :, None] == plain[..., None, :]).any(axis=-1)


def held_reach(x, local, here, count: int, moved=None) -> jax.Array:
    """What a layer's live rows reached of the experts held here, i32 [3]
    (`REACH_FIELDS`): distinct held experts some pair chose, pairs whose
    expert is held, rows with at least one such pair. A zero row (a
    bucket's padding) counts nowhere, as in `_chosen_experts`. With `moved`
    (`bias_moved`) two more (`BIAS_FIELDS`): the live rows' pairs, on
    whichever chip their expert lies, that the bias moved, and all of
    them."""
    live = jnp.any(x != 0, axis=-1)[..., None]
    here = here & live
    hit = (
        (local[..., None] == jnp.arange(count, dtype=local.dtype))
        & here[..., None]
    ).any(axis=tuple(range(local.ndim)))
    counts = [hit.sum(), here.sum(), here.any(axis=-1).sum()]
    if moved is not None:
        counts += [(moved & live).sum(), live.sum() * local.shape[-1]]
    return jnp.stack(counts).astype(jnp.int32)


def _listed(hit: jax.Array, pairs: int):
    """The experts that are `hit` [E], ascending, as a list of static length
    P = min(pairs, E) (no more can be hit), padded past the `n` hit ones
    with the last of them; which slots count [P]."""
    n = hit.sum().astype(jnp.int32)
    (listed,) = jnp.nonzero(hit, size=min(pairs, hit.shape[0]), fill_value=0)
    live = jnp.arange(listed.shape[0], dtype=jnp.int32) < n
    slot_expert = jnp.where(live, listed, listed[jnp.maximum(n - 1, 0)])
    return slot_expert.astype(jnp.int32), n, live


def _chosen_experts(x, idx, weights, num_experts: int, here=None):
    """The grouped form's plan from the router's per-row choices: the experts
    some LIVE row chose, ascending ([P], padded with the last one), their
    count, and each slot's per-row weights [P, R]. `here` [R, k] leaves out
    the pairs whose expert another chip holds."""
    r, k = idx.shape
    live_row = jnp.any(x != 0, axis=-1)  # a zero row's output is zero
    chose = (
        idx[..., None] == jnp.arange(num_experts, dtype=idx.dtype)
    ) & live_row[:, None, None]  # [R, k, E]
    if here is not None:
        chose &= here[..., None]
    row_weights = jnp.where(chose, weights[..., None], 0.0).sum(axis=1)
    slot_expert, n, live = _listed(chose.any(axis=(0, 1)), r * k)
    slot_weights = jnp.where(live[:, None], row_weights.T[slot_expert], 0.0)
    return slot_expert, n, slot_weights


def _tiled_plan(x, idx, weights, num_experts: int, here=None):
    """The tiled form's plan from the router's per-row choices: the LIVE
    pairs sorted by expert (`src` [R*k] each one's row, `w` [R*k] its
    weight; the pairs of zero rows and of experts held elsewhere sort past
    the end and are in no run), and the experts that have a pair, ascending
    ([P], padded with the last one), their count, and each one's run of
    pairs as (start [P], count [P])."""
    r, k = idx.shape
    live = jnp.broadcast_to(jnp.any(x != 0, axis=-1)[:, None], (r, k))
    if here is not None:
        live &= here
    key = jnp.where(live, idx, num_experts).astype(jnp.int32).reshape(-1)
    row = jnp.broadcast_to(
        jnp.arange(r, dtype=jnp.int32)[:, None], (r, k)
    ).reshape(-1)
    key, src, w = jax.lax.sort(
        (key, row, weights.astype(jnp.float32).reshape(-1)), num_keys=1
    )
    # expert e's run is key == e: [starts[e], starts[e + 1])
    starts = jnp.searchsorted(
        key, jnp.arange(num_experts + 1, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    counts = starts[1:] - starts[:-1]
    slot_expert, n, live = _listed(counts > 0, r * k)
    return (
        slot_expert, n, starts[slot_expert],
        jnp.where(live, counts[slot_expert], 0), src, w,
    )


def relu2(x: jax.Array) -> jax.Array:
    """relu(x) ** 2, the activation of an UNGATED expert (nemotron_h)."""
    return jnp.square(jax.nn.relu(x))


def expert_hidden(g: jax.Array | None, u: jax.Array, activation: str):
    """An expert's hidden activations from its products: silu(g) * u, the
    gated form every family but one has, or with no gate product
    `activation`(u), an expert of TWO matrices ("relu2": nemotron_h). One
    copy for the dense einsums and both kernels."""
    if g is not None:
        return jax.nn.silu(g) * u
    if activation != "relu2":
        raise ValueError(f"no ungated expert activation {activation!r}")
    return relu2(u)


def moe_mlp(
    x: jax.Array,  # [B, T, D]
    router_w: jax.Array | None,  # [D, E]; None with `router_logits`
    gate_w: jax.Array | None,  # [E, D, I]; None: an UNGATED expert
    up_w: jax.Array,  # [E, D, I]
    down_w: jax.Array,  # [E, I, D]
    top_k: int,
    router_weights: jax.Array | None = None,  # precomputed [B, T, E]
    pre_softmax: bool = False,
    norm_topk: bool = False,
    expert_base: jax.Array | None = None,  # i32 scalar: kernels may run (the
    # LIST or the TILED form, by `expert_form`); gate/up/down may then hold
    # several layers' experts [N, D, I], this layer's E starting at that row
    interpret: bool = False,
    groups: int = 0,  # the group-limited router (route_topk)
    topk_groups: int = 0,
    route_scale: float = 1.0,
    held: tuple[int, int] | None = None,  # (first, count): gate/up/down
    # hold only these of the router's experts; the router scores them all
    router_logits: jax.Array | None = None,  # [B, T, E] where the caller
    # made the router's product itself (a router stored output-major)
    reach_out: list | None = None,  # with `held`: gets this layer's
    # `held_reach` appended (the caller carries it out of its program)
    sigmoid: bool = False,  # the sigmoid router (route_topk)
    router_bias: jax.Array | None = None,  # [E]: its choice's correction
    activation: str = "silu",  # static; "relu2" with `gate_w` None: an
    # expert is down(relu(up(x)) ** 2), two matrices (`expert_hidden`)
) -> jax.Array:
    """Expert MLPs (gated, or ungated two-matrix ones: `activation`)
    weighted by the top-k router weights: by a list of
    the chosen experts, by the chosen pairs in row tiles, or dense over all
    experts (module docstring). `expert_form` picks, from the rows, the
    router and whether the caller says kernels can run (`expert_base`).

    With `held`, every form takes the router's choices over all its experts,
    keep the pairs whose expert is held and index the held stack; on one
    chip there is no exchange, the other chips' pairs are theirs to add.

    When experts are sharded, pass `router_weights` computed from the full
    router and slice gate/up/down to the local experts; sum partial outputs
    with psum outside. That takes the dense form.
    """
    b, t, d = x.shape
    if expert_base is None:
        form = "dense"
    else:
        routed = (
            router_w if router_logits is None else router_logits
        ).shape[-1]
        form = expert_form(b * t, top_k, routed, True)
        if form == "dense":
            # the caller lifted the stacks where the rule would not have:
            # the list form is right for any rows
            form = "list"
    num_experts = (
        held[1] if held is not None
        else up_w.shape[0] if form == "dense" else routed
    )
    if router_weights is None:
        with jax.named_scope("moe_router"):
            if router_logits is None:
                router_logits = x @ router_w
            idx, weights = route_topk(
                router_logits, top_k, pre_softmax=pre_softmax,
                norm_topk=norm_topk, groups=groups, topk_groups=topk_groups,
                scale=route_scale, sigmoid=sigmoid, bias=router_bias,
            )
            here = None
            if held is not None:
                moved = None
                if reach_out is not None and router_bias is not None:
                    moved = bias_moved(router_logits, idx)
                idx, weights, here = _held_local(idx, weights, held)
                if reach_out is not None:
                    reach_out.append(
                        held_reach(x, idx, here, held[1], moved)
                    )
            if form == "dense":
                router_weights = _spread(idx, weights, num_experts).astype(
                    x.dtype
                )
            else:
                rows = x.reshape(b * t, d)
                plan = (_chosen_experts if form == "list" else _tiled_plan)(
                    rows, idx.reshape(b * t, top_k),
                    weights.reshape(b * t, top_k), num_experts,
                    None if here is None else here.reshape(b * t, top_k),
                )
    with jax.named_scope("moe_experts"):
        if form != "dense":
            from bloombee_tpu.ops.pallas.grouped_experts import (
                grouped_experts,
                tiled_experts,
            )

            slot_expert, *rest = plan
            out = (grouped_experts if form == "list" else tiled_experts)(
                rows, slot_expert + expert_base, *rest,
                gate_w, up_w, down_w, interpret=interpret,
                activation=activation,
            )
            return out.astype(x.dtype).reshape(b, t, d)
        g = None if gate_w is None else jnp.einsum(
            "btd,edi->btei", x, gate_w)
        u = jnp.einsum("btd,edi->btei", x, up_w)
        # the router's weight goes in before the down projection (linear, so
        # the same sum): one contraction over (expert, intermediate), the
        # stack read as it lies, and no [B, T, E, D] partial outputs
        h = expert_hidden(g, u, activation) * router_weights[..., None]
        return jnp.einsum("btei,eid->btd", h, down_w)
