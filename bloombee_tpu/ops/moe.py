"""Mixture-of-experts MLP: rows go to their top-k experts by index.

The router gives each row exactly `top_k` expert indices and their weights
(`route_topk`: Mixtral's form masks to the top-k logits and takes the softmax
over them; Qwen3-MoE's takes the softmax over all experts, picks the top-k
and renormalises). The experts are stacked weight tensors `[E, D, I]` /
`[E, I, D]`, the loader's layout, and a step computes them in one of two
forms, chosen at trace time from the shape it is compiled for:

- `rows * top_k < E` (a decode group) and Pallas kernels may run in this
  program: the GROUPED form. The experts some row chose are listed in index
  order and ops/pallas/grouped_experts.py walks the list over the stacks
  where they lie, so an expert nobody chose is never read. A row whose input
  is exactly zero (a bucket's padding row) has a zero output whichever
  experts it goes to, so it adds none to the list.
- otherwise (the 128-row prefill chunk and the fused ragged pack, where the
  rows hit every expert anyway; any program under a mesh or off the TPU,
  where the kernel cannot run): the DENSE form, three einsums over all
  experts. The router's `[rows, E]` weights, zero off the top-k, scale the
  gated activations, so the down projection is ONE contraction over (expert,
  intermediate) that reads `[E, I, D]` as it lies; per-expert outputs
  `[rows, E, D]` weighted afterwards made the compiler re-lay-out the whole
  stack every run. It tiles onto the MXU, and the expert dimension shards
  over a mesh for real expert parallelism (bloombee_tpu/parallel/spmd.py
  psums the partial outputs).

Both compute every chosen (row, expert) pair, drop none, accumulate in
float32 on the MXU and differ only in the order of a row's top_k-term sum.
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
    the top-k of what is left, weights times `scale`, not renormalised."""
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


def takes_grouped_form(rows: int, top_k: int, num_experts: int) -> bool:
    """Can a step of `rows` rows hit fewer than all experts?"""
    return rows * top_k < num_experts


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


def held_reach(x, local, here, count: int) -> jax.Array:
    """What a layer's live rows reached of the experts held here, i32 [3]
    (`REACH_FIELDS`): distinct held experts some pair chose, pairs whose
    expert is held, rows with at least one such pair. A zero row (a
    bucket's padding) counts nowhere, as in `_chosen_experts`."""
    here = here & jnp.any(x != 0, axis=-1)[..., None]
    hit = (
        (local[..., None] == jnp.arange(count, dtype=local.dtype))
        & here[..., None]
    ).any(axis=tuple(range(local.ndim)))
    return jnp.stack(
        [hit.sum(), here.sum(), here.any(axis=-1).sum()]
    ).astype(jnp.int32)


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
    hit = chose.any(axis=(0, 1))  # [E]
    n = hit.sum().astype(jnp.int32)
    p = min(r * k, num_experts)
    (listed,) = jnp.nonzero(hit, size=p, fill_value=0)
    slot = jnp.arange(p, dtype=jnp.int32)
    slot_expert = jnp.where(
        slot < n, listed, listed[jnp.maximum(n - 1, 0)]
    ).astype(jnp.int32)
    slot_weights = jnp.where(
        (slot < n)[:, None], row_weights.T[slot_expert], 0.0
    )
    return slot_expert, n, slot_weights


def moe_mlp(
    x: jax.Array,  # [B, T, D]
    router_w: jax.Array | None,  # [D, E]; None with `router_logits`
    gate_w: jax.Array,  # [E, D, I]
    up_w: jax.Array,  # [E, D, I]
    down_w: jax.Array,  # [E, I, D]
    top_k: int,
    router_weights: jax.Array | None = None,  # precomputed [B, T, E]
    pre_softmax: bool = False,
    norm_topk: bool = False,
    expert_base: jax.Array | None = None,  # i32 scalar: take the GROUPED
    # form; gate/up/down may then hold several layers' experts [N, D, I],
    # this layer's E starting at that row
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
) -> jax.Array:
    """Gated expert MLPs weighted by the top-k router weights, grouped by
    chosen expert or dense over all of them (module docstring). The caller
    picks the form (`takes_grouped_form`, and whether the kernel can run).

    With `held`, both forms take the router's choices over all its experts,
    keep the pairs whose expert is held and index the held stack; on one
    chip there is no exchange, the other chips' pairs are theirs to add.

    When experts are sharded, pass `router_weights` computed from the full
    router and slice gate/up/down to the local experts; sum partial outputs
    with psum outside. That takes the dense form.
    """
    b, t, d = x.shape
    grouped = expert_base is not None
    num_experts = (
        held[1] if held is not None
        else gate_w.shape[0] if not grouped
        else (router_w if router_logits is None else router_logits).shape[-1]
    )
    if router_weights is None:
        with jax.named_scope("moe_router"):
            if router_logits is None:
                router_logits = x @ router_w
            idx, weights = route_topk(
                router_logits, top_k, pre_softmax=pre_softmax,
                norm_topk=norm_topk, groups=groups, topk_groups=topk_groups,
                scale=route_scale,
            )
            here = None
            if held is not None:
                idx, weights, here = _held_local(idx, weights, held)
                if reach_out is not None:
                    reach_out.append(held_reach(x, idx, here, held[1]))
            if grouped:
                rows = x.reshape(b * t, d)
                plan = _chosen_experts(
                    rows, idx.reshape(b * t, top_k),
                    weights.reshape(b * t, top_k), num_experts,
                    None if here is None else here.reshape(b * t, top_k),
                )
            else:
                router_weights = _spread(idx, weights, num_experts).astype(
                    x.dtype
                )
    with jax.named_scope("moe_experts"):
        if grouped:
            from bloombee_tpu.ops.pallas.grouped_experts import (
                grouped_experts,
            )

            slot_expert, live, slot_weights = plan
            out = grouped_experts(
                rows, slot_expert + expert_base, live, slot_weights,
                gate_w, up_w, down_w, interpret=interpret,
            )
            return out.astype(x.dtype).reshape(b, t, d)
        g = jnp.einsum("btd,edi->btei", x, gate_w)
        u = jnp.einsum("btd,edi->btei", x, up_w)
        # the router's weight goes in before the down projection (linear, so
        # the same sum): one contraction over (expert, intermediate), the
        # stack read as it lies, and no [B, T, E, D] partial outputs
        h = jax.nn.silu(g) * u * router_weights[..., None]
        return jnp.einsum("btei,eid->btd", h, down_w)
