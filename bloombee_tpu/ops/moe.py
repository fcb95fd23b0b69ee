"""Mixture-of-experts MLP (Mixtral family).

The reference runs all 8 experts densely inside one HF block with NO expert
parallelism (SURVEY.md section 2.3 Mixtral row, 2.8: "EP is absent"). Here
the experts are stacked weight tensors so the whole MoE layer is a few
einsums — dense over experts, masked by top-k router weights — which tiles
onto the MXU, and the expert dimension shards over the mesh for real expert
parallelism (bloombee_tpu/parallel/spmd.py psums the partial outputs).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def router_topk_weights(
    logits: jax.Array,  # [B, T, E]
    top_k: int,
    pre_softmax: bool = False,
    norm_topk: bool = False,
) -> jax.Array:
    """Top-k router weights, zero off the selected experts.

    pre_softmax=False: HF Mixtral semantics — mask to the top-k logits,
    then softmax over them. pre_softmax=True: HF Qwen3-MoE semantics —
    softmax over ALL experts, select top-k, renormalize iff norm_topk."""
    if pre_softmax:
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        top_vals, _ = jax.lax.top_k(probs, top_k)
        kept = jnp.where(probs >= top_vals[..., -1:], probs, 0.0)
        if norm_topk:
            kept = kept / jnp.maximum(
                kept.sum(axis=-1, keepdims=True), 1e-20
            )
        return kept.astype(logits.dtype)
    top_vals, _ = jax.lax.top_k(logits, top_k)
    thresh = top_vals[..., -1:]
    neg = jnp.finfo(jnp.float32).min
    masked = jnp.where(logits >= thresh, logits.astype(jnp.float32), neg)
    return jax.nn.softmax(masked, axis=-1).astype(logits.dtype)  # [B, T, E]


def moe_mlp(
    x: jax.Array,  # [B, T, D]
    router_w: jax.Array,  # [D, E]
    gate_w: jax.Array,  # [E, D, I]
    up_w: jax.Array,  # [E, D, I]
    down_w: jax.Array,  # [E, I, D]
    top_k: int,
    router_weights: jax.Array | None = None,  # precomputed [B, T, E]
    pre_softmax: bool = False,
    norm_topk: bool = False,
) -> jax.Array:
    """Dense-over-experts gated MLP weighted by top-k router probabilities.

    When experts are sharded, pass `router_weights` computed from the full
    router and slice gate/up/down to the local experts; sum partial outputs
    with psum outside.
    """
    if router_weights is None:
        with jax.named_scope("moe_router"):
            logits = x @ router_w
            router_weights = router_topk_weights(
                logits, top_k, pre_softmax=pre_softmax, norm_topk=norm_topk
            )
    with jax.named_scope("moe_experts"):
        g = jnp.einsum("btd,edi->btei", x, gate_w)
        u = jnp.einsum("btd,edi->btei", x, up_w)
        h = jax.nn.silu(g) * u
        out = jnp.einsum("btei,eid->bted", h, down_w)
        return jnp.einsum("bted,bte->btd", out, router_weights)
