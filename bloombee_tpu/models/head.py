"""Shared embed / norm+LM-head math for the client and the server.

The client model (client/model.py) and the server-side multi-step decode
loop (runtime/decode_loop.py) must produce bit-identical logits on the same
backend — the server loop replaces N client round trips, so any numerical
drift between the two paths would change greedy outputs. Keeping the math in
one place makes that equivalence structural instead of coincidental.

Reference analogs: client LMHead (/root/reference/src/bloombee/client/
lm_head.py:24-93) and the embedding half of Distributed*Model.forward.
"""

from __future__ import annotations

import jax.numpy as jnp

from bloombee_tpu.ops import rms_norm
from bloombee_tpu.ops.norms import layer_norm


def embed_impl(
    params,
    input_ids,
    embedding_multiplier: float = 1.0,
    has_embed_norm: bool = False,
    eps: float = 1e-5,
):
    """Token ids -> hidden states, in the embed table's dtype."""
    h = params["embed"][input_ids]
    if embedding_multiplier != 1.0:
        h = h * embedding_multiplier
    if has_embed_norm:  # bloom: word_embeddings_layernorm
        h = layer_norm(h, params["embed_norm"], params["embed_norm_bias"], eps)
    return h


def norm_head_impl(
    params, hidden, eps: float, soft_cap: float = 0.0, norm_type: str = "rms",
    logit_multiplier: float = 1.0,
):
    """Final norm + LM head -> fp32 logits (optionally scaled by the
    family's `lm_head_multiplier`, then soft-capped)."""
    if norm_type == "ln":
        h = layer_norm(hidden, params["norm"], params.get("norm_bias"), eps)
    else:
        h = rms_norm(hidden, params["norm"], eps)
    logits = (h @ params["lm_head"]).astype(jnp.float32)
    if logit_multiplier != 1.0:
        logits = logits * logit_multiplier
    if soft_cap:
        logits = jnp.tanh(logits / soft_cap) * soft_cap
    return logits
