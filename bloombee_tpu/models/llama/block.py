"""Llama transformer block as a pure function.

TPU-native replacement for the reference's WrappedLlamaBlock + FLEX_LlamaAttention
/ FLEX_LlamaMLP pipeline (/root/reference/src/bloombee/models/llama/block.py:418-718
and flexgen_utils/pytorch_backend.py:665-1081). The FlexGen ValueHolder /
cache_read_buf / weight_read_buf plumbing collapses into function arguments and
return values; KV-cache policy lives entirely in the caller-provided `attend`
closure, so the same block code serves dense prefill, paged decode, and
speculative tree verify.

Weight convention (models/layout.py): q/k/v projections are stored as torch
`nn.Linear` has them — [out_features, in_features], the layout the TPU
compiler reads them in — and every other projection transposed,
[in_features, out_features], applied as `x @ w`.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from bloombee_tpu.models.layout import OUT_MAJOR_KEYS, project
from bloombee_tpu.models.spec import ModelSpec
from bloombee_tpu.ops import apply_rotary, masked_attention, rms_norm, silu_mlp
from bloombee_tpu.ops.attention import causal_mask

# attend(q, k_new, v_new) -> (attn_out, aux); shapes
#   q: [B, T, H, hd], k_new/v_new: [B, T, Hkv, hd], attn_out: [B, T, H, hd]
Attend = Callable[[jax.Array, jax.Array, jax.Array], tuple[jax.Array, Any]]


def init_block_params(rng: jax.Array, spec: ModelSpec, dtype=jnp.float32) -> dict:
    d, i = spec.hidden_size, spec.intermediate_size
    h, kv, hd = spec.num_attention_heads, spec.num_key_value_heads, spec.head_dim
    keys = jax.random.split(rng, 7)
    scale = d**-0.5

    def w(key, shape):
        return (jax.random.normal(key, shape) * scale).astype(dtype)

    return {
        "input_layernorm": jnp.ones((d,), dtype),
        "post_attention_layernorm": jnp.ones((d,), dtype),
        "q_proj": w(keys[0], (h * hd, d)),
        "k_proj": w(keys[1], (kv * hd, d)),
        "v_proj": w(keys[2], (kv * hd, d)),
        "o_proj": w(keys[3], (h * hd, d)),
        "gate_proj": w(keys[4], (d, i)),
        "up_proj": w(keys[5], (d, i)),
        "down_proj": w(keys[6], (i, d)),
    }


def block_forward(
    params: dict,
    spec: ModelSpec,
    hidden: jax.Array,  # [B, T, D]
    cos: jax.Array,  # [B, T, hd]
    sin: jax.Array,  # [B, T, hd]
    attend: Attend,
) -> tuple[jax.Array, Any]:
    b, t, d = hidden.shape
    h, kv, hd = spec.num_attention_heads, spec.num_key_value_heads, spec.head_dim

    x = rms_norm(hidden, params["input_layernorm"], spec.rms_norm_eps)
    q, k, v = (project(x, params[key], key)
               for key in ("q_proj", "k_proj", "v_proj"))
    q = q.reshape(b, t, h, hd)
    k = k.reshape(b, t, kv, hd)
    v = v.reshape(b, t, kv, hd)
    q, k = apply_rotary(q, k, cos, sin)

    attn_out, aux = attend(q, k, v)

    attn_out = attn_out.reshape(b, t, h * hd) @ params["o_proj"]
    hidden = hidden + attn_out

    x = rms_norm(hidden, params["post_attention_layernorm"], spec.rms_norm_eps)
    mlp_out = silu_mlp(x, params["gate_proj"], params["up_proj"], params["down_proj"])
    hidden = hidden + mlp_out
    return hidden, aux


def dense_attend(
    past_k: jax.Array | None = None,  # [B, S_past, Hkv, hd]
    past_v: jax.Array | None = None,
    offset: int = 0,
) -> Attend:
    """Plain causal attention with optional dense concatenated past (the
    'local block' reference path used by parity tests, cf.
    /root/reference/tests/test_block_exact_match.py)."""

    def attend(q, k, v):
        if past_k is not None:
            k_all = jnp.concatenate([past_k, k], axis=1)
            v_all = jnp.concatenate([past_v, v], axis=1)
        else:
            k_all, v_all = k, v
        t, s = q.shape[1], k_all.shape[1]
        mask = causal_mask(t, offset=s - t, s=s)[None]
        out = masked_attention(q, k_all, v_all, mask)
        return out, (k_all, v_all)

    return attend


def _matrix(name: str) -> tuple[str, bool]:
    # a torch [out, in] matrix is transposed at load unless its key is
    # stored output-major (models/layout.py)
    return name, name not in OUT_MAJOR_KEYS


# HF checkpoint key mapping: per-layer torch name -> (our name, transpose?)
HF_BLOCK_KEYS = {
    "input_layernorm.weight": ("input_layernorm", False),
    "post_attention_layernorm.weight": ("post_attention_layernorm", False),
    "self_attn.q_proj.weight": _matrix("q_proj"),
    "self_attn.k_proj.weight": _matrix("k_proj"),
    "self_attn.v_proj.weight": _matrix("v_proj"),
    "self_attn.o_proj.weight": _matrix("o_proj"),
    "mlp.gate_proj.weight": _matrix("gate_proj"),
    "mlp.up_proj.weight": _matrix("up_proj"),
    "mlp.down_proj.weight": _matrix("down_proj"),
}


def convert_hf_block_params(tensors: dict, dtype=None) -> dict:
    """Convert one decoder layer's HF tensors (suffix-keyed) to our pytree.

    `tensors` maps HF suffixes (e.g. 'self_attn.q_proj.weight') to arrays.
    Replaces the reference's .npy weight conversion
    (models/llama/block.py:329-384 convert_local_llama_weights).
    """
    out = {}
    for hf_key, (name, transpose) in HF_BLOCK_KEYS.items():
        w = jnp.asarray(tensors[hf_key])
        if transpose:
            w = w.T
        if dtype is not None:
            w = w.astype(dtype)
        out[name] = w
    return out
