"""Qwen3 family: Llama structure + per-head q/k RMSNorm + explicit head_dim.

Reference: /root/reference/src/bloombee/models/qwen3/ (WrappedQwen3Block).
152k vocab -> client-side head is the heavy part (README.md:103 note).
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp

from bloombee_tpu.models.auto import Family, register_family
from bloombee_tpu.models.llama.block import HF_BLOCK_KEYS, convert_hf_block_params
from bloombee_tpu.models.spec import ModelSpec


def qwen3_spec_from_hf(config: Any) -> ModelSpec:
    return ModelSpec(
        family="qwen3",
        hidden_size=config.hidden_size,
        intermediate_size=config.intermediate_size,
        num_attention_heads=config.num_attention_heads,
        num_key_value_heads=config.num_key_value_heads,
        head_dim=getattr(config, "head_dim", None)
        or config.hidden_size // config.num_attention_heads,
        num_hidden_layers=config.num_hidden_layers,
        vocab_size=config.vocab_size,
        rms_norm_eps=config.rms_norm_eps,
        rope_theta=getattr(config, "rope_theta", 1000000.0),
        tie_word_embeddings=getattr(config, "tie_word_embeddings", False),
        qk_norm=True,
    )


def _load_block(reader, layer_idx: int, dtype=None) -> dict:
    prefix = f"model.layers.{layer_idx}"
    tensors = {k: reader.tensor(f"{prefix}.{k}") for k in HF_BLOCK_KEYS}
    params = convert_hf_block_params(tensors, dtype=dtype)
    for name in ("q_norm", "k_norm"):
        w = jnp.asarray(reader.tensor(f"{prefix}.self_attn.{name}.weight"))
        params[name] = w.astype(dtype) if dtype is not None else w
    return params


register_family(
    Family("qwen3", qwen3_spec_from_hf, HF_BLOCK_KEYS, loader=_load_block)
)


# ---------------------------------------------------------------- qwen3-moe
def qwen3_moe_spec_from_hf(config: Any) -> ModelSpec:
    """Qwen3 attention (qk norms) + sparse MoE MLP. Router semantics are
    softmax-over-all-then-top-k, renormalized iff norm_topk_prob (HF
    Qwen3MoeSparseMoeBlock) — unlike Mixtral's mask-then-softmax."""
    import dataclasses

    if getattr(config, "mlp_only_layers", None) or getattr(
        config, "decoder_sparse_step", 1
    ) != 1:
        raise NotImplementedError(
            "qwen3-moe with dense interleaved layers (mlp_only_layers / "
            "decoder_sparse_step != 1) is not supported yet"
        )
    base = qwen3_spec_from_hf(config)
    return dataclasses.replace(
        base,
        family="qwen3_moe",
        intermediate_size=config.moe_intermediate_size,
        num_experts=config.num_experts,
        num_experts_per_tok=config.num_experts_per_tok,
        moe_pre_softmax=True,
        moe_norm_topk=bool(getattr(config, "norm_topk_prob", False)),
    )


def _load_block_moe(reader, layer_idx: int, dtype=None) -> dict:
    p = f"model.layers.{layer_idx}"
    from bloombee_tpu.models.checkpoint import read_tensor as _t, read_weight

    params = {
        "input_layernorm": _t(reader, f"{p}.input_layernorm.weight", dtype),
        "post_attention_layernorm": _t(
            reader, f"{p}.post_attention_layernorm.weight", dtype
        ),
    }
    for proj in ("q", "k", "v", "o"):
        params[f"{proj}_proj"] = read_weight(
            reader, f"{p}.self_attn.{proj}_proj.weight", f"{proj}_proj",
            dtype,
        )
    for name in ("q_norm", "k_norm"):
        params[name] = _t(reader, f"{p}.self_attn.{name}.weight", dtype)
    params["router"] = _t(reader, f"{p}.mlp.gate.weight", dtype).T  # [D, E]
    from bloombee_tpu.models.checkpoint import stack_expert_weights

    params.update(
        stack_expert_weights(
            reader, f"{p}.mlp.experts.{{}}", "gate_proj", "up_proj",
            "down_proj", params["router"].shape[1], dtype,
        )
    )
    return params


register_family(
    Family("qwen3_moe", qwen3_moe_spec_from_hf, loader=_load_block_moe)
)
