"""Mixtral family: Llama-style attention + sparse MoE MLP.

Reference: /root/reference/src/bloombee/models/mixtral/ runs all experts
densely inside one HF block with no expert parallelism; here experts are
stacked tensors (ops/moe.py) and shard over the mesh in the SPMD path —
an improvement the reference explicitly lacks (SURVEY.md section 2.8).
"""

from __future__ import annotations

from typing import Any


from bloombee_tpu.models.auto import Family, register_family
from bloombee_tpu.models.checkpoint import read_tensor as _t, read_weight
from bloombee_tpu.models.spec import ModelSpec


def mixtral_spec_from_hf(config: Any) -> ModelSpec:
    return ModelSpec(
        family="mixtral",
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
        num_experts=config.num_local_experts,
        num_experts_per_tok=config.num_experts_per_tok,
    )


def _load_block(reader, layer_idx: int, dtype=None) -> dict:
    p = f"model.layers.{layer_idx}"
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
    params["router"] = _t(
        reader, f"{p}.block_sparse_moe.gate.weight", dtype
    ).T  # [D, E]
    from bloombee_tpu.models.checkpoint import stack_expert_weights

    # mixtral names: w1 = gate, w3 = up, w2 = down
    params.update(
        stack_expert_weights(
            reader, f"{p}.block_sparse_moe.experts.{{}}", "w1", "w3", "w2",
            params["router"].shape[1], dtype,
        )
    )
    return params


register_family(Family("mixtral", mixtral_spec_from_hf, loader=_load_block))
