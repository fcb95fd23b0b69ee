"""Gemma2-style family: sandwich norms, alternating sliding-window layers,
gated tanh-GELU MLP, logit soft-capping, sqrt(D) embedding scaling.

Reference: /root/reference/src/bloombee/models/gemma4/ (the reference's
"gemma4" additionally varies head_dim per layer type; uniform-head-dim
gemma2 models are covered here, heterogeneous head_dim is future work).
Gemma RMSNorm weights are stored as (w) with output x_norm * (1 + w); they
are converted to (1 + w) at load so the shared rms_norm applies.
"""

from __future__ import annotations

import math
from typing import Any


from bloombee_tpu.models.auto import Family, register_family
from bloombee_tpu.models.checkpoint import read_tensor as _t, read_weight
from bloombee_tpu.models.spec import ModelSpec

_NORMS = (
    "input_layernorm",
    "post_attention_layernorm",
    "pre_feedforward_layernorm",
    "post_feedforward_layernorm",
)


def gemma2_spec_from_hf(config: Any) -> ModelSpec:
    layer_types = getattr(config, "layer_types", None)
    if layer_types:
        pattern = tuple(
            "sliding" if "sliding" in t else "full" for t in layer_types
        )
    else:
        # HF Gemma2: even layers sliding, odd layers full
        pattern = ("sliding", "full")
    return ModelSpec(
        family="gemma2",
        hidden_size=config.hidden_size,
        intermediate_size=config.intermediate_size,
        num_attention_heads=config.num_attention_heads,
        num_key_value_heads=config.num_key_value_heads,
        head_dim=config.head_dim,
        num_hidden_layers=config.num_hidden_layers,
        vocab_size=config.vocab_size,
        rms_norm_eps=config.rms_norm_eps,
        rope_theta=getattr(config, "rope_theta", 10000.0),
        tie_word_embeddings=True,
        layer_types=pattern,
        sliding_window=getattr(config, "sliding_window", 4096),
        attention_multiplier=getattr(config, "query_pre_attn_scalar", None)
        and getattr(config, "query_pre_attn_scalar") ** -0.5,
        logits_soft_cap=getattr(config, "final_logit_softcapping", 0.0) or 0.0,
        attn_logit_softcap=getattr(config, "attn_logit_softcapping", 0.0)
        or 0.0,
        embedding_multiplier=math.sqrt(config.hidden_size),
        mlp_type="gelu_tanh_gated",
        sandwich_norms=True,
    )


def _load_block(reader, layer_idx: int, dtype=None) -> dict:
    p = f"model.layers.{layer_idx}"
    params = {}
    for ln in _NORMS:
        params[ln] = 1.0 + _t(reader, f"{p}.{ln}.weight", dtype)
    for proj in ("q", "k", "v", "o"):
        params[f"{proj}_proj"] = read_weight(
            reader, f"{p}.self_attn.{proj}_proj.weight", f"{proj}_proj",
            dtype,
        )
    for proj in ("gate", "up", "down"):
        params[f"{proj}_proj"] = _t(
            reader, f"{p}.mlp.{proj}_proj.weight", dtype
        ).T
    return params


def _load_client(reader, dtype=None) -> dict:
    embed = _t(reader, "model.embed_tokens.weight", dtype)
    return {
        "embed": embed,
        "norm": 1.0 + _t(reader, "model.norm.weight", dtype),
        "lm_head": embed.T,
    }


register_family(
    Family(
        "gemma2", gemma2_spec_from_hf, loader=_load_block,
        client_loader=_load_client,
    )
)


# --------------------------------------------------------------- gemma3
def gemma3_spec_from_hf(config: Any) -> ModelSpec:
    """Gemma3 text tower: gemma2 structure + per-head q/k RMSNorm, no
    softcaps, and sliding layers roped with rope_local_base_freq.
    Multimodal gemma3 bundles nest the tower under text_config."""
    import dataclasses
    from types import SimpleNamespace

    text = getattr(config, "text_config", None)
    if text is not None:
        config = SimpleNamespace(**text) if isinstance(text, dict) else text
    base = gemma2_spec_from_hf(config)
    return dataclasses.replace(
        base,
        family="gemma3",
        qk_norm=True,
        logits_soft_cap=0.0,
        attn_logit_softcap=0.0,
        rope_theta=getattr(config, "rope_theta", 1_000_000.0),
        rope_local_theta=getattr(config, "rope_local_base_freq", 10_000.0),
        sliding_window=getattr(config, "sliding_window", 512),
    )


def _gemma3_prefix(reader) -> str:
    """Text-only checkpoints use model.*; multimodal bundles nest the tower
    under language_model.model.*."""
    if reader.has("model.embed_tokens.weight"):
        return "model"
    return "language_model.model"


def _load_block_gemma3(reader, layer_idx: int, dtype=None) -> dict:
    base = _gemma3_prefix(reader)
    p = f"{base}.layers.{layer_idx}"
    params = {}
    for ln in _NORMS:
        params[ln] = 1.0 + _t(reader, f"{p}.{ln}.weight", dtype)
    for proj in ("q", "k", "v", "o"):
        params[f"{proj}_proj"] = read_weight(
            reader, f"{p}.self_attn.{proj}_proj.weight", f"{proj}_proj",
            dtype,
        )
    for proj in ("gate", "up", "down"):
        params[f"{proj}_proj"] = _t(
            reader, f"{p}.mlp.{proj}_proj.weight", dtype
        ).T
    params["q_norm"] = 1.0 + _t(
        reader, f"{p}.self_attn.q_norm.weight", dtype
    )
    params["k_norm"] = 1.0 + _t(
        reader, f"{p}.self_attn.k_norm.weight", dtype
    )
    return params


def _load_client_gemma3(reader, dtype=None) -> dict:
    base = _gemma3_prefix(reader)
    embed = _t(reader, f"{base}.embed_tokens.weight", dtype)
    return {
        "embed": embed,
        "norm": 1.0 + _t(reader, f"{base}.norm.weight", dtype),
        "lm_head": embed.T,
    }


for _name in ("gemma3", "gemma3_text"):
    register_family(
        Family(
            _name, gemma3_spec_from_hf, loader=_load_block_gemma3,
            client_loader=_load_client_gemma3,
        )
    )
