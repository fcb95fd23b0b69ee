"""Bloom family: ALiBi attention, LayerNorm, fused-QKV, 4h GELU MLP.

Reference: /root/reference/src/bloombee/models/bloom/ (WrappedBloomBlock
wraps the HF module and converts KV layouts). Here the fused QKV weight is
split to q/k/v at load (HF layout: per head [q, k, v] interleaved) and the
block runs through the generic layer body with alibi=True (no rotary).
"""

from __future__ import annotations

from typing import Any


from bloombee_tpu.models.auto import Family, register_family
from bloombee_tpu.models.checkpoint import read_tensor as _t
from bloombee_tpu.models.spec import ModelSpec


def bloom_spec_from_hf(config: Any) -> ModelSpec:
    n_head = getattr(config, "n_head", None) or config.num_attention_heads
    hidden = config.hidden_size
    return ModelSpec(
        family="bloom",
        hidden_size=hidden,
        intermediate_size=4 * hidden,
        num_attention_heads=n_head,
        num_key_value_heads=n_head,
        head_dim=hidden // n_head,
        num_hidden_layers=getattr(config, "n_layer", None)
        or config.num_hidden_layers,
        vocab_size=config.vocab_size,
        rms_norm_eps=getattr(config, "layer_norm_epsilon", 1e-5),
        tie_word_embeddings=True,
        alibi=True,
        norm_type="ln",
        mlp_type="gelu_tanh",
    )


def _load_block(reader, layer_idx: int, dtype=None) -> dict:
    p = f"h.{layer_idx}"
    if not reader.has(f"{p}.input_layernorm.weight"):
        p = f"transformer.h.{layer_idx}"
    params = {}
    for ln in ("input_layernorm", "post_attention_layernorm"):
        params[ln] = _t(reader, f"{p}.{ln}.weight", dtype)
        params[f"{ln}_bias"] = _t(reader, f"{p}.{ln}.bias", dtype)
    # fused qkv: rows ordered per-head [q(hd), k(hd), v(hd)]
    w = _t(reader, f"{p}.self_attention.query_key_value.weight", dtype)
    b = _t(reader, f"{p}.self_attention.query_key_value.bias", dtype)
    d = w.shape[1]
    n_head = reader.config.get("n_head") or reader.config.get(
        "num_attention_heads"
    )
    head_dim = d // n_head
    w4 = w.reshape(n_head, 3, head_dim, d)
    b4 = b.reshape(n_head, 3, head_dim)
    # q/k/v are stored output-major, as these rows lie (models/layout.py)
    params["q_proj"] = w4[:, 0].reshape(n_head * head_dim, d)
    params["k_proj"] = w4[:, 1].reshape(n_head * head_dim, d)
    params["v_proj"] = w4[:, 2].reshape(n_head * head_dim, d)
    params["q_bias"] = b4[:, 0].reshape(-1)
    params["k_bias"] = b4[:, 1].reshape(-1)
    params["v_bias"] = b4[:, 2].reshape(-1)
    params["o_proj"] = _t(reader, f"{p}.self_attention.dense.weight", dtype).T
    params["o_bias"] = _t(reader, f"{p}.self_attention.dense.bias", dtype)
    params["up_proj"] = _t(reader, f"{p}.mlp.dense_h_to_4h.weight", dtype).T
    params["up_bias"] = _t(reader, f"{p}.mlp.dense_h_to_4h.bias", dtype)
    params["down_proj"] = _t(reader, f"{p}.mlp.dense_4h_to_h.weight", dtype).T
    params["down_bias"] = _t(reader, f"{p}.mlp.dense_4h_to_h.bias", dtype)
    return params


def _load_client(reader, dtype=None) -> dict:
    pref = "" if reader.has("word_embeddings.weight") else "transformer."
    out = {
        "embed": _t(reader, f"{pref}word_embeddings.weight", dtype),
        "embed_norm": _t(
            reader, f"{pref}word_embeddings_layernorm.weight", dtype
        ),
        "embed_norm_bias": _t(
            reader, f"{pref}word_embeddings_layernorm.bias", dtype
        ),
        "norm": _t(reader, f"{pref}ln_f.weight", dtype),
        "norm_bias": _t(reader, f"{pref}ln_f.bias", dtype),
    }
    out["lm_head"] = out["embed"].T  # tied
    return out


register_family(
    Family(
        "bloom", bloom_spec_from_hf, loader=_load_block,
        client_loader=_load_client,
    )
)
