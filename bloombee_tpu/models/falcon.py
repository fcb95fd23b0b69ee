"""Falcon family: rotary + MQA/GQA, LayerNorm, parallel attention/MLP.

Reference: /root/reference/src/bloombee/models/falcon/ (WrappedFalconBlock).
Supports the falcon-7b shape: multi_query fused QKV ([H q-heads | 1 k | 1 v]
rows), parallel residual with a single shared input LayerNorm, bias-free
linears, exact-GELU 4h MLP.
"""

from __future__ import annotations

from typing import Any


from bloombee_tpu.models.auto import Family, register_family
from bloombee_tpu.models.checkpoint import read_tensor as _t
from bloombee_tpu.models.spec import ModelSpec


def falcon_spec_from_hf(config: Any) -> ModelSpec:
    n_head = config.num_attention_heads
    hidden = config.hidden_size
    if getattr(config, "alibi", False) or getattr(config, "bias", False):
        raise NotImplementedError(
            "falcon-rw variants (alibi/bias) are not supported yet"
        )
    new_arch = bool(getattr(config, "new_decoder_architecture", False))
    if new_arch:
        # falcon-40b/180b: grouped GQA fused QKV + (usually) two parallel
        # LayerNorms (ln_attn feeds attention, ln_mlp feeds the MLP)
        n_kv = getattr(config, "num_kv_heads", None) or n_head
        n_ln = getattr(config, "num_ln_in_parallel_attn", None)
        if n_ln is None:
            n_ln = 2
    else:
        n_kv = 1 if getattr(config, "multi_query", True) else n_head
        n_ln = 1
    return ModelSpec(
        family="falcon",
        hidden_size=hidden,
        intermediate_size=4 * hidden,
        num_attention_heads=n_head,
        num_key_value_heads=n_kv,
        head_dim=hidden // n_head,
        num_hidden_layers=config.num_hidden_layers,
        vocab_size=config.vocab_size,
        rms_norm_eps=getattr(config, "layer_norm_epsilon", 1e-5),
        rope_theta=getattr(config, "rope_theta", 10000.0),
        tie_word_embeddings=True,
        norm_type="ln",
        mlp_type="gelu",
        parallel_attn=getattr(config, "parallel_attn", True) or new_arch,
        num_ln_in_parallel_attn=n_ln,
        alibi=getattr(config, "alibi", False),
    )


def _load_block(reader, layer_idx: int, dtype=None) -> dict:
    p = f"transformer.h.{layer_idx}"
    n_head = reader.config["num_attention_heads"]
    d = reader.config["hidden_size"]
    head_dim = d // n_head
    new_arch = bool(reader.config.get("new_decoder_architecture", False))
    params = {}
    if reader.has(f"{p}.ln_attn.weight"):
        # falcon new-arch dual norms: ln_attn feeds attention (our shared
        # "input_layernorm" slot), ln_mlp feeds the MLP
        params["input_layernorm"] = _t(reader, f"{p}.ln_attn.weight", dtype)
        params["input_layernorm_bias"] = _t(
            reader, f"{p}.ln_attn.bias", dtype
        )
        params["mlp_layernorm"] = _t(reader, f"{p}.ln_mlp.weight", dtype)
        params["mlp_layernorm_bias"] = _t(reader, f"{p}.ln_mlp.bias", dtype)
    else:
        params["input_layernorm"] = _t(
            reader, f"{p}.input_layernorm.weight", dtype
        )
        params["input_layernorm_bias"] = _t(
            reader, f"{p}.input_layernorm.bias", dtype
        )
    # q/k/v are stored output-major, as the fused matrix's rows lie
    # (models/layout.py)
    w = _t(reader, f"{p}.self_attention.query_key_value.weight", dtype)
    if new_arch:
        # grouped layout: per kv group [n_rep q rows | 1 k row | 1 v row]
        # (HF Falcon _split_heads for new_decoder_architecture)
        n_kv = reader.config.get("num_kv_heads") or n_head
        n_rep = n_head // n_kv
        grouped = w.reshape(n_kv, n_rep + 2, head_dim, d)
        params["q_proj"] = grouped[:, :-2].reshape(n_kv * n_rep * head_dim, d)
        params["k_proj"] = grouped[:, -2].reshape(n_kv * head_dim, d)
        params["v_proj"] = grouped[:, -1].reshape(n_kv * head_dim, d)
    else:
        n_kv = 1 if reader.config.get("multi_query", True) else n_head
        # rows: H query heads, then n_kv k heads, then n_kv v heads
        q_rows = n_head * head_dim
        kv_rows = n_kv * head_dim
        params["q_proj"] = w[:q_rows]
        params["k_proj"] = w[q_rows : q_rows + kv_rows]
        params["v_proj"] = w[q_rows + kv_rows :]
    params["o_proj"] = _t(reader, f"{p}.self_attention.dense.weight", dtype).T
    params["up_proj"] = _t(reader, f"{p}.mlp.dense_h_to_4h.weight", dtype).T
    params["down_proj"] = _t(reader, f"{p}.mlp.dense_4h_to_h.weight", dtype).T
    return params


def _load_client(reader, dtype=None) -> dict:
    out = {
        "embed": _t(reader, "transformer.word_embeddings.weight", dtype),
        "norm": _t(reader, "transformer.ln_f.weight", dtype),
        "norm_bias": _t(reader, "transformer.ln_f.bias", dtype),
    }
    if reader.has("lm_head.weight"):
        out["lm_head"] = _t(reader, "lm_head.weight", dtype).T
    else:
        out["lm_head"] = out["embed"].T
    return out


register_family(
    Family(
        "falcon", falcon_spec_from_hf, loader=_load_block,
        client_loader=_load_client,
    )
)
