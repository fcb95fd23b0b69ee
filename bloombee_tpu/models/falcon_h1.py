"""Falcon-H1 family: a Mamba-2 state-space mixer BESIDE attention in every
layer, both reading the same normed input, then a gated-SiLU MLP; muP-style
scalar multipliers on nearly every edge (HF `modeling_falcon_h1.py`).

No relation to the falcon-7b/40b family in models/falcon.py. The layer's
attention half and MLP are the llama layout (`pre_ff_layernorm` takes
`post_attention_layernorm`'s key: the norm before the MLP); the mixer's
tensors take `ssm_*` keys and `ModelSpec.ssm` describes it. Multipliers are
applied where the published code applies them (runtime/layer_body.py), none
is folded into a weight. The client's final norm is `final_layernorm`, its
logits are scaled by `lm_head_multiplier`.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any

import numpy as np

from bloombee_tpu.models.auto import Family, register_family
from bloombee_tpu.models.checkpoint import read_tensor as _t, read_weight
from bloombee_tpu.models.layout import lane_padded
from bloombee_tpu.models.spec import ModelSpec, SsmSpec


def falcon_h1_spec_from_hf(config: Any) -> ModelSpec:
    def get(name, default=None):
        return getattr(config, name, default)

    for flag in ("attention_bias", "mlp_bias", "projectors_bias",
                 "mamba_proj_bias"):
        if get(flag, False):
            raise NotImplementedError(f"falcon_h1 with {flag} is not supported")
    if not get("mamba_rms_norm", True) or get("mamba_norm_before_gate", False):
        raise NotImplementedError(
            "falcon_h1 without the gated RMSNorm after the scan (gate "
            "before norm) is not supported"
        )
    if get("rope_scaling"):
        raise NotImplementedError("falcon_h1 with rope_scaling is not supported")
    if not get("mamba_conv_bias", True):
        raise NotImplementedError("falcon_h1 without a conv bias is not supported")
    d_ssm = get("mamba_d_ssm") or int(get("mamba_expand") * config.hidden_size)
    heads = config.mamba_n_heads
    return ModelSpec(
        family="falcon_h1",
        hidden_size=config.hidden_size,
        intermediate_size=config.intermediate_size,
        num_attention_heads=config.num_attention_heads,
        num_key_value_heads=config.num_key_value_heads,
        head_dim=get("head_dim")
        or config.hidden_size // config.num_attention_heads,
        num_hidden_layers=config.num_hidden_layers,
        vocab_size=config.vocab_size,
        rms_norm_eps=config.rms_norm_eps,
        rope_theta=float(get("rope_theta", 10000.0)),
        tie_word_embeddings=bool(get("tie_word_embeddings", False)),
        max_position_embeddings=get("max_position_embeddings", 4096),
        embedding_multiplier=float(get("embedding_multiplier", 1.0)),
        attention_in_multiplier=float(get("attention_in_multiplier", 1.0)),
        key_multiplier=float(get("key_multiplier", 1.0)),
        attention_out_multiplier=float(get("attention_out_multiplier", 1.0)),
        mlp_multipliers=tuple(
            float(x) for x in get("mlp_multipliers", (1.0, 1.0))
        ),
        lm_head_multiplier=float(get("lm_head_multiplier", 1.0)),
        ssm=SsmSpec(
            heads=heads,
            head_dim=d_ssm // heads,
            state=config.mamba_d_state,
            groups=config.mamba_n_groups,
            conv=config.mamba_d_conv,
            chunk=get("mamba_chunk_size", 128),
            in_multiplier=float(get("ssm_in_multiplier", 1.0)),
            multipliers=tuple(
                float(x) for x in get("ssm_multipliers", (1.0,) * 5)
            ),
            out_multiplier=float(get("ssm_out_multiplier", 1.0)),
        ),
    )


def _load_block(reader, layer_idx: int, dtype=None) -> dict:
    p = f"model.layers.{layer_idx}"
    params = {
        "input_layernorm": _t(reader, f"{p}.input_layernorm.weight", dtype),
        "post_attention_layernorm": _t(
            reader, f"{p}.pre_ff_layernorm.weight", dtype
        ),
    }
    for proj in ("q", "k", "v", "o"):
        params[f"{proj}_proj"] = read_weight(
            reader, f"{p}.self_attn.{proj}_proj.weight", f"{proj}_proj",
            dtype,
        )
    for proj in ("gate", "up", "down"):
        params[f"{proj}_proj"] = _t(
            reader, f"{p}.feed_forward.{proj}_proj.weight", dtype
        ).T
    m = f"{p}.mamba"
    # in_proj is stored with zero columns up to whole lanes, or every step
    # re-lays the stack out before its scan (models/layout.py); the rows
    # are added on the host, so the device never holds a second copy
    import jax.numpy as jnp

    ssm = falcon_h1_spec_from_hf(SimpleNamespace(**reader.config)).ssm
    w = reader.tensor(f"{m}.in_proj.weight")  # [proj_dim, D]
    w = np.pad(w, ((0, lane_padded(ssm.proj_dim) - w.shape[0]), (0, 0)))
    params["ssm_in_proj"] = jnp.asarray(w, dtype=dtype).T
    params["ssm_out_proj"] = _t(reader, f"{m}.out_proj.weight", dtype).T
    # torch [C, 1, K] -> [K, C]: tap k of every channel is one row
    params["ssm_conv_w"] = _t(reader, f"{m}.conv1d.weight", dtype)[:, 0, :].T
    params["ssm_conv_b"] = _t(reader, f"{m}.conv1d.bias", dtype)
    params["ssm_norm"] = _t(reader, f"{m}.norm.weight", dtype)
    # the recurrence's own vectors stay float32 whatever the compute dtype
    for key, name in (("ssm_a_log", "A_log"), ("ssm_d", "D"),
                      ("ssm_dt_bias", "dt_bias")):
        params[key] = _t(reader, f"{m}.{name}", jnp.float32)
    return params


register_family(
    Family(
        "falcon_h1",
        falcon_h1_spec_from_hf,
        loader=_load_block,
        client_names={
            "embed": "model.embed_tokens.weight",
            "norm": "model.final_layernorm.weight",
            "lm_head": "lm_head.weight",
        },
    )
)
