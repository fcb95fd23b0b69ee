"""Qwen3-Next family: gated-DeltaNet linear-attention layers with a gated
full-attention layer every `full_attention_interval`-th, a sparse MLP with
ONE gated shared expert in every layer (HF `modeling_qwen3_next.py`).

Layer i is "full" if (i + 1) % interval == 0, else "linear"
(`ModelSpec.layer_types`). Every norm's stored weight is zero-centred,
`x / rms * (1 + w)` (`norm_type="rms1p"`), except the mixer's gated norm.

What a layer holds, stored the way the step programs read it
(models/layout.py):

- full: `q_proj` makes per head a 256-wide query and a 256-wide output gate;
  split here into `q_proj` (the query rows) and `q_gate_proj` (the gate
  rows), both [out, in] like k/v, so the program reshapes no product;
  `q_norm` / `k_norm`; rotary on the first `partial_rotary_factor` of a head.
- linear: `in_proj_qkvz` lays its rows out per KEY head as q | k | v (its
  value heads') | z; regrouped here to `gdn_in_proj` [D, q | k | v | z]
  with every segment head-major, which is the order `conv1d`'s channels
  have (q | k | v) and every cut a whole number of lanes. `in_proj_ba`
  (b | a per key head, 64 rows in all) becomes `gdn_ba_proj` [D, 256]: b in
  columns 0.., a in columns 128.., zeros between, so neither cut re-lays
  the stack. `gdn_conv_w` [K, C] taps, `gdn_a_log` / `gdn_dt_bias` float32,
  `gdn_norm` [value_dim], `gdn_out_proj` [in, out].
- both: `router` [D, E] over ALL the model's experts, the stacks `experts_*`
  of the experts this server HOLDS (`run_server --experts`), the shared
  expert `shared_*` and its gate `shared_gate_w` [D].

Layers of the two kinds in one span load as one stack a POSITION in the
period (checkpoint.load_span_params, layout.py `linear_prefix`); the K/V arena has a row a full
layer, the state arena a row a linear one (`ModelSpec.arena_layers`). The
multi-token-prediction head of the published checkpoint (`mtp.*`) is not
loaded: nothing here predicts more than one token a step.
"""

from __future__ import annotations

import functools
from typing import Any

import numpy as np

from bloombee_tpu.models.auto import Family, register_family
from bloombee_tpu.models.checkpoint import (
    held_experts,
    read_tensor as _t,
    read_weight,
    refine_held,
    stack_expert_weights,
)
from bloombee_tpu.models.layout import LANES
from bloombee_tpu.models.spec import GdnSpec, ModelSpec


def qwen3_next_spec_from_hf(config: Any) -> ModelSpec:
    def get(name, default=None):
        return getattr(config, name, default)

    if get("attention_bias", False):
        raise NotImplementedError("qwen3_next with attention_bias")
    if get("mlp_only_layers") or get("decoder_sparse_step", 1) != 1:
        raise NotImplementedError(
            "qwen3_next with dense interleaved layers (mlp_only_layers / "
            "decoder_sparse_step != 1) is not supported"
        )
    if get("rope_scaling"):
        raise NotImplementedError("qwen3_next with rope_scaling")
    if get("use_sliding_window", False):
        raise NotImplementedError("qwen3_next with a sliding window")
    interval = get("full_attention_interval", 4)
    types = get("layer_types")
    period = ("linear",) * (interval - 1) + ("full",)
    if types and tuple(
        "full" if t == "full_attention" else "linear" for t in types
    ) != period * (len(types) // interval):
        raise NotImplementedError(
            "qwen3_next layer_types other than (interval - 1) linear layers "
            "then a full one"
        )
    head_dim = get("head_dim") or config.hidden_size // config.num_attention_heads
    return ModelSpec(
        family="qwen3_next",
        hidden_size=config.hidden_size,
        intermediate_size=config.moe_intermediate_size,
        num_attention_heads=config.num_attention_heads,
        num_key_value_heads=config.num_key_value_heads,
        head_dim=head_dim,
        num_hidden_layers=config.num_hidden_layers,
        vocab_size=config.vocab_size,
        rms_norm_eps=config.rms_norm_eps,
        rope_theta=float(get("rope_theta", 10000000.0)),
        tie_word_embeddings=bool(get("tie_word_embeddings", False)),
        max_position_embeddings=get("max_position_embeddings", 4096),
        norm_type="rms1p",
        qk_norm=True,
        attn_gate=True,
        rotary_dim=int(head_dim * get("partial_rotary_factor", 1.0)),
        layer_types=period,
        num_experts=config.num_experts,
        num_experts_per_tok=config.num_experts_per_tok,
        moe_pre_softmax=True,
        moe_norm_topk=bool(get("norm_topk_prob", True)),
        moe_intermediate_size=config.moe_intermediate_size,
        moe_shared_intermediate=get("shared_expert_intermediate_size") or 0,
        moe_shared_gate=True,
        gdn=GdnSpec(
            key_heads=config.linear_num_key_heads,
            value_heads=config.linear_num_value_heads,
            key_dim=config.linear_key_head_dim,
            value_dim=config.linear_value_head_dim,
            conv=config.linear_conv_kernel_dim,
        ),
    )


def _regroup_qkvz(w: np.ndarray, gdn: GdnSpec) -> np.ndarray:
    """torch [key_heads * (2 dk + 2 r dv), D] (per key head q | k | v | z)
    -> [D, q | k | v | z], each segment head-major."""
    r = gdn.value_heads // gdn.key_heads
    dk, dv = gdn.key_dim, gdn.value_dim
    per = w.reshape(gdn.key_heads, 2 * dk + 2 * r * dv, -1)
    cuts = np.cumsum([dk, dk, r * dv])
    parts = np.split(per, cuts, axis=1)  # q, k, v, z: [Hk, width, D]
    return np.concatenate(
        [p.reshape(-1, p.shape[-1]) for p in parts]
    ).T


def _regroup_ba(w: np.ndarray, gdn: GdnSpec) -> np.ndarray:
    """torch [key_heads * 2 r, D] (per key head b | a) -> [D, 2 * LANES]:
    b of every value head from column 0, a from column LANES."""
    r = gdn.value_heads // gdn.key_heads
    per = w.reshape(gdn.key_heads, 2 * r, -1)
    out = np.zeros((2 * LANES, w.shape[-1]), w.dtype)
    out[: gdn.value_heads] = per[:, :r].reshape(gdn.value_heads, -1)
    out[LANES : LANES + gdn.value_heads] = per[:, r:].reshape(
        gdn.value_heads, -1
    )
    return out.T


def _load_block(reader, layer_idx: int, dtype=None) -> dict:
    import jax.numpy as jnp

    spec = qwen3_next_spec_from_hf(_config(reader))
    gdn = spec.gdn
    if gdn.value_heads > LANES:
        raise NotImplementedError("qwen3_next with more than 128 value heads")
    p = f"model.layers.{layer_idx}"
    params = {
        "input_layernorm": _t(reader, f"{p}.input_layernorm.weight", dtype),
        "post_attention_layernorm": _t(
            reader, f"{p}.post_attention_layernorm.weight", dtype
        ),
    }
    if spec.layer_type(layer_idx) == "full":
        a = f"{p}.self_attn"
        heads, hd = spec.num_attention_heads, spec.head_dim
        q = np.asarray(reader.tensor(f"{a}.q_proj.weight")).reshape(
            heads, 2 * hd, -1
        )
        params["q_proj"] = jnp.asarray(
            q[:, :hd].reshape(heads * hd, -1), dtype=dtype
        )
        params["q_gate_proj"] = jnp.asarray(
            q[:, hd:].reshape(heads * hd, -1), dtype=dtype
        )
        for proj in ("k", "v", "o"):
            params[f"{proj}_proj"] = read_weight(
                reader, f"{a}.{proj}_proj.weight", f"{proj}_proj", dtype
            )
        for name in ("q_norm", "k_norm"):
            params[name] = _t(reader, f"{a}.{name}.weight", dtype)
    else:
        m = f"{p}.linear_attn"
        params["gdn_in_proj"] = jnp.asarray(
            _regroup_qkvz(np.asarray(reader.tensor(f"{m}.in_proj_qkvz.weight")),
                          gdn), dtype=dtype,
        )
        params["gdn_ba_proj"] = jnp.asarray(
            _regroup_ba(np.asarray(reader.tensor(f"{m}.in_proj_ba.weight")),
                        gdn), dtype=dtype,
        )
        # torch [C, 1, K] -> [K, C]: tap k of every channel is one row
        params["gdn_conv_w"] = _t(reader, f"{m}.conv1d.weight", dtype)[:, 0, :].T
        # the gates' own vectors stay float32 whatever the compute dtype
        params["gdn_a_log"] = _t(reader, f"{m}.A_log", jnp.float32)
        params["gdn_dt_bias"] = _t(reader, f"{m}.dt_bias", jnp.float32)
        params["gdn_norm"] = _t(reader, f"{m}.norm.weight", dtype)
        params["gdn_out_proj"] = _t(reader, f"{m}.out_proj.weight", dtype).T
    params["router"] = _t(reader, f"{p}.mlp.gate.weight", dtype).T  # [D, E]
    first, count = held_experts(reader, "num_experts")
    params.update(
        stack_expert_weights(
            reader, f"{p}.mlp.experts.{{}}", "gate_proj", "up_proj",
            "down_proj", count, dtype, first=first,
        )
    )
    for proj in ("gate", "up", "down"):
        params[f"shared_{proj}"] = _t(
            reader, f"{p}.mlp.shared_expert.{proj}_proj.weight", dtype
        ).T
    params["shared_gate_w"] = _t(
        reader, f"{p}.mlp.shared_expert_gate.weight", dtype
    )[0]
    return params


def _config(reader):
    from types import SimpleNamespace

    return SimpleNamespace(**reader.config)


def _load_client(reader, dtype=None) -> dict:
    """Embedding, final norm with the 1 of `1 + w` folded in (the client's
    head runs the plain RMSNorm), head."""
    import jax.numpy as jnp

    embed = _t(reader, "model.embed_tokens.weight", dtype)
    norm = 1.0 + _t(reader, "model.norm.weight", jnp.float32)
    head = (
        _t(reader, "lm_head.weight", dtype).T
        if reader.has("lm_head.weight") else embed.T
    )
    return {
        "embed": embed,
        "norm": norm if dtype is None else norm.astype(dtype),
        "lm_head": head,
    }


register_family(
    Family(
        "qwen3_next", qwen3_next_spec_from_hf, loader=_load_block,
        client_loader=_load_client,
        refine_spec=functools.partial(refine_held, config_key="num_experts"),
    )
)
