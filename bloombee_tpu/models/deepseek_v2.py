"""DeepSeek-V2 family: multi-head latent attention (MLA) and, after
`first_k_dense_replace` dense layers, DeepSeekMoE layers (routed experts
behind a group-limited softmax router, beside shared experts). HF
`modeling_deepseek.py` as published with the checkpoint.

What a layer holds, stored the way the step programs read it
(models/layout.py): `q_a_proj`, `kv_a_proj` as the checkpoint has them
([out, in]); `kv_b_proj` split on the host into its key half
`kv_b_k` and its value half `kv_b_v`, each [heads, dim, kv_rank], which the
ABSORBED attention contracts directly, and `q_b_proj` into the rows that
make q_nope (`q_b_nope`) and those that make q_pe (`q_b_rope`)
(runtime/layer_body.py); `o_proj` [in, out]. With `q_lora_rank` null
(V2-Lite) there is no `q_a_proj` / `q_a_norm` and the two keys hold the
full-rank `q_proj`'s rows, fed the hidden rows (`MlaSpec.q_rank` 0). A dense layer has `gate/up/down_proj`; a sparse one `router_t`
[E, D] over ALL the model's experts, the stacks `experts_*` of the experts
this server HOLDS ([first, first + count) of the published numbering:
`run_server --experts`, default every expert the checkpoint has) and the
fused shared experts `shared_*`. Layers of the two kinds in one span load as
two stacks (checkpoint.load_span_params).

The cache keeps the latent (after its norm) and the rotary key (after
rotary) a token: `ModelSpec.mla` declares that payload and kv/arena.py
makes it.
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
    split_query_rows,
    stack_expert_weights,
)
from bloombee_tpu.models.spec import MlaSpec, ModelSpec
from bloombee_tpu.ops.rotary import deinterleave


def deepseek_v2_spec_from_hf(config: Any) -> ModelSpec:
    def get(name, default=None):
        return getattr(config, name, default)

    if get("attention_bias", False):
        raise NotImplementedError("deepseek_v2 with attention_bias")
    if get("moe_layer_freq", 1) != 1:
        raise NotImplementedError("deepseek_v2 with moe_layer_freq != 1")
    if get("norm_topk_prob", False):
        raise NotImplementedError("deepseek_v2 with norm_topk_prob")
    rs = get("rope_scaling") or {}
    kind = rs.get("type", rs.get("rope_type"))
    if rs and kind != "yarn":
        raise NotImplementedError(f"deepseek_v2 rope_scaling {kind!r}")
    experts = get("n_routed_experts") or 0
    grouped = get("topk_method", "greedy") == "group_limited_greedy"
    return ModelSpec(
        family="deepseek_v2",
        hidden_size=config.hidden_size,
        intermediate_size=config.intermediate_size,
        num_attention_heads=config.num_attention_heads,
        num_key_value_heads=config.num_attention_heads,
        head_dim=config.qk_nope_head_dim + config.qk_rope_head_dim,
        num_hidden_layers=config.num_hidden_layers,
        vocab_size=config.vocab_size,
        rms_norm_eps=config.rms_norm_eps,
        rope_theta=float(get("rope_theta", 10000.0)),
        tie_word_embeddings=bool(get("tie_word_embeddings", False)),
        max_position_embeddings=get("max_position_embeddings", 4096),
        num_experts=experts,
        num_experts_per_tok=get("num_experts_per_tok") or 0,
        moe_groups=get("n_group", 0) if grouped else 0,
        moe_topk_groups=get("topk_group", 0) if grouped else 0,
        moe_route_scale=float(get("routed_scaling_factor", 1.0)),
        moe_shared_intermediate=(get("n_shared_experts") or 0)
        * (get("moe_intermediate_size") or 0),
        moe_intermediate_size=get("moe_intermediate_size") or 0,
        first_dense_layers=get("first_k_dense_replace", 0) if experts else 0,
        # the softmax-over-all router; with no groups it is Qwen3-MoE's
        # form without renormalisation, times the scale
        moe_pre_softmax=True,
        mla=MlaSpec(
            # null: V2-Lite's ONE full-rank q_proj, no query norm
            q_rank=get("q_lora_rank") or 0,
            kv_rank=config.kv_lora_rank,
            nope_dim=config.qk_nope_head_dim,
            rope_dim=config.qk_rope_head_dim,
            v_dim=config.v_head_dim,
            rope_factor=float(rs.get("factor", 1.0)),
            rope_original_max=int(
                rs.get("original_max_position_embeddings", 4096)
            ),
            rope_beta_fast=float(rs.get("beta_fast", 32)),
            rope_beta_slow=float(rs.get("beta_slow", 1)),
            rope_mscale=float(rs.get("mscale", 1.0)),
            rope_mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)),
        ),
    )


# the router's width and the experts held, read off the checkpoint
refine_spec = functools.partial(refine_held, config_key="n_routed_experts")


def _load_block(reader, layer_idx: int, dtype=None) -> dict:
    import jax.numpy as jnp

    cfg = reader.config
    p = f"model.layers.{layer_idx}"
    a = f"{p}.self_attn"
    params = {
        "input_layernorm": _t(reader, f"{p}.input_layernorm.weight", dtype),
        "post_attention_layernorm": _t(
            reader, f"{p}.post_attention_layernorm.weight", dtype
        ),
        "kv_a_norm": _t(reader, f"{a}.kv_a_layernorm.weight", dtype),
    }
    params["o_proj"] = read_weight(reader, f"{a}.o_proj.weight", "o_proj", dtype)
    heads = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    # the rotary dims are interleaved pairs, which the published code
    # de-interleaves before its half-rotation: done here, once, on the rows
    # that make them (the step then rotates halves and gathers nothing)
    perm = deinterleave(rope)
    kv_a = np.asarray(reader.tensor(f"{a}.kv_a_proj_with_mqa.weight"))
    kvr = cfg["kv_lora_rank"]
    params["kv_a_proj"] = jnp.asarray(
        np.concatenate([kv_a[:kvr], kv_a[kvr:][perm]]), dtype=dtype
    )
    if cfg.get("q_lora_rank"):
        params["q_a_norm"] = _t(reader, f"{a}.q_a_layernorm.weight", dtype)
        params["q_a_proj"] = read_weight(
            reader, f"{a}.q_a_proj.weight", "q_a_proj", dtype
        )
        q_up = reader.tensor(f"{a}.q_b_proj.weight")
    else:  # ONE full-rank projection: the same rows, fed the hidden rows
        q_up = reader.tensor(f"{a}.q_proj.weight")
    params.update(split_query_rows(q_up, heads, nope, rope, dtype, perm))
    # kv_b: torch [heads * (nope + v), kv_rank] -> the key half and the value
    # half, split on the host so the device never holds the fused tensor too
    kv_b = np.asarray(reader.tensor(f"{a}.kv_b_proj.weight")).reshape(
        heads, nope + vd, cfg["kv_lora_rank"]
    )
    params["kv_b_k"] = jnp.asarray(kv_b[:, :nope], dtype=dtype)
    params["kv_b_v"] = jnp.asarray(kv_b[:, nope:], dtype=dtype)
    if not reader.has(f"{p}.mlp.gate.weight"):  # a leading dense layer
        for proj in ("gate", "up", "down"):
            params[f"{proj}_proj"] = _t(
                reader, f"{p}.mlp.{proj}_proj.weight", dtype
            ).T
        return params
    # output-major [E, D] as the checkpoint has it: 160 columns are no
    # whole lanes, and stored [D, E] every program copied the stack
    params["router_t"] = _t(reader, f"{p}.mlp.gate.weight", dtype)
    first, count = held_experts(reader, "n_routed_experts")
    params.update(
        stack_expert_weights(
            reader, f"{p}.mlp.experts.{{}}", "gate_proj", "up_proj",
            "down_proj", count, dtype, first=first,
        )
    )
    if cfg.get("n_shared_experts"):
        for proj in ("gate", "up", "down"):
            params[f"shared_{proj}"] = _t(
                reader, f"{p}.mlp.shared_experts.{proj}_proj.weight", dtype
            ).T
    return params


register_family(
    Family(
        "deepseek_v2", deepseek_v2_spec_from_hf, loader=_load_block,
        refine_spec=refine_spec,
    )
)
