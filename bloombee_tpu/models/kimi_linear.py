"""Kimi Linear family (`model_type: "kimi_linear"`, Moonshot's
Kimi-Linear-48B-A3B): Kimi delta attention (KDA) layers, a delta rule whose
decay is a vector a key channel, with a latent-attention (MLA) layer that
has NO positional encoding after every three; after `first_k_dense_replace`
dense layers, sparse layers behind a sigmoid router whose choice a bias
corrects, beside one ungated shared expert. The published `modeling_kimi.py`
as the checkpoint's config.json describes it (arXiv:2510.26692):

  h += mixer(rms(h)) ; h += mlp(rms(h))            plain RMSNorm weights
  KDA (layers `linear_attn_config.kda_layers`, 1-indexed), x = rms(h):
    q, k, v = silu(conv4(x Wq)), silu(conv4(x Wk)), silu(conv4(x Wv))
    q = l2norm(q) * d_k ** -0.5 ; k = l2norm(k)      per head
    g = -exp(A_log[head]) * softplus((x W_fa) W_fb + dt_bias)  [H, d_k]
    beta = sigmoid(x W_b)                                      [H]
    S = diag(exp(g_t)) S ; u = beta_t (v_t - S^T k_t) ; S += k_t u^T ;
    o_t = S^T q_t                       (ops/linear_attention.py `kda_*`)
    out = (rms_head(o; w) * sigmoid((x W_ga) W_gb)) W_o
  MLA (layers `full_attn_layers`): `q_lora_rank: null` (ONE q_proj, no
    query norm), kv_a_proj_with_mqa -> latent | shared key, RMSNorm on the
    latent, kv_b_proj, softmax scale (nope + rope) ** -0.5, and
    `mla_use_nope`: no rotary anywhere; positions come from the KDA layers
  MLP: dense below `first_k_dense_replace`; else scores = sigmoid(x Wr) in
    float32, the top-k of scores + e_score_correction_bias (one group), the
    weights the unbiased scores renormalised (`moe_renormalize`) times
    `routed_scaling_factor`, plus the shared expert

Everything is a switch the layer body already reads: `gdn` (GdnSpec with
`channel_decay` and `gate_rank`: runtime/layer_body.py `_gdn_mixer`, ONE
mixer for this family and qwen3_next), `mla` (MlaSpec with `q_rank` 0 and
`rope` False), `moe_router="sigmoid"` with `expert_bias`, `moe_held`
(`run_server --experts`), `first_dense_layers`.

What a layer holds, stored the way the step programs read it
(models/layout.py):

- linear: `gdn_in_proj` [D, q | k | v] (the three projections side by side,
  the order the convolution's channels have), `gdn_conv_w` [K, q | k | v]
  taps, `gdn_low_proj` [D, 3 * LANES]: f_a | g_a | b side by side (b's
  `heads` columns from 2 * LANES, zeros after them), ONE product for the
  three narrow ones; `gdn_f_b_proj`, `gdn_g_b_proj` [rank, heads * d_k];
  `gdn_a_log` [heads], `gdn_dt_bias` [heads * d_k] float32; `gdn_norm`
  [d_v]; `gdn_out_proj` [in, out].
- full: `q_b_nope` / `q_b_rope` (q_proj's rows that make a head's first
  `qk_nope_head_dim` and last `qk_rope_head_dim` query dims, [out, in]:
  deepseek_v2's keys, fed the hidden rows where `q_rank` is 0), `kv_a_proj`,
  `kv_a_norm`, `kv_b_k` / `kv_b_v` [heads, dim, kv_rank], `o_proj`.
- MLP: `gate/up/down_proj`, or `router_t` [E, D] over ALL the model's
  experts, `expert_bias` [E] float32, the stacks `experts_*` of the experts
  this server HOLDS, the shared expert `shared_*`.

Layer kinds are LISTS in the config, and the published model ends on a short
period (24, 25 linear, 26 full): `ModelSpec.layer_types` is every layer's
kind, a span holds whole periods (`ModelSpec.period_runs`), and its params
are one stack a position in a period, the run of periods that differ from
the rest (layer 0's dense MLP; the short tail) under `lead.`.

The tensor names below are the published checkpoint's as remembered; none
could be confirmed here (no network): cellbench/configs/
kimi-linear-48b-ep4-span8.json lists them under `assumed`.
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
from bloombee_tpu.models.layout import LANES
from bloombee_tpu.models.spec import GdnSpec, MlaSpec, ModelSpec

ROUTER = "block_sparse_moe.gate.weight"


def kimi_linear_spec_from_hf(config: Any) -> ModelSpec:
    def get(name, default=None):
        return getattr(config, name, default)

    lin = dict(get("linear_attn_config") or {})
    layers = config.num_hidden_layers
    full = set(lin.get("full_attn_layers") or ())
    kda = set(lin.get("kda_layers") or ())
    if full | kda != set(range(1, layers + 1)) or full & kda:
        raise NotImplementedError(
            "kimi_linear: linear_attn_config.kda_layers and full_attn_layers "
            f"must name each of the {layers} layers once (1-indexed)"
        )
    if get("rope_scaling"):
        raise NotImplementedError("kimi_linear with rope_scaling")
    if not get("mla_use_nope", False):
        raise NotImplementedError(
            "kimi_linear with rotary in its latent layers (mla_use_nope false)"
        )
    if get("q_lora_rank"):
        raise NotImplementedError("kimi_linear with low-rank queries")
    if get("moe_router_activation_func", "sigmoid") != "sigmoid":
        raise NotImplementedError(
            "kimi_linear moe_router_activation_func "
            f"{get('moe_router_activation_func')!r}"
        )
    if (get("num_expert_group", 1), get("topk_group", 1)) != (1, 1):
        raise NotImplementedError("kimi_linear with a group-limited router")
    if get("moe_layer_freq", 1) != 1:
        raise NotImplementedError("kimi_linear with moe_layer_freq != 1")
    if lin["num_heads"] > LANES:
        raise NotImplementedError("kimi_linear with more than 128 KDA heads")
    experts = get("num_experts") or 0
    moe_width = get("moe_intermediate_size") or 0
    return ModelSpec(
        family="kimi_linear",
        hidden_size=config.hidden_size,
        intermediate_size=config.intermediate_size,
        num_attention_heads=config.num_attention_heads,
        num_key_value_heads=config.num_attention_heads,
        head_dim=config.qk_nope_head_dim + config.qk_rope_head_dim,
        num_hidden_layers=layers,
        vocab_size=config.vocab_size,
        rms_norm_eps=config.rms_norm_eps,
        rope_theta=float(get("rope_theta", 10000.0)),
        tie_word_embeddings=bool(get("tie_word_embeddings", False)),
        max_position_embeddings=get("model_max_length", 4096),
        layer_types=tuple(
            "full" if i + 1 in full else "linear" for i in range(layers)
        ),
        gdn=GdnSpec(
            key_heads=lin["num_heads"],
            value_heads=lin["num_heads"],
            key_dim=lin["head_dim"],
            value_dim=lin["head_dim"],
            conv=lin["short_conv_kernel_size"],
            channel_decay=True,
            # the published modeling file sizes both low-rank gates by the
            # head's dim; the config has no key for it
            gate_rank=lin["head_dim"],
        ),
        mla=MlaSpec(
            q_rank=0,
            kv_rank=config.kv_lora_rank,
            nope_dim=config.qk_nope_head_dim,
            rope_dim=config.qk_rope_head_dim,
            v_dim=config.v_head_dim,
            rope=False,
        ),
        num_experts=experts,
        num_experts_per_tok=get("num_experts_per_token") or 0,
        moe_router="sigmoid",
        moe_norm_topk=bool(get("moe_renormalize", True)),
        moe_route_scale=float(get("routed_scaling_factor", 1.0)),
        moe_shared_intermediate=(get("num_shared_experts") or 0) * moe_width,
        moe_intermediate_size=moe_width,
        first_dense_layers=get("first_k_dense_replace", 0) if experts else 0,
    )


# the router's width and the experts held, read off the checkpoint
refine_spec = functools.partial(
    refine_held, config_key="num_experts", router_name=ROUTER
)


def _config(reader):
    from types import SimpleNamespace

    return SimpleNamespace(**reader.config)


def _load_block(reader, layer_idx: int, dtype=None) -> dict:
    import jax.numpy as jnp

    spec = kimi_linear_spec_from_hf(_config(reader))
    p = f"model.layers.{layer_idx}"
    a = f"{p}.self_attn"
    params = {
        "input_layernorm": _t(reader, f"{p}.input_layernorm.weight", dtype),
        "post_attention_layernorm": _t(
            reader, f"{p}.post_attention_layernorm.weight", dtype
        ),
    }
    if spec.layer_type(layer_idx) == "linear":
        # torch [out, D] each -> [D, q | k | v]
        params["gdn_in_proj"] = jnp.asarray(np.concatenate([
            np.asarray(reader.tensor(f"{a}.{x}_proj.weight")) for x in "qkv"
        ]).T, dtype=dtype)
        # torch [C, 1, K] each -> [K, q | k | v]: tap k of every channel
        params["gdn_conv_w"] = jnp.asarray(np.concatenate([
            np.asarray(reader.tensor(f"{a}.{x}_conv1d.weight"))[:, 0, :]
            for x in "qkv"
        ]).T, dtype=dtype)
        # f_a | g_a | b, each from a lane boundary, zeros between
        narrow = [
            np.asarray(reader.tensor(f"{a}.{name}_proj.weight"))
            for name in ("f_a", "g_a", "b")
        ]
        low = np.zeros((3 * LANES, spec.hidden_size), narrow[0].dtype)
        for j, w in enumerate(narrow):
            low[j * LANES : j * LANES + w.shape[0]] = w
        params["gdn_low_proj"] = jnp.asarray(low.T, dtype=dtype)
        for name in ("f_b", "g_b"):
            params[f"gdn_{name}_proj"] = _t(
                reader, f"{a}.{name}_proj.weight", dtype
            ).T
        # the gates' own vectors stay float32 whatever the compute dtype
        params["gdn_a_log"] = _t(reader, f"{a}.A_log", jnp.float32).reshape(-1)
        params["gdn_dt_bias"] = _t(reader, f"{a}.dt_bias", jnp.float32)
        params["gdn_norm"] = _t(reader, f"{a}.o_norm.weight", dtype)
        params["gdn_out_proj"] = _t(reader, f"{a}.o_proj.weight", dtype).T
    else:
        mla, heads = spec.mla, spec.num_attention_heads
        params["kv_a_norm"] = _t(reader, f"{a}.kv_a_layernorm.weight", dtype)
        params["kv_a_proj"] = read_weight(
            reader, f"{a}.kv_a_proj_with_mqa.weight", "kv_a_proj", dtype
        )
        params["o_proj"] = read_weight(
            reader, f"{a}.o_proj.weight", "o_proj", dtype
        )
        params.update(split_query_rows(
            np.asarray(reader.tensor(f"{a}.q_proj.weight")), heads,
            mla.nope_dim, mla.rope_dim, dtype,
        ))
        kv_b = np.asarray(reader.tensor(f"{a}.kv_b_proj.weight")).reshape(
            heads, mla.nope_dim + mla.v_dim, mla.kv_rank
        )
        params["kv_b_k"] = jnp.asarray(kv_b[:, : mla.nope_dim], dtype=dtype)
        params["kv_b_v"] = jnp.asarray(kv_b[:, mla.nope_dim :], dtype=dtype)
    if not reader.has(f"{p}.{ROUTER}"):  # a leading dense layer
        for proj in ("gate", "up", "down"):
            params[f"{proj}_proj"] = _t(
                reader, f"{p}.mlp.{proj}_proj.weight", dtype
            ).T
        return params
    m = f"{p}.block_sparse_moe"
    # output-major [E, D] as the checkpoint has it: the layer body makes the
    # router's product in float32 (a score rounded to bfloat16 flips a
    # near-tie at the k-th place, and a flipped expert is no rounding)
    params["router_t"] = _t(reader, f"{p}.{ROUTER}", dtype)
    params["expert_bias"] = _t(
        reader, f"{m}.gate.e_score_correction_bias", jnp.float32
    )
    first, count = held_experts(reader, "num_experts")
    # w1 the gate, w3 the up, w2 the down projection (Mixtral's names)
    params.update(
        stack_expert_weights(
            reader, f"{m}.experts.{{}}", "w1", "w3", "w2", count, dtype,
            first=first,
        )
    )
    if reader.config.get("num_shared_experts"):
        for proj in ("gate", "up", "down"):
            params[f"shared_{proj}"] = _t(
                reader, f"{m}.shared_experts.{proj}_proj.weight", dtype
            ).T
    return params


register_family(
    Family(
        "kimi_linear", kimi_linear_spec_from_hf, loader=_load_block,
        refine_spec=refine_spec,
    )
)
