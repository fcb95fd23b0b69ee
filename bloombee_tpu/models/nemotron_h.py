"""Nemotron-H family (`model_type: "nemotron_h"`, NVIDIA Nemotron 3 Nano):
every layer is ONE sublayer behind ONE norm, `x + f(rms(x))`, and
`hybrid_override_pattern` names each layer's: `M` a Mamba-2 state-space
mixer alone, `E` an expert layer alone, `*` grouped-query attention alone.
The published `modeling_nemotron_h.py` as the checkpoint's config.json
describes it:

  M: zxbcdt = x W_in^T, z | xBC | dt = 4096 | 6144 | 64 columns (the inner
     width is mamba_num_heads * mamba_head_dim, NOT expand * hidden_size);
     xBC = silu(conv4(xBC) + bias); dt = softplus(dt + dt_bias);
     S_t = exp(dt_t A) S_{t-1} + dt_t outer(x_t, B_t); y_t = S_t C_t + D x_t;
     out = (rms_groups(y * silu(z)) * w) W_out^T     (the gate BEFORE the norm)
  E: s = sigmoid(float32(x Wr^T)); the top-k of s + e_score_correction_bias
     (one group); the weights the unbiased s of the chosen over their sum
     (`norm_topk_prob`), times `routed_scaling_factor`; an expert is
     down(relu(up(x)) ** 2), TWO matrices and no gate; plus one shared
     expert of the same form on every row
  *: q, k, v, o without bias, causal softmax at head_dim ** -0.5, NO rotary
     and no other positional signal: positions come from the M layers

Everything is a switch the layer body already reads: `one_sublayer` with
every layer's kind in `layer_types` ("mamba" | "moe" | "full"), `ssm`
(runtime/layer_body.py `_ssm_mixer`, falcon_h1's, with multipliers of 1),
`rope` False, `mlp_type` "relu2" (ops/moe.py `moe_mlp(activation=)`),
`moe_router` "sigmoid" with `expert_bias`, `moe_held` (`run_server
--experts`).

What a layer holds, stored the way the step programs read it
(models/layout.py), its one norm under `input_layernorm`:

- mamba: falcon_h1's `ssm_*` keys, `ssm_in_proj` with zero columns up to
  whole lanes (10304 -> 10368 at the published widths).
- moe: `router_t` [E, D] over ALL the model's experts, `expert_bias` [E]
  float32, `experts_up` [held, D, I'] / `experts_down` [held, I', D] with
  the intermediate width padded with zeros to whole lanes (1856 -> 1920:
  `relu(0) ** 2 * 0` adds nothing, and the expert kernels tile I' by 128),
  `shared_up` / `shared_down`. No gate leaf.
- full: `q_proj` / `k_proj` / `v_proj` [out, in], `o_proj` [in, out].

The tensor names below are the published checkpoint's as remembered; none
could be confirmed here (no network): cellbench/configs/
nemotron3-nano-30b-ep2-span14.json lists them under `assumed`.
"""

from __future__ import annotations

import functools
from types import SimpleNamespace
from typing import Any

import numpy as np

from bloombee_tpu.models.auto import Family, register_family
from bloombee_tpu.models.checkpoint import (
    held_experts,
    read_tensor as _t,
    read_weight,
    refine_held,
)
from bloombee_tpu.models.layout import lane_padded
from bloombee_tpu.models.spec import ModelSpec, SsmSpec

LAYERS = "backbone.layers"
ROUTER = "mixer.gate.weight"
KINDS = {"M": "mamba", "E": "moe", "*": "full"}


def nemotron_h_spec_from_hf(config: Any) -> ModelSpec:
    def get(name, default=None):
        return getattr(config, name, default)

    pattern = get("hybrid_override_pattern") or ""
    layers = config.num_hidden_layers
    if len(pattern) != layers or set(pattern) - set(KINDS):
        raise NotImplementedError(
            f"nemotron_h: hybrid_override_pattern must name each of the "
            f"{layers} layers M, E or * (got {pattern!r}; a dense-MLP layer "
            "'-' is not supported)"
        )
    for flag in ("attention_bias", "mlp_bias", "use_bias", "mamba_proj_bias"):
        if get(flag, False):
            raise NotImplementedError(f"nemotron_h with {flag}")
    if not get("use_conv_bias", True):
        raise NotImplementedError("nemotron_h without a conv bias")
    if get("mlp_hidden_act", "relu2") != "relu2":
        raise NotImplementedError(
            f"nemotron_h mlp_hidden_act {get('mlp_hidden_act')!r}"
        )
    if get("mamba_hidden_act", "silu") != "silu":
        raise NotImplementedError(
            f"nemotron_h mamba_hidden_act {get('mamba_hidden_act')!r}"
        )
    if (get("n_group", 1), get("topk_group", 1)) != (1, 1):
        raise NotImplementedError("nemotron_h with a group-limited router")
    if get("sliding_window"):
        raise NotImplementedError("nemotron_h with a sliding window")
    limit = get("time_step_limit")
    if limit and (limit[0] > 0 or limit[1] != float("inf")):
        raise NotImplementedError("nemotron_h with a clamped time step")
    experts = get("n_routed_experts") or 0
    moe_width = get("moe_intermediate_size") or 0
    heads = config.mamba_num_heads
    return ModelSpec(
        family="nemotron_h",
        hidden_size=config.hidden_size,
        intermediate_size=get("intermediate_size") or moe_width,
        num_attention_heads=config.num_attention_heads,
        num_key_value_heads=config.num_key_value_heads,
        head_dim=get("head_dim")
        or config.hidden_size // config.num_attention_heads,
        num_hidden_layers=layers,
        vocab_size=config.vocab_size,
        rms_norm_eps=get("layer_norm_epsilon", 1e-5),
        tie_word_embeddings=bool(get("tie_word_embeddings", False)),
        max_position_embeddings=get("max_position_embeddings", 4096),
        layer_types=tuple(KINDS[c] for c in pattern),
        one_sublayer=True,
        rope=False,
        mlp_type="relu2",
        ssm=SsmSpec(
            heads=heads,
            head_dim=config.mamba_head_dim,
            state=config.ssm_state_size,
            groups=config.n_groups,
            conv=config.conv_kernel,
            chunk=get("chunk_size", 128),
        ),
        num_experts=experts,
        num_experts_per_tok=get("num_experts_per_tok") or 0,
        moe_router="sigmoid",
        moe_norm_topk=bool(get("norm_topk_prob", True)),
        moe_route_scale=float(get("routed_scaling_factor", 1.0)),
        moe_shared_intermediate=(
            (get("n_shared_experts") or 0)
            * (get("moe_shared_expert_intermediate_size") or 0)
        ),
        moe_intermediate_size=moe_width,
    )


# the router's width and the experts held, read off the checkpoint
refine_spec = functools.partial(
    refine_held, config_key="n_routed_experts", router_name=ROUTER,
    layer_prefix=LAYERS,
)


def _padded_t(w, axis: int, dtype):
    """The torch matrix `w` [out, in] stored [in, out], the expert's
    intermediate dimension (`axis` of `w`) padded with zeros to whole lanes.
    Padded on the host: the device never holds a second copy."""
    import jax.numpy as jnp

    pad = [(0, 0), (0, 0)]
    pad[axis] = (0, lane_padded(w.shape[axis]) - w.shape[axis])
    return jnp.asarray(np.pad(np.asarray(w), pad), dtype=dtype).T


def _stack_experts(reader, prefix: str, first: int, count: int, dtype):
    """{experts_up [held, D, I'], experts_down [held, I', D]}: one
    projection's stack at a time, settled before the next is read
    (checkpoint.stack_expert_weights says why)."""
    import jax
    import jax.numpy as jnp

    def stacked(name: str, axis: int):
        return jax.block_until_ready(jnp.stack([
            _padded_t(
                reader.tensor(f"{prefix}.experts.{e}.{name}.weight"), axis,
                dtype,
            )
            for e in range(first, first + count)
        ]))

    return {
        "experts_up": stacked("up_proj", 0),
        "experts_down": stacked("down_proj", 1),
    }


def _load_block(reader, layer_idx: int, dtype=None) -> dict:
    import jax.numpy as jnp

    spec = nemotron_h_spec_from_hf(SimpleNamespace(**reader.config))
    p = f"{LAYERS}.{layer_idx}"
    m = f"{p}.mixer"
    params = {"input_layernorm": _t(reader, f"{p}.norm.weight", dtype)}
    kind = spec.layer_type(layer_idx)
    if kind == "mamba":
        # in_proj with zero columns up to whole lanes, added on the host
        # (models/falcon_h1.py: or every step re-lays the stack out)
        w = reader.tensor(f"{m}.in_proj.weight")  # [proj_dim, D]
        w = np.pad(
            w, ((0, lane_padded(spec.ssm.proj_dim) - w.shape[0]), (0, 0))
        )
        params["ssm_in_proj"] = jnp.asarray(w, dtype=dtype).T
        params["ssm_out_proj"] = _t(reader, f"{m}.out_proj.weight", dtype).T
        # torch [C, 1, K] -> [K, C]: tap k of every channel is one row
        params["ssm_conv_w"] = _t(
            reader, f"{m}.conv1d.weight", dtype)[:, 0, :].T
        params["ssm_conv_b"] = _t(reader, f"{m}.conv1d.bias", dtype)
        params["ssm_norm"] = _t(reader, f"{m}.norm.weight", dtype)
        # the recurrence's own vectors stay float32 whatever the compute dtype
        for key, name in (("ssm_a_log", "A_log"), ("ssm_d", "D"),
                          ("ssm_dt_bias", "dt_bias")):
            params[key] = _t(reader, f"{m}.{name}", jnp.float32)
    elif kind == "moe":
        # output-major [E, D] as the checkpoint has it: the layer body makes
        # the router's product in float32
        params["router_t"] = _t(reader, f"{p}.{ROUTER}", dtype)
        params["expert_bias"] = _t(
            reader, f"{m}.gate.e_score_correction_bias", jnp.float32
        )
        first, count = held_experts(reader, "n_routed_experts")
        params.update(_stack_experts(reader, m, first, count, dtype))
        if spec.moe_shared_intermediate:
            for proj in ("up", "down"):
                params[f"shared_{proj}"] = _t(
                    reader, f"{m}.shared_experts.{proj}_proj.weight", dtype
                ).T
    else:
        for proj in ("q", "k", "v", "o"):
            params[f"{proj}_proj"] = read_weight(
                reader, f"{m}.{proj}_proj.weight", f"{proj}_proj", dtype
            )
    return params


register_family(
    Family(
        "nemotron_h",
        nemotron_h_spec_from_hf,
        loader=_load_block,
        layer_prefix=LAYERS,
        client_names={
            "embed": "backbone.embeddings.weight",
            "norm": "backbone.norm_f.weight",
            "lm_head": "lm_head.weight",
        },
        refine_spec=refine_spec,
    )
)
