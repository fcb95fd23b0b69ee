"""Megatron-style SPMD block compute under shard_map (tp + sp + dp).

Replaces the reference's intra-host tensor parallelism
(/root/reference/src/bloombee/server/flexgen_tensor_parallel.py:172-828:
per-device CUDA streams, row/col weight slices, stream all-reduce) with the
TPU idiom: weights sharded over the "tp" mesh axis, local matmuls on each
shard, one psum over ICI after o_proj and down_proj. Attention runs as ring
attention over the "sp" axis, so long sequences scale across the mesh instead
of offloading to host.

All functions here execute INSIDE shard_map (they use axis primitives);
`shard_span_params` prepares the NamedSharding placement that makes shard_map
hand each device its local shard.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bloombee_tpu.models.layout import project
from bloombee_tpu.models.spec import ModelSpec
from bloombee_tpu.ops import rms_norm, silu_mlp
from bloombee_tpu.ops.rotary import apply_rotary, rotary_cos_sin
from bloombee_tpu.parallel.ring_attention import ring_attention

# PartitionSpecs for stacked span params [L, ...]; layer dim shards over pp
PARAM_SPECS = {
    "input_layernorm": P("pp", None),
    "input_layernorm_bias": P("pp", None),
    "post_attention_layernorm": P("pp", None),
    "post_attention_layernorm_bias": P("pp", None),
    "mlp_layernorm": P("pp", None),  # falcon new-arch dual-LN
    "mlp_layernorm_bias": P("pp", None),
    "pre_feedforward_layernorm": P("pp", None),  # gemma2 sandwich
    "post_feedforward_layernorm": P("pp", None),
    # stored output-major [L, out, in] (models/layout.py)
    "q_proj": P("pp", "tp", None),
    "k_proj": P("pp", "tp", None),
    "v_proj": P("pp", "tp", None),
    "o_proj": P("pp", "tp", None),
    # qkv biases shard with their projection's OUTPUT dim, so they add
    # shard-locally before any psum (qwen2-style biased attention)
    "q_bias": P("pp", "tp"),
    "k_bias": P("pp", "tp"),
    "v_bias": P("pp", "tp"),
    "gate_proj": P("pp", None, "tp"),
    "up_proj": P("pp", None, "tp"),
    "down_proj": P("pp", "tp", None),
    "q_norm": P("pp", None),
    "k_norm": P("pp", None),
    # MoE (mixtral): experts shard over the tp axis = expert parallelism,
    # which the reference lacks entirely (SURVEY.md section 2.8)
    "router": P("pp", None, None),
    "experts_gate": P("pp", "tp", None, None),
    "experts_up": P("pp", "tp", None, None),
    "experts_down": P("pp", "tp", None, None),
}


def _check_known_keys(params: dict) -> None:
    unknown = sorted(set(params) - set(PARAM_SPECS))
    if unknown:
        # loud, named failure instead of a raw KeyError: these are the
        # same exclusions _spmd_unsupported documents (row-parallel
        # biases / exotic families)
        raise NotImplementedError(
            f"SPMD path has no sharding specs for params {unknown} "
            "(row-parallel biases and this family's extras aren't "
            "supported here yet)"
        )


def param_specs(params: dict) -> dict:
    _check_known_keys(params)
    return {k: PARAM_SPECS[k] for k in params}


def shard_span_params(params: dict, mesh: Mesh) -> dict:
    """Place stacked span params on the mesh (pp over layers, tp over
    heads/ffn)."""
    _check_known_keys(params)
    return {
        k: jax.device_put(v, NamedSharding(mesh, PARAM_SPECS[k]))
        for k, v in params.items()
    }


def _spmd_unsupported(spec: ModelSpec, params_l: dict) -> str | None:
    """Why this family cannot run the SPMD training body; None when it
    can. The remaining exclusions are RING-ATTENTION limits (no sliding
    window, no ALiBi positional bias, no logit soft-cap) plus row-parallel
    output biases — everything else routes through the same spec switches
    as the serving layer_body."""
    if spec.layer_types and "sliding" in spec.layer_types:
        return (
            "ring attention is full-causal; sliding-window families "
            "(mistral/gemma) aren't supported here yet"
        )
    if spec.alibi:
        return "ring attention has no positional-bias (ALiBi) path yet"
    if spec.attn_logit_softcap:
        return "ring attention has no logit soft-cap path yet"
    if spec.heterogeneous:
        return "heterogeneous head_dim spans don't stack into one scan"
    if any(
        k in params_l
        for k in ("o_bias", "down_bias", "gate_bias", "up_bias")
    ):
        # row-parallel biases would be added once per shard before the
        # psum; no in-scope family carries them (bloom does, but ALiBi
        # already excludes it)
        return "row-parallel projection biases aren't supported here yet"
    return None


def spmd_block_forward(
    params_l: dict,  # one layer's LOCAL param shards
    hidden: jax.Array,  # [b_local, C, D] (dp-sharded batch, sp-sharded seq)
    *,
    spec: ModelSpec,
    sp_axis: str = "sp",
    tp_axis: str = "tp",
    return_kv: bool = False,  # also return this layer's LOCAL (k, v)
    # chunk shards [b, C, kv_local, hd] — the sp-serving prefill collects
    # them into the paged arena so decode can continue single-chip
):
    """Family-generic SPMD layer: the same ModelSpec switches as the
    serving layer_body (norm type + biases, parallel-attn residual,
    sandwich norms, gelu/silu/MoE MLPs, qk-norm, qkv biases) over ring
    attention + Megatron psums. Covers llama/qwen2/qwen3/mixtral/falcon;
    `_spmd_unsupported` lists what still fails loudly."""
    from bloombee_tpu.runtime.layer_body import _norm, attn_scale

    b, c, d = hidden.shape
    reason = _spmd_unsupported(spec, params_l)
    if reason is not None:
        raise NotImplementedError(
            f"spmd block body doesn't cover family {spec.family!r}: {reason}"
        )
    tp = lax.axis_size(tp_axis)
    if spec.num_attention_heads % tp or spec.num_key_value_heads % tp:
        raise ValueError(
            f"tp={tp} must divide num_attention_heads="
            f"{spec.num_attention_heads} and num_key_value_heads="
            f"{spec.num_key_value_heads} (KV-head replication not yet "
            "implemented)"
        )
    h_local = spec.num_attention_heads // tp
    kv_local = spec.num_key_value_heads // tp
    hd = spec.head_dim

    sp_rank = lax.axis_index(sp_axis)
    positions = sp_rank * c + jnp.arange(c)
    positions = jnp.broadcast_to(positions[None], (b, c))
    cos, sin = rotary_cos_sin(positions, hd, spec.rope_theta)
    cos = cos.astype(hidden.dtype)
    sin = sin.astype(hidden.dtype)

    def col(x, key):
        # column-parallel projection: output dim sharded, so the bias
        # shard adds locally (before any reduction)
        y = project(x, params_l[key], key)
        bias = params_l.get(f"{key.removesuffix('_proj')}_bias")
        if bias is not None:
            y = y + bias
        return y

    x = _norm(hidden, params_l, "input_layernorm", spec)
    q = col(x, "q_proj").reshape(b, c, h_local, hd)
    k = col(x, "k_proj").reshape(b, c, kv_local, hd)
    v = col(x, "v_proj").reshape(b, c, kv_local, hd)
    if spec.qk_norm:
        q = rms_norm(q, params_l["q_norm"], spec.rms_norm_eps)
        k = rms_norm(k, params_l["k_norm"], spec.rms_norm_eps)
    q, k = apply_rotary(q, k, cos, sin)

    attn = ring_attention(
        q, k, v, axis_name=sp_axis, causal=True, scale=attn_scale(spec)
    )
    partial = attn.reshape(b, c, h_local * hd) @ params_l["o_proj"]
    attn_out = lax.psum(partial, tp_axis)

    def mlp_partial(x):
        """LOCAL MLP contribution (intermediate dim sharded); the caller
        psums. Same spec switches as layer_body._mlp, bias-free (checked
        in _spmd_unsupported)."""
        if spec.num_experts:
            # expert parallelism: full router everywhere, local expert
            # shard computes its weighted contribution, psum combines
            from bloombee_tpu.ops.moe import moe_mlp, router_topk_weights

            weights = router_topk_weights(
                x @ params_l["router"], spec.num_experts_per_tok,
                pre_softmax=spec.moe_pre_softmax,
                norm_topk=spec.moe_norm_topk,
            )  # [b, c, E] full
            e_local = params_l["experts_gate"].shape[0]
            rank = lax.axis_index(tp_axis)
            local_w = lax.dynamic_slice_in_dim(
                weights, rank * e_local, e_local, axis=-1
            )
            return moe_mlp(
                x, None, params_l["experts_gate"], params_l["experts_up"],
                params_l["experts_down"], spec.num_experts_per_tok,
                router_weights=local_w,
            )
        if spec.mlp_type == "silu":
            return silu_mlp(
                x, params_l["gate_proj"], params_l["up_proj"],
                params_l["down_proj"],
            )
        if spec.mlp_type == "gelu_tanh_gated":
            g = jax.nn.gelu(x @ params_l["gate_proj"], approximate=True)
            return (g * (x @ params_l["up_proj"])) @ params_l["down_proj"]
        # plain 4h GELU ("gelu" = exact/erf for falcon)
        h = jax.nn.gelu(
            x @ params_l["up_proj"], approximate=spec.mlp_type != "gelu"
        )
        return h @ params_l["down_proj"]

    if spec.parallel_attn:
        # falcon: parallel attention+MLP residual; new-arch uses a second
        # LN for the MLP branch, 7b shares the input norm
        if spec.num_ln_in_parallel_attn == 2:
            x_mlp = _norm(hidden, params_l, "mlp_layernorm", spec)
        else:
            x_mlp = x
        out = hidden + attn_out + lax.psum(mlp_partial(x_mlp), tp_axis)
    elif spec.sandwich_norms:
        attn_out = _norm(attn_out, params_l, "post_attention_layernorm", spec)
        hidden = hidden + attn_out
        x2 = _norm(hidden, params_l, "pre_feedforward_layernorm", spec)
        mlp_out = lax.psum(mlp_partial(x2), tp_axis)
        mlp_out = _norm(mlp_out, params_l, "post_feedforward_layernorm", spec)
        out = hidden + mlp_out
    else:
        hidden = hidden + attn_out
        x2 = _norm(hidden, params_l, "post_attention_layernorm", spec)
        out = hidden + lax.psum(mlp_partial(x2), tp_axis)
    if return_kv:
        return out, (k, v)
    return out


def spmd_span_forward(
    stacked_local: dict,  # local param shards with leading local-layer dim
    hidden: jax.Array,
    *,
    spec: ModelSpec,
    sp_axis: str = "sp",
    tp_axis: str = "tp",
) -> jax.Array:
    def body(h, params_l):
        return (
            spmd_block_forward(
                params_l, h, spec=spec, sp_axis=sp_axis, tp_axis=tp_axis
            ),
            None,
        )

    hidden, _ = lax.scan(body, hidden, stacked_local)
    return hidden


def spmd_span_forward_kv(
    stacked_local: dict,
    hidden: jax.Array,
    *,
    spec: ModelSpec,
    sp_axis: str = "sp",
    tp_axis: str = "tp",
):
    """spmd_span_forward that also stacks every layer's local (k, v)
    chunk shards [L, b, C, kv_local, hd] — the sp-serving prefill writes
    them into the paged arena so DECODE continues on the ordinary
    single-chip paged path."""

    def body(h, params_l):
        h, (k, v) = spmd_block_forward(
            params_l, h, spec=spec, sp_axis=sp_axis, tp_axis=tp_axis,
            return_kv=True,
        )
        return h, (k, v)

    hidden, (ks, vs) = lax.scan(body, hidden, stacked_local)
    return hidden, ks, vs
