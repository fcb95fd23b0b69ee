"""Tensor-parallel SERVING: the paged span step partitioned over a tp mesh.

The reference serves real decode under tensor parallelism with hand-rolled
per-device CUDA streams and stream all-reduces
(/root/reference/src/bloombee/server/flexgen_tensor_parallel.py:540-828:
row/col weight slices, `_reduce_partials`, per-shard KV merge). The TPU
idiom is the opposite of hand-scheduling: annotate the *placement* of the
weights and the KV arena over the mesh and let GSPMD partition the very same
`span_step_packed` computation, inserting the Megatron collectives (psum
after o_proj and down_proj) over ICI automatically.

Sharding layout (serving mesh has one axis, "tp"):
- q/k/v projections: output dim sharded -> each device computes its local
  heads. Attention is embarrassingly parallel over heads, so the paged
  gather/scatter and masks replicate per shard.
- o_proj / down_proj: input dim sharded -> local partial matmul, XLA psums.
- KV arena: the kv-head dim sharded -> each device holds its heads' pages
  (the per-shard KV merge of the reference's `_merge_cache_parts` never
  needs to happen).
- Mixtral experts: the expert dim shards over tp = true expert parallelism
  (the reference runs all experts densely on every device).

Requires num_attention_heads % tp == 0; homogeneous spans also require
num_key_value_heads % tp == 0, while HETEROGENEOUS spans replicate the K/V
of layers whose own KV-head count does not divide tp (gemma-4 full layers
with a single KV head) and shard everything else — see
place_hetero_span_params / place_hetero_arena.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bloombee_tpu.models.spec import ModelSpec

# specs for stacked span params [L, ...] (L unsharded: one server owns the
# whole span; cf. parallel/spmd.py PARAM_SPECS which also shards pp)
SERVING_PARAM_SPECS = {
    "input_layernorm": P(None, None),
    "input_layernorm_bias": P(None, None),
    "post_attention_layernorm": P(None, None),
    "post_attention_layernorm_bias": P(None, None),
    "mlp_layernorm": P(None, None),
    "mlp_layernorm_bias": P(None, None),
    "pre_feedforward_layernorm": P(None, None),
    "post_feedforward_layernorm": P(None, None),
    # stored output-major [L, out, in] (models/layout.py)
    "q_proj": P(None, "tp", None),
    "k_proj": P(None, "tp", None),
    "v_proj": P(None, "tp", None),
    "o_proj": P(None, "tp", None),
    "q_bias": P(None, "tp"),
    "k_bias": P(None, "tp"),
    "v_bias": P(None, "tp"),
    "o_bias": P(None, None),
    "gate_proj": P(None, None, "tp"),
    "up_proj": P(None, None, "tp"),
    "down_proj": P(None, "tp", None),
    "gate_bias": P(None, "tp"),
    "up_bias": P(None, "tp"),
    "down_bias": P(None, None),
    "q_norm": P(None, None),
    "k_norm": P(None, None),
    "router": P(None, None, None),
    "experts_gate": P(None, "tp", None, None),
    "experts_up": P(None, "tp", None, None),
    "experts_down": P(None, "tp", None, None),
}

# KV arena [L, S_tot, Hkv, hd]: heads shard over tp
ARENA_SPEC = P(None, None, "tp", None)


def make_serving_mesh(tp: int, devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    if tp > len(devices):
        raise ValueError(f"tp={tp} needs {tp} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:tp]), ("tp",))


def check_tp_divides(spec: ModelSpec, tp: int, hetero: bool = False) -> None:
    """hetero=True skips the kv-head check only: per-layer KV geometry is
    handled by the per-layer placement (layers whose kv heads don't divide
    replicate their K/V); q heads and experts are uniform either way."""
    if spec.num_attention_heads % tp:
        raise ValueError(
            f"tp={tp} must divide num_attention_heads="
            f"{spec.num_attention_heads}"
        )
    if not hetero and spec.num_key_value_heads % tp:
        raise ValueError(
            f"tp={tp} must divide num_key_value_heads="
            f"{spec.num_key_value_heads} (KV-head replication only exists "
            "on the heterogeneous path)"
        )
    if spec.num_experts and spec.num_experts % tp:
        raise ValueError(
            f"tp={tp} must divide num_experts={spec.num_experts}"
        )


def _quant_leaf_spec(base, shape, tp):
    """Sharding spec for one leaf of a quantized weight: keep the base
    placement wherever the leaf's dim divides tp, replicate the rest.
    Handles every layout by shape alone: int8 scales [L, 1, out] drop an
    input-dim "tp" (size 1), int4 group scales [L, in/GROUP, out] keep it,
    packed int4 codes [L, in/2, out] keep it, expert leaves [L, E, ...]
    keep the expert-dim shard; an output-major key's leaves have the last
    two axes swapped, as its base spec has."""
    spec = tuple(
        None if (s == "tp" and shape[i] % tp != 0) else s
        for i, s in enumerate(base)
    )
    return P(*spec)


def place_span_params(params: dict, mesh: Mesh) -> dict:
    """Commit stacked span params to the serving mesh (tp-sharded).

    Quantized projections (models/wquant.py QuantWeight) shard like their
    dense counterparts: codes follow the weight's row/col placement, and
    each scale/zero leaf keeps the shards' scales local (the dequantize is
    an elementwise producer, so GSPMD keeps it fused shard-local and the
    Megatron psums are unchanged — the composition the reference builds by
    hand from compression.py + flexgen_tensor_parallel.py)."""
    from bloombee_tpu.models.wquant import QuantWeight

    tp = mesh.devices.size
    out = {}
    for k, v in params.items():
        base = SERVING_PARAM_SPECS[k]
        if isinstance(v, QuantWeight):
            def put(leaf):
                if leaf is None:
                    return None
                return jax.device_put(
                    leaf,
                    NamedSharding(
                        mesh, _quant_leaf_spec(base, leaf.shape, tp)
                    ),
                )

            out[k] = QuantWeight(
                codes=put(v.codes), scale=put(v.scale), zero=put(v.zero)
            )
        else:
            out[k] = jax.device_put(v, NamedSharding(mesh, base))
    return out


def place_arena(arena: dict, mesh: Mesh) -> dict:
    """Commit the KV arena to the serving mesh (kv heads sharded)."""
    return {
        k: jax.device_put(v, NamedSharding(mesh, ARENA_SPEC))
        for k, v in arena.items()
    }


def replicated(x, mesh: Mesh):
    """Commit a host array replicated over the mesh (step payloads/masks)."""
    return jax.device_put(x, NamedSharding(mesh, P()))


def _layer_spec(base, shape, tp, kv_replicate: bool):
    """Per-layer (no leading L dim) spec from the stacked base: delegate
    to the shared drop-tp-where-indivisible rule; `kv_replicate` forces
    replication regardless of the flattened dim (a single KV head whose
    head_dim happens to divide tp must NOT be split WITHIN the head — the
    arena keys the same decision on the layer's KV-head count)."""
    if kv_replicate:
        return P(*(None for _ in base[1:]))
    return _quant_leaf_spec(base[1:], shape, tp)


def _place_one_layer(params: dict, mesh: Mesh, kv_replicate: bool) -> dict:
    """Commit ONE layer's (unstacked) param dict to the tp mesh — the
    shared leaf-placement body of the hetero and weight-offload paths.
    `kv_replicate` forces the k/v leaves replicated (a layer whose KV-head
    count doesn't divide tp)."""
    from bloombee_tpu.models.wquant import QuantWeight

    tp = mesh.devices.size
    out = {}
    for key, leaf in params.items():
        base = SERVING_PARAM_SPECS[key]
        kv_rep = kv_replicate and key.startswith(("k_", "v_"))

        def put(x, base=base, kv_rep=kv_rep):
            if x is None:
                return None
            return jax.device_put(
                x,
                NamedSharding(mesh, _layer_spec(base, x.shape, tp, kv_rep)),
            )

        if isinstance(leaf, QuantWeight):
            out[key] = QuantWeight(
                codes=put(leaf.codes), scale=put(leaf.scale),
                zero=put(leaf.zero),
            )
        else:
            out[key] = put(leaf)
    return out


def place_hetero_span_params(
    layer_params: tuple, mesh: Mesh, spec: ModelSpec, start_block: int = 0
) -> tuple:
    """Commit per-layer param dicts (heterogeneous spans) to the tp mesh:
    each layer shards like its stacked counterpart where its dims divide.
    K/V projections follow the LAYER'S KV-HEAD count (the same rule the
    arena placement uses): layers whose kv heads don't divide tp
    replicate their k/v leaves, so K/V writes stay collective-free."""
    tp = mesh.devices.size
    return tuple(
        _place_one_layer(
            params, mesh,
            kv_replicate=spec.kv_heads_for_layer(start_block + i) % tp != 0,
        )
        for i, params in enumerate(layer_params)
    )


def place_layer_params(params: dict, mesh: Mesh) -> dict:
    """Per-step placement of a weight-offloaded host layer: the same
    row/col sharding as its stacked counterpart, so the streamed H2D
    bytes split across the tp chips instead of replicating."""
    return _place_one_layer(params, mesh, kv_replicate=False)


def place_arena_for(spec: ModelSpec, arena: dict, mesh: Mesh) -> dict:
    """Arena placement dispatch shared by executor init and the
    post-failure rebuild (one site decides hetero vs dense, so a rebuilt
    arena can never be placed with the wrong helper)."""
    if spec.heterogeneous:
        return place_hetero_arena(arena, mesh)
    return place_arena(arena, mesh)


def place_hetero_arena(arena: dict, mesh: Mesh) -> dict:
    """Commit per-layer KV slabs to the tp mesh: a layer's KV heads shard
    when they divide tp, else that layer's slab replicates (the scatter of
    sharded K/V into a replicated slab is an all-gather GSPMD inserts)."""
    tp = mesh.devices.size

    def put(slab):
        def leaf_put(x):
            # slab leaves are [1, S_tot, Hkv_l, ...]; shard the head dim
            spec = (
                P(None, None, "tp", None)
                if x.shape[2] % tp == 0 else P()
            )
            return jax.device_put(x, NamedSharding(mesh, spec))

        return jax.tree.map(leaf_put, slab)

    return {
        "k": tuple(put(s) for s in arena["k"]),
        "v": tuple(put(s) for s in arena["v"]),
    }
