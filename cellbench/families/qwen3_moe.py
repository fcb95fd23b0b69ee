"""`model_type: qwen3_moe`: Llama-named GQA attention with per-head RMSNorm on
queries and keys (QK-norm) and rotary positions, then the Qwen3-MoE sparse
block in the MLP's place; RMSNorm, an untied head, no multiplier at the
client's ends.

  per layer: x = rms(hidden) ; q,k,v = x@Wq^T.. ; per-head rms on q,k ;
             rotary (HF rotate_half) ; causal softmax attention with GQA ;
             hidden += attn@Wo^T ; x = rms(hidden) ; hidden += moe(x)
  moe: softmax over all experts, top-k, renormalised (norm_topk_prob), the
       chosen experts' gated-SiLU MLPs
  logits = rms(hidden) @ head^T
"""

from __future__ import annotations

from cellbench.checkpoint import ONES
from cellbench.reference import _rms, _rope_attention
from cellbench.roofline import BF16, expected_distinct_experts



def _head_dim(config: dict) -> int:
    return config.get("head_dim") or (
        config["hidden_size"] // config["num_attention_heads"])


# ------------------------------------------------------- checkpoint plan
def layer_tensors(config: dict, layer: int) -> list[tuple]:
    d, i = config["hidden_size"], config["moe_intermediate_size"]
    heads, kv_heads = config["num_attention_heads"], config["num_key_value_heads"]
    hd = _head_dim(config)
    p = f"model.layers.{layer}"
    tensors = [
        (f"{p}.input_layernorm.weight", (d,), ONES),
        (f"{p}.post_attention_layernorm.weight", (d,), ONES),
        (f"{p}.self_attn.q_proj.weight", (heads * hd, d)),
        (f"{p}.self_attn.k_proj.weight", (kv_heads * hd, d)),
        (f"{p}.self_attn.v_proj.weight", (kv_heads * hd, d)),
        (f"{p}.self_attn.o_proj.weight", (d, heads * hd)),
        (f"{p}.self_attn.q_norm.weight", (hd,), ONES),
        (f"{p}.self_attn.k_norm.weight", (hd,), ONES),
        (f"{p}.mlp.gate.weight", (config["num_experts"], d)),
    ]
    for e in range(config["num_experts"]):
        q = f"{p}.mlp.experts.{e}"
        tensors += [
            (f"{q}.gate_proj.weight", (i, d)),
            (f"{q}.up_proj.weight", (i, d)),
            (f"{q}.down_proj.weight", (d, i)),
        ]
    return tensors


def client_tensors(config: dict) -> list[tuple]:
    v, d = config["vocab_size"], config["hidden_size"]
    return [
        ("model.embed_tokens.weight", (v, d)),
        ("model.norm.weight", (d,), ONES),
        ("lm_head.weight", (v, d)),
    ]


# ------------------------------------------------------------- reference
def layer_params(tensors: dict, config: dict, layer: int) -> dict:
    """One layer's tensors under short names, torch layout [out, in]; the
    experts stacked [E, out, in]. Still bfloat16 (exact); cast on use."""
    import numpy as np

    p = f"model.layers.{layer}."
    out = {
        "ln1": tensors[p + "input_layernorm.weight"],
        "ln2": tensors[p + "post_attention_layernorm.weight"],
        **{k: tensors[p + f"self_attn.{k}_proj.weight"] for k in "qkvo"},
        "q_norm": tensors[p + "self_attn.q_norm.weight"],
        "k_norm": tensors[p + "self_attn.k_norm.weight"],
        "router": tensors[p + "mlp.gate.weight"],
    }
    for k in ("gate", "up", "down"):
        out[f"e_{k}"] = np.stack([
            tensors[p + f"mlp.experts.{e}.{k}_proj.weight"]
            for e in range(config["num_experts"])
        ])
    return out


def _moe(x, p, config, block: int = 512):
    """Qwen3MoeSparseMoeBlock on [R, D] rows, every expert computed for a
    block of rows at a time and weighted by the renormalised top-k router
    probabilities (zero off the top-k): the same sum the sparse form makes."""
    import jax
    import jax.numpy as jnp

    k = config["num_experts_per_tok"]
    r, d = x.shape
    pad = -r % block
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, d)

    def one(rows):
        probs = jax.nn.softmax(rows @ p["router"].T, axis=-1)
        top, idx = jax.lax.top_k(probs, k)
        if config.get("norm_topk_prob"):
            top = top / top.sum(-1, keepdims=True)
        w = jnp.zeros_like(probs).at[jnp.arange(block)[:, None], idx].set(top)
        g = jnp.einsum("rd,eid->rei", rows, p["e_gate"])
        u = jnp.einsum("rd,eid->rei", rows, p["e_up"])
        # the router weight goes in before the down projection (linear, so
        # the same sum) to keep the [R, E, D] intermediate out of memory
        h = jax.nn.silu(g) * u * w[:, :, None]
        return jnp.einsum("rei,edi->rd", h, p["e_down"])

    return jax.lax.map(one, xb).reshape(-1, d)[:r]


def layer_forward(p: dict, config: dict, hidden, positions):
    """One sequence: hidden [T, D] float32, positions [T]; p's leaves may be
    bfloat16 (exact) and are cast to float32 here."""
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    t, d = hidden.shape
    heads, kvh = config["num_attention_heads"], config["num_key_value_heads"]
    hd = _head_dim(config)
    eps = config["rms_norm_eps"]
    x = _rms(hidden, p["ln1"], eps)
    q = (x @ p["q"].T).reshape(t, heads, hd)
    k = (x @ p["k"].T).reshape(t, kvh, hd)
    v = (x @ p["v"].T).reshape(t, kvh, hd)
    q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    attn = _rope_attention(q, k, v, positions, config["rope_theta"],
                           config.get("sliding_window") or 0)
    hidden = hidden + attn @ p["o"].T
    x = _rms(hidden, p["ln2"], eps)
    return hidden + _moe(x, p, config)


def embed(client: dict, config: dict, ids):
    import numpy as np

    return np.asarray(client["model.embed_tokens.weight"][ids], np.float32)


def logits_rows(client: dict, config: dict, hidden_rows):
    import jax.numpy as jnp

    norm, head = (jnp.asarray(client[name]).astype(jnp.float32)
                  for name in ("model.norm.weight", "lm_head.weight"))
    return _rms(hidden_rows, norm, config["rms_norm_eps"]) @ head.T


# -------------------------------------------------------- roofline needs
def _needs(config: dict, rows: float, kv_bytes: float, attended: float) -> dict:
    """Each layer's attention weights once, the router and only the DISTINCT
    experts `rows` tokens are routed to (in expectation under uniform
    routing), `kv_bytes` of keys and values, the rows' activations in and
    out; every row computes its top-k experts and attends `attended`
    positions."""
    layers, d, hd = config["num_hidden_layers"], config["hidden_size"], _head_dim(config)
    q = config["num_attention_heads"] * hd
    kv = config["num_key_value_heads"] * hd
    attn = d * q + 2 * d * kv + q * d
    router = d * config["num_experts"]
    expert = 3 * d * config["moe_intermediate_size"]
    top_k = config["num_experts_per_tok"]
    distinct = expected_distinct_experts(config["num_experts"], top_k, rows)
    weight_bytes = layers * (attn + (router + distinct * expert)) * BF16
    act_bytes = 2 * rows * d * BF16
    flops = layers * rows * (
        2 * (attn + (router + top_k * expert))
        + 4 * attended * config["num_attention_heads"] * hd)
    return {"bytes": weight_bytes + kv_bytes + act_bytes, "flops": flops,
            "weight_bytes": weight_bytes, "kv_bytes": kv_bytes}


def _live(config: dict, context: float) -> float:
    window = config.get("sliding_window")
    return min(context, window) if window else context


def _kv_row(config: dict) -> int:
    return 2 * config["num_key_value_heads"] * _head_dim(config)  # K and V


def decode_step_needs(config: dict, rows: float, context: float) -> dict:
    """Bytes and FLOPs one decode step of `rows` rows at mean live context
    `context` needs, over the configuration's layers: every row's live keys
    and values once."""
    context = _live(config, context)
    kv_bytes = (config["num_hidden_layers"] * rows * (context + 1)
                * _kv_row(config) * BF16)
    return _needs(config, rows, kv_bytes, context)


def chunk_needs(config: dict, rows: float, context: float) -> dict:
    """Bytes and FLOPs one prefill chunk of `rows` tokens of ONE sequence
    needs when `context` tokens of it are already cached: the cached keys and
    values once, the chunk's own written once; causal attention over the
    cache and the chunk's own lower triangle."""
    context = _live(config, context)
    kv_bytes = (config["num_hidden_layers"] * (context + rows)
                * _kv_row(config) * BF16)
    return _needs(config, rows, kv_bytes, context + rows / 2)
