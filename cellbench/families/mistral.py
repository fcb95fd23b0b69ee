"""`model_type: mistral`: a Llama-named dense decoder layer (GQA attention with
rotary positions and a sliding window, gated-SiLU MLP, RMSNorm), an untied
head; the client neither scales the embedding nor the logits.

  per layer: x = rms(hidden) ; q,k,v = x@Wq^T.. ; rotary (HF rotate_half) ;
             causal softmax attention with GQA inside the sliding window ;
             hidden += attn@Wo^T ; x = rms(hidden) ;
             hidden += silu(x@Wg^T) * (x@Wu^T) @ Wd^T
  logits = rms(hidden) @ head^T
"""

from __future__ import annotations

from cellbench.checkpoint import ONES
from cellbench.reference import _rms, _rope_attention
from cellbench.roofline import BF16



def _head_dim(config: dict) -> int:
    return config.get("head_dim") or (
        config["hidden_size"] // config["num_attention_heads"])


# ------------------------------------------------------- checkpoint plan
def layer_tensors(config: dict, layer: int) -> list[tuple]:
    d, i = config["hidden_size"], config["intermediate_size"]
    heads, kv_heads = config["num_attention_heads"], config["num_key_value_heads"]
    hd = _head_dim(config)
    p = f"model.layers.{layer}"
    return [
        (f"{p}.input_layernorm.weight", (d,), ONES),
        (f"{p}.post_attention_layernorm.weight", (d,), ONES),
        (f"{p}.self_attn.q_proj.weight", (heads * hd, d)),
        (f"{p}.self_attn.k_proj.weight", (kv_heads * hd, d)),
        (f"{p}.self_attn.v_proj.weight", (kv_heads * hd, d)),
        (f"{p}.self_attn.o_proj.weight", (d, heads * hd)),
        (f"{p}.mlp.gate_proj.weight", (i, d)),
        (f"{p}.mlp.up_proj.weight", (i, d)),
        (f"{p}.mlp.down_proj.weight", (d, i)),
    ]


def client_tensors(config: dict) -> list[tuple]:
    v, d = config["vocab_size"], config["hidden_size"]
    return [
        ("model.embed_tokens.weight", (v, d)),
        ("model.norm.weight", (d,), ONES),
        ("lm_head.weight", (v, d)),
    ]


# ------------------------------------------------------------- reference
def layer_params(tensors: dict, config: dict, layer: int) -> dict:
    """One layer's tensors under short names, torch layout [out, in]. Still
    bfloat16 (exact); cast on use."""
    p = f"model.layers.{layer}."
    return {
        "ln1": tensors[p + "input_layernorm.weight"],
        "ln2": tensors[p + "post_attention_layernorm.weight"],
        **{k: tensors[p + f"self_attn.{k}_proj.weight"] for k in "qkvo"},
        **{k: tensors[p + f"mlp.{k}_proj.weight"]
           for k in ("gate", "up", "down")},
    }


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
    attn = _rope_attention(q, k, v, positions, config["rope_theta"],
                           config.get("sliding_window") or 0)
    hidden = hidden + attn @ p["o"].T
    x = _rms(hidden, p["ln2"], eps)
    y = (jax.nn.silu(x @ p["gate"].T) * (x @ p["up"].T)) @ p["down"].T
    return hidden + y


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
    """Each layer's attention and MLP weights once, `kv_bytes` of keys and
    values, the rows' activations in and out; every row attends `attended`
    positions."""
    layers, d, hd = config["num_hidden_layers"], config["hidden_size"], _head_dim(config)
    q = config["num_attention_heads"] * hd
    kv = config["num_key_value_heads"] * hd
    weights = d * q + 2 * d * kv + q * d + 3 * d * config["intermediate_size"]
    weight_bytes = layers * weights * BF16
    act_bytes = 2 * rows * d * BF16
    flops = layers * rows * (
        2 * weights + 4 * attended * config["num_attention_heads"] * hd)
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
    and values once (inside the sliding window)."""
    context = _live(config, context)
    kv_bytes = (config["num_hidden_layers"] * rows * (context + 1)
                * _kv_row(config) * BF16)
    return _needs(config, rows, kv_bytes, context)


def chunk_needs(config: dict, rows: float, context: float) -> dict:
    """Bytes and FLOPs one prefill chunk of `rows` tokens of ONE sequence
    needs when `context` tokens of it are already cached: the cached keys and
    values once (inside the sliding window), the chunk's own written once;
    causal attention over the cache and the chunk's own lower triangle."""
    context = _live(config, context)
    kv_bytes = (config["num_hidden_layers"] * (context + rows)
                * _kv_row(config) * BF16)
    return _needs(config, rows, kv_bytes, context + rows / 2)
