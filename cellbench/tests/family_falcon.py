"""`model_type: falcon` (the falcon-7b shape), as the rehearsal ADDS it: a
family the program serves and the harness has no file for. test_rehearsal.py
copies this file to `cellbench/families/falcon.py` of its copy of the
benchmark and edits nothing that is there. It brings what the two built-in
families do not: other tensor names (`transformer.h.*`, a fused
`query_key_value`), fills that are not random bits (LayerNorm weights by
name, biases from a range), a client without a head (tied to the embedding)
and a LayerNorm with bias at both ends.

  per layer: x = ln(hidden) ; [q | k | v] = x@Wqkv^T (H query heads, one key
             and one value head) ; rotary (HF rotate_half) ; causal softmax
             attention ; hidden += attn@Wo^T + gelu(x@W1^T)@W2^T   (parallel
             residual: attention and MLP read the same normed input)
  logits = ln_f(hidden) @ embed^T
"""

from __future__ import annotations

from cellbench.checkpoint import ONES
from cellbench.reference import _rope_attention
from cellbench.roofline import BF16

BIAS = {"low": -0.1, "high": 0.1, "spacing": "uniform"}


def _head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


# ------------------------------------------------------- checkpoint plan
def layer_tensors(config: dict, layer: int) -> list[tuple]:
    d, hd = config["hidden_size"], _head_dim(config)
    p = f"transformer.h.{layer}"
    return [
        (f"{p}.input_layernorm.weight", (d,), ONES),
        (f"{p}.input_layernorm.bias", (d,), BIAS),
        (f"{p}.self_attention.query_key_value.weight",
         ((config["num_attention_heads"] + 2) * hd, d)),
        (f"{p}.self_attention.dense.weight", (d, d)),
        (f"{p}.mlp.dense_h_to_4h.weight", (4 * d, d)),
        (f"{p}.mlp.dense_4h_to_h.weight", (d, 4 * d)),
    ]


def client_tensors(config: dict) -> list[tuple]:
    d = config["hidden_size"]
    return [
        ("transformer.word_embeddings.weight", (config["vocab_size"], d)),
        ("transformer.ln_f.weight", (d,), ONES),
        ("transformer.ln_f.bias", (d,), BIAS),
    ]


# ------------------------------------------------------------- reference
def layer_params(tensors: dict, config: dict, layer: int) -> dict:
    p = f"transformer.h.{layer}."
    return {
        "ln": tensors[p + "input_layernorm.weight"],
        "ln_bias": tensors[p + "input_layernorm.bias"],
        "qkv": tensors[p + "self_attention.query_key_value.weight"],
        "o": tensors[p + "self_attention.dense.weight"],
        "up": tensors[p + "mlp.dense_h_to_4h.weight"],
        "down": tensors[p + "mlp.dense_4h_to_h.weight"],
    }


def _layer_norm(x, w, b, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jnp.reciprocal(jnp.sqrt(var + eps)) * w + b


def layer_forward(p: dict, config: dict, hidden, positions):
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    t, d = hidden.shape
    heads, hd = config["num_attention_heads"], _head_dim(config)
    x = _layer_norm(hidden, p["ln"], p["ln_bias"], config["layer_norm_epsilon"])
    qkv = (x @ p["qkv"].T).reshape(t, heads + 2, hd)
    q, k, v = qkv[:, :heads], qkv[:, heads:heads + 1], qkv[:, heads + 1:]
    attn = _rope_attention(q, k, v, positions, config["rope_theta"], 0)
    mlp = jax.nn.gelu(x @ p["up"].T, approximate=False) @ p["down"].T
    return hidden + attn @ p["o"].T + mlp


def embed(client: dict, config: dict, ids):
    import numpy as np

    return np.asarray(client["transformer.word_embeddings.weight"][ids],
                      np.float32)


def logits_rows(client: dict, config: dict, hidden_rows):
    import jax.numpy as jnp

    w, b, table = (jnp.asarray(client[f"transformer.{name}"]).astype(jnp.float32)
                   for name in ("ln_f.weight", "ln_f.bias",
                                "word_embeddings.weight"))
    return _layer_norm(hidden_rows, w, b, config["layer_norm_epsilon"]) @ table.T


# -------------------------------------------------------- roofline needs
def _needs(config: dict, rows: float, kv_tokens: float, attended: float) -> dict:
    layers, d, hd = config["num_hidden_layers"], config["hidden_size"], _head_dim(config)
    weights = d * (config["num_attention_heads"] + 2) * hd + d * d + 8 * d * d
    weight_bytes = layers * weights * BF16
    kv_bytes = layers * kv_tokens * 2 * hd * BF16  # one key and one value head
    flops = layers * rows * (2 * weights + 4 * attended * d)
    return {"bytes": weight_bytes + kv_bytes + 2 * rows * d * BF16,
            "flops": flops, "weight_bytes": weight_bytes, "kv_bytes": kv_bytes}


def decode_step_needs(config: dict, rows: float, context: float) -> dict:
    return _needs(config, rows, rows * (context + 1), context)


def chunk_needs(config: dict, rows: float, context: float) -> dict:
    return _needs(config, rows, context + rows, context + rows / 2)
