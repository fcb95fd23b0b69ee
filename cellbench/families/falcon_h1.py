"""`model_type: falcon_h1` (Falcon-H1): in every layer a Mamba-2 state-space
mixer BESIDE grouped-query attention, both reading the same normed input,
then a gated-SiLU MLP; muP-style scalar multipliers from the configuration
on nearly every edge. Checked line by line against
`transformers/models/falcon_h1/modeling_falcon_h1.py` (tests/test_falcon_h1.py
holds this file to `FalconH1ForCausalLM` on the same weights).

  h   = rms(x; input_layernorm)
  attention: q,k,v = (h*attention_in_multiplier) @ Wq^T, .. @ Wk^T * key_multiplier,
             .. @ Wv^T ; rotary (HF rotate_half) ; causal softmax, GQA,
             scale head_dim**-0.5 ; a = attn @ Wo^T
  mixer:     zxbcdt = ((h*ssm_in_multiplier) @ W_in^T) * mup_vector
               mup_vector = ssm_multipliers[0..4] on z | x | B | C | dt
             z, xBC, dt = split ; xBC = silu(causal depthwise conv1d(xBC) + bias)
             xs, B, C = split(xBC) ; dt = softplus(dt + dt_bias) ; A = -exp(A_log)
             S_t = exp(dt_t*A) * S_{t-1} + dt_t * outer(xs_t[head], B_t[group])
             y_t = S_t @ C_t[group] + D[head] * xs_t              (a scan over t)
             m = rms_grouped(y * silu(z); norm.weight, mamba_n_groups) @ W_out^T
  x   = x + m*ssm_out_multiplier + a*attention_out_multiplier
  g   = rms(x; pre_ff_layernorm)
  x   = x + ((g @ Wup^T) * silu((g @ Wgate^T) * mlp_multipliers[0])) @ Wdown^T
            * mlp_multipliers[1]
  client: embed(ids) * embedding_multiplier ;
          logits = rms(x; final_layernorm) @ head^T * lm_head_multiplier
"""

from __future__ import annotations

from cellbench.checkpoint import ONES
from cellbench.reference import _rms, _rope_attention
from cellbench.roofline import BF16

F32 = 4
# Mamba-2's own initialisation: decay rates 1..16, steps 1e-3..1e-1
A_LOG = {"low": 1, "high": 16, "then": "log"}
DT_BIAS = {"low": 1e-3, "high": 1e-1, "spacing": "log",
           "then": "softplus_inverse"}
# Set from readings (PERF.md section 2) so that the recurrent state S carries
# about twice what the skip D * xs_t does, and the judged logits lose 0.05
# and more when S is lost. Under "bits" (2**-9..2**-6) for in_proj and the
# taps with D = 1, the muP multipliers leave xs, B and C near 0.007, S @ C
# at 1e-4 of D * xs, and y * silu(z) so far under the grouped norm's eps
# that the norm damps the mixer thirtyfold: no comparison of logits could
# see a lost state, a wrong chunk boundary or a padding row fed to S.
IN_PROJ = {"low": -0.25, "high": 0.25}  # rows of zxbcdt near 1 before mup
CONV_W = {"low": -0.7, "high": 0.7}
D_SKIP = {"low": 0.05, "high": 0.2}
# the int8 control quantises projections; the convolution's taps are a
# [C, 1, K] array of 4 numbers a channel, kept as the program keeps them
INT8_KEEPS = ("conv_w",)


def _dims(config: dict) -> dict:
    d_ssm = config.get("mamba_d_ssm") or int(
        config["mamba_expand"] * config["hidden_size"])
    heads = config["mamba_n_heads"]
    groups, state = config["mamba_n_groups"], config["mamba_d_state"]
    conv_dim = d_ssm + 2 * groups * state
    return {"d_ssm": d_ssm, "heads": heads, "head_dim": d_ssm // heads,
            "groups": groups, "state": state, "conv": config["mamba_d_conv"],
            "conv_dim": conv_dim, "proj": d_ssm + conv_dim + heads}


def _head_dim(config: dict) -> int:
    return config.get("head_dim") or (
        config["hidden_size"] // config["num_attention_heads"])


# ------------------------------------------------------- checkpoint plan
def layer_tensors(config: dict, layer: int) -> list[tuple]:
    d, i = config["hidden_size"], config["intermediate_size"]
    heads, kvh = config["num_attention_heads"], config["num_key_value_heads"]
    hd, m = _head_dim(config), _dims(config)
    p = f"model.layers.{layer}"
    return [
        (f"{p}.input_layernorm.weight", (d,), ONES),
        (f"{p}.pre_ff_layernorm.weight", (d,), ONES),
        (f"{p}.self_attn.q_proj.weight", (heads * hd, d)),
        (f"{p}.self_attn.k_proj.weight", (kvh * hd, d)),
        (f"{p}.self_attn.v_proj.weight", (kvh * hd, d)),
        (f"{p}.self_attn.o_proj.weight", (d, heads * hd)),
        (f"{p}.mamba.in_proj.weight", (m["proj"], d), IN_PROJ),
        (f"{p}.mamba.conv1d.weight", (m["conv_dim"], 1, m["conv"]), CONV_W),
        (f"{p}.mamba.conv1d.bias", (m["conv_dim"],)),
        (f"{p}.mamba.A_log", (m["heads"],), A_LOG),
        (f"{p}.mamba.D", (m["heads"],), D_SKIP),
        (f"{p}.mamba.dt_bias", (m["heads"],), DT_BIAS),
        (f"{p}.mamba.norm.weight", (m["d_ssm"],), ONES),
        (f"{p}.mamba.out_proj.weight", (d, m["d_ssm"])),
        (f"{p}.feed_forward.gate_proj.weight", (i, d)),
        (f"{p}.feed_forward.up_proj.weight", (i, d)),
        (f"{p}.feed_forward.down_proj.weight", (d, i)),
    ]


def client_tensors(config: dict) -> list[tuple]:
    v, d = config["vocab_size"], config["hidden_size"]
    return [
        ("model.embed_tokens.weight", (v, d)),
        ("model.final_layernorm.weight", (d,), ONES),
        ("lm_head.weight", (v, d)),
    ]


# ------------------------------------------------------------- reference
def layer_params(tensors: dict, config: dict, layer: int) -> dict:
    p = f"model.layers.{layer}."
    return {
        "ln1": tensors[p + "input_layernorm.weight"],
        "ln2": tensors[p + "pre_ff_layernorm.weight"],
        **{k: tensors[p + f"self_attn.{k}_proj.weight"] for k in "qkvo"},
        "in": tensors[p + "mamba.in_proj.weight"],
        "conv_w": tensors[p + "mamba.conv1d.weight"],
        "conv_b": tensors[p + "mamba.conv1d.bias"],
        "a_log": tensors[p + "mamba.A_log"],
        "d": tensors[p + "mamba.D"],
        "dt_bias": tensors[p + "mamba.dt_bias"],
        "ssm_norm": tensors[p + "mamba.norm.weight"],
        "out": tensors[p + "mamba.out_proj.weight"],
        **{k: tensors[p + f"feed_forward.{k}_proj.weight"]
           for k in ("gate", "up", "down")},
    }


def mup_vector(config: dict):
    import numpy as np

    m, mult = _dims(config), config["ssm_multipliers"]
    gn = m["groups"] * m["state"]
    return np.concatenate([
        np.full(n, x, np.float32) for n, x in zip(
            (m["d_ssm"], m["d_ssm"], gn, gn, m["heads"]), mult)])


def mixer_forward(p: dict, config: dict, h):
    """The state-space mixer for one sequence from an empty state: h [T, D]
    (the normed input) -> [T, D], before `ssm_out_multiplier`."""
    import jax
    import jax.numpy as jnp

    m = _dims(config)
    t = h.shape[0]
    heads, hd, groups, n = m["heads"], m["head_dim"], m["groups"], m["state"]
    zxbcdt = ((h * config["ssm_in_multiplier"]) @ p["in"].T) * mup_vector(config)
    z, xbc, dt = jnp.split(zxbcdt, [m["d_ssm"], m["d_ssm"] + m["conv_dim"]], -1)
    k = m["conv"]
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    w = p["conv_w"][:, 0, :]  # [C, K]
    xbc = sum(padded[i: i + t] * w[:, i] for i in range(k)) + p["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs, b, c = jnp.split(xbc, [m["d_ssm"], m["d_ssm"] + groups * n], -1)
    xs = xs.reshape(t, heads, hd)
    rep = heads // groups
    b = jnp.repeat(b.reshape(t, groups, n), rep, axis=1)  # [T, H, N]
    c = jnp.repeat(c.reshape(t, groups, n), rep, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])  # [T, H]
    a = -jnp.exp(p["a_log"])  # [H]

    def step(s, row):
        x_t, b_t, c_t, dt_t = row
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, c_t) + p["d"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((heads, hd, n), jnp.float32),
                        (xs, b, c, dt))
    y = y.reshape(t, m["d_ssm"]) * jax.nn.silu(z)  # the gate BEFORE the norm
    y = y.reshape(t, groups, -1)
    y = y * jax.lax.rsqrt(
        jnp.mean(jnp.square(y), -1, keepdims=True) + config["rms_norm_eps"])
    return (y.reshape(t, m["d_ssm"]) * p["ssm_norm"]) @ p["out"].T


def layer_forward(p: dict, config: dict, hidden, positions):
    """One sequence: hidden [T, D] float32, positions [T]."""
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    t = hidden.shape[0]
    heads, kvh = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = _head_dim(config), config["rms_norm_eps"]
    h = _rms(hidden, p["ln1"], eps)
    ha = h * config["attention_in_multiplier"]
    q = (ha @ p["q"].T).reshape(t, heads, hd)
    k = ((ha @ p["k"].T) * config["key_multiplier"]).reshape(t, kvh, hd)
    v = (ha @ p["v"].T).reshape(t, kvh, hd)
    # the published base is the integer 100000000000: past int32
    attn = _rope_attention(q, k, v, positions, float(config["rope_theta"]), 0)
    hidden = (hidden
              + mixer_forward(p, config, h) * config["ssm_out_multiplier"]
              + (attn @ p["o"].T) * config["attention_out_multiplier"])
    g = _rms(hidden, p["ln2"], eps)
    gate_mult, down_mult = config["mlp_multipliers"]
    y = ((g @ p["up"].T) * jax.nn.silu((g @ p["gate"].T) * gate_mult)
         ) @ p["down"].T
    return hidden + y * down_mult


def embed(client: dict, config: dict, ids):
    import numpy as np

    return (np.asarray(client["model.embed_tokens.weight"][ids], np.float32)
            * np.float32(config["embedding_multiplier"]))


def logits_rows(client: dict, config: dict, hidden_rows):
    import jax.numpy as jnp

    norm, head = (jnp.asarray(client[name]).astype(jnp.float32)
                  for name in ("model.final_layernorm.weight",
                               "lm_head.weight"))
    return (_rms(hidden_rows, norm, config["rms_norm_eps"]) @ head.T
            ) * config["lm_head_multiplier"]


# -------------------------------------------------------- roofline needs
def _layer_weights(config: dict) -> int:
    d, hd, m = config["hidden_size"], _head_dim(config), _dims(config)
    q = config["num_attention_heads"] * hd
    kv = config["num_key_value_heads"] * hd
    attention = d * q + 2 * d * kv + q * d
    mixer = d * m["proj"] + m["d_ssm"] * d
    return attention + mixer + 3 * d * config["intermediate_size"]


def _state_bytes(config: dict) -> int:
    """One sequence's recurrent state in one layer: S in float32 and the
    convolution's tail in bfloat16."""
    m = _dims(config)
    return (m["heads"] * m["head_dim"] * m["state"] * F32
            + (m["conv"] - 1) * m["conv_dim"] * BF16)


def _needs(config: dict, rows: float, kv_bytes: float, state_bytes: float,
           attended: float) -> dict:
    """Every layer's weights once, `kv_bytes` of keys and values,
    `state_bytes` of recurrent state (read and written), the rows'
    activations in and out; every row attends `attended` positions and runs
    one recurrence step (6 FLOPs a state element: decay, update, read-out)."""
    layers, d, hd = config["num_hidden_layers"], config["hidden_size"], _head_dim(config)
    m = _dims(config)
    weights = _layer_weights(config)
    weight_bytes = layers * weights * BF16
    flops = layers * rows * (
        2 * weights + 4 * attended * config["num_attention_heads"] * hd
        + 6 * m["heads"] * m["head_dim"] * m["state"])
    return {"bytes": weight_bytes + kv_bytes + state_bytes + 2 * rows * d * BF16,
            "flops": flops, "weight_bytes": weight_bytes,
            "kv_bytes": kv_bytes, "state_bytes": state_bytes}


def _kv_row(config: dict) -> int:
    return 2 * config["num_key_value_heads"] * _head_dim(config)  # K and V


def decode_step_needs(config: dict, rows: float, context: float) -> dict:
    """One decode step of `rows` rows at mean context `context`: every row's
    keys and values once, every row's state read and written once."""
    layers = config["num_hidden_layers"]
    kv_bytes = layers * rows * (context + 1) * _kv_row(config) * BF16
    state = layers * rows * 2 * _state_bytes(config)
    return _needs(config, rows, kv_bytes, state, context)


def chunk_needs(config: dict, rows: float, context: float) -> dict:
    """One prefill chunk of `rows` tokens of ONE sequence with `context`
    tokens cached: the cached keys and values once, the chunk's own written
    once; the sequence's state once each way."""
    layers = config["num_hidden_layers"]
    kv_bytes = layers * (context + rows) * _kv_row(config) * BF16
    state = layers * 2 * _state_bytes(config)
    return _needs(config, rows, kv_bytes, state, context + rows / 2)


def ssm_scan_needs(config: dict, rows: float, kind: str) -> dict:
    """What the `ssm_scan` scope alone needs over the configuration's
    layers: the convolution, dt, and the recurrence (`kind` "decode": `rows`
    rows, each its own state read and written once; "chunk": `rows` tokens
    of one sequence, its state once each way). No projection's weights."""
    layers, m = config["num_hidden_layers"], _dims(config)
    seqs = rows if kind == "decode" else 1
    io = rows * (2 * m["conv_dim"] + m["heads"]) * BF16 + rows * m["d_ssm"] * F32
    small = (m["conv"] + 1) * m["conv_dim"] * BF16 + 3 * m["heads"] * F32
    return {
        "bytes": layers * (seqs * 2 * _state_bytes(config) + io + small),
        "flops": layers * rows * (
            6 * m["heads"] * m["head_dim"] * m["state"]
            + 2 * m["conv"] * m["conv_dim"]),
    }
