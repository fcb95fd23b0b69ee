"""`model_type: qwen3_next` (Qwen3-Next): gated-DeltaNet linear-attention
layers with a gated full-attention layer every `full_attention_interval`-th,
and in EVERY layer a sparse MLP with one gated shared expert. Written from
the published `config.json` and `modeling_qwen3_next.py`, token by token and
with no cache (the program's chunk form and its two arenas are not used here):

  layer i is "full" if (i + 1) % full_attention_interval == 0, else "linear"
  norm(x; w) = x / rms(x) * (1 + w)      zero-centred weight: the layer norms,
                                          q_norm, k_norm, the final norm
  x += mixer_i(norm(x)) ; x += moe(norm(x))

  full:   [q | gate] per head = split(h @ Wq^T) (each head's 2 * head_dim rows:
          q then gate) ; k, v = h @ Wk^T, h @ Wv^T ; q, k = norm over head_dim ;
          rotary (rotate-half) on the first head_dim * partial_rotary_factor
          dims of q and k ; causal softmax, GQA, scale head_dim ** -0.5 ;
          out = (attn * sigmoid(gate)) @ Wo^T
  linear: h @ W_qkvz^T per KEY head q | k | v (its value heads') | z ;
          h @ W_ba^T per key head b | a ; channels q | k | v through a causal
          depthwise conv of width `linear_conv_kernel_dim`, no bias, then SiLU ;
          beta = sigmoid(b) ; g = -exp(A_log) * softplus(a + dt_bias) ;
          q, k L2-normalised per head (eps 1e-6), q *= d_k ** -0.5 ; key head g
          serves value heads g*r .. g*r + r - 1 ; per value head, S [d_k, d_v]:
              S = exp(g_t) S ; u = (v_t - S^T k_t) beta_t ; S += k_t u^T ;
              o_t = S^T q_t                                 (a scan over t)
          y = w_n * o / rms(o) * silu(z) per head (PLAIN weight) ;
          out = y @ W_out^T
  moe:    p = softmax(g @ Wr^T) over ALL router outputs ; top-k ; renormalised
          to sum 1 ; x += sum over the row's experts THAT THIS SHARE HOLDS of
          w_e E_e(g) + sigmoid(g . w_sg) * shared(g)
  logits = norm(x) @ head^T

Left out: the multi-token-prediction head (`mtp.*` in the published
checkpoint; not in `config`).

A share of a deployment: the checkpoint holds `num_experts` experts,
`experts_held` = [first, count) of the published numbering, and the router
scores all `router_experts` of them (two keys of the configuration's file
beside the source's own). A pair whose expert lies on another chip adds
nothing here, in the program and in this reference alike. The shared expert
and its gate are computed on every chip alike. Without the two keys every
expert is held.

Fills: see FILLS below (`cellbench.assumed` records the readings).
"""

from __future__ import annotations

from cellbench.checkpoint import ONES
from cellbench.reference import _attention, _rotate_half
from cellbench.roofline import BF16

F32 = 4
# The plan's fills. "bits" (|w| in 2**-9..2**-6) unless set apart:
#   o_proj, out_proj, down projections: narrower, so a layer's update stays
#     near the residual it is added to and rows keep their own direction
#     (PR 35's reading, the same here);
#   a full layer's o_proj: wider than that, narrower than "bits": softmax
#     attention with scores of unit spread averages v over thousands of
#     keys, so its output is nine tenths ONE vector for every row. At the
#     narrow range its update read 0.010 beside a linear layer's 0.075 (rms,
#     CPU float32 at the published widths) and an attention fault would
#     hide under the linear layers; at +-0.04 it read 0.149, the rows'
#     cosine rose to 0.33 after layer 7 and a 512-row chunk reached 85-90
#     of the 128 held experts there (the collapse PR 35 met);
#   the router: wider, so its logits follow the row and not the common part;
#   the zero-centred norms: small around 0, so `1 + w` and `w` differ by all
#     of the signal; the mixer's gated norm (plain weight): 1.0;
#   conv taps: as Falcon-H1's, so the convolved v stays of order 1 before the
#     gated norm's eps;
#   A_log, dt_bias: the published initialisation (A ~ U(0, 16), dt_bias 1)
#     forgets within a token (g near -8): every head's S would be its last
#     outer product and no comparison could see a lost state. Here A in
#     0.02..2 (log-spaced) and softplus(dt_bias) in 1e-3..3e-2 (log-spaced),
#     so g = -A * softplus(a + dt_bias) puts the heads' memories 1 / |g| at
#     tens to tens of thousands of tokens.
FILLS = {
    "o_proj": {"low": -0.006, "high": 0.006},
    "attn_o_proj": {"low": -0.015, "high": 0.015},
    "down_proj": {"low": -0.006, "high": 0.006},
    "router": {"low": -0.06, "high": 0.06},
    "norm": {"low": -0.0625, "high": 0.0625},
    "conv": {"low": -0.7, "high": 0.7},
    "A_log": {"low": 0.02, "high": 2.0, "spacing": "log", "then": "log"},
    "dt_bias": {"low": 1e-3, "high": 3e-2, "spacing": "log",
                "then": "softplus_inverse"},
}
# the int8 control quantises projections; the router stays as the checkpoint
# has it (a choice flipped by a rounded score is another expert, not a
# rounding), so do the taps (4 numbers a channel) and the gates' vectors
INT8_KEEPS = ("router", "conv_w", "a_log", "dt_bias")


def _dims(config: dict) -> dict:
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    return {"hk": hk, "hv": hv, "dk": dk, "dv": dv, "rep": hv // hk,
            "d_key": hk * dk, "d_value": hv * dv,
            "conv_dim": 2 * hk * dk + hv * dv,
            "conv": config["linear_conv_kernel_dim"]}


def is_full(config: dict, layer: int) -> bool:
    return (layer + 1) % config["full_attention_interval"] == 0


def _router_width(config: dict) -> int:
    return config.get("router_experts", config["num_experts"])


def _held(config: dict) -> tuple[int, int]:
    first, count = config.get("experts_held", (0, config["num_experts"]))
    return int(first), int(count)


def _rotary_dim(config: dict) -> int:
    return int(config["head_dim"] * config.get("partial_rotary_factor", 1.0))


# ------------------------------------------------------- checkpoint plan
def _mlp_tensors(prefix: str, d: int, i: int) -> list[tuple]:
    return [
        (f"{prefix}.gate_proj.weight", (i, d)),
        (f"{prefix}.up_proj.weight", (i, d)),
        (f"{prefix}.down_proj.weight", (d, i), FILLS["down_proj"]),
    ]


def layer_tensors(config: dict, layer: int) -> list[tuple]:
    d, m = config["hidden_size"], _dims(config)
    p = f"model.layers.{layer}"
    tensors = [
        (f"{p}.input_layernorm.weight", (d,), FILLS["norm"]),
        (f"{p}.post_attention_layernorm.weight", (d,), FILLS["norm"]),
    ]
    if is_full(config, layer):
        heads, kvh = config["num_attention_heads"], config["num_key_value_heads"]
        hd, a = config["head_dim"], f"{p}.self_attn"
        tensors += [
            (f"{a}.q_proj.weight", (heads * 2 * hd, d)),
            (f"{a}.k_proj.weight", (kvh * hd, d)),
            (f"{a}.v_proj.weight", (kvh * hd, d)),
            (f"{a}.o_proj.weight", (d, heads * hd), FILLS["attn_o_proj"]),
            (f"{a}.q_norm.weight", (hd,), FILLS["norm"]),
            (f"{a}.k_norm.weight", (hd,), FILLS["norm"]),
        ]
    else:
        a = f"{p}.linear_attn"
        tensors += [
            (f"{a}.in_proj_qkvz.weight", (2 * m["d_key"] + 2 * m["d_value"], d)),
            (f"{a}.in_proj_ba.weight", (2 * m["hv"], d)),
            (f"{a}.conv1d.weight", (m["conv_dim"], 1, m["conv"]), FILLS["conv"]),
            (f"{a}.A_log", (m["hv"],), FILLS["A_log"]),
            (f"{a}.dt_bias", (m["hv"],), FILLS["dt_bias"]),
            (f"{a}.norm.weight", (m["dv"],), ONES),
            (f"{a}.out_proj.weight", (d, m["d_value"]), FILLS["o_proj"]),
        ]
    tensors.append(
        (f"{p}.mlp.gate.weight", (_router_width(config), d), FILLS["router"]))
    first, count = _held(config)
    for e in range(first, first + count):
        tensors += _mlp_tensors(
            f"{p}.mlp.experts.{e}", d, config["moe_intermediate_size"])
    tensors += _mlp_tensors(
        f"{p}.mlp.shared_expert", d, config["shared_expert_intermediate_size"])
    tensors.append((f"{p}.mlp.shared_expert_gate.weight", (1, d)))
    return tensors


def client_tensors(config: dict) -> list[tuple]:
    v, d = config["vocab_size"], config["hidden_size"]
    return [
        ("model.embed_tokens.weight", (v, d)),
        ("model.norm.weight", (d,), FILLS["norm"]),
        ("lm_head.weight", (v, d)),
    ]


# ------------------------------------------------------------- reference
def layer_params(tensors: dict, config: dict, layer: int) -> dict:
    """One layer's tensors under short names, torch layout [out, in]; the
    held experts stacked [E_held, out, in]. Still bfloat16 (exact)."""
    import numpy as np

    p = f"model.layers.{layer}."
    out = {
        "ln1": tensors[p + "input_layernorm.weight"],
        "ln2": tensors[p + "post_attention_layernorm.weight"],
    }
    if is_full(config, layer):
        a = p + "self_attn."
        out.update({k: tensors[a + f"{k}_proj.weight"] for k in "qkvo"})
        out["q_norm"] = tensors[a + "q_norm.weight"]
        out["k_norm"] = tensors[a + "k_norm.weight"]
    else:
        a = p + "linear_attn."
        out.update({
            "qkvz": tensors[a + "in_proj_qkvz.weight"],
            "ba": tensors[a + "in_proj_ba.weight"],
            "conv_w": tensors[a + "conv1d.weight"],
            "a_log": tensors[a + "A_log"],
            "dt_bias": tensors[a + "dt_bias"],
            "gdn_norm": tensors[a + "norm.weight"],
            "out": tensors[a + "out_proj.weight"],
        })
    out["router"] = tensors[p + "mlp.gate.weight"]
    first, count = _held(config)
    for k in ("gate", "up", "down"):
        out[f"e_{k}"] = np.stack([
            tensors[p + f"mlp.experts.{e}.{k}_proj.weight"]
            for e in range(first, first + count)
        ])
        out[f"s_{k}"] = tensors[p + f"mlp.shared_expert.{k}_proj.weight"]
    out["s_w"] = tensors[p + "mlp.shared_expert_gate.weight"]
    return out


def _norm1p(x, w, eps):
    import jax.numpy as jnp

    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jnp.reciprocal(jnp.sqrt(var + eps)) * (1.0 + w)


def gated_attention(p: dict, config: dict, h, positions):
    """The full-attention mixer on one sequence's normed rows h [T, D]."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    heads, kvh = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = config["head_dim"], config["rms_norm_eps"]
    qg = (h @ p["q"].T).reshape(t, heads, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:].reshape(t, heads * hd)
    k = (h @ p["k"].T).reshape(t, kvh, hd)
    v = (h @ p["v"].T).reshape(t, kvh, hd)
    q, k = _norm1p(q, p["q_norm"], eps), _norm1p(k, p["k_norm"], eps)
    rd = _rotary_dim(config)
    inv = 1.0 / (float(config["rope_theta"]) ** (jnp.arange(0, rd, 2) / rd))
    ang = positions.astype(jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]

    def rope(x):
        turned = x[..., :rd] * jnp.cos(ang) + _rotate_half(x[..., :rd]) * jnp.sin(ang)
        return jnp.concatenate([turned, x[..., rd:]], -1)

    attn = _attention(rope(q), rope(k), v, positions, 0)  # [T, heads * hd]
    return (attn * jax.nn.sigmoid(gate)) @ p["o"].T


def gdn_inputs(p: dict, config: dict, h):
    """The gated delta rule's inputs for one sequence from an empty
    convolution tail: (q, k [T, Hv, dk], v [T, Hv, dv], g, beta [T, Hv],
    z [T, Hv, dv])."""
    import jax
    import jax.numpy as jnp

    m = _dims(config)
    t = h.shape[0]
    hk, hv, dk, dv, rep = m["hk"], m["hv"], m["dk"], m["dv"], m["rep"]
    qkvz = (h @ p["qkvz"].T).reshape(t, hk, 2 * dk + 2 * rep * dv)
    q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + rep * dv], axis=-1)
    ba = (h @ p["ba"].T).reshape(t, hk, 2 * rep)
    b, a = ba[..., :rep].reshape(t, hv), ba[..., rep:].reshape(t, hv)
    mixed = jnp.concatenate(
        [q.reshape(t, -1), k.reshape(t, -1), v.reshape(t, -1)], -1)
    width = m["conv"]
    padded = jnp.pad(mixed, ((width - 1, 0), (0, 0)))
    w = p["conv_w"][:, 0, :]  # [C, K]
    mixed = jax.nn.silu(sum(padded[i: i + t] * w[:, i] for i in range(width)))
    q, k, v = jnp.split(mixed, [m["d_key"], 2 * m["d_key"]], -1)

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2(q.reshape(t, hk, dk)), rep, axis=1) * dk ** -0.5
    k = jnp.repeat(l2(k.reshape(t, hk, dk)), rep, axis=1)
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(a + p["dt_bias"])
    return (q, k, v.reshape(t, hv, dv), g, jax.nn.sigmoid(b),
            z.reshape(t, hv, dv))


def gated_delta_net(p: dict, config: dict, h):
    """The linear mixer for one sequence from an empty state: h [T, D] (the
    normed input) -> [T, D]; the recurrence token by token."""
    import jax
    import jax.numpy as jnp

    m = _dims(config)
    q, k, v, g, beta, z = gdn_inputs(p, config, h)

    def step(s, row):
        q_t, k_t, v_t, g_t, beta_t = row
        s = s * jnp.exp(g_t)[:, None, None]
        u = (v_t - jnp.einsum("hkv,hk->hv", s, k_t)) * beta_t[:, None]
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    _, o = jax.lax.scan(
        step, jnp.zeros((m["hv"], m["dk"], m["dv"]), jnp.float32),
        (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(
        jnp.mean(jnp.square(o), -1, keepdims=True) + config["rms_norm_eps"])
    y = p["gdn_norm"] * o * jax.nn.silu(z)
    return y.reshape(h.shape[0], m["d_value"]) @ p["out"].T


def _silu_mlp(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def route(x, p: dict, config: dict):
    """(indices [R, k] over all router outputs, weights [R, k] summing 1)."""
    import jax

    probs = jax.nn.softmax(x @ p["router"].T, -1)
    top, idx = jax.lax.top_k(probs, config["num_experts_per_tok"])
    if config.get("norm_topk_prob", True):
        top = top / top.sum(-1, keepdims=True)
    return idx, top


def moe(x, p: dict, config: dict, block: int = 256):
    """The sparse MLP on normed rows [R, D]: the held experts' weighted sum
    plus the gated shared expert."""
    import jax
    import jax.numpy as jnp

    r, d = x.shape
    first, count = _held(config)
    pad = -r % block
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, block, d)

    def one(rows):
        idx, top = route(rows, p, config)
        local = idx - first
        held = (local >= 0) & (local < count)
        w = jnp.zeros((block, count), jnp.float32).at[
            jnp.arange(block)[:, None], jnp.clip(local, 0, count - 1)
        ].add(jnp.where(held, top, 0.0))
        g = jnp.einsum("rd,eid->rei", rows, p["e_gate"])
        u = jnp.einsum("rd,eid->rei", rows, p["e_up"])
        hid = jax.nn.silu(g) * u * w[:, :, None]
        return jnp.einsum("rei,edi->rd", hid, p["e_down"])

    out = jax.lax.map(one, xb).reshape(-1, d)[:r]
    shared = _silu_mlp(x, p["s_gate"], p["s_up"], p["s_down"])
    return out + jax.nn.sigmoid(x @ p["s_w"].T) * shared


def layer_forward(p: dict, config: dict, hidden, positions):
    """One sequence: hidden [T, D] float32, positions [T]. The layer's kind
    is what its tensors say."""
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    eps = config["rms_norm_eps"]
    h = _norm1p(hidden, p["ln1"], eps)
    if "qkvz" in p:
        hidden = hidden + gated_delta_net(p, config, h)
    else:
        hidden = hidden + gated_attention(p, config, h, positions)
    return hidden + moe(_norm1p(hidden, p["ln2"], eps), p, config)


def embed(client: dict, config: dict, ids):
    import numpy as np

    return np.asarray(client["model.embed_tokens.weight"][ids], np.float32)


def logits_rows(client: dict, config: dict, hidden_rows):
    import jax.numpy as jnp

    norm, head = (jnp.asarray(client[name]).astype(jnp.float32)
                  for name in ("model.norm.weight", "lm_head.weight"))
    return _norm1p(hidden_rows, norm, config["rms_norm_eps"]) @ head.T


# -------------------------------------------------------- roofline needs
def _kinds(config: dict) -> tuple[int, int]:
    """(linear layers, full layers) of the configuration's depth."""
    layers = config["num_hidden_layers"]
    full = sum(is_full(config, i) for i in range(layers))
    return layers - full, full


def _mixer_weights(config: dict) -> tuple[int, int]:
    """(a linear layer's mixer, a full layer's attention), in parameters."""
    d, m = config["hidden_size"], _dims(config)
    heads, kvh, hd = (config["num_attention_heads"],
                      config["num_key_value_heads"], config["head_dim"])
    linear = (d * (2 * m["d_key"] + 2 * m["d_value"]) + d * 2 * m["hv"]
              + m["d_value"] * d)
    full = d * heads * 2 * hd + 2 * d * kvh * hd + heads * hd * d
    return linear, full


def state_bytes(config: dict) -> int:
    """One sequence's recurrent state in one LINEAR layer: S in float32 and
    the convolution's tail in bfloat16."""
    m = _dims(config)
    return (m["hv"] * m["dk"] * m["dv"] * F32
            + (m["conv"] - 1) * m["conv_dim"] * BF16)


def kv_row_bytes(config: dict) -> int:
    """One token's K and V in one FULL layer."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * BF16


# per row and state element: decay, S^T k, the rank-one update, S^T q
RULE_FLOPS = 7


def _expert_reach(config: dict, rows: float) -> tuple[float, float]:
    """(held pairs a row, distinct held experts `rows` rows reach), in
    expectation under routing that is uniform over experts."""
    _first, count = _held(config)
    p = config["num_experts_per_tok"] / _router_width(config)
    return count * p, count * (1.0 - (1.0 - p) ** rows)


def _needs(config: dict, rows: float, kv: float, state: float,
           attended: float) -> dict:
    """Every layer's mixer weights, router, shared expert and the distinct
    held experts the rows reach, once; `kv` bytes of keys and values (the
    full layers') and `state` bytes of recurrent state (the linear layers');
    the rows' activations in and out. Every row computes its held pairs,
    attends `attended` positions in a full layer and takes one rule step in
    a linear one."""
    d, m = config["hidden_size"], _dims(config)
    n_lin, n_full = _kinds(config)
    lin_w, full_w = _mixer_weights(config)
    pairs, distinct = _expert_reach(config, rows)
    expert = 3 * d * config["moe_intermediate_size"]
    shared = 3 * d * config["shared_expert_intermediate_size"] + d
    router = d * _router_width(config)
    layers = n_lin + n_full
    weights = (n_lin * lin_w + n_full * full_w
               + layers * (router + shared + distinct * expert))
    flops = rows * (
        2 * (n_lin * lin_w + n_full * full_w
             + layers * (router + shared + pairs * expert))
        + n_full * 4 * attended * config["num_attention_heads"] * config["head_dim"]
        + n_lin * RULE_FLOPS * m["hv"] * m["dk"] * m["dv"])
    return {"bytes": weights * BF16 + kv + state + 2 * rows * d * BF16,
            "flops": flops, "weight_bytes": weights * BF16,
            "kv_bytes": kv, "state_bytes": state}


def decode_step_needs(config: dict, rows: float, context: float) -> dict:
    """One decode step of `rows` rows at mean context `context`: every row's
    keys and values once in the full layers, every row's state read and
    written once in the linear ones."""
    n_lin, n_full = _kinds(config)
    kv = n_full * rows * (context + 1) * kv_row_bytes(config)
    state = n_lin * rows * 2 * state_bytes(config)
    return _needs(config, rows, kv, state, context)


def chunk_needs(config: dict, rows: float, context: float) -> dict:
    """One prefill chunk of `rows` tokens of ONE sequence with `context`
    tokens cached: the cached keys and values once and the chunk's own
    written once in the full layers; the sequence's state once each way in
    the linear ones; the held stacks read once."""
    n_lin, n_full = _kinds(config)
    kv = n_full * (context + rows) * kv_row_bytes(config)
    state = n_lin * 2 * state_bytes(config)
    return _needs(config, rows, kv, state, context + rows / 2)


def gdn_rule_needs(config: dict, rows: float, kind: str) -> dict:
    """What the scopes `gdn_conv` + `gdn_rule` + `state_io` alone need over
    the configuration's LINEAR layers: the convolution, the gates, the
    recurrence (`kind` "decode": `rows` rows, each its own state read and
    written once; "chunk": `rows` tokens of one sequence, its state once
    each way). No projection's weights."""
    n_lin, _ = _kinds(config)
    m = _dims(config)
    seqs = rows if kind == "decode" else 1
    io = rows * (m["conv_dim"] * BF16 + 2 * m["hv"] * F32 + m["d_value"] * F32)
    small = m["conv"] * m["conv_dim"] * BF16 + 2 * m["hv"] * F32
    return {
        "bytes": n_lin * (seqs * 2 * state_bytes(config) + io + small),
        "flops": n_lin * rows * (
            RULE_FLOPS * m["hv"] * m["dk"] * m["dv"]
            + 2 * m["conv"] * m["conv_dim"]),
    }
